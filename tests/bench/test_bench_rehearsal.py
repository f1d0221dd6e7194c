"""One rehearsal of a whole run on the CPU at a tiny size, behind the
test-only entry (`python benchmarks/run.py` itself refuses a CPU): the
open-loop cell with tracing on, then the same run with the program
broken underneath (`benchmarks/control.py` patches the program's own
classes), which must come out as not correct.

Every flush is 20 lanes, so one kernel shape is built in this process.
No number of a CPU run is a device number: the test reads counts,
keys and the verdict, never a time.
"""

import json
import os

import pytest

import control
from benchlib import engine

from conftest import ROOT

TINY = engine.Rehearsal(block_txs=10, blocks_per_pass=1)
CELL = "solo1-500tx.steady"


@pytest.fixture(scope="module")
def sound():
    return engine.run_cell(ROOT, CELL, 2**31 + 99, 1.0, True, rehearsal=TINY)


def test_a_sound_run_is_correct_and_reports_the_cells_per_layer_metrics(sound):
    assert sound["correct"] is True
    assert sound["attempted"] >= 4 and sound["failed"] == 0
    assert set(sound) >= {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert set(sound["device"]) >= {"platform", "kind", "count", "memory_peak_bytes",
                                    "busy_s", "window_s"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    names = {m["name"] for m in doc["per_layer"]}
    assert set(sound["metrics"]) <= names
    # the host-side readers find something to read; the device's find
    # nothing on a CPU and are left out of the line
    assert {"collect_ms_per_block.steady", "commit_ms_per_block.steady",
            "lanes_per_flush.steady", "device_lane_share.steady",
            "late_arrival_p95_ms.steady", "first_block_s"} <= set(sound["metrics"])
    assert "device_idle_share.steady" not in sound["metrics"]
    assert sound["metrics"]["lanes_per_flush.steady"]["value"] == 20.0
    assert sound["metrics"]["device_lane_share.steady"]["value"] == 100.0
    for m in sound["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


@pytest.fixture
def unpatched():
    """A control patches the program's classes; put them back."""
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger.txmgmt import MVCCValidator

    saved = (TPUCSP.verify_batch_async, MVCCValidator._committed_version)
    yield
    TPUCSP.verify_batch_async, MVCCValidator._committed_version = saved


@pytest.mark.parametrize("name", sorted(control.CONTROLS))
def test_a_broken_program_comes_out_as_not_correct(sound, unpatched, name, capsys):
    control.CONTROLS[name]()
    line = engine.run_cell(ROOT, CELL, 2**31 + 99, 1.0, False, rehearsal=TINY)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0     # wrong in every block
    assert set(line["metrics"]) == {"block_commit_p50_ms", "block_commit_p95_ms", "setup_s"}
    compared = {}
    for out_line in capsys.readouterr().out.splitlines():
        if out_line.startswith("# compared: "):
            rec = json.loads(out_line[len("# compared: "):])
            compared[rec["number"]] = rec["value"]
    # the broken guarantee shows in the ledger's state too, not only
    # in the flags the harness collected
    assert compared["state_entries_differing_from_reference"] > 0
