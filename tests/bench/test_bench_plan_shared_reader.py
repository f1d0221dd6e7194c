"""`plan_shared_hit_share` (`benchmarks/layer_metrics/
plan_shared_hit_share.py`): of a window's endorsement-plan lookups, the
share that found a plan other identities had built.  Against answers by
hand (an organisation's second peer finding the first one's plans, a
channel where one peer an organisation endorses, deferred decisions on
the `policy` spans, the spans of a program before PR 53), and on a CPU
rehearsal of `mixedcc-8cc-5org-1000tx.catchup` under the entry PR 53
appends to the manifest:

    {"name": "plan_shared_hit_share.catchup", "unit": "%", "better": "higher",
     "source": "program_span", "layer": "validator (peer/txvalidator.py)",
     "moves": "committed_tx_per_s", "workloads": ["mixedcc-8cc-5org-1000tx.catchup"]}

No number of a CPU run is a device number: the tests read counts and
shares of counts, never a time."""

import copy
import json
import os

import pytest

from benchlib import engine
from benchlib.manifest import Manifest

from conftest import ROOT

NAME = "plan_shared_hit_share.catchup"
CELL = "mixedcc-8cc-5org-1000tx.catchup"
ENTRY = {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
         "layer": "validator (peer/txvalidator.py)", "moves": "committed_tx_per_s",
         "workloads": [CELL]}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def read(man):
    return man.reader(NAME)


def _span(name, block, hits, misses, shared, clears=0):
    return {"ph": "X", "name": name, "cat": "stage", "ts": 10 * block, "dur": 5,
            "tid": "MainThread",
            "args": {"block": block, "plan_hits": hits, "plan_misses": misses,
                     "plan_clears": clears, "plan_shared_hits": shared, "plan_build_ms": 1.0}}


def _said(capsys):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("# plan_sharing: "):
            return json.loads(line.split(": ", 1)[1])
    return None


@pytest.mark.parametrize("spans,want,said", [
    # two peers an organisation: 640 of 1,030 lookups found the other peer's plan
    ([_span("collect", 1, 900, 130, 620), _span("collect", 2, 940, 90, 660)],
     100.0 * 1280 / 2060, (1030.0, 640.0, 280.0, 110.0)),
    # one peer an organisation endorses everything: a miss a pass, nothing shared
    ([_span("collect", 1, 999, 1, 0), _span("collect", 2, 1000, 0, 0)], 0.0,
     (1000.0, 0.0, 999.5, 0.5)),
    # a block that deferred decisions counts those on its `policy` span
    ([_span("collect", 1, 700, 10, 300), _span("policy", 1, 280, 10, 200),
      _span("collect", 2, 1000, 0, 500)], 100.0 * 1000 / 2000, (1000.0, 500.0, 490.0, 10.0)),
])
def test_the_share_of_the_lookups_that_found_another_identitys_plan(read, capsys, spans, want, said):
    capsys.readouterr()
    assert read({"spans": spans}) == pytest.approx(want)
    line = _said(capsys)
    assert line["blocks"] == len([e for e in spans if e["name"] == "collect"])
    assert (line["lookups_per_block"], line["shared_hits_per_block"],
            line["own_hits_per_block"], line["misses_per_block"]) == pytest.approx(said)


def test_a_window_without_a_lookup_gives_nothing_to_read(read, capsys):
    capsys.readouterr()
    assert read({"spans": [_span("collect", 1, 0, 0, 0), _span("collect", 2, 0, 0, 0)]}) is None
    assert _said(capsys) is None


def test_the_spans_of_a_program_before_pr_53_give_nothing_to_read(read, man):
    """The parent: `collect` counts the plans found, built and cleared,
    not whose they were; `plan_miss_share` reads such a stream and this
    reader reads nothing.  And an untraced run."""
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_mixedcc.json")) as f:
        recorded = json.load(f)
    assert any(e["args"].get("plan_misses") for e in recorded["spans"])
    assert man.reader("plan_miss_share.catchup")(recorded) is not None
    assert read(recorded) is None
    new = [_span("collect", 1, 900, 100, 600)]
    old = copy.deepcopy(new)
    del old[0]["args"]["plan_shared_hits"]
    assert read({"spans": new}) == pytest.approx(60.0)
    assert read({"spans": old}) is None
    assert read({"spans": None}) is None and read({"spans": []}) is None
    assert read({}) is None


def test_the_entry_stands_beside_the_cells_other_plan_metrics(man):
    """The reader is found by the name, and the entry says what its
    neighbours of the same layer say.  (Where it stands in the list is
    not held: the next PR appends behind it.)"""
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    assert declared[NAME] == ENTRY
    beside = declared["plan_miss_share.catchup"]
    assert {k: ENTRY[k] for k in ("unit", "source", "layer", "moves", "workloads")} \
        == {k: beside[k] for k in ("unit", "source", "layer", "moves", "workloads")}
    assert set(ENTRY) == set(beside) and ENTRY["better"] != beside["better"]
    assert NAME in {m["name"] for m in man.metrics("per_layer", CELL)}
    assert all(NAME not in {m["name"] for m in man.metrics("per_layer", w["name"])}
               for w in man.doc["workloads"] if w["name"] != CELL)


@pytest.fixture(scope="module")
def rehearsed():
    """A traced rehearsal of the cell: twenty transactions a block are
    enough for an organisation's two peers to meet under one policy and
    order, and a pass's three blocks are still one flush under 256
    lanes (one kernel shape in this process)."""
    size = engine.Rehearsal(block_txs=20, blocks_per_pass=3)
    return engine.run_cell(ROOT, CELL, 2**31 + 153, 0.5, True, rehearsal=size)


def test_a_traced_rehearsal_of_the_cell_reports_it(rehearsed):
    assert rehearsed["correct"] is True and rehearsed["failed"] == 0
    metrics = rehearsed["metrics"]
    assert metrics[NAME]["unit"] == "%"
    shared, missed = metrics[NAME]["value"], metrics["plan_miss_share.catchup"]["value"]
    # some lookups found the other peer's plan; a lookup is shared, a hit of its own, or a miss
    assert 0.0 < shared < 100.0 - missed
    assert {"plan_build_ms_per_block.catchup", "plan_clears_per_block.catchup",
            "policy_ms_per_block.catchup"} <= set(metrics)
