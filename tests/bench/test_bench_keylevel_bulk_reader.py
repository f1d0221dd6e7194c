"""`keylevel_bulk_hit_share` (`benchmarks/layer_metrics/
keylevel_bulk_hit_share.py`): of the state-metadata lookups the
validator's plugins asked, the share its one bulk read a stage had
fetched.  Against answers by hand (all from the bulk read, some by
themselves, none asked, the spans of a program before PR 41), and on a
CPU rehearsal of `keylevel-5org-1000tx.catchup` whose copy of the
manifest appends the entry PR 41 proposes:

    {"name": "keylevel_bulk_hit_share.catchup", "unit": "%", "better": "higher",
     "source": "program_span", "layer": "validator (peer/txvalidator.py)",
     "moves": "committed_tx_per_s", "workloads": ["keylevel-5org-1000tx.catchup"]}

PR 41 had to leave `BENCHMARK.json` without it (an accepted test pinned
the last three entries of `per_layer`); PR 43 took the pin out and
appended the entry, and the tests here hold the declared entry to this
one.

No number of a CPU run is a device number: the tests read counts and
shares of counts, never a time."""

import copy
import json
import os
import shutil

import pytest

from benchlib import engine
from benchlib.manifest import Manifest

from conftest import ROOT

NAME = "keylevel_bulk_hit_share.catchup"
CELL = "keylevel-5org-1000tx.catchup"
ENTRY = {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
         "layer": "validator (peer/txvalidator.py)", "moves": "committed_tx_per_s",
         "workloads": [CELL]}


@pytest.fixture(scope="module")
def read():
    return Manifest(ROOT).reader(NAME)


def _span(name, block, **args):
    return {"ph": "X", "name": name, "cat": "stage", "ts": 10 * block, "dur": 5,
            "tid": "MainThread", "args": dict(args, block=block)}


def _collect(block, reads, bulk, point):
    return _span("collect", block, keylevel_reads=reads, keylevel_ms=0.1, keylevel_policies=2,
                 keylevel_bulk_keys=bulk, keylevel_point_reads=point)


def _policy(block, deferred, reads=0, bulk=0, point=0):
    if not deferred:
        return _span("policy", block, deferred=0)
    return _span("policy", block, deferred=deferred, deferred_reads=reads, deferred_ms=0.1,
                 deferred_bulk_keys=bulk, deferred_point_reads=point)


def _said(capsys):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("# keylevel_bulk: "):
            return json.loads(line.split(": ", 1)[1])
    return None


@pytest.mark.parametrize("spans,want,said", [
    # every lookup of both stages answered from its stage's bulk read
    ([_collect(1, 450, 440, 0), _policy(1, 300, 290, 280, 0),
      _collect(2, 430, 430, 0), _policy(2, 0)], 100.0, (435.0, 140.0, 0.0, 0.0)),
    # some went to the ledger by themselves: 30 + 10 of 800
    ([_collect(1, 400, 370, 30), _policy(1, 300, 300, 290, 10),
      _collect(2, 100, 100, 0), _policy(2, 0)], 95.0, (235.0, 145.0, 15.0, 5.0)),
    # a read a key (faithful mode, the Python collector)
    ([_collect(1, 40, 0, 40), _policy(1, 10, 10, 0, 10)], 0.0, (0.0, 0.0, 40.0, 10.0)),
])
def test_the_share_of_the_lookups_the_bulk_reads_answered(read, capsys, spans, want, said):
    capsys.readouterr()
    assert read({"spans": spans}) == pytest.approx(want)
    line = _said(capsys)
    assert line["blocks"] == sum(e["name"] == "collect" for e in spans)
    assert (line["bulk_keys_in_collect_per_block"], line["bulk_keys_deferred_per_block"],
            line["point_reads_in_collect_per_block"],
            line["point_reads_deferred_per_block"]) == pytest.approx(said)


def test_a_window_in_which_nothing_was_asked_gives_nothing_to_read(read, capsys):
    """A channel without key-level policies on this program."""
    quiet = [_collect(b, 0, 0, 0) for b in (1, 2)] + [_policy(b, 0) for b in (1, 2)]
    capsys.readouterr()
    assert read({"spans": quiet}) is None
    assert _said(capsys) is None


def test_the_spans_of_a_program_before_pr_41_give_nothing_to_read(read):
    """The parent: `collect` and `policy` carry the lookups and their
    wall, not where they were answered.  And an untraced run."""
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_keylevel.json")) as f:
        recorded = json.load(f)
    assert any(e["args"].get("keylevel_reads") for e in recorded["spans"])
    assert read(recorded) is None
    new = [_collect(1, 40, 40, 0), _policy(1, 10, 10, 10, 0)]
    old = copy.deepcopy(new)
    for e in old:
        e["args"] = {k: v for k, v in e["args"].items()
                     if not k.endswith(("_bulk_keys", "_point_reads"))}
    assert read({"spans": new}) == 100.0 and read({"spans": old}) is None
    assert read({"spans": None}) is None and read({"spans": []}) is None
    assert read({}) is None


def test_the_entry_stands_beside_the_cells_other_key_level_metrics():
    """What a `benchmark` PR appends (or has appended): the reader is
    found by the name, and the entry says what its neighbours say."""
    man = Manifest(ROOT)
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    assert declared.get(NAME, ENTRY) == ENTRY
    beside = declared["keylevel_lookup_ms_per_block.catchup"]
    assert {k: ENTRY[k] for k in ("source", "layer", "moves", "workloads")} \
        == {k: beside[k] for k in ("source", "layer", "moves", "workloads")}
    assert set(ENTRY) == set(beside)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A traced rehearsal of the cell from a copy of the checkout's
    benchmark whose manifest appends the entry."""
    root = str(tmp_path_factory.mktemp("with_the_entry"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copytree(os.path.join(ROOT, "sampleconfig"), os.path.join(root, "sampleconfig"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    if ENTRY not in doc["per_layer"]:
        doc["per_layer"].append(ENTRY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    size = engine.Rehearsal(block_txs=12, blocks_per_pass=3)
    return engine.run_cell(root, CELL, 2**31 + 141, 1.0, True, rehearsal=size)


def test_a_traced_rehearsal_of_the_cell_reports_it(rehearsed):
    from fabric_tpu import native

    assert rehearsed["correct"] is True and rehearsed["failed"] == 0
    metrics = rehearsed["metrics"]
    assert metrics[NAME]["unit"] == "%"
    share = metrics[NAME]["value"]
    if native.available():
        # the walker's collect parses ahead and reads once a stage
        assert share == 100.0
    else:
        assert 0.0 <= share <= 100.0
    # beside the metric whose wall it explains, and the cell's others
    assert {"keylevel_lookup_ms_per_block.catchup", "keylevel_deferred_tx_share.catchup",
            "keylevel_commit_wait_ms_per_block.catchup", "collect_ms_per_block.catchup",
            "policy_ms_per_block.catchup"} <= set(metrics)
    assert metrics["keylevel_lookup_ms_per_block.catchup"]["value"] > 0.0
