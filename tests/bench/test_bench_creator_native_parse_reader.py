"""`creator_native_parse_share` (`benchmarks/layer_metrics/
creator_native_parse_share.py`): of the creators a window's blocks
validated afresh, the share whose certificate the native reader read.
Against answers by hand (every stranger read natively, some handed
back, a window of one-creator blocks, the spans of a program before
PR 50), and on a CPU rehearsal of `manyclients-10k.catchup` from a copy
of the manifest that holds the entry PR 50 appends:

    {"name": "creator_native_parse_share.catchup", "unit": "%", "better": "higher",
     "source": "program_span", "layer": "validator (peer/txvalidator.py)",
     "moves": "committed_tx_per_s", "workloads": ["manyclients-10k.catchup"]}

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

NAME = "creator_native_parse_share.catchup"
CELL = "manyclients-10k.catchup"
ENTRY = {"name": NAME, "unit": "%", "better": "higher", "source": "program_span",
         "layer": "validator (peer/txvalidator.py)", "moves": "committed_tx_per_s",
         "workloads": [CELL]}


@pytest.fixture(scope="module")
def read():
    return Manifest(ROOT).reader(NAME)


def _collect(block, validated, native, batched=None):
    return {"ph": "X", "name": "collect", "cat": "stage", "ts": 10 * block, "dur": 5,
            "tid": "MainThread",
            "args": {"block": block, "creators": validated, "creator_validations": validated,
                     "creator_ms": 1.0, "creator_native_parse": native,
                     "creator_chain_batch": validated if batched is None else batched}}


def _said(capsys):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("# creator_parses: "):
            return json.loads(line.split(": ", 1)[1])
    return None


@pytest.mark.parametrize("spans,want,said", [
    # every stranger of every block read in its block's one call
    ([_collect(1, 520, 520), _collect(2, 500, 500)], 100.0, (510.0, 510.0, 510.0)),
    # the reader handed five back, the deserialize cache still held fifteen: 1000 of 1020
    ([_collect(1, 520, 515, 519), _collect(2, 500, 485)], 100.0 * 1000 / 1020,
     (510.0, 500.0, 509.5)),
    # one client a block: under the batch's size, each parsed in place
    ([_collect(1, 1, 0, 0), _collect(2, 1, 0, 0), _collect(3, 1, 0, 0)], 0.0, (1.0, 0.0, 0.0)),
])
def test_the_share_of_the_validated_creators_the_native_reader_read(read, capsys, spans, want, said):
    capsys.readouterr()
    assert read({"spans": spans}) == pytest.approx(want)
    line = _said(capsys)
    assert line["blocks"] == len(spans)
    assert (line["validated_per_block"], line["read_natively_per_block"],
            line["chain_signatures_batched_per_block"]) == pytest.approx(said)


def test_a_window_in_which_nothing_was_validated_gives_nothing_to_read(read, capsys):
    capsys.readouterr()
    assert read({"spans": [_collect(1, 0, 0), _collect(2, 0, 0)]}) is None
    assert _said(capsys) is None


def test_the_spans_of_a_program_before_pr_50_give_nothing_to_read(read):
    """The parent: `collect` says how many creators it validated and what
    that cost, not who read their certificates.  And an untraced run."""
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_manyclients.json")) as f:
        recorded = json.load(f)
    assert any(e["args"].get("creator_validations") for e in recorded["spans"])
    assert read(recorded) is None
    new = [_collect(1, 520, 515)]
    old = copy.deepcopy(new)
    del old[0]["args"]["creator_native_parse"]
    assert read({"spans": new}) == pytest.approx(100.0 * 515 / 520)
    assert read({"spans": old}) is None
    assert read({"spans": None}) is None and read({"spans": []}) is None
    assert read({}) is None


def test_the_entry_stands_beside_the_cells_other_creator_metrics():
    """The reader is found by the name, and the entry says what its
    neighbours of the same layer say."""
    man = Manifest(ROOT)
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    assert declared.get(NAME, ENTRY) == ENTRY
    beside = declared["creator_validate_ms_per_block.catchup"]
    assert {k: ENTRY[k] for k in ("source", "layer", "moves", "workloads")} \
        == {k: beside[k] for k in ("source", "layer", "moves", "workloads")}
    assert set(ENTRY) == set(beside)
    if NAME in declared:
        assert NAME in {m["name"] for m in man.metrics("per_layer", CELL)}
        assert all(NAME not in {m["name"] for m in man.metrics("per_layer", w["name"])}
                   for w in man.doc["workloads"] if w["name"] != CELL)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A traced rehearsal of the cell from a copy of the checkout's
    benchmark whose manifest holds the entry."""
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
    size = engine.Rehearsal(block_txs=24, blocks_per_pass=6)
    return engine.run_cell(root, CELL, 2**31 + 150, 0.5, True, rehearsal=size)


def test_a_traced_rehearsal_of_the_cell_reports_it(rehearsed):
    from fabric_tpu import native

    assert rehearsed["correct"] is True and rehearsed["failed"] == 0
    metrics = rehearsed["metrics"]
    assert metrics[NAME]["unit"] == "%"
    share = metrics[NAME]["value"]
    if native.x509_read([]) is not None and native.ecdsa_verify_host([]) is not None:
        # the tiny blocks are crowded too: nearly every creator is a
        # stranger to the caches, and every certificate of this world
        # is of the shape the reader qualifies
        assert 50.0 < share <= 100.0
    else:
        assert share == 0.0
    assert {"creator_validate_ms_per_block.catchup", "creator_miss_share.catchup",
            "collect_ms_per_block.catchup"} <= set(metrics)
