"""`BENCHMARK.json` keeps to the builder's contract, and the harness is
driven by data: a configuration, a traffic mix, a cell and a per-layer
metric are each added as new files and entries, with no edit to a file
that is there.

"Appended, nothing moved" is held by data too: `data/accepted_<list>.json`
keeps the accepted entries of each of the manifest's four lists in
order, and whatever `BENCHMARK.json` holds has to begin with them.  A
PR that appends passes unedited; a `benchmark` PR, which alone may
change what was accepted, rewrites the data file.  Every test here
takes the checkout's root as a fixture, so `test_bench_rehearsal.py`
can hold a copy with one more entry to the same tests."""

import json
import os
import re
import shutil

import pytest

from benchlib.manifest import Manifest, ManifestError

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


LISTS = ("configs", "workloads", "end_to_end", "per_layer")


@pytest.fixture(scope="module")
def root():
    return ROOT


@pytest.fixture(scope="module")
def doc(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def accepted(name):
    with open(os.path.join(os.path.dirname(__file__), "data", f"accepted_{name}.json")) as f:
        return json.load(f)


def _line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(doc, root):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["paths"]) <= 16 and all(PATH.match(p) for p in doc["paths"])
    assert len(doc["command"]) <= 32 and all(_line(w) for w in doc["command"])
    assert not any(w.startswith("/") or ".." in w for w in doc["command"])
    # the full check fits its budget with all 24 cells
    runs = 2 + 14 * 24
    assert runs * (doc["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(doc, root):
    names = [c["name"] for c in doc["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in doc["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["name"] in used
        with open(os.path.join(root, c["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == c["reduced"]
        for key in ("deployment", "guarantees", "assumed", "planted", "source"):
            assert held[key], key
    assert len({c["source"] for c in doc["configs"]}) == len(doc["configs"])


def test_workloads(doc):
    names = [w["name"] for w in doc["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in doc["configs"]}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 2)


def test_metrics(doc, root):
    cells = {w["name"] for w in doc["workloads"]}
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    every = doc["end_to_end"] + doc["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    assert 1 <= len(doc["end_to_end"]) <= 16 and 1 <= len(doc["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        layers.add(m["layer"])
        movers = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", movers)) <= movers    # each cell reports what it moves
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    man = Manifest(root)
    for w in cells:
        got = [m["name"] for m in man.metrics("end_to_end", w)]
        assert "setup_s" in got and len(got) >= 2
        assert man.metrics("per_layer", w)
        for m in man.metrics("per_layer", w):
            assert callable(man.reader(m["name"]))


def stands(was: dict, now: dict) -> bool:
    """An accepted entry stands: every key and value as it was, but for
    a `workloads` list, which may have grown at its end (a later cell
    reports the metric too).  A list that appears or goes changes
    where the metric is due, and does not stand."""
    if set(was) != set(now):
        return False
    for key, value in was.items():
        if key == "workloads":
            if now[key][:len(value)] != value:
                return False
        elif now[key] != value:
            return False
    return True


@pytest.mark.parametrize("name", LISTS)
def test_what_was_accepted_stands_first_in_its_order_and_as_it_was(doc, name):
    was, now = accepted(name), doc[name]
    assert len(now) >= len(was)
    for i, entry in enumerate(was):
        assert stands(entry, now[i]), (name, i, entry["name"], now[i]["name"])


ENTRY = {"name": "x.catchup", "unit": "ms", "better": "lower", "source": "program_span",
         "layer": "validator", "moves": "committed_tx_per_s", "workloads": ["a.catchup"]}


NO_LIST = {k: v for k, v in ENTRY.items() if k != "workloads"}


@pytest.mark.parametrize("was, now, verdict", [
    (ENTRY, ENTRY, True),
    (ENTRY, dict(ENTRY, workloads=["a.catchup", "b.catchup"]), True),   # a later cell, appended
    (ENTRY, dict(ENTRY, workloads=["b.catchup", "a.catchup"]), False),  # put before
    (ENTRY, dict(ENTRY, workloads=[]), False),                          # taken away
    (ENTRY, NO_LIST, False),                   # now due wherever what it moves is reported
    (NO_LIST, ENTRY, False),                   # no longer due but where it lists
    (ENTRY, dict(ENTRY, unit="us"), False),
    (ENTRY, dict(ENTRY, layer="validator (peer/txvalidator.py)"), False),
    (ENTRY, dict(ENTRY, why="a key of its own"), False),
], ids=["same", "cell_appended", "cell_put_before", "cell_dropped", "list_dropped",
        "list_added", "unit", "layer", "key_added"])
def test_what_stands_and_what_does_not(was, now, verdict):
    assert stands(was, now) is verdict


def test_every_cell_loads_with_its_config_and_traffic(doc, root):
    man = Manifest(root)
    for w in doc["workloads"]:
        cell = man.cell(w["name"])
        assert man.config(cell)["deployment"]["block_txs"] > 0
        mix = man.traffic(cell)
        assert mix["arrivals"] in ("backlog", "open_loop")
        if mix["arrivals"] == "open_loop":
            assert mix["rate_blocks_per_s"] > 0       # the cell's own number
    with pytest.raises(ManifestError):
        man.cell("no-such-cell")


def test_a_config_a_mix_a_cell_and_a_metric_are_added_as_new_files_only(tmp_path, doc, root):
    here, root = root, str(tmp_path)
    shutil.copytree(os.path.join(here, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "raft3-250tx.json"), "w") as f:
        json.dump({"deployment": {"orgs": 3, "block_txs": 250}, "reduced": []}, f)
    with open(os.path.join(b, "traffic", "burst.json"), "w") as f:
        json.dump({"arrivals": "open_loop", "blocks_per_pass": 4}, f)
    with open(os.path.join(b, "cells", "raft3-250tx.burst.json"), "w") as f:
        json.dump({"rate_blocks_per_s": 20.0}, f)
    with open(os.path.join(b, "layer_metrics", "fsync_ms_per_block.py"), "w") as f:
        f.write("def read(obs):\n"
                "    return 1e3 * obs['commit_stage_seconds']['fsync'] / obs['blocks']\n")
    new = json.loads(json.dumps(doc))
    new["configs"].append({"name": "raft3-250tx", "source": "x", "reduced": [], "why": "y",
                           "file": "benchmarks/configs/raft3-250tx.json"})
    new["workloads"].append({"name": "raft3-250tx.burst", "config": "raft3-250tx",
                             "traffic": "burst", "chips": 1, "why": "z"})
    for m in new["end_to_end"]:
        if m["name"].startswith("block_commit_"):
            m["workloads"].append("raft3-250tx.burst")
    new["per_layer"].append({"name": "fsync_ms_per_block.burst", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "committer and ledger",
                             "moves": "block_commit_p50_ms",
                             "workloads": ["raft3-250tx.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)

    man = Manifest(root)
    cell = man.cell("raft3-250tx.burst")
    assert man.config(cell)["deployment"]["orgs"] == 3
    assert man.traffic(cell) == {"arrivals": "open_loop", "blocks_per_pass": 4,
                                 "rate_blocks_per_s": 20.0}
    due = [m["name"] for m in man.metrics("per_layer", "raft3-250tx.burst")]
    assert "fsync_ms_per_block.burst" in due and "first_block_s" in due
    assert "collect_ms_per_block.steady" not in due      # lists its own cells
    read = man.reader("fsync_ms_per_block.burst")
    assert read({"commit_stage_seconds": {"fsync": 0.5}, "blocks": 10}) == pytest.approx(50.0)
    assert "block_commit_p50_ms" in [
        m["name"] for m in man.metrics("end_to_end", "raft3-250tx.burst")
    ]
    # what was accepted still stands first, and nothing that was there was edited
    for name in LISTS:
        assert all(stands(was, new[name][i]) for i, was in enumerate(accepted(name)))
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p
