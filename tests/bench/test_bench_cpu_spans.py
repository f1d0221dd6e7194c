"""The readers of the spans' CPU clocks (PR 37: `tts` / `tdur` on a span
that begins and ends on one thread, `args.proc_cpu_us` on detached
roots and the harness's roots), each on a small hand-made document
kept beside this file, against answers computed by hand; the `#` lines
that stand beside them; and what each reader does with a program whose
spans lack the fields (the parent of that PR): it reads nothing, says
nothing, and the metric is left out of the line."""

import json
import os

import pytest

from benchlib.manifest import Manifest

from conftest import ROOT

NEW = {
    "collect_cpu_ms_per_block": ("catchup", "steady"),
    "commit_cpu_ms_per_block": ("catchup", "steady"),
    "host_cores_busy": ("catchup", "steady"),
    "idemix_host_cpu_ms_per_block": ("catchup",),
}
# by hand from data/spans_cpu.json, five blocks
ANSWERS = {
    # collect tdur 60 + 50 + 29 + 31 + 30 = 200 ms
    "collect_cpu_ms_per_block": 200 / 5,
    # mvcc 12 + 9.5, block_append 8, fsync 0.5, kv_txn 10 + 9, state 4;
    # the abandoned `history` has no CPU time of its own
    "commit_cpu_ms_per_block": 53 / 5,
    # proc_cpu_us 1,100 + 90 + 95 + 70 over dur 1,000 + 100 + 110 + 230 ms
    "host_cores_busy": 1355 / 1440,
    # prepare 6 + normalize 4 + rehash 5 + pairing 40; not device_wait
    "idemix_host_cpu_ms_per_block": 55 / 5,
}
SUFFIX_MODE = {"catchup": "backlog", "steady": "open_loop"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(os.path.dirname(__file__), "data", "spans_cpu.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def parent_of(doc):
    """The same window as the parent of PR 37 records it."""
    old = []
    for e in doc["spans"]:
        e = {k: v for k, v in e.items() if k not in ("tts", "tdur")}
        e["args"] = {k: v for k, v in e["args"].items() if k != "proc_cpu_us"}
        old.append(e)
    return dict(doc, spans=old)


def said(capsys):
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("# "):
            tag, _, rest = line[2:].partition(": ")
            out[tag] = json.loads(rest)
    return out


@pytest.mark.parametrize("name, suffix", [(n, s) for n in sorted(NEW) for s in NEW[n]])
def test_each_reader_against_the_answer_by_hand(doc, man, name, suffix):
    obs = dict(doc, mode=SUFFIX_MODE[suffix])
    assert man.reader(f"{name}.{suffix}")(obs) == pytest.approx(ANSWERS[name])


@pytest.mark.parametrize("name, suffix", [(n, s) for n in sorted(NEW) for s in NEW[n]])
def test_the_parent_gives_nothing_to_read_and_says_nothing(doc, man, capsys, name, suffix):
    read = man.reader(f"{name}.{suffix}")
    assert read(dict(parent_of(doc), mode=SUFFIX_MODE[suffix])) is None
    for empty in ({"blocks": 5, "spans": None}, {"blocks": 0, "spans": []},
                  {"blocks": 5, "spans": []}):
        assert read(dict(empty, mode=SUFFIX_MODE[suffix])) is None
    assert said(capsys) == {}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_answers_to_both_of_its_names_and_to_the_contract_once_declared(man, name):
    """`BENCHMARK.json` does not declare these metrics yet (CHANGES.md,
    PR 37: accepted tests pin the end of `per_layer` and the count due
    in two cells, and only a `benchmark` PR may edit them).  Whatever
    entry a later PR adds under one of these names is read by this
    file and is held to the split by suffix."""
    cells = {w["name"] for w in man.doc["workloads"]}
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    for suffix in NEW[name]:
        assert man.reader(f"{name}.{suffix}").__module__ == "bench_layer_metrics_" + name
        entry = declared.get(f"{name}.{suffix}")
        if entry is None:
            continue
        assert entry["source"] == "program_span"
        assert entry["moves"] == {"catchup": "committed_tx_per_s",
                                  "steady": "block_commit_p50_ms"}[suffix]
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        assert all(w.endswith("." + suffix) for w in entry["workloads"])
        assert (entry["unit"], entry["better"]) == (
            ("cores", "higher") if name == "host_cores_busy" else ("ms", "lower"))


def test_the_split_by_thread_and_span_stands_beside_collects_cpu(doc, man, capsys):
    man.reader("collect_cpu_ms_per_block.catchup")(dict(doc, mode="backlog"))
    split = said(capsys)["oncpu_split_ms_per_block"]
    assert set(split) == {"MainThread", "committer-stream", "tpu-flush-waiter"}
    main, committer = split["MainThread"], split["committer-stream"]
    # every stage span and the flush's own; not `tpu.collect`, not the
    # Idemix spans (cat "span"), not the detached `tpu.flush`, not the
    # abandoned `history`
    assert set(main) == {"collect", "verify_wait", "mvcc", "state", "kv_txn", "gc.pause",
                         "tpu.dispatch", "tpu.marshal"}
    assert set(committer) == {"commit.idle", "mvcc", "block_append", "fsync", "kv_txn"}
    assert main["collect"] == pytest.approx({"wall": 402 / 5, "cpu": 200 / 5, "off_cpu": 202 / 5})
    assert main["kv_txn"] == pytest.approx({"wall": 7.0, "cpu": 1.8, "off_cpu": 5.2})
    assert main["gc.pause"] == pytest.approx({"wall": 0.4, "cpu": 0.4, "off_cpu": 0.0})
    assert main["tpu.dispatch"] == pytest.approx({"wall": 4.0, "cpu": 3.0, "off_cpu": 1.0})
    assert committer["mvcc"] == pytest.approx({"wall": 6.0, "cpu": 2.4, "off_cpu": 3.6})
    assert committer["kv_txn"] == pytest.approx({"wall": 8.0, "cpu": 2.0, "off_cpu": 6.0})
    # the controls: a waiting span reads near no CPU
    assert committer["commit.idle"] == pytest.approx(
        {"wall": 26.0, "cpu": 0.08, "off_cpu": 25.92})
    assert main["verify_wait"] == pytest.approx({"wall": 6.6, "cpu": 0.1, "off_cpu": 6.5})
    assert split["tpu-flush-waiter"]["tpu.device_wait"] == pytest.approx(
        {"wall": 6.0, "cpu": 0.02, "off_cpu": 5.98})


def test_the_stalls_are_read_where_they_happen(doc, man, capsys):
    """Block 5's run stood 230 ms, 160 of them in `collect` with 30 ms
    of its thread's CPU, and the whole process got 70 ms where an
    ordinary run gets 90: nobody held the lock against it, the process
    was not running."""
    man.reader("host_cores_busy.steady")(dict(doc, mode="open_loop"))
    collect = {"name": "collect", "tid": "MainThread", "wall_ms": 160.0, "cpu_ms": 30.0}
    # lone runs 100, 110, 230 ms: median 110, one 60 over it
    assert said(capsys)["stalled_blocks"] == {
        "median_ms": 110.0, "stalled": 1,
        "longest": [{"block": 5, "wall_ms": 230.0, "proc_cpu_ms": 70.0,
                     "thread_cpu_ms": 61.0, "longest_stage": collect}]}


def test_the_stall_line_is_the_open_loops_and_holds_eight_at_most(doc, man, capsys):
    read = man.reader("host_cores_busy.catchup")
    read(dict(doc, mode="backlog"))
    lines = said(capsys)
    assert "stalled_blocks" not in lines
    # the sums beside the metric; no run's own thread read more CPU than
    # the whole process (the nearest: 600 ms of 1,100; 61 of 70)
    assert lines["host_cpu"] == pytest.approx({
        "runs": 4, "wall_s": 1.44, "proc_cpu_s": 1.355, "own_thread_cpu_s": 0.783,
        "own_thread_over_process_us_max": -9_000})
    # twenty more lone runs of the usual length and twelve stalled ones:
    # thirteen stand 60 ms over the median, the eight longest are shown;
    # a run of two blocks is no lone block, however long
    run = doc["spans"][3]

    def at(i, dur, blocks=1):
        return dict(run, ts=3_000_000 + 500_000 * i, dur=dur, args=dict(run["args"], blocks=blocks))

    more = [at(i, 105_000) for i in range(20)]
    more += [at(20 + i, 300_000 + 1_000 * i) for i in range(1, 13)]
    more.append(at(40, 400_000, blocks=2))
    read(dict(doc, mode="open_loop", spans=doc["spans"] + more))
    runs = said(capsys)["stalled_blocks"]
    assert runs["median_ms"] == 105.0
    assert runs["stalled"] == 13 and len(runs["longest"]) == 8
    assert [r["wall_ms"] for r in runs["longest"]] == [312.0 - i for i in range(8)]
    assert all(r["block"] is None and r["longest_stage"] is None for r in runs["longest"])


def test_the_idemix_shares_are_printed(doc, man, capsys):
    man.reader("idemix_host_cpu_ms_per_block.catchup")(dict(doc, mode="backlog"))
    assert said(capsys)["idemix_cpu_shares"] == pytest.approx({
        "idemix.prepare": 1.2, "idemix.normalize": 0.8, "idemix.rehash": 1.0,
        "idemix.pairing": 8.0})
