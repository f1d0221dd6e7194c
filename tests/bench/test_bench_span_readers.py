"""The readers that read tracelens spans (`obs["spans"]`), each on a
small hand-made span document kept beside this file, against answers
computed by hand; and what each does with a program that lacks its
spans (the parent of the PR that added them): it reads nothing and
the metric is left out."""

import json
import os

import pytest

from benchlib import spans
from benchlib.manifest import Manifest

from conftest import ROOT

NEW = {
    "collect_self_ms_per_block": ("catchup", "steady"),
    "gc_pause_span_ms_per_block": ("catchup", "steady"),
    "dispatch_ms_per_flush": ("catchup", "steady"),
    "flush_wall_ms_per_flush": ("catchup", "steady"),
    "bucket_fill_share": ("catchup", "steady"),
    "host_race_start_share": ("catchup", "steady"),
    "commit_idle_ms_per_block": ("catchup",),
    "validator_backpressure_ms_per_block": ("catchup",),
}


@pytest.fixture(scope="module")
def obs():
    with open(os.path.join(os.path.dirname(__file__), "data", "spans_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    raise AssertionError(f"no '# {tag}:' line")


def test_interval_arithmetic():
    assert spans.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [[0, 4], [5, 9]]
    assert spans.overlap_us(1, 8, [(0, 3), (2, 4), (7, 20)]) == 3 + 1
    assert spans.overlap_us(1, 8, []) == 0


def test_collect_self_takes_out_the_dispatch_of_its_thread_and_any_threads_pause(obs, man, capsys):
    # collect spans: 100 + 50 + 40 + 60 = 250 ms over 4 blocks.
    # block 1 (10..110): tpu.dispatch on its thread 80..100 (20), and the
    #   committer thread's pause 95..120 covers 100..110 more (10): self 70
    # block 2 (200..250): only another thread's dispatch overlaps: self 50
    # block 3 (360..400): a 2 ms young-generation pause: self 38
    # block 4 (420..480): a pause 470..500 covers its last 10: self 50
    value = man.reader("collect_self_ms_per_block.catchup")(obs)
    assert value == pytest.approx((70 + 50 + 38 + 50) / 4)
    parts = said(capsys, "collect_split_ms_per_block")
    assert parts["under_tpu_dispatch"] == pytest.approx(20 / 4)
    assert parts["under_gc_pause"] == pytest.approx((10 + 2 + 10) / 4)
    assert parts["self"] + parts["under_tpu_dispatch"] + parts["under_gc_pause"] == \
        pytest.approx(250 / 4) == pytest.approx(parts["collect_spans"])


def test_gc_pause_span_counts_generation_2_inside_the_timed_spans(obs, man, capsys):
    # generation 2: 25 and 30 inside bench.store_stream (0..1000 ms), 10 of
    # the 20 that straddle its end, none of the 40 between passes, 8 inside
    # bench.drain_run; the generation-0 pause is not counted
    value = man.reader("gc_pause_span_ms_per_block.steady")(obs)
    assert value == pytest.approx((25 + 30 + 10 + 0 + 8) / 4)
    seen = said(capsys, "gc_pause_spans")
    assert seen["generation2"] == 5 and seen["all"] == 6
    assert seen["generation2_ms"] == pytest.approx(25 + 30 + 20 + 40 + 8)


def test_dispatch_leaves_out_the_cold_one_and_prints_its_shares(obs, man, capsys):
    # warm dispatches: 20 ms (marshal 8, keytable 2, enqueue 9) and 10 ms
    # (marshal 6, enqueue 3); the 30 ms one held a cold enqueue
    value = man.reader("dispatch_ms_per_flush.catchup")(obs)
    assert value == pytest.approx((20 + 10) / 2)
    shares = said(capsys, "dispatch_shares")
    assert shares == pytest.approx(
        {"tpu.marshal": 14 / 30, "tpu.keytable": 2 / 30, "tpu.enqueue": 12 / 30})


def test_flush_wall_fill_and_races(obs, man, capsys):
    assert man.reader("flush_wall_ms_per_flush.steady")(obs) == pytest.approx((60 + 30 + 90) / 3)
    # lanes 3000 + 4000 + 1000 over buckets 4096 + 4096 + 2048
    assert man.reader("bucket_fill_share.catchup")(obs) == pytest.approx(100 * 8000 / 10240)
    # four collects, one raced (and won)
    assert man.reader("host_race_start_share.steady")(obs) == pytest.approx(25.0)
    assert said(capsys, "host_races") == {"collects": 4, "raced": 1, "race_won": 1, "sole": 3}


def test_who_waited_for_whom(obs, man):
    assert man.reader("commit_idle_ms_per_block.catchup")(obs) == pytest.approx((100 + 20 + 4) / 4)
    assert man.reader("validator_backpressure_ms_per_block.catchup")(obs) == \
        pytest.approx((1 + 3 + 36) / 4)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_metric_is_declared_split_and_a_float(obs, man, name):
    declared = {m["name"]: m for m in man.doc["per_layer"]}
    for suffix in NEW[name]:
        entry = declared[f"{name}.{suffix}"]
        assert entry["moves"] == {"catchup": "committed_tx_per_s",
                                  "steady": "block_commit_p50_ms"}[suffix]
        assert all(w.endswith("." + suffix) for w in entry["workloads"])
        assert isinstance(man.reader(entry["name"])(obs), float)
    assert {f"{name}.{s}" for s in ("catchup", "steady")} & set(declared) == \
        {f"{name}.{s}" for s in NEW[name]}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_spans_gives_nothing_to_read(obs, man, name):
    """The parent of the PR that added the spans: tracing armed, the
    old spans there, none of the new ones (and `tpu.collect` without
    `raced`).  Only the readers that need nothing new still read."""
    new = ("gc.pause", "tpu.flush", "tpu.enqueue", "tpu.marshal", "tpu.keytable",
           "tpu.device_wait", "commit.idle", "commit.backpressure", "commit.await_flags")
    old = []
    for e in obs["spans"]:
        if e["name"] in new or e.get("ph") != "X":
            continue
        e = dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("raced", "race_won", "sole", "deadline_ms")})
        old.append(e)
    parent = {"blocks": 4, "spans": old}
    value = man.reader(f"{name}.catchup")(parent)
    if name in ("collect_self_ms_per_block", "dispatch_ms_per_flush"):
        assert isinstance(value, float)       # collect and tpu.dispatch were there
    else:
        assert value is None
    for empty in ({"blocks": 4, "spans": None}, {"blocks": 0, "spans": []}):
        assert man.reader(f"{name}.catchup")(empty) is None
