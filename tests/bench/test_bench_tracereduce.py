"""The reduction from the profiler's trace to device metrics, on a
small recorded trace kept beside this file: busy union, kernel time,
idle gaps and their attribution to host spans, the clock anchor."""

import json
import os

import pytest

from benchlib import tracereduce as tr

MS = 1e6


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(os.path.dirname(__file__), "data", "trace_small.json")) as f:
        return json.load(f)


def test_only_device_planes_and_only_the_op_line_count(doc):
    (plane,) = tr.device_planes(doc)
    names = {e[0] for e in tr.op_events(plane)}
    assert "%tpu_custom_call.1" in names
    assert not any(n.startswith("jit_call") for n in names)   # roll-ups would double busy


def test_busy_is_the_union_not_the_sum(doc):
    ev = tr.op_events(tr.device_planes(doc)[0])
    # first flush 10..60 ms; second 200..251 ms with an op overlapping the kernel
    assert tr.busy_seconds(ev, 0, 300 * MS) == pytest.approx(0.050 + 0.051)
    assert sum(e[2] for e in ev) / 1e9 > 0.101
    # a window that cuts an operation counts only the part inside
    assert tr.busy_seconds(ev, 30 * MS, 220 * MS) == pytest.approx(0.030 + 0.020)


def test_idle_gaps_fill_the_rest_of_the_window(doc):
    ev = tr.op_events(tr.device_planes(doc)[0])
    gaps = tr.idle_gaps(ev, 0, 300 * MS)
    assert gaps == [(0, 10 * MS), (60 * MS, 200 * MS), (251 * MS, 300 * MS)]
    busy = tr.busy_seconds(ev, 0, 300 * MS)
    assert busy + sum(b - a for a, b in gaps) / 1e9 == pytest.approx(0.3)


def test_kernel_time_sums_the_kernels_events(doc):
    ev = tr.op_events(tr.device_planes(doc)[0])
    secs, n = tr.kernel_seconds(ev, ["tpu_custom_call"], 0, 300 * MS)
    assert (secs, n) == (pytest.approx(0.098), 2)
    totals = tr.op_totals(ev, 0, 300 * MS)
    assert totals["%copy.1"] == pytest.approx(0.002)


def test_anchor_ties_the_profilers_clock_to_the_hosts(doc):
    # the harness emitted the anchors at monotonic 1000.005 and 1000.305
    off = tr.anchor_offset_s(doc, "bench.anchor", [1000.005, 1000.305])
    assert off == pytest.approx(-1000.0)
    assert tr.anchor_offset_s(doc, "bench.missing", [1.0]) is None


def test_idle_seconds_go_to_the_host_span_open_in_them():
    gaps = [(0.060, 0.200)]
    spans = [
        ("bench.store_stream", 0.000, 0.300, "main"),
        ("collect", 0.050, 0.120, "main"),
        ("mvcc", 0.100, 0.150, "committer-stream"),
        ("bench.between_passes", 0.180, 0.300, "main"),
    ]
    got = dict(tr.attribute_gaps(gaps, spans))
    assert got["bench.store_stream/collect"] == pytest.approx(0.040)        # 60..100
    assert got["bench.store_stream/collect_mvcc"] == pytest.approx(0.020)   # 100..120
    assert got["bench.store_stream/mvcc"] == pytest.approx(0.030)           # 120..150
    assert got["bench.store_stream"] == pytest.approx(0.030)                # 150..180
    assert got["bench.between_passes"] == pytest.approx(0.020)              # 180..200
    assert sum(got.values()) == pytest.approx(0.140)


def test_reduce_device_reports_busy_window_kernel_and_gaps(doc):
    red = tr.reduce_device(doc, 0, 300 * MS, ["tpu_custom_call"])
    assert red["planes"] == ["/device:TPU:0"]
    assert red["busy_s"] == pytest.approx(0.101)
    assert red["window_s"] == pytest.approx(0.3)
    assert red["kernel_events"] == 2
    assert len(red["gaps"]) == 3
    none = tr.reduce_device({"planes": []}, 0, 1e9, ["x"])
    assert none["busy_s"] == 0.0 and none["ops"] == {}
