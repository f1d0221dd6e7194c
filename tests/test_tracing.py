"""Tracelens acceptance: the zero-overhead disarmed contract, span
nesting across an RPC hop and a pooled (run_chunked) fan-out, byte-
deterministic traces under a virtual clock, the /traces endpoint,
flight-recorder dumps on injected crashes, faultfuzz trace artifacts
with same-seed determinism, and traced-vs-untraced commit parity under
the invariants oracle."""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.request

import pytest

from fabric_tpu.common import flogging, tracing, workpool
from fabric_tpu.common.operations import System
from fabric_tpu.comm.rpc import RPCClient, RPCServer
from fabric_tpu.devtools import clockskew, faultfuzz, faultline, invariants

from test_faultfuzz import _SEEDED_PLAN

CHANNEL = faultfuzz.CHANNEL


# -- disarmed: the zero-overhead contract ------------------------------------


def test_disarmed_span_entry_points_are_noops(tmp_path):
    """FABRIC_TPU_TRACE unset (tier-1 default): no recorder exists,
    every entry point returns the shared no-op singleton, and a real
    RPC round trip plus a pooled fan-out never touch the armed path."""
    assert not tracing.enabled()
    assert tracing.recorder() is None
    before = tracing.lookup_count()

    s = tracing.span("x", anything=1)
    assert s is tracing._NOOP
    assert tracing.begin("y") is tracing._NOOP
    assert s.ctx is None
    s.annotate(a=1)
    s.end()
    assert tracing.current() is None
    assert tracing.wire_token() is None
    assert tracing.attached(None) is tracing._NOOP
    tracing.instant("nope")
    tracing.annotate(z=1)

    # a live RPC round trip and a pooled fan-out, fully disarmed
    srv = RPCServer()
    srv.register("echo", lambda body, stream: body)
    srv.start()
    try:
        assert RPCClient(*srv.addr, timeout=5.0).call(
            "echo", b"hi"
        ) == b"hi"
    finally:
        srv.stop()
    with workpool.scoped_pool(2) as pool:
        out = workpool.run_chunked(
            pool, lambda off, chunk: [v * 2 for v in chunk],
            list(range(10)), 2,
        )
    assert out == [v * 2 for v in range(10)]

    # nothing above consulted the armed path, and no ring buffer exists
    assert tracing.lookup_count() == before
    assert tracing.recorder() is None


def test_env_knob_arms_and_sizes_the_recorder(monkeypatch):
    monkeypatch.setenv("FABRIC_TPU_TRACE", "0")
    tracing._init_from_env()
    assert not tracing.enabled()
    monkeypatch.setenv("FABRIC_TPU_TRACE", "1")
    tracing._init_from_env()
    try:
        assert tracing.enabled()
        assert tracing.recorder().capacity == tracing.DEFAULT_CAPACITY
    finally:
        tracing.disarm()
    monkeypatch.setenv("FABRIC_TPU_TRACE", "256")
    tracing._init_from_env()
    try:
        assert tracing.recorder().capacity == 256
    finally:
        tracing.disarm()
    assert not tracing.enabled()


# -- nesting: RPC hop + pooled fan-out ---------------------------------------


def _by_name(doc, name):
    return [e for e in doc["traceEvents"] if e["name"] == name]


def test_span_nesting_across_rpc_round_trip():
    """The server's rpc.serve span must nest under the client's
    rpc.call span (same trace, parent=call span id), which itself
    nests under the caller's span — context crossed the wire inside
    the frame."""
    with tracing.scope() as rec:
        srv = RPCServer()
        srv.register("echo", lambda body, stream: body)
        srv.start()
        try:
            with tracing.span("client.work") as outer:
                cli = RPCClient(*srv.addr, timeout=5.0)
                assert cli.call("echo", b"ping") == b"ping"
        finally:
            srv.stop()
        doc = tracing.export(rec)

    (serve,) = _by_name(doc, "rpc.serve")
    (call,) = _by_name(doc, "rpc.call")
    (work,) = _by_name(doc, "client.work")
    assert serve["args"]["method"] == "echo"
    assert serve["args"]["trace"] == call["args"]["trace"]
    assert serve["args"]["parent"] == call["args"]["span"]
    assert call["args"]["parent"] == work["args"]["span"]
    assert call["args"]["trace"] == work["args"]["trace"]
    # the hop really crossed threads
    assert serve["tid"] != call["tid"]


@pytest.mark.parametrize("width", [1, 2, 8])
def test_pooled_fanout_nests_under_caller(width):
    """run_chunked flows the caller's span into every chunk: results
    stay identical to serial at every width, and (at width > 1) each
    chunk span parents under the calling span on a pool thread."""
    items = list(range(40))
    serial = [v * 3 for v in items]
    with tracing.scope() as rec:
        with workpool.scoped_pool(4) as pool:
            with tracing.span("fanout.caller") as caller:
                got = workpool.run_chunked(
                    pool, lambda off, chunk: [v * 3 for v in chunk],
                    items, width,
                )
        doc = tracing.export(rec)
    assert got == serial
    chunks = _by_name(doc, "workpool.chunk")
    (call_ev,) = _by_name(doc, "fanout.caller")
    if width <= 1:
        assert chunks == []  # serial short-circuit: no fan-out spans
        return
    assert len(chunks) == width
    assert sorted(c["args"]["offset"] for c in chunks) == [
        i * (len(items) // width) for i in range(width)
    ]
    for c in chunks:
        assert c["args"]["trace"] == call_ev["args"]["trace"]
        assert c["args"]["parent"] == call_ev["args"]["span"]


def test_exception_mid_span_repairs_the_stack():
    """A BaseException (FaultCrash) escaping an explicit begin() must
    not corrupt later parenting: ending an outer span closes abandoned
    children and pops them."""
    with tracing.scope() as rec:
        outer = tracing.begin("outer")
        inner = tracing.begin("inner")
        assert tracing.current() == inner.ctx
        # simulate a crash path that never reached inner.end()
        outer.end()
        with tracing.span("after") as after:
            assert after.parent_id is None  # outer is gone from stack
        doc = tracing.export(rec)
    (inner_ev,) = _by_name(doc, "inner")
    assert inner_ev["args"].get("abandoned") is True


# -- determinism under VirtualClock ------------------------------------------


def _clocked_workload():
    with tracing.span("root", cat="pipeline", block=0):
        with tracing.span("stage.a", cat="stage", block=0):
            clockskew.sleep(0.010)
        with tracing.span("stage.b", cat="stage", block=0):
            clockskew.sleep(0.020)
        tracing.instant("mark", k=1)


def test_virtual_clock_traces_are_byte_identical():
    runs = []
    for _ in range(2):
        with clockskew.use_virtual(clockskew.VirtualClock(start=500.0)):
            with tracing.scope() as rec:
                _clocked_workload()
                runs.append(tracing.export(rec))
    assert runs[0]["traceEvents"] == runs[1]["traceEvents"]
    # ...including timestamps: the virtual clock IS the time base
    (a,) = _by_name(runs[0], "stage.a")
    assert a["dur"] == 10_000  # exactly the virtual 10ms, in µs


# -- CPU time: how long a span's thread was on a CPU -------------------------


def _spin_cpu(ns: int) -> None:
    t0 = time.thread_time_ns()
    while time.thread_time_ns() - t0 < ns:
        pass


def _armed_doc(work) -> dict:
    tracing.arm(256)
    try:
        work()
        return tracing.export()
    finally:
        tracing.disarm()
        tracing.reset_ids()     # as scope() leaves the disarmed world


def _thread_clock_step_us() -> int:
    """The largest step this host's thread CPU clock takes, seen over a
    short spin: under a microsecond on Linux, a whole 10 ms tick on a
    sandboxed kernel that credits CPU time by timer."""
    step, last = 0, time.thread_time_ns()
    until = time.monotonic() + 0.03
    while time.monotonic() < until:
        now = time.thread_time_ns()
        if now != last:
            step, last = max(step, now - last), now
    return step // 1000 + 1


def test_a_spinning_span_is_all_cpu_and_a_sleeping_one_none():
    """`tdur` is the thread's CPU between begin and end, `tts` the
    thread clock at the start: a spin reads within a tenth of its wall,
    a sleep under a twentieth, each with one step of the host's clock
    to spare (a single span is quantised by it) and in the best of a
    few tries (the suite's other workers share the machine's cores)."""
    step = _thread_clock_step_us()

    def work():
        with tracing.span("block", cat="pipeline"):
            with tracing.span("spin", cat="stage"):
                _spin_cpu(40_000_000)
            with tracing.span("sleep", cat="stage"):
                time.sleep(0.06 + 20 * step / 1e6)

    for _ in range(6):
        before = time.thread_time_ns() / 1e3
        doc = _armed_doc(work)
        (spin,) = _by_name(doc, "spin")
        (sleep,) = _by_name(doc, "sleep")
        if (0.9 * spin["dur"] - step <= spin["tdur"] <= spin["dur"] + step
                and spin["tdur"] >= 40_000 - step
                and sleep["tdur"] < sleep["dur"] / 20):
            break
    else:
        pytest.fail(f"no try read a spin as CPU and a sleep as none: {spin} {sleep}")
    assert before - step <= spin["tts"] <= sleep["tts"] - spin["tdur"] + step
    assert sleep["dur"] >= 60_000
    for ev in (spin, sleep):        # children: the thread's clock alone
        assert "proc_cpu_us" not in ev["args"]


def test_spans_that_follow_each_other_share_a_reading_and_tile(monkeypatch):
    """A read of the thread's clock is a system call, and stages follow
    each other within microseconds: within a few reads' worth of wall
    (here 20 us) after the thread's last reading a span's begin or end
    shares it, so
    neighbours tile (one's end is the next one's start) and no CPU is
    counted twice; one span is off by that much at most.  On clocks of the test's own: the wall steps 7 us a read,
    the thread's clock 3 us a read."""
    wall, cpu = [1000.0], [7_000_000]

    def fake_wall():
        wall[0] += 7e-6
        return wall[0]

    def fake_cpu():
        cpu[0] += 3_000
        return cpu[0]

    monkeypatch.setattr(clockskew, "monotonic", fake_wall)
    monkeypatch.setattr(time, "thread_time_ns", fake_cpu)
    monkeypatch.setattr(tracing, "_cpu_reuse_s", 20e-6)

    def work():
        for name in "abcde":
            with tracing.span(name, cat="stage"):
                pass
        wall[0] += 1.0                      # a pause: nothing is shared over it
        with tracing.span("later", cat="stage"):
            wall[0] += 0.5

    reads0 = cpu[0]
    events = [e for e in _armed_doc(work)["traceEvents"] if e["ph"] == "X"]
    # ten begins and ends 7 us apart: a fresh reading every third, then
    # both ends of the late span
    assert (cpu[0] - reads0) // 3_000 == 4 + 2
    five, later = events[:5], events[5]
    t0 = five[0]["tts"]
    # a, c and d lie between two shared readings; b and e span a fresh one
    assert [(e["name"], e["tts"] - t0, e["tdur"]) for e in five] == [
        ("a", 0, 0), ("b", 0, 3), ("c", 3, 0), ("d", 6, 0), ("e", 6, 3)]
    assert (later["tts"] - t0, later["tdur"]) == (12, 3)


def test_detached_spans_and_roots_carry_the_process_cpu():
    """A detached span has no thread of its own: no `tdur`.  The roots
    that bound a piece of work read the process's clock: a detached
    root (a peer's `block`) that alone, a harness's root
    (`cat="bench"`) beside its thread's.  The process's clock counts
    every thread, so a reading holds a helper thread's spin that the
    root's own `tdur` does not.  No other span pays for the second
    clock, a detached span under a root among them."""
    def spin_beside():
        th = threading.Thread(target=_spin_cpu, args=(30_000_000,))
        th.start()
        th.join()

    def work():
        block = tracing.begin("block", detach=True, cat="pipeline")
        spin_beside()
        block.end()
        with tracing.span("root", cat="bench"):
            det = tracing.begin("det", detach=True)
            spin_beside()
            det.end()
            with tracing.span("child"):
                pass

    step = _thread_clock_step_us()
    doc = _armed_doc(work)
    (block,) = _by_name(doc, "block")
    (root,) = _by_name(doc, "root")
    (det,) = _by_name(doc, "det")
    (child,) = _by_name(doc, "child")
    for ev in (block, det):
        assert "tts" not in ev and "tdur" not in ev
    assert block["args"]["proc_cpu_us"] >= 29_000 - step
    assert root["args"]["proc_cpu_us"] >= 29_000 - step
    assert isinstance(root["tts"], int) and isinstance(root["tdur"], int)
    # it joined, did not spin
    assert root["tdur"] < root["args"]["proc_cpu_us"] - 20_000 + step
    assert "proc_cpu_us" not in det["args"]
    assert "tdur" in child and "proc_cpu_us" not in child["args"]
    # a span under a context carried from another thread is no root
    def hop():
        with tracing.span("origin") as sp:
            ctx = sp.ctx
        with tracing.attached(ctx), tracing.span("hopped"):
            pass

    (hopped,) = _by_name(_armed_doc(hop), "hopped")
    assert "tdur" in hopped and "proc_cpu_us" not in hopped["args"]
    # nor does a root of another category read the process's clock
    def waits():
        with tracing.span("commit.idle", cat="stage"):
            pass

    (idle,) = _by_name(_armed_doc(waits), "commit.idle")
    assert "parent" not in idle["args"]
    assert "tdur" in idle and "proc_cpu_us" not in idle["args"]


def test_an_abandoned_child_has_no_cpu_time_of_its_own():
    def work():
        outer = tracing.begin("outer", cat="bench")
        tracing.begin("inner")        # a crash path never ends it
        outer.end()

    doc = _armed_doc(work)
    (inner,) = _by_name(doc, "inner")
    (outer,) = _by_name(doc, "outer")
    assert inner["args"]["abandoned"] is True
    assert "tts" not in inner and "tdur" not in inner
    assert "tdur" in outer and "proc_cpu_us" in outer["args"]


def test_a_virtual_clock_document_carries_no_cpu_time():
    """Seeded documents stay byte-identical: under an installed virtual
    clock no event has `tts`, `tdur` or `proc_cpu_us`, roots and
    detached spans among them."""
    def run():
        with clockskew.use_virtual(clockskew.VirtualClock(start=500.0)):
            with tracing.scope() as rec:
                _clocked_workload()
                det = tracing.begin("det", detach=True, cat="pipeline")
                clockskew.sleep(0.005)
                det.end()
                return tracing.export(rec)

    a, b = run(), run()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert len(a["traceEvents"]) == 5
    for ev in a["traceEvents"]:
        assert "tts" not in ev and "tdur" not in ev
        assert "proc_cpu_us" not in ev["args"]
    # what a stage event holds is what it held before the CPU clocks
    (stage,) = _by_name(a, "stage.a")
    assert set(stage) == {"ph", "name", "cat", "ts", "dur", "pid", "tid", "args", "id"}


def test_gc_pause_events_count_as_cpu():
    import gc

    tracing.arm(256)
    try:
        gc.collect(2)
        (pause,) = _by_name(tracing.export(), "gc.pause")
    finally:
        tracing.disarm()
        tracing.reset_ids()
    assert pause["tdur"] == pause["dur"] and pause["tts"] >= 0


# -- /traces endpoint --------------------------------------------------------


def _get(addr, path):
    host, port = addr
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=5
    ) as r:
        return r.status, r.read()


def test_traces_endpoint_serves_flight_recorder():
    sys_ = System(("127.0.0.1", 0))
    sys_.start()
    try:
        # disarmed: valid, empty, explicitly not armed
        status, body = _get(sys_.addr, "/traces")
        assert status == 200
        doc = json.loads(body)
        assert doc["traceEvents"] == []
        assert doc["otherData"]["armed"] is False

        # armed: drive one RPC hop and one pooled fan-out, then assert
        # the NESTED spans straight off the endpoint's JSON
        with tracing.scope():
            srv = RPCServer()
            srv.register("echo", lambda body_, stream: body_)
            srv.start()
            try:
                with tracing.span("ops.probe", block=7, cat="stage"):
                    RPCClient(*srv.addr, timeout=5.0).call("echo", b"x")
                    with workpool.scoped_pool(2) as pool:
                        workpool.run_chunked(
                            pool, lambda off, chunk: list(chunk),
                            list(range(8)), 2,
                        )
            finally:
                srv.stop()
            status, body = _get(sys_.addr, "/traces")
        assert status == 200
        doc = json.loads(body)
        assert doc["otherData"]["armed"] is True
        (probe,) = _by_name(doc, "ops.probe")
        assert probe["ph"] == "X"
        assert probe["args"]["block"] == 7
        # every span here began and ended on one thread: Perfetto reads
        # a thread duration on each
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) >= 5
        assert all(isinstance(e["tts"], int) and 0 <= e["tdur"] for e in spans)
        # RPC hop: serve nests under call nests under ops.probe
        (serve,) = _by_name(doc, "rpc.serve")
        (call,) = _by_name(doc, "rpc.call")
        assert serve["args"]["parent"] == call["args"]["span"]
        assert call["args"]["parent"] == probe["args"]["span"]
        # pooled fan-out: every chunk nests under ops.probe
        chunks = _by_name(doc, "workpool.chunk")
        assert len(chunks) == 2
        assert all(
            c["args"]["parent"] == probe["args"]["span"] for c in chunks
        )
    finally:
        sys_.stop()


# -- flight recorder + faultline ---------------------------------------------


def test_injected_crash_annotates_span_and_dumps(tmp_path):
    """An injected FaultCrash mid-commit lands an instant 'fault' mark,
    annotates the stage span it interrupted, and the recorder dumps to
    a loadable Chrome trace file."""
    from fabric_tpu.ledger import LedgerProvider

    provider = LedgerProvider(str(tmp_path / "src"))
    ledger = provider.open(CHANNEL)
    writes = faultfuzz.workload_writes(1)
    try:
        with tracing.scope() as rec:
            with faultline.use_plan({"faults": [
                {"point": "commit.stage", "ctx": {"stage": "pvt"},
                 "action": "crash", "nth": 1},
            ]}):
                blk = faultfuzz._endorsed_block(ledger, 0, writes[0])
                with pytest.raises(faultline.FaultCrash):
                    ledger.commit(blk)
            doc = tracing.export(rec)
            path = tracing.dump_to(
                str(tmp_path / "crash.trace.json"), rec
            )
    finally:
        provider.close()

    (fault,) = _by_name(doc, "fault")
    assert fault["args"]["point"] == "commit.stage"
    assert fault["args"]["action"] == "crash"
    (pvt,) = _by_name(doc, "pvt")
    assert pvt["args"]["fault"] == "commit.stage"
    assert fault["args"]["parent"] == pvt["args"]["span"]
    with open(path, "r", encoding="utf-8") as f:
        loaded = json.load(f)
    assert loaded["traceEvents"] == doc["traceEvents"]


def test_failing_faultfuzz_plan_ships_trace_and_replays_identically(
    tmp_path,
):
    """The seeded acceptance violation under an armed tracer: run_plan
    returns the flight-recorder export alongside the violations, and
    two same-seed runs produce identical span sequences (timestamps
    aside)."""
    seqs = []
    for i in range(2):
        with tracing.scope():
            res = faultfuzz.run_plan(
                _SEEDED_PLAN, str(tmp_path / f"run{i}"), comm=False
            )
        assert res["violations"], "seeded violation must fail the oracle"
        assert res["trace"]["traceEvents"]
        seqs.append(tracing.span_sequence(res["trace"]))
    assert seqs[0] == seqs[1]


def test_campaign_writes_trace_artifact_next_to_repro(
    tmp_path, monkeypatch,
):
    """A failing campaign plan leaves <repro>.trace.json beside the
    repro JSON when tracelens is armed."""
    monkeypatch.setattr(
        faultfuzz, "generate_plan",
        lambda rng, registry, label, tripped=frozenset():
            {**_SEEDED_PLAN, "label": label},
    )
    out_dir = tmp_path / "artifacts"
    with tracing.scope():
        summary = faultfuzz.Campaign(
            seed=11, plans=1, out_dir=str(out_dir),
            workdir=str(tmp_path / "work"), shrink=False, comm=False,
        ).run()
    assert summary["failures"] == 1
    (repro,) = summary["repro"]
    (trace,) = summary["trace"]
    assert trace == repro[: -len(".json")] + ".trace.json"
    with open(trace, "r", encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    # the dump shows the injected faults in causal context
    assert any(e["name"] == "fault" for e in doc["traceEvents"])


# -- traced vs untraced commit parity ----------------------------------------


def _run_commit_workload(root: str, blocks: int = 3):
    """Commit the canned per-block writes; returns (block bytes list,
    state records, last hash) with the provider closed after."""
    from fabric_tpu.ledger import LedgerProvider

    provider = LedgerProvider(root)
    ledger = provider.open(CHANNEL)
    writes = faultfuzz.workload_writes(blocks)
    try:
        for n in range(blocks + 2):
            ledger.commit(
                faultfuzz._endorsed_block(ledger, n, writes[n])
            )
        blocks_raw = [
            ledger.get_block_by_number(n).SerializeToString()
            for n in range(blocks + 2)
        ]
        state = list(ledger.state_db.export_records())
        return blocks_raw, state, ledger.block_store.last_block_hash
    finally:
        provider.close()


def test_traced_commit_stream_is_byte_identical_to_untraced(tmp_path):
    """The parity acceptance: tracing observes, never participates —
    committed blocks, exported state records, and the chain head hash
    are byte-identical with and without an armed tracer, and the
    invariants oracle passes the traced ledger."""
    plain = _run_commit_workload(str(tmp_path / "plain"))
    with tracing.scope() as rec:
        traced = _run_commit_workload(str(tmp_path / "traced"))
        assert len(rec) > 0  # the tracer really was recording
    assert traced[0] == plain[0]  # every block, byte for byte
    assert traced[1] == plain[1]  # every state record
    assert traced[2] == plain[2]  # chain head

    from fabric_tpu.ledger import LedgerProvider

    provider = LedgerProvider(str(tmp_path / "traced"))
    try:
        vs = invariants.check_ledger(
            provider.open(CHANNEL), faultfuzz.workload_writes(3)
        )
        assert vs == []
    finally:
        provider.close()


def test_span_sequence_of_a_traced_commit_is_what_it_was(tmp_path):
    """The CPU clocks ride on the events and stay out of the
    determinism view: a commit traced on the real clock (its spans
    carry `tdur`) and the same commit under a virtual clock (none does)
    give one `span_sequence()`, the four-tuples it always gave."""
    with tracing.scope() as rec:
        _run_commit_workload(str(tmp_path / "real"))
        real = tracing.export(rec)
    with clockskew.use_virtual(clockskew.VirtualClock(start=10.0, auto_step=1e-4)):
        with tracing.scope() as rec:
            _run_commit_workload(str(tmp_path / "virtual"))
            virtual = tracing.export(rec)
    assert any("tdur" in e for e in real["traceEvents"])
    assert not any("tdur" in e for e in virtual["traceEvents"])
    seq = tracing.span_sequence(real)
    assert seq == tracing.span_sequence(virtual)
    assert seq and all(len(t) == 4 for t in seq)
    names = [t[0] for t in seq]
    for stage in ("mvcc", "block_append", "state", "history", "kv_txn"):
        assert names.count(stage) == 5, stage


# -- satellites: log correlation + workpool metrics --------------------------


def test_flogging_emits_trace_ids_when_armed():
    fmt = flogging._TraceFormatter("%(message)s")
    record = logging.LogRecord(
        "fabric_tpu.test", logging.INFO, __file__, 1, "hello", (), None
    )
    assert fmt.format(record) == "hello"  # disarmed: unchanged bytes
    with tracing.scope():
        with tracing.span("logged.work") as sp:
            line = fmt.format(record)
            assert f"trace={sp.trace_id:x}" in line
            assert f"span={sp.span_id:x}" in line
        assert fmt.format(record) == "hello"  # no active span
    assert fmt.format(record) == "hello"


def test_workpool_metrics_gauges():
    from fabric_tpu.common.metrics import PrometheusProvider, WorkpoolMetrics

    prov = PrometheusProvider()
    workpool.set_metrics(WorkpoolMetrics(prov))
    try:
        with workpool.scoped_pool(2) as pool:
            out = workpool.run_chunked(
                pool, lambda off, chunk: [v + 1 for v in chunk],
                list(range(20)), 4,
            )
        assert out == [v + 1 for v in range(20)]
        exposed = prov.registry.expose()
        # four chunks went out and all came back
        assert "workpool_in_flight_chunks 0" in exposed
        assert "workpool_worker_saturation 1" in exposed
        assert "workpool_queue_depth" in exposed
        assert workpool.saturation()[0] == 0
    finally:
        workpool.set_metrics(None)


def test_operations_system_builds_workpool_metrics_lazily():
    sys_ = System(("127.0.0.1", 0), provider="disabled")
    m = sys_.workpool_metrics()
    assert m is sys_.workpool_metrics()  # memoized
    sys_._server.server_close()


# -- PR 24: gc pauses, queue waits, the flush's anatomy ----------------------


def test_arm_records_gc_pauses_and_scope_records_none():
    """arm() owns the process's one gc callback while armed: a forced
    generation-2 collection becomes a `gc.pause` span with no span id
    (the seeded counter is left alone).  scope(), the seeded tests'
    entry, records none, and disarm() takes the callback out again."""
    import gc

    assert tracing._on_gc not in gc.callbacks
    with tracing.scope() as rec:
        gc.collect(2)
        assert _by_name(tracing.export(rec), "gc.pause") == []
    assert tracing._on_gc not in gc.callbacks

    tracing.arm(256)
    try:
        assert gc.callbacks.count(tracing._on_gc) == 1
        with tracing.span("work"):
            gc.collect(2)
        doc = tracing.export()
        with tracing.scope() as inner:       # a scope inside an armed run
            gc.collect(2)
            assert _by_name(tracing.export(inner), "gc.pause") == []
    finally:
        tracing.disarm()
    assert tracing._on_gc not in gc.callbacks
    # a younger collection of the interpreter's own may have fallen in
    # the armed stretch too (generation 1 gets a span since PR 26)
    (pause,) = [
        e for e in _by_name(doc, "gc.pause") if e["args"]["generation"] == 2
    ]
    assert pause["cat"] == "stage" and pause["ph"] == "X"
    assert pause["tid"] == "MainThread" and pause["dur"] >= 0
    assert pause["args"]["generation"] == 2
    assert set(pause["args"]) == {"generation", "collected", "uncollectable"}
    (work,) = _by_name(doc, "work")
    assert work["ts"] <= pause["ts"] <= work["ts"] + work["dur"]
    assert work["args"]["span"] == "1"       # the pause took no id
    assert [s[0] for s in tracing.span_sequence(doc)] == ["work"]


def test_a_generation_1_collection_gets_a_span_and_a_short_generation_0_none():
    """The rule since PR 26: every collection of generation 1 or older,
    and a generation-0 one of at least GC_SPAN_MIN_S."""
    import gc

    tracing.arm(256)
    try:
        gc.collect(1)
        gc.collect(0)        # nothing to walk: far under a millisecond
        gc.collect(0)
        doc = tracing.export()
    finally:
        tracing.disarm()
    by_gen = [e["args"]["generation"] for e in _by_name(doc, "gc.pause")]
    assert by_gen.count(1) >= 1
    assert [e for e in _by_name(doc, "gc.pause")
            if e["args"]["generation"] == 0
            and e["dur"] < tracing.GC_SPAN_MIN_S * 1e6] == []
    # a generation-0 pause of a millisecond and more is kept
    tracing.arm(256)
    try:
        tracing._on_gc("start", {"generation": 0})
        tracing._gc_t0 -= 2 * tracing.GC_SPAN_MIN_S
        tracing._on_gc("stop", {"generation": 0, "collected": 7, "uncollectable": 0})
        (slow,) = [e for e in _by_name(tracing.export(), "gc.pause")
                   if e["args"]["collected"] == 7]
        assert slow["args"]["generation"] == 0 and slow["dur"] >= 2000
    finally:
        tracing.disarm()


def test_no_pause_is_dropped_before_export():
    """The callback cannot take the recorder's lock, so pauses wait in
    a queue that export() drains; the harness exports once, at the end
    of its window.  The queue is sized to the recorder: ten thousand
    generation-1 pauses and then a generation-2 one lose none."""
    n = 10_000
    rec = tracing.arm(1 << 14)
    try:
        for i in range(n):
            tracing._on_gc("start", {"generation": 1})
            tracing._on_gc("stop", {"generation": 1, "collected": i, "uncollectable": 0})
        tracing._on_gc("start", {"generation": 2})
        tracing._on_gc("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
        assert len(rec.snapshot()) == 0      # nothing reached the ring yet
        pauses = _by_name(tracing.export(), "gc.pause")
    finally:
        tracing.disarm()
    # (a collection of the interpreter's own may have joined them)
    ours = [e for e in pauses if e["args"]["generation"] == 1]
    assert {e["args"]["collected"] for e in ours} >= set(range(n))
    assert [e["args"]["generation"] for e in pauses].count(2) >= 1


def test_process_metrics_and_tracelens_share_one_gc_callback():
    import gc

    from fabric_tpu.common.metrics import ProcessMetrics, PrometheusProvider

    prov = PrometheusProvider()
    pm = ProcessMetrics(prov)
    try:
        assert gc.callbacks.count(tracing._on_gc) == 1
        tracing.arm(64)
        tracing.disarm()                     # pinned by the gauge: stays
        assert gc.callbacks.count(tracing._on_gc) == 1
        before = tracing.gc_pause_seconds()
        gc.collect(2)
        assert tracing.gc_pause_seconds() > before
        pm.collect()
        assert "process_gc_pause_seconds_total" in prov.registry.expose()
    finally:
        gc.callbacks.remove(tracing._on_gc)
        tracing._gc_keep = False


@pytest.fixture(scope="module")
def tpu_world():
    """One org, a few signed blocks, and a TPUCSP on whatever backend
    JAX has (here the XLA fallback): min_device_batch=1 sends every
    batch through `_dispatch`, and one 32-lane kernel shape serves all
    the tests below."""
    from orgfix import make_org

    from fabric_tpu import protoutil
    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.msp import msp_config_from_ca
    from fabric_tpu.peer.endorser import Endorser
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import proposal_pb2

    org, oorg = make_org("Org1MSP"), make_org("OrdererMSP")
    genesis = ctx.genesis_block("trch", ctx.channel_group(
        ctx.application_group({"Org1": ctx.org_group(
            "Org1MSP", msp_config_from_ca(org.ca, "Org1MSP"))}),
        ctx.orderer_group({"O": ctx.org_group(
            "OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
            consensus_type="solo"),
    ))

    def cc(sim, args):
        sim.set_state("trcc", args[0].decode(), args[1])
        return 200, "", b""

    ledger, bundle = _fresh_ledger(org, genesis)
    endorser = Endorser("trch", ledger, bundle,
                        org.signer("peer0", role_ou="peer"), {"trcc": cc}, org.csp)
    client = org.signer("user1", role_ou="client")
    blocks = []
    for b in range(3):
        blk = common_pb2.Block()
        blk.header.number = b + 1
        for i in range(3):
            prop, _ = protoutil.create_chaincode_proposal(
                client.serialize(), "trch", "trcc", [b"k%d-%d" % (b, i), b"v"])
            raw = prop.SerializeToString()
            resp = endorser.process_proposal(proposal_pb2.SignedProposal(
                proposal_bytes=raw, signature=client.sign(raw)))
            blk.data.data.append(
                protoutil.create_signed_tx(prop, client, [resp]).SerializeToString())
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
    prov = PrometheusProvider()
    csp = TPUCSP(min_device_batch=1, metrics=CSPMetrics(prov))
    yield org, genesis, blocks, csp, prov
    csp.close()


def _fresh_ledger(org, genesis):
    from fabric_tpu.common.channelconfig import bundle_from_genesis
    from fabric_tpu.ledger import LedgerProvider

    ledger = LedgerProvider(None).create(genesis)
    return ledger, bundle_from_genesis(genesis, org.csp)


def _committer(tpu_world):
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    org, genesis, blocks, csp, _prov = tpu_world
    ledger, bundle = _fresh_ledger(org, genesis)
    committer = Committer(TxValidator("trch", ledger, bundle, csp), ledger)
    return committer, [common_pb2.Block.FromString(b) for b in blocks]


def test_disarmed_commit_path_consults_nothing(tpu_world):
    """The zero-overhead pin over the whole commit path, new sites
    included: a store_stream and a store_block through the TPU provider
    (queue waits, flush anatomy, kernel enqueue, waiter, collector)
    with tracing never armed leave the armed-path counter where it was
    and install no gc callback."""
    import gc

    assert not tracing.enabled()
    before = tracing.lookup_count()
    committer, blocks = _committer(tpu_world)
    flags = list(committer.store_stream(iter(blocks[:2]), depth=2))
    flags.append(committer.store_block(blocks[2]))
    assert len(flags) == 3 and all(f == [0, 0, 0] for f in flags)
    tpu_world[3].drain()
    assert tracing.lookup_count() == before
    assert tracing.recorder() is None
    assert tracing._on_gc not in gc.callbacks


def test_flush_anatomy_and_queue_waits_are_spans(tpu_world):
    """Armed, the same path shows who waited for whom (commit.idle on
    the committer thread, commit.backpressure / commit.await_flags on
    the main one), what a dispatch spent its time on (tpu.marshal and
    tpu.enqueue under tpu.dispatch under tpu.flush), one tpu.flush per
    generation ended by whoever sealed it, the waiter's tpu.device_wait,
    and every span of one block under that block's trace in both
    store_stream and store_block."""
    csp = tpu_world[3]
    committer, blocks = _committer(tpu_world)    # genesis commits untraced
    with tracing.scope() as rec:
        list(committer.store_stream(iter(blocks[:2]), depth=2))
        committer.store_block(blocks[2])
        csp.drain()
        doc = tracing.export(rec)

    span_of = {e["args"]["span"]: e for e in doc["traceEvents"] if "span" in e["args"]}
    flushes = _by_name(doc, "tpu.flush")
    dispatches = _by_name(doc, "tpu.dispatch")
    assert len(flushes) == len(dispatches) >= 2
    assert len({f["args"]["batch"] for f in flushes}) == len(flushes)
    for f in flushes:
        assert f["cat"] == "span"            # tpu.* stays out of the gap labels
        assert f["args"]["sealed_by"] == "device"
        assert f["args"]["buckets"] == [32] and f["args"]["lanes"] <= 32
    for d in dispatches:
        flush = span_of[d["args"]["parent"]]
        assert flush["name"] == "tpu.flush" and flush["args"]["batch"] == d["args"]["batch"]
        assert flush["ts"] <= d["ts"] and flush["dur"] >= d["dur"]
    for name in ("tpu.marshal", "tpu.enqueue"):
        found = _by_name(doc, name)
        assert len(found) >= len(dispatches)
        assert all(span_of[e["args"]["parent"]]["name"] == "tpu.dispatch" for e in found)
    for e in _by_name(doc, "tpu.enqueue"):
        assert e["args"]["bucket"] == 32 and 0 < e["args"]["lanes"] <= 32
        assert e["args"]["device"] == 0 and isinstance(e["args"]["cold"], bool)
        assert e["args"]["kernel"] == "xla_p256_verify"     # this backend's
    waits = _by_name(doc, "tpu.device_wait")
    assert len(waits) == len(flushes)
    assert all(w["tid"] == "tpu-flush-waiter" for w in waits)
    assert all(span_of[w["args"]["parent"]]["name"] == "tpu.flush" for w in waits)
    collects = _by_name(doc, "tpu.collect")
    assert {c["args"]["batch"] for c in collects} == {f["args"]["batch"] for f in flushes}
    for c in collects:
        assert c["args"]["raced"] is False and "sole" in c["args"]
        assert {"deadline_ms", "lane_wall_ewma_us", "host_rate_ewma"} <= set(c["args"])

    idle = _by_name(doc, "commit.idle")
    back = _by_name(doc, "commit.backpressure")
    tail = _by_name(doc, "commit.await_flags")
    assert len(back) == 2 and len(idle) == 3 and 1 <= len(tail) <= 2
    assert all(e["cat"] == "stage" and "depth" in e["args"] for e in idle + back + tail)
    assert {e["tid"] for e in idle} == {"committer-stream"}
    assert {e["tid"] for e in back + tail} == {"MainThread"}

    # one block, one trace, in both paths
    roots = {e["args"]["block"]: e for e in _by_name(doc, "block")}
    assert sorted(roots) == [1, 2, 3]
    for name in ("collect", "verify_wait", "policy", "mvcc", "block_append", "state"):
        for e in _by_name(doc, name):
            root = roots[e["args"]["block"]]
            assert e["args"]["trace"] == root["args"]["trace"], (name, e["args"])
            assert e["args"]["parent"] == root["args"]["span"], (name, e["args"])
    assert {e["args"]["parent"] for e in back} == {
        roots[1]["args"]["span"], roots[2]["args"]["span"]}
    # a block's creators on its collect span: one client signed all three
    for e in _by_name(doc, "collect"):
        assert (e["args"]["creators"], e["args"]["creator_validations"]) == (1, 1)
        assert 0 < e["args"]["creator_ms"] <= e["dur"] / 1e3 + 1.0


def test_a_flush_past_its_deadline_carries_raced(tpu_world):
    """The deadline machinery on the trace and on /metrics: a device
    that answers late makes the sole consumer race on the host; its
    tpu.collect says so, the tpu.flush names who sealed it, and the
    operator's counters count the race and the dispatch."""
    import time

    import numpy as np

    from fabric_tpu.csp.api import VerifyBatchItem

    org, _genesis, _blocks, csp, prov = tpu_world
    key = org.csp.key_gen()
    digest = org.csp.hash(b"late")
    items = [VerifyBatchItem(key.public_key(), digest, org.csp.sign(key, digest))] * 4
    inner = csp._dispatch

    def late(batch):
        res = inner(batch)
        res.deadline = 0.2               # the XLA fallback sets none

        def slow(out):
            time.sleep(0.6)
            return np.asarray(out)

        res._pending = [(lambda o=o: slow(o), keep) for o, keep in res._pending]
        return res

    csp._dispatch = late
    try:
        with tracing.scope() as rec:
            assert csp.verify_batch(items) == [True] * 4
            csp.drain()
            doc = tracing.export(rec)
    finally:
        del csp._dispatch
    (collect,) = _by_name(doc, "tpu.collect")
    assert collect["args"]["raced"] is True and collect["args"]["race_won"] is True
    assert collect["args"]["sole"] is True
    assert 50.0 <= collect["args"]["deadline_ms"] <= 200.0
    (flush,) = _by_name(doc, "tpu.flush")
    assert flush["args"]["sealed_by"] == "host_race"
    assert flush["args"]["deadline_ms"] == pytest.approx(200.0)
    exposed = prov.registry.expose()
    assert 'csp_tpu_host_races_total{outcome="won"} 1' in exposed
    assert 'csp_tpu_dispatches_total{bucket="32"}' in exposed


def test_compile_events_are_counted_and_marked_when_armed(tpu_world):
    from fabric_tpu.csp.tpu import provider

    prov = tpu_world[4]

    def traces_counted():
        key = 'csp_tpu_compile_events_total{event="jaxpr_trace_duration"} '
        return sum(float(line[len(key):]) for line in
                   prov.registry.expose().splitlines() if line.startswith(key))

    n0 = traces_counted()        # the fixture's own compile may have counted
    before = tracing.lookup_count()
    provider._on_compile_event("/jax/core/compile/backend_compile_duration", 0.5)
    provider._on_compile_event("/jax/not/a/compile/event", 0.5)
    assert tracing.lookup_count() == before          # disarmed: counter only
    with tracing.scope() as rec:
        with tracing.span("tpu.enqueue") as sp:
            provider._on_compile_event(
                "/jax/core/compile/jaxpr_trace_duration", 0.25)
            provider._on_compile_event(      # nested, tiny: counted only
                "/jax/core/compile/jaxpr_trace_duration", 2e-5)
        doc = tracing.export(rec)
    (mark,) = _by_name(doc, "jax.compile")
    assert mark["args"]["event"] == "jaxpr_trace_duration"
    assert mark["args"]["secs"] == 0.25
    assert mark["args"]["parent"] == f"{sp.span_id:x}"
    exposed = prov.registry.expose()
    assert 'csp_tpu_compile_events_total{event="backend_compile_duration"}' in exposed
    assert traces_counted() == n0 + 2
    assert "not/a/compile" not in exposed
