"""Profscope acceptance: the zero-overhead disarmed contract, the env
knob, sampler capture with source-site frames, per-span CPU attribution
joined to tracelens' critical path, lock-contention roles mirrored into
lock_wait_seconds{role} on /metrics (and visible to a netscope scrape),
workpool chunk queue-wait/run attribution, profiled-vs-unprofiled
commit parity under the invariants oracle, faultfuzz profile artifacts,
and the scripts/profile.py CLI line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from fabric_tpu.common import profile, tracing, workpool
from fabric_tpu.common.operations import System
from fabric_tpu.comm.rpc import RPCClient, RPCServer
from fabric_tpu.devtools import faultfuzz, invariants, lockwatch

from test_faultfuzz import _SEEDED_PLAN

CHANNEL = faultfuzz.CHANNEL


# -- disarmed: the zero-overhead contract ------------------------------------


def test_disarmed_profile_entry_points_are_noops():
    """FABRIC_TPU_PROFILE unset (tier-1 default): no profiler exists,
    every entry point no-ops, and a real RPC round trip plus a pooled
    fan-out (both of which cross watched locks and run_chunked's feed
    point) never touch the armed path."""
    assert not profile.enabled()
    assert profile.profiler() is None
    before = profile.lookup_count()

    # every feed/control point, disarmed
    profile.note_lock_wait("kvledger.commit_lock", 0.5)
    profile.note_lock_hold("kvledger.commit_lock", 0.5)
    profile.note_chunk(0.1, 0.2)
    profile.reset()
    doc = profile.export()
    assert doc["$schema"] == profile.SPEEDSCOPE_SCHEMA
    assert doc["profiles"] == []
    assert doc["otherData"]["armed"] is False

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

    # nothing above consulted the armed path, and no sampler exists
    assert profile.lookup_count() == before
    assert profile.profiler() is None


def test_env_knob_arms_and_sizes_the_sampler(monkeypatch):
    for falsy in ("0", "false", "off", "no", ""):
        monkeypatch.setenv("FABRIC_TPU_PROFILE", falsy)
        profile._init_from_env()
        assert not profile.enabled(), falsy
    monkeypatch.setenv("FABRIC_TPU_PROFILE", "1")
    profile._init_from_env()
    try:
        assert profile.enabled()
        assert profile.profiler().interval_s == profile.DEFAULT_INTERVAL_S
        assert profile.profiler().running
    finally:
        profile.disarm()
    # a number > 1 is a sampling rate in Hz (the FABRIC_TPU_TRACE
    # sizing convention)
    monkeypatch.setenv("FABRIC_TPU_PROFILE", "250")
    profile._init_from_env()
    try:
        assert profile.profiler().interval_s == pytest.approx(1 / 250)
    finally:
        profile.disarm()
    assert not profile.enabled()
    assert profile.profiler() is None


def test_scope_restores_previous_state_and_joins_sampler():
    assert not profile.enabled()
    with profile.scope(interval_s=0.002) as p:
        assert profile.enabled()
        assert profile.profiler() is p
        assert p.running
    assert not profile.enabled()
    assert not p.running  # the sampler service thread was joined


# -- sampling: source-site frames + CPU heuristic ----------------------------


def _spin_until(stop: threading.Event) -> None:
    # fresh call frames each iteration so consecutive samples see a
    # moved frame (the on-CPU heuristic)
    def burn(n):
        return sum(i * i for i in range(n))

    while not stop.is_set():
        burn(200)


def test_sampler_folds_spinning_thread_into_collapsed_stacks():
    stop = threading.Event()
    t = lockwatch.spawn_thread(
        lambda: _spin_until(stop), name="profscope-test-spin",
        kind="worker",
    )
    t.start()
    try:
        with profile.scope(sampler=False) as p:
            p.sample_rounds(6)
            doc = profile.export("test.session")
    finally:
        stop.set()
        t.join(timeout=10.0)

    assert doc["name"] == "test.session"
    assert doc["otherData"]["samples"] == 6
    frames = [f["name"] for f in doc["shared"]["frames"]]
    # frame names carry the source site: "fn (file.py:NN)"
    assert any(f.startswith("_spin_until (") for f in frames)
    (prof,) = doc["profiles"]
    assert prof["type"] == "sampled"
    assert prof["unit"] == "seconds"
    assert len(prof["samples"]) == len(prof["weights"])
    assert prof["endValue"] == pytest.approx(sum(prof["weights"]))
    # collapsed rows are "a;b;c N" and their counts sum to the wall
    # samples attributed across stacks
    for row in doc["otherData"]["collapsed"]:
        stack, _, count = row.rpartition(" ")
        assert int(count) >= 1
        assert ";" in stack or stack


def test_span_self_cpu_attribution_joins_critical_path():
    """Samples landing inside a live tracelens span are charged to it:
    self_cpu_ms keys are span names that also appear among the trace's
    own stage events — busy-CPU read next to wall time per stage."""
    stop = threading.Event()
    started = threading.Event()

    def staged():
        with tracing.span("hot.stage", cat="stage", block=0):
            started.set()
            _spin_until(stop)

    with tracing.scope() as rec:
        with profile.scope(sampler=False) as p:
            t = lockwatch.spawn_thread(
                staged, name="profscope-test-stage", kind="worker",
            )
            t.start()
            try:
                assert started.wait(timeout=10.0)
                p.sample_rounds(6)
            finally:
                stop.set()
                t.join(timeout=10.0)
            prof_doc = profile.export()
        trace_doc = tracing.export(rec)

    od = prof_doc["otherData"]
    assert "hot.stage" in od["self_cpu_ms"]
    (row,) = [r for r in od["span_cpu"] if r["name"] == "hot.stage"]
    assert row["cat"] == "stage"
    assert row["wall_samples"] >= 1
    assert row["cpu_samples"] >= 1  # fresh frames each burn() => on-CPU
    assert row["self_cpu_ms"] == od["self_cpu_ms"]["hot.stage"]
    # the join: every CPU-attributed span is a stage event of the trace
    stages = {
        ev["name"] for ev in trace_doc["traceEvents"]
        if ev.get("cat") == "stage"
    }
    assert set(od["self_cpu_ms"]) <= stages


# -- lock contention + workpool attribution ----------------------------------


def test_lock_wait_lands_in_export_metrics_and_netscope_scrape():
    """A contended watched lock feeds profscope per-role aggregates,
    mirrors into lock_wait_seconds{role} on the operations /metrics
    page, and a netscope scrape of that endpoint carries the series."""
    sys_ = System(("127.0.0.1", 0))
    sys_.start()
    try:
        with profile.scope(sampler=False):
            profile.set_lock_metrics(sys_.lock_metrics())
            try:
                lock = lockwatch.named_lock("test.contend")
                held = threading.Event()
                done = threading.Event()

                def holder():
                    with lock:
                        held.set()
                        done.wait(timeout=10.0)

                t = lockwatch.spawn_thread(
                    holder, name="profscope-test-holder", kind="worker",
                )
                t.start()
                try:
                    assert held.wait(timeout=10.0)
                    done.set()  # waiter below blocks until holder exits
                    with lock:
                        pass
                finally:
                    t.join(timeout=10.0)
                doc = profile.export()
            finally:
                profile.set_lock_metrics(None)

        locks = doc["otherData"]["locks"]
        assert "test.contend" in locks
        assert locks["test.contend"]["wait_count"] >= 2
        assert locks["test.contend"]["hold_count"] >= 2
        assert locks["test.contend"]["wait_s"] >= 0.0
        assert (
            locks["test.contend"]["max_wait_s"]
            >= locks["test.contend"]["wait_s"]
            / locks["test.contend"]["wait_count"]
        )

        host, port = sys_.addr
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ) as r:
            exposed = r.read().decode("utf-8")
        assert 'lock_wait_seconds_count{role="test.contend"}' in exposed
        assert 'lock_hold_seconds_count{role="test.contend"}' in exposed

        from fabric_tpu.devtools.netscope import Netscope

        scope = Netscope({"n0": sys_.addr}, seed=1)
        scope.run_rounds(1)
        names = {name for (_, name, _) in scope.series_keys()}
        assert any(n.startswith("lock_wait_seconds") for n in names)
    finally:
        sys_.stop()


def test_workpool_chunk_queue_wait_vs_run_attribution():
    with profile.scope(sampler=False):
        with workpool.scoped_pool(2) as pool:
            out = workpool.run_chunked(
                pool, lambda off, chunk: [v + 1 for v in chunk],
                list(range(20)), 4,
            )
        doc = profile.export()
    assert out == [v + 1 for v in range(20)]
    wp = doc["otherData"]["workpool"]
    assert wp["chunks"] == 4
    assert wp["queue_wait_s"] >= 0.0
    assert wp["run_s"] > 0.0


# -- profiled vs unprofiled commit parity ------------------------------------


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


def test_profiled_commit_stream_is_byte_identical_to_unprofiled(tmp_path):
    """The parity acceptance: the sampler observes, never participates
    — committed blocks, exported state records, and the chain head
    hash are byte-identical with and without a live background sampler,
    and the invariants oracle passes the profiled ledger."""
    plain = _run_commit_workload(str(tmp_path / "plain"))
    with profile.scope(interval_s=0.002):
        profiled = _run_commit_workload(str(tmp_path / "profiled"))
        doc = profile.export()
        # the sampler really ran over the workload (it always takes at
        # least one sweep on start)
        assert doc["otherData"]["samples"] >= 1
    assert profiled[0] == plain[0]  # every block, byte for byte
    assert profiled[1] == plain[1]  # every state record
    assert profiled[2] == plain[2]  # chain head

    from fabric_tpu.ledger import LedgerProvider

    provider = LedgerProvider(str(tmp_path / "profiled"))
    try:
        vs = invariants.check_ledger(
            provider.open(CHANNEL), faultfuzz.workload_writes(3)
        )
        assert vs == []
    finally:
        provider.close()


# -- faultfuzz: profile artifact beside the repro ----------------------------


def test_campaign_writes_profile_artifact_next_to_repro(
    tmp_path, monkeypatch,
):
    """A failing campaign plan leaves <repro>.profile.json beside the
    repro JSON when profscope is armed (the trace-artifact contract)."""
    monkeypatch.setattr(
        faultfuzz, "generate_plan",
        lambda rng, registry, label, tripped=frozenset():
            {**_SEEDED_PLAN, "label": label},
    )
    out_dir = tmp_path / "artifacts"
    with profile.scope(sampler=False):
        summary = faultfuzz.Campaign(
            seed=11, plans=1, out_dir=str(out_dir),
            workdir=str(tmp_path / "work"), shrink=False, comm=False,
        ).run()
    assert summary["failures"] == 1
    (repro,) = summary["repro"]
    (prof_path,) = summary["profile"]
    assert prof_path == repro[: -len(".json")] + ".profile.json"
    with open(prof_path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["$schema"] == profile.SPEEDSCOPE_SCHEMA
    # the run's workpool/lock aggregates rode along with the stacks
    assert "workpool" in doc["otherData"]
    assert "locks" in doc["otherData"]


# -- scripts/profile.py: the CLI line ----------------------------------------


def test_profile_cli_emits_bench_style_line_and_artifact(tmp_path):
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "profile.py",
    )
    out = tmp_path / "profscope.json"
    env = dict(os.environ)
    env.pop("FABRIC_TPU_PROFILE", None)  # the CLI arms its own scope
    res = subprocess.run(
        [sys.executable, script, "--blocks", "2", "--hz", "400",
         "--out", str(out)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert res.returncode == 0, res.stderr
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["experiment"] == "profscope"
    assert line["final_height"] == 4  # the blocks + 2 workload commits
    assert line["samples"] >= 1
    assert line["top_frames"], "hot frames must be attributed"
    assert all(
        set(f) == {"frame", "samples"} for f in line["top_frames"]
    )
    assert isinstance(line["lock_wait_ms"], dict)
    assert line["artifact"] == str(out)
    with open(out, "r", encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["$schema"] == profile.SPEEDSCOPE_SCHEMA
    assert doc["otherData"]["collapsed"]
