"""Interpreter-teardown regression tests (ISSUE 4: retire os._exit).

Round 5's MULTICHIP artifact regressed to rc=134: dryrun_multichip(8)
passed every assertion, printed success, then ABORTED at interpreter
teardown — a `tpu-flush-waiter` daemon thread was still inside an XLA
kernel when Python exited, the runtime pthread-killed it, the forced
unwind crossed XLA's catch(...), and glibc raised "FATAL: exception not
rethrown".  The old bench.py papered the same abort over with
os._exit(0).

The fix is a lifecycle, not a bigger hammer: TPUCSP.drain() joins every
in-flight flush waiter (cancelling their EWMA feedback), every entry
point calls it on the way out (`node.quiesce`), and threadwatch asserts
the worker ledger is empty.  These tests pin the property: the dryrun subprocess
must exit rc=0 through NORMAL teardown, with no os._exit anywhere on
the entry paths and nothing left in the threadwatch ledger."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_os_exit_in_entry_points():
    # the workaround must stay dead: a reintroduced os._exit would mask
    # the next lifecycle regression instead of failing loudly
    import ast

    for rel in ("__graft_entry__.py", "chip_smoke.py"):
        with open(os.path.join(ROOT, rel), "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        calls = [
            node.lineno
            for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_exit"
        ]
        assert not calls, (
            f"{rel}:{calls} reintroduced os._exit — drain the provider "
            "instead (TPUCSP.drain joins the flush waiters)"
        )


def _run_dryrun(n_devices: int, timeout: float) -> None:
    code = textwrap.dedent(f"""
        import __graft_entry__

        __graft_entry__.dryrun_multichip({n_devices})

        from fabric_tpu.devtools import lockwatch

        assert not lockwatch.thread_violations, (
            "threadwatch ledger not empty: "
            + repr(lockwatch.thread_violations)
        )
        stragglers = lockwatch.drain_threads(timeout=30.0)
        assert not stragglers, (
            "worker threads alive after dryrun: " + repr(stragglers)
        )
        print("TEARDOWN-OK", flush=True)
    """)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            f"--xla_force_host_platform_device_count={n_devices}"
        ),
        "FABRIC_TPU_LOCKWATCH": "1",
        "FABRIC_TPU_THREADWATCH": "1",
    })
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    # rc=0 through NORMAL teardown is the whole point: -6/134 here is
    # the "FATAL: exception not rethrown" abort this PR fixes
    assert proc.returncode == 0, (
        f"dryrun_multichip({n_devices}) exited rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    )
    assert "TEARDOWN-OK" in proc.stdout


def test_dryrun_multichip_teardown_rc0_two_devices():
    """Tier-1 variant (2 virtual devices): the full dryrun — including
    the injected slow flush whose waiter is the historical orphan —
    must drain and exit rc=0 with an empty threadwatch ledger."""
    pytest.importorskip(
        "cryptography", reason="dryrun builds a 5-org world"
    )
    _run_dryrun(2, timeout=840.0)


@pytest.mark.slow
def test_dryrun_multichip_teardown_rc0_driver_shape():
    """Driver-shape variant (8 virtual devices) — the exact MULTICHIP
    artifact configuration that regressed in round 5."""
    pytest.importorskip(
        "cryptography", reason="dryrun builds a 5-org world"
    )
    _run_dryrun(8, timeout=1800.0)


# -- TPUCSP.drain unit coverage (satellite: cancelled flushes feed no
# EWMA) ----------------------------------------------------------------------


def test_drain_joins_waiters_and_skips_cancelled_ewma():
    pytest.importorskip("cryptography", reason="provider imports SWCSP")
    import threading
    import time as _time

    from fabric_tpu.csp.tpu.provider import _FlushResult

    fed: list = []

    def make(cancelled: bool) -> _FlushResult:
        release = threading.Event()

        def collect():
            release.wait(5)
            return [True]

        res = _FlushResult(
            [(collect, 1)], 1, device_items=[object()],
            on_device_wall=lambda lanes, wall: fed.append((lanes, wall)),
        )
        res.cancelled = cancelled
        res.start_background()
        _time.sleep(0.02)
        release.set()
        return res

    # a live (uncancelled) flush feeds the lane-wall EWMA...
    res = make(cancelled=False)
    assert res.collect() == [True]
    res._waiter.join(5)
    assert len(fed) == 1

    # ...a flush cancelled during drain never does: its wall measures
    # teardown contention, not chip speed
    fed.clear()
    res = make(cancelled=True)
    assert res.collect() == [True]
    res._waiter.join(5)
    assert fed == []


def test_drain_flushes_pending_and_returns_true():
    pytest.importorskip("cryptography")
    import hashlib

    from fabric_tpu.csp import SWCSP
    from fabric_tpu.csp.api import VerifyBatchItem
    from fabric_tpu.csp.tpu.provider import TPUCSP

    sw = SWCSP()
    key = sw.key_gen()
    d = hashlib.sha256(b"drain").digest()
    items = [
        VerifyBatchItem(key.public_key(), d, sw.sign(key, d))
        for _ in range(24)
    ]
    # coalesce_lanes high: the batch stays BUFFERED (no flush yet);
    # drain must flush it so no collector can dangle, then join
    csp = TPUCSP(min_device_batch=1, coalesce_lanes=10_000)
    collector = csp.verify_batch_async(items)
    assert csp.drain(timeout=60.0) is True
    assert csp._inflight == []
    assert collector() == [True] * 24
    # idempotent on a quiesced provider
    assert csp.drain() is True
    csp.close()
