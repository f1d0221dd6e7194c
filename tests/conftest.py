"""Test configuration.

Tests run on CPU with a virtual 8-device mesh so multi-chip sharding
(shard_map over jax.sharding.Mesh) is exercised without TPU hardware, per
the reference test strategy of simulating multi-node on one host
(integration/nwo).  Must run before jax initializes a backend.
"""

import os

# Force (not setdefault): unit tests are hermetic and run on the virtual
# CPU mesh whatever the ambient environment says — on a machine with a
# chip a test process must never claim it (one process owns the chip;
# chip_smoke.py is the on-device check).
os.environ["JAX_PLATFORMS"] = "cpu"

# The whole tier-1 suite doubles as a lock-order soak test: coordination
# locks created through devtools.lockwatch.named_lock/named_rlock become
# instrumented wrappers that maintain the process-wide acquisition-order
# graph and raise LockOrderError on any acquisition that closes a cycle.
# setdefault so FABRIC_TPU_LOCKWATCH=0 can switch it off (or =record to
# log without raising) when bisecting a failure.
os.environ.setdefault("FABRIC_TPU_LOCKWATCH", "1")

# ...and as a thread-lifecycle soak test: every daemonized worker is
# created through devtools.lockwatch.spawn_thread (fabriclint's
# thread-hygiene rule enforces this statically), and under
# FABRIC_TPU_THREADWATCH each spawn registers in a process-wide live
# registry and records unhandled exceptions.  The session-end fixture
# below drains worker-kind threads and asserts the violation ledger is
# empty, so a worker leaked past its owner's drain/close fails the
# suite here instead of aborting interpreter teardown ("FATAL:
# exception not rethrown", the MULTICHIP rc=134 class).
os.environ.setdefault("FABRIC_TPU_THREADWATCH", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_soak_gate():
    """Fail the session if ANY lock-order inversion was recorded and not
    examined-and-cleared by a test.  Without this, a violation raised on
    a background thread (snapshot export) or inside a broad exception
    handler dies silently and tier-1 stays green — the suite-wide soak
    only has teeth if the violation ledger is asserted empty at the end.
    (tests/test_lockwatch.py injects inversions deliberately; its autouse
    fixture resets the ledger after each test.)"""
    yield
    from fabric_tpu.devtools import lockwatch

    assert not lockwatch.violations, (
        "lock-order inversions recorded during the test session "
        f"(likely on a background thread): {lockwatch.violations!r}"
    )


@pytest.fixture(scope="session", autouse=True)
def _threadwatch_drain_gate():
    """Fail the session if any watched WORKER thread outlives the tests
    or died with an unhandled exception.  Workers are bounded jobs
    (flush waiters, snapshot exports, stream committers) whose owners
    must drain them — a worker still alive here is precisely the daemon
    thread the interpreter would kill mid-kernel at teardown, and one
    that died silently is how green runs become rc=134 aborts.
    Service-kind threads (acceptors, gossip/consensus loops) are
    covered by their owners' stop()/close() paths and excluded from the
    sweep."""
    yield
    from fabric_tpu.devtools import lockwatch

    if not lockwatch.threads_enabled():
        return
    stragglers = lockwatch.drain_threads(timeout=15.0)
    assert not stragglers, (
        f"worker threads still alive at session end: {stragglers!r} — "
        "their owner never drained them; they would be killed "
        "mid-execution at interpreter exit"
    )
    assert not lockwatch.thread_violations, (
        "threadwatch violations recorded during the test session: "
        f"{lockwatch.thread_violations!r}"
    )


@pytest.fixture(autouse=True)
def _soak_residue_drain():
    """Under an ENV-ARMED session plan (``FABRIC_TPU_SOAK``, or a
    session-wide ``FABRIC_TPU_FAULTLINE``) the background plan fires
    across EVERY test — drain its trips between tests so tests
    asserting on the trip ledger see their own plans' trips, not
    accumulated background residue.  Keys off the plan faultline
    actually armed (which encodes the FAULTLINE-beats-SOAK precedence),
    never a re-parse of the environment.  A no-op in unarmed runs."""
    yield
    from fabric_tpu.devtools import faultline

    env_plan = faultline.session_env_plan()
    if env_plan is not None and faultline.current_plan() is env_plan:
        faultline.drain_trips(env_plan.label)


@pytest.fixture(scope="session", autouse=True)
def _workpool_shutdown():
    """Shut the shared host work pool down at session end.  The commit
    path's parallel collect/prepare stages lazily spin up one
    process-wide tracked executor (common/workpool.py, registered as a
    service whose stop path is this shutdown) — declared AFTER the
    gates above so its teardown runs FIRST (fixtures finalize in
    reverse instantiation order) and the pool is gone before the
    threadwatch sweep.  A pool nobody started makes this a no-op."""
    yield
    from fabric_tpu.common import workpool

    workpool.shutdown()


@pytest.fixture(scope="session", autouse=True)
def _faultline_drain_gate():
    """Fail the session if a fault plan is still armed or the trip
    ledger was left undrained.  Chaos tests arm plans through
    faultline.use_plan, which disarms and clears the ledger on exit —
    a plan leaking past its test would silently inject faults into
    every later test, and unexamined trips mean a test fired faults it
    never asserted on (the same teeth as the threadwatch drain gate).

    Exception: an ENV-ARMED session plan (``FABRIC_TPU_SOAK=<seed>``,
    or a session-wide ``FABRIC_TPU_FAULTLINE``) deliberately stays
    armed for the WHOLE session (tier-1 as a chaos soak) — exactly that
    plan is expected to still be armed here and its background trips
    are drained, not asserted on; test-local plans nested inside it
    still drain themselves via use_plan.  Identity is checked against
    ``faultline.session_env_plan()`` (the plan _init_from_env actually
    armed, encoding the FAULTLINE-beats-SOAK precedence), never a
    re-parse of the environment."""
    yield
    from fabric_tpu.devtools import faultline

    env_plan = faultline.session_env_plan()
    if env_plan is not None:
        plan = faultline.current_plan()
        assert plan is env_plan, (
            "an environment plan was armed for this session but the "
            f"plan at session end is {plan.label if plan else None!r} — "
            "a chaos test leaked a plan over it (use faultline.use_plan)"
        )
        stray = [
            t for t in faultline.trips() if t["plan"] != env_plan.label
        ]
        assert not stray, (
            f"undrained non-background faultline trips at session end: "
            f"{stray!r}"
        )
        faultline.deactivate()
        faultline.reset_trips()
        return
    assert not faultline.active(), (
        "a faultline plan is still armed at session end — a chaos test "
        "leaked its plan (use faultline.use_plan)"
    )
    assert not faultline.trips(), (
        "undrained faultline trips at session end: "
        f"{faultline.trips()!r} — the test that injected them never "
        "drained the ledger (use faultline.use_plan)"
    )
