"""TPU provider parity vs the sw oracle (hash + verify batch APIs)."""

import hashlib
import random

from fabric_tpu.csp import SWCSP, VerifyBatchItem, api, init_factories
from fabric_tpu.csp.tpu.provider import TPUCSP


def test_factory_selects_tpu():
    csp = init_factories("tpu", force=True)
    assert isinstance(csp, TPUCSP)
    init_factories("sw", force=True)


def _hash_msgs():
    rng = random.Random(3)
    msgs = [bytes(rng.randrange(256) for _ in range(rng.randrange(0, 200))) for _ in range(37)]
    return msgs + [b"", b"a" * 55, b"a" * 56, b"a" * 64, b"a" * 119, b"a" * 120]


def test_hash_batch_parity():
    csp = TPUCSP(min_device_batch=1)
    msgs = _hash_msgs()
    got = csp.hash_batch(msgs)
    want = [hashlib.sha256(m).digest() for m in msgs]
    assert got == want


def test_sha256_kernel_parity():
    """The device SHA-256 kernel has no product caller (hash_batch is
    hashlib at every size); this is the guard that keeps it correct
    for the dryrun's compile check, on the padding edges above."""
    from fabric_tpu.csp.tpu import sha256 as dev_sha

    msgs = _hash_msgs()
    want = [hashlib.sha256(m).digest() for m in msgs]
    assert dev_sha.sha256_batch(msgs) == want
    # a static width wider than the longest message needs (bucketing)
    assert dev_sha.sha256_batch(msgs, n_blocks=8) == want


def test_verify_batch_parity_with_tampering():
    rng = random.Random(11)
    sw = SWCSP()
    tpu = TPUCSP(sw=sw, min_device_batch=1)
    items = []
    for i in range(40):
        key = sw.key_gen()
        digest = sw.hash(b"payload-%d" % i)
        sig = sw.sign(key, digest)
        roll = rng.random()
        if roll < 0.15:
            sig = sig[:-2] + bytes([sig[-2] ^ 1, sig[-1]])
        elif roll < 0.25:
            digest = sw.hash(b"evil-%d" % i)
        elif roll < 0.3:
            sig = b"\x30\x02\x01\x01"  # malformed DER
        elif roll < 0.35:
            r, s = api.unmarshal_ecdsa_signature(sig)
            sig = api.marshal_ecdsa_signature(r, api.P256_N - s)  # high-S
        items.append(VerifyBatchItem(key.public_key(), digest, sig))
    got = tpu.verify_batch(items)
    want = sw.verify_batch(items)
    assert got == want
    assert any(got) and not all(got)
    # the lane tally names who sealed the mask: here the device alone
    tpu.drain()
    tally = tpu.lane_tally()
    assert tally.pop("device") == len(items)
    assert not any(tally.values()), tally


def test_verify_batch_small_falls_back_to_host():
    sw = SWCSP()
    tpu = TPUCSP(sw=sw, min_device_batch=64)
    key = sw.key_gen()
    d = sw.hash(b"x")
    items = [VerifyBatchItem(key.public_key(), d, sw.sign(key, d))]
    assert tpu.verify_batch(items) == [True]
    assert tpu.lane_tally()["small"] == 1
    assert sum(tpu.lane_tally().values()) == 1


# -- flush waiter / deadline host-race mechanics -------------------------


def _signed_items(n, sw=None):
    sw = sw or SWCSP()
    key = sw.key_gen()
    out = []
    for i in range(n):
        d = sw.hash(b"race-%d" % i)
        sig = sw.sign(key, d)
        if i % 5 == 4:
            sig = b"\x30\x02\x01\x01"  # invalid lane
        out.append(VerifyBatchItem(key.public_key(), d, sig))
    return out


def test_deadline_ewma_budget(monkeypatch):
    """The stall deadline is a latency budget: host anchor until the
    EWMA is primed, then 1.5x the predicted flush wall clamped to
    [0.15s, anchor] — so ordinary windows race early while a starved
    chip window cannot inflate its own deadline past the host cost."""
    import fabric_tpu.csp.tpu.provider as prov

    # the process-wide measured host rate (fed by other tests' host
    # races) must not leak into these exact-equality assertions
    monkeypatch.setattr(prov, "_host_rate_ewma", [None])
    csp = TPUCSP(stall_factor=1.0, host_rate_hint=10000.0)
    # unprimed: the anchor (lanes/host_rate, floor 0.2)
    assert csp._deadline_for(4000) == 0.4
    assert csp._deadline_for(100) == 0.2
    # primed with a fast chip: tight budget, floored at 0.15
    for _ in range(4):
        csp._note_device_wall(4000, 0.08)  # 20 us/lane -> 50 klane/s
    d = csp._deadline_for(4000)
    assert abs(d - 0.15) < 1e-9 or d < 0.2  # 1.5*0.08=0.12 -> floor 0.15
    # a big flush scales linearly but stays under the anchor
    d = csp._deadline_for(16000)
    assert 0.15 <= d <= 1.6
    assert abs(d - 1.5 * (0.08 / 4000) * 16000) < 1e-9
    # a starved window (chip 10x slower) is capped by the anchor
    for _ in range(12):
        csp._note_device_wall(4000, 3.2)
    assert csp._deadline_for(4000) == 0.4  # anchor, not 1.5*3.2
    # disabled stall factor -> no deadline at all
    assert TPUCSP(stall_factor=None)._deadline_for(4000) is None


def test_sole_flush_deadline_is_absolute_budget():
    """A sole-flush consumer (the serial p99 path) gets an ABSOLUTE
    latency budget — deadline + estimated host-race stays inside
    ~420 ms even when a slow chip window inflates the EWMA past it —
    while the pipelined deadline keeps its anchor.  The race reserve
    uses the MEASURED host rate when one exists."""
    import fabric_tpu.csp.tpu.provider as prov

    with prov._host_rate_lock:
        saved = prov._host_rate_ewma[0]
        prov._host_rate_ewma[0] = None  # hint-only, deterministic
    try:
        csp = TPUCSP(stall_factor=1.0, host_rate_hint=9000.0)
        # slow window: ordinary flush wall 0.25s for 3000 lanes
        for _ in range(8):
            csp._note_device_wall(3000, 0.25)
        pipelined = csp._deadline_for(3000)
        assert pipelined == max(0.2, 3000 / 9000.0)  # anchor-capped
        sole = csp._sole_deadline_for(3000)
        assert sole is not None
        assert sole + 3000 / 9000.0 <= 0.421  # budget holds
        assert sole >= 0.05
        assert TPUCSP(stall_factor=None)._sole_deadline_for(3000) is None
        # a SLOWER measured host rate shrinks the deadline further
        prov._note_host_rate(3000, 0.5)  # 6000 sigs/s observed
        tighter = csp._sole_deadline_for(3000)
        assert tighter == 0.05  # 0.42 - 0.5 < floor
    finally:
        with prov._host_rate_lock:
            prov._host_rate_ewma[0] = saved


def test_flush_deadline_host_race_beats_stalled_device():
    """A device that never answers is beaten by the host race after the
    deadline; mask matches the host oracle exactly."""
    import threading

    from fabric_tpu.csp.tpu.provider import _FlushResult

    sw = SWCSP()
    items = _signed_items(12, sw)
    release = threading.Event()

    def stalled_collect():
        release.wait(10)
        return [True] * len(items)

    sealed = []
    res = _FlushResult(
        [(stalled_collect, len(items))], len(items), sw=sw,
        device_items=items, deadline=0.05,
        on_sealed=lambda kind, lanes: sealed.append((kind, lanes)),
    )
    got = res.collect()
    release.set()
    assert got == sw.verify_batch(items)
    assert sealed == [("host_race", len(items))]


def test_flush_race_yields_to_device_completion():
    """If the device finishes while the host race is mid-way, the device
    mask wins (no partial/mixed result)."""
    from fabric_tpu.csp.tpu.provider import _FlushResult

    sw = SWCSP()
    items = _signed_items(8, sw)
    res = _FlushResult(
        [(lambda: [True] * len(items), len(items))], len(items), sw=sw,
        device_items=items, deadline=0.01,
    )
    # seal via the waiter path first, as the background thread would
    res.start_background()
    got = res.collect()
    assert got == [True] * len(items)


def test_flush_waiter_failure_degrades_to_host():
    """A device collector that raises mid-flight leaves the host oracle
    answering for the whole flush."""
    from fabric_tpu.csp.tpu.provider import _FlushResult

    sw = SWCSP()
    items = _signed_items(10, sw)

    def broken_collect():
        raise RuntimeError("device lost")

    res = _FlushResult(
        [(broken_collect, len(items))], len(items), sw=sw,
        device_items=items,
    )
    assert res.collect() == sw.verify_batch(items)


def test_flush_collect_concurrent_segments_consistent():
    """Many threads collecting the same flush all see the one sealed
    mask (the r3 advisor's double-materialization race)."""
    import threading

    from fabric_tpu.csp.tpu.provider import _FlushResult

    sw = SWCSP()
    items = _signed_items(16, sw)
    calls = []

    def device_collect():
        calls.append(1)
        return sw.verify_batch(items)

    res = _FlushResult(
        [(device_collect, len(items))], len(items), sw=sw,
        device_items=items,
    )
    got: list = [None] * 6
    ths = [
        threading.Thread(target=lambda i=i: got.__setitem__(i, res.collect()))
        for i in range(6)
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    want = sw.verify_batch(items)
    assert all(g == want for g in got)
    assert len(calls) == 1  # materialized exactly once
