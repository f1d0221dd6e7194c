"""TPU degraded-mode circuit breaker under injected device loss
(ISSUE 6 tentpole): killing the device mid-flush must keep verdicts
correct via the host reseal, consecutive failures must open the
breaker (host routing with NO device queuing), a periodic probe batch
must close it once the device recovers, and every transition must be
visible on /metrics.

Runs WITHOUT the `cryptography` package: keys are coordinate duck
types, signatures come from a pure-python P-256 signer, and the host
oracle verifies with the same arithmetic — so the chaos suite guards
the breaker on minimal hosts too (the provider's SWCSP import is
gated for exactly this)."""

import hashlib

from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
from fabric_tpu.csp import api
from fabric_tpu.csp.api import VerifyBatchItem
from fabric_tpu.devtools import faultline
from fabric_tpu.csp.tpu.provider import TPUCSP, _ProbeKey

_P = api.P256_P
_A = api.P256_A
_N = api.P256_N
_G = (api.P256_GX, api.P256_GY)


def _inv(a, m):
    return pow(a, -1, m)


def _add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2 and (y1 + y2) % _P == 0:
        return None
    if p1 == p2:
        lam = (3 * x1 * x1 + _A) * _inv(2 * y1, _P) % _P
    else:
        lam = (y2 - y1) * _inv(x2 - x1, _P) % _P
    x3 = (lam * lam - x1 - x2) % _P
    return (x3, (lam * (x1 - x3) - y1) % _P)


def _mul(k, pt):
    r = None
    while k:
        if k & 1:
            r = _add(r, pt)
        pt = _add(pt, pt)
        k >>= 1
    return r


def _keypair(tag: bytes):
    d = int.from_bytes(hashlib.sha256(b"priv-" + tag).digest(), "big") % _N
    qx, qy = _mul(d, _G)
    return d, _ProbeKey(qx, qy)


def _sign(d: int, digest: bytes, tag: bytes) -> bytes:
    z = int.from_bytes(digest, "big")
    k = int.from_bytes(hashlib.sha256(b"k-" + tag).digest(), "big") % _N
    r = _mul(k, _G)[0] % _N
    s = _inv(k, _N) * (z + r * d) % _N
    return api.marshal_ecdsa_signature(r, api.to_low_s(s))


class HostOracle:
    """Pure-python P-256 verify — the `sw` stand-in on hosts without
    the cryptography package (verdict-compatible: strict DER, low-S)."""

    def verify_batch(self, items):
        out = []
        for it in items:
            try:
                r, s = api.unmarshal_ecdsa_signature(it.signature)
            except ValueError:
                out.append(False)
                continue
            if not (0 < r < _N and api.is_low_s(s) and 0 < s):
                out.append(False)
                continue
            z = int.from_bytes(it.digest, "big")
            w = _inv(s, _N)
            v = _add(
                _mul(z * w % _N, _G),
                _mul(r * w % _N, (it.key.x, it.key.y)),
            )
            out.append(v is not None and v[0] % _N == r)
        return out


def _items(n: int):
    """n lanes, every 4th tampered (so masks are non-trivial)."""
    d, key = _keypair(b"degraded")
    out = []
    for i in range(n):
        digest = hashlib.sha256(b"msg-%d" % i).digest()
        sig = _sign(d, digest, b"n-%d" % i)
        if i % 4 == 3:
            sig = _sign(d, hashlib.sha256(b"evil").digest(), b"n-%d" % i)
        out.append(VerifyBatchItem(key, digest, sig))
    return out


def _csp(metrics=None, threshold=2, probe_every=2):
    return TPUCSP(
        sw=HostOracle(), min_device_batch=1,
        breaker_threshold=threshold, breaker_probe_every=probe_every,
        metrics=metrics,
    )


def test_device_failure_mid_flush_reseals_on_host():
    """One injected device loss at collect time: the waiter's host
    fallback answers with CORRECT verdicts and the breaker counts one
    failure without opening (threshold 2)."""
    csp = _csp()
    items = _items(24)
    want = HostOracle().verify_batch(items)
    try:
        with faultline.use_plan({"faults": [
            {"point": "tpu.collect", "action": "raise",
             "error": "DeviceUnavailable", "nth": 1},
        ]}):
            assert csp.verify_batch(list(items)) == want
            assert faultline.trips()
        assert not csp.breaker.open
        # and the next flush (healthy) resets the consecutive count
        assert csp.verify_batch(list(items)) == want
        assert csp.breaker._consecutive == 0
    finally:
        csp.close()
    assert any(want) and not all(want)
    # lane tally: the failed flush is the failover's, the healthy one
    # the device's, and together they are what was submitted
    tally = csp.lane_tally()
    assert (tally["failover"], tally["device"]) == (24, 24)
    assert sum(tally.values()) == 48


def test_breaker_opens_routes_host_probes_and_recovers():
    """The full lifecycle: two consecutive device losses open the
    breaker; held calls serve from the host with no device queuing;
    the probe_every-th held call probes, and once the injection count
    is exhausted (device \"recovered\") the probe closes the breaker
    and device dispatch resumes — with every transition on /metrics."""
    prov = PrometheusProvider()
    metrics = CSPMetrics(prov)
    csp = _csp(metrics=metrics, threshold=2, probe_every=2)
    items = _items(16)
    want = HostOracle().verify_batch(items)
    try:
        with faultline.use_plan({"faults": [
            # exactly two device failures, then the device is healthy
            {"point": "tpu.collect", "action": "raise",
             "error": "DeviceUnavailable", "count": 2},
        ]}):
            # failures 1 + 2: verdicts stay correct via host reseal
            assert csp.verify_batch(list(items)) == want
            assert csp.verify_batch(list(items)) == want
            assert csp.breaker.open
            assert csp.breaker.trips == 1
            assert "csp_tpu_breaker_state 1" in prov.registry.expose()

            # held call 1: host path, NO device queuing (gen frozen)
            gen = csp._gen
            assert csp.verify_batch(list(items)) == want
            assert csp._gen == gen
            assert csp.breaker.open

            # held call 2: probe due -> device healthy now -> breaker
            # closes and THIS call already dispatches to the device
            assert csp.verify_batch(list(items)) == want
            assert not csp.breaker.open
            assert csp._gen > gen
            assert faultline.trips()
    finally:
        csp.close()
    exposed = prov.registry.expose()
    assert "csp_tpu_breaker_state 0" in exposed
    assert "csp_tpu_breaker_trips_total 1" in exposed
    assert 'csp_tpu_breaker_probes_total{result="ok"} 1' in exposed
    assert "csp_tpu_device_failures_total 2" in exposed
    # four 16-lane calls: two failed over, one held by the open breaker,
    # one back on the device; the probe's own lanes are not counted
    assert csp.lane_tally() == {
        "device": 16, "host_race": 0, "failover": 32, "breaker": 16,
        "small": 0, "host_fraction": 0,
    }
    assert 'csp_tpu_lanes_total{sealed_by="failover"} 32' in exposed
    assert 'csp_tpu_lanes_total{sealed_by="breaker"} 16' in exposed
    assert 'csp_tpu_lanes_total{sealed_by="device"} 16' in exposed


def test_probe_fails_while_device_still_down():
    """A probe against a still-dead device must NOT close the breaker
    (and counts as a failed probe on /metrics)."""
    prov = PrometheusProvider()
    metrics = CSPMetrics(prov)
    csp = _csp(metrics=metrics, threshold=1, probe_every=1)
    items = _items(8)
    want = HostOracle().verify_batch(items)
    try:
        with faultline.use_plan({"faults": [
            {"point": "tpu.collect", "action": "raise",
             "error": "DeviceUnavailable", "count": 100},
        ]}):
            assert csp.verify_batch(list(items)) == want  # opens (t=1)
            assert csp.breaker.open
            # probe_every=1: this held call probes; the probe's own
            # collect dies too, so the breaker stays open and the call
            # is served by the host
            assert csp.verify_batch(list(items)) == want
            assert csp.breaker.open
        assert 'csp_tpu_breaker_probes_total{result="fail"} 1' in (
            prov.registry.expose()
        )
    finally:
        csp.close()


def test_dispatch_failure_counts_toward_breaker():
    """A dispatch-time death (not just collect-time) degrades the flush
    to the host oracle and feeds the breaker."""
    csp = _csp(threshold=1)
    items = _items(8)
    want = HostOracle().verify_batch(items)
    try:
        with faultline.use_plan({"faults": [
            {"point": "tpu.dispatch", "action": "raise",
             "error": "DeviceUnavailable", "nth": 1},
        ]}):
            assert csp.verify_batch(list(items)) == want
            assert csp.breaker.open
    finally:
        csp.close()
    assert csp.lane_tally()["failover"] == len(items)
    assert sum(csp.lane_tally().values()) == len(items)


def test_probe_vector_is_device_valid():
    """The hardcoded probe vector really verifies on the device path —
    if it rotted, every probe would fail and an open breaker could
    never close."""
    csp = _csp()
    try:
        assert csp._probe_device() is True
    finally:
        csp.close()
