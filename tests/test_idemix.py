"""Idemix tests: pairing math, credential lifecycle, signatures.

Mirrors the reference's idemix test coverage (idemix/idemix_test.go):
issuer key check, cred request check, credential ver, signature
sign/verify with selective disclosure, nym signatures, weak-BB, CRI.
"""

import random

import pytest

from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix import nymsignature, revocation, signature, weakbb
from fabric_tpu.idemix.credential import (
    attribute_to_scalar,
    new_cred_request,
    new_credential,
)
from fabric_tpu.idemix.issuer import IssuerKey

RNG = random.Random(42)

ATTRS = ["OU", "Role", "EnrollmentID", "RevocationHandle"]


def _rogue_signer(issuer):
    """A maker of proofs from a credential of a rogue issuer (the same
    bases, another secret key): each passes every Schnorr relation under
    `issuer`'s key and fails only the pairing."""
    import dataclasses

    x = bn.rand_zr(RNG)
    rogue = IssuerKey(isk=x, ipk=dataclasses.replace(
        issuer.ipk, w=bn.g2_mul(bn.G2_GEN, x)))
    sk = bn.rand_zr(RNG)
    req = new_cred_request(sk, b"n", rogue.ipk, rng=RNG)
    cred = new_credential(rogue, req, [1, 2, 3, 4], rng=RNG)
    return lambda: signature.new_signature(cred, sk, issuer.ipk, b"", rng=RNG)


@pytest.fixture(scope="module")
def issuer():
    return IssuerKey.generate(ATTRS, rng=RNG)


@pytest.fixture(scope="module")
def user(issuer):
    sk = bn.rand_zr(RNG)
    req = new_cred_request(sk, b"nonce-1", issuer.ipk, rng=RNG)
    attrs = [
        attribute_to_scalar("org1"),
        attribute_to_scalar(2),
        attribute_to_scalar("alice"),
        attribute_to_scalar(100),
    ]
    cred = new_credential(issuer, req, attrs, rng=RNG)
    cred.ver(sk, issuer.ipk)
    return sk, cred


class TestPairing:
    def test_bilinearity(self):
        a, b = 1234567, 987654321
        e = bn.pairing(bn.G1_GEN, bn.G2_GEN)
        assert e != bn.FP12_ONE
        lhs = bn.pairing(bn.g1_mul(bn.G1_GEN, a), bn.g2_mul(bn.G2_GEN, b))
        assert lhs == bn.fp12_pow(e, a * b % bn.R)

    def test_gt_order(self):
        e = bn.pairing(bn.G1_GEN, bn.G2_GEN)
        assert bn.fp12_pow(e, bn.R) == bn.FP12_ONE

    def test_multi_pairing_cancellation(self):
        mp = bn.multi_pairing(
            [(bn.G1_GEN, bn.G2_GEN), (bn.g1_neg(bn.G1_GEN), bn.G2_GEN)]
        )
        assert mp == bn.FP12_ONE

    def test_serialization_roundtrip(self):
        p = bn.g1_mul(bn.G1_GEN, 77)
        q = bn.g2_mul(bn.G2_GEN, 99)
        assert bn.g1_from_bytes(bn.g1_to_bytes(p)) == p
        assert bn.g2_from_bytes(bn.g2_to_bytes(q)) == q
        with pytest.raises(ValueError):
            bn.g1_from_bytes(b"\x01" * 64)  # not on curve


class TestIssuerAndCredential:
    def test_issuer_key_check(self, issuer):
        issuer.ipk.check()

    def test_issuer_key_tamper(self, issuer):
        import copy

        bad = copy.deepcopy(issuer.ipk)
        bad.w = bn.g2_mul(bn.G2_GEN, 123)
        with pytest.raises(ValueError):
            bad.check()

    def test_cred_request_bad_proof(self, issuer):
        sk = bn.rand_zr(RNG)
        req = new_cred_request(sk, b"n", issuer.ipk, rng=RNG)
        req.proof_s = (req.proof_s + 1) % bn.R
        with pytest.raises(ValueError):
            req.check(issuer.ipk)

    def test_credential_wrong_sk(self, issuer, user):
        _, cred = user
        with pytest.raises(ValueError):
            cred.ver(bn.rand_zr(RNG), issuer.ipk)

    def test_credential_attr_mismatch(self, issuer, user):
        sk, cred = user
        import copy

        bad = copy.deepcopy(cred)
        bad.attrs[0] = attribute_to_scalar("org2")
        with pytest.raises(ValueError):
            bad.ver(sk, issuer.ipk)


class TestSignature:
    def test_sign_verify_no_disclosure(self, issuer, user):
        sk, cred = user
        sig = signature.new_signature(
            cred, sk, issuer.ipk, b"msg", rng=RNG
        )
        assert signature.verify(sig, issuer.ipk, b"msg")
        assert not signature.verify(sig, issuer.ipk, b"other msg")

    def test_sign_verify_selective_disclosure(self, issuer, user):
        sk, cred = user
        disclosure = [True, True, False, False]
        sig = signature.new_signature(
            cred, sk, issuer.ipk, b"msg", disclosure=disclosure, rng=RNG
        )
        assert sig.disclosed_attrs == {
            0: cred.attrs[0], 1: cred.attrs[1]
        }
        assert signature.verify(sig, issuer.ipk, b"msg")
        # Lying about a disclosed attribute fails.
        sig.disclosed_attrs[0] = attribute_to_scalar("org2")
        assert not signature.verify(sig, issuer.ipk, b"msg")

    def test_tampered_pairing_component(self, issuer, user):
        sk, cred = user
        sig = signature.new_signature(cred, sk, issuer.ipk, b"m", rng=RNG)
        # Replacing ABar with a consistent-looking but wrong point must
        # fail the pairing check even if we can't fake the Schnorr part.
        sig.a_bar = bn.g1_mul(bn.G1_GEN, 5)
        assert not signature.verify(sig, issuer.ipk, b"m")

    def test_batch_verify(self, issuer, user):
        sk, cred = user
        msgs = [b"m%d" % i for i in range(4)]
        sigs = [
            signature.new_signature(cred, sk, issuer.ipk, m, rng=RNG)
            for m in msgs
        ]
        assert signature.verify_batch(sigs, issuer.ipk, msgs, rng=RNG) == [
            True
        ] * 4
        # Corrupt one: batch falls back and isolates it.
        sigs[2].a_bar = bn.g1_mul(bn.G1_GEN, 9)
        assert signature.verify_batch(sigs, issuer.ipk, msgs, rng=RNG) == [
            True, True, False, True,
        ]
        # Corrupt another at the Schnorr level.
        sigs[0].challenge = (sigs[0].challenge + 1) % bn.R
        assert signature.verify_batch(sigs, issuer.ipk, msgs, rng=RNG) == [
            False, True, False, True,
        ]


class TestIdemixCSPDeviceSelect:
    """The provider auto-selects the device Schnorr path at or above
    the measured crossover (VERDICT r4 #6): callers never need to know
    the constant, and small batches never pay a kernel compile."""

    def _record_dispatch(self, monkeypatch):
        calls = []

        def host(sigs, ipk, msgs, rng=None, stats=None):
            calls.append("host")
            return [True] * len(sigs)

        def device(self, items, ipk):
            calls.append("device")
            return [True] * len(items), "pallas", len(items), 128

        from fabric_tpu.csp import idemix_provider as ip

        monkeypatch.setattr(ip.signature, "verify_batch", host)
        # the device path's one entry (the flush worker calls it)
        monkeypatch.setattr(ip.IdemixCSP, "_device_mask", device)
        # the suite runs on CPU; pretend a TPU backend is present so
        # the auto path's size threshold is what's under test
        monkeypatch.setattr(ip, "_on_tpu", lambda: True)
        return calls

    def test_auto_select_by_batch_size(self, issuer, monkeypatch):
        from fabric_tpu.csp import IdemixCSP, IdemixVerifyItem

        calls = self._record_dispatch(monkeypatch)
        csp = IdemixCSP(rng=RNG)
        small = [IdemixVerifyItem(None, b"m")] * (csp.DEVICE_CROSSOVER - 1)
        large = [IdemixVerifyItem(None, b"m")] * csp.DEVICE_CROSSOVER
        csp.verify_batch(small, issuer.ipk)
        csp.verify_batch(large, issuer.ipk)
        assert calls == ["host", "device"]
        # 99 -> the host, and counted with its reason; 100 -> the device
        tally = csp.tally()
        assert tally["fallbacks"] == {"below_crossover": 1}
        assert tally["items"] == {
            "proof.host": csp.DEVICE_CROSSOVER - 1,
            "proof.pallas": csp.DEVICE_CROSSOVER,
        }
        assert [b["path"] for b in csp.recent_batches()] == ["host", "pallas"]

    def test_forced_and_overridden(self, issuer, monkeypatch):
        from fabric_tpu.csp import IdemixCSP, IdemixVerifyItem

        calls = self._record_dispatch(monkeypatch)
        items = [IdemixVerifyItem(None, b"m")] * 8
        IdemixCSP(rng=RNG, device=True).verify_batch(items, issuer.ipk)
        IdemixCSP(rng=RNG, device=False).verify_batch(
            items * 40, issuer.ipk
        )
        IdemixCSP(rng=RNG, device_crossover=8).verify_batch(
            items, issuer.ipk
        )
        assert calls == ["device", "host", "device"]

    def test_auto_device_path_is_correct(self, issuer, user, monkeypatch):
        """Real (un-mocked) dispatch above the crossover must produce
        the same mask as the host path — parity at the provider level.
        Uses a lowered crossover so the suite stays fast; _on_tpu is
        forced True (the suite runs on CPU) so the REAL
        verify_batch_device call executes via its XLA fallback."""
        from fabric_tpu.csp import IdemixCSP, IdemixVerifyItem
        from fabric_tpu.csp import idemix_provider as ip

        monkeypatch.setattr(ip, "_on_tpu", lambda: True)
        sk, cred = user
        msgs = [b"b%d" % i for i in range(6)]
        sigs = [
            signature.new_signature(cred, sk, issuer.ipk, m, rng=RNG)
            for m in msgs
        ]
        sigs[3].a_bar = bn.g1_mul(bn.G1_GEN, 7)
        items = [IdemixVerifyItem(s, m) for s, m in zip(sigs, msgs)]
        csp = IdemixCSP(rng=RNG, device_crossover=4)
        want = [True, True, True, False, True, True]
        assert csp.verify_batch(items, issuer.ipk) == want


class TestIdemixFallbacksAreCounted:
    """Every route by which an Idemix item is verified elsewhere than
    the Pallas BN254 kernel is counted with its reason, and the verdicts
    stay the host oracle's."""

    def _items(self, issuer, user):
        from fabric_tpu.csp.idemix_provider import IdemixNymItem, IdemixVerifyItem

        sk, cred = user
        items, want = [], []
        for i in range(3):
            nym, r_nym = signature.make_nym(sk, issuer.ipk, RNG)
            proof = signature.new_signature(
                cred, sk, issuer.ipk, b"", nym=nym, r_nym=r_nym, rng=RNG)
            msg = b"payload-%d" % i
            nsig = nymsignature.new_nym_signature(
                sk, nym, r_nym, issuer.ipk, msg, rng=RNG)
            items += [IdemixVerifyItem(proof, b""),
                      IdemixNymItem(nsig, nym, msg if i != 1 else b"another")]
            want += [True, i != 1]
        # a signature that did not parse: an item, and False
        items.append(IdemixNymItem(None, nym, b"x"))
        return items, want + [False]

    @pytest.mark.parametrize("reason", [
        "below_crossover", "no_tpu", "forced_host", "device_error", "pallas_to_xla",
    ])
    def test_each_reason_increments_its_counter(self, issuer, user, reason, monkeypatch):
        from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
        from fabric_tpu.csp import idemix_provider as ip
        from fabric_tpu.csp.tpu import bn254_batch

        assert reason in ip.FALLBACK_REASONS
        prov = PrometheusProvider()
        kwargs = {
            "below_crossover": {},
            "no_tpu": {"device_crossover": 2},
            "forced_host": {"device": False},
            "device_error": {"device": True},
            "pallas_to_xla": {"device": True},
        }[reason]
        csp = ip.IdemixCSP(rng=RNG, metrics=CSPMetrics(prov), **kwargs)
        if reason == "device_error":
            def broken(*a, **k):
                raise RuntimeError("the device path is broken")

            monkeypatch.setattr(bn254_batch, "prepare", broken)
        if reason == "pallas_to_xla":
            from fabric_tpu.csp.tpu import pallas_bn254

            def no_mosaic(*a, **k):
                raise RuntimeError("Mosaic refused the kernel")

            monkeypatch.setenv("FABRIC_BN254_FORCE_PALLAS", "1")
            monkeypatch.setattr(pallas_bn254, "pack", no_mosaic)
            monkeypatch.setattr(bn254_batch, "_PALLAS_FAILURES", {})
        items, want = self._items(issuer, user)
        try:
            assert csp.verify_batch_async(items, issuer.ipk)() == want
        finally:
            csp.close()
        tally = csp.tally()
        assert tally["fallbacks"] == {reason: 1}
        path = "xla" if reason == "pallas_to_xla" else "host"
        assert tally["items"] == {f"proof.{path}": 3, f"nym.{path}": 4}
        assert tally["batches"] == ({16: 1} if path == "xla" else {})
        text = prov.registry.expose()
        assert f'csp_idemix_fallbacks_total{{reason="{reason}"}} 1' in text
        assert f'csp_idemix_items_total{{kind="nym",path="{path}"}} 4' in text
        assert ('csp_idemix_batches_total{bucket="16"} 1' in text) == (path == "xla")

    def test_the_tpu_provider_builds_and_drains_its_idemix_provider(self):
        from fabric_tpu.csp import idemix_provider as ip
        from fabric_tpu.csp.sw import SWCSP
        from fabric_tpu.csp.tpu.provider import TPUCSP

        csp = TPUCSP()
        try:
            assert ip.for_csp(csp) is csp.idemix
            assert csp.idemix._device is None      # auto: crossover, then a TPU
        finally:
            csp.close()
        host_only = ip.for_csp(SWCSP())
        assert host_only is ip.for_csp(object()) and host_only._device is False


class TestIdemixFlushSpans:
    """The device path's anatomy in tracelens (forced onto the XLA
    engine here; the shape is the one the provider tests above built):
    a detached `idemix.flush` from dispatch begun to mask sealed, and
    under it prepare, enqueue, device_wait, normalize, rehash, pairing.
    Disarmed, the same path consults nothing."""

    def _batch(self, issuer, user):
        return TestIdemixFallbacksAreCounted()._items(issuer, user)

    def test_a_flush_is_a_span_with_its_parts_under_it(self, issuer, user):
        from fabric_tpu.common import tracing
        from fabric_tpu.csp.idemix_provider import IdemixCSP

        items, want = self._batch(issuer, user)
        csp = IdemixCSP(rng=RNG, device=True)
        with tracing.scope() as rec:
            collect = csp.verify_batch_async(items, issuer.ipk)
            assert collect() == want
            csp.close()
            events = [e for e in tracing.export(rec)["traceEvents"] if e.get("ph") == "X"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        (flush,) = by_name["idemix.flush"]
        assert (flush["args"]["proofs"], flush["args"]["nyms"]) == (3, 4)
        # the unparsed signature has no lane: 3 proofs + 3 pseudonym signatures
        assert (flush["args"]["lanes"], flush["args"]["bucket"]) == (6, 16)
        assert flush["args"]["path"] == "xla"
        parts = ("idemix.prepare", "idemix.enqueue", "idemix.device_wait",
                 "idemix.normalize", "idemix.rehash", "idemix.pairing")
        for name in parts:
            (e,) = by_name[name]
            assert e["args"]["parent"] == flush["args"]["span"], name
            assert e["tid"] == "idemix-flush"
            assert flush["ts"] <= e["ts"] and e["ts"] + e["dur"] <= flush["ts"] + flush["dur"]
        assert by_name["idemix.enqueue"][0]["args"]["bucket"] == 16
        assert by_name["idemix.enqueue"][0]["args"]["lanes"] == 6
        pairing = by_name["idemix.pairing"][0]["args"]
        assert pairing["combined_ok"] is True and pairing["isolated"] == 0
        assert pairing["checks"] == 1
        assert csp.tally()["pairing_checks"] == {"combined": 1, "subset": 0, "item": 0}
        # three proofs: both weighted sums under the bucket method's threshold
        assert (pairing["msm_terms"], pairing["msm_window_terms"]) == (0, 6)
        assert pairing["msm_ms"] > 0
        assert csp.tally()["msm_terms"] == {"bucket": 0, "window": 6}

    def test_a_failed_combined_check_shows_its_isolation(self, issuer, user):
        """A proof from a credential of a rogue issuer (the same bases,
        another secret key) passes every Schnorr relation and fails only
        the pairing: the combined check fails, the surviving proofs are
        isolated (three: one pairing check each), and that one alone is
        refused."""
        from fabric_tpu.common import tracing
        from fabric_tpu.csp.idemix_provider import IdemixCSP, IdemixVerifyItem

        outsider = _rogue_signer(issuer)()
        assert signature._check_schnorr(outsider, issuer.ipk, b"")
        assert not signature.verify(outsider, issuer.ipk, b"")
        items, want = self._batch(issuer, user)
        items[2] = IdemixVerifyItem(outsider, b"")
        csp = IdemixCSP(rng=RNG, device=True)
        with tracing.scope() as rec:
            got = csp.verify_batch_async(items, issuer.ipk)()
            csp.close()
            events = tracing.export(rec)["traceEvents"]
        (pairing,) = [e for e in events if e.get("name") == "idemix.pairing"]
        assert got == want[:2] + [False] + want[3:]
        assert pairing["args"]["combined_ok"] is False
        assert pairing["args"]["isolated"] == 3
        assert pairing["args"]["checks"] == 4
        assert (pairing["args"]["subset_checks"], pairing["args"]["item_checks"]) == (0, 3)
        # up to three survivors are checked one by one: no sum but the combined check's
        assert (pairing["args"]["msm_terms"], pairing["args"]["msm_window_terms"]) == (0, 6)

    def test_disarmed_the_device_path_consults_nothing(self, issuer, user):
        from fabric_tpu.common import tracing
        from fabric_tpu.csp.idemix_provider import IdemixCSP

        assert not tracing.enabled()
        before = tracing.lookup_count()
        items, want = self._batch(issuer, user)
        csp = IdemixCSP(rng=RNG, device=True)
        assert csp.verify_batch_async(items, issuer.ipk)() == want
        csp.close()
        assert tracing.lookup_count() == before


class TestIsolationByBisection:
    """After a failed combined check the forged proofs are found by
    bisection over the same random linear combination: every verdict is
    `signature.verify`'s, one forgery among 125 costs at most 14 checks
    after the combined one, and a batch full of forgeries little more
    than a check an item."""

    N = 125

    @pytest.fixture(scope="class")
    def proofs(self, issuer, user):
        """N genuine proofs and N of a rogue issuer, each with the
        verdict `signature.verify` gives it."""
        sk, cred = user
        forge = _rogue_signer(issuer)
        good = [signature.new_signature(cred, sk, issuer.ipk, b"", rng=RNG)
                for _ in range(self.N)]
        bad = [forge() for _ in range(self.N)]
        assert all(signature.verify(s, issuer.ipk, b"") for s in good)
        assert not any(signature.verify(s, issuer.ipk, b"") for s in bad)
        return good, bad

    @pytest.mark.parametrize("n,forged", [
        (125, "none"), (125, "first"), (125, "last"), (125, "adjacent"),
        (125, "apart"), (125, "seven"), (125, "all"),
        (40, "first"), (77, "seven"), (64, "all"), (5, "last"), (4, "all"),
    ])
    def test_the_mask_is_verifys_and_the_checks_are_bounded(
            self, issuer, proofs, n, forged):
        good, bad = proofs
        places = {
            "none": [], "first": [0], "last": [n - 1],
            "adjacent": [n // 3, n // 3 + 1], "apart": [2, n - 9],
            "seven": random.Random(n).sample(range(n), min(7, n)),
            "all": list(range(n)),
        }[forged]
        # what signature.verify says of each, item by item (the fixture
        # holds the genuine and the rogue issuer's proofs to it)
        want = [i not in places for i in range(n)]
        sigs = [good[i] if want[i] else bad[i] for i in range(n)]
        # a Schnorr-level casualty is no survivor: it costs no check
        ok = [True] * n + [False]
        stats: dict = {}
        got = signature._pairing_mask(
            sigs + [good[0]], ok, issuer.ipk, random.Random(n), stats=stats)
        assert got == want + [False]
        assert stats["combined_ok"] is (not places)
        assert stats["isolated"] == (n if places else 0)
        assert stats["checks"] == 1 + stats["subset_checks"] + stats["item_checks"]
        assert stats["checks"] <= 1.25 * n + 8
        if not places:
            assert stats["checks"] == 1
        if len(places) == 1:
            depth = (n - 1).bit_length()
            assert depth + 1 <= stats["checks"] <= 2 * depth + 1
            assert n != 125 or stats["checks"] <= 15

    @pytest.mark.parametrize("forged", [
        "at 0", "at 1", "at 30", "at 31", "at 61", "at 62", "at 63", "at 93",
        "at 123", "at 124", "two", "thirteen", "all",
    ])
    def test_range_sums_give_verifys_mask(self, issuer, proofs, forged):
        """The bisection over sums of ranges (a left half's one
        multi-scalar multiplication, a right half's the parent's less
        it): `signature.verify`'s verdicts wherever the forgery sits,
        the checks inside PR 29's bounds, and no more terms summed than
        a forgery's path down the halves holds."""
        good, bad = proofs
        n = self.N
        places = {
            "two": [17, 101], "thirteen": random.Random(13).sample(range(n), 13),
            "all": list(range(n)),
        }.get(forged) or [int(forged[3:])]
        want = [i not in places for i in range(n)]
        sigs = [good[i] if want[i] else bad[i] for i in range(n)]
        stats: dict = {}
        got = signature._pairing_mask(
            sigs, [True] * n, issuer.ipk, random.Random(forged), stats=stats)
        assert got == want
        assert got == [signature._balanced(s.a_prime, s.a_bar, issuer.ipk) for s in sigs]
        assert (stats["combined_ok"], stats["isolated"]) == (False, n)
        assert stats["checks"] == 1 + stats["subset_checks"] + stats["item_checks"]
        assert stats["subset_checks"] <= n // 4 + 1
        assert stats["checks"] <= 1.25 * n + 8
        summed = stats["msm_terms"] + stats["msm_window_terms"]
        if len(places) == 1:
            assert 8 <= stats["checks"] <= 15
            # the combined check's 2n and, a side, one left half a level
            assert 2 * n < summed <= 4 * n
        # never more than the combined check and, for each level of
        # halves, n/2 terms a side
        assert summed <= 2 * n + n * (n - 1).bit_length()
        assert stats["msm_terms"] >= 2 * n + 2 * (n // 2)
        assert stats["msm_ms"] > 0

    def test_the_operator_sees_the_sums_by_engine(self, issuer, proofs):
        """`csp_idemix_msm_terms_total{engine}`, `tally()["msm_terms"]`
        and the `stats` that `idemix.pairing` carries as attributes: a
        block's 127 sound proofs are two sums by the bucket method, a
        batch of three two sums by the term."""
        from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
        from fabric_tpu.csp.idemix_provider import (
            MSM_ENGINES, IdemixCSP, IdemixVerifyItem,
        )

        good, bad = proofs
        assert bn.g1_msm_engine(127) == "bucket" and bn.g1_msm_engine(3) == "window"
        prov = PrometheusProvider()
        csp = IdemixCSP(rng=RNG, device=False, metrics=CSPMetrics(prov))
        block = [IdemixVerifyItem(s, b"") for s in good + good[:2]]
        assert csp.verify_batch(block, issuer.ipk) == [True] * 127
        assert csp.tally()["msm_terms"] == {"bucket": 254, "window": 0}
        text = prov.registry.expose()
        for engine, n in zip(MSM_ENGINES, (254, 0)):
            assert f'csp_idemix_msm_terms_total{{engine="{engine}"}} {n}' in text
        assert csp.verify_batch(block[:3], issuer.ipk) == [True] * 3
        assert csp.tally()["msm_terms"] == {"bucket": 254, "window": 6}
        assert 'csp_idemix_msm_terms_total{engine="window"} 6' in prov.registry.expose()
        # a forged proof adds the bisection's range sums to both engines
        forged = block[:60] + [IdemixVerifyItem(bad[0], b"")] + block[60:124]
        assert csp.verify_batch(forged, issuer.ipk) == [True] * 60 + [False] + [True] * 64
        after = csp.tally()["msm_terms"]
        assert after["bucket"] >= 254 + 250 + 2 * 62 and after["window"] > 6
        assert csp.tally()["pairing_checks"]["combined"] == 3
        # what the span carries: the same numbers
        stats: dict = {}
        signature._pairing_mask(
            [i.sig for i in block], [True] * 127, issuer.ipk, RNG, stats=stats)
        assert (stats["msm_terms"], stats["msm_window_terms"]) == (254, 0)
        assert stats["checks"] == 1 and 0 < stats["msm_ms"] < 1000

    def test_the_weights_are_rand_zrs_at_full_width(self, issuer, proofs, monkeypatch):
        """One weight a surviving item, drawn by `bn.rand_zr` (uniform
        over Zr, 254 bits), the same for both sums; the range sums draw
        none of their own."""
        good, bad = proofs
        drawn, summed = [], []
        rand_zr, msm_sets = bn.rand_zr, bn.g1_msm_sets
        monkeypatch.setattr(bn, "rand_zr", lambda rng=None: drawn.append(rand_zr(rng)) or drawn[-1])
        monkeypatch.setattr(
            bn, "g1_msm_sets",
            lambda lists, ks: summed.append((len(lists), list(ks))) or msm_sets(lists, ks))
        sigs = good[:40] + [bad[0]] + good[40:80]
        ok = [True] * 81
        ok[7] = False
        got = signature._pairing_mask(sigs, ok, issuer.ipk, random.Random(3))
        assert got == [True] * 7 + [False] + [True] * 32 + [False] + [True] * 40
        assert len(drawn) == 80 and max(drawn).bit_length() >= 250
        assert all(0 < w < bn.R for w in drawn)
        assert summed[0] == (2, drawn)
        # every later sum is over a slice of the same weights
        text = ",".join(map(str, drawn))
        assert len(summed) > 4
        assert all(sides == 2 and ",".join(map(str, ks)) in text for sides, ks in summed[1:])

    def test_up_to_three_survivors_get_a_check_each(self, issuer, proofs):
        good, bad = proofs
        stats: dict = {}
        got = signature._pairing_mask(
            [good[0], bad[0], good[1]], [True] * 3, issuer.ipk, RNG, stats=stats)
        assert got == [True, False, True]
        assert (stats["checks"], stats["subset_checks"], stats["item_checks"]) == (4, 0, 3)

    def test_the_pure_python_pairing_gives_the_same_mask(
            self, issuer, proofs, monkeypatch):
        good, bad = proofs
        sigs = [good[0], good[1], bad[0], good[2]]
        native = signature._pairing_mask(sigs, [True] * 4, issuer.ipk, RNG)
        monkeypatch.setattr(bn, "_NATIVE", None)
        stats: dict = {}
        assert signature._pairing_mask(
            sigs, [True] * 4, issuer.ipk, RNG, stats=stats) == native
        assert native == [True, True, False, True]
        assert 3 <= stats["checks"] <= 6

    def test_the_operator_sees_the_checks_by_stage(self, issuer, proofs):
        """`csp_idemix_pairing_checks_total{stage}` and `tally()`: a
        sound batch is one `combined` check; a forged credential shows
        as `subset` and `item` checks."""
        from fabric_tpu.common.metrics import CSPMetrics, PrometheusProvider
        from fabric_tpu.csp.idemix_provider import (
            PAIRING_STAGES, IdemixCSP, IdemixVerifyItem,
        )

        good, bad = proofs
        prov = PrometheusProvider()
        csp = IdemixCSP(rng=RNG, device=False, metrics=CSPMetrics(prov))
        sound = [IdemixVerifyItem(s, b"") for s in good[:12]]
        assert csp.verify_batch(sound, issuer.ipk) == [True] * 12
        assert csp.tally()["pairing_checks"] == {"combined": 1, "subset": 0, "item": 0}
        text = prov.registry.expose()
        for stage, n in zip(PAIRING_STAGES, (1, 0, 0)):
            assert f'csp_idemix_pairing_checks_total{{stage="{stage}"}} {n}' in text
        forged = sound[:7] + [IdemixVerifyItem(bad[0], b"")] + sound[7:]
        assert csp.verify_batch(forged, issuer.ipk) == [True] * 7 + [False] + [True] * 5
        spent = csp.tally()["pairing_checks"]
        assert spent["combined"] == 2
        # 13 survivors: bisection stops after 3 or 4 subset checks, what
        # is still undecided goes item by item
        assert 3 <= spent["subset"] <= 4 and spent["subset"] + spent["item"] <= 13
        text = prov.registry.expose()
        assert 'csp_idemix_pairing_checks_total{stage="combined"} 2' in text
        assert f'csp_idemix_pairing_checks_total{{stage="subset"}} {spent["subset"]}' in text
        # the counts the condition `idemix-on-device` reads stay as they were
        assert csp.tally()["items"] == {"proof.host": 25}
        assert csp.tally()["fallbacks"] == {"forced_host": 2}
        assert csp.tally()["batches"] == {}


class TestNymSignature:
    def test_roundtrip(self, issuer):
        sk = bn.rand_zr(RNG)
        r_nym = bn.rand_zr(RNG)
        nym = bn.g1_add(
            bn.g1_mul(issuer.ipk.h_sk, sk),
            bn.g1_mul(issuer.ipk.h_rand, r_nym),
        )
        sig = nymsignature.new_nym_signature(
            sk, nym, r_nym, issuer.ipk, b"hello", rng=RNG
        )
        assert nymsignature.verify_nym(sig, nym, issuer.ipk, b"hello")
        assert not nymsignature.verify_nym(sig, nym, issuer.ipk, b"bye")
        sig.z_sk = (sig.z_sk + 1) % bn.R
        assert not nymsignature.verify_nym(sig, nym, issuer.ipk, b"hello")


class TestWeakBB:
    def test_roundtrip(self):
        sk, pk = weakbb.wbb_key_gen(rng=RNG)
        m = bn.rand_zr(RNG)
        sig = weakbb.wbb_sign(sk, m)
        assert weakbb.wbb_verify(pk, sig, m)
        assert not weakbb.wbb_verify(pk, sig, (m + 1) % bn.R)


class TestRevocation:
    def test_cri(self):
        ra = revocation.generate_long_term_revocation_key()
        cri = revocation.create_cri(ra, epoch=7, rng=RNG)
        raw = cri.to_bytes()
        back = revocation.CredentialRevocationInformation.from_bytes(raw)
        assert revocation.verify_epoch_pk(ra.public_key(), back)
        back.epoch = 8
        assert not revocation.verify_epoch_pk(ra.public_key(), back)
