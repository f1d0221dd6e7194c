"""Device-batched idemix Schnorr recomputation vs the host path.

The XLA program in csp/tpu/bn254_batch.py must produce bit-identical
T1/T2/T3 commitments to signature._relations +
schnorr.recompute_commitments for every disclosure shape, and the
device-backed verify_batch must agree with the host verify mask on
valid, tampered, and malformed signatures."""

from __future__ import annotations

import pytest

from fabric_tpu.idemix import bn254 as bn


@pytest.fixture(autouse=True)
def _pin_xla_engine(monkeypatch):
    """This module tests the XLA scan engine; the fused Pallas ladder
    (now the preferred engine) has its own parity suite in
    tests/test_pallas_bn254.py."""
    monkeypatch.setenv("FABRIC_BN254_NO_PALLAS", "1")
from fabric_tpu.idemix import schnorr, signature
from fabric_tpu.idemix.credential import new_cred_request, new_credential
from fabric_tpu.idemix.issuer import IssuerKey


@pytest.fixture(scope="module")
def world():
    isk = IssuerKey.generate(["a0", "a1", "a2"])
    sk = bn.rand_zr()
    req = new_cred_request(sk, b"nonce", isk.ipk)
    attrs = [11, 22, 33]
    cred = new_credential(isk, req, attrs)
    return isk, sk, cred, attrs


def _sigs(world, n=6):
    isk, sk, cred, attrs = world
    out = []
    for i in range(n):
        disclosure = [
            [False, False, False],
            [True, False, True],
            [True, True, True],
        ][i % 3]
        msg = b"msg-%d" % i
        sig = signature.new_signature(
            cred, sk, isk.ipk, msg, disclosure=disclosure
        )
        out.append((sig, msg))
    return out


def _host_commitments(sig, ipk):
    rels = signature._relations(
        ipk, sig.a_prime, sig.a_bar, sig.b_prime, sig.nym,
        sig.disclosure, sig.disclosed_attrs,
    )
    return schnorr.recompute_commitments(rels, sig.challenge, sig.responses)


def test_device_commitments_match_host(world):
    from fabric_tpu.csp.tpu import bn254_batch

    isk, *_ = world
    pairs = _sigs(world)
    got = bn254_batch.schnorr_commitments_batch(
        [s for s, _ in pairs], isk.ipk
    )
    for j, (sig, _msg) in enumerate(pairs):
        want = _host_commitments(sig, isk.ipk)
        assert got[j] is not None
        assert list(got[j]) == list(want), f"sig {j} commitments diverge"


def test_device_verify_batch_mask(world):
    from fabric_tpu.idemix.signature import verify_batch_device

    isk, sk, cred, attrs = world
    pairs = _sigs(world)
    sigs = [s for s, _ in pairs]
    msgs = [m for _, m in pairs]
    # tamper: wrong message for #1, wrong challenge for #3
    msgs = list(msgs)
    msgs[1] = b"not-the-message"
    import dataclasses

    sigs[3] = dataclasses.replace(
        sigs[3], challenge=(sigs[3].challenge + 1) % bn.R
    )
    want = signature.verify_batch(list(sigs), isk.ipk, list(msgs))
    got = verify_batch_device(list(sigs), isk.ipk, list(msgs))
    assert got == want
    assert got[1] is False and got[3] is False
    assert got[0] and got[2]


def test_device_malformed_inputs_never_throw(world):
    from fabric_tpu.idemix.signature import verify_batch_device

    isk, *_ = world
    pairs = _sigs(world, 2)
    good_sig, good_msg = pairs[0]
    import dataclasses

    off_curve = dataclasses.replace(
        good_sig, a_prime=(good_sig.a_prime[0], good_sig.a_prime[1] + 1)
    )
    missing = dataclasses.replace(
        good_sig, responses={k: v for k, v in good_sig.responses.items()
                             if k != "sk"}
    )
    bad_len = dataclasses.replace(good_sig, disclosure=[True])
    sigs = [good_sig, off_curve, missing, bad_len]
    msgs = [good_msg] * 4
    got = verify_batch_device(sigs, isk.ipk, msgs)
    assert got == [True, False, False, False]


# -- pseudonym signatures ride the same launch, a lane each ----------------


def _host_nym_commitment(sig, nym, ipk):
    """`nymsignature.verify_nym`'s one commitment."""
    return bn.g1_add(
        bn.g1_add(bn.g1_mul(ipk.h_sk, sig.z_sk), bn.g1_mul(ipk.h_rand, sig.z_rnym)),
        bn.g1_mul(nym, (-sig.challenge) % bn.R),
    )


def _nyms(world, n=4):
    from fabric_tpu.idemix import nymsignature

    isk, sk, *_ = world
    out = []
    for i in range(n):
        nym, r_nym = signature.make_nym(sk, isk.ipk)
        msg = b"payload-%d" % i
        out.append((nymsignature.new_nym_signature(sk, nym, r_nym, isk.ipk, msg), nym, msg))
    return out


def test_device_nym_commitments_match_verify_nyms(world):
    """Sound signatures, one whose commitment is the point at infinity,
    one with a pseudonym off the curve, one whose challenge is off by
    one: the device's T3 of each lane is the host's commitment."""
    from fabric_tpu.csp.tpu import bn254_batch
    from fabric_tpu.idemix import nymsignature

    isk, *_ = world
    ipk = isk.ipk
    nyms = _nyms(world)
    a, b, c = 5, 7, 11
    at_infinity = (
        nymsignature.NymSignature(challenge=c, z_sk=c * a % bn.R, z_rnym=c * b % bn.R),
        bn.g1_add(bn.g1_mul(ipk.h_sk, a), bn.g1_mul(ipk.h_rand, b)), b"m",
    )
    sound = nyms[0]
    off_curve = (sound[0], (sound[1][0], (sound[1][1] + 1) % bn.P), sound[2])
    import dataclasses

    off_by_one = (dataclasses.replace(sound[0], challenge=(sound[0].challenge + 1) % bn.R),
                  sound[1], sound[2])
    lanes = nyms + [at_infinity, off_curve, off_by_one]
    got = bn254_batch.nym_commitments_batch([(s, n) for s, n, _m in lanes], ipk)
    for j, (sig, nym, msg) in enumerate(lanes):
        if j == len(nyms) + 1:
            assert got[j] is False                      # the malformed lane
            assert not nymsignature.verify_nym(sig, nym, ipk, msg)
            continue
        assert got[j] == _host_nym_commitment(sig, nym, ipk), j
        assert nymsignature.challenge_matches(sig, nym, ipk, msg, got[j]) == \
            nymsignature.verify_nym(sig, nym, ipk, msg)
    assert got[len(nyms)] is None                       # the point at infinity
    assert [nymsignature.verify_nym(s, n, ipk, m) for s, n, m in lanes] == \
        [True] * len(nyms) + [False, False, False]


def test_proofs_and_nyms_share_one_launch(world):
    """A block's launch: proof lanes then pseudonym-signature lanes;
    every lane's commitments are the host's."""
    from fabric_tpu.csp.tpu import bn254_batch

    isk, *_ = world
    pairs = _sigs(world, 3)
    nyms = _nyms(world, 3)
    prep = bn254_batch.prepare(
        [s for s, _ in pairs], [(s, n) for s, n, _m in nyms], isk.ipk)
    assert prep.lanes == 6 and prep.bucket == 16
    launched = bn254_batch.enqueue(prep)
    assert launched.path == "xla" and launched.fallback is None
    launched.wait()
    got = bn254_batch.normalize(launched)
    for j, (sig, _msg) in enumerate(pairs):
        assert list(got[j]) == list(_host_commitments(sig, isk.ipk))
    for j, (sig, nym, _msg) in enumerate(nyms):
        assert got[3 + j] == (None, None, _host_nym_commitment(sig, nym, isk.ipk))
