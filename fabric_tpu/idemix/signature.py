"""Idemix presentation signatures (reference idemix/signature.go).

A signature proves, in zero knowledge: "I hold a credential (A, B, e, s)
from this issuer over attributes (m_1..m_L) and secret key sk; I disclose
the attributes in D and hide the rest; Nym is a pseudonym bound to the same
sk" — and signs a message via Fiat-Shamir.

Construction (re-derived from the CDL scheme the reference implements; see
signature.go NewSignature for the reference's randomization with
r1/r2/r3 and the same APrime/ABar/BPrime triple):

    r1 <- Zr*, r3 = 1/r1, r2 <- Zr
    APrime = A^r1
    ABar   = B^r1 * APrime^{-e}        # equals APrime^x
    BPrime = B^r1 * HRand^{-r2}
    s'     = s - r2 * r3

which gives the verifier-checkable identities

    e(APrime, W) == e(ABar, g2)                       (pairing check)
    ABar * BPrime^{-1} == APrime^{-e} * HRand^{r2}    (relation 1)
    g1^{-1} * prod_{i in D} HAttrs_i^{-m_i}
        == HSk^{sk} * HRand^{s'} * prod_{i in H} HAttrs_i^{m_i}
           * BPrime^{-r3}                             (relation 2)
    Nym == HSk^{sk} * HRand^{r_nym}                   (relation 3)

Relations 1-3 are proven with the generalized Schnorr engine
(fabric_tpu/idemix/schnorr.py); sk is shared between relations 2 and 3,
binding the pseudonym to the credential.

Batched verification (`verify_batch`): all N pairing checks against one
issuer key collapse — with random weights t_i — into TWO pairings:

    e(sum_i t_i * APrime_i, W) * e(-sum_i t_i * ABar_i, g2) == 1

This is the BN256 batch-verify baseline configuration (BASELINE.json): the
reference spends two FP256BN.Ate calls per signature
(signature.go:290-291); the batch spends two per *block*.  Where the
product is not 1 some item is forged, and `_isolate` finds which by
bisection over the same weighted sums: two pairings a subset, 7 to 14
subsets for one forgery among 125.  Every weighted sum, the batch's and
a subset's, is ONE multi-scalar multiplication (`_weighted_sums`).
"""

from __future__ import annotations

import dataclasses
import time

from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix import schnorr
from fabric_tpu.idemix.credential import Credential
from fabric_tpu.idemix.issuer import IssuerPublicKey


@dataclasses.dataclass
class Signature:
    a_prime: tuple
    a_bar: tuple
    b_prime: tuple
    nym: tuple
    challenge: int
    responses: dict[str, int]
    disclosure: list[bool]
    disclosed_attrs: dict[int, int]  # index -> scalar value
    nonce: bytes

    def to_bytes(self) -> bytes:
        import json

        return json.dumps(
            {
                "a_prime": bn.g1_to_bytes(self.a_prime).hex(),
                "a_bar": bn.g1_to_bytes(self.a_bar).hex(),
                "b_prime": bn.g1_to_bytes(self.b_prime).hex(),
                "nym": bn.g1_to_bytes(self.nym).hex(),
                "challenge": self.challenge,
                "responses": self.responses,
                "disclosure": self.disclosure,
                "disclosed_attrs": {
                    str(k): v for k, v in self.disclosed_attrs.items()
                },
                "nonce": self.nonce.hex(),
            },
            sort_keys=True,
        ).encode()

    @classmethod
    def from_bytes(cls, raw: bytes) -> "Signature":
        import json

        d = json.loads(raw)
        return cls(
            a_prime=bn.g1_from_bytes(bytes.fromhex(d["a_prime"])),
            a_bar=bn.g1_from_bytes(bytes.fromhex(d["a_bar"])),
            b_prime=bn.g1_from_bytes(bytes.fromhex(d["b_prime"])),
            nym=bn.g1_from_bytes(bytes.fromhex(d["nym"])),
            challenge=int(d["challenge"]),
            responses={k: int(v) for k, v in d["responses"].items()},
            disclosure=[bool(b) for b in d["disclosure"]],
            disclosed_attrs={
                int(k): int(v) for k, v in d["disclosed_attrs"].items()
            },
            nonce=bytes.fromhex(d["nonce"]),
        )


def _relations(
    ipk: IssuerPublicKey,
    a_prime,
    a_bar,
    b_prime,
    nym,
    disclosure: list[bool],
    disclosed_attrs: dict[int, int],
) -> list[schnorr.Relation]:
    hidden = [i for i, d in enumerate(disclosure) if not d]
    y1 = bn.g1_add(a_bar, bn.g1_neg(b_prime))
    rel1 = schnorr.Relation(
        target=y1, bases=[a_prime, ipk.h_rand], names=["neg_e", "r2"]
    )
    y2 = bn.g1_neg(bn.G1_GEN)
    for i, d in enumerate(disclosure):
        if d:
            y2 = bn.g1_add(
                y2,
                bn.g1_mul(ipk.h_attrs[i], (-disclosed_attrs[i]) % bn.R),
            )
    rel2 = schnorr.Relation(
        target=y2,
        bases=[ipk.h_sk, ipk.h_rand, *[ipk.h_attrs[i] for i in hidden],
               b_prime],
        names=["sk", "sprime", *[f"m_{i}" for i in hidden], "neg_r3"],
    )
    rel3 = schnorr.Relation(
        target=nym, bases=[ipk.h_sk, ipk.h_rand], names=["sk", "r_nym"]
    )
    return [rel1, rel2, rel3]


def _challenge_bytes(
    ipk: IssuerPublicKey,
    commitments,
    a_prime,
    a_bar,
    b_prime,
    nym,
    disclosure,
    disclosed_attrs,
    msg: bytes,
    nonce: bytes,
) -> int:
    chunks = [b"idemix-signature"]
    chunks += [bn.g1_to_bytes(t) for t in commitments]
    chunks += [
        bn.g1_to_bytes(a_prime),
        bn.g1_to_bytes(a_bar),
        bn.g1_to_bytes(b_prime),
        bn.g1_to_bytes(nym),
        ipk.hash(),
        bytes(disclosure),
        b"".join(
            i.to_bytes(4, "big") + v.to_bytes(32, "big")
            for i, v in sorted(disclosed_attrs.items())
        ),
        msg,
        nonce,
    ]
    return bn.hash_to_zr(*chunks)


def make_nym(sk: int, ipk: IssuerPublicKey, rng=None) -> tuple[tuple, int]:
    """(Nym, r_nym) — a fresh pseudonym commitment to sk (reference
    idemix/util.go MakeNym)."""
    r_nym = bn.rand_zr(rng)
    nym = bn.g1_add(bn.g1_mul(ipk.h_sk, sk), bn.g1_mul(ipk.h_rand, r_nym))
    return nym, r_nym


def new_signature(
    cred: Credential,
    sk: int,
    ipk: IssuerPublicKey,
    msg: bytes,
    disclosure: list[bool] | None = None,
    nonce: bytes = b"",
    nym: tuple | None = None,
    r_nym: int | None = None,
    rng=None,
) -> Signature:
    n_attrs = len(ipk.attr_names)
    if disclosure is None:
        disclosure = [False] * n_attrs
    if len(disclosure) != n_attrs or len(cred.attrs) != n_attrs:
        raise ValueError("disclosure/attribute length mismatch")
    if (nym is None) != (r_nym is None):
        raise ValueError("nym and r_nym must be supplied together")

    r1 = bn.rand_zr(rng)
    r2 = bn.rand_zr(rng)
    r3 = pow(r1, -1, bn.R)
    if nym is None:
        nym, r_nym = make_nym(sk, ipk, rng)

    a_prime = bn.g1_mul(cred.a, r1)
    b_r1 = bn.g1_mul(cred.b, r1)
    a_bar = bn.g1_add(b_r1, bn.g1_mul(a_prime, (-cred.e) % bn.R))
    b_prime = bn.g1_add(b_r1, bn.g1_mul(ipk.h_rand, (-r2) % bn.R))
    sprime = (cred.s - r2 * r3) % bn.R

    disclosed_attrs = {
        i: cred.attrs[i] for i, d in enumerate(disclosure) if d
    }
    hidden = [i for i, d in enumerate(disclosure) if not d]
    secrets = {
        "neg_e": (-cred.e) % bn.R,
        "r2": r2,
        "sk": sk,
        "sprime": sprime,
        "neg_r3": (-r3) % bn.R,
        "r_nym": r_nym,
    }
    for i in hidden:
        secrets[f"m_{i}"] = cred.attrs[i]

    rels = _relations(
        ipk, a_prime, a_bar, b_prime, nym, disclosure, disclosed_attrs
    )
    c, responses = schnorr.prove(
        rels,
        secrets,
        lambda ts: _challenge_bytes(
            ipk, ts, a_prime, a_bar, b_prime, nym, disclosure,
            disclosed_attrs, msg, nonce,
        ),
        rng=rng,
    )
    return Signature(
        a_prime=a_prime,
        a_bar=a_bar,
        b_prime=b_prime,
        nym=nym,
        challenge=c,
        responses=responses,
        disclosure=list(disclosure),
        disclosed_attrs=disclosed_attrs,
        nonce=nonce,
    )


def _check_schnorr(sig: Signature, ipk: IssuerPublicKey, msg: bytes) -> bool:
    """The host-side (non-pairing) part of verification.  Every field of
    `sig` is attacker-controlled: any malformed content (missing
    responses, out-of-range disclosed attrs, wrong shapes) must yield
    False, never an exception."""
    try:
        if sig.a_prime is None:
            return False
        for pt in (sig.a_prime, sig.a_bar, sig.b_prime, sig.nym):
            if pt is None or not bn.g1_is_on_curve(pt):
                return False
        rels = _relations(
            ipk, sig.a_prime, sig.a_bar, sig.b_prime, sig.nym,
            sig.disclosure, sig.disclosed_attrs,
        )
        commitments = schnorr.recompute_commitments(
            rels, sig.challenge, sig.responses
        )
        c = _challenge_bytes(
            ipk, commitments, sig.a_prime, sig.a_bar, sig.b_prime, sig.nym,
            sig.disclosure, sig.disclosed_attrs, msg, sig.nonce,
        )
        return c == sig.challenge
    except (ValueError, IndexError, KeyError, TypeError, OverflowError,
            AttributeError):
        return False


def _balanced(a_prime, a_bar, ipk) -> bool:
    """e(a_prime, W) == e(a_bar, g2), as one two-pairing check."""
    return bn.pairing_check([(a_prime, ipk.w), (bn.g1_neg(a_bar), bn.G2_GEN)])


def verify(sig: Signature, ipk: IssuerPublicKey, msg: bytes) -> bool:
    """Single-signature verification (reference signature.go Ver: Schnorr
    recomputation then two Ate pairings at :290-291)."""
    if not _check_schnorr(sig, ipk, msg):
        return False
    return _balanced(sig.a_prime, sig.a_bar, ipk)


def verify_batch(
    sigs: list[Signature],
    ipk: IssuerPublicKey,
    msgs: list[bytes],
    rng=None,
    stats: dict | None = None,
) -> list[bool]:
    """Batched verification against one issuer key.

    Per-item Schnorr checks run first (cheap, host); surviving items enter
    the combined two-pairing check with random weights.  If the combined
    check fails, the forged items are isolated by bisection over the same
    weighted sums (`_pairing_mask`), so the result is a per-signature
    mask, each verdict the one `verify` gives — matching the CSP
    batch-verify contract (fabric_tpu/csp/api.py: policy evaluation
    tolerates invalid items).  `stats` is `_pairing_mask`'s.
    """
    ok = [
        _check_schnorr(s, ipk, m) for s, m in zip(sigs, msgs)
    ]
    return _pairing_mask(sigs, ok, ipk, rng, stats=stats)


def _weighted_sums(a_primes, a_bars, weights, seen: dict):
    """(sum_i r_i*A'_i, sum_i r_i*Abar_i): the two sides of a weighted
    check as one multi-scalar multiplication call (`bn.g1_msm_sets`:
    the bucket method from its threshold up).  `seen` learns the terms
    summed, by engine, and the wall spent."""
    begun = time.perf_counter()
    sums = bn.g1_msm_sets([a_primes, a_bars], weights)
    seen["msm_ms"] += (time.perf_counter() - begun) * 1e3
    engine = bn.g1_msm_engine(len(weights))
    seen["msm_terms" if engine == "bucket" else "msm_window_terms"] += (
        2 * len(weights)
    )
    return sums


def _pairing_mask(sigs, ok: list[bool], ipk, rng=None,
                  stats: dict | None = None) -> list[bool]:
    """Combined two-pairing check over the Schnorr-surviving items with
    random weights: one check when every pairing holds.  When it fails,
    `_isolate` finds the forged items by bisection over the same
    weighted sums (one forgery among 125: 7 to 14 further checks where
    a check an item took 125), and the result stays a per-signature
    mask.  `stats`, if given, learns `combined_ok`, how many items were
    `isolated` (the survivors of a batch whose combined check failed),
    the pairing `checks` of the batch (the combined one included: 1
    on the passing path), `subset_checks` and `item_checks` among them,
    and what the weighted sums cost: `msm_terms` (point-and-scalar
    terms, both sides, summed by the bucket method; 0 where every sum
    was under its threshold), `msm_window_terms` (those summed a
    multiplication a term) and `msm_ms` (the wall of the sums)."""
    live = [i for i, v in enumerate(ok) if v]
    seen = {"combined_ok": True, "isolated": 0, "checks": 0,
            "subset_checks": 0, "item_checks": 0,
            "msm_terms": 0, "msm_window_terms": 0, "msm_ms": 0.0}
    if live:
        weights = [bn.rand_zr(rng) for _ in live]
        a_primes = [sigs[i].a_prime for i in live]
        a_bars = [sigs[i].a_bar for i in live]
        sums = _weighted_sums(a_primes, a_bars, weights, seen)
        if not _balanced(*sums, ipk):
            # Rare path: at least one forged pairing.
            seen.update(combined_ok=False, isolated=len(live))
            for i, v in zip(live, _isolate(a_primes, a_bars, weights, sums,
                                           ipk, seen)):
                ok[i] = v
        seen["checks"] = 1 + seen["subset_checks"] + seen["item_checks"]
        seen["msm_ms"] = round(seen["msm_ms"], 3)
    if stats is not None:
        stats.update(seen)
    return ok


def _isolate(a_primes, a_bars, weights, sums, ipk, seen: dict) -> list[bool]:
    """The verdicts of n items whose weighted combined check has failed
    (`sums`: its two sides), each the one its own pairing check gives;
    counts what it spends into `seen`.

    Bisection over the weights the combined check drew.  A subset's
    check balances its sums of r_i*A'_i and r_i*Abar_i.  A subset that
    passes is sound as a whole, by the argument the combined check has
    always rested on (uniform 254-bit weights).  Of a subset that fails,
    the left half is checked: if it passes, the right half holds a
    forgery (the parent's product is the halves' product) and needs no
    check of its own; if it fails, the right half is checked too.  A
    lone item known to fail is refused without a check: r_i is non-zero
    in a group of prime order, so its weighted check fails exactly when
    its own does.  Nothing is sampled, and no half is accepted that was
    neither checked nor inferred.

    A left half's sums are one multi-scalar multiplication over its
    terms, the right half's the parent's less the left's: one forgery
    costs about n terms a side over the whole bisection (n/2 + n/4 +
    ...), and the sums of one level never more than n/2 a side.

    One forgery costs at most two checks a level, 2*ceil(log2 n).  So
    that a batch full of forgeries costs little more than a check an
    item, bisection stops once it has spent a quarter of n checks, and
    what is still undecided is checked item by item: at most n/4 + 1
    subset checks and n item checks.  Up to three items that is all
    there is to do."""
    n = len(a_primes)

    def item(j):
        seen["item_checks"] += 1
        return _balanced(a_primes[j], a_bars[j], ipk)

    if n <= 3:
        return [item(j) for j in range(n)]

    def subset(sides):
        seen["subset_checks"] += 1
        return _balanced(*sides, ipk)

    verdicts = [True] * n
    # (lo, hi, sums, forged): a range whose verdicts are owed, with its
    # two weighted sums; `forged` when it is known to hold a forgery
    owed = [(0, n, sums, True)]
    while owed:
        lo, hi, sides, forged = owed.pop()
        if hi - lo == 1:
            verdicts[lo] = not forged and item(lo)
        elif seen["subset_checks"] >= n // 4:
            for j in range(lo, hi):
                verdicts[j] = item(j)
        elif forged or not subset(sides):
            mid = (lo + hi) // 2
            left = _weighted_sums(a_primes[lo:mid], a_bars[lo:mid],
                                  weights[lo:mid], seen)
            right = [bn.g1_add(whole, bn.g1_neg(part))
                     for whole, part in zip(sides, left)]
            if subset(left):
                owed.append((mid, hi, right, True))
            else:
                owed += [(mid, hi, right, False), (lo, mid, left, True)]
    return verdicts


def challenge_matches(sig: Signature, ipk: IssuerPublicKey, msg: bytes,
                      commitments) -> bool:
    """The Fiat-Shamir re-hash over commitments (T1, T2, T3) computed
    elsewhere (the device): True when it gives the signature's
    challenge.  `commitments` None is a lane the device path refused."""
    if commitments is None:
        return False
    try:
        c = _challenge_bytes(
            ipk, list(commitments), sig.a_prime, sig.a_bar, sig.b_prime,
            sig.nym, sig.disclosure, sig.disclosed_attrs, msg, sig.nonce,
        )
        return c == sig.challenge
    except (ValueError, IndexError, KeyError, TypeError,
            OverflowError, AttributeError):
        return False


def verify_batch_device(
    sigs: list[Signature],
    ipk: IssuerPublicKey,
    msgs: list[bytes],
    rng=None,
) -> list[bool]:
    """verify_batch with the Schnorr commitment recomputation batched on
    the device (csp/tpu/bn254_batch.py — one program re-derives every
    signature's T1/T2/T3 G1 MSMs); challenge re-hash and the
    RLC-collapsed pairings stay on host.  A failure of the device path
    is the caller's to see: nothing here falls back to the host (the
    provider, csp/idemix_provider.py, does, and counts it)."""
    from fabric_tpu.csp.tpu import bn254_batch

    comms = bn254_batch.schnorr_commitments_batch(sigs, ipk)
    ok = [
        challenge_matches(sig, ipk, msg, tri)
        for sig, msg, tri in zip(sigs, msgs, comms)
    ]
    return _pairing_mask(sigs, ok, ipk, rng)
