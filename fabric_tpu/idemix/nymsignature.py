"""Pseudonym signatures (reference idemix/nymsignature.go).

A nym signature proves knowledge of (sk, r_nym) with
Nym = HSk^sk * HRand^r_nym over a message — no credential, no pairing
(the reference's NymSignature.Ver at nymsignature.go:74 is three scalar
multiplications).  Used by the idemix MSP for per-transaction signing once
the session pseudonym is established.
"""

from __future__ import annotations

import dataclasses

from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix.issuer import IssuerPublicKey


@dataclasses.dataclass
class NymSignature:
    challenge: int
    z_sk: int
    z_rnym: int


def new_nym_signature(
    sk: int,
    nym: tuple,
    r_nym: int,
    ipk: IssuerPublicKey,
    msg: bytes,
    rng=None,
) -> NymSignature:
    rho_sk = bn.rand_zr(rng)
    rho_r = bn.rand_zr(rng)
    t = bn.g1_add(bn.g1_mul(ipk.h_sk, rho_sk), bn.g1_mul(ipk.h_rand, rho_r))
    c = bn.hash_to_zr(
        b"idemix-nym-signature",
        bn.g1_to_bytes(t),
        bn.g1_to_bytes(nym),
        ipk.hash(),
        msg,
    )
    return NymSignature(
        challenge=c,
        z_sk=(rho_sk + c * sk) % bn.R,
        z_rnym=(rho_r + c * r_nym) % bn.R,
    )


def challenge_matches(
    sig: NymSignature, nym: tuple, ipk: IssuerPublicKey, msg: bytes, t
) -> bool:
    """The Fiat-Shamir re-hash over the commitment `t` =
    h_sk^z_sk * h_rand^z_rnym * nym^-c, computed here or on the device
    (None = the point at infinity; False = a lane refused)."""
    if t is False:
        return False
    c = bn.hash_to_zr(
        b"idemix-nym-signature",
        bn.g1_to_bytes(t),
        bn.g1_to_bytes(nym),
        ipk.hash(),
        msg,
    )
    return c == sig.challenge


def verify_nym(
    sig: NymSignature, nym: tuple, ipk: IssuerPublicKey, msg: bytes
) -> bool:
    if nym is None or not bn.g1_is_on_curve(nym):
        return False
    t = bn.g1_add(
        bn.g1_add(
            bn.g1_mul(ipk.h_sk, sig.z_sk),
            bn.g1_mul(ipk.h_rand, sig.z_rnym),
        ),
        bn.g1_mul(nym, (-sig.challenge) % bn.R),
    )
    return challenge_matches(sig, nym, ipk, msg, t)
