"""The plain reference of the Idemix world: a serial validate-and-commit
with the peer's semantics and none of its code, over a BN254 of its own.

One transaction at a time.  The creator is an Idemix identity: its
association proof (an `idemix.Signature` disclosing OU and Role, bound
to the pseudonym) is verified by recomputing the three Schnorr
commitments, re-hashing the challenge, and checking
e(A', W) == e(Abar, g2) with two Miller loops and a final
exponentiation; the envelope's pseudonym signature by its one
commitment and re-hash.  Either failing is BAD_CREATOR_SIGNATURE
(upstream checkSignatureFromCreator).  Then, as
`reference/x509-majority.py` does: the endorsement by OpenSSL's ECDSA
verify under the X.509 organisation's CA certificate, the channel's
MAJORITY Endorsement policy by counting organisations (Idemix is for
clients: such an organisation endorses nothing and is not counted),
MVCC with a dict.

No random linear combination, no batching across signatures, no
issuer secret: the issuer's PUBLIC key comes from the world's `public`,
as a peer takes it from the channel configuration.  Independent
transactions may be spread over worker processes (this file run with
`--worker`, fed over a pipe); each still verifies one at a time.

Everything is plain Python integers: G1 in Jacobian coordinates, G2 on
the sextic twist over Fp2, the tower Fp2[v]/(v^3 - xi) = Fp6,
Fp6[w]/(w^2 - v) = Fp12 with xi = 9 + u, the optimal ate Miller loop
over 6u + 2 with the two Frobenius steps, the final exponentiation as
the easy part then a plain square-and-multiply by (p^4 - p^2 + 1)/r.
It imports of `fabric_tpu` the protobufs alone.

Departures from upstream Idemix (each also under `assumed` in
`configs/idemix-nym128.json`):

- the wire format is this repository's: `Signature` and `NymSignature`
  travel as JSON (points as 64-byte big-endian x || y in hex, scalars
  as decimal integers), the identity as `SerializedIdemixIdentity`;
  hash-to-Zr is SHA-256 over length-prefixed chunks, reduced mod r;
- the curve is BN254 (alt_bn128), where upstream's FP256BN is another
  256-bit Barreto-Naehrig curve of the same shape;
- no non-revocation proof (`ALG_NO_REVOCATION`, idemixgen's default):
  the RevocationHandle attribute is in the credential and stays hidden.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import subprocess
import sys

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.ledger.rwset import rwset_pb2
from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
from fabric_tpu.protos.msp import identities_pb2
from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

_P256_HALF_ORDER = (
    0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551 >> 1
)

# ---------------------------------------------------------------------------
# BN254 (alt_bn128): y^2 = x^3 + 3 over Fp, group order r, BN parameter u
# ---------------------------------------------------------------------------

U = 4965661367192848881
P = 36 * U**4 + 36 * U**3 + 24 * U**2 + 6 * U + 1
R = 36 * U**4 + 36 * U**3 + 18 * U**2 + 6 * U + 1
G1 = (1, 2)
G2 = (
    (10857046999023057135944570762232829481370756359578518086990519993285655852781,
     11559732032986387107991004021392285783925812861821192530917403151452391805634),
    (8495653923123431417604973247489272438418190587263600148770280649306958101930,
     4082367875863433681332203403145435568316851327593401208105741076214120093531),
)

# -- G1, Jacobian (X, Y, Z), None is the point at infinity ------------------


def g1_on_curve(pt) -> bool:
    x, y = pt
    return 0 <= x < P and 0 <= y < P and (y * y - x * x * x - 3) % P == 0


def _jac_double(p):
    if p is None:
        return None
    x, y, z = p
    if y == 0:
        return None
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) * (x + b) - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    y3 = (e * (d - x3) - 8 * c) % P
    z3 = 2 * y * z % P
    return x3, y3, z3


def _jac_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1 = z1 * z1 % P
    z2z2 = z2 * z2 % P
    u1 = x1 * z2z2 % P
    u2 = x2 * z1z1 % P
    s1 = y1 * z2 * z2z2 % P
    s2 = y2 * z1 * z1z1 % P
    if u1 == u2:
        return _jac_double(p) if s1 == s2 else None
    h = (u2 - u1) % P
    r = (s2 - s1) % P
    hh = h * h % P
    hhh = h * hh % P
    v = u1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    y3 = (r * (v - x3) - s1 * hhh) % P
    z3 = z1 * z2 * h % P
    return x3, y3, z3


def _to_affine(p):
    if p is None:
        return None
    x, y, z = p
    zi = pow(z, -1, P)
    zi2 = zi * zi % P
    return x * zi2 % P, y * zi2 * zi % P


def g1_neg(pt):
    return None if pt is None else (pt[0], (-pt[1]) % P)


def g1_add(p, q):
    """Affine in, affine out (None is infinity)."""
    return _to_affine(_jac_add(
        None if p is None else (p[0], p[1], 1),
        None if q is None else (q[0], q[1], 1),
    ))


def g1_product(terms):
    """sum of k_i * P_i for [(P_i affine, k_i)], affine out: one run of
    doublings shared by all terms, an addition where a bit is set."""
    terms = [((p[0], p[1], 1), k % R) for p, k in terms if p is not None and k % R]
    acc = None
    for bit in range(max((k.bit_length() for _p, k in terms), default=0) - 1, -1, -1):
        acc = _jac_double(acc)
        for p, k in terms:
            if (k >> bit) & 1:
                acc = _jac_add(acc, p)
    return _to_affine(acc)


# -- Fp2 = Fp[u]/(u^2 + 1): (a, b) is a + b u -------------------------------


def f2_add(x, y):
    return (x[0] + y[0]) % P, (x[1] + y[1]) % P


def f2_sub(x, y):
    return (x[0] - y[0]) % P, (x[1] - y[1]) % P


def f2_neg(x):
    return (-x[0]) % P, (-x[1]) % P


def f2_mul(x, y):
    a = x[0] * y[0]
    b = x[1] * y[1]
    return (a - b) % P, ((x[0] + x[1]) * (y[0] + y[1]) - a - b) % P


def f2_sqr(x):
    return (x[0] + x[1]) * (x[0] - x[1]) % P, 2 * x[0] * x[1] % P


def f2_scale(x, k: int):
    return x[0] * k % P, x[1] * k % P


def f2_conj(x):
    return x[0], (-x[1]) % P


def f2_inv(x):
    n = pow(x[0] * x[0] + x[1] * x[1], -1, P)
    return x[0] * n % P, (-x[1]) * n % P


def f2_xi(x):
    """x * (9 + u)."""
    return (9 * x[0] - x[1]) % P, (x[0] + 9 * x[1]) % P


def f2_pow(x, e: int):
    out = (1, 0)
    while e:
        if e & 1:
            out = f2_mul(out, x)
        x = f2_sqr(x)
        e >>= 1
    return out


F2_ZERO = (0, 0)
F2_ONE = (1, 0)
XI = (9, 1)

# -- Fp6 = Fp2[v]/(v^3 - xi): (c0, c1, c2) ----------------------------------

F6_ZERO = (F2_ZERO, F2_ZERO, F2_ZERO)
F6_ONE = (F2_ONE, F2_ZERO, F2_ZERO)


def f6_add(x, y):
    return f2_add(x[0], y[0]), f2_add(x[1], y[1]), f2_add(x[2], y[2])


def f6_sub(x, y):
    return f2_sub(x[0], y[0]), f2_sub(x[1], y[1]), f2_sub(x[2], y[2])


def f6_neg(x):
    return f2_neg(x[0]), f2_neg(x[1]), f2_neg(x[2])


def f6_mul(x, y):
    t0 = f2_mul(x[0], y[0])
    t1 = f2_mul(x[1], y[1])
    t2 = f2_mul(x[2], y[2])
    c0 = f2_add(t0, f2_xi(f2_sub(
        f2_mul(f2_add(x[1], x[2]), f2_add(y[1], y[2])), f2_add(t1, t2))))
    c1 = f2_add(f2_sub(
        f2_mul(f2_add(x[0], x[1]), f2_add(y[0], y[1])), f2_add(t0, t1)), f2_xi(t2))
    c2 = f2_add(f2_sub(
        f2_mul(f2_add(x[0], x[2]), f2_add(y[0], y[2])), f2_add(t0, t2)), t1)
    return c0, c1, c2


def f6_mul_v(x):
    """x * v."""
    return f2_xi(x[2]), x[0], x[1]


def f6_inv(x):
    c0, c1, c2 = x
    a = f2_sub(f2_sqr(c0), f2_xi(f2_mul(c1, c2)))
    b = f2_sub(f2_xi(f2_sqr(c2)), f2_mul(c0, c1))
    c = f2_sub(f2_sqr(c1), f2_mul(c0, c2))
    f = f2_add(f2_mul(c0, a), f2_xi(f2_add(f2_mul(c2, b), f2_mul(c1, c))))
    fi = f2_inv(f)
    return f2_mul(a, fi), f2_mul(b, fi), f2_mul(c, fi)


# -- Fp12 = Fp6[w]/(w^2 - v): (d0, d1) --------------------------------------

F12_ONE = (F6_ONE, F6_ZERO)


def f12_mul(x, y):
    t0 = f6_mul(x[0], y[0])
    t1 = f6_mul(x[1], y[1])
    return (
        f6_add(t0, f6_mul_v(t1)),
        f6_sub(f6_mul(f6_add(x[0], x[1]), f6_add(y[0], y[1])), f6_add(t0, t1)),
    )


def f12_sqr(x):
    return f12_mul(x, x)


def f12_conj(x):
    """x^(p^6)."""
    return x[0], f6_neg(x[1])


def f12_inv(x):
    t = f6_inv(f6_sub(f6_mul(x[0], x[0]), f6_mul_v(f6_mul(x[1], x[1]))))
    return f6_mul(x[0], t), f6_neg(f6_mul(x[1], t))


def f12_pow(x, e: int):
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_sqr(out)
        if bit == "1":
            out = f12_mul(out, x)
    return out


# x = sum g_i w^i with g_i in Fp2 and w^6 = xi; in the tower
# d0 = (g0, g2, g4), d1 = (g1, g3, g5).  x^p = sum conj(g_i) gamma_i w^i
# with gamma_i = xi^(i (p - 1) / 6).
_GAMMA = [f2_pow(XI, i * (P - 1) // 6) for i in range(6)]


def f12_frobenius(x):
    (g0, g2, g4), (g1, g3, g5) = x
    g = [f2_mul(f2_conj(c), _GAMMA[i]) for i, c in enumerate((g0, g1, g2, g3, g4, g5))]
    return (g[0], g[2], g[4]), (g[1], g[3], g[5])


# -- G2 on the twist E': y^2 = x^3 + 3/xi over Fp2, affine ------------------

_TWIST_B = f2_mul((3, 0), f2_inv(XI))


def g2_on_curve(q) -> bool:
    x, y = q
    return f2_sub(f2_sqr(y), f2_add(f2_mul(f2_sqr(x), x), _TWIST_B)) == F2_ZERO


# A point (x', y') of the twist is (x' w^2, y' w^3) on E over Fp12.  The
# line through two such points with twist slope m, evaluated at P =
# (xp, yp) of G1, is  yp - m xp w + (m x' - y') w^3.


def _line(m, t, p):
    xp, yp = p
    return (
        ((yp, 0), F2_ZERO, F2_ZERO),
        (f2_scale(f2_neg(m), xp), f2_sub(f2_mul(m, t[0]), t[1]), F2_ZERO),
    )


def _step_double(t, p):
    """(2T, the tangent at T evaluated at P)."""
    m = f2_mul(f2_scale(f2_sqr(t[0]), 3), f2_inv(f2_scale(t[1], 2)))
    x3 = f2_sub(f2_sqr(m), f2_scale(t[0], 2))
    y3 = f2_sub(f2_mul(m, f2_sub(t[0], x3)), t[1])
    return (x3, y3), _line(m, t, p)


def _step_add(t, q, p):
    """(T + Q, the chord through T and Q evaluated at P); T != +-Q."""
    m = f2_mul(f2_sub(q[1], t[1]), f2_inv(f2_sub(q[0], t[0])))
    x3 = f2_sub(f2_sub(f2_sqr(m), t[0]), q[0])
    y3 = f2_sub(f2_mul(m, f2_sub(t[0], x3)), t[1])
    return (x3, y3), _line(m, t, p)


_ATE = 6 * U + 2
_FROB_X = f2_pow(XI, (P - 1) // 3)
_FROB_Y = f2_pow(XI, (P - 1) // 2)
_FROB2_X = f2_pow(XI, (P * P - 1) // 3)
_FROB2_Y = f2_pow(XI, (P * P - 1) // 2)


def miller_loop(p, q):
    """The optimal ate Miller function f_{6u+2,Q}(P) with its two
    Frobenius lines; p in G1 (affine), q in G2 on the twist (affine)."""
    f = F12_ONE
    t = q
    for bit in bin(_ATE)[3:]:
        t, line = _step_double(t, p)
        f = f12_mul(f12_sqr(f), line)
        if bit == "1":
            t, line = _step_add(t, q, p)
            f = f12_mul(f, line)
    q1 = (f2_mul(f2_conj(q[0]), _FROB_X), f2_mul(f2_conj(q[1]), _FROB_Y))
    minus_q2 = (f2_mul(q[0], _FROB2_X), f2_neg(f2_mul(q[1], _FROB2_Y)))
    t, line = _step_add(t, q1, p)
    f = f12_mul(f, line)
    _t, line = _step_add(t, minus_q2, p)
    return f12_mul(f, line)


_HARD = (P**4 - P**2 + 1) // R


def final_exponentiation(f):
    f = f12_mul(f12_conj(f), f12_inv(f))                    # ^(p^6 - 1)
    f = f12_mul(f12_frobenius(f12_frobenius(f)), f)         # ^(p^2 + 1)
    return f12_pow(f, _HARD)


def pairing(p, q):
    """e(P, Q) for P in G1, Q in G2 (neither at infinity)."""
    return final_exponentiation(miller_loop(p, q))


def pairings_multiply_to_one(pairs) -> bool:
    """prod e(P_i, Q_i) == 1: the Miller functions multiplied, one
    final exponentiation."""
    f = F12_ONE
    for p, q in pairs:
        f = f12_mul(f, miller_loop(p, q))
    return final_exponentiation(f) == F12_ONE


# ---------------------------------------------------------------------------
# The wire format's hashing and encodings
# ---------------------------------------------------------------------------


def hash_to_zr(*chunks: bytes) -> int:
    h = hashlib.sha256()
    for c in chunks:
        h.update(len(c).to_bytes(8, "big"))
        h.update(c)
    return int.from_bytes(h.digest(), "big") % R


def g1_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * 64
    return pt[0].to_bytes(32, "big") + pt[1].to_bytes(32, "big")


def g1_from_hex(text: str):
    """A point of G1 as the wire carries it; ValueError if it is not
    canonical or not on the curve.  (The cofactor is 1.)"""
    raw = bytes.fromhex(text)
    if len(raw) != 64:
        raise ValueError("G1 encoding length")
    if raw == b"\x00" * 64:
        return None
    pt = (int.from_bytes(raw[:32], "big"), int.from_bytes(raw[32:], "big"))
    if not g1_on_curve(pt):
        raise ValueError("not a point of G1")
    return pt


def attribute_scalar(value) -> int:
    if isinstance(value, int):
        return value % R
    if isinstance(value, str):
        value = value.encode()
    return hash_to_zr(b"idemix-attr", value)


class IssuerPublicKey:
    """What the channel configuration publishes of the issuer."""

    def __init__(self, d: dict):
        self.attr_names = list(d["attr_names"])
        self.h_sk = g1_from_hex(d["h_sk"])
        self.h_rand = g1_from_hex(d["h_rand"])
        self.h_attrs = [g1_from_hex(h) for h in d["h_attrs"]]
        w = bytes.fromhex(d["w"])
        c = [int.from_bytes(w[i:i + 32], "big") for i in range(0, 128, 32)]
        self.w = ((c[0], c[1]), (c[2], c[3]))
        if not g2_on_curve(self.w):
            raise ValueError("issuer public key: W is not on the twist")
        # the fingerprint every challenge binds: SHA-256 over the key's
        # elements as they are serialized
        self.fingerprint = hashlib.sha256(b"".join([
            bytes.fromhex(d["h_sk"]), bytes.fromhex(d["h_rand"]),
            *[bytes.fromhex(h) for h in d["h_attrs"]], w,
            bytes.fromhex(d["bar_g1"]), bytes.fromhex(d["bar_g2"]),
            json.dumps(self.attr_names).encode(),
        ])).digest()


# ---------------------------------------------------------------------------
# One anonymous creator: association proof and pseudonym signature
# ---------------------------------------------------------------------------

DISCLOSED = [True, True, False, False]      # OU, Role | EnrollmentID, RevocationHandle


def association_proof_verifies(ipk: IssuerPublicKey, nym, ou: str, role: int,
                               proof_json: bytes) -> bool:
    """idemix.Signature.Ver over the empty message, and what
    msp/idemixmsp.go Validate asks of it: it discloses exactly OU and
    Role, with the claimed values, and is bound to the pseudonym."""
    try:
        d = json.loads(proof_json)
        a_prime = g1_from_hex(d["a_prime"])
        a_bar = g1_from_hex(d["a_bar"])
        b_prime = g1_from_hex(d["b_prime"])
        proof_nym = g1_from_hex(d["nym"])
        c = int(d["challenge"])
        z = {k: int(v) for k, v in d["responses"].items()}
        disclosure = [bool(b) for b in d["disclosure"]]
        disclosed = {int(k): int(v) for k, v in d["disclosed_attrs"].items()}
        nonce = bytes.fromhex(d["nonce"])
        if None in (a_prime, a_bar, b_prime, proof_nym):
            return False
        if disclosure != DISCLOSED or proof_nym != nym:
            return False
        if disclosed.get(0) != attribute_scalar(ou) or disclosed.get(1) != attribute_scalar(role):
            return False
        hidden = [i for i, shown in enumerate(disclosure) if not shown]
        # relation 1: Abar / B' = A'^(-e) * h_rand^(r2)
        y1 = g1_add(a_bar, g1_neg(b_prime))
        t1 = g1_product([(a_prime, z["neg_e"]), (ipk.h_rand, z["r2"]), (y1, -c)])
        # relation 2: g1^-1 * prod_D h_i^(-m_i)
        #   = h_sk^sk * h_rand^s' * prod_H h_i^(m_i) * B'^(-r3)
        y2 = g1_product([(G1, -1)] + [(ipk.h_attrs[i], -disclosed[i])
                                      for i, shown in enumerate(disclosure) if shown])
        t2 = g1_product(
            [(ipk.h_sk, z["sk"]), (ipk.h_rand, z["sprime"])]
            + [(ipk.h_attrs[i], z[f"m_{i}"]) for i in hidden]
            + [(b_prime, z["neg_r3"]), (y2, -c)])
        # relation 3: Nym = h_sk^sk * h_rand^(r_nym)
        t3 = g1_product([(ipk.h_sk, z["sk"]), (ipk.h_rand, z["r_nym"]), (nym, -c)])
        want = hash_to_zr(
            b"idemix-signature", g1_bytes(t1), g1_bytes(t2), g1_bytes(t3),
            g1_bytes(a_prime), g1_bytes(a_bar), g1_bytes(b_prime), g1_bytes(nym),
            ipk.fingerprint, bytes(disclosure),
            b"".join(i.to_bytes(4, "big") + v.to_bytes(32, "big")
                     for i, v in sorted(disclosed.items())),
            b"", nonce,
        )
        if want != c:
            return False
    except (ValueError, KeyError, TypeError, IndexError, OverflowError, AttributeError):
        return False
    # e(A', W) == e(Abar, g2)
    return pairings_multiply_to_one([(a_prime, ipk.w), (g1_neg(a_bar), G2)])


def pseudonym_signature_verifies(ipk: IssuerPublicKey, nym, message: bytes,
                                 signature: bytes) -> bool:
    """idemix.NymSignature.Ver: three scalar multiplications, no pairing."""
    try:
        d = json.loads(signature)
        c, z_sk, z_rnym = int(d["c"]), int(d["z_sk"]), int(d["z_rnym"])
    except (ValueError, KeyError, TypeError):
        return False
    t = g1_product([(ipk.h_sk, z_sk), (ipk.h_rand, z_rnym), (nym, -c)])
    return c == hash_to_zr(b"idemix-nym-signature", g1_bytes(t), g1_bytes(nym),
                           ipk.fingerprint, message)


def creator_verifies(ipk: IssuerPublicKey, creator: dict) -> bool:
    """One anonymous creator, as the parent process decoded it."""
    nym = (int.from_bytes(creator["nym_x"], "big"), int.from_bytes(creator["nym_y"], "big"))
    if not g1_on_curve(nym):
        return False
    try:
        ou = creator["ou"].decode()
    except UnicodeDecodeError:
        return False
    role = int.from_bytes(creator["role"], "big")
    return (
        association_proof_verifies(ipk, nym, ou, role, creator["proof"])
        and pseudonym_signature_verifies(ipk, nym, creator["payload"], creator["signature"])
    )


# ---------------------------------------------------------------------------
# Workers: the same function over a share of the transactions
# ---------------------------------------------------------------------------

_MIN_FOR_WORKERS = 16
_MAX_WORKERS = 12


def _creators_verify(issuers: dict, creators: list) -> list:
    """A verdict for each (mspid, creator); independent, so spread over
    worker processes where there are enough of them to pay."""
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        cpus = os.cpu_count() or 1
    n = min(_MAX_WORKERS, cpus, len(creators) // (_MIN_FOR_WORKERS // 2))
    if n < 2:
        return _verify_share(issuers, creators)
    shares = [creators[k::n] for k in range(n)]
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    procs = []
    for share in shares:
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
        )
        procs.append(p)
    # feed every worker before reading any: they all compute at once
    for p, share in zip(procs, shares):
        p.stdin.write(pickle.dumps((issuers, share)))
        p.stdin.close()
    out = [False] * len(creators)
    for k, p in enumerate(procs):
        verdicts = pickle.loads(p.stdout.read())
        if p.wait() != 0 or len(verdicts) != len(shares[k]):
            raise RuntimeError(f"reference worker {k} failed (exit {p.returncode})")
        out[k::n] = verdicts
    return out


def _verify_share(issuers: dict, creators: list) -> list:
    keys = {mspid: IssuerPublicKey(d) for mspid, d in issuers.items()}
    return [
        mspid in keys and creator_verifies(keys[mspid], creator)
        for mspid, creator in creators
    ]


def _worker_main() -> int:
    issuers, share = pickle.loads(sys.stdin.buffer.read())
    sys.stdout.buffer.write(pickle.dumps(_verify_share(issuers, share)))
    return 0


# ---------------------------------------------------------------------------
# The serial validator and state
# ---------------------------------------------------------------------------


class Reference:
    def __init__(self, public: dict, n_orgs: int):
        self._cas = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in public["ca_certs_pem"].items()
        }
        self._issuers = public["idemix_issuers"]
        self._need = n_orgs // 2 + 1          # MAJORITY of the endorsing (X.509) orgs
        self._idents: dict = {}
        self.state: dict = {}

    def _x509_identity(self, serialized: bytes):
        hit = self._idents.get(serialized, False)
        if hit is not False:
            return hit
        out = None
        try:
            sid = identities_pb2.SerializedIdentity.FromString(serialized)
            ca = self._cas.get(sid.mspid)
            if ca is not None:
                cert = x509.load_pem_x509_certificate(sid.id_bytes)
                ca.public_key().verify(
                    cert.signature, cert.tbs_certificate_bytes,
                    ec.ECDSA(cert.signature_hash_algorithm),
                )
                ous = {
                    a.value for a in cert.subject
                    if a.oid == x509.NameOID.ORGANIZATIONAL_UNIT_NAME
                }
                out = (sid.mspid, cert.public_key(), ous)
        except (ValueError, InvalidSignature):
            out = None
        self._idents[serialized] = out
        return out

    @staticmethod
    def _ecdsa_verifies(key, signature: bytes, message: bytes) -> bool:
        try:
            _r, s = decode_dss_signature(signature)
            if s > _P256_HALF_ORDER:      # Fabric accepts low-S only
                return False
            key.verify(signature, message, ec.ECDSA(hashes.SHA256()))
            return True
        except (ValueError, InvalidSignature):
            return False

    @staticmethod
    def _creator_of(env_bytes: bytes):
        """(mspid, what a worker needs of the creator) of an envelope,
        or None where the creator is not an Idemix identity."""
        env = common_pb2.Envelope.FromString(env_bytes)
        payload = common_pb2.Payload.FromString(env.payload)
        shdr = common_pb2.SignatureHeader.FromString(payload.header.signature_header)
        try:
            sid = identities_pb2.SerializedIdentity.FromString(shdr.creator)
            sii = identities_pb2.SerializedIdemixIdentity.FromString(sid.id_bytes)
        except Exception:
            return None
        return sid.mspid, {
            "nym_x": sii.nym_x, "nym_y": sii.nym_y, "ou": sii.ou, "role": sii.role,
            "proof": sii.proof, "payload": env.payload, "signature": env.signature,
        }

    def _after_creator(self, env_bytes: bytes):
        """(flag, reads, writes) of an envelope whose creator stood."""
        env = common_pb2.Envelope.FromString(env_bytes)
        payload = common_pb2.Payload.FromString(env.payload)
        tx = transaction_pb2.Transaction.FromString(payload.data)
        cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
        prp = cap.action.proposal_response_payload
        orgs = set()
        for e in cap.action.endorsements:
            ident = self._x509_identity(e.endorser)
            if ident is None or "peer" not in ident[2]:
                continue
            if self._ecdsa_verifies(ident[1], e.signature, prp + e.endorser):
                orgs.add(ident[0])
        if len(orgs) < self._need:
            return ENDORSEMENT_POLICY_FAILURE, (), ()
        action = proposal_pb2.ChaincodeAction.FromString(
            proposal_response_pb2.ProposalResponsePayload.FromString(prp).extension
        )
        reads, writes = [], []
        for ns in rwset_pb2.TxReadWriteSet.FromString(action.results).ns_rwset:
            kv = kv_rwset_pb2.KVRWSet.FromString(ns.rwset)
            for r in kv.reads:
                ver = (
                    (r.version.block_num, r.version.tx_num)
                    if r.HasField("version") else None
                )
                reads.append(((ns.namespace, r.key), ver))
            for w in kv.writes:
                writes.append(((ns.namespace, w.key), None if w.is_delete else w.value))
        return VALID, reads, writes

    def apply_block(self, block_bytes: bytes, creator_ok: list) -> list:
        block = common_pb2.Block.FromString(block_bytes)
        num = block.header.number
        flags = []
        for i, env_bytes in enumerate(block.data.data):
            if not creator_ok[i]:
                flags.append(BAD_CREATOR_SIGNATURE)
                continue
            flag, reads, writes = self._after_creator(env_bytes)
            if flag == VALID:
                for key, ver in reads:
                    have = self.state.get(key)
                    if (have[1] if have else None) != ver:
                        flag = MVCC_READ_CONFLICT
                        break
            if flag == VALID:
                for key, value in writes:
                    if value is None:
                        self.state.pop(key, None)
                    else:
                        self.state[key] = (value, (num, i))
            flags.append(flag)
        return flags


def run(public: dict, deployment: dict, blocks: list):
    """(per-block flags, the state after each block) of a fresh chain."""
    ref = Reference(public, int(deployment["orgs"]))
    # every creator's verdict first (they depend on nothing but the
    # envelope), then the chain in order
    where, creators = [], []
    for bno, b in enumerate(blocks):
        for i, env_bytes in enumerate(common_pb2.Block.FromString(b).data.data):
            got = ref._creator_of(env_bytes)
            if got is not None:
                where.append((bno, i))
                creators.append(got)
    verdicts = _creators_verify(ref._issuers, creators)
    ok = [[False] * len(common_pb2.Block.FromString(b).data.data) for b in blocks]
    for (bno, i), v in zip(where, verdicts):
        ok[bno][i] = bool(v)
    flags, states = [], []
    for bno, b in enumerate(blocks):
        flags.append(ref.apply_block(b, ok[bno]))
        states.append(dict(ref.state))
    return flags, states


if __name__ == "__main__":
    sys.exit(_worker_main() if sys.argv[1:] == ["--worker"] else 2)
