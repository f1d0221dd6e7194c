"""Batched ECDSA-P256 verification on TPU.

Replaces the reference's per-signature `ecdsa.Verify` hot loop
(bccsp/sw/ecdsa.go:41-57, fanned out per tx/endorsement by
core/committer/txvalidator/v20/validator.go goroutines) with one jitted XLA
program that verifies an entire block's signatures at once — the
"embarrassingly batchable" rework called out in SURVEY.md §3.4.

TPU-first design:

* All signatures in the batch advance in lockstep through a fixed
  64-window (4-bit) joint Shamir ladder ``R = u1*G + u2*Q``: a
  `lax.scan` over windows, `lax.fori_loop` over the 4 doublings —
  static shapes, no data-dependent branching, pure VPU work on the
  limb representation from `limbs.py`.
* Exception/degenerate cases (point at infinity, equal/opposite addends)
  are handled with per-lane boolean flags + `jnp.where` selects, never
  host branches, so one adversarial signature cannot desynchronize the
  batch (SURVEY.md §7 hard part (4): per-signature failure semantics).
* The final affine check avoids modular inversion entirely: instead of
  x(R) = X/Z^2 mod p == r mod n, it checks X == c*Z^2 (mod p) for each
  admissible candidate c in {r, r+n} (r+n only when < p).
* Host does only O(1)-per-item scalar work: DER parse, range/low-S
  checks, u1/u2 = e*s^-1, r*s^-1 mod n, and window-digit recoding.

Parity oracle: fabric_tpu.csp.sw (OpenSSL), tested on NIST/Wycheproof-style
edge cases in tests/test_ec.py / tests/test_csp_tpu.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from fabric_tpu.csp.api import P256_GX, P256_GY, P256_N, P256_P
from fabric_tpu.csp.tpu import limbs, named_jit
from fabric_tpu.csp.tpu.limbs import WIDE, ints_to_limbs, mod_ctx

WINDOW_BITS = 4
NWINDOWS = 64  # 256 / 4
TABLE = 1 << WINDOW_BITS


# ---------------------------------------------------------------------------
# Host-side affine P-256 (python ints) — used only to precompute the fixed
# G window table and in tests as a reference; never on the hot path.
# ---------------------------------------------------------------------------


def affine_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P256_P == 0:
            return None
        lam = (3 * x1 * x1 - 3) * pow(2 * y1, -1, P256_P) % P256_P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P256_P) % P256_P
    x3 = (lam * lam - x1 - x2) % P256_P
    y3 = (lam * (x1 - x3) - y1) % P256_P
    return (x3, y3)


def affine_mul(k: int, p):
    acc = None
    while k:
        if k & 1:
            acc = affine_add(acc, p)
        p = affine_add(p, p)
        k >>= 1
    return acc


@functools.lru_cache(maxsize=None)
def g_table() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Affine multiples 0..15 of the base point; index 0 is infinity."""
    xs, ys, inf = [], [], []
    for i in range(TABLE):
        pt = affine_mul(i, (P256_GX, P256_GY))
        if pt is None:
            xs.append(0)
            ys.append(0)
            inf.append(True)
        else:
            xs.append(pt[0])
            ys.append(pt[1])
            inf.append(False)
    return (
        np.asarray(ints_to_limbs(xs)),
        np.asarray(ints_to_limbs(ys)),
        np.asarray(inf),
    )


# ---------------------------------------------------------------------------
# Jacobian point ops (batched, flag-carried infinity).
# ---------------------------------------------------------------------------


class Jac(NamedTuple):
    """Batched Jacobian point: limb arrays (..., 17) + infinity flag (...)."""

    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    inf: jnp.ndarray


class Aff(NamedTuple):
    """Batched affine point (for table entries); inf marks identity."""

    x: jnp.ndarray
    y: jnp.ndarray
    inf: jnp.ndarray


def _sel(c, a, b):
    """Lane select: c (...,) bool picks a (...,K) else b."""
    return jnp.where(c[..., None], a, b)


def _sel_pt(c, a: Jac, b: Jac) -> Jac:
    return Jac(
        _sel(c, a.x, b.x), _sel(c, a.y, b.y), _sel(c, a.z, b.z), jnp.where(c, a.inf, b.inf)
    )


def point_dbl(fp: limbs.Mod, p: Jac) -> Jac:
    """dbl-2001-b for a = -3 (3M + 5S).  Doubling infinity stays infinity via
    the flag; P-256 has odd order so no finite point doubles to infinity."""
    delta = fp.sqr(p.z)
    gamma = fp.sqr(p.y)
    beta = fp.mul(p.x, gamma)
    alpha = fp.mul_const(fp.mul(fp.sub(p.x, delta), fp.add(p.x, delta)), 3)
    x3 = fp.sub(fp.sqr(alpha), fp.mul_const(beta, 8))
    z3 = fp.sub(fp.sub(fp.sqr(fp.add(p.y, p.z)), gamma), delta)
    y3 = fp.sub(
        fp.mul(alpha, fp.sub(fp.mul_const(beta, 4), x3)),
        fp.mul_const(fp.sqr(gamma), 8),
    )
    return Jac(x3, y3, z3, p.inf)


def point_add(fp: limbs.Mod, p1: Jac, p2: Jac, dbl=None) -> Jac:
    """add-2007-bl (11M + 5S) with full degenerate handling: equal inputs
    fall back to doubling, opposite inputs yield infinity, identity inputs
    pass the other operand through."""
    z1z1 = fp.sqr(p1.z)
    z2z2 = fp.sqr(p2.z)
    u1 = fp.mul(p1.x, z2z2)
    u2 = fp.mul(p2.x, z1z1)
    s1 = fp.mul(fp.mul(p1.y, p2.z), z2z2)
    s2 = fp.mul(fp.mul(p2.y, p1.z), z1z1)
    h = fp.sub(u2, u1)
    rr = fp.sub(s2, s1)
    h_zero = fp.is_zero(h)
    r_zero = fp.is_zero(rr)
    i = fp.sqr(fp.add(h, h))
    j = fp.mul(h, i)
    rr2 = fp.add(rr, rr)
    v = fp.mul(u1, i)
    x3 = fp.sub(fp.sub(fp.sqr(rr2), j), fp.add(v, v))
    t = fp.mul(s1, j)
    y3 = fp.sub(fp.mul(rr2, fp.sub(v, x3)), fp.add(t, t))
    z3 = fp.mul(fp.sub(fp.sub(fp.sqr(fp.add(p1.z, p2.z)), z1z1), z2z2), h)
    out = Jac(x3, y3, z3, jnp.zeros_like(p1.inf))
    out = _sel_pt(h_zero & r_zero, (dbl or point_dbl)(fp, p1), out)  # P1 == P2
    out = Jac(out.x, out.y, out.z, out.inf | (h_zero & ~r_zero))  # P1 == -P2
    out = _sel_pt(p2.inf, p1, out)
    out = _sel_pt(p1.inf, p2, out)
    return out


def point_add_mixed(fp: limbs.Mod, p1: Jac, a2: Aff, dbl=None) -> Jac:
    """madd-2007-bl (7M + 4S), second operand affine (Z2 = 1)."""
    z1z1 = fp.sqr(p1.z)
    u2 = fp.mul(a2.x, z1z1)
    s2 = fp.mul(fp.mul(a2.y, p1.z), z1z1)
    h = fp.sub(u2, p1.x)
    rr = fp.sub(s2, p1.y)
    h_zero = fp.is_zero(h)
    r_zero = fp.is_zero(rr)
    hh = fp.sqr(h)
    i = fp.mul_const(hh, 4)
    j = fp.mul(h, i)
    rr2 = fp.add(rr, rr)
    v = fp.mul(p1.x, i)
    x3 = fp.sub(fp.sub(fp.sqr(rr2), j), fp.add(v, v))
    t = fp.mul(p1.y, j)
    y3 = fp.sub(fp.mul(rr2, fp.sub(v, x3)), fp.add(t, t))
    z3 = fp.sub(fp.sub(fp.sqr(fp.add(p1.z, h)), z1z1), hh)
    out = Jac(x3, y3, z3, jnp.zeros_like(p1.inf))
    out = _sel_pt(h_zero & r_zero, (dbl or point_dbl)(fp, p1), out)
    out = Jac(out.x, out.y, out.z, out.inf | (h_zero & ~r_zero))
    a2j = Jac(a2.x, a2.y, fp.one_like(a2.x), a2.inf)
    out = _sel_pt(a2.inf, p1, out)
    out = _sel_pt(p1.inf, a2j, out)
    return out


# ---------------------------------------------------------------------------
# The batched verify kernel.
# ---------------------------------------------------------------------------


def _q_window_table(fp: limbs.Mod, qx, qy):
    """Jacobian multiples 0..15 of each Q: (B, 16, 17) coordinate stacks.
    Built with 14 mixed adds; index 0 is infinity."""
    b = qx.shape[:-1]
    zero = jnp.zeros(b + (WIDE,), jnp.uint32)
    inf_t = jnp.ones(b, bool)
    fin = jnp.zeros(b, bool)
    q_aff = Aff(qx, qy, fin)
    q1 = Jac(qx, qy, fp.one_like(qx), fin)

    def step(p: Jac, _):
        nxt = point_add_mixed(fp, p, q_aff)
        return nxt, nxt

    # scan the add chain (2Q .. 15Q) so the graph holds ONE mixed add
    _, rest = jax.lax.scan(step, q1, None, length=TABLE - 2)
    # rest leaves: (TABLE-2, B, ...) -> move table axis next to batch
    cat = lambda z, o, r: jnp.concatenate(  # noqa: E731
        [z[..., None, :], o[..., None, :], jnp.moveaxis(r, 0, -2)], axis=-2
    )
    tinf = jnp.concatenate(
        [inf_t[..., None], fin[..., None], jnp.moveaxis(rest.inf, 0, -1)], axis=-1
    )
    return (
        cat(zero, q1.x, rest.x),
        cat(zero, q1.y, rest.y),
        cat(zero, q1.z, rest.z),
        tinf,
    )


def _gather_pt(tx, ty, tz, tinf, idx) -> Jac:
    """Select per-lane table entry idx (B,) from (B, 16, 17) stacks."""
    ii = idx[..., None, None]
    g = lambda t: jnp.take_along_axis(t, ii, axis=-2)[..., 0, :]  # noqa: E731
    inf = jnp.take_along_axis(tinf, idx[..., None], axis=-1)[..., 0]
    return Jac(g(tx), g(ty), g(tz), inf)


def verify_kernel(qx, qy, d1, d2, cand0, cand1, cand1_ok, valid):
    """Batched ECDSA-P256 verify core.

    Args (B = batch):
      qx, qy:    (B, 17) uint32 — public key affine coords (canonical limbs)
      d1, d2:    (B, 64) int32 — 4-bit MSB-first window digits of u1, u2
      cand0:     (B, 17) uint32 — r (mod p)
      cand1:     (B, 17) uint32 — r + n when < p (else ignored)
      cand1_ok:  (B,) bool — whether cand1 is admissible
      valid:     (B,) bool — host precheck passed (DER, range, low-S)
    Returns: (B,) bool — signature valid.
    """
    fp = mod_ctx(P256_P)
    gx, gy, ginf = (jnp.asarray(t) for t in g_table())
    tqx, tqy, tqz, tqinf = _q_window_table(fp, qx, qy)

    b = qx.shape[:-1]
    zero = jnp.zeros(b + (WIDE,), jnp.uint32)
    r0 = Jac(zero, zero, zero, jnp.ones(b, bool))

    def window(r: Jac, digs):
        w1, w2 = digs
        r = jax.lax.fori_loop(0, WINDOW_BITS, lambda _, p: point_dbl(fp, p), r)
        ga = Aff(gx[w1], gy[w1], ginf[w1])
        r = point_add_mixed(fp, r, ga)
        qj = _gather_pt(tqx, tqy, tqz, tqinf, w2)
        r = point_add(fp, r, qj)
        return r, None

    # scan over the 64 windows, MSB first; digits transposed to (64, B)
    r_final, _ = jax.lax.scan(window, r0, (d1.T, d2.T))

    z2 = fp.sqr(r_final.z)
    x_can = fp.canon(r_final.x)
    m0 = jnp.all(x_can == fp.canon(fp.mul(cand0, z2)), axis=-1)
    m1 = jnp.all(x_can == fp.canon(fp.mul(cand1, z2)), axis=-1) & cand1_ok
    return (m0 | m1) & ~r_final.inf & valid


@functools.lru_cache(maxsize=None)
def _jit_verify():
    return named_jit(verify_kernel, "xla_p256_verify")


def verify_prepared(qx, qy, d1, d2, cand0, cand1, cand1_ok, valid):
    """Jitted entry; compiles once per batch shape (callers bucket batches)."""
    return _jit_verify()(qx, qy, d1, d2, cand0, cand1, cand1_ok, valid)


# ---------------------------------------------------------------------------
# Host-side preparation: scalar math per item, numpy packing.
# ---------------------------------------------------------------------------

_HALF_N = P256_N >> 1


def recode_windows(u: int) -> np.ndarray:
    """256-bit scalar -> 64 MSB-first 4-bit window digits."""
    return np.asarray(
        [(u >> (WINDOW_BITS * (NWINDOWS - 1 - k))) & (TABLE - 1) for k in range(NWINDOWS)],
        dtype=np.int32,
    )


def prepare_batch(items) -> dict:
    """Host preprocessing for a batch of (x, y, digest32, r, s) tuples.

    Performs the reference's host-side checks (bccsp/sw/ecdsa.go:41-57 —
    malformed encoding, zero/negative or out-of-range r/s, high-S rejection)
    and the cheap modular scalar math; returns numpy arrays for the kernel.
    Items that fail prechecks stay in the batch with `valid=False` and dummy
    values so shapes remain static.
    """
    n = len(items)
    xs, ys, u1s, u2s = [], [], [], []
    c0, c1 = [], []
    c1_ok = np.zeros(n, bool)
    valid = np.zeros(n, bool)
    for i, (x, y, digest, r, s) in enumerate(items):
        ok = (
            isinstance(r, int)
            and isinstance(s, int)
            and 0 < r < P256_N
            and 0 < s <= _HALF_N  # low-S enforced, as the reference does
            and len(digest) == 32
        )
        if not ok:
            xs.append(P256_GX)
            ys.append(P256_GY)
            u1s.append(1)
            u2s.append(1)
            c0.append(1)
            c1.append(1)
            continue
        valid[i] = True
        e = int.from_bytes(digest, "big") % P256_N
        w = pow(s, -1, P256_N)
        u1s.append(e * w % P256_N)
        u2s.append(r * w % P256_N)
        xs.append(x)
        ys.append(y)
        c0.append(r)
        rpn = r + P256_N
        if rpn < P256_P:
            c1.append(rpn)
            c1_ok[i] = True
        else:
            c1.append(1)
    return dict(
        qx=np.asarray(ints_to_limbs(xs)),
        qy=np.asarray(ints_to_limbs(ys)),
        d1=np.stack([recode_windows(u) for u in u1s]),
        d2=np.stack([recode_windows(u) for u in u2s]),
        cand0=np.asarray(ints_to_limbs(c0)),
        cand1=np.asarray(ints_to_limbs(c1)),
        cand1_ok=c1_ok,
        valid=valid,
    )


__all__ = [
    "Jac",
    "Aff",
    "affine_add",
    "affine_mul",
    "g_table",
    "point_dbl",
    "point_add",
    "point_add_mixed",
    "verify_kernel",
    "verify_prepared",
    "prepare_batch",
    "recode_windows",
]
