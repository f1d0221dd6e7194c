"""Fused Pallas TPU kernel for the batched idemix Schnorr MSMs (BN254 G1).

The XLA ladder in `bn254_batch.py` is HBM-bound the same way `ec.py`'s
was: every field multiplication round-trips (B, ~600)-wide limb-product
intermediates through HBM, so the whole 64-window ladder runs ~100x
slower than its arithmetic.  This kernel is
the `pallas_ec.py` treatment for BN254: the entire joint T1/T2/T3 ladder
stays resident in VMEM — inputs stream in once, nine coordinates stream
out.

What differs from the P-256 kernel:

* **Montgomery REDC instead of Solinas.**  BN254's p is not a Solinas
  prime, so products reduce on the R = 2^272 word boundary (the same
  form as limbs.MontMod; coordinates arrive from the host already in
  Montgomery form x·R mod p): t = (T + ((T·m' mod R)·m)) / R — two
  extra schoolbook multiplies and one carry resolve, no fold chains.
  add/sub/mul_const keep the < 2^257 invariant with a SINGLE top-limb
  fold (2^256 mod p ~ 2^251.8 is small, unlike the near-m fold rows
  that make limbs.Mod's generic product chains slow); bound calculus in
  FpBN254.
* **One unified Jacobian table stack, rolled term loop.**  All bases —
  the issuer-key shared points (broadcast over lanes with z = R mod p)
  and the four per-lane points (a', a_bar, b', nym; one 14-step
  mixed-add chain builds all four tables at once) — live in one
  (n_tables*16, 17, BLK) VMEM scratch.  The per-window term loop is a
  lax.fori_loop whose body is ONE full Jacobian add with pl.ds table
  and accumulator indexing: graph size stays ~one-point-add regardless
  of attribute count (an unrolled-terms variant exceeded 10^5 HLO ops
  and did not compile in useful time), while VMEM residency keeps the
  runtime compute-bound.
* **a = 0 curve formulas** (y² = x³ + 3, dbl-2009-l), limb axis at -2
  so the table chain (4, 17, BLK) and the three ladder accumulators
  (3, 17, BLK) vectorize over a leading batch axis.

Parity: tests/test_pallas_bn254.py checks bit-for-bit agreement with the
host path (idemix/schnorr.py) through schnorr_commitments_batch.
Reference baseline being replaced: the per-signature AMCL G1 scalar
multiplications of idemix Ver (/root/reference/idemix/signature.go:243,
290-291 via math/amcl FP256BN).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fabric_tpu.csp.tpu import limbs, named_jit
from fabric_tpu.csp.tpu import bn254_batch as _xla_engine
from fabric_tpu.csp.tpu.limbs import LIMB_BITS, MASK, WIDE, int_to_limbs
from fabric_tpu.idemix import bn254 as bn

BLK = 128  # lanes (signatures) per grid block
# window geometry and lane-base order are the XLA engine's — the two
# engines must agree bit-for-bit on the digit recoding and term layout
NWINDOWS = _xla_engine.NWINDOWS
TABLE = _xla_engine.TABLE
N_LANE_BASES = len(_xla_engine.LANE_BASES)  # a', a_bar, b', nym


@functools.lru_cache(maxsize=None)
def _consts():
    ctx = limbs.mont_ctx(bn.P)
    return dict(
        m=int_to_limbs(bn.P, WIDE).astype(np.uint32)[:, None],
        mp=ctx.m_prime_limbs.astype(np.uint32)[:, None],
        one=ctx._one.astype(np.uint32)[:, None],  # R mod p
        sub_c=ctx.sub_c.astype(np.uint32)[:, None],
        # 2^256 mod p ~ 2^251.8 (2^256 - 5p): small enough that ONE
        # top-limb fold restores the < 2^257 invariant after add/sub
        r256=int_to_limbs((1 << 256) % bn.P, WIDE - 1).astype(
            np.uint32
        )[:, None],
    )


# ---------------------------------------------------------------------------
# Carry machinery on (..., 17, LANES) uint32 — limb axis at -2 (the
# pallas_ec helpers pin it to axis 0; here a leading batch axis carries
# the stacked bases/accumulators).
# ---------------------------------------------------------------------------


def _shift_up(a, d: int):
    """result[..., i, :] = a[..., i-d, :], zero filled."""
    if d == 0:
        return a
    pad = [(0, 0)] * (a.ndim - 2) + [(d, 0), (0, 0)]
    keep = a[..., : a.shape[-2] - d, :] if d < a.shape[-2] else a[..., :0, :]
    return jnp.pad(keep, pad)


def _grow(v, width: int):
    if v.shape[-2] < width:
        pad = [(0, 0)] * (v.ndim - 2) + [(0, width - v.shape[-2]), (0, 0)]
        v = jnp.pad(v, pad)
    return v


def _coarse(v, width: int):
    """One carry pass: limbs < 2^31 in, limbs <= 2^16 + small out.
    Value-preserving except for the (dropped) carry out of the top limb."""
    v = _grow(v, width)
    one = jnp.uint32(LIMB_BITS)
    m = jnp.uint32(MASK)
    return (v & m) + _shift_up(v >> one, 1)


def _resolve(v, width: int):
    """Exact carry resolution to canonical 16-bit limbs (Kogge-Stone,
    see limbs.resolve); caller guarantees value < 2^(16*width)."""
    v = _grow(v, width)
    one = jnp.uint32(LIMB_BITS)
    m = jnp.uint32(MASK)
    c = v >> one
    v = (v & m) + _shift_up(c, 1)
    c = v >> one
    v = (v & m) + _shift_up(c, 1)
    g = (v >> one).astype(jnp.uint32)
    lo = v & m
    pprop = (lo == m).astype(jnp.uint32)
    d = 1
    while d < width:
        g = g | (pprop & _shift_up(g, d))
        pprop = pprop & _shift_up(pprop, d)
        d *= 2
    return (lo + _shift_up(g, 1)) & m


# ---------------------------------------------------------------------------
# Montgomery field ops; elements are (..., 17, LANES) uint32.
# ---------------------------------------------------------------------------


def _mul_cols(a, b, width: int):
    """Schoolbook product columns 0..width-1 of a x b, coarse limbs out
    (<= 2^16 + 2^6).  Limb bounds: every product must stay below 2^32 —
    canonical x canonical, or double-coarse (<= 2^16 + 1) x canonical
    ((2^16+1)(2^16-1) = 2^32 - 1).  Dropping columns >= width is exact
    truncation mod 2^(16*width)."""
    na = a.shape[-2]
    nb = b.shape[-2]
    prod = a[..., :, None, :] * b[..., None, :, :]  # (..., na, nb, L)
    plo = prod & jnp.uint32(MASK)
    phi = prod >> jnp.uint32(LIMB_BITS)
    zrow = jnp.zeros(plo.shape[:-3] + (1,) + plo.shape[-1:], jnp.uint32)
    parts = []
    for i in range(na):
        # row i contributes at columns i..i+nb (lo at +0, hi at +1)
        row = jnp.concatenate([plo[..., i, :, :], zrow], axis=-2)
        row = row + jnp.concatenate([zrow, phi[..., i, :, :]], axis=-2)
        lo_col, hi_col = i, min(i + nb + 1, width)
        if lo_col >= width:
            continue
        row = row[..., : hi_col - lo_col, :]
        parts.append(jnp.pad(
            row,
            [(0, 0)] * (row.ndim - 2)
            + [(lo_col, width - hi_col), (0, 0)],
        ))
    while len(parts) > 1:
        parts = [
            parts[k] + parts[k + 1] if k + 1 < len(parts) else parts[k]
            for k in range(0, len(parts), 2)
        ]
    return _coarse(parts[0], width)


class FpBN254:
    """Montgomery field ops mod BN254 p on (..., 17, LANES) uint32, all
    preserving the shared lazy invariant value < 2^257.

    Bound calculus: mul/sqr outputs are < 1.01m + 2^242 < 2m (REDC of a
    T < 2^514 product — inputs < 2^257 keep T far under the m*R ~
    2^525.6 precondition).  add/sub/mul_const resolve limbs, then fold
    the top limb once through r256 = 2^256 mod p: r256 ~ 2^251.8 is
    small (2^256 - 5p), so a single fold of any value < 2^261 lands
    under 2^256 + 32*2^251.8 < 2^257.  The invariant in turn keeps the
    relaxed-subtraction constant limbwise dominant (its top limb is 7;
    invariant operands have top limb <= 1) — an earlier no-reduction
    variant let sub's subtrahend reach top limb ~2^6 and underflowed
    exactly there.  Limb bounds: every op output is canonical; REDC's
    internal T_lo and u take one extra coarse pass to <= 2^16 + 1
    before multiplying a canonical constant (products <= 2^32 - 1,
    exact in u32); the top-limb fold multiplies a coarse top limb
    (<= 32 for every caller) into canonical r256 limbs (< 2^21)."""

    def __init__(self, m, mp, one, sub_c, r256):
        self.m_limbs = m          # (17, 1) canonical p
        self.mp_limbs = mp        # (17, 1) -p^-1 mod 2^272
        self.one_limbs = one      # (17, 1) R mod p (Montgomery 1)
        self.sub_c = sub_c        # (17, 1) relaxed multiple of p
        self.r256 = r256          # (16, 1) 2^256 mod p

    def one(self, shape_like):
        return jnp.broadcast_to(self.one_limbs, shape_like.shape)

    def _redc(self, t_cols):
        """Coarse product columns (value < m*R) -> element < 1.1m with
        canonical limbs: t = (T + (T*m' mod R)*m) / R.  The division is
        exact — after full carry resolution the low 17 limbs of the sum
        are identically zero — so it is a slice."""
        t_lo = _coarse(t_cols[..., :WIDE, :], WIDE)  # limbs <= 2^16+1
        u = _coarse(_mul_cols(t_lo, self.mp_limbs, WIDE), WIDE)
        v = _mul_cols(u, self.m_limbs, 2 * WIDE)
        w = 2 * WIDE + 1
        s = _resolve(_grow(t_cols, w) + _grow(v, w), w)
        return s[..., WIDE:2 * WIDE, :]

    def mul(self, a, b):
        return self._redc(_mul_cols(a, b, 2 * WIDE))

    def sqr(self, a):
        return self.mul(a, a)

    def _fold_resolve(self, s):
        """Coarse 17-row value (top limb <= 32) -> canonical invariant
        element: fold the top limb through r256, resolve carries."""
        t = s[..., :WIDE - 1, :] + s[..., WIDE - 1:WIDE, :] * self.r256
        return _resolve(t, WIDE)

    def add(self, a, b):
        # a + b < 2^258: coarse top limb <= 3
        return self._fold_resolve(_coarse(a + b, WIDE))

    def sub(self, a, b):
        # a + (C - b), C a relaxed multiple of p (~2^259) limbwise
        # dominating any invariant b; coarse top limb <= 10
        return self._fold_resolve(_coarse(a + (self.sub_c - b), WIDE))

    def mul_const(self, a, k: int):
        # a*k < 2^260 for k <= 8: coarse top limb <= 17
        assert 0 < k <= 8
        return self._fold_resolve(_coarse(a * jnp.uint32(k), WIDE))

    def is_zero(self, a):
        # REDC(a) lands in [0, 1.1m) and is ≡ a*R^-1 (mod p): a ≡ 0 iff
        # the residue is exactly 0 or exactly p — two limbwise compares.
        # int32 0/1 flags (Mosaic handles i1 vectors poorly).
        r = self._redc(_grow(a, 2 * WIDE))

        def mism(c):
            return jnp.sum(
                (r != c).astype(jnp.int32), axis=-2, keepdims=True
            )

        n = mism(jnp.zeros_like(r)) * mism(self.m_limbs)
        return (n == 0).astype(jnp.int32)

    def canon(self, a):
        # one mont-mul by the form's 1 preserves value and lands < 1.1m;
        # a single conditional subtract of p finishes
        v = self.mul(a, jnp.broadcast_to(self.one_limbs, a.shape))
        return self._cond_sub_m(v)

    def _cond_sub_m(self, a):
        notb = jnp.uint32(MASK) - self.m_limbs
        one_row = jnp.concatenate(
            [jnp.ones_like(a[..., :1, :]), jnp.zeros_like(a[..., 1:, :])],
            axis=-2,
        )
        t = _resolve(a + notb + one_row, WIDE + 1)
        ge = (t[..., WIDE:WIDE + 1, :] > 0).astype(jnp.int32)
        return _sel(ge, t[..., :WIDE, :], a)


# ---------------------------------------------------------------------------
# Selection + a = 0 point formulas; int32 0/1 flags shaped (..., 1, L).
# ---------------------------------------------------------------------------


def _sel(c, a, b):
    mask = (-c).astype(jnp.uint32)  # 0 or 0xffffffff, broadcasts on -2
    return b ^ ((a ^ b) & mask)


def _fsel(c, a, b):
    return b + (a - b) * c


def _pt_sel(c, p1, p2):
    return (
        _sel(c, p1[0], p2[0]),
        _sel(c, p1[1], p2[1]),
        _sel(c, p1[2], p2[2]),
        _fsel(c, p1[3], p2[3]),
    )


def _dbl_a0(fp, p):
    """dbl-2009-l for a = 0 (BN254: y² = x³ + 3)."""
    x, y, z, inf = p
    a = fp.sqr(x)
    b = fp.sqr(y)
    c = fp.sqr(b)
    d_inner = fp.sqr(fp.add(x, b))
    d = fp.mul_const(fp.sub(fp.sub(d_inner, a), c), 2)
    e = fp.mul_const(a, 3)
    f = fp.sqr(e)
    x3 = fp.sub(f, fp.add(d, d))
    y3 = fp.sub(fp.mul(e, fp.sub(d, x3)), fp.mul_const(c, 8))
    z3 = fp.mul_const(fp.mul(y, z), 2)
    return (x3, y3, z3, inf)


def _add_full(fp, p1, p2):
    """add-2007-bl with degenerate handling; equal points fall back to
    the a=0 doubling, opposites to infinity, identities pass through."""
    x1, y1, z1, inf1 = p1
    x2, y2, z2, inf2 = p2
    z1z1 = fp.sqr(z1)
    z2z2 = fp.sqr(z2)
    u1 = fp.mul(x1, z2z2)
    u2 = fp.mul(x2, z1z1)
    s1 = fp.mul(fp.mul(y1, z2), z2z2)
    s2 = fp.mul(fp.mul(y2, z1), z1z1)
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
    z3 = fp.mul(fp.sub(fp.sub(fp.sqr(fp.add(z1, z2)), z1z1), z2z2), h)
    fin = jnp.zeros_like(inf1)
    out = (x3, y3, z3, fin)
    out = _pt_sel(h_zero * r_zero, _dbl_a0(fp, p1), out)
    out = (out[0], out[1], out[2],
           jnp.maximum(out[3], h_zero * (1 - r_zero)))
    out = _pt_sel(inf2, p1, out)
    out = _pt_sel(inf1, p2, out)
    return out


def _add_mixed(fp, p1, a2):
    """madd-2007-bl, second operand affine with z = one (Montgomery 1);
    used only for the per-lane window-table build chain."""
    x1, y1, z1, inf1 = p1
    ax, ay, ainf = a2
    z1z1 = fp.sqr(z1)
    u2 = fp.mul(ax, z1z1)
    s2 = fp.mul(fp.mul(ay, z1), z1z1)
    h = fp.sub(u2, x1)
    rr = fp.sub(s2, y1)
    h_zero = fp.is_zero(h)
    r_zero = fp.is_zero(rr)
    hh = fp.sqr(h)
    i = fp.mul_const(hh, 4)
    j = fp.mul(h, i)
    rr2 = fp.add(rr, rr)
    v = fp.mul(x1, i)
    x3 = fp.sub(fp.sub(fp.sqr(rr2), j), fp.add(v, v))
    t = fp.mul(y1, j)
    y3 = fp.sub(fp.mul(rr2, fp.sub(v, x3)), fp.add(t, t))
    z3 = fp.sub(fp.sub(fp.sqr(fp.add(z1, h)), z1z1), hh)
    fin = jnp.zeros_like(inf1)
    out = (x3, y3, z3, fin)
    out = _pt_sel(h_zero * r_zero, _dbl_a0(fp, p1), out)
    out = (out[0], out[1], out[2],
           jnp.maximum(out[3], h_zero * (1 - r_zero)))
    a2j = (ax, ay, fp.one(ax), ainf)
    out = _pt_sel(ainf, p1, out)
    out = _pt_sel(inf1, a2j, out)
    return out


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------


def _unpack_rows(w):
    """(..., 8, X) uint32 32-bit words -> (..., 17, X) canonical limbs
    (inputs are canonical field elements < 2^256: top limb 0)."""
    rows = []
    for i in range(8):
        rows.append(w[..., i:i + 1, :] & jnp.uint32(MASK))
        rows.append(w[..., i:i + 1, :] >> jnp.uint32(LIMB_BITS))
    rows.append(jnp.zeros_like(rows[0]))
    return jnp.concatenate(rows, axis=-2)


def _onehot(digit, blk):
    """digit (1, BLK) int32 -> (16, BLK) int32 one-hot."""
    t = jax.lax.broadcasted_iota(jnp.int32, (TABLE, blk), 0)
    return (t == digit).astype(jnp.int32)


def _isum(mask_i32, tab_u32):
    """One-hot select over the table-entry axis (-3), int32-exact
    (limbs < 2^16)."""
    return jnp.sum(
        mask_i32 * tab_u32.astype(jnp.int32), axis=-3
    ).astype(jnp.uint32)


def _make_kernel(n_terms: int, n_tables: int):
    def kernel(lanes_ref, laneinf_ref, digits_ref, termmeta_ref,
               sx_ref, sy_ref, sz_ref, sinf_ref,
               m_ref, mp_ref, one_ref, subc_ref, r256_ref, out_ref,
               tabx, taby, tabz, tabinf,
               accx, accy, accz, accinf):
        fp = FpBN254(
            m_ref[:], mp_ref[:], one_ref[:], subc_ref[:], r256_ref[:]
        )
        blk = laneinf_ref.shape[-1]
        n_shared = n_tables - N_LANE_BASES

        # -- shared-base tables: broadcast over lanes into the unified
        # scratch (z = Montgomery 1 everywhere; entry 0 carries inf=1
        # via sinf and is never read through z) --
        tabx[: n_shared * TABLE] = jnp.broadcast_to(
            sx_ref[:][:, :, None], (n_shared * TABLE, WIDE, blk)
        )
        taby[: n_shared * TABLE] = jnp.broadcast_to(
            sy_ref[:][:, :, None], (n_shared * TABLE, WIDE, blk)
        )
        tabz[: n_shared * TABLE] = jnp.broadcast_to(
            sz_ref[:][:, :, None], (n_shared * TABLE, WIDE, blk)
        )
        tabinf[: n_shared * TABLE] = jnp.broadcast_to(
            sinf_ref[:], (n_shared * TABLE, blk)
        )

        # -- per-lane points: 4 bases stacked on a leading axis
        # (static row slices, base-major x-then-y word planes) --
        px = jnp.stack([
            _unpack_rows(lanes_ref[2 * b4 * 8:(2 * b4 + 1) * 8])
            for b4 in range(N_LANE_BASES)
        ])
        py = jnp.stack([
            _unpack_rows(lanes_ref[(2 * b4 + 1) * 8:(2 * b4 + 2) * 8])
            for b4 in range(N_LANE_BASES)
        ])
        pinf = laneinf_ref[:][:, None, :].astype(jnp.int32)  # (4, 1, BLK)

        # -- per-lane Jacobian tables: one 14-step mixed-add chain
        # builds all four bases' windows at once --
        base0 = n_shared * TABLE
        zero4 = jnp.zeros((N_LANE_BASES, WIDE, blk), jnp.uint32)
        one4 = jnp.broadcast_to(one_ref[:], (N_LANE_BASES, WIDE, blk))

        def write_entry(i, pt):
            for b4 in range(N_LANE_BASES):
                r = pl.ds(base0 + b4 * TABLE + i, 1)
                tabx[r] = pt[0][b4][None]
                taby[r] = pt[1][b4][None]
                tabz[r] = pt[2][b4][None]
                tabinf[r] = pt[3][b4].astype(jnp.uint32)

        write_entry(0, (zero4, zero4, zero4, jnp.ones_like(pinf)))
        write_entry(1, (px, py, one4, pinf))
        q_aff = (px, py, pinf)

        def build(i, carry):
            nxt = _add_mixed(fp, carry, q_aff)
            write_entry(i, nxt)
            return nxt

        jax.lax.fori_loop(2, TABLE, build, (px, py, one4, pinf))

        # -- accumulators in scratch: (3, 17, BLK) + (3, BLK) inf --
        accx[:] = jnp.zeros((4, WIDE, blk), jnp.uint32)
        accy[:] = jnp.zeros((4, WIDE, blk), jnp.uint32)
        accz[:] = jnp.zeros((4, WIDE, blk), jnp.uint32)
        accinf[:] = jnp.ones((4, blk), jnp.uint32)

        # -- 64-window joint ladder, MSB first ------------------------
        def term_step(t, w):
            meta = termmeta_ref[pl.ds(t, 1)]  # (1, 2): [table, acc]
            ti = meta[0, 0]
            ai = meta[0, 1]
            word = digits_ref[pl.ds(t * 8 + w // 8, 1)]
            shift = jnp.uint32(4) * (w % 8).astype(jnp.uint32)
            dig = ((word >> shift) & jnp.uint32(0xF)).astype(jnp.int32)
            oh = _onehot(dig, blk)[:, None, :]  # (16, 1, BLK)
            ts = pl.ds(ti * TABLE, TABLE)
            q = (
                _isum(oh, tabx[ts]),
                _isum(oh, taby[ts]),
                _isum(oh, tabz[ts]),
                jnp.sum(
                    oh[:, 0, :] * tabinf[ts].astype(jnp.int32),
                    axis=0, keepdims=True,
                ),
            )
            ar = pl.ds(ai, 1)
            cur = (
                accx[ar][0], accy[ar][0], accz[ar][0],
                accinf[ar].astype(jnp.int32),
            )
            new = _add_full(fp, cur, q)
            accx[ar] = new[0][None]
            accy[ar] = new[1][None]
            accz[ar] = new[2][None]
            accinf[ar] = new[3].astype(jnp.uint32)
            return w

        def window(w, _):
            st = (
                accx[0:3], accy[0:3], accz[0:3],
                accinf[0:3][:, None, :].astype(jnp.int32),
            )
            for _i in range(4):
                st = _dbl_a0(fp, st)
            accx[0:3] = st[0]
            accy[0:3] = st[1]
            accz[0:3] = st[2]
            accinf[0:3] = st[3][:, 0, :].astype(jnp.uint32)
            jax.lax.fori_loop(0, n_terms, term_step, w)
            return 0

        jax.lax.fori_loop(0, NWINDOWS, window, 0)

        # canonical Montgomery residues: one canon over all 9 coords
        coords = jnp.concatenate(
            [accx[0:3], accy[0:3], accz[0:3]], axis=0
        )  # (9, 17, BLK): rows 0-2 x, 3-5 y, 6-8 z of T1..T3
        can = fp.canon(coords)
        infrow = jnp.concatenate(
            [accinf[0:3], jnp.zeros((WIDE - 3, blk), jnp.uint32)], axis=0
        )[None]  # (1, 17, BLK), acc t's flag in limb row t
        out_ref[:] = jnp.concatenate([can, infrow], axis=0)[None]

    return kernel


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _build_call(nblocks: int, blk: int, n_terms: int, n_tables: int,
                interpret: bool):
    lane_spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, blk), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    const_spec = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.VMEM
    )
    n_shared = n_tables - N_LANE_BASES
    fn = pl.pallas_call(
        _make_kernel(n_terms, n_tables),
        in_specs=[
            lane_spec(2 * N_LANE_BASES * 8),       # packed lane coords
            lane_spec(N_LANE_BASES),               # lane inf flags
            lane_spec(n_terms * 8),                # packed digits
            const_spec((n_terms, 2)),              # (table, acc) per term
            const_spec((n_shared * TABLE, WIDE)),  # shared x limbs
            const_spec((n_shared * TABLE, WIDE)),  # shared y limbs
            const_spec((n_shared * TABLE, WIDE)),  # shared z limbs
            const_spec((n_shared * TABLE, 1)),     # shared inf
            const_spec((WIDE, 1)),                 # p
            const_spec((WIDE, 1)),                 # m' = -p^-1 mod R
            const_spec((WIDE, 1)),                 # R mod p
            const_spec((WIDE, 1)),                 # sub_c
            const_spec((WIDE - 1, 1)),             # 2^256 mod p
        ],
        grid=(nblocks,),
        out_specs=pl.BlockSpec(
            (1, 10, WIDE, blk), lambda i: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((nblocks, 10, WIDE, blk), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((n_tables * TABLE, WIDE, blk), jnp.uint32),  # tabx
            pltpu.VMEM((n_tables * TABLE, WIDE, blk), jnp.uint32),  # taby
            pltpu.VMEM((n_tables * TABLE, WIDE, blk), jnp.uint32),  # tabz
            pltpu.VMEM((n_tables * TABLE, blk), jnp.uint32),        # tabinf
            pltpu.VMEM((4, WIDE, blk), jnp.uint32),                 # accx
            pltpu.VMEM((4, WIDE, blk), jnp.uint32),                 # accy
            pltpu.VMEM((4, WIDE, blk), jnp.uint32),                 # accz
            pltpu.VMEM((4, blk), jnp.uint32),                       # accinf
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="pallas_bn254_pairing",
    )
    return named_jit(fn, "pallas_bn254_pairing")


# ---------------------------------------------------------------------------
# Host packing.
# ---------------------------------------------------------------------------


def _words_from_ints(vals: list[int]) -> np.ndarray:
    """Canonical ints < 2^256 -> (8, B) uint32 little-endian words."""
    buf = bytearray(32 * len(vals))
    for i, v in enumerate(vals):
        buf[32 * i:32 * i + 32] = v.to_bytes(32, "little")
    return np.ascontiguousarray(
        np.frombuffer(bytes(buf), np.uint32).reshape(len(vals), 8).T
    )


def _digits_from_ints(vals: list[int]) -> np.ndarray:
    """Scalars < 2^256 -> (8, B) uint32: 64 MSB-first 4-bit window
    digits, 8 per word (digit k in bits 4*(k%8) of word k//8) — the
    same recoding as bn254_batch._recode, packed."""
    n = len(vals)
    buf = bytearray(32 * n)
    for i, v in enumerate(vals):
        buf[32 * i:32 * i + 32] = v.to_bytes(32, "little")
    u8 = np.frombuffer(bytes(buf), np.uint8).reshape(n, 32)
    nibbles = np.empty((n, 64), np.uint32)
    nibbles[:, 0::2] = u8 & 0xF
    nibbles[:, 1::2] = u8 >> 4
    d = nibbles[:, ::-1]  # digit k = nibble 63-k (MSB first)
    shifts = (np.uint32(4) * np.arange(8, dtype=np.uint32))[None, None]
    return np.ascontiguousarray(
        (d.reshape(n, 8, 8) << shifts).sum(axis=2, dtype=np.uint32).T
    )


@functools.lru_cache(maxsize=8)
def _shared_limbs(ipk_key: tuple) -> tuple:
    """Shared-base affine window tables in Montgomery form:
    (x, y, z (n_shared*16, 17), inf (n_shared*16, 1)); z is the
    Montgomery 1 on finite entries.  The raw small multiples come from
    the engine-shared cache (bn254_batch.shared_multiples) so the host
    scalar multiplications are done once per issuer key, not once per
    engine."""
    from fabric_tpu.csp.tpu.bn254_batch import shared_multiples

    ctx = limbs.mont_ctx(bn.P)
    one = int_to_limbs(ctx.one_int, WIDE)
    zero = int_to_limbs(0, WIDE)
    xs, ys, zs, infs = [], [], [], []
    for row in shared_multiples(ipk_key):
        for q in row:
            if q is None:
                xs.append(zero)
                ys.append(zero)
                zs.append(zero)
                infs.append(1)
            else:
                xs.append(int_to_limbs(ctx.to_mont_int(q[0]), WIDE))
                ys.append(int_to_limbs(ctx.to_mont_int(q[1]), WIDE))
                zs.append(one)
                infs.append(0)
    return (
        np.stack(xs).astype(np.uint32),
        np.stack(ys).astype(np.uint32),
        np.stack(zs).astype(np.uint32),
        np.asarray(infs, np.uint32)[:, None],
    )


@dataclasses.dataclass
class Packed:
    """A batch's host arrays in the kernel's layout."""

    n: int          # lanes asked for
    lanes: int      # lanes the kernel runs: whole blocks, a power of two of them
    blk: int
    n_terms: int
    n_tables: int
    interpret: bool
    args: tuple


def pack(lane_pts, scalars, ok, term_table, term_acc, shared_pts,
         blk: int = BLK, interpret: bool | None = None) -> Packed:
    """Host limb packing of a prepared batch.

    lane_pts: per-lane tuple of 4 affine int points (None = infinity);
    scalars: per-lane list of n_terms ints (None when not ok); ok:
    per-lane validity (bad lanes run with zero scalars and infinity
    bases)."""
    if interpret is None:
        interpret = _use_interpret()
    n = len(lane_pts)
    n_terms = len(term_table)
    n_shared = len(shared_pts)
    n_tables = n_shared + N_LANE_BASES
    nb = -(-n // blk)
    while nb & (nb - 1):  # power-of-two blocks: one compile per
        nb += 1           # (nblocks, n_attrs) pair
    padded = nb * blk
    ctx = limbs.mont_ctx(bn.P)

    coords: list[list[int]] = [[] for _ in range(2 * N_LANE_BASES)]
    laneinf = np.ones((N_LANE_BASES, padded), np.uint32)
    digit_ints: list[list[int]] = [[] for _ in range(n_terms)]
    for j in range(padded):
        good = j < n and ok[j]
        pts = lane_pts[j] if good else (None,) * N_LANE_BASES
        sc = scalars[j] if good else [0] * n_terms
        for b4 in range(N_LANE_BASES):
            p = pts[b4]
            if p is None:
                coords[2 * b4].append(0)
                coords[2 * b4 + 1].append(0)
            else:
                coords[2 * b4].append(ctx.to_mont_int(p[0]))
                coords[2 * b4 + 1].append(ctx.to_mont_int(p[1]))
                laneinf[b4, j] = 0
        for t in range(n_terms):
            digit_ints[t].append(sc[t])

    # lane coord plane order matches the kernel's reshape: base-major,
    # x words then y words
    lanes = np.concatenate(
        [_words_from_ints(coords[c]) for c in range(2 * N_LANE_BASES)],
        axis=0,
    )  # (64, padded)
    digits = np.concatenate(
        [_digits_from_ints(d) for d in digit_ints], axis=0
    )  # (n_terms*8, padded)
    termmeta = np.stack(
        [
            np.asarray(term_table, np.int32),
            np.asarray(term_acc, np.int32),
        ],
        axis=1,
    )  # (n_terms, 2)
    sxl, syl, szl, sinf = _shared_limbs(tuple(shared_pts))
    c = _consts()
    return Packed(
        n, padded, blk, n_terms, n_tables, bool(interpret),
        (lanes, laneinf, digits, termmeta, sxl, syl, szl, sinf,
         c["m"], c["mp"], c["one"], c["sub_c"], c["r256"]),
    )


class _Reader:
    """A launch's result: `block()` waits for the device and copies
    back, `unpack()` turns the copy into Python integers."""

    def __init__(self, dev, n: int, blk: int):
        self._dev, self._n, self._blk = dev, n, blk

    def block(self):
        return np.asarray(self._dev)  # (nb, 10, 17, blk)

    def unpack(self, out) -> list:
        return _unpack(out, self._n, self._blk)


def enqueue(packed: Packed) -> _Reader:
    """Launch the ladder without waiting for it; the reader gives
    per-lane [(x, y, z, inf)] * 3 Jacobian ints (plain form)."""
    call = _build_call(
        packed.lanes // packed.blk, packed.blk, packed.n_terms,
        packed.n_tables, packed.interpret,
    )
    return _Reader(call(*packed.args), packed.n, packed.blk)


def _unpack(out, n: int, blk: int) -> list:
    """The kernel's canonical 16-bit limbs -> ints: the nine coordinates
    of a lane side by side as little-endian bytes, one `from_bytes` a
    coordinate (the outputs are canonical: no limb carries)."""
    ctx = limbs.mont_ctx(bn.P)
    step = 2 * WIDE
    raw = np.ascontiguousarray(
        out[:, :9].transpose(0, 3, 1, 2)
    ).astype("<u2").tobytes()  # (nb, blk, 9, 17) uint16
    infs = out[:, 9, :3, :]  # (nb, 3, blk)
    results = []
    for j in range(n):
        b_i, l_i = divmod(j, blk)
        at = j * 9 * step
        vals = [
            ctx.from_mont_int(
                int.from_bytes(raw[at + c * step:at + (c + 1) * step], "little")
            )
            for c in range(9)
        ]
        results.append([
            (vals[t], vals[3 + t], vals[6 + t], bool(infs[b_i, t, l_i]))
            for t in range(3)
        ])
    return results


def commitments(lane_pts, scalars, ok, term_table, term_acc, shared_pts,
                blk: int = BLK, interpret: bool | None = None):
    """Run the ladder for a prepared batch: pack, launch, read."""
    reader = enqueue(pack(
        lane_pts, scalars, ok, term_table, term_acc, shared_pts,
        blk=blk, interpret=interpret,
    ))
    return reader.unpack(reader.block())


__all__ = ["commitments", "pack", "enqueue", "Packed", "FpBN254", "BLK"]
