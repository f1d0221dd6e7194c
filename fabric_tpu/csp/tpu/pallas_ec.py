"""Fused Pallas TPU kernel for batched ECDSA-P256 verification.

The XLA graph in `ec.py` is correct but HBM-bound: each of the ~3800
field multiplications per ladder round-trips (B, ~600)-wide intermediates
through HBM (the matmul that sums limb products breaks XLA fusion).  This
kernel keeps the ENTIRE 64-window joint Shamir ladder resident in VMEM —
inputs stream in once, one bit streams out — so the arithmetic runs at
VPU rate instead of HBM rate.

Kernel-specific design (everything else mirrors `ec.py` exactly):

* **Layout** ``(limb, lane)``: a field element is ``(17, BLK)`` uint32 —
  limbs on the sublane axis, signatures on the 128-wide lane axis; every
  field op is a handful of full-tile VPU ops.  Grid = batch/BLK blocks.
* **Solinas reduction.** p = 2^256 − 2^224 + 2^192 + 2^96 − 1, so a
  product reduces by the FIPS-186 shifted-add recombination of its
  32-bit words (s1 + 2s2 + 2s3 + s4 + s5 − s6 − s7 − s8 − s9) instead of
  the generic fold-table multiplies of `limbs.Mod` — no multiplications
  in the reduction at all.  Negative terms are absorbed by a relaxed
  multiple-of-p bias constant whose every limb dominates the worst-case
  per-limb negative sum (the `sub_c` trick from limbs.py, scaled by 8
  so it still dominates for coarse — limbs <= 2^16 + 2^6 — input).
  Operands carry the lazy invariant value < 2^257, so the product has
  one word beyond the 512-bit Solinas range; its (tiny) top limb is
  folded with one extra multiply by 2^512 mod p.
* **No gathers.** Per-lane window-table selection is a one-hot masked
  sum over the 16 table entries; the Q table lives in VMEM scratch and
  is built in-kernel with 14 mixed adds.

Parity: tests/test_pallas_ec.py checks this kernel bit-for-bit against
ec.verify_kernel and the OpenSSL oracle on valid/tampered/edge batches.
Reference baseline being replaced: bccsp/sw/ecdsa.go:41-57 fanned out by
core/committer/txvalidator/v20/validator.go goroutines.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fabric_tpu.csp.api import P256_GX, P256_GY, P256_P
from fabric_tpu.csp.tpu import ec, named_jit
from fabric_tpu.csp.tpu.limbs import (
    LIMB_BITS,
    MASK,
    NLIMBS,
    WIDE,
    int_to_limbs,
)

BLK = 256  # lanes (signatures) per grid block (measured best vs 128/512/1024)
NWINDOWS = ec.NWINDOWS
TABLE = ec.TABLE

# ---------------------------------------------------------------------------
# Host-precomputed constants.
# ---------------------------------------------------------------------------

# Solinas term tables (FIPS 186-4 / HMV Alg 2.29 for P-256).  Each term is
# 8 32-bit words, most-significant first; entries index the 512-bit
# product's words c0..c15 (c0 least significant); None is a zero word.
_S_TERMS = [
    # (words ms-first, weight); positive terms first
    ([7, 6, 5, 4, 3, 2, 1, 0], 1),                     # s1 (low half)
    ([15, 14, 13, 12, 11, None, None, None], 2),       # s2
    ([None, 15, 14, 13, 12, None, None, None], 2),     # s3
    ([15, 14, None, None, None, 10, 9, 8], 1),         # s4
    ([8, 13, 15, 14, 13, 11, 10, 9], 1),               # s5
    ([10, 8, None, None, None, 13, 12, 11], -1),       # s6
    ([11, 9, None, None, 15, 14, 13, 12], -1),         # s7
    ([12, None, 10, 9, 8, 15, 14, 13], -1),            # s8
    ([13, None, 11, 10, 9, None, 15, 14], -1),         # s9
]


def _term_limb_indices(words_ms_first):
    """8 words (ms first) -> 16 limb indices into the 34-limb product
    (ls first); -1 marks a zero limb."""
    out = []
    for w in reversed(words_ms_first):
        if w is None:
            out += [-1, -1]
        else:
            out += [2 * w, 2 * w + 1]
    return out


@functools.lru_cache(maxsize=None)
def _solinas_runs():
    """Static (weight, out_pos, src_limb, length) runs: each Solinas term
    decomposes into 1-4 CONTIGUOUS limb slices of the product, so the
    recombination is ~21 pad+add VPU ops instead of an MXU contraction."""
    runs = []
    for words, w in _S_TERMS:
        li = _term_limb_indices(words)
        k = 0
        while k < NLIMBS:
            if li[k] < 0:
                k += 1
                continue
            start = k
            while (
                k + 1 < NLIMBS
                and li[k + 1] == li[k] + 1
            ):
                k += 1
            runs.append((w, start, li[start], k - start + 1))
            k += 1
    return runs


@functools.lru_cache(maxsize=None)
def _consts():
    """All numpy constants the kernel closes over."""
    p = P256_P
    # Signed Solinas matrix: output limb k accumulates product limb i
    # with net weight solmat[k, i].  Weights are small (|sum per row|
    # <= 11) and the product limbs are coarse (<= 2^16 + 2^6 after one
    # carry pass; the contraction is linear in the limb vector so
    # canonicality is not required), so the f32 contraction stays exact
    # (|sum| < 2^21 << 2^24).
    solmat = np.zeros((NLIMBS, 2 * WIDE), np.float32)
    for words, w in _S_TERMS:
        for k, i in enumerate(_term_limb_indices(words)):
            if i >= 0:
                solmat[k, i] += w

    # bias: 8 * (ceil(2^259/p) * p), in relaxed limbs every one of which
    # >= 8*2^16 - 8 (dominates the worst per-limb negative sum of the 4
    # subtracted terms even for coarse — limbs <= 2^16 + 2^6 — input:
    # 4*(2^16+2^6) < 8*MASK); value is a multiple of p so it vanishes
    # mod p.
    c = (1 << 259) // p + 1
    e = int_to_limbs(8 * c * p, WIDE).astype(np.int64)
    r = e.copy()
    r[0] += 8 << LIMB_BITS
    r[1:NLIMBS] += 8 * MASK
    r[NLIMBS] -= 8
    assert (r[:NLIMBS] >= 8 * MASK).all() and r[NLIMBS] >= 8
    bias = r.astype(np.uint32)[:, None]  # (17, 1)

    # fold rows: 2^256 mod p and 2^512 mod p (canonical 16 limbs)
    r256 = int_to_limbs((1 << 256) % p, NLIMBS)[:, None]  # (16, 1)
    r512 = int_to_limbs((1 << 512) % p, NLIMBS)[:, None]

    # relaxed-subtraction constant (limbs.Mod.sub_c)
    c1 = ((1 << 259) + p - 1) // p
    e1 = int_to_limbs(c1 * p, WIDE).astype(np.int64)
    s = e1.copy()
    s[0] += 1 << LIMB_BITS
    s[1:NLIMBS] += MASK
    s[NLIMBS] -= 1
    sub_c = s.astype(np.uint32)[:, None]  # (17, 1)

    p_limbs = int_to_limbs(p, WIDE)[:, None]  # (17, 1)
    from fabric_tpu.csp.api import P256_N
    n_limbs = int_to_limbs(P256_N, WIDE)[:, None]  # (17, 1)

    gx, gy, ginf = ec.g_table()  # (16, 17), (16, 17), (16,)
    return dict(
        solmat=solmat,
        bias=bias,
        r256=r256,
        r512=r512,
        sub_c=sub_c,
        p_limbs=p_limbs,
        n_limbs=n_limbs,
        gx=gx[:, :, None].astype(np.uint32),  # (16, 17, 1)
        gy=gy[:, :, None].astype(np.uint32),
        ginf=ginf.astype(np.uint32)[:, None],  # (16, 1)
    )


# ---------------------------------------------------------------------------
# In-kernel field arithmetic on (17, BLK) uint32, limbs on the sublane axis.
# ---------------------------------------------------------------------------


def _u2f(x):
    return x.astype(jnp.int32).astype(jnp.float32)


def _f2u(x):
    return x.astype(jnp.int32).astype(jnp.uint32)


def _shift_up(a, d: int):
    """result[i] = a[i-d] along the limb (first) axis, zero filled."""
    if d == 0:
        return a
    pad = [(d, 0)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a[: a.shape[0] - d] if d < a.shape[0] else a[:0], pad)


def _coarse(v, width: int):
    """One carry pass: limbs < 2**31 in, limbs <= 2**16 + (carry bound)
    out.  Value-preserving; does NOT canonicalize (use _resolve for that).
    Cheap replacement for _resolve wherever the consumer only needs
    bounded — not canonical — limbs (the Solinas contraction is linear in
    the limb vector, so bounded limbs suffice for exactness)."""
    if v.shape[0] < width:
        pad = [(0, width - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
        v = jnp.pad(v, pad)
    one = jnp.uint32(LIMB_BITS)
    m = jnp.uint32(MASK)
    return (v & m) + _shift_up(v >> one, 1)


def _resolve(v, width: int):
    """Carry resolution (see limbs.resolve): limbs < 2**31 in, canonical
    16-bit limbs out; caller guarantees value < 2**(16*width)."""
    if v.shape[0] < width:
        pad = [(0, width - v.shape[0])] + [(0, 0)] * (v.ndim - 1)
        v = jnp.pad(v, pad)
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


class FpP256:
    """Field ops mod P-256 on (17, BLK) uint32; drop-in for limbs.Mod in
    the point formulas (same method names, lazy invariant value < 2^257).
    Constants arrive as kernel inputs (Pallas kernels cannot capture
    array constants)."""

    def __init__(self, solmat, bias, r256, r512, sub_c, p_limbs):
        self.solmat = solmat
        self.bias = bias
        self.r256 = r256
        self.r512 = r512
        self.sub_c = sub_c
        self.p_limbs = p_limbs
        # 2p in canonical limbs (2*p_i is even, one coarse pass exact)
        self.p2_limbs = _coarse(p_limbs * jnp.uint32(2), WIDE)

    def _minifold(self, v):
        """17-limb value with small top limb -> invariant element."""
        acc = v[:NLIMBS] + v[NLIMBS:NLIMBS + 1] * self.r256
        return _resolve(acc, WIDE)

    def _fold_resolve(self, s):
        """Coarse 17-row value (limbs <= 2^16 + 2^8, top limb <= 2^9) ->
        canonical invariant element (17 rows, value < 2^257).

        Folds the top limb through r256 = 2^256 mod p, then resolves
        carries on 16 ALIGNED rows (two (8, lane) tiles, 4 Kogge-Stone
        steps) instead of 17 (three tiles, 5 steps) — this tail runs at
        the end of every field op, so the tile alignment matters more
        than anything inside the op.  Bound chain: r256's nonzero limbs
        sit at positions <= 13, so t[15] < 2^17 and the coarse carry out
        of limb 15 is {0,1}; t's value is < 2^257, so coarse-carry-out +
        KS-carry-out <= 1 and their sum IS the output's 17th limb."""
        t = s[:NLIMBS] + s[NLIMBS:NLIMBS + 1] * self.r256  # 16 rows, < 2^26
        one = jnp.uint32(LIMB_BITS)
        m = jnp.uint32(MASK)
        c = t >> one
        v = (t & m) + _shift_up(c, 1)  # limbs < 2^17
        cout = c[NLIMBS - 1:NLIMBS]  # {0,1} by the t[15] bound
        g = v >> one  # {0,1}
        lo = v & m
        pp = (lo == m).astype(jnp.uint32)
        d = 1
        while d < NLIMBS:
            g = g | (pp & _shift_up(g, d))
            pp = pp & _shift_up(pp, d)
            d *= 2
        res = (lo + _shift_up(g, 1)) & m
        return jnp.concatenate([res, cout + g[NLIMBS - 1:NLIMBS]], axis=0)

    def add(self, a, b):
        # a + b < 2^258: after one coarse pass limbs <= 2^16 and (value
        # argument: limb16 * 2^256 <= value) the top limb is <= 3, so the
        # r256 fold stays far below u32.
        return self._fold_resolve(_coarse(a + b, WIDE))

    def sub(self, a, b):
        # a + (C - b) with C = sub_c (relaxed multiple of p, limbwise
        # dominant): limbs < 2^18, value < 2^260 -> coarse top limb <= 15.
        return self._fold_resolve(_coarse(a + (self.sub_c - b), WIDE))

    def mul(self, a, b):
        # Schoolbook product with pure-VPU column accumulation: the
        # (i, j) limb products land in column i+j (lo half) and i+j+1
        # (hi half) via statically shifted adds — no dtype conversions,
        # no MXU round-trips (Mosaic's f32 dot at usable precision costs
        # 6 bf16 passes and dominated the kernel).
        prod = a[:, None, :] * b[None, :, :]  # (17, 17, BLK), exact u32
        plo = prod & jnp.uint32(MASK)
        phi = prod >> jnp.uint32(LIMB_BITS)
        blk = a.shape[-1]
        parts = []
        for i in range(WIDE):
            # row i contributes at columns i..i+17 (lo at +0, hi at +1)
            row = jnp.concatenate(
                [plo[i], jnp.zeros((1, blk), jnp.uint32)]
            ) + jnp.concatenate([jnp.zeros((1, blk), jnp.uint32), phi[i]])
            parts.append(
                jnp.pad(row, [(i, 2 * WIDE - (WIDE + 1) - i), (0, 0)])
            )
        # balanced tree sum keeps the column bound (< 34 * 2^17) tight
        while len(parts) > 1:
            parts = [
                parts[k] + parts[k + 1] if k + 1 < len(parts) else parts[k]
                for k in range(0, len(parts), 2)
            ]
        cols = _coarse(parts[0], 2 * WIDE)  # bounded 34-limb product
        return self._reduce_cols(cols)

    def _reduce_cols(self, cols):
        """Coarse 34-limb product (limbs <= 2^16 + 2^6) -> invariant
        element (< 2^257).

        Solinas recombination of the 512-bit range (limbs 0..31): one
        small signed f32 MXU contraction (measured faster than the
        equivalent pad+add chain on the VPU), negatives absorbed by the
        bias constant (a relaxed multiple of p dominating them).  The
        contraction is linear in the limb vector, so coarse — not
        canonical — limbs suffice: |sum| <= 12 * 2^16.1 + bias < 2^21,
        exact in f32 (< 2^24).  Limb 32 is <= 2^6.2 by the value bound
        (product < 2^514), so the 2^512-fold fits u32."""
        signed = jnp.dot(
            self.solmat,
            _u2f(cols),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        acc = _f2u(signed + _u2f(self.bias[:NLIMBS]))
        acc = acc + cols[32:33] * self.r512
        top = jnp.broadcast_to(self.bias[NLIMBS:], (1, acc.shape[-1]))
        acc = jnp.concatenate([acc, top], axis=0)
        # acc limbs < 2^23, value < 2^263 -> coarse top limb <= 2^7.
        return self._fold_resolve(_coarse(acc, WIDE))

    def sqr(self, a):
        return self.mul(a, a)

    def mul_const(self, a, k: int):
        # a*k limbs < 2^24; one coarse pass leaves the top limb <= 2^9
        # (a16 <= 1 so a16*k <= 256, plus a sub-2^8 carry) — no carry out
        # of limb 16, so width 17 suffices and the r256 fold fits u32.
        assert 0 < k <= 256
        return self._fold_resolve(_coarse(a * jnp.uint32(k), WIDE))

    def canon(self, a):
        v = self._minifold(a)
        for _ in range(3):
            v = _cond_sub(v, self.p_limbs)
        return v

    def is_zero(self, a):
        # An invariant element (canonical limbs, value < 2^257 < 3p) is
        # 0 mod p iff it equals 0, p, or 2p exactly — three limbwise
        # compares instead of canon's four carry networks.  int32 0/1
        # flag via mismatch counts, no i1 vectors (Mosaic reduces i1 via
        # i8 and cannot truncate back).

        def mism(c):
            return jnp.sum((a != c).astype(jnp.int32), axis=0, keepdims=True)

        n = mism(jnp.zeros_like(a)) * mism(self.p_limbs) * mism(self.p2_limbs)
        return (n == 0).astype(jnp.int32)


def _cond_sub(a, b_const):
    """a - b if a >= b else a; canonical limbs, (17, BLK)."""
    width = a.shape[0]
    notb = jnp.uint32(MASK) - b_const
    t = a + notb + _row_one(width, a.shape[-1])
    t = _resolve(t, width + 1)
    ge = (t[width:width + 1] > 0).astype(jnp.int32)
    return _sel(ge, t[:width], a)


def _row_one(rows: int, blk: int):
    """(rows, blk) uint32 with 1s in row 0, 0 elsewhere (scatter-free)."""
    return jnp.concatenate(
        [jnp.ones((1, blk), jnp.uint32), jnp.zeros((rows - 1, blk), jnp.uint32)]
    )


# ---------------------------------------------------------------------------
# Point formulas: identical structure to ec.py, (limb, lane) layout,
# infinity flags shaped (1, BLK).
# ---------------------------------------------------------------------------


# Flags are int32 0/1 vectors (1, BLK) throughout the point formulas:
# Mosaic handles i1 vectors poorly (broadcasts/loop carries round-trip
# through i8 and fail to truncate back), so selection is arithmetic.


def _sel(c, a, b):
    """c (1, BLK) int32 0/1 selects a (u32) else b via an XOR mask."""
    mask = (-c).astype(jnp.uint32)  # 0 or 0xffffffff
    return b ^ ((a ^ b) & mask)


def _fsel(c, a, b):
    """Flag select: all of c/a/b int32 0/1."""
    return b + (a - b) * c


def _pt_sel(c, p1, p2):
    return (
        _sel(c, p1[0], p2[0]),
        _sel(c, p1[1], p2[1]),
        _sel(c, p1[2], p2[2]),
        _fsel(c, p1[3], p2[3]),
    )


def _one(blk):
    return _row_one(WIDE, blk)


def _dbl(fp, p):
    x, y, z, inf = p
    delta = fp.sqr(z)
    gamma = fp.sqr(y)
    beta = fp.mul(x, gamma)
    alpha = fp.mul_const(fp.mul(fp.sub(x, delta), fp.add(x, delta)), 3)
    x3 = fp.sub(fp.sqr(alpha), fp.mul_const(beta, 8))
    z3 = fp.sub(fp.sub(fp.sqr(fp.add(y, z)), gamma), delta)
    y3 = fp.sub(
        fp.mul(alpha, fp.sub(fp.mul_const(beta, 4), x3)),
        fp.mul_const(fp.sqr(gamma), 8),
    )
    return (x3, y3, z3, inf)


def _add_full(fp, p1, p2):
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
    out = _pt_sel(h_zero * r_zero, _dbl(fp, p1), out)
    out = (out[0], out[1], out[2],
           jnp.maximum(out[3], h_zero * (1 - r_zero)))
    out = _pt_sel(inf2, p1, out)
    out = _pt_sel(inf1, p2, out)
    return out


def _add_mixed(fp, p1, a2):
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
    out = _pt_sel(h_zero * r_zero, _dbl(fp, p1), out)
    out = (out[0], out[1], out[2],
           jnp.maximum(out[3], h_zero * (1 - r_zero)))
    a2j = (ax, ay, _one(ax.shape[-1]), ainf)
    out = _pt_sel(ainf, p1, out)
    out = _pt_sel(inf1, a2j, out)
    return out


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------


def _onehot(digit, blk):
    """digit (1, BLK) int32 -> (16, BLK) int32 one-hot (signed: Mosaic
    has no unsigned reductions)."""
    t = jax.lax.broadcasted_iota(jnp.int32, (TABLE, blk), 0)
    return (t == digit).astype(jnp.int32)


def _isum(mask_i32, tab_u32):
    """One-hot select: sum(mask * table) over entries in int32 (Mosaic
    has no unsigned reductions; limbs < 2^16 so this is exact)."""
    return jnp.sum(mask_i32 * tab_u32.astype(jnp.int32), axis=0).astype(
        jnp.uint32
    )


def _unpack_words(wref):
    """(8, BLK) uint32 32-bit words -> (17, BLK) canonical 16-bit limbs.
    Inputs are canonical field elements (< 2^256), so the top limb is 0.
    Word inputs quarter the host->device transfer."""
    w = wref[:]
    rows = []
    for i in range(8):
        rows.append(w[i:i + 1] & jnp.uint32(MASK))
        rows.append(w[i:i + 1] >> jnp.uint32(LIMB_BITS))
    rows.append(jnp.zeros_like(rows[0]))
    return jnp.concatenate(rows, axis=0)


KEYTAB = 256  # fixed unique-key table size for the dedup kernel variant


def _kernel(qx_ref, qy_ref, d1_ref, d2_ref, c0_ref, flags_ref,
            solmat_ref, bias_ref, r256_ref, r512_ref,
            subc_ref, plimbs_ref, nlimbs_ref, gx_ref, gy_ref,
            out_ref, tabx, taby, tabz, tabinf):
    fp = FpP256(
        solmat_ref[:], bias_ref[:], r256_ref[:],
        r512_ref[:], subc_ref[:], plimbs_ref[:],
    )
    qx = _unpack_words(qx_ref)
    qy = _unpack_words(qy_ref)
    _kernel_body(fp, qx, qy, d1_ref, d2_ref, c0_ref, flags_ref,
                 nlimbs_ref, gx_ref, gy_ref, out_ref,
                 tabx, taby, tabz, tabinf)


def _kernel_dedup(ktabx_ref, ktaby_ref, kidx_ref, d1_ref, d2_ref, c0_ref,
                  flags_ref, solmat_ref, bias_ref, r256_ref, r512_ref,
                  subc_ref, plimbs_ref, nlimbs_ref, gx_ref, gy_ref,
                  out_ref, tabx, taby, tabz, tabinf):
    """Variant with a shared unique-key table: real blocks carry few
    distinct endorser keys, so per-lane pubkeys (64B/sig of transfer)
    collapse to a (8, KEYTAB)-word table + one u32 index per lane.
    Per-lane coordinates materialize via an exact one-hot f32 MXU
    contraction (limbs < 2^16, one-hot sum -> < 2^24)."""
    fp = FpP256(
        solmat_ref[:], bias_ref[:], r256_ref[:],
        r512_ref[:], subc_ref[:], plimbs_ref[:],
    )
    blk = kidx_ref.shape[-1]
    tx = _unpack_words(ktabx_ref)  # (17, KEYTAB); shape-agnostic helper
    ty = _unpack_words(ktaby_ref)
    idx = kidx_ref[0:1].astype(jnp.int32)  # (1, blk)
    iota = jax.lax.broadcasted_iota(jnp.int32, (KEYTAB, blk), 0)
    oh = (iota == idx).astype(jnp.float32)  # (KEYTAB, blk)
    qx = _f2u(jnp.dot(_u2f(tx), oh, precision=jax.lax.Precision.HIGHEST))
    qy = _f2u(jnp.dot(_u2f(ty), oh, precision=jax.lax.Precision.HIGHEST))
    _kernel_body(fp, qx, qy, d1_ref, d2_ref, c0_ref, flags_ref,
                 nlimbs_ref, gx_ref, gy_ref, out_ref,
                 tabx, taby, tabz, tabinf)


def _kernel_body(fp, qx, qy, d1_ref, d2_ref, c0_ref, flags_ref,
                 nlimbs_ref, gx_ref, gy_ref, out_ref,
                 tabx, taby, tabz, tabinf):
    blk = qx.shape[-1]
    fin = jnp.zeros((1, blk), jnp.int32)  # flags are int32 0/1

    # -- Q window table (entries 0, 1 direct; 2..15 via mixed-add chain) --
    zero = jnp.zeros((1, WIDE, blk), jnp.uint32)
    tabx[0:1] = zero
    taby[0:1] = zero
    tabz[0:1] = zero
    tabinf[0:1] = jnp.ones((1, blk), jnp.uint32)
    tabx[1:2] = qx[None]
    taby[1:2] = qy[None]
    tabz[1:2] = _one(blk)[None]
    tabinf[1:2] = jnp.zeros((1, blk), jnp.uint32)
    q_aff = (qx, qy, fin)

    def build(i, _):
        prev = (
            tabx[pl.ds(i - 1, 1)][0],
            taby[pl.ds(i - 1, 1)][0],
            tabz[pl.ds(i - 1, 1)][0],
            tabinf[pl.ds(i - 1, 1)].astype(jnp.int32),
        )
        nxt = _add_mixed(fp, prev, q_aff)
        tabx[pl.ds(i, 1)] = nxt[0][None]
        taby[pl.ds(i, 1)] = nxt[1][None]
        tabz[pl.ds(i, 1)] = nxt[2][None]
        tabinf[pl.ds(i, 1)] = nxt[3].astype(jnp.uint32)
        return 0

    jax.lax.fori_loop(2, TABLE, build, 0)

    gx = gx_ref[:][:, :, None]  # (16, 17, 1)
    gy = gy_ref[:][:, :, None]

    # -- 64-window joint ladder, MSB first.  The infinity flag crosses
    # the fori_loop boundary as int32: an i1 loop carry round-trips
    # through i8 in Mosaic, which cannot truncate back to i1. --
    zeros = jnp.zeros((WIDE, blk), jnp.uint32)
    r0 = (zeros, zeros, zeros, jnp.ones((1, blk), jnp.int32))

    def window(w, r):
        for _ in range(4):
            r = _dbl(fp, r)
        # digits arrive packed 8-per-u32: word w//8, nibble w%8
        shift = (jnp.uint32(4) * (w % 8).astype(jnp.uint32))
        w1 = ((d1_ref[pl.ds(w // 8, 1)] >> shift) & jnp.uint32(0xF)).astype(
            jnp.int32
        )  # (1, BLK)
        w2 = ((d2_ref[pl.ds(w // 8, 1)] >> shift) & jnp.uint32(0xF)).astype(
            jnp.int32
        )
        oh1 = _onehot(w1, blk)  # (16, BLK) int32
        ga = (
            _isum(oh1[:, None, :], gx),
            _isum(oh1[:, None, :], gy),
            (w1 == 0).astype(jnp.int32),
        )
        r = _add_mixed(fp, r, ga)
        oh2 = _onehot(w2, blk)
        qj = (
            _isum(oh2[:, None, :], tabx[:]),
            _isum(oh2[:, None, :], taby[:]),
            _isum(oh2[:, None, :], tabz[:]),
            jnp.sum(oh2 * tabinf[:].astype(jnp.int32), axis=0,
                    keepdims=True),
        )
        r = _add_full(fp, r, qj)
        return r

    x, y, z, inf = jax.lax.fori_loop(0, NWINDOWS, window, r0)

    # -- final check: x(R) == r (mod n) without inversion --
    z2 = fp.sqr(z)
    x_can = fp.canon(x)

    def matches(cand):
        n = jnp.sum(
            (x_can != fp.canon(fp.mul(cand, z2))).astype(jnp.int32),
            axis=0,
            keepdims=True,
        )
        return (n == 0).astype(jnp.int32)

    cand0 = _unpack_words(c0_ref)
    m0 = matches(cand0)
    # cand1 = r + n, built on-device (saves a 32B/sig host transfer);
    # only consulted when the host flagged r + n < p, so the unreduced
    # value (< 2^257, canonicalized below) is safe to feed fp.mul.
    cand1 = fp._fold_resolve(_coarse(cand0 + nlimbs_ref[:], WIDE))
    m1 = matches(cand1)
    cand1_ok = flags_ref[0:1].astype(jnp.int32)
    valid = flags_ref[1:2].astype(jnp.int32)
    # z == 0 means the ladder degenerated (possible only for
    # out-of-group inputs, e.g. an off-curve or zero public key); the
    # x(R) check would then compare 0 == cand*0 and accept everything,
    # so such lanes are forced invalid (defense in depth — the host
    # stack never feeds off-curve keys).
    z_ok = 1 - fp.is_zero(z)
    ok = (
        jnp.minimum(m0 + m1 * cand1_ok, 1)
        * (1 - jnp.minimum(inf, 1)) * z_ok * valid
    )
    # (1, 8, BLK) block: row dim padded to the TPU sublane tile
    out_ref[:] = jnp.broadcast_to(
        ok.astype(jnp.uint32)[None], out_ref.shape
    )


def _specs(blk):
    lane_spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, blk), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    const_spec = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i: tuple(0 for _ in shape), memory_space=pltpu.VMEM
    )
    return lane_spec, const_spec


def _common_specs(const_spec):
    return [
        const_spec((NLIMBS, 2 * WIDE)),           # solmat
        const_spec((WIDE, 1)),                    # bias
        const_spec((NLIMBS, 1)),                  # r256
        const_spec((NLIMBS, 1)),                  # r512
        const_spec((WIDE, 1)),                    # sub_c
        const_spec((WIDE, 1)),                    # p_limbs
        const_spec((WIDE, 1)),                    # n_limbs (group order)
        const_spec((TABLE, WIDE)),                # gx
        const_spec((TABLE, WIDE)),                # gy
    ]


def _pallas_opts(nblocks, blk, interpret):
    return dict(
        grid=(nblocks,),
        out_specs=pl.BlockSpec(
            (1, 8, blk), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((nblocks, 8, blk), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((TABLE, WIDE, blk), jnp.uint32),  # tabx
            pltpu.VMEM((TABLE, WIDE, blk), jnp.uint32),  # taby
            pltpu.VMEM((TABLE, WIDE, blk), jnp.uint32),  # tabz
            pltpu.VMEM((TABLE, blk), jnp.uint32),        # tabinf
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _build_call(nblocks: int, blk: int, interpret: bool):
    lane_spec, const_spec = _specs(blk)
    fn = pl.pallas_call(
        _kernel,
        in_specs=[
            lane_spec(8),      # qx (packed 32-bit words)
            lane_spec(8),      # qy
            lane_spec(8),      # d1 (8 window digits per word)
            lane_spec(8),      # d2
            lane_spec(8),      # cand0
            lane_spec(2),      # flags: [cand1_ok; valid]
        ] + _common_specs(const_spec),
        name="pallas_ec_p256_verify",
        **_pallas_opts(nblocks, blk, interpret),
    )
    return named_jit(fn, "pallas_ec_p256_verify")


@functools.lru_cache(maxsize=None)
def _build_call_dedup(nblocks: int, blk: int, interpret: bool):
    lane_spec, const_spec = _specs(blk)
    fn = pl.pallas_call(
        _kernel_dedup,
        in_specs=[
            const_spec((8, KEYTAB)),  # ktabx (unique-key words)
            const_spec((8, KEYTAB)),  # ktaby
            lane_spec(1),      # kidx (u32 per lane)
            lane_spec(8),      # d1
            lane_spec(8),      # d2
            lane_spec(8),      # cand0
            lane_spec(2),      # flags
        ] + _common_specs(const_spec),
        name="pallas_ec_p256_verify_ktab",
        **_pallas_opts(nblocks, blk, interpret),
    )
    return named_jit(fn, "pallas_ec_p256_verify_ktab")


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def prepare_packed(items) -> dict:
    """Host preprocessing straight to PACKED device inputs.

    Replaces ec.prepare_batch + prepack for the hot path: the scalar
    field work uses ONE modular inversion for the whole batch
    (Montgomery's trick over all s values) and the array packing is
    vectorized numpy over little-endian byte dumps — no per-limb Python
    loops.  ~10x faster than ec.prepare_batch on large batches.

    items: (x, y, digest32, r, s) tuples.  Returns the packed-array dict
    that verify_packed consumes.
    """
    from fabric_tpu.csp.api import P256_N

    n = len(items)
    half_n = P256_N >> 1
    valid = np.zeros(n, bool)
    c1_ok = np.zeros(n, bool)
    svals = []
    for i, it in enumerate(items):
        r, s = it[3], it[4]
        if (
            isinstance(r, int)
            and isinstance(s, int)
            and 0 < r < P256_N
            and 0 < s <= half_n
            and len(it[2]) == 32
        ):
            valid[i] = True
            svals.append(s)
        else:
            svals.append(1)

    # Montgomery batch inversion: one pow, 3(n-1) modular multiplies
    prefix = [1] * (n + 1)
    for i in range(n):
        prefix[i + 1] = prefix[i] * svals[i] % P256_N
    inv = pow(prefix[n], -1, P256_N)

    xb = bytearray(32 * n)
    yb = bytearray(32 * n)
    u1b = bytearray(32 * n)
    u2b = bytearray(32 * n)
    c0b = bytearray(32 * n)
    for i in range(n - 1, -1, -1):
        it = items[i]
        w = inv * prefix[i] % P256_N
        inv = inv * svals[i] % P256_N
        o = 32 * i
        if not valid[i]:
            x, y, u1, u2, c0 = P256_GX, P256_GY, 1, 1, 1
        else:
            x, y = it[0], it[1]
            r = it[3]
            e = int.from_bytes(it[2], "big") % P256_N
            u1 = e * w % P256_N
            u2 = r * w % P256_N
            c0 = r
            if r + P256_N < P256_P:
                c1_ok[i] = True
        xb[o:o + 32] = x.to_bytes(32, "little")
        yb[o:o + 32] = y.to_bytes(32, "little")
        u1b[o:o + 32] = u1.to_bytes(32, "little")
        u2b[o:o + 32] = u2.to_bytes(32, "little")
        c0b[o:o + 32] = c0.to_bytes(32, "little")

    def words(buf):  # (B, 32) LE bytes -> (8, B) u32 words
        return np.ascontiguousarray(
            np.frombuffer(bytes(buf), np.uint32).reshape(n, 8).T
        )

    def digits_packed(buf):  # LE bytes -> (8, B) u32, MSB-first nibbles
        u8 = np.frombuffer(bytes(buf), np.uint8).reshape(n, 32)
        nibbles = np.empty((n, 64), np.uint32)
        nibbles[:, 0::2] = u8 & 0xF        # nibble m even = low
        nibbles[:, 1::2] = u8 >> 4
        d = nibbles[:, ::-1]               # digit k = nibble 63-k
        shifts = (np.uint32(4) * np.arange(8, dtype=np.uint32))[None, None]
        return np.ascontiguousarray(
            (d.reshape(n, 8, 8) << shifts).sum(axis=2, dtype=np.uint32).T
        )

    return {
        "qx": words(xb),
        "qy": words(yb),
        "d1": digits_packed(u1b),
        "d2": digits_packed(u2b),
        "cand0": words(c0b),
        "cand1_ok": c1_ok,
        "valid": valid,
    }


def verify_packed(packed: dict, blk: int = BLK,
                  interpret: bool | None = None):
    """Run the kernel on prepare_packed / dedup_keys output; returns a
    lazy device array handle via a callable -> (B,) bool (so callers can
    dispatch several chunks before blocking on any result).

    When `packed` carries "kidx"/"ktabx"/"ktaby" (the deduplicated-key
    layout from `dedup_keys`), the key-table kernel variant runs: 64B of
    per-lane pubkey transfer collapses to one shared (8, 256)-word
    table + a u32 index per lane."""
    if interpret is None:
        interpret = _use_interpret()
    dedup = "kidx" in packed
    b = (packed["kidx"] if dedup else packed["qx"]).shape[-1]
    nb = -(-b // blk)
    pad = nb * blk - b

    def padlanes(a):
        if pad:
            a = np.concatenate(
                [a, np.zeros(a.shape[:-1] + (pad,), a.dtype)], axis=-1
            )
        return a

    flags = np.stack(
        [
            np.asarray(packed["cand1_ok"], np.uint32),
            np.asarray(packed["valid"], np.uint32),
        ]
    )
    c = _consts()
    if dedup:
        head = [
            packed["ktabx"],
            packed["ktaby"],
            padlanes(packed["kidx"].reshape(1, -1)),
        ]
    else:
        head = [padlanes(packed["qx"]), padlanes(packed["qy"])]
    inputs = head + [
        padlanes(packed["d1"]),
        padlanes(packed["d2"]),
        padlanes(packed["cand0"]),
        padlanes(flags),
        c["solmat"],
        c["bias"],
        c["r256"],
        c["r512"],
        c["sub_c"],
        c["p_limbs"],
        c["n_limbs"],
        c["gx"][:, :, 0],
        c["gy"][:, :, 0],
    ]
    build = _build_call_dedup if dedup else _build_call
    out = build(nb, blk, interpret)(*inputs)

    def collect():
        return np.asarray(out)[:, 0, :].reshape(-1)[:b].astype(bool)

    return collect


def dedup_keys(packed: dict, seen: dict | None = None) -> dict:
    """Rewrite a packed dict into the deduplicated-key layout when the
    batch uses at most KEYTAB distinct public keys (typical blocks carry
    a handful of endorser identities); otherwise return it unchanged.
    Saves 64B/signature of host->device transfer.  `seen`, where given,
    is told how many `distinct` keys the batch held.

    The table shape is pinned to (8, KEYTAB): the kernel's one-hot is
    hard-wired to KEYTAB lanes, and an index outside it would select the
    zero point — which the kernel's z==0 guard rejects, but the layout
    never produces such an index in the first place."""
    qx, qy = packed["qx"], packed["qy"]
    cols = np.concatenate([qx, qy]).T  # (B, 16) words per key
    uniq, idx = np.unique(cols, axis=0, return_inverse=True)
    if seen is not None:
        seen["distinct"] = int(uniq.shape[0])
    if uniq.shape[0] > KEYTAB:
        return packed
    ktab = np.zeros((KEYTAB, 16), np.uint32)
    ktab[: uniq.shape[0]] = uniq
    out = {k: v for k, v in packed.items() if k not in ("qx", "qy")}
    out["ktabx"] = np.ascontiguousarray(ktab[:, :8].T)
    out["ktaby"] = np.ascontiguousarray(ktab[:, 8:].T)
    out["kidx"] = idx.astype(np.uint32)
    return out


def _pack_words(limbs_bn: np.ndarray) -> np.ndarray:
    """(B, 17) canonical limbs -> (8, B) uint32 32-bit words (top limb
    must be 0, true for canonical < 2^256 field elements)."""
    a = np.asarray(limbs_bn, np.uint32)
    return np.ascontiguousarray(
        (a[:, 0:16:2] | (a[:, 1:17:2] << np.uint32(16))).T
    )


def _pack_digits(d_bn: np.ndarray) -> np.ndarray:
    """(B, 64) 4-bit window digits -> (8, B) uint32, 8 digits per word
    (digit k in bits 4*(k%8) of word k//8)."""
    d = np.asarray(d_bn, np.uint32).reshape(-1, 8, 8)
    shifts = (np.uint32(4) * np.arange(8, dtype=np.uint32))[None, None, :]
    return np.ascontiguousarray((d << shifts).sum(axis=2, dtype=np.uint32).T)


def prepack(prep: dict, blk: int = BLK) -> tuple[list, int]:
    """prepare_batch arrays -> padded, packed device inputs (~4x smaller
    host->device transfers than raw limbs)."""
    b = prep["qx"].shape[0]
    nb = -(-b // blk)
    pad = nb * blk - b

    def padded(a):
        a = np.asarray(a)
        if pad:
            a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        return a

    flags = np.stack(
        [
            padded(np.asarray(prep["cand1_ok"], np.uint32)),
            padded(np.asarray(prep["valid"], np.uint32)),
        ]
    )
    c = _consts()
    inputs = [
        _pack_words(padded(prep["qx"])),
        _pack_words(padded(prep["qy"])),
        _pack_digits(padded(prep["d1"])),
        _pack_digits(padded(prep["d2"])),
        _pack_words(padded(prep["cand0"])),
        flags,
        c["solmat"],
        c["bias"],
        c["r256"],
        c["r512"],
        c["sub_c"],
        c["p_limbs"],
        c["n_limbs"],
        c["gx"][:, :, 0],
        c["gy"][:, :, 0],
    ]
    return inputs, b


def verify_prepared(qx, qy, d1, d2, cand0, cand1, cand1_ok, valid,
                    blk: int = BLK, interpret: bool | None = None):
    """Same contract as ec.verify_prepared (prepare_batch arrays in,
    (B,) bool out) via the fused Pallas kernel; pads to a lane multiple."""
    if interpret is None:
        interpret = _use_interpret()
    inputs, b = prepack(
        dict(qx=qx, qy=qy, d1=d1, d2=d2, cand0=cand0, cand1=cand1,
             cand1_ok=cand1_ok, valid=valid),
        blk,
    )
    nb = inputs[0].shape[1] // blk
    call = _build_call(nb, blk, interpret)
    out = call(*inputs)
    return np.asarray(out)[:, 0, :].reshape(-1)[:b].astype(bool)


__all__ = [
    "verify_prepared",
    "prepare_packed",
    "verify_packed",
    "FpP256",
    "BLK",
]
