"""Batched idemix Schnorr recomputation on the device (BN254 G1).

The idemix verify hot path (reference idemix/signature.go:243 Ver)
re-derives three ZK commitments per signature — small G1 multi-scalar
multiplications — before the two pairings.  Round 2 ran this on the
native CPU backend; here the whole batch's MSMs execute as ONE jitted
XLA program over the shared limb machinery (csp/tpu/limbs.py, the same
16-bit-limb arithmetic the ECDSA kernel uses), with the pairings staying
on the native host path (csp's verify_batch collapses them to two per
batch via random linear combination).

Per signature the verifier needs (signature.py _relations +
schnorr.recompute_commitments, with targets flattened into the MSMs —
y1^(−c) = a_bar^(−c)·b_prime^{c}, y2^(−c) = G1^{c}·Π h_attrs[i]^{c·m_i}):

  T1 = a_bar^{-c} · b_prime^{c} · a_prime^{z_neg_e} · h_rand^{z_r2}
  T2 = G1^{c} · h_sk^{z_sk} · h_rand^{z_s'} · Π_i h_attrs[i]^{s_i}
         · b_prime^{z_neg_r3}         s_i = c·m_i (disclosed) | z_mi (hidden)
  T3 = nym^{-c} · h_sk^{z_sk} · h_rand^{z_r_nym}

Shared bases (G1, h_sk, h_rand, h_attrs[*]) come as precomputed affine
4-bit window tables (per issuer key, built once on host); per-lane bases
(a_prime, a_bar, b_prime, nym) get device-built Jacobian tables.  One
MSB-first 64-window ladder accumulates all three commitments; outputs
are Jacobian, normalized on host with one batched inversion.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

import jax
import jax.numpy as jnp

from fabric_tpu.csp.tpu import ec, limbs
from fabric_tpu.csp.tpu.ec import Aff, Jac
from fabric_tpu.csp.tpu.limbs import WIDE
from fabric_tpu.idemix import bn254 as bn

NWINDOWS = 64
TABLE = 16
# pad buckets (one XLA compile per (bucket, n_attrs)); batches beyond
# the largest bucket chunk at _MAX_LANES so compiled shapes are reused
_BUCKETS = (16, 64, 256, 1024)
_MAX_LANES = _BUCKETS[-1]

# per-lane scalar slots, fixed order (public: the Pallas engine keys
# its lane layout off this tuple)
LANE_BASES = ("a_prime", "a_bar", "b_prime", "nym")
_LANE_BASES = LANE_BASES  # backwards-compatible alias

# Pallas failure bookkeeping, scoped per (batch, n_attrs) SHAPE with a
# bounded retry budget: one transient failure (an OOM at an unusually
# large bucket) must not permanently downgrade every later batch to
# the slower XLA engine, while a shape that fails repeatedly stops
# re-packing + re-failing + re-warning each time.  chip_smoke.py
# asserts this stays empty: a run that fell back did not run the kernel.
_PALLAS_FAILURES: dict = {}
_PALLAS_MAX_FAILURES = 2


def _pallas_preferred(shape=None) -> bool:
    """Use the Pallas engine only where it runs compiled: on the TPU
    backend (or when a test forces it — interpret mode executes the
    grid in Python and would be far slower than the XLA fallback it
    preempts on CPU/GPU hosts)."""
    if os.environ.get("FABRIC_BN254_NO_PALLAS"):
        return False
    if _PALLAS_FAILURES.get(shape, 0) >= _PALLAS_MAX_FAILURES:
        return False
    if os.environ.get("FABRIC_BN254_FORCE_PALLAS"):
        return True
    return jax.default_backend() == "tpu"


def _fp():
    # Montgomery context: all device coordinates live in Montgomery form
    # x·R mod p (R = 2**272), where a 254-bit mul costs one REDC instead
    # of ~6 fold passes (limbs.MontMod) — conversion happens only in the
    # host int<->limb boundary helpers below
    return limbs.mont_ctx(bn.P)


def _to_limbs(x: int) -> np.ndarray:
    return limbs.int_to_limbs(_fp().to_mont_int(x), WIDE)


def _recode(u: int) -> np.ndarray:
    return np.asarray(
        [(u >> (4 * (NWINDOWS - 1 - k))) & 15 for k in range(NWINDOWS)],
        np.int32,
    )


@functools.lru_cache(maxsize=8)
def shared_multiples(ipk_key: tuple) -> tuple:
    """k*P for k in 0..15 per shared base (None = infinity): the raw
    host scalar multiplications both device engines derive their window
    tables from (one cache, not one per engine).  ipk_key is the
    hashable ((x, y), ...) tuple of (G1, h_sk, h_rand, *h_attrs)."""
    return tuple(
        tuple(bn.g1_mul(pt, k) if k else None for k in range(TABLE))
        for pt in ipk_key
    )


@functools.lru_cache(maxsize=8)
def shared_tables(ipk_key: tuple) -> dict:
    """Affine 4-bit window tables (16 multiples) for the issuer key's
    fixed bases in the XLA engine's limb layout."""
    tabs_x, tabs_y, tabs_inf = [], [], []
    for row in shared_multiples(ipk_key):
        xs, ys, infs = [], [], []
        for q in row:
            if q is None:
                xs.append(_to_limbs(0))
                ys.append(_to_limbs(0))
                infs.append(True)
            else:
                xs.append(_to_limbs(q[0]))
                ys.append(_to_limbs(q[1]))
                infs.append(False)
        tabs_x.append(np.stack(xs))
        tabs_y.append(np.stack(ys))
        tabs_inf.append(np.asarray(infs))
    return {
        "x": np.stack(tabs_x),  # (n_shared, 16, 17)
        "y": np.stack(tabs_y),
        "inf": np.stack(tabs_inf),  # (n_shared, 16)
    }


def _dbl_a0(fp, p: Jac) -> Jac:
    """Jacobian doubling for a = 0 (BN254: y^2 = x^3 + 3), dbl-2009-l."""
    a = fp.sqr(p.x)
    b = fp.sqr(p.y)
    c = fp.sqr(b)
    d_inner = fp.sqr(fp.add(p.x, b))
    d = fp.mul_const(fp.sub(fp.sub(d_inner, a), c), 2)
    e = fp.mul_const(a, 3)
    f = fp.sqr(e)
    x3 = fp.sub(f, fp.add(d, d))
    y3 = fp.sub(fp.mul(e, fp.sub(d, x3)), fp.mul_const(c, 8))
    z3 = fp.mul_const(fp.mul(p.y, p.z), 2)
    return Jac(x3, y3, z3, p.inf)


def _lane_window_table(fp, px, py, pinf):
    """Jacobian multiples 0..15 of per-lane affine points, a=0 chain."""
    b = px.shape[:-1]
    zero = jnp.zeros(b + (WIDE,), jnp.uint32)
    inf_t = jnp.ones(b, bool)
    p_aff = Aff(px, py, pinf)
    p1 = Jac(px, py, fp.one_like(px), pinf)

    def step(p: Jac, _):
        nxt = ec.point_add_mixed(fp, p, p_aff, dbl=_dbl_a0)
        return nxt, nxt

    _, rest = jax.lax.scan(step, p1, None, length=TABLE - 2)
    cat = lambda z, o, r: jnp.concatenate(  # noqa: E731
        [z[..., None, :], o[..., None, :], jnp.moveaxis(r, 0, -2)], axis=-2
    )
    tinf = jnp.concatenate(
        [inf_t[..., None], pinf[..., None], jnp.moveaxis(rest.inf, 0, -1)],
        axis=-1,
    )
    return (
        cat(zero, p1.x, rest.x),
        cat(zero, p1.y, rest.y),
        cat(zero, p1.z, rest.z),
        tinf,
    )


def commitments_kernel(
    lane_x, lane_y, lane_inf,      # (4, B, 17) / (4, B)  a',abar,b',nym
    shared_x, shared_y, shared_inf,  # (n_shared, 16, 17) / (n_shared, 16)
    digits,                        # (n_terms, B, 64) int32
    term_table,                    # (n_terms,) int32: unified table index
    term_acc,                      # (n_terms,) int32: accumulator 0..2
):
    """One joint 64-window MSB-first ladder accumulating T1, T2, T3.

    Kept deliberately SMALL as a traced graph: the three accumulators
    are one stacked (3, B) Jacobian (one vectorized doubling), all
    window tables live in one (n_tables, B, 16) stack, and the per-term
    adds run as an inner scan whose body is a single full Jacobian add
    with dynamic table/accumulator indexing.  (An unrolled-terms
    variant with static table slices and mixed affine adds was measured
    SLOWER on the chip — 2.19s vs 1.46s at 1024 lanes — and tripled
    compile time; the scan structure is what lets XLA keep the working
    set resident, so it stays.)"""
    fp = _fp()
    b = lane_x.shape[1]
    n_shared = shared_x.shape[0]

    # per-lane Jacobian tables, all 4 bases at once (batch dims (4, B))
    ltx, lty, ltz, ltinf = _lane_window_table(fp, lane_x, lane_y, lane_inf)
    # unified stack: shared tables broadcast over lanes, z = 1, then the
    # 4 per-lane tables.  (n_tables, B, 16, 17) / (n_tables, B, 16)
    ones = jnp.broadcast_to(
        fp.one_like(shared_x)[:, None], (n_shared, b, TABLE, WIDE)
    )
    utx = jnp.concatenate(
        [jnp.broadcast_to(shared_x[:, None], (n_shared, b, TABLE, WIDE)),
         ltx], axis=0
    )
    uty = jnp.concatenate(
        [jnp.broadcast_to(shared_y[:, None], (n_shared, b, TABLE, WIDE)),
         lty], axis=0
    )
    utz = jnp.concatenate([ones, ltz], axis=0)
    utinf = jnp.concatenate(
        [jnp.broadcast_to(shared_inf[:, None], (n_shared, b, TABLE)),
         ltinf], axis=0
    )

    zeros = jnp.zeros((3, b, WIDE), jnp.uint32)
    acc0 = Jac(zeros, zeros, zeros, jnp.ones((3, b), bool))

    def window(acc, w):
        for _ in range(4):
            acc = _dbl_a0(fp, acc)  # all 3 accumulators at once

        def term(acc, t):
            dig = jax.lax.dynamic_index_in_dim(
                digits, t, axis=0, keepdims=False
            )[:, w]  # (B,)
            ti = term_table[t]
            gx = jax.lax.dynamic_index_in_dim(utx, ti, 0, keepdims=False)
            gy = jax.lax.dynamic_index_in_dim(uty, ti, 0, keepdims=False)
            gz = jax.lax.dynamic_index_in_dim(utz, ti, 0, keepdims=False)
            ginf = jax.lax.dynamic_index_in_dim(
                utinf, ti, 0, keepdims=False
            )
            q = ec._gather_pt(gx, gy, gz, ginf, dig)
            ai = term_acc[t]
            cur = Jac(
                jax.lax.dynamic_index_in_dim(acc.x, ai, 0, False),
                jax.lax.dynamic_index_in_dim(acc.y, ai, 0, False),
                jax.lax.dynamic_index_in_dim(acc.z, ai, 0, False),
                jax.lax.dynamic_index_in_dim(acc.inf, ai, 0, False),
            )
            new = ec.point_add(fp, cur, q, dbl=_dbl_a0)
            upd = lambda s, v: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
                s, v, ai, 0
            )
            return Jac(
                upd(acc.x, new.x), upd(acc.y, new.y),
                upd(acc.z, new.z), upd(acc.inf, new.inf),
            ), None

        acc, _ = jax.lax.scan(term, acc, jnp.arange(digits.shape[0]))
        return acc, None

    acc, _ = jax.lax.scan(window, acc0, jnp.arange(NWINDOWS))
    return (
        fp.canon(acc.x), fp.canon(acc.y), fp.canon(acc.z),
        acc.inf.astype(jnp.uint32),
    )


@functools.lru_cache(maxsize=None)
def _jit_kernel():
    return jax.jit(commitments_kernel)


def _term_layout(n_attrs: int, n_shared: int) -> tuple:
    """(table index, accumulator) of every term.  Shared tables occupy
    indices 0..n_shared-1 of the kernel's table stack, the 4 per-lane
    bases (LANE_BASES order) follow at n_shared+0..3.
      T1: h_rand^z_r2, a_bar^{-c}, b_prime^{c}, a_prime^{z_neg_e}
      T2: G1^c, h_sk^z_sk, h_rand^z_s', h_attrs[i]^{s_i}, b'^{z_neg_r3}
      T3: h_sk^z_sk, h_rand^z_r_nym, nym^{-c}"""
    term_table = (
        2, n_shared + 1, n_shared + 2, n_shared + 0,
        0, 1, 2, *range(3, 3 + n_attrs), n_shared + 2,
        1, 2, n_shared + 3,
    )
    term_acc = (0, 0, 0, 0, 1, 1, 1, *([1] * n_attrs), 1, 2, 2, 2)
    return term_table, term_acc


# (engine, bucket, n_attrs) shapes this process has enqueued: the first
# enqueue of a shape traces, lowers and compiles (or loads) inside it
_enqueued: set = set()


@dataclasses.dataclass
class Prepared:
    """One batch of at most _MAX_LANES lanes, ready for either engine.
    A lane is a credential proof (T1, T2, T3 all meaningful) or a
    pseudonym signature, which rides as a lane of its own: bases
    a', a_bar, b' at infinity, every T1/T2 scalar zero, so only
    T3 = h_sk^z_sk * h_rand^z_rnym * nym^-c is computed (the kernel
    needs no change and a block's proofs and pseudonym signatures
    share one launch: 2 lanes a transaction)."""

    pts: list
    scalars: list
    ok: list
    shared_pts: tuple
    n_attrs: int
    bucket: int                # the XLA engine's pad size; the failure budget's key
    packed: object = None      # the Pallas engine's host arrays

    @property
    def lanes(self) -> int:
        return len(self.ok)


@dataclasses.dataclass
class Launched:
    prepared: Prepared
    path: str                  # "pallas" | "xla": the engine that ran
    bucket: int                # lanes the kernel ran at, padding included
    cold: bool                 # first enqueue of this shape
    fallback: str | None       # why the preferred engine did not run
    _reader: object = None     # the engine's: block() then unpack(raw)
    _raw: object = None

    def wait(self) -> None:
        """Block on the device and copy the result back.  A Pallas
        launch that fails only here (a runtime error surfaces at the
        copy) is rerun on the XLA engine."""
        try:
            self._raw = self._reader.block()
        except Exception as exc:
            if self.path != "pallas":
                raise
            _note_pallas_failure(self.prepared, exc)
            self.path, self.fallback = "xla", "pallas_to_xla"
            self.bucket = self.prepared.bucket
            self._reader = _commitments_xla(self.prepared)
            self._raw = self._reader.block()

    def jacobians(self) -> list:
        """Per lane [(x, y, z, inf)] * 3 Jacobian ints, after wait()."""
        return self._reader.unpack(self._raw)


def _bucket_of(n: int) -> int:
    return next((b for b in _BUCKETS if n <= b), _MAX_LANES)


def prepare(sigs, nyms, ipk) -> Prepared:
    """Host half before the launch: validity, scalars and lane bases of
    `sigs` (presentation Signatures) followed by `nyms` ((NymSignature,
    nym point) pairs), and the preferred engine's limb packing.  At
    most _MAX_LANES lanes; the caller chunks."""
    n_attrs = len(ipk.h_attrs)
    shared_pts = (bn.G1_GEN, ipk.h_sk, ipk.h_rand, *ipk.h_attrs)
    pts_l, scalars_l, ok = _prepare_sigs(sigs, ipk, n_attrs)
    p2, s2, ok2 = _prepare_nyms(nyms, n_attrs)
    pts_l += p2
    scalars_l += s2
    ok += ok2
    if len(ok) > _MAX_LANES:
        raise ValueError(f"{len(ok)} lanes in one launch (max {_MAX_LANES})")
    # budget key = the COMPILE bucket, not the raw batch length: every
    # length padding to the same bucket shares one compiled kernel, so
    # a deterministic failure is retried per compile unit, not per
    # distinct batch size
    prep = Prepared(pts_l, scalars_l, ok, shared_pts, n_attrs,
                    _bucket_of(len(ok)))
    if _pallas_preferred((prep.bucket, n_attrs)):
        try:
            from fabric_tpu.csp.tpu import pallas_bn254

            term_table, term_acc = _term_layout(n_attrs, len(shared_pts))
            prep.packed = pallas_bn254.pack(
                pts_l, scalars_l, ok, term_table, term_acc, shared_pts
            )
        except Exception as exc:
            _note_pallas_failure(prep, exc)
    return prep


def _note_pallas_failure(prep: Prepared, exc: Exception) -> None:
    from fabric_tpu.common.flogging import must_get_logger

    shape = (prep.bucket, prep.n_attrs)
    _PALLAS_FAILURES[shape] = _PALLAS_FAILURES.get(shape, 0) + 1
    prep.packed = None
    must_get_logger("bn254").warning(
        "pallas BN254 ladder failed for shape %s (%s: %s), "
        "failure %d/%d; using the XLA path for this batch",
        shape, type(exc).__name__, exc,
        _PALLAS_FAILURES[shape], _PALLAS_MAX_FAILURES,
    )


def enqueue(prep: Prepared) -> Launched:
    """Launch the batch on the preferred engine (the fused Pallas
    ladder where it runs compiled, pallas_bn254.py; else the XLA scan
    kernel) and return without waiting for the device."""
    shape = (prep.bucket, prep.n_attrs)
    if prep.packed is not None:
        try:
            from fabric_tpu.csp.tpu import pallas_bn254

            key = ("pallas", prep.packed.lanes, prep.n_attrs)
            cold = key not in _enqueued
            reader = pallas_bn254.enqueue(prep.packed)
            _enqueued.add(key)
            _PALLAS_FAILURES.pop(shape, None)  # success resets the budget
            # the kernel runs whole 128-lane blocks, a power of two of them
            return Launched(
                prep, "pallas", prep.packed.lanes, cold, None, reader
            )
        except Exception as exc:
            _note_pallas_failure(prep, exc)
    # where the Pallas ladder is the path (a TPU, or a test forcing
    # it), whatever kept it from running (a failure now, the failure
    # budget spent, FABRIC_BN254_NO_PALLAS) is a fallback to the scan
    fallback = (
        "pallas_to_xla"
        if jax.default_backend() == "tpu"
        or os.environ.get("FABRIC_BN254_FORCE_PALLAS")
        else None
    )
    cold = ("xla", prep.bucket, prep.n_attrs) not in _enqueued
    reader = _commitments_xla(prep)
    _enqueued.add(("xla", prep.bucket, prep.n_attrs))
    return Launched(prep, "xla", prep.bucket, cold, fallback, reader)


def normalize(launched: Launched) -> list:
    """The copy's limbs to integers, then Jacobian -> affine with ONE
    batched modular inversion (host ints): per lane (T1, T2, T3) as
    affine int tuples (None = infinity), or None for lanes whose inputs
    were malformed.  After `launched.wait()`."""
    prep = launched.prepared
    jac = launched.jacobians()
    n = prep.lanes
    zs, metas = [], []
    results: list = [None] * n
    for j in range(n):
        if not prep.ok[j]:
            continue
        tri = jac[j]
        metas.append((j, tri))
        for (_, _, zv, inf) in tri:
            zs.append(1 if (inf or zv == 0) else zv)
    if metas:
        invs = _batch_inverse(zs, bn.P)
        k = 0
        for j, tri in metas:
            pts = []
            for (x, y, zv, inf) in tri:
                if inf or zv == 0:
                    pts.append(None)
                else:
                    zi = invs[k]
                    zi2 = zi * zi % bn.P
                    pts.append((x * zi2 % bn.P, y * zi2 * zi % bn.P))
                k += 1
            results[j] = tuple(pts)
    return results


def schnorr_commitments_batch(sigs, ipk) -> list | None:
    """Device-batched T1/T2/T3 for every signature; returns per-sig
    [(T1, T2, T3)] as affine int tuples (None = infinity), or None for
    lanes whose inputs are malformed (caller marks them failed).

    Mirrors signature._relations + schnorr.recompute_commitments; parity
    is enforced by tests/test_bn254_device.py against the host path.
    """
    out: list = []
    # chunk at the largest bucket: bounds pad waste to the tail and
    # reuses the already-compiled shapes
    for off in range(0, len(sigs), _MAX_LANES):
        out.extend(_run(prepare(sigs[off:off + _MAX_LANES], [], ipk)))
    return out


def nym_commitments_batch(nyms, ipk) -> list:
    """Device-batched pseudonym-signature commitments
    h_sk^z_sk * h_rand^z_rnym * nym^-c for (NymSignature, nym) pairs:
    per pair the affine point (None = infinity), or False for a
    malformed lane."""
    out: list = []
    for off in range(0, len(nyms), _MAX_LANES):
        got = _run(prepare([], nyms[off:off + _MAX_LANES], ipk))
        out.extend(False if tri is None else tri[2] for tri in got)
    return out


def _run(prep: Prepared) -> list:
    launched = enqueue(prep)
    launched.wait()
    return normalize(launched)


def _prepare_nyms(nyms, n_attrs):
    """Lanes of pseudonym signatures: only the nym base and the three
    T3 scalars are set."""
    pts_l: list = []
    scalars_l: list = []
    ok = [True] * len(nyms)
    for j, (sig, nym) in enumerate(nyms):
        try:
            if nym is None or not bn.g1_is_on_curve(nym):
                raise ValueError("bad point")
            c = sig.challenge % bn.R
            scalars = [0] * (8 + n_attrs) + [
                sig.z_sk % bn.R, sig.z_rnym % bn.R, (-c) % bn.R,
            ]
            pts_l.append((None, None, None, nym))
            scalars_l.append(scalars)
        except (ValueError, IndexError, KeyError, TypeError,
                OverflowError, AttributeError):
            ok[j] = False
            pts_l.append((None,) * 4)
            scalars_l.append(None)
    return pts_l, scalars_l, ok


def _prepare_sigs(sigs, ipk, n_attrs):
    """Shared host prep for both device engines: per sig the 4 lane
    base points, the n_terms scalars (term order matching term_table),
    and validity.  Bad sigs get ok=False (the engines run them with
    zero scalars / infinity bases and the caller marks them failed)."""
    pts_l: list = []
    scalars_l: list = []
    ok = [True] * len(sigs)
    for j, sig in enumerate(sigs):
        try:
            pts = (sig.a_prime, sig.a_bar, sig.b_prime, sig.nym)
            if any(p is None or not bn.g1_is_on_curve(p) for p in pts):
                raise ValueError("bad point")
            if len(sig.disclosure) != n_attrs:
                raise ValueError("bad disclosure length")
            c = sig.challenge % bn.R
            z = sig.responses
            hidden = [i for i, d in enumerate(sig.disclosure) if not d]
            need = {"neg_e", "r2", "sk", "sprime", "neg_r3", "r_nym",
                    *{f"m_{i}" for i in hidden}}
            if not need <= set(z):
                raise ValueError("missing responses")
            s_attr = []
            for i in range(n_attrs):
                if sig.disclosure[i]:
                    if i not in sig.disclosed_attrs:
                        raise ValueError("missing disclosed attr")
                    s_attr.append((c * sig.disclosed_attrs[i]) % bn.R)
                else:
                    s_attr.append(z[f"m_{i}"] % bn.R)
            scalars = [
                # T1
                z["r2"] % bn.R,         # h_rand
                (-c) % bn.R,            # a_bar
                c,                      # b_prime
                z["neg_e"] % bn.R,      # a_prime
                # T2
                c,                      # G1
                z["sk"] % bn.R,         # h_sk
                z["sprime"] % bn.R,     # h_rand
                *s_attr,                # h_attrs
                z["neg_r3"] % bn.R,     # b_prime
                # T3
                z["sk"] % bn.R,         # h_sk
                z["r_nym"] % bn.R,      # h_rand
                (-c) % bn.R,            # nym
            ]
            pts_l.append(pts)
            scalars_l.append(scalars)
        except (ValueError, IndexError, KeyError, TypeError,
                OverflowError, AttributeError):
            ok[j] = False  # zero scalars: lane computes but is ignored
            pts_l.append((None,) * 4)
            scalars_l.append(None)
    return pts_l, scalars_l, ok


def _commitments_xla(prep: Prepared):
    """The XLA scan-kernel engine: launches, and returns the reader
    whose `block()` waits and whose `unpack()` gives per-lane
    [(x, y, z, inf)] * 3 Jacobian ints in plain (non-Montgomery) form."""
    pts_l, scalars_l, ok = prep.pts, prep.scalars, prep.ok
    term_table, term_acc = _term_layout(prep.n_attrs, len(prep.shared_pts))
    n = len(pts_l)
    n_terms = len(term_table)
    tabs = shared_tables(tuple(prep.shared_pts))

    # pad lanes to a bucket size so each (bucket, n_attrs) pair compiles
    # once; padded lanes carry zero scalars (every digit selects the
    # infinity table entry) and infinity bases
    bsz = prep.bucket
    lane_x = np.zeros((4, bsz, WIDE), np.uint32)
    lane_y = np.zeros((4, bsz, WIDE), np.uint32)
    lane_inf = np.ones((4, bsz), bool)
    digits = np.zeros((n_terms, bsz, NWINDOWS), np.int32)
    for j in range(n):
        if not ok[j]:
            continue
        for i, p in enumerate(pts_l[j]):
            if p is None:
                continue
            lane_x[i, j] = _to_limbs(p[0])
            lane_y[i, j] = _to_limbs(p[1])
            lane_inf[i, j] = False
        for t, u in enumerate(scalars_l[j]):
            if u:
                digits[t, j] = _recode(u)
    kern = _jit_kernel()
    outs = kern(
        jnp.asarray(lane_x), jnp.asarray(lane_y), jnp.asarray(lane_inf),
        jnp.asarray(tabs["x"]), jnp.asarray(tabs["y"]),
        jnp.asarray(tabs["inf"]),
        jnp.asarray(digits),
        jnp.asarray(term_table, jnp.int32),
        jnp.asarray(term_acc, jnp.int32),
    )

    return _XlaReader(outs, n, ok)


class _XlaReader:
    def __init__(self, outs, n: int, ok: list):
        self._outs, self._n, self._ok = outs, n, ok

    def block(self):
        return tuple(np.asarray(o) for o in self._outs)

    def unpack(self, raw) -> list:
        ax, ay, az, ainf = raw
        fp = _fp()
        jac = []
        for j in range(self._n):
            if not self._ok[j]:
                jac.append(None)
                continue
            tri = []
            for t in range(3):
                x = fp.from_mont_int(limbs.limbs_to_int(ax[t, j]))
                y = fp.from_mont_int(limbs.limbs_to_int(ay[t, j]))
                zv = fp.from_mont_int(limbs.limbs_to_int(az[t, j]))
                inf = bool(ainf[t, j])
                tri.append((x, y, zv, inf))
            jac.append(tri)
        return jac


def _batch_inverse(vals: list[int], m: int) -> list[int]:
    """Montgomery's trick: one pow for the whole list."""
    pre = [1] * (len(vals) + 1)
    for i, v in enumerate(vals):
        pre[i + 1] = pre[i] * v % m
    inv = pow(pre[-1], -1, m)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = inv * pre[i] % m
        inv = inv * vals[i] % m
    return out


__all__ = [
    "schnorr_commitments_batch", "nym_commitments_batch", "shared_tables",
    "prepare", "enqueue", "normalize",
]
