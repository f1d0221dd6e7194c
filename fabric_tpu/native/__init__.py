"""Native (C++) host-side components, loaded via ctypes.

`marshal_batch` is the batch signature marshaller feeding the TPU verify
kernel (SURVEY.md §7 native-components policy).  The shared library is
compiled on first use with the system g++ and cached next to the source;
callers fall back to the pure-Python path when the build or load fails,
and `load_error()` says why (measured paths — chip_smoke.py,
benchmarks/run.py — refuse to run on the fallback).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import typing

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_DIR, "marshal.cc"), os.path.join(_DIR, "collect.cc"),
         os.path.join(_DIR, "bn254.cc"), os.path.join(_DIR, "pairing.cc"),
         os.path.join(_DIR, "ecverify.cc"), os.path.join(_DIR, "x509.cc")]
_LIB = os.path.join(_DIR, "libfabricmarshal.so")

_lock = threading.Lock()
_lib = None
_tried = False
_load_error: str | None = None


def _load():
    global _lib, _tried, _load_error
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            if not os.path.exists(_LIB) or any(
                os.path.getmtime(_LIB) < os.path.getmtime(src)
                for src in _SRCS
            ):
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-o", _LIB] + _SRCS,
                    check=True,
                    capture_output=True,
                )
            lib = ctypes.CDLL(_LIB)
            fn = lib.fabric_marshal_batch
            fn.restype = ctypes.c_int
            fn.argtypes = [
                ctypes.c_int,
                ctypes.c_char_p,  # xs
                ctypes.c_char_p,  # ys
                ctypes.c_char_p,  # digests
                ctypes.c_char_p,  # sigs
                np.ctypeslib.ndpointer(np.int32, flags="C"),
                np.ctypeslib.ndpointer(np.uint32, flags="C"),  # qx
                np.ctypeslib.ndpointer(np.uint32, flags="C"),  # qy
                np.ctypeslib.ndpointer(np.uint32, flags="C"),  # d1
                np.ctypeslib.ndpointer(np.uint32, flags="C"),  # d2
                np.ctypeslib.ndpointer(np.uint32, flags="C"),  # c0
                np.ctypeslib.ndpointer(np.uint8, flags="C"),   # c1ok
                np.ctypeslib.ndpointer(np.uint8, flags="C"),   # valid
            ]
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
            cb = lib.fabric_collect_block
            cb.restype = ctypes.c_int
            cb.argtypes = (
                [ctypes.c_int, ctypes.c_char_p, i64p, ctypes.c_char_p,
                 ctypes.c_int]
                + [i32p, i32p]                    # status, type
                + [i64p, i32p] * 2 + [u8p]        # creator, sig, payload_digest
                + [i64p, i32p] * 4                # txid, prp, rwset, ccid
                + [i32p, i32p, ctypes.c_int]      # endo_start/count, max
                + [i64p, i32p] * 2 + [u8p]        # endorser, esig, edigest
            )
            msm = lib.bn254_g1_msm
            msm.restype = ctypes.c_int
            msm.argtypes = [ctypes.c_int] + [ctypes.c_char_p] * 3 + [u8p, u8p]
            ms = lib.bn254_g1_msm_sets
            ms.restype = ctypes.c_int
            ms.argtypes = (
                [ctypes.c_int] * 2 + [ctypes.c_char_p] * 3 + [u8p] * 3
            )
            lib.bn254_g1_msm_bucket_threshold.restype = ctypes.c_int
            lib.bn254_g1_msm_bucket_threshold.argtypes = []
            mm = lib.bn254_g1_mul_many
            mm.restype = ctypes.c_int
            mm.argtypes = [ctypes.c_int] + [ctypes.c_char_p] * 3 + [u8p] * 3
            pc = lib.bn254_pairing_check
            pc.restype = ctypes.c_int
            pc.argtypes = [ctypes.c_int] + [ctypes.c_char_p] * 6
            ev = lib.fabric_ecdsa_verify_host
            ev.restype = ctypes.c_int
            ev.argtypes = [
                ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_char_p, i32p, i32p, u8p,
            ]
            xr = lib.fabric_x509_read
            xr.restype = ctypes.c_int
            xr.argtypes = [ctypes.c_int, ctypes.c_char_p, i64p, ctypes.c_int,
                           i64p] + [u8p] * 5
            lib.fabric_x509_meta_cols.restype = ctypes.c_int
            lib.fabric_x509_meta_cols.argtypes = []
            if lib.fabric_x509_meta_cols() != _X509_COLS:
                raise RuntimeError("x509.cc and x509_read disagree on a row")
            _lib = lib
        except Exception as exc:
            _lib = None
            detail = getattr(exc, "stderr", None)  # g++'s own words
            if isinstance(detail, bytes):
                detail = detail.decode("utf-8", "replace")
            _load_error = f"{type(exc).__name__}: {exc}" + (
                f"\n{detail.strip()}" if detail else ""
            )
            from fabric_tpu.common.flogging import must_get_logger

            must_get_logger("native").warning(
                "native library unavailable, every caller takes the "
                "pure-Python path: %s", _load_error,
            )
        return _lib


def available() -> bool:
    return _load() is not None


def load_error() -> str | None:
    """Why the library could not be built or loaded (the compiler's
    stderr included); None while it is available or untried."""
    return _load_error


def marshal_batch(xs: bytes, ys: bytes, digests: bytes, sigs: bytes,
                  sig_off: np.ndarray) -> dict | None:
    """One pass: DER parse + prechecks + batch inversion + packing.
    Inputs: concatenated 32-byte big-endian x/y/digest buffers and
    concatenated DER signatures with (n+1,) int32 offsets.  Returns the
    packed dict fabric_tpu.csp.tpu.pallas_ec.verify_packed consumes, or
    None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(sig_off) - 1
    qx = np.empty((8, n), np.uint32)
    qy = np.empty((8, n), np.uint32)
    d1 = np.empty((8, n), np.uint32)
    d2 = np.empty((8, n), np.uint32)
    c0 = np.empty((8, n), np.uint32)
    c1ok = np.empty(n, np.uint8)
    valid = np.empty(n, np.uint8)
    lib.fabric_marshal_batch(
        n, xs, ys, digests, sigs, np.ascontiguousarray(sig_off, np.int32),
        qx, qy, d1, d2, c0, c1ok, valid,
    )
    return {
        "qx": qx,
        "qy": qy,
        "d1": d1,
        "d2": d2,
        "cand0": c0,
        # c1 (r+n words) is no longer shipped: the kernel rebuilds cand1
        # on-device from cand0; only the admissibility flag travels.
        "cand1_ok": c1ok.astype(bool),
        "valid": valid.astype(bool),
    }


def ecdsa_verify_host(items) -> list[bool] | None:
    """Batched host ECDSA-P256 verification through libcrypto
    (ecverify.cc): the TPU provider's chip-stall fallback — OpenSSL's
    nistz256 verify is a multiple of the python-wrapped rate, which
    directly bounds the p99 cost of a stalled flush; and the X.509
    MSP's batch of a block's chain signatures (msp/msp.py
    prove_chains).  The call holds no interpreter lock.  Verdicts match
    csp/sw.py _verify_one (strict DER, low-S).  Returns None when the
    native library or libcrypto is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(items)
    if n == 0:
        return []
    qxy = bytearray(64 * n)
    digs = bytearray(32 * n)
    sig_off = np.empty(n, np.int32)
    sig_len = np.empty(n, np.int32)
    sigs = bytearray()
    for i, it in enumerate(items):
        key = it.key
        pub = key.public_key() if hasattr(key, "public_key") else key
        try:
            # the key caches its 32-byte big-endian coordinates exactly
            # for hot-path marshalling — no per-lane int conversion
            qxy[64 * i:64 * i + 32] = pub.x_bytes
            qxy[64 * i + 32:64 * i + 64] = pub.y_bytes
        except (AttributeError, ValueError):
            pass  # zeroed key never validates a real signature
        d = it.digest
        if len(d) == 32:
            digs[32 * i:32 * i + 32] = d
        sig_off[i] = len(sigs)
        sig_len[i] = len(it.signature)
        sigs += it.signature
    out = np.zeros(n, np.uint8)
    rc = lib.fabric_ecdsa_verify_host(
        n, bytes(qxy), bytes(digs), bytes(sigs), sig_off, sig_len, out
    )
    if rc != 0:
        return None  # libcrypto unavailable at runtime
    # a non-32-byte digest is invalid by definition (sw.py returns
    # False); the zeroed placeholder row would also fail, but make it
    # explicit rather than rely on digest(0) never verifying
    mask = out.astype(bool)
    for i, it in enumerate(items):
        if len(it.digest) != 32:
            mask[i] = False
    return mask.tolist()


# a row of fabric_x509_read's `meta` (x509.cc's Col): the status, six
# (offset, length) pairs, two times, two counts, then the OU pairs
_X509_OUS = 8
_X509_COLS = 17 + 2 * _X509_OUS


class X509Fields(typing.NamedTuple):
    """What `x509_read` read of one certificate it qualified."""

    mspid: bytes            # b"" for a bare PEM
    pem: bytes              # canonical: what public_bytes(PEM) gives
    der: bytes
    issuer: bytes           # the raw issuer Name
    subject: bytes          # the raw subject Name
    serial: bytes           # the serial number's INTEGER contents
    not_before: int         # seconds since 1970
    not_after: int
    x_bytes: bytes          # the P-256 key, 32 bytes big-endian each
    y_bytes: bytes
    tbs_digest: bytes       # SHA-256 of the TBS bytes
    r_bytes: bytes          # the signature as it stands
    s_bytes: bytes
    low_s_signature: bytes  # DER of (r, min(s, n - s))
    ous: tuple              # the subject's OU values, undecoded (UTF-8)


def x509_read(items, wrapped: bool = False) -> list | None:
    """The certificates of many identities in one native call that
    holds no interpreter lock (x509.cc).  `items` are PEM certificates,
    or with `wrapped` SerializedIdentity messages around them.  For
    each, an `X509Fields` where the reader qualified it, or its status
    (an int above 0: x509.cc's Status says which rule handed it back)
    where it did not, and the caller reads that one as ever.  None
    where the native library is unavailable or its verifier has no
    libcrypto: every certificate is then read as ever."""
    lib = _load()
    if lib is None:
        return None
    n = len(items)
    if n == 0:
        return []
    off = np.zeros(n + 1, np.int64)
    np.cumsum([len(it) for it in items], out=off[1:])
    buf = b"".join(items)
    meta = np.empty((n, _X509_COLS), np.int64)
    der = np.empty(max(len(buf), 1), np.uint8)
    digest = np.empty(32 * n, np.uint8)
    xy = np.empty(64 * n, np.uint8)
    rs = np.empty(64 * n, np.uint8)
    lowsig = np.empty(72 * n, np.uint8)
    if lib.fabric_x509_read(n, buf, off, 1 if wrapped else 0, meta, der,
                            digest, xy, rs, lowsig) != 0:
        return None
    der_b, digest_b = der.tobytes(), digest.tobytes()
    xy_b, rs_b, lowsig_b = xy.tobytes(), rs.tobytes(), lowsig.tobytes()
    out = []
    for i, row in enumerate(meta.tolist()):
        if row[0]:
            out.append(row[0])
            continue
        (_, mspid_at, mspid_n, pem_at, pem_n, der_at, der_n, iss_at, iss_n,
         sub_at, sub_n, ser_at, ser_n, not_before, not_after, sig_n,
         n_ous) = row[:17]
        out.append(X509Fields(
            buf[mspid_at:mspid_at + mspid_n], buf[pem_at:pem_at + pem_n],
            der_b[der_at:der_at + der_n], der_b[iss_at:iss_at + iss_n],
            der_b[sub_at:sub_at + sub_n], der_b[ser_at:ser_at + ser_n],
            not_before, not_after,
            xy_b[64 * i:64 * i + 32], xy_b[64 * i + 32:64 * i + 64],
            digest_b[32 * i:32 * i + 32],
            rs_b[64 * i:64 * i + 32], rs_b[64 * i + 32:64 * i + 64],
            lowsig_b[72 * i:72 * i + sig_n],
            tuple(der_b[row[17 + 2 * k]:row[17 + 2 * k] + row[18 + 2 * k]]
                  for k in range(n_ous)),
        ))
    return out


def collect_block(env_bytes: bytes, env_off: np.ndarray,
                  channel_id: bytes) -> dict | None:
    """Native block-collect pass: walk every envelope's wire format,
    run the syntactic checks, and emit per-tx offsets + SHA-256 digests
    (see collect.cc).  Returns None when the library is unavailable.

    Output dict of numpy arrays; offsets index into env_bytes.  status
    uses collect.cc's codes: 0 endorser-tx ok, 1 config-tx ok, negative
    = error/fallback (mapped to TxValidationCode by the caller)."""
    lib = _load()
    if lib is None:
        return None
    n = len(env_off) - 1
    out = {
        "status": np.empty(n, np.int32),
        "type": np.empty(n, np.int32),
        "creator_off": np.zeros(n, np.int64),
        "creator_len": np.zeros(n, np.int32),
        "sig_off": np.zeros(n, np.int64),
        "sig_len": np.zeros(n, np.int32),
        "payload_digest": np.zeros(32 * n, np.uint8),
        "txid_off": np.zeros(n, np.int64),
        "txid_len": np.zeros(n, np.int32),
        "prp_off": np.zeros(n, np.int64),
        "prp_len": np.zeros(n, np.int32),
        "rwset_off": np.zeros(n, np.int64),
        "rwset_len": np.zeros(n, np.int32),
        "ccid_off": np.zeros(n, np.int64),
        "ccid_len": np.zeros(n, np.int32),
        "endo_start": np.zeros(n, np.int32),
        "endo_count": np.zeros(n, np.int32),
    }
    max_endos = max(64, 8 * n)  # >= 8 endorsements/tx before a retry
    while True:
        endos = {
            "e_endorser_off": np.zeros(max_endos, np.int64),
            "e_endorser_len": np.zeros(max_endos, np.int32),
            "e_sig_off": np.zeros(max_endos, np.int64),
            "e_sig_len": np.zeros(max_endos, np.int32),
            "e_digest": np.zeros(32 * max_endos, np.uint8),
        }
        rc = lib.fabric_collect_block(
            n, env_bytes, np.ascontiguousarray(env_off, np.int64),
            channel_id, len(channel_id),
            out["status"], out["type"],
            out["creator_off"], out["creator_len"],
            out["sig_off"], out["sig_len"], out["payload_digest"],
            out["txid_off"], out["txid_len"],
            out["prp_off"], out["prp_len"],
            out["rwset_off"], out["rwset_len"],
            out["ccid_off"], out["ccid_len"],
            out["endo_start"], out["endo_count"], max_endos,
            endos["e_endorser_off"], endos["e_endorser_len"],
            endos["e_sig_off"], endos["e_sig_len"], endos["e_digest"],
        )
        if rc >= 0:
            out.update(endos)
            out["n_endos"] = rc
            return out
        max_endos *= 4  # undersized endorsement arrays: retry larger


def _pack_g1(points) -> tuple[bytes, bytes]:
    """32-byte big-endian x and y of each point, one after another;
    None (infinity) as zeros."""
    xs = bytearray(32 * len(points))
    ys = bytearray(32 * len(points))
    for i, pt in enumerate(points):
        if pt is not None:
            xs[32 * i:32 * i + 32] = pt[0].to_bytes(32, "big")
            ys[32 * i:32 * i + 32] = pt[1].to_bytes(32, "big")
    return bytes(xs), bytes(ys)


def _pack_zr(scalars) -> bytes:
    return b"".join((k % _BN254_R).to_bytes(32, "big") for k in scalars)


def _unpack_g1(ox, oy, inf) -> list[tuple[int, int] | None]:
    """The affine points of a native call's output buffers."""
    b_ox, b_oy = ox.tobytes(), oy.tobytes()
    return [
        None if inf[i] else (
            int.from_bytes(b_ox[32 * i:32 * i + 32], "big"),
            int.from_bytes(b_oy[32 * i:32 * i + 32], "big"),
        )
        for i in range(len(inf))
    ]


def _bn254_lib():
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return lib


def bn254_msm(points, scalars) -> tuple[int, int] | None:
    """sum_i scalars[i] * points[i] over BN254 G1 (affine int coords;
    None encodes a point at infinity, on input and output): by the
    bucket method from `bn254_msm_bucket_threshold()` terms up, term by
    term below.  Raises RuntimeError when the native library is
    unavailable — gate on available() (idemix.bn254._native does)."""
    lib = _bn254_lib()
    n = min(len(points), len(scalars))
    xs, ys = _pack_g1(points[:n])
    ox = np.zeros(32, np.uint8)
    oy = np.zeros(32, np.uint8)
    rc = lib.bn254_g1_msm(n, xs, ys, _pack_zr(scalars[:n]), ox, oy)
    return _unpack_g1(ox, oy, [rc])[0]


def bn254_msm_sets(point_lists, scalars) -> list[tuple[int, int] | None]:
    """[sum_i scalars[i] * points[i] for points in point_lists]: several
    sums under ONE list of scalars (every list as long as it) in one
    native call, which recodes the scalars once and returns to the
    interpreter once.  Raises RuntimeError when the native library is
    unavailable."""
    lib = _bn254_lib()
    n, sets = len(scalars), len(point_lists)
    if any(len(points) != n for points in point_lists):
        raise ValueError("a list of points for every list of scalars")
    xs, ys = _pack_g1([pt for points in point_lists for pt in points])
    ox = np.zeros(32 * sets, np.uint8)
    oy = np.zeros(32 * sets, np.uint8)
    inf = np.zeros(sets, np.uint8)
    lib.bn254_g1_msm_sets(n, sets, xs, ys, _pack_zr(scalars), ox, oy, inf)
    return _unpack_g1(ox, oy, inf)


def bn254_msm_bucket_threshold() -> int:
    """The term count from which `bn254_msm` / `bn254_msm_sets` form a
    sum by the bucket method and not term by term (a constant of
    bn254.cc).  Raises RuntimeError when the native library is
    unavailable."""
    return _bn254_lib().bn254_g1_msm_bucket_threshold()


def bn254_mul_many(points, scalars) -> list[tuple[int, int] | None]:
    """Independent scalars[i] * points[i]; one shared field inversion.
    Raises RuntimeError when the native library is unavailable."""
    lib = _bn254_lib()
    n = min(len(points), len(scalars))
    xs, ys = _pack_g1(points[:n])
    ox = np.zeros(32 * n, np.uint8)
    oy = np.zeros(32 * n, np.uint8)
    inf = np.zeros(n, np.uint8)
    lib.bn254_g1_mul_many(n, xs, ys, _pack_zr(scalars[:n]), ox, oy, inf)
    return _unpack_g1(ox, oy, inf)


_BN254_R = 0x30644e72e131a029b85045b68181585d2833e84879b9709143e1f593f0000001


def bn254_pairing_check(pairs) -> bool:
    """prod e(P_i, Q_i) == 1?  pairs: [(g1_point|None, g2_point|None)]
    with g1 = (x, y) ints and g2 = ((xa, xb), (ya, yb)) Fp2 ints.
    Raises RuntimeError when the native library is unavailable."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(pairs)
    bufs = [bytearray(32 * n) for _ in range(6)]
    for i, (pg1, qg2) in enumerate(pairs):
        if pg1 is None or qg2 is None:
            continue  # identity factor
        o = 32 * i
        bufs[0][o:o + 32] = pg1[0].to_bytes(32, "big")
        bufs[1][o:o + 32] = pg1[1].to_bytes(32, "big")
        bufs[2][o:o + 32] = qg2[0][0].to_bytes(32, "big")
        bufs[3][o:o + 32] = qg2[0][1].to_bytes(32, "big")
        bufs[4][o:o + 32] = qg2[1][0].to_bytes(32, "big")
        bufs[5][o:o + 32] = qg2[1][1].to_bytes(32, "big")
    return bool(lib.bn254_pairing_check(n, *(bytes(b) for b in bufs)))


__all__ = [
    "available", "load_error", "marshal_batch", "collect_block", "bn254_msm",
    "bn254_msm_sets", "bn254_msm_bucket_threshold", "bn254_mul_many",
    "bn254_pairing_check", "ecdsa_verify_host", "x509_read", "X509Fields",
]
