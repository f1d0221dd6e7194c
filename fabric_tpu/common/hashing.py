"""The host-side CSP hash seam — stdlib-only, importable everywhere.

fabriclint's csp-seam rule requires every SHA-256 call site outside
fabric_tpu/csp/ to route through here (or carry a reviewed pragma), so
new hashing stays VISIBLE to the batched providers.  The CSP factory
registers the process default provider via set_hash_backend at init;
until then (or on hosts without a configured CSP) hashlib produces the
identical digests.

This module deliberately imports NOTHING beyond hashlib: protoutil,
chaincode, and the ledger must stay importable on hosts without the
`cryptography` package (the cert/CA helpers that need it live in
common/crypto.py, which re-exports this seam).  The dependency points
csp -> common.hashing, never the reverse, so it stays import-cycle-free.
"""

from __future__ import annotations

import hashlib

_HASH_BACKEND = None


def set_hash_backend(csp) -> None:
    """Install the process CSP as the seam's backend (csp/factory.py
    calls this whenever the default provider is (re)initialized).

    The seam now feeds consensus-critical digests (tx ids, block header
    hashes, pvt key hashes), so a backend whose output is not
    byte-identical SHA-256 would silently fork this peer from the
    hashlib fallback — probe once at install time and fail fast."""
    if csp is not None:
        probe = b"fabric-tpu hash seam probe"
        want = hashlib.sha256(probe).digest()
        if csp.hash(probe) != want or list(
            csp.hash_batch([probe, b""])
        ) != [want, hashlib.sha256(b"").digest()]:
            raise ValueError(
                f"refusing hash backend {type(csp).__name__}: its "
                "hash/hash_batch is not byte-identical SHA-256 — "
                "installing it would change tx ids and block hashes "
                "on this peer only"
            )
    global _HASH_BACKEND
    _HASH_BACKEND = csp


def sha256(data: bytes) -> bytes:
    """SHA-256 through the CSP seam: the configured provider's `hash`
    when one is installed, hashlib otherwise (identical digests)."""
    backend = _HASH_BACKEND
    if backend is not None:
        return backend.hash(data)
    return hashlib.sha256(data).digest()


def sha256_many(blobs) -> list[bytes]:
    """Batch SHA-256 through the CSP seam (`hash_batch`: one call for
    the whole batch); hashlib where no provider is installed."""
    blobs = list(blobs)
    backend = _HASH_BACKEND
    if backend is not None:
        return list(backend.hash_batch(blobs))
    return [hashlib.sha256(b).digest() for b in blobs]


__all__ = ["set_hash_backend", "sha256", "sha256_many"]
