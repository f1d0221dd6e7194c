"""Caching MSP wrapper (reference msp/cache: memoizes
DeserializeIdentity, Validate, and SatisfiesPrincipal — the second-order
perf lever under signature-heavy validation).

Wraps any object with the MSP/MSPManager surface; safe because
identities and principals are immutable once parsed and the underlying
MSP config is fixed for a Bundle's lifetime (a config update builds a
NEW bundle with fresh MSPs, so caches never go stale).
"""

from __future__ import annotations

import copy
import threading
import time
from collections import OrderedDict

_DESERIALIZE_CACHE = 100
_VALIDATE_CACHE = 100
_PRINCIPAL_CACHE = 100
# validate() compares wall clock against the cert validity window, so its
# cache entries expire instead of living for the bundle's lifetime
_VALIDATE_TTL_S = 60.0


def _template(exc: Exception) -> Exception | None:
    """A copy of ``exc`` that is never raised: same type, arguments and
    attributes, no traceback, cause or context.  A cached failure is
    kept as this and every hit raises a fresh copy of it.  Raising the
    cached object itself would append the raising frames to its
    ``__traceback__`` on every hit, and those frames keep their callers
    and all their locals (a block's tx work, rwsets, pending policy
    evaluations) alive for as long as the cache entry lives.  None where
    the type cannot be copied: such a failure is not cached."""
    try:
        return copy.copy(exc)
    except Exception:
        # an exotic error type only costs the cache entry: the caller
        # still gets the error it raised
        return None


class _LRU:
    def __init__(self, cap: int):
        self._cap = cap
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            if key not in self._d:
                return None, False
            self._d.move_to_end(key)
            return self._d[key], True

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self._cap:
                self._d.popitem(last=False)


class CachedMSP:
    """Memoizing facade over an MSP or MSPManager."""

    def __init__(
        self,
        inner,
        deserialize_cap: int = _DESERIALIZE_CACHE,
        validate_cap: int = _VALIDATE_CACHE,
        principal_cap: int = _PRINCIPAL_CACHE,
    ):
        self._inner = inner
        self._deserialize = _LRU(deserialize_cap)
        self._validate = _LRU(validate_cap)
        self._principal = _LRU(principal_cap)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def deserialize_identity(self, serialized: bytes):
        ident, hit = self._deserialize.get(serialized)
        if hit:
            return ident
        ident = self._inner.deserialize_identity(serialized)
        if not getattr(ident, "anonymous", False):
            self._deserialize.put(bytes(serialized), ident)
        return ident

    def deserialize_creator(self, serialized: bytes):
        """A block creator, deserialized and validated under its MSP.
        An anonymous identity (Idemix: a fresh pseudonym a transaction)
        comes back with its credential proof deferred to the block's
        batched verify, and never enters the LRUs: it would not be
        asked for again, and a block of them would flush every X.509
        identity out."""
        deferred = getattr(self._inner, "deserialize_deferred", None)
        if deferred is not None:
            ident = deferred(serialized)
            if ident is not None:
                self._inner.validate(ident)
                return ident
        ident = self.deserialize_identity(serialized)
        self.validate(ident)
        return ident

    def validate(self, identity) -> None:
        if getattr(identity, "anonymous", False):
            return self._inner.validate(identity)  # single-use: no entry
        key = identity.serialize()
        res, hit = self._validate.get(key)
        if hit:
            stamp, outcome = res
            if time.monotonic() - stamp < _VALIDATE_TTL_S:
                if outcome is not None:
                    raise copy.copy(outcome)
                return
        try:
            self._inner.validate(identity)
        except Exception as exc:
            failure = _template(exc)
            if failure is not None:
                self._validate.put(key, (time.monotonic(), failure))
            raise
        self._validate.put(key, (time.monotonic(), None))

    def satisfies_principal(self, identity, principal) -> None:
        if getattr(identity, "anonymous", False):
            return self._inner.satisfies_principal(identity, principal)
        key = (identity.serialize(), principal.SerializeToString())
        res, hit = self._principal.get(key)
        if hit:
            if res is not None:
                raise copy.copy(res)
            return
        try:
            self._inner.satisfies_principal(identity, principal)
        except Exception as exc:
            failure = _template(exc)
            if failure is not None:
                self._principal.put(key, failure)
            raise
        self._principal.put(key, None)


__all__ = ["CachedMSP"]
