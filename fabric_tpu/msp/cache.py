"""Caching MSP wrapper (reference msp/cache: memoizes
DeserializeIdentity, Validate, and SatisfiesPrincipal — the second-order
perf lever under signature-heavy validation).

Wraps any object with the MSP/MSPManager surface; safe because
identities and principals are immutable once parsed and the underlying
MSP config is fixed for a Bundle's lifetime (a config update builds a
NEW bundle with fresh MSPs, so caches never go stale).

The sizes are upstream's (msp/cache/cache.go: 100 / 100 / 100).  They
suit a channel with a handful of identities.  On a channel whose
blocks carry hundreds of distinct creators (one enrolment certificate
a user) the tail evicts the head between two blocks and nearly every
lookup of a creator misses; every lookup and every eviction is counted
(`CachedMSP.tally()`, `msp_cache_requests_total{cache,outcome}` and
`msp_cache_evictions_total{cache}` on /metrics) so that an operator
can see which channel a peer serves.
"""

from __future__ import annotations

import copy
import threading
import time
from collections import OrderedDict

from fabric_tpu.msp import msp as _msp

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


# an optional common.metrics.MSPMetrics (wired by operations.System
# through the node): process-wide, as bundles and their caches come and
# go with every config update
_metrics = None


def set_metrics(metrics) -> None:
    """Attach a common.metrics.MSPMetrics: every lookup and eviction
    of every CachedMSP then shows on /metrics, and every chain
    signature of the X.509 MSPs behind them, by path."""
    global _metrics
    _metrics = metrics
    _msp.chain_signatures = None if metrics is None else metrics.chain_signatures


def _validated_lately(entry) -> bool:
    """A validate entry, (stamp, outcome), younger than its time."""
    return time.monotonic() - entry[0] < _VALIDATE_TTL_S


class _LRU:
    def __init__(self, cap: int, name: str):
        self._cap = cap
        self._name = name
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.requests = {"hit": 0, "miss": 0, "expired": 0}
        self.evictions = 0

    def get(self, key, fresh=None):
        """(value, True) for an entry that is there and, where `fresh`
        is given, still passes it; else (None, False), counted as a
        miss or as expired."""
        with self._lock:
            if key not in self._d:
                outcome, found = "miss", (None, False)
            elif fresh is not None and not fresh(self._d[key]):
                outcome, found = "expired", (None, False)
            else:
                self._d.move_to_end(key)
                outcome, found = "hit", (self._d[key], True)
            self.requests[outcome] += 1
        if _metrics is not None:
            _metrics.cache_requests.With(
                "cache", self._name, "outcome", outcome
            ).add()
        return found

    def absent(self, keys) -> list:
        """Those of `keys` that are not there now.  Counts nothing and
        moves nothing: a look ahead, not a lookup."""
        with self._lock:
            return [k for k in keys if k not in self._d]

    def put(self, key, value) -> None:
        dropped = 0
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self._cap:
                self._d.popitem(last=False)
                dropped += 1
            self.evictions += dropped
        if dropped and _metrics is not None:
            _metrics.cache_evictions.With("cache", self._name).add(dropped)


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
        self._deserialize = _LRU(deserialize_cap, "deserialize")
        self._validate = _LRU(validate_cap, "validate")
        self._principal = _LRU(principal_cap, "principal")
        # certificates the batch door read because the deserialize
        # cache did not hold them, by who read them
        self._creator_parses = {"native": 0, "python": 0}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def tally(self) -> dict:
        """Since this facade was built: lookups by cache and outcome
        (`requests["validate"]["miss"]`), evictions by cache, and the
        certificates `deserialize_creators` read by path."""
        caches = (self._deserialize, self._validate, self._principal)
        return {
            "requests": {c._name: dict(c.requests) for c in caches},
            "evictions": {c._name: c.evictions for c in caches},
            "creator_parses": dict(self._creator_parses),
        }

    def deserialize_identity(self, serialized: bytes):
        return self._deserialized(serialized, self._inner.deserialize_identity)

    def _deserialized(self, serialized: bytes, parse):
        ident, hit = self._deserialize.get(serialized)
        if hit:
            return ident
        ident = parse(serialized)
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
        ident = self._anonymous_creator(serialized)
        if ident is None:
            ident = self.deserialize_identity(serialized)
            self.validate(ident)
        return ident

    def _anonymous_creator(self, serialized: bytes):
        """The creator through its MSP's own door (`deserialize_
        deferred`), validated; None where its MSP has no such door."""
        deferred = getattr(self._inner, "deserialize_deferred", None)
        ident = None if deferred is None else deferred(serialized)
        if ident is not None:
            self._inner.validate(ident)
        return ident

    def deserialize_creators(self, creators) -> tuple[list, int]:
        """A block's distinct creators in one pass: for each what
        `deserialize_creator` gives it, or None where that raises, by
        the same lookups in the same caches in the same order; and how
        many chain signatures one native call decided on the way.
        Where the block is crowded, the certificates the deserialize
        cache does not hold are read together first, in one native call
        (`read_identities` of the MSP or manager behind this facade:
        `tally()["creator_parses"]` says how many it read and how many
        went one at a time).  The X.509 identities that have to be
        validated afresh then have their chain signatures checked
        together (`prove_chains`), so the `validate` of each finds its
        verdict waiting; where nothing was decided ahead, each
        `validate` checks its own."""
        idents: list = [None] * len(creators)
        read = self._read_ahead(creators)

        def parse(serialized):
            ident = read.get(serialized)
            path = "python" if ident is None else "native"
            self._creator_parses[path] += 1
            if _metrics is not None:
                _metrics.creator_parses.With("path", path).add()
            if ident is None:
                ident = self._inner.deserialize_identity(serialized)
            return ident

        owing = []
        for i, serialized in enumerate(creators):
            try:
                ident = self._anonymous_creator(serialized)
                if ident is not None:
                    idents[i] = ident
                    continue
                ident = self._deserialized(serialized, parse)
                key = ident.serialize()
                if self._validated(key):
                    idents[i] = ident
                else:
                    owing.append((i, key, ident))
            except Exception:
                pass
        decided = 0
        prove = getattr(self._inner, "prove_chains", None)
        if owing and prove is not None:
            decided = prove([ident for _, _, ident in owing])
        for i, key, ident in owing:
            try:
                self._validate_afresh(key, ident)
                idents[i] = ident
            except Exception:
                pass
        return idents, decided

    def _read_ahead(self, creators) -> dict:
        """serialized -> identity for the creators the deserialize cache
        does not hold now and the native reader qualified (none in a
        small block: `msp.read_identities`).  A look ahead only: the
        lookups that count, and every entry, are the pass's own, so a
        creator the cache holds now and drops before its turn is read
        then, one at a time."""
        read = getattr(self._inner, "read_identities", None)
        if read is None:
            return {}
        absent = self._deserialize.absent(creators)
        return {
            c: ident for c, ident in zip(absent, read(absent))
            if ident is not None
        }

    def validate(self, identity) -> None:
        if getattr(identity, "anonymous", False):
            return self._inner.validate(identity)  # single-use: no entry
        key = identity.serialize()
        if not self._validated(key):
            self._validate_afresh(key, identity)

    def _validated(self, key) -> bool:
        """True for an identity validated lately; raises what refused
        it lately; False: validate it afresh."""
        res, hit = self._validate.get(key, fresh=_validated_lately)
        if hit and res[1] is not None:
            raise copy.copy(res[1])
        return hit

    def _validate_afresh(self, key, identity) -> None:
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


__all__ = ["CachedMSP", "set_metrics"]
