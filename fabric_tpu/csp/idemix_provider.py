"""Idemix CSP: a crypto-service-provider facade over the idemix scheme.

Reference: bccsp/idemix/bccsp.go:24 New + the handlers/bridge split
(bccsp/idemix/handlers/{issuer,user,cred,signer,nymsigner,revocation}.go).
The reference dispatches on opts types through the generic BCCSP SPI; here
the same capability surface is explicit methods — issuer/user key
generation, credential request/issue/verify, presentation sign/verify
(single and batched), nym sign/verify, CRI generation/verification —
over the BN254 backend (fabric_tpu/idemix/).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Sequence

from fabric_tpu.common import tracing
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.devtools.lockwatch import spawn_thread
from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix import nymsignature, revocation, signature
from fabric_tpu.idemix.credential import (
    CredRequest,
    Credential,
    new_cred_request,
    new_credential,
)
from fabric_tpu.idemix.issuer import IssuerKey, IssuerPublicKey

_logger = must_get_logger("idemix")


def _on_tpu() -> bool:
    """True when jax resolves to a TPU backend (lazy: importing jax —
    and initializing its backend — only happens once a batch actually
    crosses the auto-select threshold)."""
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:
        return False


@dataclasses.dataclass(frozen=True)
class IdemixVerifyItem:
    """One (signature, message) pair for batched presentation verify:
    a credential proof."""

    sig: signature.Signature
    msg: bytes


@dataclasses.dataclass(frozen=True)
class IdemixNymItem:
    """One pseudonym signature over `msg` under pseudonym `nym`; `sig`
    None is a signature that did not parse (its verdict is False)."""

    sig: nymsignature.NymSignature | None
    nym: tuple
    msg: bytes


# why an item was verified elsewhere than the Pallas BN254 kernel
# (the `reason` label of csp_idemix_fallbacks_total)
FALLBACK_REASONS = (
    "below_crossover", "no_tpu", "forced_host", "device_error",
    "pallas_to_xla",
)
# what a pairing check was for (the `stage` label of
# csp_idemix_pairing_checks_total): a batch's combined check; after it
# failed, a subset's in the bisection, or one item's own
PAIRING_STAGES = ("combined", "subset", "item")
# how the terms of a batch's weighted G1 sums were summed (the `engine`
# label of csp_idemix_msm_terms_total): by the bucket method, one
# multi-scalar multiplication a sum, or a windowed scalar multiplication
# a term (a sum under the native threshold)
MSM_ENGINES = ("bucket", "window")
_RECENT_BATCHES = 16384


def _kind(item) -> str:
    return "nym" if isinstance(item, IdemixNymItem) else "proof"


class _Flush:
    """One batch on the device path: a worker thread carries it from
    the host packing to the sealed mask (`idemix.flush`, detached:
    begun by the dispatching thread, ended here)."""

    def __init__(self, csp: "IdemixCSP", items: list, ipk, gen: int):
        self._csp, self._items, self._ipk = csp, items, ipk
        self._gen = gen
        self._done = threading.Event()
        self._mask: list | None = None
        self._exc: BaseException | None = None
        self._span = tracing.begin("idemix.flush", detach=True, batch=gen)
        self.thread = spawn_thread(
            target=self._run, name="idemix-flush", kind="worker"
        )

    def _run(self) -> None:
        csp = self._csp
        try:
            with tracing.attached(self._span.ctx):
                try:
                    mask, path, lanes, bucket = csp._device_mask(
                        self._items, self._ipk
                    )
                except Exception as exc:
                    # loud and counted: a broken device path must not
                    # pass for the kernel
                    _logger.warning(
                        "idemix device path failed (%s: %s); verifying "
                        "%d items on the host", type(exc).__name__, exc,
                        len(self._items),
                    )
                    csp._note_fallback("device_error")
                    mask, path, lanes, bucket = (
                        csp._host_mask(self._items, self._ipk), "host", 0, 0
                    )
            self._mask = csp._seal(self._items, mask, path, lanes, bucket)
            if tracing.enabled():
                kinds = collections.Counter(_kind(i) for i in self._items)
                self._span.annotate(
                    proofs=kinds["proof"], nyms=kinds["nym"], lanes=lanes,
                    bucket=bucket, path=path,
                )
        except BaseException as exc:  # surfaced to the collector
            self._exc = exc
        finally:
            self._span.end()
            self._done.set()

    def collect(self) -> list:
        with tracing.span("idemix.collect", batch=self._gen):
            self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._mask


class IdemixCSP:
    """The Idemix provider; keys are passed explicitly (reference keeps
    them behind bccsp.Key handles — our callers hold the dataclasses
    directly).  A peer's `TPUCSP` builds one (`TPUCSP.idemix`) and
    drains it with itself; `for_csp` finds it."""

    # Host/device crossover: the Pallas ladder won from ~100 signatures
    # in the rounds before PR 21 (BASELINE.md, deleted there, in git
    # history); below it per-dispatch overhead made the host path
    # faster.  Not re-measured on the directly attached v5e yet
    # (ROADMAP Queue 1 item 6).  Counted in lanes: a credential proof
    # and a pseudonym signature are a lane each.
    DEVICE_CROSSOVER = 100

    def __init__(self, rng=None, device: bool | None = None,
                 device_crossover: int | None = None, metrics=None):
        self._rng = rng
        # device batches the Schnorr commitment recomputation on the
        # TPU (csp/tpu/bn254_batch.py); pairings stay native-host.
        # None (default) AUTO-SELECTS per batch: device at or above the
        # measured crossover, host below it — so large batches hit the
        # TPU without callers knowing the constant, and host-only flows
        # never pay a kernel compile for small ones.  True/False force.
        self._device = device
        self._crossover = (
            device_crossover
            if device_crossover is not None
            else self.DEVICE_CROSSOVER
        )
        self._metrics = metrics
        self._lock = threading.Lock()
        self._gen = 0
        self._inflight: list = []
        # who verified what, from process start (`tally()`), and the
        # last batches one by one (`recent_batches()`)
        self._items: dict = {}
        self._fallbacks: dict = {}
        self._batches: dict = {}
        self._pairing_checks = dict.fromkeys(PAIRING_STAGES, 0)
        self._msm_terms = dict.fromkeys(MSM_ENGINES, 0)
        self._recent: collections.deque = collections.deque(
            maxlen=_RECENT_BATCHES
        )

    # -- what ran where ----------------------------------------------------

    def set_metrics(self, metrics) -> None:
        """Bind a common.metrics.CSPMetrics: csp_idemix_items_total,
        csp_idemix_fallbacks_total, csp_idemix_batches_total,
        csp_idemix_pairing_checks_total, csp_idemix_msm_terms_total."""
        self._metrics = metrics

    def tally(self) -> dict:
        """From process start: `items` by "kind.path" (kind proof|nym,
        path pallas|xla|host), `fallbacks` by reason (FALLBACK_REASONS),
        `batches` by the bucket (padded lanes) a device launch ran at,
        `pairing_checks` by stage (PAIRING_STAGES), `msm_terms` by
        engine (MSM_ENGINES).  A peer whose Idemix items all went
        through the Pallas kernel shows only `proof.pallas` and
        `nym.pallas` and no fallback; one that has met no forged
        credential shows only `combined` checks, one a batch of proofs,
        and its blocks' weighted sums under `bucket`, two terms a
        surviving proof."""
        with self._lock:
            return {"items": dict(self._items),
                    "fallbacks": dict(self._fallbacks),
                    "batches": dict(self._batches),
                    "pairing_checks": dict(self._pairing_checks),
                    "msm_terms": dict(self._msm_terms)}

    def recent_batches(self) -> list:
        """The last batches in order, each {"proofs", "nyms", "path",
        "lanes", "bucket"} (bucket 0: no device launch)."""
        with self._lock:
            return list(self._recent)

    def _note_fallback(self, reason: str) -> None:
        with self._lock:
            self._fallbacks[reason] = self._fallbacks.get(reason, 0) + 1
        if self._metrics is not None:
            self._metrics.idemix_fallbacks.With("reason", reason).add()

    def _note_pairing(self, stats: dict) -> None:
        """Count a batch's pairing checks by stage and the terms of its
        weighted sums by engine (`signature._pairing_mask`'s `stats`)."""
        subset = stats.get("subset_checks", 0)
        item = stats.get("item_checks", 0)
        spent = {"combined": stats.get("checks", 0) - subset - item,
                 "subset": subset, "item": item}
        summed = {"bucket": stats.get("msm_terms", 0),
                  "window": stats.get("msm_window_terms", 0)}
        with self._lock:
            for stage, n in spent.items():
                self._pairing_checks[stage] += n
            for engine, n in summed.items():
                self._msm_terms[engine] += n
        if self._metrics is not None:
            for stage, n in spent.items():
                self._metrics.idemix_pairing_checks.With(
                    "stage", stage
                ).add(n)
            for engine, n in summed.items():
                self._metrics.idemix_msm_terms.With("engine", engine).add(n)

    def _seal(self, items, mask, path: str, lanes: int, bucket: int) -> list:
        """Count a batch's items by kind and path; the mask as sealed."""
        counts = collections.Counter(_kind(i) for i in items)
        with self._lock:
            for kind, n in counts.items():
                key = f"{kind}.{path}"
                self._items[key] = self._items.get(key, 0) + n
            if bucket:
                self._batches[bucket] = self._batches.get(bucket, 0) + 1
            self._recent.append({
                "proofs": counts["proof"], "nyms": counts["nym"],
                "path": path, "lanes": lanes, "bucket": bucket,
            })
        if self._metrics is not None:
            for kind, n in counts.items():
                self._metrics.idemix_items.With(
                    "kind", kind, "path", path
                ).add(n)
            if bucket:
                self._metrics.idemix_batches.With(
                    "bucket", str(bucket)
                ).add()
        return mask

    # -- lifecycle ---------------------------------------------------------

    def drain(self, timeout: float | None = 60.0) -> bool:
        """Join every flush worker; True when none is left alive."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                self._inflight = [
                    f for f in self._inflight if f.thread.is_alive()
                ]
                live = list(self._inflight)
            if not live:
                return True
            for f in live:
                f.thread.join(
                    None if deadline is None
                    else max(0.0, deadline - time.monotonic())
                )
                if f.thread.is_alive():
                    return False

    def close(self) -> None:
        self.drain(timeout=None)

    # -- key generation (handlers/issuer.go, handlers/user.go) -------------

    def issuer_key_gen(self, attr_names: list[str]) -> IssuerKey:
        return IssuerKey.generate(attr_names, rng=self._rng)

    def user_secret_key_gen(self) -> int:
        return bn.rand_zr(self._rng)

    def make_nym(self, sk: int, ipk: IssuerPublicKey):
        return signature.make_nym(sk, ipk, rng=self._rng)

    # -- credentials (handlers/cred.go) ------------------------------------

    def cred_request(
        self, sk: int, nonce: bytes, ipk: IssuerPublicKey
    ) -> CredRequest:
        return new_cred_request(sk, nonce, ipk, rng=self._rng)

    def cred_request_verify(
        self, req: CredRequest, ipk: IssuerPublicKey
    ) -> bool:
        try:
            req.check(ipk)
            return True
        except ValueError:
            return False

    def cred_issue(
        self, issuer: IssuerKey, req: CredRequest, attrs: list[int]
    ) -> Credential:
        return new_credential(issuer, req, attrs, rng=self._rng)

    def cred_verify(
        self, cred: Credential, sk: int, ipk: IssuerPublicKey
    ) -> bool:
        try:
            cred.ver(sk, ipk)
            return True
        except ValueError:
            return False

    # -- presentation signatures (handlers/signer.go) ----------------------

    def sign(
        self,
        cred: Credential,
        sk: int,
        ipk: IssuerPublicKey,
        msg: bytes,
        disclosure: list[bool] | None = None,
        nym=None,
        r_nym: int | None = None,
    ) -> signature.Signature:
        return signature.new_signature(
            cred, sk, ipk, msg, disclosure=disclosure, nym=nym, r_nym=r_nym,
            rng=self._rng,
        )

    def verify(
        self, sig: signature.Signature, ipk: IssuerPublicKey, msg: bytes
    ) -> bool:
        return signature.verify(sig, ipk, msg)

    def verify_batch(self, items: Sequence, ipk: IssuerPublicKey) -> list[bool]:
        """Per-item mask, two pairings for the whole batch (BASELINE.json
        BN256 batch-verify configuration).  Ref being beaten: the
        reference verifies serially per signature
        (idemix/signature.go:290)."""
        return self.verify_batch_async(items, ipk)()

    def verify_batch_async(self, items: Sequence, ipk: IssuerPublicKey):
        """Dispatch a batch of credential proofs (IdemixVerifyItem) and
        pseudonym signatures (IdemixNymItem) against one issuer key and
        return its zero-argument collector: a mask, one verdict an item,
        each the one `signature.verify` / `nymsignature.verify_nym`
        gives.

        On the device path (a TPU, at or above the crossover) a worker
        thread starts at once and the caller goes on: every G1
        multi-scalar product of both kinds runs as ONE launch of the
        Pallas BN254 ladder (a lane an item); the challenge re-hash,
        the batched inversion, the random linear combination and the
        two pairings a batch stay on the host, on that thread.  Below
        the crossover, off a TPU, or forced, the host verifies when the
        collector is called.  Every such route, and every failure of
        the device path, is counted with its reason (`tally()`).

        A batch whose credentials are all genuine costs ONE combined
        pairing check.  When that fails, the forged proofs are found by
        bisection over the same random linear combination
        (`signature._isolate`): 7 to 14 further checks for one forgery
        among 125, and never more than a quarter over a check an item.
        `tally()["pairing_checks"]` counts them by stage."""
        items = list(items)
        if not items:
            return lambda: []
        reason = self._host_reason(len(items))
        if reason is not None:
            return self._host_collector(items, ipk, reason)
        with self._lock:
            gen = self._gen
            self._gen += 1
            flush = _Flush(self, items, ipk, gen)
            self._inflight = [
                f for f in self._inflight if f.thread.is_alive()
            ]
            self._inflight.append(flush)
        flush.thread.start()
        return flush.collect

    def _host_reason(self, n: int) -> str | None:
        """Why this batch stays on the host; None: it goes to the device."""
        if self._device is not None:
            return None if self._device else "forced_host"
        if n < self._crossover:
            return "below_crossover"
        # auto: only when a TPU backend is actually present — a CPU-only
        # host must never pay the per-bucket kernel compile the host
        # path exists to avoid
        return None if _on_tpu() else "no_tpu"

    def _host_collector(self, items, ipk, reason: str):
        memo: list = []
        lock = threading.Lock()

        def collector():
            with lock:
                if not memo:
                    self._note_fallback(reason)
                    memo.append(self._seal(
                        items, self._host_mask(items, ipk), "host", 0, 0
                    ))
                return memo[0]

        return collector

    def _split(self, items):
        proofs = [(i, it) for i, it in enumerate(items)
                  if _kind(it) == "proof"]
        nyms = [(i, it) for i, it in enumerate(items) if _kind(it) == "nym"]
        return proofs, nyms

    def _host_mask(self, items, ipk) -> list[bool]:
        """The host oracle's verdicts (idemix/signature.py verify_batch,
        nymsignature.verify_nym)."""
        proofs, nyms = self._split(items)
        mask = [False] * len(items)
        got = []
        if proofs:
            stats: dict = {}
            got = signature.verify_batch(
                [it.sig for _, it in proofs], ipk,
                [it.msg for _, it in proofs], rng=self._rng, stats=stats,
            )
            self._note_pairing(stats)
        for (i, _), v in zip(proofs, got):
            mask[i] = bool(v)
        for i, it in nyms:
            mask[i] = it.sig is not None and nymsignature.verify_nym(
                it.sig, it.nym, ipk, it.msg
            )
        return mask

    def _device_mask(self, items, ipk):
        """(mask, path, lanes, bucket) of a batch whose commitments the
        device computes; runs on the flush worker."""
        from fabric_tpu.common import gcpolicy
        from fabric_tpu.csp.tpu import bn254_batch

        mask = [False] * len(items)
        path, lanes, bucket = "pallas", 0, 0
        step = bn254_batch._MAX_LANES
        for off in range(0, len(items), step):
            proofs, nyms = self._split(items[off:off + step])
            nyms = [(i, it) for i, it in nyms if it.sig is not None]
            with tracing.span("idemix.prepare", lanes=len(proofs) + len(nyms)):
                prep = bn254_batch.prepare(
                    [it.sig for _, it in proofs],
                    [(it.sig, it.nym) for _, it in nyms], ipk,
                )
            with tracing.span(
                "idemix.enqueue", lanes=prep.lanes, bucket=prep.bucket,
                cold=False,
            ):
                launched = bn254_batch.enqueue(prep)
                tracing.annotate(
                    bucket=launched.bucket, cold=launched.cold,
                    engine=launched.path,
                )
            if launched.cold:
                # trace-and-lower left a heap behind that lives as long
                # as the process: keep every later collection off it
                gcpolicy.absorb()
            with tracing.span("idemix.device_wait", lanes=prep.lanes):
                launched.wait()
            with tracing.span("idemix.normalize"):
                comms = bn254_batch.normalize(launched)
            if launched.fallback is not None:
                self._note_fallback(launched.fallback)
            if launched.path != "pallas":
                path = launched.path
            lanes += prep.lanes
            bucket = max(bucket, launched.bucket)
            with tracing.span("idemix.rehash"):
                ok = [
                    signature.challenge_matches(it.sig, ipk, it.msg, tri)
                    for (_, it), tri in zip(proofs, comms)
                ]
                for (i, it), tri in zip(nyms, comms[len(proofs):]):
                    mask[off + i] = nymsignature.challenge_matches(
                        it.sig, it.nym, ipk, it.msg,
                        False if tri is None else tri[2],
                    )
            stats: dict = {}
            with tracing.span("idemix.pairing"):
                ok = signature._pairing_mask(
                    [it.sig for _, it in proofs], ok, ipk, self._rng,
                    stats=stats,
                )
                tracing.annotate(**stats)
            self._note_pairing(stats)
            for (i, _), v in zip(proofs, ok):
                mask[off + i] = bool(v)
        return mask, path, lanes, bucket

    # -- nym signatures (handlers/nymsigner.go) ----------------------------

    def nym_sign(
        self, sk: int, nym, r_nym: int, ipk: IssuerPublicKey, msg: bytes
    ) -> nymsignature.NymSignature:
        return nymsignature.new_nym_signature(
            sk, nym, r_nym, ipk, msg, rng=self._rng
        )

    def nym_verify(
        self, sig: nymsignature.NymSignature, nym, ipk: IssuerPublicKey,
        msg: bytes,
    ) -> bool:
        return nymsignature.verify_nym(sig, nym, ipk, msg)

    # -- revocation (handlers/revocation.go) -------------------------------

    def revocation_key_gen(self):
        return revocation.generate_long_term_revocation_key()

    def create_cri(self, ra_key, epoch: int):
        return revocation.create_cri(ra_key, epoch, rng=self._rng)

    def verify_cri(self, ra_pub, cri) -> bool:
        return revocation.verify_epoch_pk(ra_pub, cri)


_default: list = []


def for_csp(csp) -> IdemixCSP:
    """The Idemix provider beside `csp`: the one a `TPUCSP` built
    (`csp.idemix`, drained and closed with it), else one host-only
    provider for the process (no thread, no device)."""
    own = getattr(csp, "idemix", None)
    if own is not None:
        return own
    if not _default:
        _default.append(IdemixCSP(device=False))
    return _default[0]


__all__ = [
    "IdemixCSP", "IdemixVerifyItem", "IdemixNymItem", "FALLBACK_REASONS",
    "PAIRING_STAGES", "MSM_ENGINES", "for_csp",
]
