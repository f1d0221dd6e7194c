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

import dataclasses
from typing import Sequence

from fabric_tpu.idemix import bn254 as bn
from fabric_tpu.idemix import nymsignature, revocation, signature
from fabric_tpu.idemix.credential import (
    CredRequest,
    Credential,
    new_cred_request,
    new_credential,
)
from fabric_tpu.idemix.issuer import IssuerKey, IssuerPublicKey


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
    """One (signature, message) pair for batched presentation verify."""

    sig: signature.Signature
    msg: bytes


class IdemixCSP:
    """Stateless provider; keys are passed explicitly (reference keeps them
    behind bccsp.Key handles — our callers hold the dataclasses directly)."""

    # Host/device crossover: the Pallas ladder won from ~100 signatures
    # in the rounds before PR 21 (BASELINE.md, deleted there, in git
    # history); below it per-dispatch overhead made the host path
    # faster.  Not re-measured on the directly attached v5e yet
    # (ROADMAP Queue 1 item 6).
    DEVICE_CROSSOVER = 100

    def __init__(self, rng=None, device: bool | None = None,
                 device_crossover: int | None = None):
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

    def verify_batch(
        self, items: Sequence[IdemixVerifyItem], ipk: IssuerPublicKey
    ) -> list[bool]:
        """Per-item mask, two pairings for the whole batch (BASELINE.json
        BN256 batch-verify configuration).  Ref being beaten: the
        reference verifies serially per signature
        (idemix/signature.go:290)."""
        if self._device is not None:
            use_device = self._device
        else:
            # auto: device at or above the TPU-measured crossover, and
            # only when a TPU backend is actually present — a CPU-only
            # host must never pay the per-bucket kernel compile the
            # host path exists to avoid
            use_device = len(items) >= self._crossover and _on_tpu()
        fn = (
            signature.verify_batch_device
            if use_device
            else signature.verify_batch
        )
        return fn(
            [i.sig for i in items], ipk, [i.msg for i in items],
            rng=self._rng,
        )

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


__all__ = ["IdemixCSP", "IdemixVerifyItem"]
