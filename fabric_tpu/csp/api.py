"""CSP interface: keys, options, provider protocol.

Modeled on the reference's BCCSP SPI (bccsp/bccsp.go:15-134: Key, KeyGen,
KeyImport, GetKey, Hash, Sign, Verify) plus the batch extension described in
SURVEY.md section 7 step 1: `verify_batch(keys, digests, sigs) -> mask` and
`hash_batch`.  The batch API returns a *per-item* validity mask, never a
single bool: the reference's policy evaluation tolerates invalid endorsements
(common/policies/policy.go:365-402 collects only the valid identities and the
policy may still pass), so a batch must preserve per-signature failure
semantics.
"""

from __future__ import annotations

import abc
import dataclasses
import functools
import hashlib
import typing
from typing import Sequence

# Guarded: the interface types (CSP protocol, VerifyBatchItem) must stay
# importable on hosts without the `cryptography` package — policy/
# validation modules import them for type use only.  Key construction
# and (de)serialization raise at call time instead of import time.
# ModuleNotFoundError only: a PRESENT-but-broken cryptography install
# (version mismatch, missing symbol) must surface, not degrade silently.
try:
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import ec
except ModuleNotFoundError as _exc:  # pragma: no cover - minimal hosts
    # Same policy as csp/__init__.py: only cryptography ITSELF missing is
    # forgivable; a missing transitive dep (cffi) is a broken install.
    if (_exc.name or "").split(".")[0] != "cryptography":
        raise
    serialization = ec = None


def _require_crypto() -> None:
    """Called at every key-construction/serialization entry point so a
    minimal host gets an actionable error, not AttributeError on None."""
    if serialization is None:
        raise ImportError(
            "the 'cryptography' package is required for ECDSA key "
            "construction and (de)serialization but is not installed"
        )

# ---------------------------------------------------------------------------
# P-256 domain parameters (NIST FIPS 186-4).
# ---------------------------------------------------------------------------

P256_P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
P256_A = P256_P - 3
P256_B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
P256_GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
P256_GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5
P256_HALF_N = P256_N // 2


class Key(abc.ABC):
    """A cryptographic key held by a CSP (reference bccsp/bccsp.go:15-40)."""

    @abc.abstractmethod
    def ski(self) -> bytes:
        """Subject key identifier of this key."""

    @abc.abstractmethod
    def raw(self) -> bytes:
        """Serialized form (public keys: uncompressed EC point, as the
        reference hashes for SKI; private keys: PKCS8 DER)."""

    @property
    def is_private(self) -> bool:
        return False

    def public_key(self) -> "Key":
        raise NotImplementedError


def _point_ski(x_bytes: bytes, y_bytes: bytes) -> bytes:
    # Reference computes SKI = SHA-256 over the uncompressed marshaled point
    # (bccsp/sw/keys.go ecdsaPublicKey.SKI / elliptic.Marshal).
    return hashlib.sha256(b"\x04" + x_bytes + y_bytes).digest()


class ECDSAP256PublicKey(Key):
    def __init__(self, key: ec.EllipticCurvePublicKey):
        _require_crypto()
        if not isinstance(key.curve, ec.SECP256R1):
            raise ValueError("only P-256 keys supported")
        self._key = key
        nums = key.public_numbers()
        self.x: int = nums.x
        self.y: int = nums.y
        # fixed-width coordinates, precomputed once: the batch
        # marshaller consumes these per verify item on the hot path
        self.x_bytes: bytes = self.x.to_bytes(32, "big")
        self.y_bytes: bytes = self.y.to_bytes(32, "big")
        self._ski = _point_ski(self.x_bytes, self.y_bytes)

    @classmethod
    def from_coordinates(cls, x_bytes: bytes, y_bytes: bytes) -> "ECDSAP256PublicKey":
        """The key of a point the caller has checked to lie on P-256
        (the native certificate reader has: native/x509.cc), from its
        32-byte big-endian coordinates.  What the marshal and the key
        table read (`x_bytes`, `y_bytes`, `ski()`) is there at once;
        the `cryptography` object is built when `crypto_key`, `der`,
        `pem` or the sw provider's verify first asks."""
        self = cls.__new__(cls)
        self.x_bytes, self.y_bytes = x_bytes, y_bytes
        self.x = int.from_bytes(x_bytes, "big")
        self.y = int.from_bytes(y_bytes, "big")
        self._ski = _point_ski(x_bytes, y_bytes)
        return self

    @functools.cached_property
    def _key(self) -> "ec.EllipticCurvePublicKey":
        # only a key `from_coordinates` made comes here: `__init__`
        # holds the object it was given
        _require_crypto()
        return ec.EllipticCurvePublicNumbers(
            self.x, self.y, ec.SECP256R1()
        ).public_key()

    def ski(self) -> bytes:
        return self._ski

    def public_key(self) -> "ECDSAP256PublicKey":
        # A public key's public key is itself (reference bccsp/sw/keys
        # ecdsaPublicKey.PublicKey).
        return self

    def raw(self) -> bytes:
        return b"\x04" + self.x.to_bytes(32, "big") + self.y.to_bytes(32, "big")

    def der(self) -> bytes:
        return self._key.public_bytes(
            serialization.Encoding.DER,
            serialization.PublicFormat.SubjectPublicKeyInfo,
        )

    def pem(self) -> bytes:
        return self._key.public_bytes(
            serialization.Encoding.PEM,
            serialization.PublicFormat.SubjectPublicKeyInfo,
        )

    @property
    def crypto_key(self) -> ec.EllipticCurvePublicKey:
        return self._key

    @classmethod
    def from_point(cls, x: int, y: int) -> "ECDSAP256PublicKey":
        _require_crypto()
        nums = ec.EllipticCurvePublicNumbers(x, y, ec.SECP256R1())
        return cls(nums.public_key())

    @classmethod
    def from_der(cls, der: bytes) -> "ECDSAP256PublicKey":
        _require_crypto()
        key = serialization.load_der_public_key(der)
        return cls(key)

    @classmethod
    def from_pem(cls, pem: bytes) -> "ECDSAP256PublicKey":
        _require_crypto()
        key = serialization.load_pem_public_key(pem)
        return cls(key)


class ECDSAP256PrivateKey(Key):
    def __init__(self, key: ec.EllipticCurvePrivateKey):
        _require_crypto()
        if not isinstance(key.curve, ec.SECP256R1):
            raise ValueError("only P-256 keys supported")
        self._key = key
        self._pub = ECDSAP256PublicKey(key.public_key())

    def ski(self) -> bytes:
        return self._pub.ski()

    def raw(self) -> bytes:
        return self._key.private_bytes(
            serialization.Encoding.DER,
            serialization.PrivateFormat.PKCS8,
            serialization.NoEncryption(),
        )

    @property
    def is_private(self) -> bool:
        return True

    def public_key(self) -> ECDSAP256PublicKey:
        return self._pub

    @property
    def crypto_key(self) -> ec.EllipticCurvePrivateKey:
        return self._key

    @classmethod
    def generate(cls) -> "ECDSAP256PrivateKey":
        _require_crypto()
        return cls(ec.generate_private_key(ec.SECP256R1()))

    @classmethod
    def from_der(cls, der: bytes) -> "ECDSAP256PrivateKey":
        _require_crypto()
        return cls(serialization.load_der_private_key(der, password=None))

    @classmethod
    def from_pem(cls, pem: bytes) -> "ECDSAP256PrivateKey":
        _require_crypto()
        return cls(serialization.load_pem_private_key(pem, password=None))


# ---------------------------------------------------------------------------
# Signature encoding: DER <-> (r, s), low-S normalization.
# Reference: bccsp/utils/ecdsa.go:39 MarshalECDSASignature, :84 IsLowS,
# :94 ToLowS.  Fabric rejects high-S signatures on verify and always emits
# low-S on sign (signature malleability defense).
# ---------------------------------------------------------------------------


def _der_int(v: int) -> bytes:
    """Minimal DER INTEGER content for a positive integer."""
    raw = v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")
    if raw[0] & 0x80:
        raw = b"\x00" + raw
    return bytes([0x02, len(raw)]) + raw


def _der_read_int(sig: bytes, off: int) -> tuple[int, int]:
    """Strict-DER INTEGER at `off`; returns (value, next offset)."""
    if off + 2 > len(sig) or sig[off] != 0x02:
        raise ValueError("invalid DER signature: expected INTEGER")
    ln = sig[off + 1]
    off += 2
    if ln == 0 or ln > 0x7F or off + ln > len(sig):
        raise ValueError("invalid DER signature: bad integer length")
    raw = sig[off:off + ln]
    if raw[0] & 0x80:
        raise ValueError("invalid DER signature: negative integer")
    if ln > 1 and raw[0] == 0 and not raw[1] & 0x80:
        raise ValueError("invalid DER signature: non-minimal integer")
    return int.from_bytes(raw, "big"), off + ln


def marshal_ecdsa_signature(r: int, s: int) -> bytes:
    """DER ECDSA-Sig-Value encoding — pure stdlib (a P-256 r/s pair
    fits short-form lengths), so signature marshaling works on minimal
    hosts without the `cryptography` package."""
    body = _der_int(r) + _der_int(s)
    if len(body) > 0x7F:
        # enforce the short-form assumption instead of silently
        # emitting malformed DER for oversized integers
        raise ValueError("r/s too large for short-form DER encoding")
    return bytes([0x30, len(body)]) + body


def unmarshal_ecdsa_signature(sig: bytes) -> tuple[int, int]:
    """DER-decode a signature. Raises ValueError on malformed input or
    non-positive r/s (reference bccsp/utils/ecdsa.go:47-62).  Strict:
    trailing bytes, non-minimal integers, and negatives are rejected,
    matching the asn1 backends the sw provider verifies with."""
    if len(sig) < 2 or sig[0] != 0x30:
        raise ValueError("invalid DER signature: expected SEQUENCE")
    if sig[1] > 0x7F or 2 + sig[1] != len(sig):
        raise ValueError("invalid DER signature: bad sequence length")
    r, off = _der_read_int(sig, 2)
    s, off = _der_read_int(sig, off)
    if off != len(sig):
        raise ValueError("invalid DER signature: trailing bytes")
    if r <= 0 or s <= 0:
        raise ValueError("invalid signature: r and s must be positive")
    return r, s


def is_low_s(s: int) -> bool:
    return s <= P256_HALF_N


def to_low_s(s: int) -> int:
    return P256_N - s if s > P256_HALF_N else s


# ---------------------------------------------------------------------------
# Batch verify item.
# ---------------------------------------------------------------------------


class VerifyBatchItem(typing.NamedTuple):
    """One (public key, digest, signature) triple for batched
    verification.  A NamedTuple, not a dataclass: the validator creates
    one per creator/endorsement lane (thousands per block), and tuple
    construction runs in C at roughly half the dataclass __init__
    cost — this is hot-path object churn."""

    key: ECDSAP256PublicKey
    digest: bytes  # 32-byte SHA-256 digest of the signed message
    signature: bytes  # DER-encoded (r, s)


class CSP(abc.ABC):
    """Provider protocol (reference bccsp/bccsp.go:90-134), plus batch ops."""

    @abc.abstractmethod
    def key_gen(self) -> ECDSAP256PrivateKey: ...

    @abc.abstractmethod
    def key_import(self, raw: bytes, private: bool = False) -> Key: ...

    @abc.abstractmethod
    def get_key(self, ski: bytes) -> Key: ...

    @abc.abstractmethod
    def hash(self, msg: bytes) -> bytes: ...

    @abc.abstractmethod
    def sign(self, key: Key, digest: bytes) -> bytes: ...

    @abc.abstractmethod
    def verify(self, key: Key, signature: bytes, digest: bytes) -> bool: ...

    # -- batch extension (the TPU seam) ------------------------------------

    @abc.abstractmethod
    def hash_batch(self, msgs: Sequence[bytes]) -> list[bytes]: ...

    @abc.abstractmethod
    def verify_batch(self, items: Sequence[VerifyBatchItem]) -> list[bool]: ...

    def verify_batch_async(self, items: Sequence[VerifyBatchItem],
                           flush: bool = False):
        """Dispatch a batch verify and return a zero-arg collector.

        Device providers override this to return BEFORE the device
        finishes, so callers can overlap host work for the next batch
        with the device's current one (the block-pipeline mode of the
        txvalidator).  The default computes eagerly — correct for host
        providers, which have nothing to overlap.  `flush=True` asks a
        provider that buffers batches to dispatch now; the default has
        buffered nothing."""
        result = self.verify_batch(items)
        return lambda: result

    def early_chunk(self, lanes: int) -> int | None:
        """How many lanes of a batch of `lanes` that is collected alone
        the provider wants first (handed over with `flush=True` while
        the caller collects the rest), or None: it takes the batch
        whole.  A host provider overlaps nothing, so None."""
        return None


__all__ = [
    "CSP",
    "Key",
    "ECDSAP256PublicKey",
    "ECDSAP256PrivateKey",
    "VerifyBatchItem",
    "marshal_ecdsa_signature",
    "unmarshal_ecdsa_signature",
    "is_low_s",
    "to_low_s",
    "P256_P",
    "P256_A",
    "P256_B",
    "P256_N",
    "P256_GX",
    "P256_GY",
    "P256_HALF_N",
]
