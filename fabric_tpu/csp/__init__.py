"""Crypto service provider (CSP) -- the pluggable crypto SPI.

Equivalent of the reference's BCCSP (bccsp/bccsp.go:90-134) with one
deliberate extension the reference lacks: a first-class *batch* API
(`verify_batch`, `hash_batch`) so a whole block's signatures become a single
device call (hashing stays hashlib in every provider). Providers:

- sw:  host reference implementation (OpenSSL via `cryptography`, hashlib)
- tpu: JAX/XLA batched implementation (csp/tpu/)
"""

from fabric_tpu.csp.api import (
    CSP,
    Key,
    ECDSAP256PublicKey,
    ECDSAP256PrivateKey,
    VerifyBatchItem,
)
# Guarded: the SPI types above must stay importable on hosts without the
# `cryptography` package (policy/validation modules need VerifyBatchItem
# for type use only); the concrete providers genuinely need it and stay
# unavailable there — `from fabric_tpu.csp import SWCSP` raises an
# ImportError that names the missing dependency (module __getattr__
# below), so the operator still sees the actionable cause.
try:
    from fabric_tpu.csp.sw import SWCSP
    from fabric_tpu.csp.idemix_provider import IdemixCSP, IdemixVerifyItem
    from fabric_tpu.csp.factory import (
        csp_from_config,
        get_default,
        init_factories,
    )
    from fabric_tpu.csp.keystore import (
        DummyKeyStore,
        FileKeyStore,
        InMemoryKeyStore,
    )
    _HAVE_PROVIDERS = True
except ImportError as _exc:  # pragma: no cover - exercised on minimal hosts
    # Only the known-optional dependency being ABSENT is forgivable
    # (ModuleNotFoundError); a broken or version-mismatched cryptography
    # install raises plain ImportError with the same .name and must not
    # be masked — nodes would silently lose signing with no hint why.
    if not (
        isinstance(_exc, ModuleNotFoundError)
        and (_exc.name or "").split(".")[0] == "cryptography"
    ):
        raise
    _HAVE_PROVIDERS = False

_PROVIDER_NAMES = (
    "SWCSP",
    "IdemixCSP",
    "IdemixVerifyItem",
    "get_default",
    "init_factories",
    "csp_from_config",
    "InMemoryKeyStore",
    "FileKeyStore",
    "DummyKeyStore",
)

__all__ = [
    "CSP",
    "Key",
    "ECDSAP256PublicKey",
    "ECDSAP256PrivateKey",
    "VerifyBatchItem",
]
if _HAVE_PROVIDERS:
    __all__ += list(_PROVIDER_NAMES)
else:
    def __getattr__(name: str):  # pragma: no cover - minimal hosts
        # keep the diagnostic actionable: without this, a minimal host
        # sees a bare "cannot import name 'SWCSP'" with no hint that
        # installing cryptography is the fix
        if name in _PROVIDER_NAMES:
            raise ImportError(
                f"fabric_tpu.csp.{name} requires the 'cryptography' "
                "package, which is not installed on this host"
            )
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        )
