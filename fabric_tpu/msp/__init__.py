"""Membership service provider (X.509 identity layer).

Reference: msp/ (interfaces msp/msp.go:16,60,118,173; impl mspimpl.go).
Identities expose `verification_item` so signature checks batch onto the
TPU data plane instead of being verified one at a time.
"""

from fabric_tpu.msp.identity import Identity, SigningIdentity
from fabric_tpu.msp.msp import (
    MSP,
    MSPError,
    MSPManager,
    msp_config_name,
    msp_from_config,
)
from fabric_tpu.msp.config import msp_config_from_ca, load_msp_dir, write_msp_dir

__all__ = [
    "Identity",
    "SigningIdentity",
    "MSP",
    "MSPError",
    "MSPManager",
    "msp_config_name",
    "msp_from_config",
    "msp_config_from_ca",
    "load_msp_dir",
    "write_msp_dir",
]
