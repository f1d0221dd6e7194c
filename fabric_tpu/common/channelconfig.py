"""Typed, immutable view over a channel configuration.

Reference: common/channelconfig (Bundle bundle.go:32 +
NewBundleFromEnvelope :158 — builds MSPs, the policy manager, and typed
Orderer/Application config from a Config proto in one shot).
"""

from __future__ import annotations

import dataclasses

from fabric_tpu.common import configtx_builder as keys
from fabric_tpu.msp import MSP, MSPManager, msp_config_name, msp_from_config
from fabric_tpu.policies import Manager, manager_from_config_group
from fabric_tpu.protos.common import common_pb2, configtx_pb2
from fabric_tpu.protos.msp import msp_config_pb2
from fabric_tpu.protos.orderer import configuration_pb2 as orderer_config_pb2
from fabric_tpu import protoutil


@dataclasses.dataclass
class OrdererConfig:
    consensus_type: str
    consensus_metadata: bytes
    max_message_count: int
    absolute_max_bytes: int
    preferred_max_bytes: int
    batch_timeout_s: float
    org_mspids: list[str]
    # ConsensusType.State: STATE_NORMAL / STATE_MAINTENANCE (the
    # consensus-type migration gate, reference maintenancefilter.go)
    consensus_state: int = 0


@dataclasses.dataclass
class ApplicationOrg:
    name: str
    mspid: str


@dataclasses.dataclass
class ApplicationConfig:
    orgs: dict[str, ApplicationOrg]


def _parse_timeout(s: str) -> float:
    s = s.strip()
    units = {"ms": 0.001, "s": 1.0, "m": 60.0, "h": 3600.0}
    for suffix, mult in sorted(units.items(), key=lambda kv: -len(kv[0])):
        if s.endswith(suffix):
            return float(s[: -len(suffix)]) * mult
    return float(s)


class Bundle:
    """Immutable resources derived from one Config (reference
    channelconfig.Bundle: PolicyManager/MSPManager/OrdererConfig/
    ApplicationConfig accessors)."""

    def __init__(self, channel_id: str, config: configtx_pb2.Config, csp=None):
        self.channel_id = channel_id
        self.config = config
        group = config.channel_group
        # MSPs from all org groups (reference: channelconfig builds all MSPs
        # via the MSPConfigHandler before policies are compiled)
        msps: list[MSP] = []
        for top in ("Application", "Orderer", "Consortiums"):
            if top not in group.groups:
                continue
            self._collect_msps(group.groups[top], msps, csp)
        # wrapped in the memoizing cache (reference msp/cache); safe for
        # the bundle's lifetime since config changes build a new bundle
        from fabric_tpu.msp.cache import CachedMSP

        self.msp_manager = CachedMSP(MSPManager(msps))
        self.policy_manager: Manager = manager_from_config_group(
            "Channel", group, self.msp_manager
        )
        self.orderer_config = self._orderer_config(group)
        self.application_config = self._application_config(group)
        self.acls = self._acls(group)

    @staticmethod
    def _acls(group: configtx_pb2.ConfigGroup) -> dict[str, str]:
        """Application ACLs value: resource name -> policy ref overrides
        (reference common/channelconfig/acls.go newAPIsProvider, fed to
        aclmgmt's resourceprovider)."""
        if "Application" not in group.groups:
            return {}
        values = group.groups["Application"].values
        if keys.ACLS_KEY not in values:
            return {}
        from fabric_tpu.protos.peer import configuration_pb2 as peer_cfg

        acls = peer_cfg.ACLs.FromString(values[keys.ACLS_KEY].value)
        return {name: a.policy_ref for name, a in acls.acls.items()}

    @staticmethod
    def _collect_msps(group: configtx_pb2.ConfigGroup, out: list[MSP], csp) -> None:
        if keys.MSP_KEY in group.values:
            conf = msp_config_pb2.MSPConfig.FromString(group.values[keys.MSP_KEY].value)
            out.append(msp_from_config(conf, csp))
        for sub in group.groups.values():
            Bundle._collect_msps(sub, out, csp)

    @staticmethod
    def _orderer_config(group: configtx_pb2.ConfigGroup) -> OrdererConfig | None:
        if "Orderer" not in group.groups:
            return None
        og = group.groups["Orderer"]
        ct = orderer_config_pb2.ConsensusType.FromString(
            og.values[keys.CONSENSUS_TYPE_KEY].value
        )
        bs = orderer_config_pb2.BatchSize.FromString(og.values[keys.BATCH_SIZE_KEY].value)
        bt = orderer_config_pb2.BatchTimeout.FromString(
            og.values[keys.BATCH_TIMEOUT_KEY].value
        )
        mspids = []
        for sub in og.groups.values():
            if keys.MSP_KEY in sub.values:
                conf = msp_config_pb2.MSPConfig.FromString(sub.values[keys.MSP_KEY].value)
                mspids.append(msp_config_name(conf))
        return OrdererConfig(
            consensus_type=ct.type,
            consensus_metadata=ct.metadata,
            consensus_state=ct.state,
            max_message_count=bs.max_message_count,
            absolute_max_bytes=bs.absolute_max_bytes,
            preferred_max_bytes=bs.preferred_max_bytes,
            batch_timeout_s=_parse_timeout(bt.timeout),
            org_mspids=mspids,
        )

    @staticmethod
    def _application_config(group: configtx_pb2.ConfigGroup) -> ApplicationConfig | None:
        if "Application" not in group.groups:
            return None
        orgs = {}
        for name, sub in group.groups["Application"].groups.items():
            mspid = name
            if keys.MSP_KEY in sub.values:
                conf = msp_config_pb2.MSPConfig.FromString(sub.values[keys.MSP_KEY].value)
                mspid = msp_config_name(conf)
            orgs[name] = ApplicationOrg(name=name, mspid=mspid)
        return ApplicationConfig(orgs=orgs)


def bundle_from_genesis(block: common_pb2.Block, csp=None) -> Bundle:
    """Reference NewBundleFromEnvelope: unwrap the CONFIG envelope."""
    env = protoutil.extract_envelope(block, 0)
    payload = common_pb2.Payload.FromString(env.payload)
    chdr = common_pb2.ChannelHeader.FromString(payload.header.channel_header)
    if chdr.type != common_pb2.CONFIG:
        raise ValueError("block 0 does not carry a CONFIG transaction")
    config_env = configtx_pb2.ConfigEnvelope.FromString(payload.data)
    return Bundle(chdr.channel_id, config_env.config, csp)


__all__ = [
    "Bundle",
    "OrdererConfig",
    "ApplicationConfig",
    "ApplicationOrg",
    "bundle_from_genesis",
]
