"""Channel config-tree construction (configtxgen's encoder core).

Reference: internal/configtxgen/encoder (NewChannelGroup/NewOrdererGroup/
NewApplicationGroup build the ConfigGroup tree from configtx.yaml
profiles) + protoutil genesis assembly.  This is the programmatic
equivalent; the configtxgen CLI feeds parsed YAML profiles into it.
"""

from __future__ import annotations

from fabric_tpu.protos.common import common_pb2, configtx_pb2, configuration_pb2, policies_pb2
from fabric_tpu.protos.msp import msp_config_pb2
from fabric_tpu.protos.orderer import configuration_pb2 as orderer_config_pb2
from fabric_tpu import protoutil
from fabric_tpu.policies import from_string

# config value keys (reference common/channelconfig/*.go key constants)
MSP_KEY = "MSP"
HASHING_ALGORITHM_KEY = "HashingAlgorithm"
BLOCK_DATA_HASHING_STRUCTURE_KEY = "BlockDataHashingStructure"
ORDERER_ADDRESSES_KEY = "OrdererAddresses"
CONSENSUS_TYPE_KEY = "ConsensusType"
BATCH_SIZE_KEY = "BatchSize"
BATCH_TIMEOUT_KEY = "BatchTimeout"
CONSORTIUM_KEY = "Consortium"
ENDORSEMENT_POLICY_KEY = "Endorsement"
ACLS_KEY = "ACLs"

IDEMIX_MSP_TYPE = 1  # MSPConfig.type of an Idemix organisation


def _implicit_meta(group: configtx_pb2.ConfigGroup, name: str, rule, sub_policy: str | None = None):
    group.policies[name].policy.type = policies_pb2.Policy.IMPLICIT_META
    group.policies[name].policy.value = policies_pb2.ImplicitMetaPolicy(
        sub_policy=sub_policy or name, rule=rule
    ).SerializeToString()
    group.policies[name].mod_policy = "Admins"


def _signature_policy(group: configtx_pb2.ConfigGroup, name: str, dsl: str):
    group.policies[name].policy.type = policies_pb2.Policy.SIGNATURE
    group.policies[name].policy.value = from_string(dsl).SerializeToString()
    group.policies[name].mod_policy = "Admins"


def _set_value(group: configtx_pb2.ConfigGroup, key: str, msg, mod_policy="Admins"):
    group.values[key].value = msg.SerializeToString()
    group.values[key].mod_policy = mod_policy


def org_group(mspid: str, msp_conf: msp_config_pb2.MSPConfig, anchor=None) -> configtx_pb2.ConfigGroup:
    """An application/orderer org group: MSP value + org-scoped policies
    (reference encoder.NewOrdererOrgGroup / NewApplicationOrgGroup)."""
    g = configtx_pb2.ConfigGroup()
    g.mod_policy = "Admins"
    _set_value(g, MSP_KEY, msp_conf)
    _signature_policy(g, "Readers", f"'{mspid}.member'")
    _signature_policy(g, "Writers", f"'{mspid}.member'")
    _signature_policy(g, "Admins", f"'{mspid}.admin'")
    if msp_conf.type != IDEMIX_MSP_TYPE:
        # Idemix is for clients only (docs/source/idemix.rst: peers and
        # orderers stay X.509): such an org has no peer to endorse, and
        # its group carries no Endorsement policy, so the application's
        # MAJORITY Endorsement counts the X.509 orgs alone
        _signature_policy(g, ENDORSEMENT_POLICY_KEY, f"'{mspid}.peer'")
    return g


def application_group(
    orgs: dict[str, configtx_pb2.ConfigGroup],
    acls: dict[str, str] | None = None,
) -> configtx_pb2.ConfigGroup:
    """`acls` maps resource names (peer/aclmgmt catalog) to policy refs,
    emitted as the Application ACLs config value (reference
    encoder.NewApplicationGroup addValue(ACLValues), consumed by
    aclmgmt's resourceprovider)."""
    g = configtx_pb2.ConfigGroup()
    g.mod_policy = "Admins"
    R = policies_pb2.ImplicitMetaPolicy
    _implicit_meta(g, "Readers", R.ANY)
    _implicit_meta(g, "Writers", R.ANY)
    _implicit_meta(g, "Admins", R.MAJORITY)
    _implicit_meta(g, "Endorsement", R.MAJORITY, sub_policy=ENDORSEMENT_POLICY_KEY)
    _implicit_meta(g, "LifecycleEndorsement", R.MAJORITY, sub_policy=ENDORSEMENT_POLICY_KEY)
    if acls:
        from fabric_tpu.protos.peer import configuration_pb2 as peer_cfg

        msg = peer_cfg.ACLs()
        for name, ref in acls.items():
            msg.acls[name].policy_ref = ref
        _set_value(g, ACLS_KEY, msg)
    for name, org in orgs.items():
        g.groups[name].CopyFrom(org)
    return g


def orderer_group(
    orgs: dict[str, configtx_pb2.ConfigGroup],
    consensus_type: str = "solo",
    consensus_metadata: bytes = b"",
    max_message_count: int = 500,
    absolute_max_bytes: int = 10 * 1024 * 1024,
    preferred_max_bytes: int = 2 * 1024 * 1024,
    batch_timeout: str = "2s",
) -> configtx_pb2.ConfigGroup:
    g = configtx_pb2.ConfigGroup()
    g.mod_policy = "Admins"
    R = policies_pb2.ImplicitMetaPolicy
    _implicit_meta(g, "Readers", R.ANY)
    _implicit_meta(g, "Writers", R.ANY)
    _implicit_meta(g, "Admins", R.MAJORITY)
    _implicit_meta(g, "BlockValidation", R.ANY, sub_policy="Writers")
    _set_value(
        g, CONSENSUS_TYPE_KEY,
        orderer_config_pb2.ConsensusType(type=consensus_type, metadata=consensus_metadata),
    )
    _set_value(
        g, BATCH_SIZE_KEY,
        orderer_config_pb2.BatchSize(
            max_message_count=max_message_count,
            absolute_max_bytes=absolute_max_bytes,
            preferred_max_bytes=preferred_max_bytes,
        ),
    )
    _set_value(g, BATCH_TIMEOUT_KEY, orderer_config_pb2.BatchTimeout(timeout=batch_timeout))
    for name, org in orgs.items():
        g.groups[name].CopyFrom(org)
    return g


def channel_group(
    application: configtx_pb2.ConfigGroup | None,
    orderer: configtx_pb2.ConfigGroup | None,
    orderer_addresses: list[str] | None = None,
) -> configtx_pb2.ConfigGroup:
    g = configtx_pb2.ConfigGroup()
    g.mod_policy = "Admins"
    R = policies_pb2.ImplicitMetaPolicy
    _implicit_meta(g, "Readers", R.ANY)
    _implicit_meta(g, "Writers", R.ANY)
    _implicit_meta(g, "Admins", R.MAJORITY)
    _set_value(g, HASHING_ALGORITHM_KEY, configuration_pb2.HashingAlgorithm(name="SHA256"))
    _set_value(
        g, BLOCK_DATA_HASHING_STRUCTURE_KEY,
        configuration_pb2.BlockDataHashingStructure(width=0xFFFFFFFF),
    )
    if orderer_addresses:
        _set_value(
            g, ORDERER_ADDRESSES_KEY,
            configuration_pb2.OrdererAddresses(addresses=orderer_addresses),
            mod_policy="/Channel/Orderer/Admins",
        )
    if application is not None:
        g.groups["Application"].CopyFrom(application)
    if orderer is not None:
        g.groups["Orderer"].CopyFrom(orderer)
    return g


def genesis_block(channel_id: str, group: configtx_pb2.ConfigGroup) -> common_pb2.Block:
    """Block 0 wrapping the CONFIG envelope (reference protoutil genesis +
    encoder.NewBootstrapper)."""
    config_env = configtx_pb2.ConfigEnvelope(
        config=configtx_pb2.Config(sequence=0, channel_group=group)
    )
    chdr = protoutil.make_channel_header(common_pb2.CONFIG, channel_id, tx_id="")
    shdr = protoutil.make_signature_header(b"", protoutil.random_nonce())
    payload = protoutil.make_payload_bytes(chdr, shdr, config_env.SerializeToString())
    env = common_pb2.Envelope(payload=payload)
    blk = protoutil.new_block(0, b"")
    blk.data.data.append(env.SerializeToString())
    blk.header.data_hash = protoutil.block_data_hash(blk.data)
    protoutil.set_tx_filter(blk, b"\x00")
    return blk


__all__ = [
    "org_group",
    "application_group",
    "orderer_group",
    "channel_group",
    "genesis_block",
    "MSP_KEY",
    "CONSENSUS_TYPE_KEY",
    "BATCH_SIZE_KEY",
    "BATCH_TIMEOUT_KEY",
    "ENDORSEMENT_POLICY_KEY",
    "ACLS_KEY",
]
