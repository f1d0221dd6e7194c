"""configtxgen: build genesis blocks from configtx.yaml profiles
(reference internal/configtxgen/{genesisconfig,encoder} + cmd/configtxgen).

Supported schema (subset):

    Organizations:
      - Name: Org1
        ID: Org1MSP
        MSPDir: crypto-config/peerOrganizations/org1.example.com/msp
      - name: idemixMSP1               # anonymous clients (idemix.rst)
        id: idemixMSPID1
        msptype: idemix
        mspdir: idemix-config          # idemixgen ca-keygen's output
    Profiles:
      TwoOrgsApplicationGenesis:
        Orderer:
          OrdererType: solo            # or raft/etcdraft
          BatchTimeout: 250ms
          BatchSize: {MaxMessageCount: 10}
          Organizations: [Orderer]
          Addresses: [127.0.0.1:7050]
        Application:
          Organizations: [Org1, Org2]

Flags mirror the reference: -profile, -channelID, -outputBlock,
-inspectBlock, -configPath.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import yaml

from fabric_tpu.common import configtx_builder as ctx
from fabric_tpu.msp.config import load_msp_dir
from fabric_tpu.protos.common import common_pb2


def _key(org: dict, key: str, default=None):
    """An organisation's key, whatever its case (upstream reads
    configtx.yaml through viper: `idemix.rst` writes `msptype`)."""
    for k, v in org.items():
        if k.lower() == key.lower():
            return v
    return default


def _org_groups(org_names, org_index, config_dir):
    out = {}
    for name in org_names or []:
        org = {
            canon: _key(org_index[name], canon)
            for canon in ("Name", "ID", "MSPDir", "MSPType")
        }
        msp_dir = org["MSPDir"]
        if not os.path.isabs(msp_dir):
            msp_dir = os.path.join(config_dir, msp_dir)
        if str(_key(org, "MSPType", "")).lower() == "idemix":
            # an org of anonymous clients (docs/source/idemix.rst):
            # MSPDir is idemixgen's output, the issuer's public key
            from fabric_tpu.msp.idemixmsp import load_idemix_msp_dir

            try:
                conf = load_idemix_msp_dir(msp_dir, org["ID"])
            except OSError as e:
                raise SystemExit(
                    f"MSPDir {msp_dir!r} for org {org['Name']!r} has no "
                    f"IssuerPublicKey (run idemixgen ca-keygen first?): {e}"
                ) from e
        else:
            conf = load_msp_dir(msp_dir, org["ID"])
            from fabric_tpu.protos.msp import msp_config_pb2

            fconf = msp_config_pb2.FabricMSPConfig.FromString(conf.config)
            if not fconf.root_certs:
                raise SystemExit(
                    f"MSPDir {msp_dir!r} for org {org['Name']!r} has no CA "
                    "certs (run cryptogen first?)"
                )
        out[org["Name"]] = ctx.org_group(org["ID"], conf)
    return out


def build_genesis(doc: dict, profile_name: str, channel_id: str,
                  config_dir: str) -> common_pb2.Block:
    profile = (doc.get("Profiles") or {})[profile_name]
    org_index = {_key(o, "Name"): o for o in doc.get("Organizations") or []}

    app = None
    if profile.get("Application"):
        app = ctx.application_group(
            _org_groups(
                profile["Application"].get("Organizations"), org_index,
                config_dir,
            )
        )
    ordg = None
    addresses = None
    if profile.get("Orderer"):
        oconf = profile["Orderer"]
        batch = oconf.get("BatchSize") or {}
        ordg = ctx.orderer_group(
            _org_groups(oconf.get("Organizations"), org_index, config_dir),
            consensus_type=oconf.get("OrdererType", "solo"),
            max_message_count=batch.get("MaxMessageCount", 500),
            absolute_max_bytes=batch.get(
                "AbsoluteMaxBytes", 10 * 1024 * 1024
            ),
            preferred_max_bytes=batch.get(
                "PreferredMaxBytes", 2 * 1024 * 1024
            ),
            batch_timeout=oconf.get("BatchTimeout", "2s"),
        )
        addresses = oconf.get("Addresses")
    group = ctx.channel_group(app, ordg, orderer_addresses=addresses)
    return ctx.genesis_block(channel_id, group)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="configtxgen")
    ap.add_argument("-profile")
    ap.add_argument("-channelID", default="testchannel")
    ap.add_argument("-outputBlock")
    ap.add_argument("-inspectBlock")
    ap.add_argument("-configPath", default=".")
    args = ap.parse_args(argv)

    if args.inspectBlock:
        with open(args.inspectBlock, "rb") as f:
            blk = common_pb2.Block.FromString(f.read())
        print(json.dumps({
            "number": blk.header.number,
            "previous_hash": blk.header.previous_hash.hex(),
            "data_hash": blk.header.data_hash.hex(),
            "tx_count": len(blk.data.data),
        }, indent=2))
        return 0

    if not args.profile or not args.outputBlock:
        ap.error("-profile and -outputBlock are required")
    cfg = os.path.join(args.configPath, "configtx.yaml")
    with open(cfg) as f:
        doc = yaml.safe_load(f) or {}
    blk = build_genesis(doc, args.profile, args.channelID, args.configPath)
    with open(args.outputBlock, "wb") as f:
        f.write(blk.SerializeToString())
    print(f"wrote genesis block for {args.channelID!r} to {args.outputBlock}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
