"""Seeded world and block generator, the one X.509 generator of the
tree (its original, `_build_world`/`_make_blocks` of a script that
PR 30 deleted, drove the endorser; this is that made seeded and
quick).  `benchmarks/worlds/x509-majority.py` gives it to the
harness under the name the configurations use; its helpers (seeded
keys and CAs, `Org`) are there for a later world to build on.

What `--seed` fixes: every organisation's CA key and every identity's
key (`ec.derive_private_key` from seeded scalars), key names and
values, transaction nonces, which transactions carry a corrupted
creator or endorsement signature, which endorsement is the corrupted
one, and which pairs of transactions conflict.  What stays random:
ECDSA signature nonces, certificate serial numbers and the
`not_valid_before` instant, and with the creator's certificate the
transaction ids, which hash it behind the nonce.  None of them changes
the work.

Quick, because every run of every later check pays block generation as
set-up: the original drives `Endorser.process_proposal`, which
deserialises, validates and verifies the client three times a
transaction (2.5 s a 1000-tx 3-of-5 block on this sandbox's CPU).  Here
one simulator run gives the read-write set and each endorser signs it
through the same `protoutil.create_proposal_response` the endorser
calls, so the bytes a peer receives are the same.

Nothing here touches JAX: the harness runs it while the device
initialises.
"""

from __future__ import annotations

import dataclasses
import datetime
import random

CHANNEL = "benchch"
CHAINCODE = "benchcc"

# transaction validation codes (protos/peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

_P256_ORDER = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551


def _seeded_key(rng: random.Random):
    from cryptography.hazmat.primitives.asymmetric import ec

    return ec.derive_private_key(rng.randrange(1, _P256_ORDER), ec.SECP256R1())


def _seeded_ca(rng: random.Random, common_name: str, org: str):
    """A `fabric_tpu.common.crypto.CA` whose key comes from the seed.
    `CA.__init__` draws a random key, so the object is filled in here
    with the same self-signed certificate it would build."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes

    from fabric_tpu.common import crypto

    ca = crypto.CA.__new__(crypto.CA)
    ca.key = _seeded_key(rng)
    ca.org = org
    subject = crypto._name(common_name, org)
    now = datetime.datetime.now(datetime.timezone.utc)
    pub = ca.key.public_key()
    ca.cert = (
        x509.CertificateBuilder()
        .subject_name(subject)
        .issuer_name(subject)
        .public_key(pub)
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=3650))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None), critical=True)
        .add_extension(
            x509.KeyUsage(
                digital_signature=True, key_cert_sign=True, crl_sign=True,
                content_commitment=False, key_encipherment=False,
                data_encipherment=False, key_agreement=False,
                encipher_only=False, decipher_only=False,
            ),
            critical=True,
        )
        .add_extension(x509.SubjectKeyIdentifier(crypto._ski(pub)), critical=False)
        .sign(ca.key, hashes.SHA256())
    )
    ca.parent = None
    ca._revoked = []
    return ca


@dataclasses.dataclass
class Org:
    mspid: str
    ca: object
    csp: object

    def signer(self, rng: random.Random, name: str, role_ou: str):
        from fabric_tpu.common.crypto import CertKeyPair
        from fabric_tpu.msp import SigningIdentity

        key = _seeded_key(rng)
        cert = self.ca.issue_for_public_key(name, key.public_key(), ous=[role_ou])
        pair = CertKeyPair(cert, key)
        return SigningIdentity.from_pem(
            self.mspid, pair.cert_pem, pair.key_pem, self.csp
        )


@dataclasses.dataclass
class World:
    """What a run is generated from and checked against: the contract
    in `benchlib/manifest.py`, and what this kind of world keeps for
    itself (`orgs`, `writes`)."""

    orgs: list
    genesis: object               # common_pb2.Block
    blocks: list                  # serialized Block bytes, numbers 1..n
    planted: list                 # per block: per tx, the flag the generator expects
    writes: list                  # per block: per tx, (key, value)
    lanes_per_block: int          # signatures a block carries
    public: dict                  # {"ca_certs_pem": mspid -> CA certificate}, for the reference
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        """(namespace, key) -> (value, (block, tx)) after all blocks, as
        the generator planted it: the last valid write of each key."""
        state: dict = {}
        for bno, (want, wrote) in enumerate(zip(self.planted, self.writes)):
            for i, (flag, (key, value)) in enumerate(zip(want, wrote)):
                if flag == VALID:
                    state[CHAINCODE, key] = (value, (1 + bno, i))
        return state


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    """`n_blocks` blocks of `deployment["block_txs"]` endorsed
    transactions, each endorsed by the first `endorsers_per_tx`
    organisations, one client identity of the first organisation."""
    from fabric_tpu import protoutil
    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.msp import msp_config_from_ca
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    rng = random.Random(f"fabric-bench:{int(seed)}")
    sw = SWCSP()
    n_orgs = int(deployment["orgs"])
    n_txs = int(deployment["block_txs"])
    endorsers = int(deployment["endorsers_per_tx"])
    value_bytes = int(deployment["value_bytes"])

    orgs = [
        Org(f"Org{i + 1}MSP",
            _seeded_ca(rng, f"ca.org{i + 1}msp.example.com", f"Org{i + 1}MSP"), sw)
        for i in range(n_orgs)
    ]
    oorg = Org("OrdererMSP",
               _seeded_ca(rng, "ca.orderermsp.example.com", "OrdererMSP"), sw)
    app = ctx.application_group({
        f"Org{i + 1}": ctx.org_group(o.mspid, msp_config_from_ca(o.ca, o.mspid))
        for i, o in enumerate(orgs)
    })
    ordg = ctx.orderer_group(
        {"O": ctx.org_group("OrdererMSP", msp_config_from_ca(oorg.ca, "OrdererMSP"))},
        consensus_type=deployment.get("orderer", "solo"),
        max_message_count=n_txs,
    )
    genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))

    client = orgs[0].signer(rng, "client", "client")
    peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(orgs[:endorsers])]
    creator = client.serialize()
    cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
    ok = proposal_pb2.Response(status=200)

    # an in-memory ledger at genesis: every key is fresh, so every
    # simulated read sees "absent", as on the timed ledgers
    sim_ledger = LedgerProvider(None).create(genesis)

    n_bad_c = int(planted["bad_creator_per_block"])
    n_bad_e = int(planted["bad_endorsement_per_block"])
    n_conf = int(planted["conflict_pairs_per_block"])

    blocks, flags_all, writes_all = [], [], []
    for bno in range(n_blocks):
        picks = rng.sample(range(n_txs), n_bad_c + n_bad_e + 2 * n_conf)
        bad_creator = set(picks[:n_bad_c])
        bad_endorse = set(picks[n_bad_c:n_bad_c + n_bad_e])
        pairs = picks[n_bad_c + n_bad_e:]
        # tx -> the earlier tx whose key it shares (read absent, write)
        shares: dict[int, int] = {}
        readers: set[int] = set()
        for a, b in zip(pairs[0::2], pairs[1::2]):
            first, second = min(a, b), max(a, b)
            shares[second] = first
            readers.update((first, second))
        want = [VALID] * n_txs
        keys = [f"k{bno}-{i}-{rng.getrandbits(40):010x}" for i in range(n_txs)]
        envs, wrote = [], []
        for i in range(n_txs):
            key = keys[shares.get(i, i)]
            value = rng.randbytes(value_bytes)
            prop, _txid = protoutil.create_chaincode_proposal(
                creator, CHANNEL, CHAINCODE, [key.encode(), value],
                nonce=rng.randbytes(24),
            )
            sim = sim_ledger.new_tx_simulator()
            if i in readers:
                sim.get_state(CHAINCODE, key)
            sim.set_state(CHAINCODE, key, value)
            results = sim.get_tx_simulation_results()
            resps = [
                protoutil.create_proposal_response(
                    prop, results=results, events=b"", response=ok,
                    chaincode_id=cc_id, endorser_signer=p,
                )
                for p in peers
            ]
            if i in bad_endorse:
                e = resps[rng.randrange(len(resps))].endorsement
                e.signature = _flip_last_byte(e.signature)
                want[i] = ENDORSEMENT_POLICY_FAILURE
            env = protoutil.create_signed_tx(prop, client, resps)
            if i in bad_creator:
                env.signature = _flip_last_byte(env.signature)
                want[i] = BAD_CREATOR_SIGNATURE
            if i in shares:
                want[i] = MVCC_READ_CONFLICT
            envs.append(env.SerializeToString())
            wrote.append((key, value))
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        blk.data.data.extend(envs)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
        flags_all.append(want)
        writes_all.append(wrote)
    return World(
        orgs=orgs, genesis=genesis, blocks=blocks, planted=flags_all,
        writes=writes_all, lanes_per_block=n_txs * (1 + endorsers),
        public={"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in orgs}},
    )


def _flip_last_byte(sig: bytes) -> bytes:
    """Still strict DER, still low-S with overwhelming odds, wrong s
    (as `chip_smoke.py` leg A corrupts)."""
    return sig[:-1] + bytes([sig[-1] ^ 1])
