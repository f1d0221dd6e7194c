"""The world of `smallbank-100k-zipf`: SmallBank (the macro benchmark of
Blockbench, as Hyperledger Caliper ships it; the procedures of H-Store's
and OLTP-Bench's SmallBank) on the five-organisation MAJORITY channel
of `x509-majority` (`benchlib/generator.py`: its CAs, organisations,
client and peers), over accounts that every ledger holds before its
first measured block.

An account is two rows of chaincode `benchcc`, `savings_<id>` and
`checking_<id>`, a balance each as a ten-byte decimal (`%010d`).  The
`setup_blocks` create all of them: `setup_txs_per_block` transactions a
block, each a blind write of `setup_accounts_per_tx` accounts' two rows
at `opening_balance`, endorsed by 3 of 5 as every transaction here is
(a signature a transaction, none a row).  The measured blocks hold the
five updating procedures, drawn by `operations`' shares; `query`
(Balance) is evaluated and never ordered, so no block holds one:

    transact_savings(a)   reads and writes savings_a
    deposit_checking(a)   reads and writes checking_a
    send_payment(a, b)    reads and writes checking_a, checking_b;
                          fails at the endorser where checking_a is
                          under the amount
    write_check(a)        reads savings_a, checking_a, writes checking_a
                          (a unit more where the two are under the amount)
    amalgamate(a, b)      reads savings_a, checking_a, checking_b, writes
                          all three: a's to 0, their sum onto checking_b

Accounts are drawn Zipfian over the accounts ranked by id (constant
`zipf_constant`), `a != b`; amounts 1 to `amount_max`.  A draw whose
simulation fails is drawn again, accounts and amount, the procedure
kept: a client whose proposal is refused submits nothing, and the mix
stays what `operations` says (`redrawn` counts them).

ENDORSED A BLOCK BEHIND (`endorsement_lag_blocks` L, 1 here): every
transaction of measured block k is simulated against the state as it
stands after block k - 1 - L (never before the state the set-up left),
and its reads carry those versions.  So it is valid only if no valid
transaction of the L blocks before k, and none earlier in k, wrote a
key it read.  The world works that out itself, flag by flag, in the
order of a validator's checks (creator signature, endorsement policy,
MVCC), keeping the state as commits leave it (`_Bank`), without the
program.  Planted beside what the traffic makes, in every measured
block, as the older configurations plant theirs:
`bad_creator_per_block` corrupted creator signatures,
`bad_endorsement_per_block` transactions with one of three endorsement
signatures corrupted (3 of 5 then fails) and `conflict_pairs_per_block`
pairs of `deposit_checking` on one account that nothing else has
touched since the state their endorser saw: the first is VALID, the
second MVCC_READ_CONFLICT, whatever the hot accounts do around them.

What the world keeps for the condition `smallbank-shape`, the
configuration's file and the run's lines, per measured block:
`mvcc_refused` (the transactions its MVCC model refuses), `read_keys`
(the distinct rows the transactions that reach MVCC read: every one is
populated, so the program's preload has to find as many),
`planted_classes` (a pair counts where its flags came out VALID, then
MVCC_READ_CONFLICT), `redrawn`, and the pass's `operations`.

TWO SEEDS.  The configuration's `workload_seed` fixes THE WORK, for
every `--seed`: the procedure, the accounts and the amount of every
transaction of every set-up and measured block, and every planted
place, so which rows collide, how many transactions commit and how many
rows a commit group writes are the configuration's and not the run's
(as `timeoutcut-2s` fixes its arrivals with `arrival_seed`: PERF.md
section 6, PR 34).  `--seed` fixes what it fixes in every other world,
THE KEY MATERIAL: every CA's and identity's key, the transactions'
nonces (so the transaction ids) and which of a planted transaction's
three endorsement signatures is the corrupted one.  What stays random,
as in the accepted worlds: ECDSA signature nonces, certificate serial
numbers and validity instants.  Nothing here touches JAX.

The world is built only for a program that counts what the condition
reads (`fabric_tpu.ledger.txmgmt.mvcc_tally`): a checkout without it is
refused before anything is measured.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import random

from benchlib.generator import (
    BAD_CREATOR_SIGNATURE,
    CHAINCODE,
    CHANNEL,
    ENDORSEMENT_POLICY_FAILURE,
    MVCC_READ_CONFLICT,
    VALID,
    Org,
    _flip_last_byte,
    _seeded_ca,
)
from benchlib.manifest import ManifestError

OPERATIONS = ("transact_savings", "deposit_checking", "send_payment", "write_check",
              "amalgamate")
BALANCE = b"%010d"        # a row's value: a ten-byte decimal


def savings(account: int) -> str:
    return f"savings_{account}"


def checking(account: int) -> str:
    return f"checking_{account}"


class Net:
    """The channel: five organisations with a CA each, the peers of
    the first `endorsers_per_tx`, one client of the first, the genesis
    block; and the envelopes and blocks they sign."""

    def __init__(self, rng: random.Random, deployment: dict):
        from fabric_tpu.common import configtx_builder as ctx
        from fabric_tpu.csp import SWCSP
        from fabric_tpu.msp import msp_config_from_ca
        from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

        sw = SWCSP()
        orgs = [
            Org(f"Org{i + 1}MSP",
                _seeded_ca(rng, f"ca.org{i + 1}msp.example.com", f"Org{i + 1}MSP"), sw)
            for i in range(int(deployment["orgs"]))
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
            max_message_count=int(deployment["block_txs"]),
        )
        self.genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))
        self.client = orgs[0].signer(rng, "client", "client")
        self.peers = [o.signer(rng, f"peer{i}", "peer")
                      for i, o in enumerate(orgs[:int(deployment["endorsers_per_tx"])])]
        self.public = {"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in orgs}}
        self._creator = self.client.serialize()
        self._cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
        self._ok = proposal_pb2.Response(status=200)

    def envelope(self, rng: random.Random, args: list, reads, writes,
                 bad_creator: bool = False, bad_endorsement: bool = False) -> bytes:
        """A serialized Envelope: `reads` are (key, (block, tx)),
        `writes` (key, value), both in key order as a simulator leaves
        them; every peer endorses."""
        from fabric_tpu import protoutil
        from fabric_tpu.protos.ledger.rwset import rwset_pb2
        from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2

        kv = kv_rwset_pb2.KVRWSet()
        for key, (block_num, tx_num) in reads:
            version = kv.reads.add(key=key).version
            version.block_num, version.tx_num = block_num, tx_num
        for key, value in writes:
            kv.writes.add(key=key, value=value)
        results = rwset_pb2.TxReadWriteSet(data_model=rwset_pb2.TxReadWriteSet.KV)
        results.ns_rwset.add(namespace=CHAINCODE, rwset=kv.SerializeToString())
        prop, _txid = protoutil.create_chaincode_proposal(
            self._creator, CHANNEL, CHAINCODE, args, nonce=rng.randbytes(24))
        resps = [
            protoutil.create_proposal_response(
                prop, results=results.SerializeToString(), events=b"", response=self._ok,
                chaincode_id=self._cc_id, endorser_signer=p,
            )
            for p in self.peers
        ]
        if bad_endorsement:
            e = resps[rng.randrange(len(resps))].endorsement
            e.signature = _flip_last_byte(e.signature)
        env = protoutil.create_signed_tx(prop, self.client, resps)
        if bad_creator:
            env.signature = _flip_last_byte(env.signature)
        return env.SerializeToString()

    @staticmethod
    def block(number: int, envelopes: list) -> bytes:
        from fabric_tpu.protos.common import common_pb2

        blk = common_pb2.Block()
        blk.header.number = number
        blk.data.data.extend(envelopes)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        return blk.SerializeToString()


class _Bank:
    """The rows as commits leave them, key -> (balance, (block, tx)),
    and what an endorser a few blocks behind sees of them: `begin()`
    opens a block, `seen(key)` is the row as it stood `lag` blocks
    before the open one, `commit()` lands a valid transaction's writes."""

    def __init__(self, lag: int):
        self.rows: dict = {}
        # of the `lag` blocks before the open one and of the open one
        # itself, oldest first: the rows each changed, as they were before
        self._before: collections.deque = collections.deque(maxlen=lag + 1)

    def begin(self) -> None:
        self._before.append({})

    def seen(self, key: str) -> tuple:
        for changed in self._before:
            if key in changed:
                return changed[key]
        return self.rows[key]

    def touched(self, key: str) -> bool:
        """Whether a block the endorser is behind by, or the open one so
        far, changed the row: a read of it as `seen` would then conflict."""
        return any(key in changed for changed in self._before)

    def commit(self, version: tuple, writes: dict) -> None:
        mine = self._before[-1]
        for key, balance in writes.items():
            mine.setdefault(key, self.rows.get(key))
            self.rows[key] = (balance, version)


def _simulate(op: str, a: int, b: int, amount: int, seen) -> dict | None:
    """What the chaincode writes, key -> balance, from the rows an
    endorser sees (H-Store's procedures); None where it refuses.  The
    keys it reads are the keys asked of `seen`."""
    if op == "transact_savings":
        return {savings(a): seen(savings(a)) + amount}
    if op == "deposit_checking":
        return {checking(a): seen(checking(a)) + amount}
    if op == "send_payment":
        have = seen(checking(a))
        if have < amount:
            return None
        return {checking(a): have - amount, checking(b): seen(checking(b)) + amount}
    if op == "write_check":
        total = seen(savings(a)) + seen(checking(a))
        return {checking(a): seen(checking(a)) - amount - (1 if total < amount else 0)}
    if op == "amalgamate":
        total = seen(savings(a)) + seen(checking(a))
        return {savings(a): 0, checking(a): 0, checking(b): seen(checking(b)) + total}
    raise ValueError(op)


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself (see the module's docstring)."""

    genesis: object
    setup_blocks: list
    blocks: list
    planted: list
    lanes_per_block: int
    public: dict
    rows: dict                    # key -> (balance, (block, tx)) after every block
    txs: list                     # per measured block: (procedure, a, b, amount) a transaction
    accounts: int
    mvcc_refused: list            # per measured block
    read_keys: list
    planted_classes: list         # per measured block: class -> transactions planted
    redrawn: int
    operations: dict              # procedure -> transactions of the pass
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        return {(CHAINCODE, key): (BALANCE % balance, version)
                for key, (balance, version) in self.rows.items()}


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    try:
        from fabric_tpu.ledger.txmgmt import mvcc_tally  # noqa: F401
    except ImportError as e:
        raise ManifestError(
            "this checkout's ledger keeps no count of what its MVCC preload found and "
            "what MVCC refused (ledger.txmgmt.mvcc_tally): whether a run of "
            f"smallbank-100k-zipf read the populated state cannot be told ({e})"
        ) from e

    # the key material is the run's, the work the configuration's
    keys = random.Random(f"fabric-bench-smallbank:{int(seed)}")
    rng = random.Random(f"fabric-bench-smallbank-work:{int(deployment['workload_seed'])}")
    n_txs = int(deployment["block_txs"])
    accounts = int(deployment["accounts"])
    opening = int(deployment["opening_balance"])
    amount_max = int(deployment["amount_max"])
    per_tx = int(deployment["setup_accounts_per_tx"])
    per_block = int(deployment["setup_txs_per_block"])
    shares = [float(deployment["operations"][op]) for op in OPERATIONS]

    net = Net(keys, deployment)
    bank = _Bank(int(deployment["endorsement_lag_blocks"]))

    # -- the set-up blocks: every account, a few fat transactions a block
    setup_blocks, envelopes = [], []
    value = BALANCE % opening
    for tx, first in enumerate(range(0, accounts, per_tx)):
        last = min(first + per_tx, accounts)
        rows = sorted(row(i) for i in range(first, last) for row in (savings, checking))
        envelopes.append(net.envelope(
            keys, [b"create_accounts", b"%d" % first, b"%d" % last], (),
            [(k, value) for k in rows]))
        version = (1 + len(setup_blocks), tx % per_block)
        bank.rows.update(dict.fromkeys(rows, (opening, version)))
        if len(envelopes) == per_block or last == accounts:
            setup_blocks.append(net.block(1 + len(setup_blocks), envelopes))
            envelopes = []

    # -- the measured blocks
    ranks = range(accounts)
    cum = list(itertools.accumulate(
        1.0 / (r + 1) ** float(deployment["zipf_constant"]) for r in ranks))
    n_bad_c = int(planted["bad_creator_per_block"])
    n_bad_e = int(planted["bad_endorsement_per_block"])
    n_pairs = int(planted["conflict_pairs_per_block"])
    blocks, flags_all, refused_all, read_all, classes_all, txs_all = [], [], [], [], [], []
    operations: collections.Counter = collections.Counter()
    redrawn = 0
    def endorse(op, a, b, amount):
        """(the rows the endorser saw, key -> (balance, version); what
        the chaincode writes, or None where it refuses)."""
        saw: dict = {}
        return saw, _simulate(op, a, b, amount,
                              lambda key: saw.setdefault(key, bank.seen(key))[0])

    for bno in range(n_blocks):
        number = 1 + len(setup_blocks) + bno
        picks = rng.sample(range(n_txs), n_bad_c + n_bad_e + 2 * n_pairs)
        bad_creator = set(picks[:n_bad_c])
        bad_endorse = set(picks[n_bad_c:n_bad_c + n_bad_e])
        pairs = [tuple(sorted(picks[j:j + 2]))
                 for j in range(n_bad_c + n_bad_e, len(picks), 2)]
        pair_first = dict(pairs)
        pair_second: dict = {}        # the later place of a pair -> its account
        bank.begin()
        envelopes, flags, read_keys, txs = [], [], set(), []

        for i in range(n_txs):
            if i in pair_first or i in pair_second:
                # a planted pair: two deposits to the checking row of one
                # account that no block the endorser is behind by, and no
                # transaction of this one so far, has written
                op = "deposit_checking"
                if i in pair_first:
                    # (a world of a dozen accounts, a test's, may hold
                    # no such account: the scan then ends where it began
                    # and the pair's flags say what became of it)
                    a = start = rng.randrange(accounts)
                    while bank.touched(checking(a)):
                        a = (a + 1) % accounts
                        if a == start:
                            break
                    pair_second[pair_first[i]] = a
                else:
                    a = pair_second[i]
                b, amount = a, rng.randint(1, amount_max)
                saw, writes = endorse(op, a, b, amount)
            else:
                (op,) = rng.choices(OPERATIONS, weights=shares)
                while True:
                    a, b = rng.choices(ranks, cum_weights=cum, k=2)
                    while b == a:
                        (b,) = rng.choices(ranks, cum_weights=cum)
                    amount = rng.randint(1, amount_max)
                    saw, writes = endorse(op, a, b, amount)
                    if writes is not None:
                        break
                    redrawn += 1
            operations[op] += 1
            txs.append((op, a, b, amount))
            reads = {key: version for key, (_balance, version) in saw.items()}
            envelopes.append(net.envelope(
                keys, [op.encode(), b"%d" % a, b"%d" % b, b"%d" % amount],
                sorted(reads.items()),
                [(k, BALANCE % v) for k, v in sorted(writes.items())],
                bad_creator=i in bad_creator, bad_endorsement=i in bad_endorse))
            if i in bad_creator:
                flags.append(BAD_CREATOR_SIGNATURE)
            elif i in bad_endorse:
                flags.append(ENDORSEMENT_POLICY_FAILURE)
            else:
                read_keys.update(reads)
                if any(bank.rows[k][1] != version for k, version in reads.items()):
                    flags.append(MVCC_READ_CONFLICT)
                else:
                    flags.append(VALID)
                    bank.commit((number, i), writes)
        blocks.append(net.block(number, envelopes))
        flags_all.append(flags)
        refused_all.append(flags.count(MVCC_READ_CONFLICT))
        read_all.append(len(read_keys))
        txs_all.append(txs)
        classes_all.append({
            "bad_creator": len(bad_creator), "bad_endorsement": len(bad_endorse),
            "conflict_pair": sum(1 for p, q in pairs
                                 if (flags[p], flags[q]) == (VALID, MVCC_READ_CONFLICT))})
    return World(
        genesis=net.genesis, setup_blocks=setup_blocks, blocks=blocks, planted=flags_all,
        lanes_per_block=n_txs * (1 + len(net.peers)), public=net.public, rows=bank.rows,
        txs=txs_all, accounts=accounts, mvcc_refused=refused_all, read_keys=read_all,
        planted_classes=classes_all, redrawn=redrawn, operations=dict(operations),
    )
