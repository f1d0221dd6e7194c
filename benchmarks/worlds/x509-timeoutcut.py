"""The world of `timeoutcut-2s`: the five-organisation MAJORITY channel
of `x509-majority` (`benchlib/generator.py`) with one thing changed,
the CUT.  There every block is full; here the blocks are what an
orderer under upstream's default batch settings cuts from a light,
bursty load: transactions arrive as a Poisson process whose rate
follows a fixed cycle (`deployment["load"]`: `cycle`, the phases in
order from t = 0, and `arrival_seed`, the draw), and the arrivals go,
in time order, through the rule upstream's solo consenter applies
(orderer/consensus/solo consensus.go main loop; this tree's
`fabric_tpu/orderer/solo.py`):

    a message that enters an EMPTY batch arms the batch timer
    (`BatchTimeout`); messages that follow leave it alone; when it
    fires the pending batch is cut, whatever it holds; a batch that
    `BlockCutter.ordered` cuts (`MaxMessageCount` reached, or the
    preferred bytes overrun) and that leaves nothing pending disarms
    it; a timer that fires on nothing pending cuts no block.

The first `n_blocks` blocks are the world.  The cutter is the program's
own `BlockCutter`, and it is shown every message at ONE length,
`deployment["envelope_bytes"]`, which the configuration states (4,333:
what this network's envelope, three endorsements with their
certificates, measures, give or take four bytes).  Not at its own
length, for two reasons.  A block's transactions can only be made once
its size is known (what is planted depends on it).  And 484 such
envelopes stand within 20 bytes of `PreferredMaxBytes` (2 MiB / 484 =
4,332.96), while an envelope's length moves by a byte or two with every
signature's random nonce and every certificate's random serial number:
at their own lengths the same seed would cut 482 transactions in one
run and 484 in the next (the mean of a run's envelopes read 4,333 to
4,343 over four runs).  So the rule that cuts a burst here is the BYTE
rule, at 483 transactions, and `MaxMessageCount` 500 is never reached:
that is upstream's default configuration meeting this network's
transactions, not a choice of this world's.  A full block may thus
stand a few hundred bytes to either side of `PreferredMaxBytes`, which
is a preference (upstream cuts a batch over it whenever one message is
large); no block is over `AbsoluteMaxBytes`, and a world whose
envelopes are, in the mean, more than 2% off the stated length is
refused: the byte rule would have been shown another network's
messages.

`deployment["block_txs"]` IS `MaxMessageCount`.  Where a rehearsal or a
contract test overrides it with a toy size, the load's rates are scaled
by `block_txs / orderer_batch.max_message_count`, so a toy pass keeps
the make-up of the real one (a few blocks of one or two transactions,
many a fraction full, a few full); at the configuration's own size the
factor is 1.  Times are never scaled: they stand against the timeout.

Planted (`planted` of the configuration), so that "accept everything"
and "accept what is too small for the device" are both wrong many times
a pass: a block of `full_set_from_txs` transactions or more carries
`bad_creator_per_block` corrupted creator signatures,
`bad_endorsement_per_block` corrupted endorsement signatures and
`conflict_pairs_per_block` conflicting pairs; a smaller block of two or
more carries ONE of the three, by its number mod 3 (0 creator, 1
endorsement, 2 pair); a block of one transaction carries a corrupted
creator signature when its number is a multiple of 3.

What `--seed` fixes: every key, key names, values, nonces and every
planted place.  The ARRIVAL TIMES are one draw, the configuration's
(`deployment["load"]["arrival_seed"]`), so every seed replays the same
block sizes, as every seed of the other cells replays blocks of one
size.  (A draw a seed moved a pass's transactions by 3.5% either way
and their share in full blocks from 24 to 42%, and the cell's rate with
them by more than a new cell's runs may spread: the configuration's
file has the numbers.)  What stays random, as in the accepted worlds:
ECDSA signature nonces, certificate serial numbers and validity
instants.  Nothing here touches JAX.

For the condition `timeoutcut-shape` and the tests the world keeps, of
every block, its arrival times, when and by what it was cut, and its
transactions.
"""

from __future__ import annotations

import dataclasses
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


# how far the mean length of a world's envelopes may stand from
# deployment["envelope_bytes"] (run-to-run it moves by a quarter of a percent)
STATED_LENGTH_HOLDS_WITHIN = 0.02


@dataclasses.dataclass
class World:
    """The contract of `benchlib/manifest.py`, and what this kind keeps
    for itself: `writes`, and how every block came to be cut."""

    genesis: object
    blocks: list
    planted: list
    writes: list
    lanes_per_block: int          # of the LARGEST block (printed only)
    public: dict
    txs_per_block: list           # transactions of block b
    lanes_by_block: list          # signature lanes of block b
    arrivals_s: list              # per block, its transactions' arrival times
    cut_at_s: list                # when block b was cut
    cut_by: list                  # "timeout" | "count" | "bytes"
    channel: str = CHANNEL
    namespaces: tuple = (CHAINCODE,)

    def expected_state(self) -> dict:
        state: dict = {}
        for bno, (want, wrote) in enumerate(zip(self.planted, self.writes)):
            for i, (flag, (key, value)) in enumerate(zip(want, wrote)):
                if flag == VALID:
                    state[CHAINCODE, key] = (value, (1 + bno, i))
        return state


def arrivals(rng: random.Random, cycle: list, scale: float = 1.0):
    """Arrival times without end: a Poisson process whose rate is
    `tx_per_s * scale` through each phase of `cycle` in turn, the cycle
    starting at t = 0 and repeating.  A phase starts its own draws (the
    process has no memory, so nothing is lost at a boundary)."""
    t0 = 0.0
    while True:
        for phase in cycle:
            rate = float(phase["tx_per_s"]) * scale
            end = t0 + float(phase["seconds"])
            t = t0
            while rate > 0:
                t += rng.expovariate(rate)
                if t >= end:
                    break
                yield t
            t0 = end


class Consenter:
    """The solo consenter's main loop over a clock that is handed in:
    `message(t, raw)` is a message taken off the channel at t,
    `advance(t)` lets the timer fire if it is due by t.  Each returns
    the batches cut, as (batch, when, by what).  The cutter is the
    program's own `BlockCutter`."""

    def __init__(self, cutter, batch_timeout_s: float):
        self.cutter = cutter
        self.timeout = batch_timeout_s
        self.deadline = None          # when the armed timer fires

    def advance(self, t: float) -> list:
        if self.deadline is None or self.deadline > t:
            return []
        when, self.deadline = self.deadline, None
        batch = self.cutter.cut()
        # a timer that fires on nothing pending cuts no block
        return [(batch, when, "timeout")] if batch else []

    def message(self, t: float, raw: bytes) -> list:
        out = self.advance(t)
        batches, pending = self.cutter.ordered(raw)
        for batch in batches:
            full = len(batch) >= self.cutter.max_message_count
            out.append((batch, t, "count" if full else "bytes"))
        if not pending:
            self.deadline = None
        elif self.deadline is None:
            self.deadline = t + self.timeout
        return out


def _cutter(batch: dict, max_message_count: int):
    from fabric_tpu.orderer.blockcutter import BlockCutter

    return BlockCutter(
        max_message_count=max_message_count,
        preferred_max_bytes=int(batch["preferred_max_bytes"]),
        absolute_max_bytes=int(batch["absolute_max_bytes"]),
    )


def cut_from_arrivals(times, n_blocks: int, batch: dict, max_message_count: int,
                      message_bytes: int) -> list:
    """The first `n_blocks` blocks the consenter cuts from `times`
    (an iterator without end), every message `message_bytes` long: per
    block (its transactions' arrival times, when it was cut, by what)."""
    consenter = Consenter(_cutter(batch, max_message_count), float(batch["batch_timeout_s"]))
    stand_in = bytes(message_bytes)
    waiting: list = []        # arrival times of what the cutter holds, oldest first
    blocks: list = []
    for t in times:
        waiting.append(t)
        # the cutter is first in, first out, and a timer cut comes
        # before the message that showed it: every batch takes the
        # oldest of `waiting`
        for cut, when, by in consenter.message(t, stand_in):
            blocks.append((waiting[:len(cut)], when, by))
            del waiting[:len(cut)]
            if len(blocks) == n_blocks:
                return blocks
    raise AssertionError("arrivals ended")     # they never do


def _planted_places(rng: random.Random, number: int, n_txs: int, planted: dict):
    """(bad creators, bad endorsements, conflicting pairs as (first,
    second)) of block `number` holding `n_txs` transactions."""
    n_c = int(planted["bad_creator_per_block"])
    n_e = int(planted["bad_endorsement_per_block"])
    n_p = int(planted["conflict_pairs_per_block"])
    if n_txs < int(planted["full_set_from_txs"]):
        if n_txs == 1:
            n_c, n_e, n_p = int(number % 3 == 0), 0, 0
        else:
            kind = number % 3
            n_c, n_e, n_p = int(kind == 0), int(kind == 1), int(kind == 2)
    picks = rng.sample(range(n_txs), n_c + n_e + 2 * n_p)
    pairs = picks[n_c + n_e:]
    return (set(picks[:n_c]), set(picks[n_c:n_c + n_e]),
            [(min(a, b), max(a, b)) for a, b in zip(pairs[0::2], pairs[1::2])])


def build_world(seed: int, deployment: dict, planted: dict, n_blocks: int) -> World:
    from fabric_tpu import protoutil
    from fabric_tpu.common import configtx_builder as ctx
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.msp import msp_config_from_ca
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import chaincode_pb2, proposal_pb2

    rng = random.Random(f"fabric-bench-timeoutcut:{int(seed)}")
    sw = SWCSP()
    n_orgs = int(deployment["orgs"])
    max_count = int(deployment["block_txs"])
    endorsers = int(deployment["endorsers_per_tx"])
    value_bytes = int(deployment["value_bytes"])
    batch = deployment["orderer_batch"]
    timeout_s = float(batch["batch_timeout_s"])
    scale = max_count / int(batch["max_message_count"])

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
        max_message_count=max_count,
        absolute_max_bytes=int(batch["absolute_max_bytes"]),
        preferred_max_bytes=int(batch["preferred_max_bytes"]),
        batch_timeout=f"{timeout_s:g}s",
    )
    genesis = ctx.genesis_block(CHANNEL, ctx.channel_group(app, ordg))

    client = orgs[0].signer(rng, "client", "client")
    peers = [o.signer(rng, f"peer{i}", "peer") for i, o in enumerate(orgs[:endorsers])]
    creator = client.serialize()
    cc_id = chaincode_pb2.ChaincodeID(name=CHAINCODE)
    ok = proposal_pb2.Response(status=200)
    sim_ledger = LedgerProvider(None).create(genesis)

    def envelope(key: str, value: bytes, reads: bool, bad_endorsement: bool,
                 bad_creator: bool) -> bytes:
        prop, _txid = protoutil.create_chaincode_proposal(
            creator, CHANNEL, CHAINCODE, [key.encode(), value], nonce=rng.randbytes(24),
        )
        sim = sim_ledger.new_tx_simulator()
        if reads:
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
        if bad_endorsement:
            e = resps[rng.randrange(len(resps))].endorsement
            e.signature = _flip_last_byte(e.signature)
        env = protoutil.create_signed_tx(prop, client, resps)
        if bad_creator:
            env.signature = _flip_last_byte(env.signature)
        return env.SerializeToString()

    load = deployment["load"]
    times = arrivals(
        random.Random(f"fabric-bench-timeoutcut-arrivals:{int(load['arrival_seed'])}"),
        load["cycle"], scale,
    )
    cut = cut_from_arrivals(times, n_blocks, batch, max_count,
                            int(deployment["envelope_bytes"]))

    blocks, flags_all, writes_all, held_all = [], [], [], 0
    for bno, (when_arrived, _cut_at, _by) in enumerate(cut):
        n_txs = len(when_arrived)
        bad_creator, bad_endorse, pairs = _planted_places(rng, 1 + bno, n_txs, planted)
        shares = {second: first for first, second in pairs}
        readers = {i for pair in pairs for i in pair}
        want = [VALID] * n_txs
        keys = [f"k{bno}-{i}-{rng.getrandbits(40):010x}" for i in range(n_txs)]
        envs, wrote = [], []
        for i in range(n_txs):
            key = keys[shares.get(i, i)]
            value = rng.randbytes(value_bytes)
            envs.append(envelope(key, value, i in readers, i in bad_endorse, i in bad_creator))
            if i in bad_endorse:
                want[i] = ENDORSEMENT_POLICY_FAILURE
            if i in bad_creator:
                want[i] = BAD_CREATOR_SIGNATURE
            if i in shares:
                want[i] = MVCC_READ_CONFLICT
            wrote.append((key, value))
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        blk.data.data.extend(envs)
        while len(blk.metadata.metadata) < 3:
            blk.metadata.metadata.append(b"")
        blocks.append(blk.SerializeToString())
        flags_all.append(want)
        writes_all.append(wrote)
        held = sum(len(e) for e in envs)
        if held > int(batch["absolute_max_bytes"]):
            raise RuntimeError(
                f"block {1 + bno} holds {held} bytes, over AbsoluteMaxBytes: no "
                "BlockCutter under these batch settings cuts such a block"
            )
        held_all += held
    stated = int(deployment["envelope_bytes"])
    mean = held_all / sum(len(arr) for arr, _when, _by in cut)
    if abs(mean - stated) > STATED_LENGTH_HOLDS_WITHIN * stated:
        raise RuntimeError(
            f"this world's envelopes are {mean:.0f} bytes long in the mean, "
            f"deployment['envelope_bytes'] states {stated}: the byte rule was "
            "shown another network's messages"
        )
    sizes = [len(arr) for arr, _when, _by in cut]
    return World(
        genesis=genesis, blocks=blocks, planted=flags_all, writes=writes_all,
        lanes_per_block=max(sizes) * (1 + endorsers),
        public={"ca_certs_pem": {o.mspid: o.ca.cert_pem for o in orgs}},
        txs_per_block=sizes, lanes_by_block=[n * (1 + endorsers) for n in sizes],
        arrivals_s=[arr for arr, _when, _by in cut],
        cut_at_s=[when for _arr, when, _by in cut],
        cut_by=[by for _arr, _when, by in cut],
    )
