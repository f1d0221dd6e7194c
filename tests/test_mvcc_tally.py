"""What MVCC says of a block since PR 46 (`ledger/txmgmt.py`
`MVCCValidator`, `MvccTally`; `ledger/kvledger.py`): how many keys its
bulk preload asked the state for and how many rows it found (on the
`mvcc.preload` span, a stage of its own under `mvcc`), how many
transactions came in valid and how many it invalidated, by kind (on the
`mvcc` span), `rows` (and since PR 48 `clustered` / `mmap_bytes`, what
the store found at open) on `kv_txn`, three counters on /metrics and
`mvcc_tally()` from process start.  Hand-made blocks through `KVLedger.commit`: reads that hit,
reads of absent keys, an in-block conflict, a conflict with the block
before, a phantom, a transaction that came in refused.  No behaviour
changes: the flags are what they were."""

from fabric_tpu import protoutil
from fabric_tpu.common import tracing
from fabric_tpu.common.metrics import LedgerMetrics, PrometheusProvider
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.ledger.txmgmt import (
    MVCC_COUNTS,
    MVCC_READ_CONFLICT,
    PHANTOM_READ_CONFLICT,
    VALID,
    mvcc_tally,
)
from fabric_tpu.protos.common import common_pb2
from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

CHANNEL = "ch"
REFUSED_BEFORE = transaction_pb2.ENDORSEMENT_POLICY_FAILURE


def _block(num, rwsets, flags=None):
    """A block of endorser transactions whose results are `rwsets`,
    with the validator's flags as `flags` (all valid by default)."""
    blk = common_pb2.Block()
    blk.header.number = num
    for i, rw in enumerate(rwsets):
        action = proposal_pb2.ChaincodeAction(results=rw)
        prp = proposal_response_pb2.ProposalResponsePayload(
            proposal_hash=b"\x00" * 32, extension=action.SerializeToString())
        cap = transaction_pb2.ChaincodeActionPayload(
            action=transaction_pb2.ChaincodeEndorsedAction(
                proposal_response_payload=prp.SerializeToString()))
        tx = transaction_pb2.Transaction(
            actions=[transaction_pb2.TransactionAction(payload=cap.SerializeToString())])
        chdr = protoutil.make_channel_header(
            common_pb2.ENDORSER_TRANSACTION, CHANNEL, tx_id=f"tx-{num}-{i}")
        shdr = protoutil.make_signature_header(b"creator", b"nonce")
        blk.data.data.append(common_pb2.Envelope(
            payload=protoutil.make_payload_bytes(chdr, shdr, tx.SerializeToString())
        ).SerializeToString())
    blk.header.data_hash = protoutil.block_data_hash(blk.data)
    protoutil.init_block_metadata(blk)
    protoutil.set_tx_filter(blk, bytearray(flags or [VALID] * len(rwsets)))
    return blk


def _rwset(ledger, reads=(), writes=(), ranges=()):
    sim = ledger.new_tx_simulator()
    for key in reads:
        sim.get_state("cc", key)
    for start, end in ranges:
        sim.get_state_range("cc", start, end)
    for key, value in writes:
        sim.set_state("cc", key, value)
    return sim.get_tx_simulation_results()


def _chain(ledger):
    """Three blocks; what each should count, by MVCC_COUNTS' names."""
    ledger.commit(_block(0, [_rwset(ledger, writes=[(f"k{i}", b"v") for i in range(1, 5)])]))
    # simulated now, ordered two blocks later: k2 will have moved on
    late = _rwset(ledger, reads=["k2"], writes=[("k2", b"late")])
    one = _block(1, [
        _rwset(ledger, reads=["k1"], writes=[("k1", b"a")]),              # a read that hits
        _rwset(ledger, reads=["k1"], writes=[("x", b"b")]),               # in-block conflict
        _rwset(ledger, reads=["nobody", "k2"], writes=[("k2", b"c")]),    # an absent key, and a hit
        _rwset(ledger, ranges=[("k3", "k9")], writes=[("y", b"d")]),      # sound range
        _rwset(ledger, writes=[("k35", b"e")]),                           # lands inside that range
        _rwset(ledger, ranges=[("k3", "k9")], writes=[("z", b"f")]),      # a phantom
        _rwset(ledger, reads=["k4"], writes=[("k4", b"g")]),              # refused before MVCC
    ], flags=[VALID] * 6 + [REFUSED_BEFORE])
    ledger.commit(one)
    two = _block(2, [late, _rwset(ledger, reads=["k1", "k3"], writes=[("k3", b"h")])])
    ledger.commit(two)
    flags = [list(protoutil.tx_filter(b)) for b in (one, two)]
    assert flags == [[VALID, MVCC_READ_CONFLICT, VALID, VALID, VALID, PHANTOM_READ_CONFLICT,
                      REFUSED_BEFORE],
                     [MVCC_READ_CONFLICT, VALID]]
    return [
        dict(keys_asked=0, rows_found=0, valid_in=1, read_conflicts=0, phantom_conflicts=0),
        # k1, nobody, k2 asked (a range is scanned, not preloaded; the
        # refused transaction's k4 is never asked); nobody is absent
        dict(keys_asked=3, rows_found=2, valid_in=6, read_conflicts=1, phantom_conflicts=1),
        dict(keys_asked=3, rows_found=3, valid_in=2, read_conflicts=1, phantom_conflicts=0),
    ]


def test_the_spans_the_counters_and_the_tally_say_what_mvcc_made_of_each_block(tmp_path):
    metrics = PrometheusProvider()
    before = mvcc_tally()
    with tracing.scope() as rec:
        prov = LedgerProvider(str(tmp_path), ledger_metrics=LedgerMetrics(metrics))
        try:
            want = _chain(prov.open(CHANNEL))
        finally:
            prov.close()
        events = tracing.export(rec)["traceEvents"]
    spans = {e["args"]["block"]: e for e in events if e["name"] == "mvcc"}
    assert sorted(spans) == [0, 1, 2]
    for num, counts in enumerate(want):
        args = spans[num]["args"]
        # the bulk read is a stage of its own inside the block's `mvcc`,
        # on its thread's CPU clock, and says what it asked and found
        (preload,) = [e for e in events if e["args"].get("parent") == args["span"]]
        assert (preload["name"], preload["cat"]) == ("mvcc.preload", "stage")
        assert preload["args"]["block"] == num and "tdur" in preload
        assert preload["dur"] <= spans[num]["dur"]
        assert {**{k: args[k] for k in MVCC_COUNTS[2:]},
                **{k: preload["args"][k] for k in MVCC_COUNTS[:2]}} == counts, num
    # a lone block is its own commit group: the KV transaction's rows
    # are at least the state rows the block's valid transactions wrote
    kv = {e["args"]["block"]: e["args"] for e in events if e["name"] == "kv_txn"}
    assert [kv[n]["blocks"] for n in (0, 1, 2)] == [1, 1, 1]
    assert kv[0]["rows"] >= 4 and kv[1]["rows"] >= 4 and kv[2]["rows"] >= 1
    # and, since PR 48, the layout of the store it wrote them to: a
    # fresh file is clustered and its reads are mapped
    assert all(kv[n]["clustered"] is True and kv[n]["mmap_bytes"] > 0 for n in kv)
    text = metrics.registry.expose()
    assert f'ledger_mvcc_invalidated_total{{channel="{CHANNEL}",reason="read"}} 2' in text
    assert f'ledger_mvcc_invalidated_total{{channel="{CHANNEL}",reason="phantom"}} 1' in text
    assert f'ledger_preload_rows_total{{channel="{CHANNEL}",outcome="found"}} 5' in text
    assert f'ledger_preload_rows_total{{channel="{CHANNEL}",outcome="missing"}} 1' in text
    # a lone block is a group of its own: the counter is the three transactions' rows
    assert f'ledger_kv_txn_rows_total{{channel="{CHANNEL}"}} {sum(kv[n]["rows"] for n in kv)}' \
        in text
    assert f'ledger_transactions_total{{channel="{CHANNEL}"}} 6' in text
    # the tally runs from process start: it moved by what the spans hold
    after = mvcc_tally()
    assert after["blocks"] - before["blocks"] == 3
    for name in MVCC_COUNTS:
        assert after[name] - before[name] == sum(c[name] for c in want), name
    assert after["recent_blocks"][-3:] == [
        (num, *(c[name] for name in MVCC_COUNTS)) for num, c in enumerate(want)]


def test_a_block_in_a_commit_group_finds_the_rows_the_group_still_holds(tmp_path):
    """A pipelined block's preload reads through its group's overlay:
    rows an earlier block of the group wrote are rows, before any flush."""
    prov = LedgerProvider(str(tmp_path))
    try:
        ledger = prov.open(CHANNEL)
        ledger.commit(_block(0, [_rwset(ledger, writes=[("k1", b"v")])]))
        group = ledger.begin_commit_group()
        ledger.commit(_block(1, [_rwset(ledger, reads=["k1"], writes=[("n", b"1")])]), group=group)
        stale = _rwset(ledger, reads=["n", "k1"], writes=[("m", b"2")])   # sees no `n` yet
        ledger.commit(_block(2, [stale]), group=group)
        with tracing.scope() as rec:
            ledger.commit_group_flush(group)
            (kv,) = [e for e in tracing.export(rec)["traceEvents"] if e["name"] == "kv_txn"]
        assert kv["args"]["blocks"] == 2 and kv["args"]["rows"] >= 2
        assert mvcc_tally()["recent_blocks"][-1] == (2, 2, 2, 1, 1, 0)
    finally:
        prov.close()


def test_tracing_off_the_counts_consult_nothing(tmp_path):
    """The counts ride the `mvcc` and `kv_txn` sites that were there
    and the new site is a global load and an `is None` test: disarmed,
    a commit reaches no armed path."""
    assert not tracing.enabled()
    prov = LedgerProvider(str(tmp_path))
    try:
        before = tracing.lookup_count()
        _chain(prov.open(CHANNEL))
        assert tracing.lookup_count() == before
    finally:
        prov.close()
