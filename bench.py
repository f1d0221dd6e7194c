"""fabric-tpu benchmark entry point.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

North-star metric (BASELINE.json): **committed tx/s** for
1000-tx blocks under a 3-of-5 (MAJORITY over 5 orgs) endorsement policy
— and this round the timed loop really commits: every measured run
drives `Committer.store_stream`, so MVCC validation, block-file append,
state-DB apply, and history indexing are all inside the measurement
(reference kvledger CommitLegacy, core/ledger/kvledger/kv_ledger.go:447-530,
downstream of txvalidator v20, validator.go:180-265).  The ledger is
on-disk (block files + sqlite WAL), matching the reference's
blockfile+leveldb persistence.

Baseline is the *faithful* reference-shaped host path: sequential
per-signature `ecdsa.Verify` with every sub-policy re-verifying its
signatures per tx, no verify-item interning / plan caching / creator
memo (bccsp/sw/ecdsa.go:41 + common/policies/policy.go:365-402
semantics), committing each block serially after validation the way
coordinator.StoreBlock does (gossip/privdata/coordinator.go:149).

Fairness: BOTH sides take best-of-N with the SAME N (4) over fresh
on-disk ledgers, after one warmup each — on a shared host an asymmetric
N would score scheduling luck, not the pipeline.

The measured side runs on the accelerator or not at all: the line names
the device as JAX reports it and carries the provider's lane tally (who
sealed each verified lane's mask), and the run exits non-zero when the
platform is not `tpu`, when the native marshaller is unavailable, or
when a device failure path (failover, breaker) fired during it.

Also reported: p99 block-validate latency (the second north-star
metric) over every per-block validate duration observed on the
measured path.

Two storage-focused modes ride along (PR 17 storage engine v2), both
on the ``devtools/netident`` fake-identity plane so they run in
minimal containers without the ``cryptography`` package — the real
TxValidator, Committer.store_stream, MVCC, and the full on-disk ledger
stack are all inside the measurement; only signature math is faked:

* ``--sweep-storage`` — one JSON line per shards x sqlite-sync x
  segment-size combo over a best-of-2 commit stream, echoing the
  storage config in the line (mirrors ``--sweep-sqlite``);
* ``--scenario smallbank`` — hot-key read-modify-write payments over
  checking/savings accounts, each block endorsed one block behind its
  commit so hot keys storm into intra-block MVCC conflicts; reports
  committed vs conflicted and the same trace/profile artifacts.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))


def _setup_path() -> None:
    for p in (_ROOT, os.path.join(_ROOT, "scripts"), os.path.join(_ROOT, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)


# -- storage-v2 modes (netident plane: no `cryptography` needed) -------------


def _fake_env(channel: str, cc: str, rwset: bytes, tag: str) -> bytes:
    """A policy-satisfying endorser envelope over a caller-simulated
    rwset (netident.make_tx fixes its own write-only rwset; the
    smallbank scenario needs read-modify-write sets simulated against
    the live build ledger)."""
    from fabric_tpu import protoutil
    from fabric_tpu.common.hashing import sha256
    from fabric_tpu.devtools import netident
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.peer import (
        proposal_pb2,
        proposal_response_pb2,
        transaction_pb2,
    )

    creator = b"cre:bench-client"
    nonce = sha256(b"nonce:%s:%s" % (channel.encode(), tag.encode()))
    txid = protoutil.compute_tx_id(nonce, creator)
    ext = proposal_pb2.ChaincodeHeaderExtension()
    ext.chaincode_id.name = cc
    chdr = protoutil.make_channel_header(
        common_pb2.ENDORSER_TRANSACTION, channel, tx_id=txid,
        extension=ext.SerializeToString(), timestamp=0,
    )
    shdr = protoutil.make_signature_header(creator, nonce)
    chdr_b = chdr.SerializeToString()
    shdr_b = shdr.SerializeToString()
    ccpp_b = proposal_pb2.ChaincodeProposalPayload(
        input=b"input:" + tag.encode()
    ).SerializeToString()
    action = proposal_pb2.ChaincodeAction(results=rwset)
    action.chaincode_id.name = cc
    prp = proposal_response_pb2.ProposalResponsePayload(
        proposal_hash=protoutil.proposal_hash2(chdr_b, shdr_b, ccpp_b),
        extension=action.SerializeToString(),
    )
    prp_b = prp.SerializeToString()
    endos = [
        proposal_response_pb2.Endorsement(
            endorser=eb,
            signature=netident.sign_as(eb, sha256(prp_b + eb)),
        )
        for eb in netident.org_endorsers(3)
    ]
    cap = transaction_pb2.ChaincodeActionPayload(
        chaincode_proposal_payload=ccpp_b,
        action=transaction_pb2.ChaincodeEndorsedAction(
            proposal_response_payload=prp_b, endorsements=endos
        ),
    )
    tx = transaction_pb2.Transaction(actions=[
        transaction_pb2.TransactionAction(payload=cap.SerializeToString())
    ])
    payload_b = common_pb2.Payload(
        header=common_pb2.Header(
            channel_header=chdr_b, signature_header=shdr_b
        ),
        data=tx.SerializeToString(),
    ).SerializeToString()
    return common_pb2.Envelope(
        payload=payload_b,
        signature=netident.sign_as(creator, sha256(payload_b)),
    ).SerializeToString()


def _seal_block(blk, prev_hash: bytes):
    from fabric_tpu import protoutil

    blk.header.previous_hash = prev_hash
    blk.header.data_hash = protoutil.block_data_hash(blk.data)
    protoutil.init_block_metadata(blk)
    protoutil.set_tx_filter(blk, bytearray(len(blk.data.data)))
    return blk


def _storage_stream_world(channel: str, n_txs: int, n_blocks: int):
    """Pre-built uniform commit stream for the storage sweep: write-only
    txs (always MVCC-valid) across 8 chaincode namespaces, so every
    shard width has real fan-out.  Returns (genesis, bundle, csp,
    blocks) — blocks chained from genesis, numbers 1..n_blocks."""
    from fabric_tpu import protoutil
    from fabric_tpu.devtools import netident
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.protos.common import common_pb2

    genesis = netident.make_genesis(channel)
    provider = LedgerProvider(None)
    ledger = provider.create(genesis)
    blocks = []
    prev = protoutil.block_header_hash(genesis.header)
    for bno in range(n_blocks):
        blk = common_pb2.Block()
        blk.header.number = 1 + bno
        for i in range(n_txs):
            sim = ledger.new_tx_simulator()
            cc = f"cc{i % 8}"
            sim.set_state(cc, f"k{bno}-{i}", b"v" * 128)
            blocks_tag = f"b{bno}t{i}"
            blk.data.data.append(_fake_env(
                channel, cc, sim.get_tx_simulation_results(), blocks_tag
            ))
        _seal_block(blk, prev)
        prev = protoutil.block_header_hash(blk.header)
        blocks.append(blk)
    provider.close()
    return genesis, netident.FakeBundle(), netident.FakeCSP(), blocks


def _run_fake_stream(genesis, bundle, csp, blocks, root: str,
                     passes: int = 2, depth: int = 6):
    """Best-of-N Committer.store_stream over fresh on-disk ledgers;
    returns (best_seconds, commit_stages, flags_of_best)."""
    import copy as _copy

    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator

    best = float("inf")
    stages: dict = {}
    best_flags: list[list[int]] = []
    for p in range(passes):
        provider = LedgerProvider(os.path.join(root, f"p{p}"))
        led = provider.create(genesis)
        committer = Committer(
            TxValidator("benchch", led, bundle, csp), led
        )
        bs = [_copy.deepcopy(b) for b in blocks]
        flags: list[list[int]] = []
        t0 = time.perf_counter()
        for f in committer.store_stream(iter(bs), depth=depth):
            flags.append(list(f))
        dt = time.perf_counter() - t0
        assert led.height == 1 + len(blocks)
        if dt < best:
            best = dt
            stages = dict(led.commit_stage_seconds)
            best_flags = flags
        provider.close()
    return best, stages, best_flags


def _sweep_storage() -> None:
    """One JSON line per shards x sqlite-sync x segment combo, each over
    a best-of-2 uniform commit stream — the storage-v2 A/B scoreboard
    (shards=1 + 16m is the pre-v2 single-file shape)."""
    # same WAL-checkpoint shape as the main bench path (main() sets it
    # after this mode has already dispatched)
    os.environ.setdefault("FABRIC_TPU_WAL_CHECKPOINT", "4000")
    n_txs, n_blocks = 400, 8
    genesis, bundle, csp, blocks = _storage_stream_world(
        "benchch", n_txs, n_blocks
    )
    tmp = tempfile.TemporaryDirectory(prefix="fabric-bench-storage-")
    combo = 0
    for shards in (1, 2, 4):
        for sync in ("NORMAL", "FULL"):
            for seg in ("1m", "16m"):
                combo += 1
                os.environ["FABRIC_TPU_STORE_SHARDS"] = str(shards)
                os.environ["FABRIC_TPU_SQLITE_SYNC"] = sync
                os.environ["FABRIC_TPU_STORE_SEGMENT"] = seg
                best, stages, flags = _run_fake_stream(
                    genesis, bundle, csp, blocks,
                    os.path.join(tmp.name, f"c{combo}"),
                )
                assert all(
                    f == 0 for blk in flags for f in blk
                ), "uniform stream must commit clean"
                line = {
                    "metric": "storage_sweep_tx_per_s",
                    "shards": shards,
                    "synchronous": sync,
                    "segment": seg,
                    "value": round(n_blocks * n_txs / best, 2),
                    "unit": "tx/s",
                    "fsync_ms": round(
                        stages.get("fsync", 0.0) * 1e3, 2
                    ),
                    "kv_txn_ms": round(
                        stages.get("kv_txn", 0.0) * 1e3, 2
                    ),
                }
                for k in sorted(stages):
                    if k.startswith("kv_") and k != "kv_txn":
                        line[f"{k}_ms"] = round(stages[k] * 1e3, 2)
                print(json.dumps(line))
    for k in ("FABRIC_TPU_STORE_SHARDS", "FABRIC_TPU_SQLITE_SYNC",
              "FABRIC_TPU_STORE_SEGMENT"):
        del os.environ[k]
    sys.stdout.flush()
    from fabric_tpu.common import workpool

    workpool.shutdown()
    tmp.cleanup()


def _scenario_smallbank(trace_out: str | None,
                        profile_out: str | None) -> None:
    """Hot-key contention scoreboard (workload-zoo seed): payment txs
    read-modify-write checking balances with a quarter of the endpoints
    drawn from 10 hot accounts, each block endorsed one block behind its
    commit (the endorse->order->commit staleness), so every block
    storms into intra-block MVCC read conflicts on the hot keys — the
    conflict-heavy counterpart to the uniform canned stream.  Reports
    committed vs conflicted (deterministic across passes) plus the
    usual stage splits and artifacts."""
    import random

    from fabric_tpu import protoutil
    from fabric_tpu.common import profile, tracing
    from fabric_tpu.devtools import netident
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    os.environ.setdefault("FABRIC_TPU_WAL_CHECKPOINT", "4000")
    channel = "benchch"
    n_accounts, n_hot, hot_prob = 1000, 10, 0.25
    n_txs, n_blocks = 400, 6
    rng = random.Random(11)
    accounts = [f"acct{a:04d}" for a in range(n_accounts)]

    genesis = netident.make_genesis(channel)
    provider = LedgerProvider(None)
    ledger = provider.create(genesis)

    # block 1 seeds every checking/savings balance in one tx
    sim = ledger.new_tx_simulator()
    for a in accounts:
        sim.set_state("checking", a, b"1000")
        sim.set_state("savings", a, b"1000")
    seed_blk = common_pb2.Block()
    seed_blk.header.number = 1
    seed_blk.data.data.append(_fake_env(
        channel, "checking", sim.get_tx_simulation_results(), "seed"
    ))
    _seal_block(seed_blk, protoutil.block_header_hash(genesis.header))
    ledger.commit(seed_blk)  # endorsements below read the seeded state

    def pick() -> str:
        if rng.random() < hot_prob:
            return accounts[rng.randrange(n_hot)]
        return accounts[rng.randrange(n_accounts)]

    blocks = []
    prev = protoutil.block_header_hash(seed_blk.header)
    for bno in range(n_blocks):
        blk = common_pb2.Block()
        blk.header.number = 2 + bno
        for i in range(n_txs):
            src = pick()
            dst = pick()
            while dst == src:
                dst = accounts[rng.randrange(n_accounts)]
            s = ledger.new_tx_simulator()
            a = int(s.get_state("checking", src) or b"0")
            b = int(s.get_state("checking", dst) or b"0")
            s.get_state("savings", src)  # overdraft check reads savings
            s.set_state("checking", src, b"%d" % (a - 1))
            s.set_state("checking", dst, b"%d" % (b + 1))
            blk.data.data.append(_fake_env(
                channel, "checking", s.get_tx_simulation_results(),
                f"pay-b{bno}t{i}",
            ))
        _seal_block(blk, prev)
        prev = protoutil.block_header_hash(blk.header)
        blocks.append(blk)
        # advance the build ledger one block behind endorsement (the
        # realistic endorse->order->commit staleness): block k+1's
        # reads see block k's WINNERS, so conflicts come from hot-key
        # contention inside each block, not from a saturating cascade
        import copy as _copy

        ledger.commit(_copy.deepcopy(blk))
    provider.close()

    if (trace_out or profile_out) and not tracing.enabled():
        tracing.arm()
    if profile_out and not profile.enabled():
        profile.arm()

    import copy as _copy

    bundle, csp = netident.FakeBundle(), netident.FakeCSP()
    tmp = tempfile.TemporaryDirectory(prefix="fabric-bench-smallbank-")
    best = float("inf")
    stages: dict = {}
    best_flags: list[int] = []
    trace = prof = None
    per_pass_flags = []
    for p in range(2):
        if tracing.enabled():
            tracing.reset()
        if profile.enabled():
            profile.reset()
        prov = LedgerProvider(os.path.join(tmp.name, f"p{p}"))
        led = prov.create(genesis)
        committer = Committer(
            TxValidator(channel, led, bundle, csp), led
        )
        sf = committer.store_block(_copy.deepcopy(seed_blk))
        assert all(f == 0 for f in sf), "the seed block must be clean"
        bs = [_copy.deepcopy(b) for b in blocks]
        flags: list[int] = []
        t0 = time.perf_counter()
        for f in committer.store_stream(iter(bs), depth=6):
            flags.extend(f)
        dt = time.perf_counter() - t0
        assert led.height == 2 + n_blocks
        per_pass_flags.append(flags)
        if dt < best:
            best = dt
            stages = dict(led.commit_stage_seconds)
            best_flags = flags
            if tracing.enabled():
                trace = tracing.export()
            if profile.enabled():
                prof = profile.export("bench.smallbank")
        prov.close()
    # the conflict outcome is part of the scoreboard's contract: same
    # blocks, same order -> byte-identical flags on every pass
    assert per_pass_flags[0] == per_pass_flags[1], \
        "smallbank flags must be deterministic"

    committed = sum(1 for f in best_flags if f == 0)
    conflicted = len(best_flags) - committed
    by_code: dict = {}
    for f in best_flags:
        if f:
            by_code[str(f)] = by_code.get(str(f), 0) + 1
    from fabric_tpu.ledger.blkstorage import segment_size
    from fabric_tpu.ledger.kvstore import store_shards
    from fabric_tpu.ledger.kvstore import _sqlite_sync_level as _sync

    line = {
        "metric": "smallbank_committed_tx_per_s",
        "scenario": "smallbank",
        "value": round(committed / best, 2),
        "unit": "tx/s",
        "attempted_tx_per_s": round(len(best_flags) / best, 2),
        "attempted": len(best_flags),
        "committed": committed,
        "conflicted": conflicted,
        "conflict_rate": round(conflicted / len(best_flags), 4),
        "invalid_by_code": by_code,
        "accounts": n_accounts,
        "hot_accounts": n_hot,
        "hot_prob": hot_prob,
        "commit_stage_ms": {
            k: round(v * 1e3, 2) for k, v in sorted(stages.items())
        },
        "storage": {
            "shards": store_shards(),
            "segment": segment_size(None),
            "synchronous": _sync(None),
        },
    }
    if trace_out and trace is not None:
        with open(trace_out, "w", encoding="utf-8") as f:
            json.dump(trace, f, indent=1, sort_keys=True)
            f.write("\n")
        line["trace_out"] = trace_out
    if profile_out and prof is not None:
        from fabric_tpu.common import profile as _profile

        _profile.dump_to(profile_out, prof)
        line["self_cpu_ms"] = prof["otherData"]["self_cpu_ms"]
        line["profile_out"] = profile_out
        _profile.disarm()
    print(json.dumps(line))
    sys.stdout.flush()
    from fabric_tpu.common import workpool

    workpool.shutdown()
    tmp.cleanup()


def main() -> None:
    _setup_path()

    scenario = None
    if "--scenario" in sys.argv:
        i = sys.argv.index("--scenario")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
            sys.exit("bench.py: --scenario requires a NAME argument")
        scenario = sys.argv[i + 1]
        if scenario != "smallbank":
            sys.exit(f"bench.py: unknown scenario {scenario!r}")
    early_trace = None
    if "--trace-out" in sys.argv:
        i = sys.argv.index("--trace-out")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
            sys.exit("bench.py: --trace-out requires a PATH argument")
        early_trace = sys.argv[i + 1]
    early_profile = None
    if "--profile-out" in sys.argv:
        i = sys.argv.index("--profile-out")
        if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("--"):
            sys.exit("bench.py: --profile-out requires a PATH argument")
        early_profile = sys.argv[i + 1]
    if "--sweep-storage" in sys.argv:
        _sweep_storage()
        return
    if scenario == "smallbank":
        _scenario_smallbank(early_trace, early_profile)
        return

    from bench_pipeline import _build_world, _make_blocks

    from fabric_tpu import native
    from fabric_tpu.csp import SWCSP
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger import LedgerProvider
    from fabric_tpu.ledger.kvstore import (
        _sqlite_sync_level as _sync_level,
        _sqlite_wal_checkpoint as _wal_ckpt,
    )
    from fabric_tpu.node import quiesce
    from fabric_tpu.peer.committer import Committer
    from fabric_tpu.peer.txvalidator import TxValidator
    from fabric_tpu.protos.common import common_pb2

    sweep_sqlite = "--sweep-sqlite" in sys.argv
    trace_out = early_trace
    profile_out = early_profile

    # before the minutes of host set-up below: no chip, no number
    device = TPUCSP.device_info()
    if device["platform"] != "tpu":
        sys.exit(
            f"bench.py: the measured side runs on a TPU, JAX reports "
            f"{device}; nothing measured"
        )
    if not native.available():
        sys.exit(
            "bench.py: the native marshaller/collector is unavailable, "
            f"the measured path would be the pure-Python one: "
            f"{native.load_error()}"
        )

    # sqlite tuning applied to BOTH sides (baseline and measured): a
    # larger WAL autocheckpoint keeps checkpoint I/O out of the timed
    # window — durability-neutral, checkpoint timing never affects
    # crash safety (the WAL replays either way).  `synchronous` stays
    # at the safe NORMAL default the chaos matrix proves;
    # `--sweep-sqlite` measures the full knob matrix.
    os.environ.setdefault("FABRIC_TPU_WAL_CHECKPOINT", "4000")

    n_txs, n_blocks = 1000, 8
    sw = SWCSP()
    orgs, genesis = _build_world(5)
    _, bundle, blocks = _make_blocks(orgs, genesis, sw, n_txs, 3, n_blocks)

    def copies(k):
        out = []
        for j in range(k):
            b = common_pb2.Block()
            b.CopyFrom(blocks[j % n_blocks])
            out.append(b)
        return out

    tmp = tempfile.TemporaryDirectory(prefix="fabric-bench-")
    fresh_n = [0]

    def fresh_ledger():
        """A brand-new on-disk ledger (block files + sqlite WAL) holding
        only the genesis block — every timed run commits 1..n_blocks."""
        fresh_n[0] += 1
        provider = LedgerProvider(os.path.join(tmp.name, f"run{fresh_n[0]}"))
        return provider.create(genesis)

    # -- baseline: faithful host path, serial validate -> commit ----------
    warm = Committer(
        TxValidator("benchch", (wl := fresh_ledger()), bundle, sw, faithful=True),
        wl,
    )
    warm.store_block(copies(1)[0])  # EC backend init, native lib, protos
    baseline = None
    if not sweep_sqlite:  # the sweep compares combos, not vs-host
        base_best = float("inf")
        for _ in range(4):
            led = fresh_ledger()
            committer = Committer(
                TxValidator("benchch", led, bundle, sw, faithful=True), led
            )
            bs = copies(n_blocks)
            t0 = time.perf_counter()
            for b in bs:
                flags = committer.store_block(b)
                assert all(f == 0 for f in flags)
            base_best = min(base_best, time.perf_counter() - t0)
            assert led.height == 1 + n_blocks
        baseline = n_blocks * n_txs / base_best

    # -- measured: pipelined validate+commit stream, TPU batch verify -----
    # coalesce_lanes=4096 under a depth-6 stream flushes two blocks at a
    # time (8,000 lanes, the 8192 bucket: chip_smoke.py leg A prints the
    # sizes); the flush/depth point itself has not been re-measured on
    # this machine and is ROADMAP Queue 1 item 2's to revisit
    csp = TPUCSP(min_device_batch=1, coalesce_lanes=4096)
    wl2 = fresh_ledger()
    Committer(
        TxValidator("benchch", wl2, bundle, csp), wl2
    ).store_block(copies(1)[0])  # compile + first transfer

    def run_stream(passes: int = 4):
        """Best-of-N pipelined validate+commit stream; returns
        (best_seconds, commit_stages, validate_stages, trace, prof) of
        the winning pass.  The provider is drained before every pass
        for the same reason the p99 loop drains: a prior pass's
        host-raced flush can leave the device leg still crunching, and
        that tail must not become the next pass's head.  Under
        --trace-out the flight recorder resets per pass and the WINNING
        pass's export is kept — the artifact matches the measured
        number; --profile-out holds profscope's aggregate to the same
        contract."""
        from fabric_tpu.common import profile, tracing

        best = float("inf")
        commit_stages: dict = {}
        validate_stages: dict = {}
        trace: dict | None = None
        prof: dict | None = None
        for _ in range(passes):
            csp.drain()
            if tracing.enabled():
                tracing.reset()
            if profile.enabled():
                profile.reset()
            led = fresh_ledger()
            validator = TxValidator("benchch", led, bundle, csp)
            committer = Committer(validator, led)
            bs = copies(n_blocks)
            t0 = time.perf_counter()
            for flags in committer.store_stream(iter(bs), depth=6):
                assert all(f == 0 for f in flags)
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
                # per-stage breakdowns of the winning run (the same
                # numbers the operations /metrics endpoint exposes as
                # ledger_commit_stage_duration /
                # validator_block_stage_duration histograms)
                commit_stages = dict(led.commit_stage_seconds)
                validate_stages = dict(validator.validate_stage_seconds)
                if tracing.enabled():
                    trace = tracing.export()
                if profile.enabled():
                    prof = profile.export("bench.stream")
            assert led.height == 1 + n_blocks
        return best, commit_stages, validate_stages, trace, prof

    if sweep_sqlite:
        # durability sweep: one JSON line per synchronous/checkpoint
        # combo, each over a shortened best-of-2 measured stream with
        # the env knobs set before the combo's fresh on-disk ledgers
        # are created (SqliteKVStore reads them at open)
        for sync in ("OFF", "NORMAL", "FULL"):
            for ckpt in (250, 1000, 4000):
                os.environ["FABRIC_TPU_SQLITE_SYNC"] = sync
                os.environ["FABRIC_TPU_WAL_CHECKPOINT"] = str(ckpt)
                best, stages, _vstages, _trace, _prof = run_stream(
                    passes=2
                )
                print(json.dumps({
                    "metric": "sqlite_sweep_tx_per_s",
                    "synchronous": sync,
                    "wal_autocheckpoint": ckpt,
                    "value": round(n_blocks * n_txs / best, 2),
                    "unit": "tx/s",
                    "fsync_ms": round(
                        stages.get("fsync", 0.0) * 1e3, 2
                    ),
                    "kv_txn_ms": round(
                        stages.get("kv_txn", 0.0) * 1e3, 2
                    ),
                }))
        del os.environ["FABRIC_TPU_SQLITE_SYNC"]
        del os.environ["FABRIC_TPU_WAL_CHECKPOINT"]
        sys.stdout.flush()
        quiesce(csp)
        tmp.cleanup()
        return

    # tracing/profiling arm AFTER the baseline measurement so the
    # (already near-zero) armed-path overhead cannot skew the
    # vs-baseline ratio; the measured side carries it inside the
    # traced/profiled passes by design
    if trace_out or profile_out:
        from fabric_tpu.common import tracing

        if not tracing.enabled():
            # FABRIC_TPU_TRACE=N may have armed a user-sized ring at
            # import; only arm the default when nothing is armed yet.
            # --profile-out arms it too: the sampler attributes CPU to
            # live tracelens spans (self_cpu_ms), which needs spans
            tracing.arm()
        from fabric_tpu.common import workpool as _workpool

        _workpool.reset_stats()
    if profile_out:
        from fabric_tpu.common import profile

        if not profile.enabled():
            # FABRIC_TPU_PROFILE may have armed a tuned cadence
            profile.arm()

    best, commit_stages, validate_stages, trace, prof = run_stream()
    value = n_blocks * n_txs / best

    # -- p99 block-validate latency on the measured path ------------------
    # (the reference logs per-block validate duration, validator.go:261;
    # here every serial validate() wall time over 3 fresh-ledger passes).
    # The provider is DRAINED between passes: pass N's last async verify
    # otherwise still holds device lanes when pass N+1's first block
    # dispatches, inflating that block's wall time — the tail of one
    # pass must not become the head of the next.
    lat = []
    for _ in range(3):
        csp.drain()
        led = fresh_ledger()
        v = TxValidator("benchch", led, bundle, csp)
        for b in copies(n_blocks):
            t0 = time.perf_counter()
            flags = v.validate(b)
            lat.append(time.perf_counter() - t0)
            assert all(f == 0 for f in flags)
            led.commit(b)
    lat.sort()
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]

    csp.drain()  # every flush sealed: the tally below is final
    lanes = csp.lane_tally()
    if lanes["failover"] or lanes["breaker"] or csp.breaker.trips:
        quiesce(csp)
        sys.exit(
            "bench.py: a device failure path fired during the run "
            f"(lanes {lanes}, breaker trips {csp.breaker.trips}); "
            "nothing measured"
        )

    line = {
        "metric": "committed_tx_per_s_1000tx_3of5_stream",
        "value": round(value, 2),
        "unit": "tx/s",
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "lanes_sealed_by": lanes,
        "vs_baseline": round(value / baseline, 3),
        "baseline_tx_per_s": round(baseline, 2),
        "p99_block_validate_ms": round(p99 * 1e3, 2),
        "commit_stage_ms": {
            k: round(v * 1e3, 2)
            for k, v in sorted(commit_stages.items())
        },
        "validate_stage_ms": {
            k: round(v * 1e3, 2)
            for k, v in sorted(validate_stages.items())
        },
        "sqlite": {
            "synchronous": _sync_level(None),
            "wal_autocheckpoint": _wal_ckpt(None),
        },
    }
    if trace_out and trace is not None:
        from fabric_tpu.common import tracing
        from fabric_tpu.common import workpool as _workpool

        with open(trace_out, "w", encoding="utf-8") as f:
            json.dump(trace, f, indent=1, sort_keys=True)
            f.write("\n")
        # per-block critical path over the winning pass's stage spans:
        # which stages actually gated the wall clock (summed ms across
        # blocks), vs the plain busy-time sums above
        line["critical_path_ms"] = {
            k: round(v, 2)
            for k, v in sorted(tracing.critical_path_ms(
                trace["traceEvents"]
            ).items())
        }
        line["trace_out"] = trace_out
        line["workpool"] = _workpool.stats()
    if profile_out and prof is not None:
        from fabric_tpu.common import profile

        profile.dump_to(profile_out, prof)
        # per-stage CPU attribution of the winning pass (sampler time
        # inside each live span) — read next to critical_path_ms:
        # busy-CPU vs wall-gating per stage
        line["self_cpu_ms"] = prof["otherData"]["self_cpu_ms"]
        line["profile_out"] = profile_out
        # stop the sampler service thread before teardown (same
        # reasoning as quiesce joining the flush waiters)
        profile.disarm()
    print(json.dumps(line))
    sys.stdout.flush()
    # quiesce the device provider AFTER the one JSON line is out (a
    # wedged chip must not discard completed measurements) but BEFORE
    # interpreter exit: joining the flush waiters is what lets teardown
    # run cleanly — a tpu-flush-waiter still inside an XLA kernel at
    # exit is killed mid-unwind and glibc aborts with "FATAL: exception
    # not rethrown" (the old os._exit(0) workaround this close
    # replaces).  close() is the indefinite join: exiting under a live
    # waiter would reproduce the abort, while a genuinely wedged chip
    # is the harness timeout's problem.
    quiesce(csp)
    tmp.cleanup()


if __name__ == "__main__":
    main()
    sys.stdout.flush()
