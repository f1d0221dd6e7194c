"""The SmallBank kind of deployment (`smallbank-100k-zipf`): its
configuration, its world at a small size (1,000 accounts, 50-tx blocks:
what `workload_seed` fixes and what `--seed` does, what is planted, the
staleness every read carries, what it keeps for the condition), its
plain reference flag for flag and row for row, whole rehearsals of
`smallbank-100k-zipf.catchup` on the CPU from a copy of the benchmark
whose configuration holds a thousand accounts (sound; under `skip_mvcc`;
under `accept_all_signatures`; with the set-up blocks kept from the
reference; with a populated row taken out of the template by hand), the
condition on hand-made cells, and the four readers on a recorded
observation (`data/spans_smallbank.json`: one traced window of the tiny
rehearsal).

No number of a CPU run is a device number: the tests read counts, flags
and verdicts, never a time.  A pass's two 50-tx blocks go out as one
flush of 400 lanes and a set-up block's three transactions are verified
on the host, so one kernel shape is built in this process.
"""

import copy
import json
import os
import shutil
import sys
import types
from collections import Counter

import pytest

from benchlib import engine
from benchlib.manifest import Manifest, ManifestError

from conftest import ROOT

SEED = 2**31 + 200
CELL = "smallbank-100k-zipf.catchup"
CONFIG = "smallbank-100k-zipf"
NS = "benchcc"
METRICS = ("mvcc_invalidated_tx_share.catchup", "mvcc_preload_ms_per_block.catchup",
           "mvcc_preload_found_share.catchup", "kv_txn_ms_per_block.catchup")
SMALL = {"accounts": 1000, "block_txs": 50, "setup_accounts_per_tx": 125}
# the engine's rehearsal: a block's 200 lanes, a pass's 400
SIZE = engine.Rehearsal(block_txs=50, blocks_per_pass=2)
TINY = {"accounts": 1000, "setup_accounts_per_tx": 125}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


def _build(man, held, seed=SEED, n_blocks=8, **changed):
    dep = dict(held["deployment"], **dict(SMALL, **changed))
    return man.world(held)(seed, dep, held["planted"], n_blocks), dep


@pytest.fixture(scope="module")
def small(man, held):
    return _build(man, held)


# -- the configuration ---------------------------------------------------------


def test_the_configuration_states_its_source_its_shapes_and_its_guarantees(man, held):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (entry,) = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == held["source"] and len(entry["source"]) <= 200
    for word in ("Blockbench", "SmallBank", "Caliper", "benchmarks/scenario/smallbank"):
        assert word in held["source"]
    assert held["world"] == held["reference"] == "x509-smallbank"
    assert held["conditions"] == ["smallbank-shape"]
    dep = held["deployment"]
    majority = man.config({"name": "x", "config": "majority5-1000tx"})
    for same in ("orgs", "endorsement_policy", "endorsers_per_tx", "block_txs", "orderer",
                 "client_identities", "signature_lanes_per_tx", "signature_lanes_per_block",
                 "ledger", "chips"):
        assert dep[same] == majority["deployment"][same], same
    assert (dep["accounts"], dep["rows"], dep["zipf_constant"], dep["endorsement_lag_blocks"]) \
        == (100_000, 200_000, 0.99, 1)
    assert isinstance(dep["workload_seed"], int) and 0 < dep["conflict_floor_share"] < 0.42
    assert dep["operations"] == dict.fromkeys(
        ("transact_savings", "deposit_checking", "send_payment", "write_check", "amalgamate"),
        0.2)
    assert dep["accounts"] % dep["setup_accounts_per_tx"] == 0
    assert entry["reduced"] == held["reduced"] == ["chain_depth", "client_identities",
                                                   "state_size"]
    assert set(held["reduced_how"]) == set(held["reduced"])
    # majority5-1000tx's five, word for word, and the populated rows
    assert held["guarantees"][:-1] == majority["guarantees"]
    assert "200,000 populated rows" in held["guarantees"][-1]
    assert any("Zipf" in a for a in held["assumed"]) \
        and any("endorsement_lag_blocks" in a for a in held["assumed"])
    planted = held["planted"]
    assert (planted["bad_creator_per_block"], planted["bad_endorsement_per_block"],
            planted["conflict_pairs_per_block"]) == (1, 1, 1)
    (cell,) = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "catchup", 1)
    own = man.traffic(cell)
    assert own["blocks_per_pass"] in (16, 8) and own["blocks_per_pass_from"]
    # the cell's four metrics, wherever they stand in `per_layer`
    declared = {m["name"]: m for m in doc["per_layer"]}
    for name in METRICS:
        m = declared[name]
        assert CELL in m["workloads"] and m["moves"] == "committed_tx_per_s"
        assert m["layer"] == "committer and ledger (peer/committer.py, ledger/kvledger.py)"
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert set(METRICS) <= due and "first_block_s" in due
    assert {"device_idle_share.catchup", "ec_kernel_ns_per_lane.catchup",
            "bucket_fill_share.catchup", "commit_ms_per_block.catchup"} <= due
    assert not {name for name in due if name.startswith(("keylevel_", "idemix_", "bn254_"))}
    assert {m["name"] for m in man.metrics("end_to_end", CELL)} \
        == {"committed_tx_per_s", "setup_s"}


# -- the world -----------------------------------------------------------------


def _decoded(block_bytes):
    """Per transaction of a block: (reads {key: version}, writes {key: value})."""
    from fabric_tpu.protos.common import common_pb2
    from fabric_tpu.protos.ledger.rwset import rwset_pb2
    from fabric_tpu.protos.ledger.rwset.kvrwset import kv_rwset_pb2
    from fabric_tpu.protos.peer import proposal_pb2, proposal_response_pb2, transaction_pb2

    out = []
    for env_bytes in common_pb2.Block.FromString(block_bytes).data.data:
        payload = common_pb2.Payload.FromString(
            common_pb2.Envelope.FromString(env_bytes).payload)
        tx = transaction_pb2.Transaction.FromString(payload.data)
        cap = transaction_pb2.ChaincodeActionPayload.FromString(tx.actions[0].payload)
        prp = proposal_response_pb2.ProposalResponsePayload.FromString(
            cap.action.proposal_response_payload)
        results = proposal_pb2.ChaincodeAction.FromString(prp.extension).results
        (ns,) = rwset_pb2.TxReadWriteSet.FromString(results).ns_rwset
        kv = kv_rwset_pb2.KVRWSet.FromString(ns.rwset)
        assert ns.namespace == NS and len(cap.action.endorsements) == 3
        out.append(({r.key: (r.version.block_num, r.version.tx_num) for r in kv.reads},
                    {w.key: w.value for w in kv.writes}))
    return out


def test_the_world_populates_every_account_and_plants_what_the_configuration_says(small, held):
    world, dep = small
    rows = {}
    for number, raw in enumerate(world.setup_blocks, start=1):
        txs = _decoded(raw)
        assert 1 <= len(txs) <= dep["setup_txs_per_block"]
        for i, (reads, writes) in enumerate(txs):
            assert not reads and len(writes) == 2 * dep["setup_accounts_per_tx"]
            assert set(writes.values()) == {b"%010d" % dep["opening_balance"]}
            assert {len(v) for v in writes.values()} == {10}
            rows.update(dict.fromkeys(writes, (number, i)))
    assert set(rows) == {f"{kind}_{a}" for a in range(1000) for kind in ("savings", "checking")}
    assert len(world.setup_blocks) == 3 and world.accounts == 1000
    assert world.lanes_per_block == 4 * 50
    p = held["planted"]
    for flags, classes, refused in zip(world.planted, world.planted_classes, world.mvcc_refused):
        c = Counter(int(f) for f in flags)
        assert c[4] == p["bad_creator_per_block"] == classes["bad_creator"]
        assert c[10] == p["bad_endorsement_per_block"] == classes["bad_endorsement"]
        assert classes["conflict_pair"] == p["conflict_pairs_per_block"] == 1
        assert c[11] == refused and c[0] + c[4] + c[10] + c[11] == 50
    # the mix is the five procedures, none of them rare
    assert set(world.operations) == set(dep["operations"])
    assert sum(world.operations.values()) == 8 * 50 and min(world.operations.values()) >= 50
    # hot accounts: the traffic alone makes the conflicts, in every block
    # (over the configuration's floor at this size too)
    assert min(world.mvcc_refused) >= dep["conflict_floor_share"] * 50
    # a planted pair is two deposits to one account, first valid, then refused
    for flags, txs in zip(world.planted, world.txs):
        twice = [a for a, n in Counter(
            t[1] for t, f in zip(txs, flags) if t[0] == "deposit_checking" and t[1] == t[2]
        ).items() if n == 2]
        assert len(twice) == 1
        assert [f for t, f in zip(txs, flags) if t[1] == t[2] == twice[0]] == [0, 11]


def test_every_read_carries_the_version_of_two_blocks_before(small):
    """Endorsed a block behind: block k's reads are of the state as it
    stood after block k-2, so the world's own flags follow from the
    read-write sets alone: valid only where no valid transaction of
    block k-1, and none earlier in k, wrote a key it read."""
    world, dep = small
    m = len(world.setup_blocks)
    version = {}                          # key -> version, as commits leave it
    for number, raw in enumerate(world.setup_blocks, start=1):
        for i, (_reads, writes) in enumerate(_decoded(raw)):
            version.update(dict.fromkeys(writes, (number, i)))
    after = [dict(version)]               # after[j]: the versions after measured block j-1
    for bno, raw in enumerate(world.blocks):
        seen = after[max(0, bno - 1)]     # k-2, never before what the set-up left
        distinct = set()
        for i, ((reads, writes), flag) in enumerate(zip(_decoded(raw), world.planted[bno])):
            assert reads and set(writes) <= set(reads)
            assert all(seen[key] == v for key, v in reads.items())
            if flag in (4, 10):
                continue
            distinct.update(reads)
            fresh = all(version[key] == v for key, v in reads.items())
            assert flag == (0 if fresh else 11)
            if fresh:
                version.update(dict.fromkeys(writes, (m + 1 + bno, i)))
        assert world.read_keys[bno] == len(distinct)
        after.append(dict(version))
    assert {key: v for (_ns, key), (_value, v) in world.expected_state().items()} == version


def test_the_procedures_read_and_write_what_h_store_says(man, held):
    man.world(held)
    sb = sys.modules["bench_worlds_x509_smallbank"]
    rows = {"savings_1": 50, "checking_1": 30, "savings_2": 7, "checking_2": 9}

    def ran(op, amount=10):
        asked = []
        wrote = sb._simulate(op, 1, 2, amount, lambda key: asked.append(key) or rows[key])
        return sorted(set(asked)), wrote

    assert ran("transact_savings") == (["savings_1"], {"savings_1": 60})
    assert ran("deposit_checking") == (["checking_1"], {"checking_1": 40})
    assert ran("send_payment") == (["checking_1", "checking_2"],
                                   {"checking_1": 20, "checking_2": 19})
    assert ran("send_payment", 31) == (["checking_1"], None)           # refused: nothing ordered
    assert ran("write_check") == (["checking_1", "savings_1"], {"checking_1": 20})
    assert ran("write_check", 81)[1] == {"checking_1": 30 - 81 - 1}    # overdrawn: a unit more
    assert ran("amalgamate") == (["checking_1", "checking_2", "savings_1"],
                                 {"savings_1": 0, "checking_1": 0, "checking_2": 9 + 80})


def _envelopes(block_bytes):
    from fabric_tpu.protos.common import common_pb2

    return [common_pb2.Envelope.FromString(e)
            for e in common_pb2.Block.FromString(block_bytes).data.data]


def _ca_keys(world):
    from cryptography import x509

    return {msp: x509.load_pem_x509_certificate(pem).public_key().public_numbers()
            for msp, pem in world.public["ca_certs_pem"].items()}


def _nonces(world):
    from fabric_tpu.protos.common import common_pb2

    return [common_pb2.SignatureHeader.FromString(
                common_pb2.Payload.FromString(e.payload).header.signature_header).nonce
            for raw in world.setup_blocks + world.blocks for e in _envelopes(raw)]


def test_two_seeds_give_the_same_work_and_different_signatures(man, held):
    """The `workload_seed` contract: the procedures, accounts, amounts,
    read-write sets, planted places and so the flags and the state's
    values are the configuration's, the same under every `--seed`; the
    key material is the run's."""
    def work(world):
        return ([_decoded(b) for b in world.setup_blocks + world.blocks], world.planted,
                world.txs, world.mvcc_refused, world.read_keys, world.planted_classes,
                sorted(world.expected_state().items()))

    size = dict(n_blocks=2, accounts=200, setup_accounts_per_tx=50)
    a, _ = _build(man, held, **size)
    b, _ = _build(man, held, **size)
    c, _ = _build(man, held, seed=SEED + 1, **size)
    d, _ = _build(man, held, workload_seed=held["deployment"]["workload_seed"] + 1, **size)
    assert work(a) == work(b) == work(c) != work(d)
    assert sum(a.mvcc_refused) * 100.0 / (2 * 50) == sum(c.mvcc_refused) * 100.0 / (2 * 50)
    # another --seed: other CA keys, other nonces (so other transaction
    # ids), other signatures; the same --seed: the same keys and nonces
    # (a certificate's serial and ECDSA's own nonces stay random)
    assert _ca_keys(a) == _ca_keys(b) != _ca_keys(c)
    assert _nonces(a) == _nonces(b)
    assert not set(_nonces(a)) & set(_nonces(c))
    for raw_a, raw_c in zip(a.setup_blocks + a.blocks, c.setup_blocks + c.blocks):
        for env_a, env_c in zip(_envelopes(raw_a), _envelopes(raw_c)):
            assert env_a.signature != env_c.signature
    assert getattr(a, "definition_provider", None) is None


def test_a_program_that_counts_nothing_is_refused_before_anything_is_measured(
        man, held, monkeypatch):
    """The world asks the program for the count its condition reads: a
    checkout without it (the parent of PR 46) is refused with a
    ManifestError, which `benchmarks/run.py` turns into exit 2."""
    from fabric_tpu.ledger import txmgmt

    monkeypatch.delattr(txmgmt, "mvcc_tally")
    with pytest.raises(ManifestError, match="mvcc_tally"):
        _build(man, held, n_blocks=1)


# -- the reference -------------------------------------------------------------


def test_the_reference_agrees_with_the_world_flag_for_flag_and_row_for_row(man, held, small):
    world, dep = small
    flags, base, changes = man.reference(held)(
        world.public, dep, world.blocks, world.setup_blocks)
    assert [list(f) for f in flags] == [list(p) for p in world.planted]
    assert {0, 4, 10, 11} <= {f for fl in flags for f in fl}
    assert len(base) == 2000 and {v for v, _version in base.values()} == {b"0001000000"}
    state = dict(base)
    for changed in changes:
        assert changed and None not in changed.values()
        state.update(changed)
    assert state == world.expected_state() and len(state) == 2000
    # a ledger that starts without its rows is another deployment: every
    # read of a populated row conflicts
    flags, base, _changes = man.reference(held)(world.public, dep, world.blocks, [])
    assert not base and {f for fl in flags for f in fl} == {4, 10, 11}


# -- the rehearsal -------------------------------------------------------------

WITHHELD_REFERENCE = '''
"""A test's: `x509-smallbank.py` with the set-up blocks kept from it."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "withheld_x509_smallbank", os.path.join(os.path.dirname(__file__), "x509-smallbank.py"))
_sound = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sound)


def run(public, deployment, blocks, setup_blocks):
    return _sound.run(public, deployment, blocks, [])
'''


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark whose `smallbank-100k-zipf` holds a
    thousand accounts, and beside it
    the same deployment checked against a reference that is not handed
    the set-up blocks."""
    root = str(tmp_path_factory.mktemp("smallbank"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copytree(os.path.join(ROOT, "sampleconfig"), os.path.join(root, "sampleconfig"))
    path = os.path.join(root, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        tiny = json.load(f)
    tiny["deployment"].update(TINY)
    with open(path, "w") as f:
        json.dump(tiny, f)
    with open(os.path.join(root, "benchmarks", "configs", "withheld.json"), "w") as f:
        json.dump(dict(tiny, name="withheld", reference="x509-smallbank-withheld"), f)
    with open(os.path.join(root, "benchmarks", "reference", "x509-smallbank-withheld.py"),
              "w") as f:
        f.write(WITHHELD_REFERENCE)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "withheld", "file": "benchmarks/configs/withheld.json"})
    doc["workloads"].append({"name": "withheld.catchup", "config": "withheld",
                             "traffic": "catchup", "chips": 1})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("withheld.catchup")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root


def run(root, cell=CELL, trace=False):
    return engine.run_cell(root, cell, SEED, 1.0, trace, rehearsal=SIZE)


@pytest.fixture(scope="module")
def sound(tiny_root):
    return run(tiny_root, trace=True)


@pytest.fixture(scope="module")
def tiny_world(tiny_root):
    man = Manifest(tiny_root)
    held = man.config({"name": CELL, "config": CONFIG})
    dep = dict(held["deployment"], block_txs=SIZE.block_txs)
    return man.world(held)(SEED, dep, held["planted"], SIZE.blocks_per_pass)


def test_a_rehearsal_agrees_with_its_reference_to_the_flag_and_the_row(sound, capsys):
    compared = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["attempted"] >= 2 and sound["failed"] == 0
    assert set(compared) >= {
        "blocks_with_too_few_conflicts", "planted_classes_missing_from_a_block",
        "reads_that_found_no_row", "most_flushes_held_one_block_alone"}
    assert all(v == 0 for v in compared.values()), compared
    assert all(v["limit"] == 0 for v in sound["compared"].values())
    assert sound["correct"] is True


def test_a_traced_rehearsal_reports_the_four_metrics(sound, tiny_root, tiny_world):
    due = {m["name"] for m in Manifest(tiny_root).metrics("per_layer", CELL)}
    assert set(sound["metrics"]) <= due
    assert set(METRICS) | {"commit_ms_per_block.catchup", "commit_cpu_ms_per_block.catchup",
                           "device_lane_share.catchup"} <= set(sound["metrics"])
    value = {k: v["value"] for k, v in sound["metrics"].items()}
    # every pass is the world's two blocks over a copy of the template
    txs = SIZE.block_txs * SIZE.blocks_per_pass
    assert value["mvcc_invalidated_tx_share.catchup"] \
        == pytest.approx(100.0 * sum(tiny_world.mvcc_refused) / txs)
    assert value["mvcc_preload_found_share.catchup"] == 100.0
    assert value["mvcc_preload_ms_per_block.catchup"] > 0.0
    assert 0.0 < value["kv_txn_ms_per_block.catchup"] <= value["commit_ms_per_block.catchup"]
    assert value["device_lane_share.catchup"] == 100.0


@pytest.fixture
def unpatched():
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger.txmgmt import MVCCValidator

    saved = TPUCSP.verify_batch_async, MVCCValidator._committed_version
    yield
    TPUCSP.verify_batch_async, MVCCValidator._committed_version = saved


@pytest.mark.parametrize("control", ["skip_mvcc", "accept_all_signatures"])
def test_a_broken_program_comes_out_as_not_correct(sound, unpatched, tiny_root, control):
    Manifest(tiny_root).control(control)()
    line = run(tiny_root)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert compared["blocks_with_flags_differing_from_reference"] == line["failed"] > 0
    assert compared["state_entries_differing_from_reference"] > 0
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["reads_that_found_no_row"] == 0


def test_set_up_blocks_kept_from_the_reference_come_out_as_not_correct(sound, tiny_root):
    """To a reference that was not handed the set-up blocks the chain
    starts empty: every transaction that reaches MVCC conflicts, and no
    populated row is there."""
    line = run(tiny_root, cell="withheld.catchup")
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    assert compared["state_entries_differing_from_reference"] == 2 * TINY["accounts"]
    assert compared["generator_disagrees_with_reference"] == 1
    assert compared["reads_that_found_no_row"] == 0


def test_a_populated_row_taken_out_of_the_template_is_missed(sound, tiny_root, tiny_world,
                                                             monkeypatch):
    """Every populated row is compared after the window, not only the
    rows the measured blocks touched: a template that lost the checking
    row of an account no transaction names reads every flag as the
    reference has it, and one state entry short."""
    named = {a for txs in tiny_world.txs for _op, a, b, _amount in txs for a in (a, b)}
    cold = max(set(range(TINY["accounts"])) - named)
    populate = engine._Ledgers.populate

    def lossy(self):
        import sqlite3

        cost = populate(self)
        db = sqlite3.connect(os.path.join(self._template, "index.sqlite"))
        with db:
            lost = db.execute("DELETE FROM kv WHERE instr(k, 'statedb') AND instr(k, ?)",
                              (f"checking_{cold}".encode(),))
        assert lost.rowcount == 1
        db.close()
        return cost

    monkeypatch.setattr(engine._Ledgers, "populate", lossy)
    line = run(tiny_root)
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False and line["failed"] == 0
    assert compared.pop("state_entries_differing_from_reference") == 1
    assert all(v == 0 for v in compared.values()), compared


# -- the condition -------------------------------------------------------------


def _cell(refused, read_keys, found, flushes=(10, 30, 1), planted=None, numbers=None,
          block_txs=1000, setup=10):
    n = len(refused)
    world = types.SimpleNamespace(
        mvcc_refused=refused, read_keys=read_keys, setup_blocks=[b""] * setup,
        planted_classes=planted
        or [{"bad_creator": 1, "bad_endorsement": 1, "conflict_pair": 1}] * n)
    csp = types.SimpleNamespace(flush_tally=lambda: dict(
        zip(("flushes", "segments", "lone"), flushes)))
    cell = types.SimpleNamespace(
        world=world, csp=csp, yielded=[(b, b"") for b in range(n)],
        deployment={"block_txs": block_txs, "conflict_floor_share": 0.3})
    # (number, keys_asked, rows_found, valid_in, read_conflicts, phantom_conflicts)
    recent = [(num, f, f, 998, r, 0)
              for num, f, r in zip(numbers or range(setup + 1, setup + 1 + n), found, refused)]
    return cell, recent


@pytest.mark.parametrize("refused,read_keys,found,more,want", [
    ([430, 540, 470], [1150, 1160, 1140], [1150, 1160, 1140], {}, (0, 0, 0, 0)),   # a good pass
    ([430, 299, 12], [1150, 1160, 1140], [1150, 1160, 1140], {}, (2, 0, 0, 0)),    # draws that do not meet
    ([430, 540, 470], [1150, 1160, 1140], [1150, 0, 1139], {}, (0, 0, 1161, 0)),   # rows that are not there
    ([430, 540, 470], [1150, 1160, 1140], [1200, 1160, 1140], {}, (0, 0, 0, 0)),   # more asked: no fault
    ([430, 540, 470], [1150, 1160, 1140], [1150, 1160, 1140],
     {"numbers": (11, 12, 14)}, (0, 0, 1140, 0)),                                  # another block's count
    ([430, 540, 470], [1150, 1160, 1140], [1150, 1160, 1140],
     {"flushes": (10, 12, 6)}, (0, 0, 0, 1)),                                      # serialised to depth 1
    ([430, 540, 470], [1150, 1160, 1140], [1150, 1160, 1140],
     {"planted": [{"bad_creator": 1, "bad_endorsement": 0, "conflict_pair": 1}] * 3},
     (0, 3, 0, 0)),
    ([430, 540, 470], [1150, 1160, 1140], [1150, 1160, 1140],
     {"planted": [{"bad_creator": 1, "bad_endorsement": 1, "conflict_pair": 0}] * 2
      + [{"bad_creator": 0, "bad_endorsement": 0, "conflict_pair": 0}]}, (0, 5, 0, 0)),
])
def test_the_condition_holds_the_traffic_and_the_program_to_the_cells_regime(
        man, held, monkeypatch, refused, read_keys, found, more, want):
    from fabric_tpu.ledger import txmgmt

    (numbers,) = man.conditions(held)
    cell, recent = _cell(refused, read_keys, found, **more)
    # the program's record also holds what went before the window
    monkeypatch.setattr(txmgmt, "mvcc_tally",
                        lambda: {"recent_blocks": [(1, 0, 0, 4, 0, 0), (11, 9, 9, 9, 1, 0)] + recent})
    assert numbers(cell) == {
        "blocks_with_too_few_conflicts": (want[0], 0),
        "planted_classes_missing_from_a_block": (want[1], 0),
        "reads_that_found_no_row": (want[2], 0),
        "most_flushes_held_one_block_alone": (want[3], 0),
    }


# -- the readers ---------------------------------------------------------------


@pytest.fixture(scope="module")
def obs():
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_smallbank.json")) as f:
        return json.load(f)


def _said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    return None


def test_the_four_readers_read_the_recorded_window(obs, man, capsys):
    spans = obs["spans"]
    by = lambda name: [e for e in spans if e["name"] == name]  # noqa: E731
    n = obs["blocks"]
    assert len(by("mvcc")) == len(by("mvcc.preload")) == n == 2
    capsys.readouterr()
    share = man.reader(METRICS[0])(obs)
    said = _said(capsys, "mvcc")
    assert share == pytest.approx(100.0 * sum(obs["mvcc_refused"]) / (obs["block_txs"] * n))
    assert said["read_conflicts_per_block"] == pytest.approx(sum(obs["mvcc_refused"]) / n)
    assert said["phantom_conflicts_per_block"] == 0 and said["valid_in_per_block"] == 48
    preload = man.reader(METRICS[1])(obs)
    said = _said(capsys, "mvcc_preload")
    assert 0 < preload == pytest.approx(sum(e["dur"] for e in by("mvcc.preload")) / 1e3 / n)
    assert said["keys_asked_per_block"] == said["rows_found_per_block"] \
        == pytest.approx(sum(obs["read_keys"]) / n)
    assert said["cpu_ms_per_block"] \
        == pytest.approx(sum(e["tdur"] for e in by("mvcc.preload")) / 1e3 / n)
    assert man.reader(METRICS[2])(obs) == 100.0
    kv = man.reader(METRICS[3])(obs)
    said = _said(capsys, "kv_txn")
    groups = by("kv_txn")
    assert 0 < kv == pytest.approx(sum(e["dur"] for e in groups) / 1e3 / n)
    assert said["groups"] == len(groups) and said["blocks_per_group"] * len(groups) == n
    assert 0 < said["rows_per_group"] == pytest.approx(
        sum(e["args"]["rows"] for e in groups) / len(groups))
    # the bulk read stands inside its block's `mvcc` span
    for parent in by("mvcc"):
        (inside,) = [e for e in spans if e["args"].get("parent") == parent["args"]["span"]]
        assert inside["name"] == "mvcc.preload" and inside["dur"] <= parent["dur"]


def test_an_unpopulated_ledger_reads_a_share_near_nothing(obs, man):
    """Every read of a key no block has written yet: asked, not found."""
    bare = copy.deepcopy(obs)
    for e in bare["spans"]:
        if e["name"] == "mvcc.preload":
            e["args"]["rows_found"] = 0
    assert man.reader(METRICS[2])(bare) == 0.0
    for e in bare["spans"]:
        if e["name"] == "mvcc.preload":
            e["args"]["keys_asked"] = 0
    assert man.reader(METRICS[2])(bare) is None      # a window that asked for nothing


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_spans_gives_a_reader_nothing(obs, man, name):
    """The parent of PR 46: `mvcc` with `block` alone, `kv_txn` with
    `blocks` alone, no `mvcc.preload` child.  And an untraced run."""
    old = copy.deepcopy(obs)
    old["spans"] = [e for e in old["spans"] if not e["name"].startswith("mvcc.")]
    for e in old["spans"]:
        e["args"] = {k: v for k, v in e["args"].items()
                     if k not in ("valid_in", "read_conflicts", "phantom_conflicts", "rows")}
    assert man.reader(name)(old) is None
    assert man.reader(name)(dict(obs, spans=None)) is None
    assert man.reader(name)(dict(obs, spans=[])) is None
