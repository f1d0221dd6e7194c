"""The key-level kind of deployment (`keylevel-5org-1000tx`): its seeded
world (what the seed fixes, what is planted, what it keeps for the
condition), its plain reference on each kind of flag a pipelined
validator used to get wrong, a whole rehearsal of
`keylevel-5org-1000tx.catchup` on the CPU at a tiny size, the same with
the program's keys never pending underneath (the control
`stale_key_policies`), the condition on a good and on a starved world,
and the three new readers on a recorded span list
(`data/spans_keylevel.json`: one small stream through `store_stream`).

No number of a CPU run is a device number: the tests read counts,
flags and verdicts, never a time.  A pass's three blocks go out as one
flush under 256 lanes, so one kernel shape is built in this process.
"""

import ast
import copy
import json
import os
import sys
import types
from collections import Counter

import pytest

from benchlib import engine
from benchlib.manifest import Manifest, ManifestError

from conftest import BENCH, ROOT

SEED = 2**31 + 140
SIZE = engine.Rehearsal(block_txs=12, blocks_per_pass=3)
CELL = "keylevel-5org-1000tx.catchup"
CONFIG = "keylevel-5org-1000tx"
NS = "benchcc"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


@pytest.fixture(scope="module")
def kl(man, held):
    man.world(held)
    return sys.modules["bench_worlds_x509_keylevel"]


def _build(man, held, seed, block_txs, n_blocks):
    dep = dict(held["deployment"], block_txs=block_txs)
    return man.world(held)(seed, dep, held["planted"], n_blocks), dep


# -- the configuration and the world -----------------------------------------


def test_the_configuration_states_its_source_its_shapes_and_its_guarantees(held):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (entry,) = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == held["source"] and len(entry["source"]) <= 200
    assert "Setting key-level endorsement policies" in held["source"]
    assert held["world"] == held["reference"] == "x509-keylevel"
    assert held["conditions"] == ["keylevel-shape"]
    dep = held["deployment"]
    assert (dep["orgs"], dep["block_txs"], dep["value_bytes"], dep["client_identities"]) \
        == (5, 1000, 32, 1)
    assert (dep["single_owner_share"], dep["transfer_share"], dep["create_blocks_share"]) \
        == (0.8, 0.3, 0.25)
    assert entry["reduced"] == held["reduced"] == ["chain_depth", "client_identities",
                                                   "state_size"]
    assert set(held["reduced_how"]) == set(held["reduced"])
    assert any("committed by the block before" in g for g in held["guarantees"])
    assert held["assumed"] and "dependent_transactions_per_work_block" in dep
    (cell,) = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "catchup", 1)
    assert Manifest(ROOT).traffic(cell)["blocks_per_pass"] == 16
    # the cell's three metrics, wherever they stand in `per_layer`, for this cell alone
    declared = {m["name"]: m for m in doc["per_layer"]}
    for name in ("keylevel_commit_wait_ms_per_block.catchup",
                 "keylevel_lookup_ms_per_block.catchup", "keylevel_deferred_tx_share.catchup"):
        m = declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "committed_tx_per_s"
        assert m["layer"] == "validator (peer/txvalidator.py)"


def test_the_world_plants_what_the_configuration_says_and_counts_its_neighbours(man, held, kl):
    world, _dep = _build(man, held, SEED, 60, 8)
    p = held["planted"]
    assert world.block_kinds == ["create"] * 2 + ["work"] * 6
    for b, (kind, flags, txs) in enumerate(zip(world.block_kinds, world.planted, world.txs)):
        c = Counter(int(f) for f in flags)
        kinds = Counter(t.kind for t in txs)
        prev = world.planted_classes[b].get("previous_owner", 0)
        if kind == "create":
            assert (c[4], c[10], c[11]) == (1, 1, 1)
        else:
            assert c[4] == p["bad_creator_per_block"] == kinds["bad_creator"]
            assert c[11] == p["conflict_pairs_per_block"] == kinds["conflict_second"]
            assert c[10] == p["bad_endorsement_per_block"] + p["in_block_pairs_per_block"] + prev
            assert kinds["in_block_transfer"] == kinds["in_block_second"] == 2
            assert prev == kinds["previous_owner"]
            # a previous owner's transaction is sound but for its endorsers
            assert all(world.planted[b][i] == 10 for i, t in enumerate(txs)
                       if t.kind == "previous_owner")
    assert world.planted_classes[2]["previous_owner"] == 0
    assert "previous_owner" not in world.due_classes[2]
    assert all(held_c["previous_owner"] == p["previous_owner_per_block"]
               for held_c in world.planted_classes[3:])
    # an asset once a block, the planted pairs aside
    for txs in world.txs[2:]:
        keys = Counter(t.key for t in txs)
        assert sorted(keys.values(), reverse=True)[:4] == [2, 2, 2, 1]
    # what it keeps of the neighbourhood is what the blocks hold
    wrote: dict = {}
    for b, (flags, txs) in enumerate(zip(world.planted, world.txs)):
        number = b + 1
        for depth, kept in ((2, world.dependent), (3, world.dependent_deep)):
            assert kept[b] == sum(1 for t in txs if number - depth <= wrote.get(t.key, -9) < number)
        for t, f in zip(txs, flags):
            if f == 0 and t.new_owners is not None:
                wrote[t.key] = number
    assert all(d >= 60 / 20 for d in world.dependent[3:])
    assert world.lanes_by_block[0] == world.lanes_per_block == 4 * 60
    assert all(2 * 60 <= n <= 3 * 60 for n in world.lanes_by_block[2:])
    assert world.parameters <= 15 and world.assets == 2 * 57
    # every owner is one organisation or two
    assert {len(t.new_owners) for txs in world.txs for t in txs if t.new_owners} == {1, 2}


def test_the_same_seed_gives_the_same_world(man, held):
    def digest(world):
        return ([[(t.key, t.value, t.endorsers, t.read, t.new_owners, t.kind) for t in txs]
                 for txs in world.txs], world.planted, sorted(world.expected_state().items()),
                world.dependent)

    a, _ = _build(man, held, SEED, 12, 3)
    b, _ = _build(man, held, SEED, 12, 3)
    c, _ = _build(man, held, SEED + 1, 12, 3)
    assert digest(a) == digest(b) != digest(c)
    assert getattr(a, "definition_provider", None) is None


def test_a_program_that_cannot_give_the_guarantee_is_refused_before_anything_is_measured(
        man, held, monkeypatch):
    """The world asks the program for the count its condition reads: a
    checkout without it (the parent of PR 40) is refused with a
    ManifestError, which `benchmarks/run.py` turns into exit 2."""
    from fabric_tpu.peer import txvalidator

    monkeypatch.delattr(txvalidator, "keylevel_tally")
    with pytest.raises(ManifestError, match="keylevel_tally"):
        _build(man, held, SEED, 12, 1)


# -- the reference -----------------------------------------------------------


def test_the_reference_agrees_with_the_generator_at_rehearsal_size(man, held):
    world, dep = _build(man, held, SEED, 24, 8)
    flags, states = man.reference(held)(world.public, dep, world.blocks)
    assert [list(f) for f in flags] == [list(p) for p in world.planted]
    assert states[-1] == world.expected_state()
    assert {0, 4, 10, 11} <= {f for fl in flags for f in fl}


def test_the_reference_imports_nothing_of_the_program_but_its_protobufs():
    with open(os.path.join(BENCH, "reference", "x509-keylevel.py")) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    ours = {m for m in mods if m.split(".")[0] == "fabric_tpu"}
    assert ours and all(m.startswith("fabric_tpu.protos") for m in ours)
    assert not {m for m in mods if m.split(".")[0] in ("benchlib", "worlds", "reference")}


def test_the_reference_refuses_kind_3_and_accepts_kinds_1_and_2(man, held, kl):
    """Kind 1: created in k, updated in k+1 by its owner alone; kind 2:
    transferred in k, updated in k+1 by its NEW owner; kind 3: the same
    by its OLD owner, read version correct."""
    import random

    rng = random.Random("keylevel-reference")
    net = kl.Net(rng, dict(held["deployment"], block_txs=4))

    def tx(key, endorsers, read=kl.BLIND, new_owners=None, value=b"v"):
        return kl.Tx(key=key, value=value, endorsers=endorsers, read=read, new_owners=new_owners)

    blocks = [
        net.block(rng, 1, [tx("a", (0, 1, 2), new_owners=(0,)),
                           tx("b", (2, 3, 4), new_owners=(0, 1)),
                           tx("c", (0, 1), new_owners=(0,))])[0],        # 2 of 5: refused
        net.block(rng, 2, [tx("a", (0,), read=(1, 0), value=b"kind-1"),
                           tx("b", (0, 1), read=(1, 1), new_owners=(2,), value=b"sold"),
                           tx("b", (0,), read=(1, 1))])[0],     # in-block rule, and half the owners
        net.block(rng, 3, [tx("b", (2,), read=(2, 1), value=b"kind-2"),
                           tx("a", (1,), read=(2, 0)),          # a stranger
                           tx("c", (0, 1, 2), read=None, new_owners=(4,))])[0],
        net.block(rng, 4, [tx("b", (0, 1), read=(3, 0), value=b"kind-3"),     # the OLD owners
                           tx("c", (4,), read=(3, 2), value=b"kind-1-again")])[0],
    ]
    flags, states = man.reference(held)(net.public, held["deployment"], blocks)
    assert [list(f) for f in flags] == [[0, 0, 10], [0, 0, 10], [0, 10, 0], [10, 0]]
    assert states[-1] == {(NS, "a"): (b"kind-1", (2, 0)), (NS, "b"): (b"kind-2", (3, 0)),
                          (NS, "c"): (b"kind-1-again", (4, 1))}
    # a metadata write bumps its key's version and keeps nothing else
    assert states[1][NS, "b"] == (b"sold", (2, 1))


# -- the rehearsal -----------------------------------------------------------


def run(trace=False):
    return engine.run_cell(ROOT, CELL, SEED, 1.0, trace, rehearsal=SIZE)


@pytest.fixture(scope="module")
def sound():
    return run(trace=True)


def test_a_rehearsal_agrees_with_its_reference_to_the_flag_and_the_state_entry(sound):
    compared = {k: v["value"] for k, v in sound["compared"].items()}
    assert sound["attempted"] >= 3 and sound["failed"] == 0
    assert set(compared) >= {
        "work_blocks_with_too_few_dependent_transactions",
        "planted_classes_missing_from_a_block", "dependent_transactions_not_deferred",
        "most_flushes_held_one_block_alone"}
    assert all(v == 0 for v in compared.values()), compared
    assert all(v["limit"] == 0 for v in sound["compared"].values())
    assert sound["correct"] is True


def test_a_traced_rehearsal_reports_the_three_metrics(sound, man, held):
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert set(sound["metrics"]) <= due
    assert {"keylevel_commit_wait_ms_per_block.catchup", "keylevel_lookup_ms_per_block.catchup",
            "keylevel_deferred_tx_share.catchup", "policy_ms_per_block.catchup",
            "blocks_per_commit_group.catchup", "blocks_per_flush.catchup"} <= set(sound["metrics"])
    value = {k: v["value"] for k, v in sound["metrics"].items()}
    world, _dep = _build(man, held, SEED, SIZE.block_txs, SIZE.blocks_per_pass)
    # the two blocks before are in flight for sure; how many more is timing
    low = 100.0 * sum(world.dependent) / (SIZE.block_txs * SIZE.blocks_per_pass)
    high = 100.0 * world.block_kinds.count("work") / SIZE.blocks_per_pass
    assert 0 < low <= value["keylevel_deferred_tx_share.catchup"] <= high
    assert value["keylevel_commit_wait_ms_per_block.catchup"] >= 0.0
    assert value["keylevel_lookup_ms_per_block.catchup"] > 0.0
    # the pass's three blocks went out as one flush
    assert value["blocks_per_flush.catchup"] == 3.0
    assert 1.0 <= value["blocks_per_commit_group.catchup"] <= 3.0


@pytest.fixture
def unpatched():
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.ledger.txmgmt import MVCCValidator
    from fabric_tpu.peer.txvalidator import _KeyWindow

    saved = TPUCSP.verify_batch_async, MVCCValidator._committed_version, _KeyWindow.pending
    yield
    TPUCSP.verify_batch_async, MVCCValidator._committed_version, _KeyWindow.pending = saved


def test_keys_that_are_never_pending_come_out_as_not_correct(sound, unpatched, man):
    """The program before PR 40: flags AND state differ (a previous
    owner's write lands, a new owner's is refused), and the condition
    says that nothing was deferred."""
    man.control("stale_key_policies")()
    line = run()
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert compared["blocks_with_flags_differing_from_reference"] > 0
    assert compared["state_entries_differing_from_reference"] > 0
    assert compared["dependent_transactions_not_deferred"] > 0
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["planted_classes_missing_from_a_block"] == 0


# -- the condition -----------------------------------------------------------


def _cell(block_kinds, dependent, deferred, flushes=(10, 30, 1), due=None, planted=None,
          block_txs=1000):
    n = len(block_kinds)
    world = types.SimpleNamespace(
        block_kinds=block_kinds, dependent=dependent,
        due_classes=due or [["bad_creator"]] * n,
        planted_classes=planted or [{"bad_creator": 1}] * n)
    csp = types.SimpleNamespace(flush_tally=lambda: dict(
        zip(("flushes", "segments", "lone"), flushes)))
    return types.SimpleNamespace(
        world=world, csp=csp, deployment={"block_txs": block_txs},
        yielded=[(b, b"") for b in range(n)]), [(b + 1, d) for b, d in enumerate(deferred)]


KINDS = ["create"] + ["work"] * 4


@pytest.mark.parametrize("dependent,deferred,more,want", [
    ([0, 226, 210, 205, 235], [0, 250, 230, 215, 240], {}, (0, 0, 0, 0)),   # a good pass
    ([0, 226, 49, 205, 12], [0, 250, 60, 215, 20], {}, (2, 0, 0, 0)),       # neighbours that do not meet
    ([0, 30, 210, 205, 235], [0, 30, 230, 215, 240], {}, (0, 0, 0, 0)),     # the first work block is exempt
    ([0, 226, 210, 205, 235], [0, 226, 200, 0, 235], {}, (0, 0, 215, 0)),   # decided without waiting
    ([0, 226, 210, 205, 235], [0, 250, 230, 215, 240], {"flushes": (10, 12, 6)},
     (0, 0, 0, 1)),                                                         # serialised to depth 1
    ([0, 226, 210, 205, 235], [0, 250, 230, 215, 240],
     {"due": [["a", "b"]] * 5, "planted": [{"a": 1, "b": 0}] * 5}, (0, 5, 0, 0)),
])
def test_the_condition_holds_the_traffic_and_the_program_to_the_cells_regime(
        man, held, monkeypatch, dependent, deferred, more, want):
    from fabric_tpu.peer import txvalidator

    (numbers,) = man.conditions(held)
    cell, recent = _cell(KINDS, dependent, deferred, **more)
    # the program's record also holds what went before the window
    monkeypatch.setattr(txvalidator, "keylevel_tally",
                        lambda: {"recent_blocks": [(1, 0), (2, 7)] + recent})
    assert numbers(cell) == {
        "work_blocks_with_too_few_dependent_transactions": (want[0], 0),
        "planted_classes_missing_from_a_block": (want[1], 0),
        "dependent_transactions_not_deferred": (want[2], 0),
        "most_flushes_held_one_block_alone": (want[3], 0),
    }


# -- the readers -------------------------------------------------------------


@pytest.fixture(scope="module")
def obs():
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_keylevel.json")) as f:
        return json.load(f)


READERS = ("keylevel_commit_wait_ms_per_block.catchup", "keylevel_lookup_ms_per_block.catchup",
           "keylevel_deferred_tx_share.catchup")


def _said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    return None


def test_the_three_readers_read_the_recorded_stream(obs, man, capsys):
    spans = obs["spans"]
    by = lambda name: [e for e in spans if e["name"] == name]  # noqa: E731
    n = obs["blocks"]
    wait = man.reader(READERS[0])(obs)
    assert wait == pytest.approx(sum(e["dur"] for e in by("policy.await_commit")) / 1e3 / n)
    capsys.readouterr()
    lookup = man.reader(READERS[1])(obs)
    said = _said(capsys, "keylevel")
    in_collect = sum(e["args"]["keylevel_reads"] for e in by("collect"))
    deferred_reads = sum(e["args"].get("deferred_reads", 0) for e in by("policy"))
    assert lookup == pytest.approx(
        (sum(e["args"]["keylevel_ms"] for e in by("collect"))
         + sum(e["args"].get("deferred_ms", 0.0) for e in by("policy"))) / n) and lookup > 0
    assert said["blocks"] == n
    assert said["lookups_in_collect_per_block"] == pytest.approx(in_collect / n) and in_collect
    assert said["lookups_deferred_per_block"] == pytest.approx(deferred_reads / n)
    assert said["plan_misses_per_block"] > 0 and said["plan_hits_per_block"] > 0
    assert said["plan_clears_per_block"] == 0
    share = man.reader(READERS[2])(obs)
    deferred = sum(e["args"]["deferred"] for e in by("policy"))
    assert share == pytest.approx(100.0 * deferred / (24 * n))
    # the program deferred at least what the two blocks before force
    # (in this recorded stream no more than the three before)
    assert sum(obs["dependent"]) <= deferred <= sum(obs["dependent_deep"])


def test_a_traced_window_without_a_wait_reads_zero_not_nothing(obs, man):
    """A channel without key-level policies on this program: `deferred`
    0 on every `policy` span and no `policy.await_commit`."""
    quiet = copy.deepcopy(obs)
    quiet["spans"] = [e for e in quiet["spans"] if e["name"] != "policy.await_commit"]
    for e in quiet["spans"]:
        if e["name"] == "policy":
            e["args"] = {"block": e["args"]["block"], "deferred": 0}
        if e["name"] == "collect":
            e["args"].update(keylevel_reads=0, keylevel_ms=0.0, keylevel_policies=0)
    assert man.reader(READERS[0])(quiet) == 0.0
    assert man.reader(READERS[1])(quiet) == 0.0
    assert man.reader(READERS[2])(quiet) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_gives_a_reader_nothing(obs, man, name):
    """The parent of PR 40: no `policy.await_commit`, and `collect` and
    `policy` without the new attributes.  And an untraced run."""
    old = copy.deepcopy(obs)
    old["spans"] = [e for e in old["spans"] if e["name"] != "policy.await_commit"]
    for e in old["spans"]:
        e["args"] = {k: v for k, v in e["args"].items()
                     if not k.startswith(("keylevel_", "deferred", "plan_"))}
    assert man.reader(name)(old) is None
    assert man.reader(name)(dict(obs, spans=None)) is None
    assert man.reader(name)(dict(obs, spans=[])) is None
