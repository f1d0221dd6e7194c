"""The mixed-chaincode kind of deployment (`mixedcc-8cc-5org-1000tx`):
its configuration as the issue fixed it, its seeded world (what the
seed fixes, the layouts it finds, what is planted, what it keeps for the
condition), its plain reference against the world's own flags at
rehearsal size, a whole rehearsal of `mixedcc-8cc-5org-1000tx.catchup`
on the CPU at a tiny size, the same with each of three guarantees
broken inside the program (the controls `ignore_chaincode_definitions`,
`identities_satisfy_many_principals` and the accepted
`accept_all_signatures`), the condition on a sound and on a doctored
`Cell`, and the four new readers on a recorded span list
(`data/spans_mixedcc.json`: one small stream through `store_stream`).

No number of a CPU run is a device number: the tests read counts,
flags and verdicts, never a time.  A pass's three blocks go out as one
flush under 256 lanes, so one kernel shape is built in this process.
"""

import copy
import json
import os
import types
from collections import Counter

import pytest

from benchlib import engine
from benchlib.manifest import Manifest, ManifestError

from conftest import ROOT

SEED = 2**31 + 152
SIZE = engine.Rehearsal(block_txs=12, blocks_per_pass=3)
CELL = "mixedcc-8cc-5org-1000tx.catchup"
CONFIG = "mixedcc-8cc-5org-1000tx"
NEW_METRICS = ("plan_miss_share.catchup", "plan_build_ms_per_block.catchup",
               "plan_clears_per_block.catchup", "namespace_prepares_per_tx.catchup")
CONTROLS = ("ignore_chaincode_definitions", "identities_satisfy_many_principals",
            "accept_all_signatures")


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def held(man):
    return man.config({"name": CELL, "config": CONFIG})


def _build(man, held, seed, block_txs, n_blocks):
    dep = dict(held["deployment"], block_txs=block_txs)
    return man.world(held)(seed, dep, held["planted"], n_blocks), dep


# -- the configuration -------------------------------------------------------


POLICIES = {
    "cc0": None,
    "cc1": "OutOf(2, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer')",
    "cc2": "AND('Org1MSP.peer', OR('Org2MSP.peer', 'Org3MSP.peer'), "
           "OR('Org4MSP.peer', 'Org5MSP.peer'))",
    "cc3": "OR('Org1MSP.peer', AND('Org2MSP.peer', 'Org3MSP.peer'))",
    "cc4": "OutOf(4, 'Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer', 'Org4MSP.peer', "
           "'Org5MSP.peer')",
    "cc5": "OR('Org1MSP.peer', 'Org2MSP.peer', 'Org3MSP.peer', 'Org4MSP.peer', 'Org5MSP.peer')",
    "cc6": "OutOf(2, AND('Org1MSP.peer','Org2MSP.peer'), AND('Org3MSP.peer','Org4MSP.peer'), "
           "AND('Org4MSP.peer','Org5MSP.peer'))",
    "cc7": None,
}
PLANTED = {"bad_creator": 3, "bad_endorsement_breaks_policy": 3,
           "bad_endorsement_policy_still_met": 10, "wrong_orgs": 3, "same_org_twice": 2,
           "duplicate_endorser": 2, "greedy_order": 2, "second_namespace_unmet": 2,
           "conflict_pairs": 2}


def test_the_configuration_states_its_source_its_shapes_and_its_guarantees(held, man):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (entry,) = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert entry["source"] == held["source"] and len(entry["source"]) <= 200
    assert "Endorsement policy syntax" in held["source"] and "cauthdsl" in held["source"]
    assert held["world"] == held["reference"] == "x509-mixedcc"
    assert held["conditions"] == ["mixedcc-shape"]
    dep = held["deployment"]
    assert (dep["orgs"], dep["peers_per_org"], dep["client_identities"], dep["block_txs"],
            dep["orderer"], dep["value_bytes"], dep["chaincodes"], dep["chips"]) \
        == (5, 2, 1, 1000, "solo", 32, 8, 1)
    assert (dep["zipf_constant"], dep["two_namespace_share"]) == (0.99, 0.03)
    # 0.10 unless the issue's rule lowered it (a flush past 8,192 lanes)
    assert dep["over_endorsed_share"] in (0.10, 0.05)
    assert {p["chaincode"]: p.get("policy") for p in dep["policies"]} == POLICIES
    assert [p["chaincode"] for p in dep["policies"]] == sorted(POLICIES)
    assert [p.get("policy_reference") for p in dep["policies"]] \
        == [None] * 7 + ["/Channel/Application/Endorsement"]
    assert {k: v for k, v in held["planted"].items() if k != "where"} == PLANTED
    assert entry["reduced"] == held["reduced"] == ["chain_depth", "client_identities",
                                                   "state_size"]
    assert set(held["reduced_how"]) == set(held["reduced"])
    for said in ("every namespace it writes", "at most one principal",
                 "only where the policy is unmet without it"):
        assert any(said in g for g in held["guarantees"]), said
    assert held["assumed"] and "counted_from_the_world_as_built" in dep
    (cell,) = [w for w in doc["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "catchup", 1)
    declared = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_METRICS:
        m = declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "committed_tx_per_s"
        assert m["layer"] == "validator (peer/txvalidator.py)" and m["source"] == "program_span"
    # what every catch-up cell reports, this one reports too
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert due >= {m["name"] for m in man.metrics("per_layer", "majority5-1000tx.catchup")}
    assert due >= set(NEW_METRICS)
    assert [m["name"] for m in man.metrics("end_to_end", CELL)] \
        == ["committed_tx_per_s", "setup_s"]


# -- the world ---------------------------------------------------------------


def test_the_world_finds_the_layouts_discovery_would_offer(man, held):
    world, _dep = _build(man, held, SEED, 12, 1)
    orgs = {ns: sorted(sorted(o + 1 for o, q in enumerate(lay) for _ in range(q))
                       for lay in lays) for ns, lays in world.layouts.items()}
    assert orgs["cc6"] == [[1, 2, 3, 4], [1, 2, 4, 5], [3, 4, 4, 5]]
    assert orgs["cc1"] == [[1, 2], [1, 3], [2, 3]]
    assert orgs["cc2"] == [[1, 2, 4], [1, 2, 5], [1, 3, 4], [1, 3, 5]]
    assert orgs["cc3"] == [[1], [2, 3]]
    assert orgs["cc5"] == [[1], [2], [3], [4], [5]]
    assert len(orgs["cc4"]) == 5 and all(len(lay) == 4 for lay in orgs["cc4"])
    assert orgs["cc0"] == orgs["cc7"] and len(orgs["cc0"]) == 10
    assert world.namespaces == tuple(sorted(POLICIES))
    # the definitions a peer's lifecycle would hold: none for cc0
    info = world.definition_provider.validation_info
    assert info("cc0") is None and info("no-such-chaincode") is None
    assert all(info(ns)[0] == "vscc" and info(ns)[1] == world.public["definitions"][ns]
               for ns in world.namespaces[1:])
    assert set(world.public) == {"ca_certs_pem", "definitions"}


def test_the_world_plants_what_the_configuration_says(man, held):
    world, _dep = _build(man, held, SEED, 120, 4)
    kinds_of = {"bad_creator": "bad_creator", "conflict_pairs": "conflict_second"}
    for b, (flags, txs) in enumerate(zip(world.planted, world.txs)):
        c, kinds = Counter(int(f) for f in flags), Counter(t.kind for t in txs)
        assert world.planted_classes[b] == PLANTED and world.due_classes[b] == list(PLANTED)
        for cls, n in PLANTED.items():
            assert kinds[kinds_of.get(cls, cls)] == n, cls
        assert c[4] == 3 and c[11] == 2 and c[10] == 3 + 3 + 2 + 2 + 2 + 2
        # the corrupted endorsement a transaction survives is its extra one
        still = [t for t in txs if t.kind == "bad_endorsement_policy_still_met"]
        assert all(flags[txs.index(t)] == 0 and len(t.bad) == 1 for t in still)
        assert world.tolerated_lanes[b] == 10
        # every other planted transaction is refused for its own reason
        assert all(flags[i] == 10 for i, t in enumerate(txs)
                   if t.kind in ("bad_endorsement_breaks_policy", "wrong_orgs", "same_org_twice",
                                 "duplicate_endorser", "greedy_order", "second_namespace_unmet"))
        for t in txs:
            if t.kind == "greedy_order":
                assert t.namespaces == (6,) and sorted(o for o, _k in t.endorsers) == [2, 3, 4]
            if t.kind == "same_org_twice":
                assert max(Counter(o for o, _k in t.endorsers).values()) == 2
                assert len(set(t.endorsers)) == len(t.endorsers)
            if t.kind == "duplicate_endorser":
                assert len(set(t.endorsers)) == len(t.endorsers) - 1
            if t.kind == "second_namespace_unmet":
                assert len(t.namespaces) == 2
        assert world.lanes_by_block[b] == sum(1 + len(set(t.endorsers)) for t in txs)
        assert sum(world.chaincodes_drawn[b].values()) == sum(len(t.namespaces) for t in txs)
        # 120 transactions owe the five most popular chaincodes (8 or more each)
        assert world.due_chaincodes[b] == ["cc0", "cc1", "cc2", "cc3", "cc4"]
    # cc6's sound twin, both of Org4's peers, is in the ordinary draw
    assert any(t.kind == "ordinary" and t.namespaces == (6,)
               and Counter(o for o, _k in t.endorsers)[3] == 2
               for txs in world.txs for t in txs)
    assert 0 < world.plan_keys_by_orgs <= world.plan_keys


def test_the_same_seed_gives_the_same_world(man, held):
    def digest(world):
        return ([[(t.namespaces, t.endorsers, t.key, t.values, t.bad, t.bad_creator, t.kind)
                  for t in txs] for txs in world.txs], world.planted,
                sorted(world.expected_state().items()), world.plan_keys)

    a, _ = _build(man, held, SEED, 12, 3)
    b, _ = _build(man, held, SEED, 12, 3)
    c, _ = _build(man, held, SEED + 1, 12, 3)
    assert digest(a) == digest(b) != digest(c)


def test_a_program_that_keeps_no_tally_is_refused_before_anything_is_measured(
        man, held, monkeypatch):
    """The world asks the program for the count its condition reads: a
    checkout without it (the parent of PR 52) is refused with a
    ManifestError, which `benchmarks/run.py` turns into exit 2."""
    from fabric_tpu.peer import txvalidator

    monkeypatch.delattr(txvalidator, "tolerated_tally")
    with pytest.raises(ManifestError, match="tolerated_tally"):
        _build(man, held, SEED, 12, 1)


# -- the reference -----------------------------------------------------------


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_the_reference_agrees_with_the_generator_at_rehearsal_size(man, held, seed):
    world, dep = _build(man, held, seed, SIZE.block_txs, SIZE.blocks_per_pass)
    flags, states = man.reference(held)(world.public, dep, world.blocks)
    assert [list(f) for f in flags] == [list(p) for p in world.planted]
    assert states[-1] == world.expected_state()
    assert {0, 4, 10, 11} <= {f for fl in flags for f in fl}
    # every planted class is in every block, at 12 transactions too
    assert all(held_c == dict.fromkeys(PLANTED, 1) for held_c in world.planted_classes)
    assert {ns for ns, _key in states[-1]} <= set(world.namespaces)


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
        "planted_classes_missing_from_a_block", "chaincodes_missing_from_a_block",
        "tolerated_bad_lanes_differing_from_planted", "lanes_sealed_by_the_host"}
    assert all(v == 0 for v in compared.values()), compared
    assert all(v["limit"] == 0 for v in sound["compared"].values())
    assert sound["correct"] is True


def test_a_traced_rehearsal_reports_the_four_metrics(sound, man, held):
    due = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert set(sound["metrics"]) <= due
    assert set(NEW_METRICS) | {"policy_ms_per_block.catchup", "collect_ms_per_block.catchup",
                               "collect_self_ms_per_block.catchup"} <= set(sound["metrics"])
    value = {k: v["value"] for k, v in sound["metrics"].items()}
    world, _dep = _build(man, held, SEED, SIZE.block_txs, SIZE.blocks_per_pass)
    # a validator a pass: every pass pays the pass's distinct plans again
    lookups = sum(len(t.namespaces) for txs in world.txs for t in txs)
    assert value["plan_miss_share.catchup"] == pytest.approx(100.0 * world.plan_keys / lookups)
    assert value["namespace_prepares_per_tx.catchup"] == pytest.approx(
        lookups / (SIZE.block_txs * SIZE.blocks_per_pass))
    assert value["plan_clears_per_block.catchup"] == 0.0     # some forty plans: far under the cap
    assert value["plan_build_ms_per_block.catchup"] > 0.0


@pytest.fixture
def unpatched():
    from fabric_tpu.csp.tpu.provider import TPUCSP
    from fabric_tpu.peer.validation_plugins import PolicyProvider
    from fabric_tpu.policies import signature_policy

    saved = (TPUCSP.verify_batch_async, PolicyProvider._resolve_chaincode_policy,
             signature_policy._compile)
    yield
    (TPUCSP.verify_batch_async, PolicyProvider._resolve_chaincode_policy,
     signature_policy._compile) = saved


@pytest.mark.parametrize("control", CONTROLS)
def test_a_broken_guarantee_comes_out_as_not_correct(sound, unpatched, man, control):
    man.control(control)()
    line = run()
    compared = {k: v["value"] for k, v in line["compared"].items()}
    assert line["correct"] is False
    assert compared["blocks_with_flags_differing_from_reference"] > 0
    assert compared["state_entries_differing_from_reference"] > 0
    assert compared["generator_disagrees_with_reference"] == 0
    assert compared["planted_classes_missing_from_a_block"] == 0
    if control == "accept_all_signatures":
        # a provider that checks nothing refuses no lane: the tally says so too
        assert compared["tolerated_bad_lanes_differing_from_planted"] > 0


# -- the condition -----------------------------------------------------------


def _cell(planted_lanes, counted, lanes=None, due=None, held_classes=None, owed=None, drawn=None):
    n = len(planted_lanes)
    world = types.SimpleNamespace(
        tolerated_lanes=planted_lanes,
        due_classes=due or [["bad_creator"]] * n,
        planted_classes=held_classes or [{"bad_creator": 3}] * n,
        due_chaincodes=owed or [["cc0", "cc7"]] * n,
        chaincodes_drawn=drawn or [{"cc0": 360, "cc7": 47}] * n)
    return types.SimpleNamespace(
        world=world, deployment={"block_txs": 1000},
        lanes_window=lanes or {"device": 30000, "host_race": 0, "failover": 0, "breaker": 0,
                               "small": 0, "host_fraction": 0},
        # two passes of the world's blocks
        yielded=[(b, b"") for b in range(n)] * 2), \
        [(b + 1, c) for b, c in enumerate(counted)] * 2


@pytest.mark.parametrize("counted,more,want", [
    ([10, 10, 10], {}, (0, 0, 0, 0)),                                  # a sound window
    ([10, 0, 11], {}, (0, 0, 22, 0)),                                  # the mask folded otherwise
    ([10, 10, 10], {"lanes": {"device": 29000, "host_race": 900, "small": 100}},
     (0, 0, 0, 1000)),                                                 # the host sealed lanes
    ([10, 10, 10], {"due": [["a", "b"]] * 3, "held_classes": [{"a": 1, "b": 0}] * 3},
     (3, 0, 0, 0)),                                                    # a class in no block
    ([10, 10, 10], {"drawn": [{"cc0": 360, "cc7": 0}] * 3}, (0, 3, 0, 0)),   # a chaincode in none
])
def test_the_condition_holds_the_traffic_and_the_program_to_the_cells_regime(
        man, held, monkeypatch, counted, more, want):
    from fabric_tpu.peer import txvalidator

    (numbers,) = man.conditions(held)
    cell, recent = _cell([10, 10, 10], counted, **more)
    # the program's record also holds what went before the window
    monkeypatch.setattr(txvalidator, "tolerated_tally",
                        lambda: {"recent_blocks": [(1, 10), (2, 7)] + recent})
    assert numbers(cell) == {
        "planted_classes_missing_from_a_block": (want[0], 0),
        "chaincodes_missing_from_a_block": (want[1], 0),
        "tolerated_bad_lanes_differing_from_planted": (want[2], 0),
        "lanes_sealed_by_the_host": (want[3], 0),
    }


def test_the_condition_refuses_a_tally_that_is_not_of_the_windows_blocks(man, held, monkeypatch):
    from fabric_tpu.peer import txvalidator

    (numbers,) = man.conditions(held)
    cell, _recent = _cell([10, 10, 10], [10, 10, 10])
    # a program that recorded nothing, and one whose record is of other blocks
    for recent in ([], [(7, 10)] * 6):
        monkeypatch.setattr(txvalidator, "tolerated_tally",
                            lambda recent=recent: {"recent_blocks": recent})
        assert numbers(cell)["tolerated_bad_lanes_differing_from_planted"] == (60, 0)


# -- the readers -------------------------------------------------------------


@pytest.fixture(scope="module")
def obs():
    with open(os.path.join(ROOT, "tests", "bench", "data", "spans_mixedcc.json")) as f:
        return json.load(f)


def _said(capsys, tag):
    for line in capsys.readouterr().out.splitlines():
        if line.startswith(f"# {tag}: "):
            return json.loads(line.split(": ", 1)[1])
    return None


def test_the_four_readers_read_the_recorded_stream(obs, man, capsys):
    by = lambda name: [e["args"] for e in obs["spans"] if e["name"] == name]  # noqa: E731
    n, collects = obs["blocks"], by("collect")
    hits = sum(a["plan_hits"] for a in collects)
    misses = sum(a["plan_misses"] for a in collects)
    assert hits + misses == sum(obs["namespace_prepares"])
    capsys.readouterr()
    share = man.reader(NEW_METRICS[0])(obs)
    said = _said(capsys, "plans")
    assert share == pytest.approx(100.0 * misses / (hits + misses)) and 50.0 < share < 100.0
    assert said["blocks"] == n and said["misses_per_block"] == pytest.approx(misses / n)
    assert said["lookups_per_block"] == pytest.approx((hits + misses) / n)
    assert said["definitions_resolved_per_block"] == pytest.approx(
        sum(a["definitions_resolved"] for a in collects) / n)
    assert man.reader(NEW_METRICS[1])(obs) == pytest.approx(
        sum(a["plan_build_ms"] for a in collects) / n)
    # the stream passed the cache's cap of 256 plans twice
    assert man.reader(NEW_METRICS[2])(obs) == pytest.approx(2 / n)
    assert said["clears_per_block"] == pytest.approx(2 / n)
    assert man.reader(NEW_METRICS[3])(obs) == pytest.approx(
        sum(obs["namespace_prepares"]) / (n * obs["block_txs"]))
    assert [a["namespace_prepares"] for a in collects] == obs["namespace_prepares"]
    assert [a["tolerated_bad_lanes"] for a in by("policy")] == obs["tolerated_lanes"]


def test_deferred_decisions_count_their_plans_too(obs, man):
    """A key-level block resolves some policies in its `policy` stage:
    the plans found, built and timed there stand on that span."""
    later = copy.deepcopy(obs)
    for e in later["spans"]:
        if e["name"] == "policy":
            e["args"].update(plan_hits=10, plan_misses=30, plan_clears=1, plan_build_ms=2.0)
    collects = [e["args"] for e in obs["spans"] if e["name"] == "collect"]
    hits = sum(a["plan_hits"] for a in collects) + 10 * obs["blocks"]
    misses = sum(a["plan_misses"] for a in collects) + 30 * obs["blocks"]
    assert man.reader(NEW_METRICS[0])(later) == pytest.approx(100.0 * misses / (hits + misses))
    assert man.reader(NEW_METRICS[1])(later) == pytest.approx(
        man.reader(NEW_METRICS[1])(obs) + 2.0)
    assert man.reader(NEW_METRICS[2])(later) == pytest.approx(
        man.reader(NEW_METRICS[2])(obs) + 1.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_spans_gives_a_reader_nothing(obs, man, name):
    """The parent of PR 52 (no `plan_build_ms`, `namespace_prepares`),
    the parent of PR 40 (no `plan_*` at all), and an untraced run."""
    old = copy.deepcopy(obs)
    for e in old["spans"]:
        e["args"] = {k: v for k, v in e["args"].items()
                     if not k.startswith(("plan_", "namespace_", "definitions_", "tolerated_"))}
    assert man.reader(name)(old) is None
    assert man.reader(name)(dict(obs, spans=None)) is None
    assert man.reader(name)(dict(obs, spans=[])) is None
