"""Faultfuzz tests (ISSUE 8 tentpole): registry discovery over the
canned workload, fixed-seed campaign determinism (the acceptance pin:
two 25-plan seed-7 campaigns produce byte-identical verdicts and
canonical trip ledgers), an intentionally-seeded oracle violation
(a crash between the block-file fsync and the KV transaction + a
skipped recovery replay) caught, shrunk to its 2-rule minimum, and
replayable from the repro artifact, the snapshot
export/import fault points (torn manifest refused, half-import refused
loudly), and the tier-1 soak mode (slow): the commit+snapshot workload
under the low-probability background plan to a green oracle."""

import copy
import json
import os
import random

import pytest

from fabric_tpu.devtools import faultfuzz, faultline, invariants
from fabric_tpu.ledger import LedgerProvider
from fabric_tpu.ledger import snapshot as snap


# -- workload + oracle baseline ----------------------------------------------


def test_workload_green_without_effective_faults(tmp_path):
    """The canned workload with a never-matching plan: all phases run,
    the oracle is green — the fuzzer's failures are real signals, not
    workload noise."""
    res = faultfuzz.run_plan(
        {"faults": [{"point": "no.such.point", "action": "delay",
                     "delay_s": 0.0}]},
        str(tmp_path / "w"),
    )
    assert res["violations"] == []
    assert res["trips"] == []
    assert res["stats"]["committed"] == faultfuzz.DEFAULT_BLOCKS + 2
    assert res["stats"]["import"] == "done"
    assert res["stats"]["rpc_ok"] == 3


def test_registry_discovery_enumerates_the_workload_surface(tmp_path):
    c = faultfuzz.Campaign(
        seed=1, plans=0, workdir=str(tmp_path), out_dir=str(tmp_path)
    )
    reg = c.discover(str(tmp_path))
    # the three layers the canned workload drives
    for point in (
        "commit.stage", "kvstore.txn", "blkstorage.file_append",
        "blkstorage.fsync", "snapshot.export.stage", "snapshot.manifest",
        "snapshot.import.stage", "rpc.accept", "rpc.client.read",
        "rpc.server.read",
    ):
        assert point in reg, sorted(reg)
    # ctx value samples give the generator concrete targets
    assert "mvcc" in reg["commit.stage"]["ctx"]["stage"]
    assert "write" in reg["snapshot.manifest"]["kinds"]
    assert "io" in reg["rpc.client.read"]["kinds"]


# -- determinism acceptance ---------------------------------------------------


def _strip_paths(summary: dict) -> dict:
    out = {k: v for k, v in summary.items() if k != "repro"}
    out["results"] = [
        {k: v for k, v in e.items() if k != "repro"}
        for e in summary["results"]
    ]
    return out


def test_campaign_25_plans_seed_7_is_deterministic(tmp_path):
    """The acceptance pin: the fixed-seed campaign
    (scripts/chaos.py --plans 25 --seed 7) run twice produces
    byte-identical trip ledgers and oracle verdicts."""
    runs = []
    for sub in ("r1", "r2"):
        c = faultfuzz.Campaign(
            seed=7, plans=25, workdir=str(tmp_path / sub),
            out_dir=str(tmp_path / sub / "out"),
        )
        runs.append(c.run())
    a, b = runs
    assert a["verdicts"] == b["verdicts"]
    assert json.dumps(a["trip_ledger"], sort_keys=True) == \
        json.dumps(b["trip_ledger"], sort_keys=True)
    assert _strip_paths(a) == _strip_paths(b)
    # the campaign actually injected faults (a dead campaign would be
    # vacuously deterministic)
    assert a["trips_total"] > 0
    assert a["registry_points"] >= 10


# -- the seeded oracle violation ---------------------------------------------


_SEEDED_PLAN = {
    "seed": 3,
    "label": "seeded",
    "faults": [
        # a crash once the block file is fsynced: the block record is
        # durable, the group's one KV transaction (state, history,
        # index, savepoint) has not begun...
        {"point": "commit.stage", "action": "crash",
         "ctx": {"stage": "fsync"}, "count": 1},
        # ...and the reopen's replay of the blocks past the state
        # savepoint is SKIPPED: the block store re-indexes the record,
        # the ledger reports its height, and its writes are in no
        # state — lost state below the recovered height
        {"point": "ledger.recovery_replay", "action": "skip", "count": 5},
    ],
}


def test_seeded_violation_caught_shrunk_and_replayable(tmp_path):
    """The full failure pipeline: the oracle catches the corruption,
    shrinking proves BOTH rules are load-bearing (the minimal plan is
    exactly the two of them), the repro artifact is written, and
    re-arming it reproduces the failure."""
    res = faultfuzz.run_plan(_SEEDED_PLAN, str(tmp_path / "run"))
    assert res["violations"], "the seeded violation was not caught"
    checks = {v["check"] for v in res["violations"]}
    assert checks & {"state", "reopen"}, res["violations"]

    # dropping either rule individually passes — the pair is minimal
    counter = [0]

    def still_fails(cand):
        counter[0] += 1
        return bool(faultfuzz.run_plan(
            cand, str(tmp_path / f"shrink{counter[0]}")
        )["violations"])

    shrunk, runs = faultfuzz.shrink_plan(_SEEDED_PLAN, still_fails)
    assert len(shrunk["faults"]) == 2
    assert {f["point"] for f in shrunk["faults"]} == {
        "commit.stage", "ledger.recovery_replay",
    }
    assert runs >= 2  # it really tried to drop both

    path = faultfuzz.write_repro(
        str(tmp_path / "repro.json"), shrunk, _SEEDED_PLAN,
        res["violations"], res["trips"], seed=3, index=0,
    )
    doc = json.loads(open(path).read())
    assert doc["format"] == faultfuzz.REPRO_FORMAT
    replayed = faultfuzz.replay(path, str(tmp_path / "replay"))
    assert replayed["violations"], "the repro artifact did not reproduce"
    assert {v["check"] for v in replayed["violations"]} & \
        {"state", "reopen"}
    # the trip ledger is the artifact's too, rule for rule
    assert replayed["trips"] == doc["trips"] == res["trips"]


@pytest.mark.parametrize(
    "crash_stage", [None, "kv_txn"], ids=["no_crash", "after_kv_txn"],
)
def test_skipped_replay_alone_is_not_a_defect(tmp_path, crash_stage):
    """The guard is a defect only behind a crash that leaves blocks
    past the savepoint.  With no crash nothing is ever past it and the
    guard is never asked; a crash AFTER the `kv_txn` stage finds the
    group's transaction landed (it is the commit point), so the reopen
    has nothing to replay either: the oracle is green in both."""
    faults = [dict(_SEEDED_PLAN["faults"][1])]
    if crash_stage:
        faults.insert(0, {"point": "commit.stage", "action": "crash",
                          "ctx": {"stage": crash_stage}, "count": 1})
    res = faultfuzz.run_plan(
        {"seed": 3, "label": "skip-only", "faults": faults},
        str(tmp_path / "run"), comm=False,
    )
    assert res["violations"] == []
    assert [t["point"] for t in res["trips"]] == \
        (["commit.stage"] if crash_stage else [])
    assert res["stats"]["committed"] == faultfuzz.DEFAULT_BLOCKS + 2 - \
        bool(crash_stage)


def test_canned_workload_runs_on_the_store_the_peer_runs(tmp_path):
    """The campaigns tear what every peer flushes: one `SqliteKVStore`
    on the clustered layout, one `index.sqlite` and no other store
    file."""
    from fabric_tpu.ledger.kvstore import SqliteKVStore

    faultfuzz._drive(str(tmp_path), blocks=1, comm=False)
    src = faultfuzz._src_root(str(tmp_path))
    provider = LedgerProvider(src)
    try:
        assert type(provider.kv) is SqliteKVStore
        assert provider.kv.clustered is True
    finally:
        provider.close()
    assert sorted(
        f for f in os.listdir(src) if f.endswith(".sqlite")
    ) == ["index.sqlite"]


def test_campaign_writes_repro_for_failing_plan(tmp_path):
    """End to end through Campaign: a campaign that happens to include
    the seeded failure writes a shrunk repro artifact and reports the
    failure in its summary (simulated by judging a single run_plan
    failure through the same artifact path chaos.py uses)."""
    res = faultfuzz.run_plan(_SEEDED_PLAN, str(tmp_path / "run"))
    out = str(tmp_path / ".faultfuzz")
    path = faultfuzz.write_repro(
        os.path.join(out, "repro_seed3_plan000.json"),
        _SEEDED_PLAN, _SEEDED_PLAN, res["violations"], res["trips"],
        seed=3, index=0,
    )
    assert os.path.isfile(path)


# -- single-edit mutants (ISSUE 19 satellite) ---------------------------------


def _seeded_registry():
    """Registry slice covering the seeded plan's two points, with the
    kinds the pinned faultmap carries — enough for mutate_plan's
    action-pool lookup."""
    return {
        "commit.stage": {"kinds": ["point"], "ctx": {}},
        "ledger.recovery_replay": {"kinds": ["guard"], "ctx": {}},
    }


def test_mutate_plan_same_seed_same_single_edit_mutant():
    """A mutant is fully derived from its rng seed and differs from
    its parent by EXACTLY one edit: a dropped rule, a swapped action
    (from the point's own pool), or a re-sampled trigger.  The plan
    seed carries over, so a mutant run isolates one variable."""
    registry = _seeded_registry()
    snapshot = copy.deepcopy(_SEEDED_PLAN)
    parent = _SEEDED_PLAN["faults"]
    kinds_of_edit = set()
    for j in range(8):
        a = faultfuzz.mutate_plan(
            random.Random(f"3:0:m{j}"), _SEEDED_PLAN, registry,
            f"seeded:m{j}",
        )
        b = faultfuzz.mutate_plan(
            random.Random(f"3:0:m{j}"), _SEEDED_PLAN, registry,
            f"seeded:m{j}",
        )
        assert a == b  # same (seed, plan index, mutant index) -> same mutant
        assert a["label"] == f"seeded:m{j}"
        assert a["seed"] == _SEEDED_PLAN["seed"]
        faults = a["faults"]
        if len(faults) == len(parent) - 1:
            kinds_of_edit.add("drop")
            assert all(f in parent for f in faults)
        else:
            assert len(faults) == len(parent)
            diffs = [k for k in range(len(parent))
                     if faults[k] != parent[k]]
            assert len(diffs) == 1, (faults, parent)
            f, p = faults[diffs[0]], parent[diffs[0]]
            assert f["point"] == p["point"]  # the rule kept its target
            if f["action"] != p["action"]:
                kinds_of_edit.add("action")
                assert f["action"] in faultfuzz._action_pool(
                    f["point"], registry[f["point"]]["kinds"]
                )
            else:
                kinds_of_edit.add("trigger")
    # all three edit kinds show up across the first 8 seeds, and the
    # parent plan itself is never touched (deepcopy, not aliasing)
    assert kinds_of_edit == {"drop", "action", "trigger"}
    assert _SEEDED_PLAN == snapshot


def test_campaign_mutants_ride_the_repro_path_and_stay_deterministic(
        tmp_path, monkeypatch):
    """Campaign-level mutant plumbing.  Generated plans at test sizes
    never fail the oracle, so the failing-plan mutant path is pinned
    by making the generator emit the seeded failure: the campaign
    derives K seed-addressed mutants, judges each, writes a repro for
    the still-failing one (mutant m5's trigger tweak keeps the
    post-fsync crash live), counts it in the summary, and two
    same-seed campaigns agree byte-for-byte once artifact paths are
    stripped."""
    def seeded_generator(rng, registry, label, tripped=frozenset()):
        plan = copy.deepcopy(_SEEDED_PLAN)
        plan["label"] = label
        return plan

    monkeypatch.setattr(faultfuzz, "generate_plan", seeded_generator)

    def strip(summary):
        out = {k: v for k, v in summary.items()
               if k not in ("repro", "trace", "profile")}
        out["results"] = [
            {
                **{k: v for k, v in e.items()
                   if k not in ("repro", "trace", "profile", "mutants")},
                "mutants": [
                    {k: v for k, v in m.items() if k != "repro"}
                    for m in e.get("mutants", ())
                ],
            }
            for e in summary["results"]
        ]
        return out

    runs = []
    for sub in ("r1", "r2"):
        c = faultfuzz.Campaign(
            seed=3, plans=1, mutants=6, shrink=False,
            workdir=str(tmp_path / sub),
            out_dir=str(tmp_path / sub / "out"),
        )
        runs.append(c.run())
    a, b = runs
    assert strip(a) == strip(b)

    assert a["mutants_per_failure"] == 6
    assert a["mutant_failures"] == 1
    [entry] = a["results"]
    assert entry["verdict"] == "fail"
    muts = entry["mutants"]
    assert [m["index"] for m in muts] == list(range(6))
    # each mutant label is addressable back to (seed, plan, mutant)
    assert muts[5]["plan"]["label"] == "fuzz:3:0:m5"
    assert [m["verdict"] for m in muts] == \
        ["pass", "pass", "pass", "pass", "pass", "fail"]
    # mutant trips feed the campaign's coverage ledger
    assert a["trips_total"] > len(entry["trips"])

    # the failing mutant wrote a repro through the same artifact path
    # as its parent, and that artifact replays to the same violation
    assert len(a["repro"]) == 2
    failing = muts[5]
    assert failing["repro"].endswith("repro_seed3_plan000_m5.json")
    assert os.path.isfile(failing["repro"])
    doc = json.loads(open(failing["repro"]).read())
    assert doc["format"] == faultfuzz.REPRO_FORMAT
    replayed = faultfuzz.replay(
        failing["repro"], str(tmp_path / "replay")
    )
    assert replayed["violations"], \
        "the mutant repro artifact did not reproduce"
    assert {v["check"] for v in replayed["violations"]} & {"state"}


# -- snapshot fault points ----------------------------------------------------


def _build_ledger(root, blocks=3):
    provider = LedgerProvider(str(root))
    ledger = provider.open(faultfuzz.CHANNEL)
    writes = faultfuzz.workload_writes(blocks)
    for n in range(blocks):
        ledger.commit(faultfuzz._endorsed_block(ledger, n, writes[n]))
    return provider, ledger


def test_torn_manifest_staging_dir_refuses_verification(tmp_path):
    """A torn write of the signable metadata mid-export: the crash
    leaves only the staging directory, nothing lands in completed/,
    and verify_snapshot refuses the torn directory — the oracle's
    rejection contract."""
    provider, ledger = _build_ledger(tmp_path / "src")
    with faultline.use_plan({"faults": [
        {"point": "snapshot.manifest", "action": "torn", "cut": 0.5},
    ]}):
        with pytest.raises(faultline.FaultCrash, match="torn write"):
            ledger.snapshots.generate()
        assert faultline.trips()
    provider.close()

    snaps = tmp_path / "src" / "snapshots"
    assert not os.path.isdir(str(snaps / "completed" / faultfuzz.CHANNEL))
    staging = snaps / "in_progress"
    [work] = os.listdir(str(staging))
    torn_dir = str(staging / work)
    # the torn manifest is really a strict prefix on disk
    raw = open(os.path.join(torn_dir, snap.METADATA_FILE), "rb").read()
    with pytest.raises(ValueError):
        json.loads(raw.decode("utf-8", "replace"))
    assert invariants.check_snapshot_rejected(torn_dir) == []
    with pytest.raises(Exception):
        snap.verify_snapshot(torn_dir)


def test_export_crash_before_rename_leaves_completed_clean(tmp_path):
    """A crash at the rename stage: the fully-written snapshot stays in
    staging, completed/ holds nothing — and a later export of the same
    height succeeds after the staging dir is reclaimed."""
    provider, ledger = _build_ledger(tmp_path / "src")
    with faultline.use_plan({"faults": [
        {"point": "snapshot.export.stage", "action": "crash",
         "ctx": {"stage": "rename"}},
    ]}):
        with pytest.raises(faultline.FaultCrash):
            ledger.snapshots.generate()
    # retry with no plan: generate_snapshot reclaims the staging dir
    path = ledger.snapshots.generate()
    assert os.path.isdir(path)
    assert invariants.check_snapshot_verifies(path) == []
    provider.close()


def test_partial_import_refused_loudly(tmp_path):
    """A crash mid-import (after txids, before state) leaves the
    half-import marker: both re-import and open() refuse the channel
    instead of serving partial state."""
    provider, ledger = _build_ledger(tmp_path / "src")
    export_dir = ledger.snapshots.generate()
    provider.close()

    dst_root = str(tmp_path / "dst")
    dst = LedgerProvider(dst_root)
    with faultline.use_plan({"faults": [
        {"point": "snapshot.import.stage", "action": "crash",
         "ctx": {"stage": "txids"}},
    ]}):
        with pytest.raises(faultline.FaultCrash):
            dst.create_from_snapshot(export_dir)
        assert faultline.trips()
    dst.close()

    dst2 = LedgerProvider(dst_root)
    try:
        assert snap.import_marker(dst2.kv, faultfuzz.CHANNEL) == \
            snap.IMPORT_IN_PROGRESS
        with pytest.raises(snap.SnapshotError, match="half-finished"):
            dst2.open(faultfuzz.CHANNEL)
        with pytest.raises(snap.SnapshotError, match="half-finished"):
            dst2.create_from_snapshot(export_dir)
        # the recovery path the refusal points at: discard the debris,
        # then the SAME provider re-imports the SAME snapshot cleanly
        deleted = dst2.discard_failed_import(faultfuzz.CHANNEL)
        assert deleted > 0  # the crashed import left real residue
        assert snap.import_marker(dst2.kv, faultfuzz.CHANNEL) is None
        with pytest.raises(snap.SnapshotError, match="no half-finished"):
            dst2.discard_failed_import(faultfuzz.CHANNEL)
        led2 = dst2.create_from_snapshot(export_dir)
        assert snap.import_marker(dst2.kv, faultfuzz.CHANNEL) == \
            snap.IMPORT_DONE
        assert invariants.check_import_state(led2, export_dir) == []
    finally:
        dst2.close()
    # and a FRESH destination imports the same snapshot cleanly
    dst3 = LedgerProvider(str(tmp_path / "dst3"))
    try:
        led3 = dst3.create_from_snapshot(export_dir)
        assert snap.import_marker(dst3.kv, faultfuzz.CHANNEL) == \
            snap.IMPORT_DONE
        assert invariants.check_import_state(led3, export_dir) == []
    finally:
        dst3.close()


def test_completed_import_marker_done_on_clean_path(tmp_path):
    provider, ledger = _build_ledger(tmp_path / "src")
    export_dir = ledger.snapshots.generate()
    provider.close()
    dst = LedgerProvider(str(tmp_path / "dst"))
    try:
        dst.create_from_snapshot(export_dir)
        assert snap.import_marker(dst.kv, faultfuzz.CHANNEL) == \
            snap.IMPORT_DONE
    finally:
        dst.close()


# -- soak mode ----------------------------------------------------------------


def test_soak_env_arms_background_plan(monkeypatch):
    monkeypatch.setattr(faultline, "_plan", None)
    monkeypatch.setattr(faultline, "_env_plan", None)
    monkeypatch.delenv("FABRIC_TPU_FAULTLINE", raising=False)
    monkeypatch.setenv("FABRIC_TPU_SOAK", "11")
    faultline._init_from_env()
    try:
        plan = faultline.current_plan()
        assert plan is not None and plan.label == "soak"
        assert any(r.wildcard for r in plan.rules)
    finally:
        faultline.deactivate()
        faultline.reset_trips()
    # an explicit FAULTLINE plan wins over SOAK
    monkeypatch.setenv(
        "FABRIC_TPU_FAULTLINE",
        '{"label": "explicit", "faults": [{"point": "x", '
        '"action": "delay", "delay_s": 0.0}]}',
    )
    faultline._init_from_env()
    try:
        assert faultline.current_plan().label == "explicit"
    finally:
        faultline.deactivate()
        faultline.reset_trips()
    with pytest.raises(faultline.PlanError):
        monkeypatch.delenv("FABRIC_TPU_FAULTLINE")
        monkeypatch.setenv("FABRIC_TPU_SOAK", "not-a-seed")
        faultline._init_from_env()


@pytest.mark.slow
def test_soak_tier1_workload_green_oracle(tmp_path):
    """Soak acceptance: the commit+snapshot workload (the tier-1
    subset) under the low-probability background plan finishes with a
    GREEN oracle — background chaos perturbs timing, never
    correctness — and the background delays really fired."""
    with faultline.use_plan(faultline.soak_plan(11)):
        stats = faultfuzz._drive(str(tmp_path), blocks=12)
        soak_trips = [
            t for t in faultline.trips() if t["plan"] == "soak"
        ]
    assert stats["committed"] == 14
    assert stats["import"] == "done"
    assert soak_trips, "the soak plan never fired in 14 commits"
    violations = faultfuzz._judge(
        str(tmp_path), stats, faultfuzz.workload_writes(12)
    )
    assert violations == [], [str(v) for v in violations]


# -- coverage-weighted generation (ISSUE 18 satellite) ------------------------


def test_generate_plan_prefers_cold_points_same_draw_count():
    """Selection is biased toward registry entries with zero trips so
    far: with every point but one marked tripped, every fault rule
    lands on the cold one — and the weighting consumes the same RNG
    draws as the unweighted path, so an empty tripped set reproduces
    the v4 stream exactly (the same-seed campaign byte-identity pin
    rides on this)."""
    import random

    reg = {
        "a.one": {"kinds": []},
        "b.two": {"kinds": []},
        "c.three": {"kinds": []},
    }
    for i in range(20):
        rng = random.Random(f"w:{i}")
        plan = faultfuzz.generate_plan(
            rng, reg, "w", tripped={"a.one", "c.three"}
        )
        assert all(f["point"] == "b.two" for f in plan["faults"])
    # empty tripped set == the unweighted stream, draw for draw
    for i in range(20):
        p0 = faultfuzz.generate_plan(
            random.Random(f"s:{i}"), reg, "s"
        )
        p1 = faultfuzz.generate_plan(
            random.Random(f"s:{i}"), reg, "s", tripped=frozenset()
        )
        assert p0 == p1
    # fully-tripped registry degrades to uniform, never to an error
    p = faultfuzz.generate_plan(
        random.Random("t"), reg, "t", tripped=set(reg)
    )
    assert all(f["point"] in reg for f in p["faults"])


# -- chaos-coverage registry cross-check (ISSUE 18 tentpole) ------------------


def test_pinned_registry_contains_fresh_discovery(tmp_path):
    """The pinned faultmap registry (fabric_tpu/devtools/
    faultmap_registry.json, refreshed via scripts/chaos.py
    --export-registry) must contain every point a fresh observer-plan
    discovery finds — discovery ⊆ registry, the runtime half of the
    containment chain (lint pins registry ⊆ static faultmap)."""
    from fabric_tpu.devtools.lint import load_faultmap_registry

    pinned = load_faultmap_registry()
    assert pinned, "faultmap_registry.json missing or empty"
    c = faultfuzz.Campaign(
        seed=1, plans=0, workdir=str(tmp_path), out_dir=str(tmp_path)
    )
    fresh = c.discover(str(tmp_path))
    for name, ent in fresh.items():
        assert name in pinned, (
            f"discovery found {name!r} missing from the pinned "
            "registry — refresh with scripts/chaos.py --export-registry"
        )
        assert set(ent["kinds"]) <= set(pinned[name]["kinds"]), name
