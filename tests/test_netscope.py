"""Netscope: the cluster-wide telemetry plane (ISSUE 12 tentpole).

Tier-1 pins:
- TSDB-lite mechanics: bounded rings, derived cross-peer-lag series,
  health timeline (ok / unhealthy with reasons / down);
- byte-determinism: two same-seed virtual-clock scrape sessions over
  the same endpoint serialize to identical ``netscope.jsonl`` bytes;
- the stall detector: flags a node strictly behind the tip whose
  height froze while a quorum of peers advanced over the window, stays
  quiet for tip-quiescent nodes, clears on recovery, and drops a
  tracelens instant mark;
- SLO rollups: catch-up seconds from restart markers + height series,
  sustained tx/s from the committed-tx counter slope, threshold
  judgments;
- artifacts: jsonl line shapes and the self-contained HTML report;
- END TO END (multi-process): a 1-org × 2-peer network with one peer's
  block-ingestion wedged by a per-node faultline plan — netscope flags
  exactly that node in the run verdict while the invariants oracle
  stays green on the survivors;
- a netbench ``--metrics-out`` run (slow: the acceptance-shaped
  2-org × 4-peer seeded campaign) emits netscope.jsonl + the HTML
  report with per-node height series and kill markers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from fabric_tpu.common import tracing
from fabric_tpu.common.metrics import GaugeOpts, CounterOpts
from fabric_tpu.common.operations import System
from fabric_tpu.devtools import clockskew
from fabric_tpu.devtools import netharness as nh
from fabric_tpu.devtools import netident
from fabric_tpu.devtools.netscope import Netscope, write_artifacts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def ops_system():
    s = System(("127.0.0.1", 0))
    s.start()
    yield s
    s.stop()


def _gauge(system, name, namespace="ledger"):
    return system.metrics_provider.new_gauge(
        GaugeOpts(namespace=namespace, name=name)
    )


# ---------------------------------------------------------------------------
# TSDB-lite mechanics
# ---------------------------------------------------------------------------


def test_ring_buffer_bound_and_series_query(ops_system):
    g = _gauge(ops_system, "height")
    g.With("channel", "ch").set(0)
    scope = Netscope(
        {"n1": ops_system.addr}, interval_s=0.01, window=4,
    )
    for i in range(9):
        g.With("channel", "ch").set(i)
        scope.scrape_once()
    pts = scope.series("n1", "ledger_height", (("channel", "ch"),))
    assert len(pts) == 4  # ring bounded at the window
    assert [v for _, v in pts] == [5.0, 6.0, 7.0, 8.0]
    assert scope.latest(
        "n1", "ledger_height", (("channel", "ch"),)
    ) == 8.0


def test_derived_lag_and_health_timeline(ops_system):
    g = _gauge(ops_system, "height")
    g.With("channel", "ch").set(10)
    down = Netscope({
        "up": ops_system.addr,
        "gone": ("127.0.0.1", 1),  # nothing listens here
    }, interval_s=0.01)
    down.scrape_once()
    # the dead node lands on the health timeline as down, and the lag
    # series only covers nodes that actually answered
    with down._lock:
        assert [s for _, s, _ in down._health["gone"]] == ["down"]
        assert [s for _, s, _ in down._health["up"]] == ["ok"]
    assert down.series("_derived", "cross_peer_lag_blocks")[0][1] == 0.0

    # a failing checker flips the timeline to unhealthy with reasons
    ops_system.register_checker("statedb", lambda: False)
    down.scrape_once()
    with down._lock:
        t, status, failed = down._health["up"][-1]
    assert status == "unhealthy" and failed == ["statedb"]


def test_two_virtual_clock_sessions_byte_identical(ops_system):
    g = _gauge(ops_system, "height")
    c = ops_system.metrics_provider.new_counter(
        CounterOpts(namespace="ledger", name="transactions_total")
    )

    def session(path):
        with clockskew.use_virtual():
            scope = Netscope(
                {"n1": ops_system.addr}, interval_s=0.25, seed=11,
            )
            for i in range(6):
                g.With("channel", "ch").set(i)
                scope.scrape_once()
                clockskew.sleep(scope._next_interval())
            scope.write_jsonl(path)
        with open(path, "rb") as f:
            return f.read()

    a = session("/tmp/netscope_det_a.jsonl")
    # replay the counter to the identical value sequence
    c._series.clear()
    b = session("/tmp/netscope_det_b.jsonl")
    assert a == b


# ---------------------------------------------------------------------------
# stall detector
# ---------------------------------------------------------------------------


def _scrape_heights(scope, gauges, rounds):
    for hs in rounds:
        for node, g in gauges.items():
            g.set(hs[node])
        scope.scrape_once()


def test_stall_detector_flags_behind_node_only(ops_system):
    """Three 'nodes' scraped off three Systems: one freezes strictly
    behind while the others advance -> flagged, with the evidence
    window and a tracelens instant mark; the tip node that stops
    because it IS the tip stays unflagged."""
    systems = {"a": ops_system}
    for n in ("b", "c"):
        s = System(("127.0.0.1", 0))
        s.start()
        systems[n] = s
    try:
        gauges = {
            n: _gauge(s, "height").With("channel", "ch")
            for n, s in systems.items()
        }
        scope = Netscope(
            {n: s.addr for n, s in systems.items()},
            interval_s=0.01, stall_window=3,
        )
        with tracing.scope() as rec:
            # b freezes at 2 while a and c advance past it
            rounds = [
                {"a": h, "b": min(h, 2), "c": h} for h in range(1, 8)
            ]
            _scrape_heights(scope, gauges, rounds)
            assert scope.stalled_nodes() == ["b"]
            episode = scope.stall_episodes()[0]
            assert episode["node"] == "b"
            assert len(episode["evidence"]) >= scope.stall_window + 1
            marks = [
                ev for ev in rec.snapshot()
                if ev.get("name") == "netscope.stall"
            ]
            assert len(marks) == 1
            assert marks[0]["args"]["node"] == "b"
        # recovery clears the flag (stall_clear event recorded)
        _scrape_heights(
            scope, gauges,
            [{"a": 8, "b": 9, "c": 8}],
        )
        assert scope.stalled_nodes() == []
        with scope._lock:
            kinds = [e["event"] for e in scope._events]
        assert kinds == ["stall", "stall_clear"]

        # tip-quiescence is NOT a stall: a stops at 12 (the tip) while
        # b/c climb toward it from behind
        scope2 = Netscope(
            {n: s.addr for n, s in systems.items()},
            interval_s=0.01, stall_window=3,
        )
        rounds = [
            {"a": 12, "b": h, "c": h} for h in range(3, 11)
        ]
        _scrape_heights(scope2, gauges, rounds)
        assert scope2.stalled_nodes() == []
    finally:
        for n in ("b", "c"):
            systems[n].stop()


# ---------------------------------------------------------------------------
# SLO rollups
# ---------------------------------------------------------------------------


def test_slo_rollups_catch_up_and_tx_rate(ops_system):
    g = _gauge(ops_system, "height").With("channel", "ch")
    tx = ops_system.metrics_provider.new_counter(
        CounterOpts(namespace="ledger", name="transactions_total")
    ).With("channel", "ch")
    with clockskew.use_virtual():
        scope = Netscope(
            {"n1": ops_system.addr}, interval_s=1.0, seed=0,
        )
        # 10 tx/s against the virtual clock; node "restarts" at ~2s
        # and rejoins the tip at the next round
        for i in range(6):
            g.set(i)
            tx.add(10)
            scope.scrape_once()
            if i == 2:
                scope.mark("kill", "n1", sig="kill9")
                scope.mark("restart", "n1")
            clockskew.sleep(1.0)
        # keep the stream going well past the stall-detector's short
        # height window: catch-up must be computed from the FULL
        # series rings (regression: the first cut read the ~8-round
        # stall window, so a long run evicted the rejoin rounds and
        # reported the earliest retained round — grossly inflated)
        for i in range(6, 18):
            g.set(i)
            tx.add(10)
            scope.scrape_once()
            clockskew.sleep(1.0)
        slo = scope.slo({
            "p99_cross_peer_lag_blocks": 1,
            "catch_up_s": 10.0,
            "min_tx_per_s": 5.0,
        })
    assert slo["catch_up_s"]["n1"] == pytest.approx(1.0, abs=0.2)
    assert slo["sustained_tx_per_s"] == pytest.approx(10.0, rel=0.1)
    assert slo["stalled_nodes"] == []
    assert all(j["ok"] for j in slo["judgments"].values())
    assert slo["pass"] is True
    # a violated threshold fails its judgment and the rollup
    bad = scope.slo({"min_tx_per_s": 1000.0})
    assert bad["judgments"]["min_tx_per_s"]["ok"] is False
    assert bad["pass"] is False


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------


def test_jsonl_and_html_artifacts(tmp_path, ops_system):
    g = _gauge(ops_system, "height").With("channel", "ch")
    scope = Netscope({"n1": ops_system.addr}, interval_s=0.01)
    for i in range(4):
        g.set(i)
        scope.scrape_once()
    scope.mark("kill", "n1", sig="kill9")
    scope.mark("restart", "n1")
    paths = write_artifacts(scope, str(tmp_path), prefix="netscope")
    lines = [
        json.loads(ln)
        for ln in open(paths["jsonl"], encoding="utf-8")
    ]
    kinds = [ln["kind"] for ln in lines]
    assert kinds[0] == "netscope-meta"
    assert kinds[-1] == "slo"
    series = [ln for ln in lines if ln["kind"] == "series"]
    assert any(
        s["name"] == "ledger_height" and s["node"] == "n1"
        and [p[1] for p in s["points"]] == [0.0, 1.0, 2.0, 3.0]
        for s in series
    )
    assert any(
        s["name"] == "cross_peer_lag_blocks" and s["node"] == "_derived"
        for s in series
    )
    events = [ln for ln in lines if ln["kind"] == "event"]
    assert [e["event"] for e in events] == ["kill", "restart"]
    health = [ln for ln in lines if ln["kind"] == "health"]
    assert health and health[0]["node"] == "n1"

    html = open(paths["html"], encoding="utf-8").read()
    assert "<svg" in html and "polyline" in html  # sparklines
    assert "ledger_height" in html
    assert "netscope report" in html
    # kill/restart markers drawn as vertical lines with titles
    assert "kill" in html and "restart" in html


# ---------------------------------------------------------------------------
# end to end: the wedged-peer stall, multi-process
# ---------------------------------------------------------------------------


def _wait_for(cond, what: str, timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out before {what}"
        time.sleep(0.05)


def test_wedged_peer_flagged_in_verdict_survivors_green(tmp_path):
    """A per-node faultline plan wedges one peer's block ingestion
    (deliver connect + the gossip.state.payload funnel — the silent
    deliver-client-wedge class PR 11 caught by luck).  The victim is
    the gossip election NON-leader, so the survivors keep committing:
    the survivor is started first and leads before the victim exists.
    (A standing leader is not contested, whoever's pki-id is smaller;
    with every node spawned at once the victim led whenever it declared
    first, and the org took no block at all: this test's unsteadiness
    beside busy xdist workers, until PR 33.)  netscope must flag
    exactly the victim in the verdict while the invariants oracle stays
    green on every node."""
    from fabric_tpu.common.hashing import sha256

    peers = ["org1-peer0", "org1-peer1"]
    # should the two ever contest, the smallest pki-id
    # (sha256(name)[:16]) wins: wedge the OTHER peer
    victim = max(peers, key=lambda n: sha256(n.encode())[:16])
    survivor = next(p for p in peers if p != victim)
    plan = {"seed": 1, "faults": [
        {"point": "gossip.state.payload", "action": "raise",
         "error": "RuntimeError", "every": 1, "count": 10 ** 9},
        {"point": "deliver.connect", "action": "raise",
         "error": "ConnectionResetError", "every": 1, "count": 10 ** 9},
    ]}
    topo = nh.Topology(
        orgs=1, peers_per_org=2, orderers=1, seed=23, ops=True,
        faultline={victim: plan},
    )
    with nh.Network(str(tmp_path / "net"), topo) as net:
        early = [n for n in net.nodes if n != victim]
        for name in early:
            net.spawn(name)
        for name in early:
            net.wait_ready(name)
        _wait_for(
            lambda: net.status(survivor)["election_leader"],
            "the survivor leads",
        )
        net.spawn(victim)
        net.wait_ready(victim)
        # the detector needs scrape windows in which orderer AND
        # survivor advance: one block through first, so the stream
        # starts on a deliver client that is connected
        net.broadcast(netident.make_tx(
            topo.channel, "prime", b"v", orgs=topo.orgs, cc="netcc",
        ))
        _wait_for(
            lambda: net.status(survivor)["height"] >= 2,
            "the survivor commits a block",
        )
        scope = nh.attach_netscope(net, interval_s=0.15)
        try:
            # a second or so of stream: several 4-round stall windows
            result = nh.run_stream(
                net, txs=200, settle_timeout_s=20, scope=scope,
            )
        finally:
            scope.stop()
    assert result["stalled_nodes"] == [victim]
    assert result["ok"] is False  # a stalled node fails the run
    verdict = nh.verdict_doc(result)
    assert verdict["stalled_nodes"] == [victim]
    # invariants green EVERYWHERE: the victim's ledger is consistent
    # (just short), the survivors committed the stream
    assert result["violations"] == {}
    assert result["heights"][survivor] > result["heights"][victim]
    # the stall episode carries its evidence window, and the episode
    # (evidence included) rides the jsonl artifact beside a repro
    episode = next(
        e for e in scope.stall_episodes() if e["node"] == victim
    )
    assert episode["evidence"]
    paths = write_artifacts(scope, str(tmp_path / "out"))
    lines = [
        json.loads(ln)
        for ln in open(paths["jsonl"], encoding="utf-8")
    ]
    episodes = [ln for ln in lines if ln["kind"] == "stall_episode"]
    assert [e["node"] for e in episodes] == [victim]
    assert episodes[0]["evidence"]


# ---------------------------------------------------------------------------
# tier-1: runtime ⊆ static (v6 metrics-conformance cross-check)
# ---------------------------------------------------------------------------


def test_runtime_scrape_series_subset_of_static_metricmap(tmp_path):
    """v6 runtime ⊆ static contract, metrics plane: every series name
    a live per-node ``/metrics`` exposition actually serves must be in
    the static ``--metricmap`` artifact's ``exposed`` set (which
    already expands histograms to their ``_bucket``/``_sum``/``_count``
    series).  A scraped series missing from the map means the
    metrics-conformance scan lost a producer — pinned here against a
    real network, not a fixture."""
    import urllib.request

    from fabric_tpu.devtools.lint import lint_tree
    from fabric_tpu.devtools.netscope import parse_prometheus

    topo = nh.Topology(
        orgs=1, peers_per_org=1, orderers=1, seed=13, ops=True,
    )
    observed: set[str] = set()
    with nh.Network(str(tmp_path / "net"), topo) as net:
        net.start()
        result = nh.run_stream(net, txs=10, settle_timeout_s=120)
        for host, port in net.ops_addrs().values():
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            ) as resp:
                text = resp.read().decode("utf-8")
            observed.update(
                name for name, _labels, _v in parse_prometheus(text)
            )
    assert result["ok"], result

    # non-vacuous: the scrape saw the consensus plane and a histogram
    assert "ledger_blocks_committed_total" in observed, sorted(observed)
    assert any(n.endswith("_bucket") for n in observed), sorted(observed)

    exposed = set(lint_tree().metricmap()["exposed"])
    assert observed <= exposed, (
        "scraped series missing from static metricmap: "
        f"{sorted(observed - exposed)}"
    )


# ---------------------------------------------------------------------------
# netbench --metrics-out (slow: acceptance-shaped seeded campaign)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_netbench_metrics_out_2org_4peer(tmp_path):
    out = tmp_path / "metrics"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "netbench.py"),
         "--orgs", "2", "--peers", "2", "--orderers", "1",
         "--txs", "120", "--seed", "9", "--kills", "1",
         "--metrics-out", str(out),
         "--workdir", str(tmp_path / "work")],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is True
    assert line["stalled_nodes"] == []
    assert line["netscope"]["pass"] is True
    lines = [
        json.loads(ln)
        for ln in open(out / "netscope.jsonl", encoding="utf-8")
    ]
    series = [ln for ln in lines if ln["kind"] == "series"]
    peer_nodes = {
        s["node"] for s in series if s["name"] == "ledger_height"
    }
    # every node of the 2-org × 4-peer (+1 orderer) topology reported
    # a height series
    assert len(peer_nodes) == 5
    events = [ln for ln in lines if ln["kind"] == "event"]
    assert any(e["event"] == "kill" for e in events)
    html = (out / "netscope.html").read_text(encoding="utf-8")
    assert "polyline" in html and "ledger_height" in html
