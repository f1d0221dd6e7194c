"""Operations endpoint, metrics SPI, and logging tests (reference
core/operations/system_test.go, common/metrics, common/flogging)."""

import json
import logging
import urllib.request

import pytest

from fabric_tpu.common import flogging
from fabric_tpu.common.metrics import (
    CounterOpts,
    GaugeOpts,
    HistogramOpts,
    PrometheusProvider,
    StatsdProvider,
)
from fabric_tpu.common.operations import System


def _get(url):
    with urllib.request.urlopen(url, timeout=3) as r:
        return r.status, r.read()


class TestMetrics:
    def test_prometheus_counter_gauge_histogram(self):
        p = PrometheusProvider()
        c = p.new_counter(
            CounterOpts(namespace="ledger", name="commits",
                        help="Total commits.")
        )
        c.with_labels("channel", "ch1").add()
        c.with_labels("channel", "ch1").add(2)
        c.with_labels("channel", "ch2").add()
        g = p.new_gauge(GaugeOpts(namespace="gossip", name="peers"))
        g.set(5)
        h = p.new_histogram(
            HistogramOpts(namespace="ledger", name="commit_seconds",
                          buckets=(0.1, 1.0))
        )
        h.with_labels("channel", "ch1").observe(0.05)
        h.with_labels("channel", "ch1").observe(0.5)
        text = p.registry.expose()
        assert 'ledger_commits{channel="ch1"} 3' in text
        assert 'ledger_commits{channel="ch2"} 1' in text
        assert "gossip_peers 5" in text
        assert (
            'ledger_commit_seconds_bucket{channel="ch1",le="0.1"} 1' in text
        )
        assert 'ledger_commit_seconds_count{channel="ch1"} 2' in text
        assert "# TYPE ledger_commits counter" in text

    def test_statsd_lines(self):
        lines = []
        p = StatsdProvider(lines.append, prefix="peer")
        p.new_counter(CounterOpts(name="tx_count")).add()
        p.new_gauge(GaugeOpts(name="height")).set(7)
        p.new_histogram(HistogramOpts(name="lat")).observe(12.5)
        assert lines == [
            "peer.tx.count:1|c", "peer.height:7|g", "peer.lat:12.5|ms"
        ]


class TestFlogging:
    def test_spec_parsing_and_prefix_match(self):
        default, overrides = flogging.parse_spec(
            "gossip=debug:ledger,orderer=error:warning"
        )
        assert default == logging.WARNING
        assert overrides == {
            "gossip": logging.DEBUG,
            "ledger": logging.ERROR,
            "orderer": logging.ERROR,
        }
        lv = flogging.LoggerLevels()
        lv.activate_spec("gossip=debug:gossip.comm=error:info")
        assert lv.level_for("gossip.pull") == logging.DEBUG
        assert lv.level_for("gossip.comm") == logging.ERROR
        assert lv.level_for("ledger") == logging.INFO

    def test_invalid_spec(self):
        with pytest.raises(flogging.LogSpecError):
            flogging.parse_spec("gossip=nope")

    def test_observer_counts(self):
        p = PrometheusProvider()
        counter = p.new_counter(
            CounterOpts(namespace="logging", name="entries_checked")
        )
        reg = flogging.global_registry()
        reg.set_observer_counter(counter)
        try:
            flogging.activate_spec("info")
            log = flogging.must_get_logger("testobs")
            log.info("hello")
            log.debug("filtered out — also not counted")
            text = p.registry.expose()
            assert 'logging_entries_checked{level="info"} 1' in text
        finally:
            reg.observer = None


class TestOperationsServer:
    @pytest.fixture()
    def system(self):
        s = System(("127.0.0.1", 0))
        s.start()
        yield s
        s.stop()

    def test_endpoints(self, system):
        host, port = system.addr
        base = f"http://{host}:{port}"
        system.metrics_provider.new_counter(
            CounterOpts(name="ops_test_total")
        ).add(4)
        status, body = _get(base + "/metrics")
        assert status == 200 and b"ops_test_total 4" in body
        status, body = _get(base + "/version")
        assert status == 200 and json.loads(body)["Version"]
        status, body = _get(base + "/healthz")
        assert status == 200 and json.loads(body)["status"] == "OK"

        # failing checker flips /healthz to 503
        system.register_checker("statedb", lambda: False)
        req = urllib.request.Request(base + "/healthz")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=3)
        assert exc.value.code == 503
        assert "statedb" in json.loads(exc.value.read())["failed_checks"]

    def test_healthz_detail_mode(self, system):
        """ISSUE 12 satellite: ?detail=1 lists every checker with its
        name, pass/fail status, and a persistent last_error — the
        netscope health timeline's per-checker input."""
        host, port = system.addr
        base = f"http://{host}:{port}"
        flaky = {"fail": True}

        def flaky_check():
            if flaky["fail"]:
                raise RuntimeError("db unreachable")
            return True

        system.register_checker("statedb", flaky_check)
        system.register_checker("always", lambda: True)

        req = urllib.request.Request(base + "/healthz?detail=1")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=3)
        assert exc.value.code == 503
        body = json.loads(exc.value.read())
        assert body["status"] == "Service Unavailable"
        assert body["failed_checks"] == ["statedb: db unreachable"]
        checks = {c["component"]: c for c in body["checks"]}
        assert checks["statedb"]["status"] == "failed"
        assert checks["statedb"]["last_error"] == "db unreachable"
        assert checks["always"] == {
            "component": "always", "status": "OK", "last_error": None,
        }

        # recovery: healthy again, but last_error persists in detail
        flaky["fail"] = False
        status, raw = _get(base + "/healthz?detail=1")
        assert status == 200
        body = json.loads(raw)
        assert body["status"] == "OK"
        checks = {c["component"]: c for c in body["checks"]}
        assert checks["statedb"]["status"] == "OK"
        assert checks["statedb"]["last_error"] == "db unreachable"
        # plain mode keeps the reference body shape (no checks key)
        status, raw = _get(base + "/healthz")
        assert status == 200 and "checks" not in json.loads(raw)

    def test_workpool_saturation_checker(self, monkeypatch):
        from fabric_tpu.common import workpool

        check = workpool.health_checker()
        # no pool ever created: healthy, and the probe must not spin
        # one up
        assert check() is True

        class _FakeQueue:
            def __init__(self, n):
                self._n = n

            def qsize(self):
                return self._n

        class _FakePool:
            _max_workers = 2
            _work_queue = _FakeQueue(3)

        monkeypatch.setattr(workpool, "_pool", _FakePool())
        monkeypatch.setattr(workpool, "_in_flight", 5)
        with pytest.raises(RuntimeError, match="saturated"):
            check()
        # full utilization with an empty queue is NOT unhealthy
        _FakePool._work_queue = _FakeQueue(0)
        monkeypatch.setattr(workpool, "_in_flight", 2)
        assert check() is True

    def test_tpu_breaker_checker(self):
        from fabric_tpu.csp.tpu import provider as tpuprov

        class _Stub:
            class _breaker:
                open = False
                trips = 0

        check = tpuprov.TPUCSP.health_checker(_Stub())
        assert check() is True
        _Stub._breaker.open = True
        _Stub._breaker.trips = 2
        with pytest.raises(RuntimeError, match="breaker open"):
            check()

    def test_logspec_roundtrip(self, system):
        host, port = system.addr
        base = f"http://{host}:{port}"
        req = urllib.request.Request(
            base + "/logspec",
            data=json.dumps({"spec": "gossip=debug:info"}).encode(),
            method="PUT",
        )
        with urllib.request.urlopen(req, timeout=3) as r:
            assert r.status == 204
        status, body = _get(base + "/logspec")
        assert json.loads(body)["spec"] == "gossip=debug:info"
        # invalid spec -> 400
        req = urllib.request.Request(
            base + "/logspec",
            data=json.dumps({"spec": "x=bogus"}).encode(),
            method="PUT",
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=3)
        assert exc.value.code == 400
