"""Export-surface tests (src/repro/obs/export.py — DESIGN.md §15):
Prometheus text round-trip, OTLP span-tree round-trip, determinism,
the pull endpoint, and the golden files under tests/golden/ that lock
both exposition formats (CI checks the same fixture without pytest via
``python -m repro.obs.export --check-golden``)."""
import json
import os
import urllib.error
import urllib.request

import pytest

from repro import obs
from repro.obs import MetricsRegistry
from repro.obs.export import (GOLDEN_FILES, ObsHttpServer, _from_otlp_value,
                              golden_fixture, golden_trace,
                              parse_prometheus_text, prometheus_text,
                              trace_from_otlp, trace_to_otlp)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture(autouse=True)
def _clean():
    obs.set_enabled(True)
    obs.SLOW_QUERIES.reset()
    obs.SLO_ENGINE.reset()
    obs.FLIGHT_RECORDER.disable()
    obs.FLIGHT_RECORDER.reset()
    yield
    obs.SLO_ENGINE.reset()
    obs.FLIGHT_RECORDER.disable()
    obs.FLIGHT_RECORDER.reset()


def _registry():
    reg = MetricsRegistry()
    reg.counter("scan_row_reads", source="fused").inc(4096)
    reg.counter("scan_row_reads", tenant="acme").inc(1234)
    reg.gauge("slo_burn_rate", tenant="acme", intent="current",
              window="60s").set(2.625)
    h = reg.histogram("trace_ms", bounds=[1.0, 10.0, 100.0], trace="batch")
    for v in (0.5, 2.0, 5.0, 50.0, 500.0):
        h.observe(v)
    return reg


class TestPrometheusRoundTrip:
    def test_values_survive_serialize_parse(self):
        reg = _registry()
        parsed = parse_prometheus_text(prometheus_text(reg))
        assert parsed["counters"][
            "scan_row_reads{source=fused}"] == 4096
        assert parsed["counters"][
            "scan_row_reads{tenant=acme}"] == 1234
        assert parsed["gauges"][
            "slo_burn_rate{intent=current,tenant=acme,window=60s}"] \
            == 2.625
        h = parsed["histograms"]["trace_ms{trace=batch}"]
        assert h["count"] == 5
        assert h["sum"] == pytest.approx(557.5)
        # buckets are CUMULATIVE per the exposition format
        assert h["buckets"] == {"1.0": 1, "10.0": 3, "100.0": 4,
                                "+Inf": 5}

    def test_label_values_escaped(self):
        reg = MetricsRegistry()
        reg.counter("odd", tag='a"b\\c').inc(1)
        text = prometheus_text(reg)
        assert '\\"' in text and "\\\\" in text
        parsed = parse_prometheus_text(text)
        assert parsed["counters"]['odd{tag=a"b\\c}'] == 1

    def test_float_values_roundtrip_exactly(self):
        reg = MetricsRegistry()
        reg.gauge("g").set(0.1 + 0.2)    # classic repr stress value
        parsed = parse_prometheus_text(prometheus_text(reg))
        assert parsed["gauges"]["g"] == 0.1 + 0.2


class TestOtlpRoundTrip:
    def _trace(self):
        with obs.trace("batch", intent="current", tenant="acme") as root:
            root.add("batch_size", 8)
            root.add("queue_wait_ms", 1.5)
            with obs.span("plan"):
                with obs.span("shard:s00"):
                    with obs.span("kernel:topk_search_q8"):
                        with obs.span("h2d") as h:
                            h.add("h2d_bytes", 8_388_608)
                    with obs.span("device_wait"):
                        pass
                try:
                    with obs.span("shard:s01"):
                        raise RuntimeError("boom")
                except RuntimeError:
                    pass
        return obs.SLOW_QUERIES.slowest.to_dict()

    def test_span_tree_round_trips(self):
        d = self._trace()
        back = trace_from_otlp(trace_to_otlp(d))
        assert back == d        # names, nesting, counters, statuses,
        #                         intent and trace attrs — everything
        #                         to_dict() emits

    def test_deterministic_bytes(self):
        d = self._trace()
        a = json.dumps(trace_to_otlp(d), sort_keys=True)
        b = json.dumps(trace_to_otlp(d), sort_keys=True)
        assert a == b

    def test_sibling_times_packed_end_to_end(self):
        d = {"name": "r", "intent": None, "wall_ms": 3.0,
             "spans": {"name": "r", "wall_ms": 3.0, "children": [
                 {"name": "a", "wall_ms": 1.0},
                 {"name": "b", "wall_ms": 2.0}]}}
        spans = trace_to_otlp(d)["resourceSpans"][0]["scopeSpans"][0][
            "spans"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["r"]["startTimeUnixNano"] == "0"
        assert by_name["a"]["startTimeUnixNano"] == "0"
        assert by_name["b"]["startTimeUnixNano"] == \
            by_name["a"]["endTimeUnixNano"] == "1000000"
        assert by_name["a"]["parentSpanId"] == by_name["r"]["spanId"]

    def test_error_status_carried(self):
        d = self._trace()
        otlp = trace_to_otlp(d)
        spans = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
        (bad,) = [s for s in spans if s["name"] == "shard:s01"]
        assert bad["status"] == {"code": "STATUS_CODE_ERROR",
                                 "message": "error:RuntimeError"}


class TestHttpEndpoint:
    def _get(self, server, path):
        with urllib.request.urlopen(server.url(path), timeout=5) as r:
            return r.status, r.headers.get("Content-Type"), \
                r.read().decode()

    def test_all_routes(self):
        obs.SLO_ENGINE.declare("acme", "current", latency_ms=50.0,
                               target=0.99)
        obs.FLIGHT_RECORDER.enable(capacity=8, sample_rate=1.0)
        with obs.trace("request", intent="current", tenant="acme"):
            pass
        server = ObsHttpServer(
            health_fn=lambda: {"ok": True, "shards": 2}).start()
        try:
            code, _, body = self._get(server, "/slo")
            slo = json.loads(body)
            assert code == 200 and slo["declared"] == 1
            assert slo["slos"][0]["tenant"] == "acme"
            # evaluating /slo published the burn gauges; the /metrics
            # scrape that follows (real scrape order) sees them
            code, ctype, body = self._get(server, "/metrics")
            assert code == 200 and ctype.startswith("text/plain")
            parsed = parse_prometheus_text(body)
            assert any(k.startswith("slo_burn_rate{")
                       for k in parsed["gauges"])
            code, _, body = self._get(server, "/traces")
            traces = json.loads(body)
            assert code == 200 and traces["summary"]["retained"] == 1
            assert traces["records"][0]["attrs"]["tenant"] == "acme"
            code, _, body = self._get(server, "/health")
            assert code == 200 and json.loads(body)["shards"] == 2
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(server, "/nope")
            assert ei.value.code == 404
        finally:
            server.stop()
        # cleanup for the histogram this test adds is unnecessary: the
        # process-wide registry tolerates extra labeled series


class TestGoldenFiles:
    """The same fixture CI checks via
    ``python -m repro.obs.export --check-golden tests/golden`` —
    a mismatch means the exposition format or the cost math drifted."""

    def test_goldens_exist_and_match(self):
        prom, otlp = golden_fixture()
        rendered = dict(zip(GOLDEN_FILES, (prom, otlp)))
        for fname, body in rendered.items():
            path = os.path.join(GOLDEN_DIR, fname)
            with open(path) as f:
                assert f.read() == body, \
                    f"{fname} drifted — regenerate with " \
                    f"python -m repro.obs.export --write-golden tests/golden"

    def test_fixture_locks_cost_math(self):
        _, otlp = golden_fixture()
        doc = json.loads(otlp)
        spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
        (h,) = [s for s in spans if s["name"] == "h2d"]
        attrs = {a["key"]: a["value"] for a in h["attributes"]}
        assert _from_otlp_value(attrs["h2d_bytes"]) == 8_388_608
        (k,) = [s for s in spans if s["name"] == "kernel:topk_search_q8"]
        assert k["attributes"] == []      # no host-time roofline numbers
        # 7 ms of the 12.5 ms batch blocked on the device
        cost = golden_trace()["cost"]
        assert cost["device_wait_ms"] == 7.0
        assert cost["device_wait_frac"] == pytest.approx(0.56)
        assert cost["bound"] == "device-bound"
