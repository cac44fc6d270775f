"""Flight-recorder tests (src/repro/obs/recorder.py — DESIGN.md §15):
trace classification, the tail-sampling retention INVARIANT (an
interesting trace is never evicted while a sampled-ok one remains),
deterministic sampling, cost annotation of retained records, JSONL
dumps, and the fault-registry autodump under a chaos battery — every
armed fault must leave a black-box artifact."""
import json

import pytest

from repro import obs
from repro.obs.recorder import FlightRecorder, classify_trace
from repro.obs.trace import Span, Trace
from repro.testing.faults import FAULTS, FaultError


def _tr(name="request", intent="current", wall_ms=5.0, status="ok",
        **attrs):
    tr = Trace(name, intent, attrs=attrs or None)
    tr.wall_ms = tr.root.wall_ms = wall_ms
    tr.root.status = status
    return tr


@pytest.fixture(autouse=True)
def _clean():
    obs.set_enabled(True)
    obs.SLOW_QUERIES.reset()
    obs.FLIGHT_RECORDER.disable()
    obs.FLIGHT_RECORDER.reset()
    FAULTS.reset()
    yield
    obs.FLIGHT_RECORDER.disable()
    obs.FLIGHT_RECORDER.reset()
    obs.SLOW_QUERIES.reset()
    FAULTS.reset()


class TestClassification:
    def test_outcomes(self):
        assert classify_trace(_tr(status="error:ValueError")) == "error"
        assert classify_trace(
            _tr(status="error:DeadlineExceeded")) == "deadline"
        assert classify_trace(_tr(degraded=True)) == "degraded"
        assert classify_trace(_tr(wall_ms=500.0)) == "over_budget"
        assert classify_trace(_tr(wall_ms=5.0)) is None

    def test_over_budget_respects_intent_budgets(self):
        # maintenance gets its 10s default budget, not the global 100ms
        assert classify_trace(
            _tr(name="maint:compact", intent="maintenance",
                wall_ms=500.0)) is None
        assert classify_trace(
            _tr(name="maint:compact", intent="maintenance",
                wall_ms=20_000.0)) == "over_budget"


class TestRetention:
    def test_sampled_evicted_before_any_interesting(self):
        rec = FlightRecorder(capacity=8, sample_rate=1.0)
        rec.enabled = True
        for i in range(4):
            rec.observe_trace(_tr(status="error:ValueError"))
        for i in range(10):
            rec.observe_trace(_tr())       # sampled-ok at rate 1.0
        # 14 observed into capacity 8: only sampled-ok records evicted
        assert rec.evicted == {"sampled": 6, "interesting": 0}
        reasons = [r["reason"] for r in rec.records()]
        assert reasons.count("error") == 4

    def test_error_never_evicted_while_sampled_remain(self):
        rec = FlightRecorder(capacity=8, sample_rate=1.0)
        rec.enabled = True
        rec.observe_trace(_tr(status="error:ValueError"))   # seq 1
        for _ in range(20):        # interleave: ok, error, ok, error...
            rec.observe_trace(_tr())
            rec.observe_trace(_tr(status="error:ValueError"))
        # interesting alone overflows capacity, so the oldest errors DO
        # eventually go — but never while a sampled-ok record remained
        assert rec.summary()["sampled"] == 0
        assert rec.evicted["interesting"] > 0
        assert all(r["reason"] == "error" for r in rec.records())

    def test_seeded_sampling_is_deterministic(self):
        kept = []
        for _ in range(2):
            rec = FlightRecorder(capacity=64, sample_rate=0.3, seed=7)
            rec.enabled = True
            for _ in range(50):
                rec.observe_trace(_tr())
            kept.append([r["seq"] for r in rec.records()])
        assert kept[0] == kept[1]
        assert 0 < len(kept[0]) < 50

    def test_rate_zero_keeps_only_interesting(self):
        rec = FlightRecorder(capacity=64, sample_rate=0.0)
        rec.enabled = True
        for _ in range(10):
            rec.observe_trace(_tr())
        rec.observe_trace(_tr(status="error:ValueError"))
        assert rec.dropped == 10
        assert [r["reason"] for r in rec.records()] == ["error"]

    def test_events_always_interesting(self):
        rec = FlightRecorder(capacity=8, sample_rate=0.0)
        rec.enabled = True
        rec.observe_event("admission_rejected", tenant="acme",
                          detail="queue_full")
        (r,) = rec.records()
        assert r["kind"] == "event"
        assert r["reason"] == "admission_rejected"
        assert r["attrs"]["tenant"] == "acme"

    def test_disabled_recorder_records_nothing(self):
        rec = FlightRecorder()
        rec.observe_trace(_tr(status="error:ValueError"))
        rec.observe_event("admission_rejected")
        assert rec.records() == []


class TestCostAnnotation:
    def _kernel_trace(self, queue_ms=0.0, wait_ms=9.0, wall_ms=10.0):
        tr = _tr(wall_ms=wall_ms, status="error:ValueError")
        if queue_ms:
            tr.root.counters["queue_wait_ms"] = queue_ms
        scan = Span("fused_scan", wall_ms=wait_ms + 0.5)
        scan.children.append(
            Span("kernel:topk_search_q8", wall_ms=0.5,
                 children=[Span("h2d", wall_ms=0.4,
                                counters={"h2d_bytes": 8_388_608})]))
        scan.children.append(Span("device_wait", wall_ms=wait_ms))
        tr.root.children.append(scan)
        return tr

    def test_retained_records_carry_roofline_numbers(self):
        # the verdict rests on the device_wait spans; no host-time
        # roofline (bytes over enqueue time) is added anywhere
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        rec.observe_trace(self._kernel_trace())
        (r,) = rec.records()
        assert r["cost"]["device_wait_ms"] == pytest.approx(9.0)
        assert r["cost"]["device_wait_frac"] == pytest.approx(0.9)
        assert r["cost"]["bound"] == "device-bound"
        k = r["spans"]["children"][0]["children"][0]
        assert k["name"] == "kernel:topk_search_q8"
        assert "counters" not in k
        assert not {"achieved_gbs", "roofline_frac",
                    "best_roofline_frac", "kernel_frac"} & \
            (r["cost"].keys() | k.get("counters", {}).keys())

    def test_unknown_device_kind_gets_no_roofline(self, monkeypatch):
        # annotation reads no device: it runs where JAX cannot
        import jax
        monkeypatch.setattr(jax, "devices", lambda *a: 1 / 0)
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        rec.observe_trace(self._kernel_trace())
        (r,) = rec.records()
        assert r["cost"]["bound"] == "device-bound"
        assert not hasattr(obs.cost, "PEAK_HBM_GBS")

    def test_bound_verdicts(self):
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        rec.observe_trace(self._kernel_trace(queue_ms=6.0))
        rec.observe_trace(self._kernel_trace(wait_ms=2.0))
        rec.observe_trace(self._kernel_trace())
        a, b, c = rec.records()
        assert a["cost"]["bound"] == "queue-bound"
        assert b["cost"]["bound"] == "host-bound"
        assert c["cost"]["bound"] == "device-bound"

    def test_annotation_happens_on_read_not_at_retention(self,
                                                         monkeypatch):
        from repro.obs import recorder
        calls = []
        real = recorder.annotate_costs
        monkeypatch.setattr(recorder, "annotate_costs",
                            lambda d: calls.append(1) or real(d))
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        for _ in range(3):
            rec.observe_trace(self._kernel_trace())
        assert calls == []                  # retention annotates nothing
        assert all("cost" not in r for r in rec._keep)
        recs = rec.records()
        assert len(calls) == 3 and all("cost" in r for r in recs)
        assert all("cost" not in r for r in rec._keep)   # copies only
        rec.dump(reason="manual")
        assert len(calls) == 6
        assert all("cost" in r for r in rec.last_dump[1:])


class TestDumps:
    def test_dump_writes_jsonl(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        rec.observe_trace(_tr(status="error:ValueError"))
        path = str(tmp_path / "box.jsonl")
        recs = rec.dump(path, reason="post_drill")
        assert len(recs) == 1
        lines = [json.loads(x) for x in
                 open(path).read().strip().splitlines()]
        assert lines[0] == {"kind": "dump", "reason": "post_drill",
                            "retained": 1}
        assert lines[1]["reason"] == "error"
        assert rec.dumps == [path]
        assert rec.dump_reasons == ["post_drill"]
        assert rec.last_dump == lines

    def test_dump_dir_numbers_files(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.enabled = True
        rec.dump_dir = str(tmp_path)
        rec.dump(reason="a")
        rec.dump(reason="b")
        assert [p.name for p in sorted(tmp_path.iterdir())] == \
            ["flight-0000.jsonl", "flight-0001.jsonl"]


class TestFaultAutodump:
    def test_chaos_battery_every_fault_leaves_a_dump(self, tmp_path):
        """The acceptance drill: arm a battery of fault points; every
        one that fires must leave a black-box JSONL artifact, and the
        follow-up dump must contain the erroring span tree."""
        obs.FLIGHT_RECORDER.enable(capacity=32, sample_rate=1.0,
                                   dump_dir=str(tmp_path))
        battery = ["lsm:merge:before_manifest", "cold:checkpoint:data",
                   "shard:s01:query"]
        for point in battery:
            FAULTS.arm(point)
            with pytest.raises(FaultError):
                with obs.trace("request", tenant="acme"):
                    FAULTS.check(point)
        reasons = obs.FLIGHT_RECORDER.dump_reasons
        for point in battery:
            assert f"fault:{point}" in reasons          # immediate dump
            assert f"fault:{point}:post" in reasons     # after the trace
        files = sorted(tmp_path.iterdir())
        assert len(files) == len(reasons) == 2 * len(battery)
        # the post dump holds the erroring trace itself
        last = [json.loads(x) for x in
                open(files[-1]).read().strip().splitlines()]
        assert last[0]["reason"] == f"fault:{battery[-1]}:post"
        errors = [r for r in last[1:] if r.get("reason") == "error"]
        assert len(errors) == len(battery)
        assert errors[-1]["spans"]["status"] == "error:FaultError"

    def test_listener_survives_faults_reset(self, tmp_path):
        obs.FLIGHT_RECORDER.enable(capacity=8, sample_rate=0.0,
                                   dump_dir=str(tmp_path))
        FAULTS.reset()          # teardown-style reset must NOT unhook
        FAULTS.arm("x:y:z")
        with pytest.raises(FaultError):
            FAULTS.check("x:y:z")
        assert "fault:x:y:z" in obs.FLIGHT_RECORDER.dump_reasons

    def test_disable_unhooks_listener(self):
        obs.FLIGHT_RECORDER.enable(capacity=8)
        obs.FLIGHT_RECORDER.disable()
        FAULTS.arm("x:y:z")
        with pytest.raises(FaultError):
            FAULTS.check("x:y:z")
        assert obs.FLIGHT_RECORDER.dump_reasons == []

    def test_trace_exit_feeds_singleton_only_when_enabled(self):
        with obs.trace("request"):
            pass
        assert obs.FLIGHT_RECORDER.records() == []
        obs.FLIGHT_RECORDER.enable(capacity=8, sample_rate=1.0)
        with obs.trace("request", tenant="acme"):
            pass
        (r,) = obs.FLIGHT_RECORDER.records()
        assert r["reason"] == "sampled"
        assert r["attrs"]["tenant"] == "acme"
