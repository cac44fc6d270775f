"""Observability layer tests (src/repro/obs — DESIGN.md §12):
hierarchical tracing (nesting, exception safety, the zero-allocation
no-op fast path), the metrics registry (labeled series, histogram
quantiles validated against numpy percentiles), the slow-query ring,
the batcher's registry-backed stats shim, the centralized
scan-accounting helper, and the fabric-wide e2e trace: one
``query_window_batch`` through a 4-shard ShardFabric produces one span
tree covering batcher -> planner -> every shard -> kernel dispatch
with per-shard rows_scanned summing to the planner total."""
import tempfile
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import (Histogram, MetricsRegistry, SlowQueryLog,
                       geometric_bounds)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test sees a quiet slow-query log and enabled tracing (the
    registry is process-wide by design; tests use private registries
    or labeled series, so it is left alone)."""
    obs.set_enabled(True)
    obs.SLOW_QUERIES.reset()
    obs.SLOW_QUERIES.configure(budget_ms=100.0, capacity=32)
    yield
    obs.set_enabled(True)
    obs.SLOW_QUERIES.reset()
    obs.SLOW_QUERIES.configure(budget_ms=100.0, capacity=32)


class TestTrace:
    def test_span_nesting_builds_the_tree(self):
        with obs.trace("batch") as root:
            with obs.span("plan") as plan:
                for s in ("s00", "s01"):
                    with obs.span(f"shard:{s}") as sh:
                        sh.add("rows_scanned", 10)
                with obs.span("merge") as m:
                    m.add("candidates", 7)
            plan.add("queries", 2)
        assert root.name == "batch"
        assert [c.name for c in root.children] == ["plan"]
        assert [c.name for c in plan.children] == \
            ["shard:s00", "shard:s01", "merge"]
        assert root.total("rows_scanned") == 20
        assert plan.counters["queries"] == 2
        assert all(c.wall_ms >= 0 for c in plan.children)

    def test_add_lands_on_the_innermost_open_span(self):
        with obs.trace("t") as root:
            obs.add("x", 1)
            with obs.span("inner") as sp:
                obs.add("x", 5)
            obs.add("x", 2)
        assert root.counters["x"] == 3
        assert sp.counters["x"] == 5
        assert root.total("x") == 8

    def test_exception_marks_span_and_unwinds_stack(self):
        with pytest.raises(ValueError):
            with obs.trace("t") as root:
                with pytest.raises(KeyError):
                    with obs.span("a"):
                        raise KeyError("inner")
                # stack unwound: this span is a SIBLING of a, not a child
                with obs.span("b"):
                    pass
                raise ValueError("outer")
        assert [c.name for c in root.children] == ["a", "b"]
        assert root.children[0].status == "error:KeyError"
        assert root.children[1].status == "ok"
        assert root.status == "error:ValueError"
        assert obs.current_trace() is None      # contextvar reset

    def test_trace_feeds_slowlog_and_registry(self):
        obs.SLOW_QUERIES.configure(budget_ms=0.0)
        reg = obs.REGISTRY
        before = reg.histogram("trace_ms", trace="t_feed").count
        with obs.trace("t_feed"):
            pass
        assert reg.histogram("trace_ms", trace="t_feed").count \
            == before + 1
        assert obs.SLOW_QUERIES.observed == 1
        assert len(obs.SLOW_QUERIES.traces()) == 1

    def test_nested_trace_degrades_to_span(self):
        with obs.trace("outer") as root:
            with obs.trace("inner"):
                with obs.span("leaf"):
                    pass
        assert obs.SLOW_QUERIES.observed == 1   # ONE trace finished
        assert [c.name for c in root.children] == ["inner"]
        assert [c.name for c in root.children[0].children] == ["leaf"]

    def test_render_and_to_dict(self):
        obs.SLOW_QUERIES.configure(budget_ms=0.0)
        with obs.trace("t", intent="current") as root:
            with obs.span("scan") as sp:
                sp.add("rows_scanned", 42)
        tr = obs.SLOW_QUERIES.traces()[0]
        assert tr.intent == "current"
        text = tr.render()
        assert "scan" in text and "rows_scanned=42" in text
        d = tr.to_dict()
        assert d["spans"]["children"][0]["counters"]["rows_scanned"] == 42
        assert root.find("scan") == [sp]
        assert root.find_prefix("sc") == [sp]


class TestNoopFastPath:
    def test_span_without_trace_is_the_shared_singleton(self):
        assert obs.current_trace() is None
        assert obs.span("anything") is obs.NOOP_SPAN
        assert obs.span("other") is obs.NOOP_SPAN

    def test_disabled_tracing_is_noop_even_for_trace(self):
        obs.set_enabled(False)
        assert obs.trace("t") is obs.NOOP_SPAN
        with obs.trace("t") as sp:
            sp.add("x", 1)
            assert obs.span("y") is obs.NOOP_SPAN
        assert obs.SLOW_QUERIES.observed == 0

    def test_noop_path_allocates_nothing(self):
        def probe(n):
            for _ in range(n):
                with obs.span("fused_scan") as sp:
                    sp.add("rows_scanned", 128)
                obs.add("bytes_streamed", 4096)

        probe(100)                               # warm caches
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        probe(10_000)
        grown = tracemalloc.get_traced_memory()[0] - base
        tracemalloc.stop()
        # zero per-iteration allocation; allow a tiny constant slack
        assert grown < 512, f"no-op path allocated {grown} bytes"

    def test_scan_row_reads_counts_without_a_trace(self):
        reg = obs.REGISTRY
        c = reg.counter("scan_row_reads", source="test_noop")
        v0 = c.value
        assert obs.scan_row_reads(100, 4, per_query=False,
                                  source="test_noop") == 100
        assert obs.scan_row_reads(100, 4, per_query=True,
                                  source="test_noop") == 400
        assert c.value == v0 + 500


class TestMetrics:
    def test_counter_gauge_series_by_label(self):
        reg = MetricsRegistry()
        reg.counter("reads", tier="hot").inc()
        reg.counter("reads", tier="hot").inc(4)
        reg.counter("reads", tier="cold").inc()
        reg.gauge("depth", shard="s00").set(7)
        snap = reg.snapshot()
        assert snap["counters"]["reads{tier=hot}"] == 5
        assert snap["counters"]["reads{tier=cold}"] == 1
        assert snap["gauges"]["depth{shard=s00}"] == 7
        assert "reads{tier=hot}" in reg.to_json()
        reg.reset()
        assert reg.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}

    def test_label_key_is_order_independent(self):
        reg = MetricsRegistry()
        a = reg.counter("m", tier="hot", shard="s01")
        b = reg.counter("m", shard="s01", tier="hot")
        assert a is b

    def test_histogram_quantiles_vs_numpy(self):
        rng = np.random.default_rng(7)
        # latency-shaped data spanning several bucket decades
        samples = np.exp(rng.normal(1.5, 1.0, 20_000))
        h = Histogram()
        for v in samples:
            h.observe(float(v))
        for q in (0.5, 0.9, 0.99, 0.999):
            got = h.quantile(q)
            want = float(np.percentile(samples, q * 100))
            # bucket factor 1.15 bounds the relative error at ~7.5%
            assert abs(got - want) / want < 0.08, (q, got, want)
        s = h.summary()
        assert s["count"] == len(samples)
        assert h.min == pytest.approx(samples.min())
        assert h.max == pytest.approx(samples.max())
        assert h.mean == pytest.approx(samples.mean(), rel=1e-6)
        assert set(s) == {"count", "sum", "mean", "min", "max",
                          "p50", "p99", "p999"}

    def test_histogram_without_storing_samples(self):
        h = Histogram()
        for v in range(100_000):
            h.observe(v * 0.01)
        # fixed memory: bucket counts only, no sample list anywhere
        assert not hasattr(h, "samples")
        assert len(h.counts) == len(h.bounds) + 1
        assert h.count == 100_000

    def test_histogram_edge_cases(self):
        h = Histogram()
        assert h.quantile(0.5) is None
        assert h.summary() == {"count": 0}
        h.observe(5.0)
        assert h.quantile(0.0) == pytest.approx(5.0)
        assert h.quantile(1.0) == pytest.approx(5.0)
        h2 = Histogram()
        h2.observe(10.0 ** 9)                    # beyond the last bound
        assert h2.quantile(0.5) == pytest.approx(10.0 ** 9)

    def test_geometric_bounds_cover_the_latency_range(self):
        b = geometric_bounds()
        assert b[0] <= 1e-3 and b[-1] >= 1e5
        ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
        assert all(abs(r - 1.15) < 1e-9 for r in ratios)


class TestSlowQueryLog:
    def _mk_trace(self, name, wall_ms):
        from repro.obs.trace import Trace
        tr = Trace(name)
        tr.wall_ms = tr.root.wall_ms = wall_ms
        return tr

    def test_ring_retains_only_over_budget_and_evicts(self):
        log = SlowQueryLog(budget_ms=10.0, capacity=4)
        for i in range(10):
            log.observe(self._mk_trace(f"t{i}", 5.0 if i % 2 else 20.0))
        assert log.observed == 10
        kept = log.traces()
        assert len(kept) == 4                    # ring evicted the rest
        assert [t.name for t in kept] == ["t2", "t4", "t6", "t8"]
        assert log.slowest.wall_ms == 20.0
        s = log.summary()
        assert s["over_budget_retained"] == 4
        assert s["observed"] == 10

    def test_slowest_is_tracked_even_under_budget(self):
        log = SlowQueryLog(budget_ms=100.0, capacity=4)
        log.observe(self._mk_trace("fast", 1.0))
        log.observe(self._mk_trace("faster", 0.5))
        assert log.traces() == []
        assert log.slowest.name == "fast"

    def test_configure_shrink_keeps_newest(self):
        log = SlowQueryLog(budget_ms=0.0, capacity=8)
        for i in range(6):
            log.observe(self._mk_trace(f"t{i}", 1.0))
        log.configure(capacity=2)
        assert [t.name for t in log.traces()] == ["t4", "t5"]
        log.configure(budget_ms=50.0)
        assert log.budget_ms == 50.0


class TestBatcherMetrics:
    def test_stats_shim_matches_registry_series(self):
        from repro.serve.batcher import Batcher
        b = Batcher(lambda ps: [p * 2 for p in ps], max_batch=4)
        for i in range(6):
            b.submit(i)
        b.drain()
        assert b.stats == {"batches": 2, "requests": 6, "hedges": 0,
                           "failed_batches": 0, "rejected": 0,
                           "deadline_expired": 0, "mean_batch_size": 3.0}
        snap = obs.REGISTRY.snapshot()
        key = f"batcher_requests{{batcher={b.label}}}"
        assert snap["counters"][key] == 6

    def test_queue_depth_and_time_in_queue_histograms(self):
        from repro.serve.batcher import Batcher
        b = Batcher(lambda ps: list(ps), max_batch=8)
        for i in range(5):
            b.submit(i)
        b.drain()
        depth = obs.REGISTRY.histogram("batcher_queue_depth",
                                       batcher=b.label)
        wait = obs.REGISTRY.histogram("batcher_time_in_queue_ms",
                                      batcher=b.label)
        assert depth.count == 1 and depth.max == 5.0
        assert wait.count == 5 and wait.min >= 0.0

    def test_batch_opens_one_trace(self):
        from repro.serve.batcher import Batcher
        obs.SLOW_QUERIES.configure(budget_ms=0.0)
        b = Batcher(lambda ps: list(ps), max_batch=8,
                    bucket_fn=lambda p: p % 2)
        for i in range(4):
            b.submit(i)
        b.drain()
        traces = obs.SLOW_QUERIES.traces()
        assert len(traces) == 2                  # one per bucket batch
        assert {t.intent for t in traces} == {"0", "1"}
        assert all(t.root.counters["batch_size"] == 2 for t in traces)


class TestScanAccountingConvention:
    def test_helper_is_the_single_convention_point(self):
        # fused/solo: once per batch, independent of nq
        assert obs.scan_row_reads(1000, 8, per_query=False,
                                  source="t1") == 1000
        # per-query sources: avg per query x nq
        assert obs.scan_row_reads(250, 8, per_query=True,
                                  source="t1") == 2000

    def test_index_paths_report_through_the_helper(self):
        from repro.core.types import ChunkRecord
        from repro.index.lsm import SegmentedIndex
        rng = np.random.default_rng(3)
        reg = obs.REGISTRY
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(8, mem_capacity=64, root=root,
                                 ivf_min_rows=128)
            idx.insert([ChunkRecord(
                chunk_id=f"c{i}", doc_id=f"d{i}", position=0,
                valid_from=1 + i, text=f"row {i}",
                embedding=rng.normal(size=8).astype(np.float32))
                for i in range(300)])
            fused0 = reg.counter("scan_row_reads", source="fused").value
            ivf0 = reg.counter("scan_row_reads", source="ivf").value
            solo0 = reg.counter("scan_row_reads", source="solo").value
            s0 = idx._scan_scanned
            idx.search(rng.normal(size=(2, 8)).astype(np.float32), k=5)
            moved = (
                (reg.counter("scan_row_reads", source="fused").value
                 - fused0)
                + (reg.counter("scan_row_reads", source="ivf").value
                   - ivf0)
                + (reg.counter("scan_row_reads", source="solo").value
                   - solo0))
            # the index's own accounting is EXACTLY the helper's sum
            assert moved == idx._scan_scanned - s0 > 0


class TestFabricEndToEnd:
    def test_window_batch_trace_covers_every_layer(self):
        obs.SLOW_QUERIES.configure(budget_ms=0.0)
        with tempfile.TemporaryDirectory() as root:
            from repro.shard.shard import ShardFabric
            fab = ShardFabric(root, n_shards=4, dim=32, replicas=2)
            for i in range(8):
                fab.ingest(f"doc{i}", f"alpha topic{i} first text. " * 3,
                           ts=1000 + i)
            for i in range(8):
                fab.ingest(f"doc{i}", f"alpha topic{i} revised text. " * 3,
                           ts=2000 + i)
            obs.SLOW_QUERIES.reset()
            b = fab.query_batcher(k=3)
            b.submit(("alpha topic1", None, (1500, 2500)))
            b.submit(("alpha topic2", None, (1500, 2500)))
            b.drain()
            traces = obs.SLOW_QUERIES.traces()
            assert len(traces) == 1              # one batch, one trace
            tr = traces[0]
            assert tr.root.name == "batch"
            assert "comparative" in tr.intent
            plan = tr.root.find("plan")
            assert len(plan) == 1
            shard_spans = plan[0].find_prefix("shard:")
            assert {s.name for s in shard_spans} == \
                {"shard:s00", "shard:s01", "shard:s02", "shard:s03"}
            per_shard = [s.total("rows_scanned") for s in shard_spans]
            assert all(r > 0 for r in per_shard)
            # per-shard subtree totals sum to the planner/root total
            assert sum(per_shard) == plan[0].total("rows_scanned") \
                == tr.root.total("rows_scanned")
            # kernel dispatches appear; they time the argument copy and
            # the enqueue, so they carry no scan counters, and the wait
            # for their outputs is a device_wait sibling
            kernels = tr.root.find_prefix("kernel:")
            assert kernels
            assert not any({"rows", "bytes_streamed"} & sp.counters.keys()
                           for sp in kernels)
            fused = tr.root.find("fused_temporal")
            assert fused
            for f in fused:
                names = [c.name for c in f.children]
                assert names.index("device_wait") > \
                    max(i for i, n in enumerate(names)
                        if n.startswith("kernel:"))
            assert tr.root.find("merge")
            # health(): one call returns topology + metrics + slowlog
            h = fab.health()
            assert h["planner"]["gathers"] == 1
            assert any(k.startswith("query_latency_ms")
                       for k in h["metrics"]["histograms"])
            assert h["slow_queries"]["observed"] == 1

    def test_trace_overhead_smoke(self):
        """The no-op fast path must not measurably slow an uninstru-
        mented caller (full gate lives in benchmarks/obs_overhead)."""
        with tempfile.TemporaryDirectory() as root:
            from repro.core.types import ChunkRecord
            from repro.index.lsm import SegmentedIndex
            rng = np.random.default_rng(0)
            idx = SegmentedIndex(16, mem_capacity=2048, root=root)
            idx.insert([ChunkRecord(
                chunk_id=f"c{i}", doc_id=f"d{i}", position=0,
                valid_from=1 + i, text="t",
                embedding=rng.normal(size=16).astype(np.float32))
                for i in range(512)])
            q = rng.normal(size=(4, 16)).astype(np.float32)
            r_noop = idx.search(q, k=5)
            with obs.trace("t"):
                r_traced = idx.search(q, k=5)
            # tracing never changes results
            assert [[x.chunk_id for x in row] for row in r_noop] == \
                [[x.chunk_id for x in row] for row in r_traced]


class TestThreadSafety:
    """Serving threads + maintenance workers hammer the same series
    concurrently; totals must be exact (DESIGN.md §13)."""

    def test_counter_hammer_exact_total(self):
        import threading
        reg = MetricsRegistry()
        c = reg.counter("hits")
        N, M = 8, 2000

        def inc():
            for _ in range(M):
                c.inc()

        ts = [threading.Thread(target=inc) for _ in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == N * M

    def test_histogram_hammer_exact_count_and_sum(self):
        import threading
        h = Histogram()
        N, M = 8, 1000

        def observe():
            for i in range(M):
                h.observe(1.0)

        ts = [threading.Thread(target=observe) for _ in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == N * M
        assert abs(h.sum - N * M) < 1e-6
        assert h.summary()["p50"] is not None

    def test_registry_get_or_create_single_instance_under_race(self):
        import threading
        reg = MetricsRegistry()
        got = []
        barrier = threading.Barrier(8)

        def get():
            barrier.wait()
            got.append(reg.counter("one", tier="hot"))

        ts = [threading.Thread(target=get) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(g is got[0] for g in got)

    def test_slowlog_hammer_observed_exact(self):
        import threading
        log = SlowQueryLog(budget_ms=0.0, capacity=16)

        class T:
            name = "t"
            intent = None
            wall_ms = 1.0

        def observe():
            for _ in range(500):
                log.observe(T())

        ts = [threading.Thread(target=observe) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert log.observed == 3000
        assert len(log.traces()) == 16


class TestSubtrace:
    def test_worker_thread_spans_graft_into_parent(self):
        import threading
        roots = {}

        def worker(name):
            with obs.subtrace(name) as sroot:
                with obs.span("inner"):
                    obs.add("rows", 7)
            roots[name] = sroot

        with obs.trace("parent") as proot:
            with obs.span("plan") as plan_sp:
                ts = [threading.Thread(target=worker, args=(f"shard:s{i}",))
                      for i in range(3)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                for name in sorted(roots):
                    plan_sp.children.append(roots[name])
        plan = proot.find("plan")[0]
        assert len(plan.children) == 3
        for child in plan.children:
            assert child.name.startswith("shard:")
            assert child.wall_ms >= 0.0
            assert child.total("rows") == 7

    def test_subtrace_does_not_feed_registry_or_slowlog(self):
        obs.REGISTRY.reset()
        obs.SLOW_QUERIES.reset()
        with obs.subtrace("detached"):
            with obs.span("x"):
                pass
        assert obs.SLOW_QUERIES.observed == 0
        snap = obs.REGISTRY.snapshot()
        assert not any(k.startswith("trace_ms") for k in snap["counters"])
        assert not any(k.startswith("trace_ms")
                       for k in snap["histograms"])

    def test_subtrace_noop_when_disabled(self):
        obs.set_enabled(False)
        try:
            assert obs.subtrace("x") is obs.NOOP_SPAN
        finally:
            obs.set_enabled(True)


class TestGaugeAndSnapshots:
    """PR 9 satellites: the locked Gauge (inc is read-modify-write) and
    the snapshot/delta primitive the SLO engine's windows ride on."""

    def test_gauge_set_and_inc(self):
        reg = MetricsRegistry()
        g = reg.gauge("queue_depth", shard="s00")
        g.set(3.0)
        g.inc(2.0)
        g.inc(-1.0)
        assert g.value == 4.0
        assert reg.gauge("queue_depth", shard="s00") is g

    def test_gauge_inc_hammer_exact_total(self):
        import threading
        reg = MetricsRegistry()
        g = reg.gauge("hammer")
        n_threads, per = 8, 5_000

        def worker():
            for _ in range(per):
                g.inc(1.0)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # a lock-free read-modify-write would drop updates here
        assert g.value == n_threads * per

    def test_snapshot_delta_isolates_new_traffic(self):
        h = Histogram(bounds=[1.0, 10.0, 100.0])
        for v in (0.5, 5.0):
            h.observe(v)
        base = h.snapshot_at()
        for v in (5.0, 50.0, 500.0):
            h.observe(v)
        d = h.delta(base)
        assert d.count == 3
        assert d.sum == pytest.approx(555.0)
        assert d.counts == (0, 1, 1, 1)
        # the live histogram is untouched
        assert h.count == 5

    def test_delta_degrades_when_prev_is_ahead(self):
        # registry reset underneath: prev has MORE than current
        h = Histogram(bounds=[1.0, 10.0])
        h.observe(5.0)
        h.observe(5.0)
        stale = h.snapshot_at()
        h2 = Histogram(bounds=[1.0, 10.0])
        h2.observe(5.0)
        d = h2.delta(stale)
        assert d.count == 1       # current state, not negative counts

    def test_count_le_interpolates_crossing_bucket(self):
        h = Histogram(bounds=[0.0, 10.0, 20.0])
        for _ in range(10):
            h.observe(5.0)        # all land in (0, 10]
        s = h.snapshot_at()
        assert s.count_le(10.0) == pytest.approx(10.0)
        assert s.count_le(5.0) == pytest.approx(5.0)   # half the bucket
        assert s.count_le(0.0) == pytest.approx(0.0)
        assert s.fraction_over(5.0) == pytest.approx(0.5)
        assert s.fraction_over(1e9) == 0.0

    def test_count_le_never_interpolates_overflow(self):
        h = Histogram(bounds=[1.0, 10.0])
        h.observe(500.0)          # overflow bucket
        s = h.snapshot_at()
        assert s.count_le(10.0) == 0.0
        assert s.fraction_over(10.0) == 1.0

    def test_parse_series_key_round_trip(self):
        from repro.obs import parse_series_key
        assert parse_series_key("plain") == ("plain", {})
        assert parse_series_key("m{a=1,b=x}") == ("m", {"a": "1",
                                                        "b": "x"})
        reg = MetricsRegistry()
        reg.counter("m", b="x", a="1").inc(1)
        ((key, _),), _, _ = reg.export_state()
        assert parse_series_key(key) == ("m", {"a": "1", "b": "x"})


class TestIntentBudgets:
    """Slow-query budgets are per-intent (DESIGN.md §15): maintenance
    jobs get a deliberately high default so compactions don't evict
    real serving outliers."""

    def _tr(self, intent, wall_ms, name="request"):
        from repro.obs.trace import Trace
        tr = Trace(name, intent)
        tr.wall_ms = tr.root.wall_ms = wall_ms
        return tr

    def test_maintenance_default_budget(self):
        assert obs.SLOW_QUERIES.budget_for("maintenance") == 10_000.0
        assert obs.SLOW_QUERIES.budget_for("current") == 100.0
        assert obs.SLOW_QUERIES.budget_for(None) == 100.0

    def test_token_matching_against_rendered_intents(self):
        obs.SLOW_QUERIES.configure(intent_budgets={"at": 2000.0})
        bucket = "(TemporalIntent(mode='at', at=5000), None)"
        assert obs.SLOW_QUERIES.budget_for(bucket) == 2000.0
        assert obs.SLOW_QUERIES.budget_for("comparative") == 100.0

    def test_per_intent_retention(self):
        # 500ms maintenance: under ITS budget; 500ms serving: over
        obs.SLOW_QUERIES.observe(self._tr("maintenance", 500.0,
                                          name="maint:compact"))
        obs.SLOW_QUERIES.observe(self._tr("current", 500.0))
        retained = obs.SLOW_QUERIES.traces()
        assert [t.intent for t in retained] == ["current"]
        # the slowest tracker still sees everything
        assert obs.SLOW_QUERIES.observed == 2

    def test_configure_merges_and_none_removes(self):
        obs.SLOW_QUERIES.configure(intent_budgets={"at": 2000.0})
        obs.SLOW_QUERIES.configure(intent_budgets={"window": 1500.0})
        got = obs.SLOW_QUERIES.summary()["intent_budgets"]
        assert got == {"maintenance": 10_000.0, "at": 2000.0,
                       "window": 1500.0}
        obs.SLOW_QUERIES.configure(intent_budgets={"maintenance": None})
        assert obs.SLOW_QUERIES.budget_for("maintenance") == 100.0

    def test_maintenance_jobs_run_traced(self):
        from repro.serve.maintenance import MaintenanceWorker
        worker = MaintenanceWorker().start()
        try:
            assert worker.submit("compact", lambda: None)
            assert worker.drain(timeout=5.0)
        finally:
            worker.stop()
        tr = obs.SLOW_QUERIES.slowest
        assert tr is not None
        assert tr.name == "maint:compact"
        assert tr.intent == "maintenance"


class TestTenantMetering:
    """Per-tenant scan metering (DESIGN.md §15): when the active trace
    carries a tenant attribute, scan_row_reads bills reads (and with
    row_bytes, bytes) to tenant-labeled series."""

    def test_helper_bills_reads_and_bytes_to_tenant(self):
        obs.REGISTRY.reset()
        with obs.trace("request", tenant="acme"):
            obs.scan_row_reads(1024, nq=4, per_query=False,
                               source="fused", row_bytes=384)
            obs.scan_row_reads(100, nq=4, per_query=True,
                               source="ivf", row_bytes=1536)
        c = obs.REGISTRY.snapshot()["counters"]
        assert c["scan_row_reads{tenant=acme}"] == 1024 + 400
        assert c["scan_bytes_streamed{tenant=acme}"] == \
            1024 * 384 + 400 * 1536
        # the per-source convention series are untouched by tenancy
        assert c["scan_row_reads{source=fused}"] == 1024
        assert c["scan_row_reads{source=ivf}"] == 400

    def test_no_tenant_attr_means_no_tenant_series(self):
        obs.REGISTRY.reset()
        with obs.trace("request"):
            obs.scan_row_reads(64, nq=1, per_query=False,
                               source="fused", row_bytes=4)
        c = obs.REGISTRY.snapshot()["counters"]
        assert not any("tenant=" in k for k in c)
        assert c["scan_row_reads{source=fused}"] == 64

    def test_index_scan_bills_bytes_end_to_end(self):
        from repro.core.types import ChunkRecord
        from repro.index.lsm import SegmentedIndex
        obs.REGISTRY.reset()
        rng = np.random.default_rng(0)
        dim = 16
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(dim, mem_capacity=64, root=root)
            idx.insert([ChunkRecord(chunk_id=f"c{i}", doc_id=f"d{i}",
                                    position=0, valid_from=1 + i,
                                    text="r",
                                    embedding=rng.normal(size=dim))
                        for i in range(32)])
            with obs.trace("request", tenant="acme"):
                idx.search(rng.normal(size=(2, dim)), k=4)
        c = obs.REGISTRY.snapshot()["counters"]
        reads = c["scan_row_reads{tenant=acme}"]
        assert reads > 0
        # row_bytes plumbed from the index: dim bytes (int8) or dim*4
        assert c["scan_bytes_streamed{tenant=acme}"] in \
            (reads * dim, reads * dim * 4)


class TestRooflineConstant:
    def test_cost_peak_mirrors_benchmarks_roofline(self):
        # obs keeps no peak (a span times the host, so a roofline share
        # of it means nothing); the one v5e HBM peak the repo's roofline
        # figures divide by is the chip benchmark's table, which
        # benchmarks/roofline.py mirrors — this is the drift guard
        import json
        import pathlib
        from benchmarks.roofline import HBM_BW
        peaks = json.loads((pathlib.Path(__file__).parents[1] / "bench"
                            / "peaks.json").read_text())
        assert peaks["chips"]["TPU v5 lite"]["hbm_gbs"] * 1e9 == HBM_BW
        assert not hasattr(obs, "PEAK_HBM_GBS")
