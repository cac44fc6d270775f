"""Always-on serving under SLO: open-loop load vs maintenance churn
(DESIGN.md §13 gate — ISSUE 7; §15 judgment layer — ISSUE 9).

An OPEN-LOOP arrival generator (arrivals pre-scheduled at rate λ;
latency = completion − *scheduled* arrival, so coordinated omission is
impossible — a stalled server keeps accumulating queue wait) drives
mixed traffic (current + point-in-time queries) against a live
replicated ``ShardFabric`` in three phases:

  quiescent  no writes; background maintenance attached but idle;
  storm      concurrent ingest churn with seal/compaction/checkpoint
             running on the ``FabricMaintenance`` worker thread —
             the same request schedule as quiescent;
  degraded   one shard's queries fault-injected dead
             (``shard:<id>:query``); with R=2 the surviving replica
             covers every key, so degraded-marked results must still
             reach recall@10 ≥ 0.95 of the full-fabric answers.

Since ISSUE 9 the harness also exercises the §15 judgment layer the
way a production deployment would: every request runs under a
tenant-labeled trace (tenants alternate per request), tenants have
DECLARED SLOs so the engine computes real burn rates from the same
traffic, the flight recorder retains the interesting tail, the JSON
record attaches per-tenant burn rates plus the WORST storm-phase trace
(cost-attributed, so BENCH_PR9.json explains *why* p99 moved), and a
scrape thread pulls ``/metrics`` + ``/slo`` off the stdlib endpoint
MID-STORM like a real Prometheus. The drill tenant declares
``degraded_bad=True``; the gate asserts its burn rate is elevated in
``health()`` and that the degraded trace is retained in the recorder
dump.

Gates (asserted in ``main`` and in CI bench-smoke):
  - storm p99 within ``max_p99_ratio`` of quiescent p99 (tightened
    25x -> 15x once segment seals moved off the writer lock);
  - degraded recall@10 ≥ 0.95 with explicit degraded/shards_missing
    markers on the gather;
  - exact request accounting: completed == submitted, zero dropped,
    zero duplicated, zero errors;
  - SLO/recorder: the drill tenant's burn rate > 0 in ``health()``,
    a degraded trace in the recorder dump, and a non-empty mid-storm
    scrape.

  PYTHONPATH=src python -m benchmarks.load_slo [--smoke] [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import queue
import tempfile
import threading
import time

import numpy as np

from repro import obs
from repro.obs import REGISTRY
from repro.serve.maintenance import FabricMaintenance
from repro.shard import ShardFabric
from repro.testing.faults import FAULTS

from .shard_scaling import VOCAB, make_stream

DIM = 64
K = 10
TENANTS = ("alpha", "beta")
DRILL_TENANT = "drill"
# windows sized to the bench (phases run ~1-5s): short window shows
# the current phase, long window spans the whole run
SLO_WINDOWS = (5.0, 30.0)


# ----------------------------------------------------------------------
# open-loop engine
# ----------------------------------------------------------------------
def _open_loop(fabric, queries, mid_ts: int, rate_hz: float,
               n_requests: int, phase: str, workers: int = 8) -> dict:
    """Fire ``n_requests`` at fixed rate; every 4th request is temporal
    (at=mid_ts); tenants alternate per request and every request runs
    under its own tenant-labeled trace (feeding SLO burn accounting and
    the flight recorder). Returns accounting + percentile record."""
    hist = REGISTRY.histogram("load_slo_latency_ms", phase=phase)
    results: dict[int, object] = {}
    errors: list[str] = []
    dup = [0]
    lock = threading.Lock()
    q: queue.Queue = queue.Queue()

    def worker():
        while True:
            item = q.get()
            if item is None:
                return
            rid, sched_t, text, at = item
            tenant = TENANTS[rid % len(TENANTS)]
            try:
                with obs.trace("request",
                               intent="at" if at is not None else "current",
                               tenant=tenant, phase=phase):
                    if at is None:
                        res = fabric.query_batch([text], k=K)[0]
                    else:
                        res = fabric.query_batch([text], k=K, at=at)[0]
                lat_ms = (time.perf_counter() - sched_t) * 1e3
                with lock:
                    if rid in results:
                        dup[0] += 1
                    results[rid] = res
                hist.observe(lat_ms)
            except Exception as e:  # noqa: BLE001 — counted, never dropped
                with lock:
                    errors.append(f"req{rid}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    t0 = time.perf_counter() + 0.02
    for i in range(n_requests):
        sched = t0 + i / rate_hz
        now = time.perf_counter()
        if sched > now:                    # open loop: never fall behind
            time.sleep(sched - now)       # the *schedule*, only ahead
        q.put((i, sched, queries[i % len(queries)],
               mid_ts if i % 4 == 3 else None))
    for _ in threads:
        q.put(None)
    for t in threads:
        t.join(60.0)
    return {
        "phase": phase,
        "submitted": n_requests,
        "completed": len(results),
        "duplicated": dup[0],
        "errors": errors,
        "p50_ms": hist.quantile(0.5),
        "p99_ms": hist.quantile(0.99),
        "p999_ms": hist.quantile(0.999),
    }


def _recall(deg_hits, full_hits) -> float:
    full = {(r.doc_id, r.position) for r in full_hits}
    if not full:
        return 1.0
    got = {(r.doc_id, r.position) for r in deg_hits}
    return len(full & got) / len(full)


def _scrape_during(server, delay_s: float, out: dict) -> threading.Thread:
    """Pull /metrics and /slo off the endpoint mid-phase, the way a
    Prometheus scraper would."""
    from urllib.request import urlopen

    def scrape():
        time.sleep(delay_s)
        try:
            with urlopen(server.url("/metrics"), timeout=10) as r:
                text = r.read().decode()
            parsed = obs.parse_prometheus_text(text)
            out["metrics_series"] = (len(parsed["counters"])
                                     + len(parsed["gauges"])
                                     + len(parsed["histograms"]))
            with urlopen(server.url("/slo"), timeout=10) as r:
                out["slo"] = json.loads(r.read().decode())
        except Exception as e:  # noqa: BLE001 — gate reports the miss
            out["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    return t


# ----------------------------------------------------------------------
def run(smoke: bool = False, max_p99_ratio: float = 15.0,
        seed: int = 0) -> dict:
    n_docs = 20 if smoke else 64
    n_versions = 2 if smoke else 3
    n_queries = 16 if smoke else 32
    rate_hz = 80.0 if smoke else 150.0
    n_requests = 96 if smoke else 360
    churn_updates = 48 if smoke else 192

    REGISTRY.reset()
    obs.SLOW_QUERIES.reset()
    obs.SLO_ENGINE.reset()
    obs.FLIGHT_RECORDER.reset()
    # declared objectives: generous latency thresholds (CI machines are
    # noisy — the bench reports burn, it only GATES the drill tenant),
    # per-intent slowlog budgets so temporal traffic doesn't drown the
    # current-tier tail
    for tenant in TENANTS:
        obs.SLO_ENGINE.declare(tenant, "current", latency_ms=500.0,
                               target=0.99, windows_s=SLO_WINDOWS)
        obs.SLO_ENGINE.declare(tenant, "at", latency_ms=2000.0,
                               target=0.99, windows_s=SLO_WINDOWS)
    obs.SLO_ENGINE.declare(DRILL_TENANT, "*", latency_ms=10_000.0,
                           target=0.999, windows_s=SLO_WINDOWS,
                           degraded_bad=True)
    obs.SLOW_QUERIES.configure(budget_ms=500.0,
                               intent_budgets={"at": 2000.0})
    obs.FLIGHT_RECORDER.enable(capacity=128, sample_rate=0.05, seed=seed)
    server = obs.ObsHttpServer().start()
    scrape: dict = {}

    rng = np.random.default_rng(seed)
    stream = make_stream(rng, n_docs, n_versions)
    queries = [" ".join(rng.choice(VOCAB, 4)) for _ in range(n_queries)]
    mid_ts = stream[-1][2] // 2

    try:
        with tempfile.TemporaryDirectory() as root:
            fab = ShardFabric(root, n_shards=2, replicas=2, dim=DIM,
                              hot_capacity=64, degraded_reads=True)
            for doc, text, ts in stream:
                fab.ingest(doc, text, ts=ts)
            fab.query_batch(queries[:2], k=K)              # warm-up
            fab.query_batch(queries[:2], k=K, at=mid_ts)

            maint = FabricMaintenance(fab, checkpoint_every=8,
                                      backoff_s=1e-4).start()
            maint.drain(timeout=30.0)

            # -- phase 1: quiescent -----------------------------------
            quiescent = _open_loop(fab, queries, mid_ts, rate_hz,
                                   n_requests, "quiescent")

            # -- phase 2: compaction storm ----------------------------
            last_ts = stream[-1][2]
            stop_churn = threading.Event()
            churned = [0]

            def churn():
                ts = last_ts
                i = 0
                while i < churn_updates and not stop_churn.is_set():
                    doc = f"doc{i % n_docs}"
                    ts += 1_000_000
                    fab.ingest(doc, " ".join(rng.choice(VOCAB, 6)),
                               ts=ts)
                    maint.tick()
                    churned[0] = i = i + 1
            ct = threading.Thread(target=churn, daemon=True)
            ct.start()
            # a real scraper doesn't wait for the storm to settle
            st = _scrape_during(server,
                                0.4 * n_requests / rate_hz, scrape)
            storm = _open_loop(fab, queries, mid_ts, rate_hz,
                               n_requests, "storm")
            stop_churn.set()
            ct.join(60.0)
            st.join(15.0)
            maint.drain(timeout=60.0)
            storm["churn_updates"] = churned[0]
            storm["maintenance"] = {
                "jobs": REGISTRY.counter("maintenance_jobs",
                                         worker=maint.worker.name).value,
                "failures": REGISTRY.counter(
                    "maintenance_failures",
                    worker=maint.worker.name).value,
            }
            # the worst trace the recorder retained through the storm,
            # cost-attributed — WHY p99 moved, not just that it did
            storm_records = obs.FLIGHT_RECORDER.dump(reason="post_storm")
            storm_traces = [r for r in storm_records
                            if r.get("kind") == "trace"]
            storm["worst_trace"] = max(storm_traces,
                                       key=lambda r: r.get("wall_ms", 0),
                                       default=None)
            storm["recorder"] = obs.FLIGHT_RECORDER.summary()

            # -- phase 3: one shard down, degraded reads --------------
            full = fab.query_batch(queries, k=K)
            dead = fab.ring.shards[0]
            FAULTS.arm(f"shard:{dead}:query", times=10**9,
                       message="load_slo drill: shard down")
            try:
                with obs.trace("request", intent="current",
                               tenant=DRILL_TENANT):
                    deg = fab.query_batch(queries, k=K)
                gather = dict(fab.planner.last_gather or {})
            finally:
                FAULTS.reset()
            drill_records = obs.FLIGHT_RECORDER.dump(reason="post_drill")
            health = fab.health()
            recall = float(np.mean([_recall(deg[i], full[i])
                                    for i in range(n_queries)]))
            drill_slo = next((s for s in health["slo"]["slos"]
                              if s["tenant"] == DRILL_TENANT), None)
            degraded_retained = [
                r for r in drill_records
                if r.get("reason") in ("degraded", "error", "deadline")]
            degraded = {
                "dead_shard": dead,
                "marked_degraded": bool(gather.get("degraded")),
                "complete": bool(gather.get("complete")),
                "shards_missing": list(gather.get("shards_missing", ())),
                "recall_at10": recall,
                "drill_slo": drill_slo,
                "degraded_retained": len(degraded_retained),
                # the fault registry auto-triggered these on fire
                "fault_dumps": [r for r in
                                obs.FLIGHT_RECORDER.dump_reasons
                                if r.startswith("fault:")],
            }
            maint.stop(drain=True, timeout=60.0)
    finally:
        server.stop()
        obs.FLIGHT_RECORDER.disable()

    slo_summary = obs.SLO_ENGINE.summary()
    ratio = storm["p99_ms"] / max(quiescent["p99_ms"] or 1e-9, 1e-9)
    accounting_ok = all(
        p["completed"] == p["submitted"] and p["duplicated"] == 0
        and not p["errors"] for p in (quiescent, storm))
    drill_burn = (max(drill_slo["burn"].values())
                  if drill_slo else 0.0)
    gate = {
        "p99_ratio": ratio,
        "max_p99_ratio": max_p99_ratio,
        "p99_ok": ratio <= max_p99_ratio,
        "recall_at10": recall,
        "degraded_ok": (degraded["marked_degraded"]
                        and bool(degraded["shards_missing"])
                        and recall >= 0.95),
        "accounting_ok": accounting_ok,
        "drill_burn": drill_burn,
        "slo_ok": (drill_burn > 0.0
                   and degraded["degraded_retained"] > 0
                   and scrape.get("metrics_series", 0) > 0),
    }
    gate["pass"] = (gate["p99_ok"] and gate["degraded_ok"]
                    and gate["accounting_ok"] and gate["slo_ok"])
    return {"smoke": smoke, "n_docs": n_docs, "rate_hz": rate_hz,
            "n_requests": n_requests,
            "quiescent": quiescent, "storm": storm, "degraded": degraded,
            "slo": slo_summary, "scrape": scrape,
            "gate": gate, "timestamp": time.time()}


def rows_from(result: dict) -> list[tuple]:
    rows = []
    for phase in ("quiescent", "storm"):
        p = result[phase]
        note = (f"open-loop {result['rate_hz']:.0f}/s, "
                f"{p['completed']}/{p['submitted']} ok")
        if phase == "storm":
            note += (f", {p['churn_updates']} churn writes, "
                     f"{p['maintenance']['jobs']:.0f} maint jobs")
        rows.append((f"load_slo/{phase}/p50_ms", p["p50_ms"], note))
        rows.append((f"load_slo/{phase}/p99_ms", p["p99_ms"], note))
        rows.append((f"load_slo/{phase}/p999_ms", p["p999_ms"], note))
    g = result["gate"]
    d = result["degraded"]
    worst = result["storm"].get("worst_trace") or {}
    cost = worst.get("cost") or {}
    if cost:
        rows.append(("load_slo/storm/worst_trace_ms",
                     worst.get("wall_ms", 0.0),
                     f"reason={worst.get('reason')}, "
                     f"bound={cost.get('bound')}, "
                     f"device_wait_frac={cost.get('device_wait_frac')}"))
    rows.append(("load_slo/degraded/recall_at10", d["recall_at10"],
                 f"shard {d['dead_shard']} down, R=2, "
                 f"marked={'yes' if d['marked_degraded'] else 'NO'}"))
    rows.append(("load_slo/drill/burn_rate", g["drill_burn"],
                 f"tenant {DRILL_TENANT} (degraded_bad), "
                 f"{d['degraded_retained']} degraded traces retained"))
    rows.append(("load_slo/gate_pass", 1.0 if g["pass"] else 0.0,
                 f"storm/quiescent p99 {g['p99_ratio']:.1f}x "
                 f"(max {g['max_p99_ratio']:.0f}x), "
                 f"accounting={'ok' if g['accounting_ok'] else 'BAD'}, "
                 f"slo={'ok' if g['slo_ok'] else 'BAD'}"))
    return rows


def main(smoke: bool = False) -> list[tuple]:
    result = run(smoke=smoke)
    rows = rows_from(result)
    assert result["gate"]["pass"], result["gate"]
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small sizes for CI")
    ap.add_argument("--json", type=str, default=None,
                    help="write the full result record to PATH")
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    for name, val, note in rows_from(result):
        print(f"{name},{val:.4f},{note}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    if not result["gate"]["pass"]:
        raise SystemExit(f"load_slo gate FAILED: {result['gate']}")
