"""Paper Table III: query latency p50/p95/p99 — current (hot tier) vs
historical (cold tier), plus the beyond-paper device-resident temporal
path (fused validity-mask kernel, no per-query snapshot load)."""
from __future__ import annotations

import tempfile

import numpy as np

from repro.core.store import LiveVectorLake
from repro.data.corpus import generate_corpus

from .common import Timer, percentiles


def build_store(root: str, n_docs: int = 100, n_versions: int = 5,
                seed: int = 0, device_resident: bool = False):
    corpus = generate_corpus(n_docs=n_docs, n_versions=n_versions,
                             seed=seed)
    store = LiveVectorLake(root, dim=384, temporal_fused=device_resident)
    for v in range(n_versions):
        ts = corpus.timestamps[v]
        for d in corpus.doc_ids():
            store.ingest(d, corpus.versions[v][d], ts=ts)
    return store, corpus


def run(n_queries: int = 60, seed: int = 0, n_docs: int = 100,
        n_versions: int = 5) -> dict:
    rng = np.random.default_rng(seed)
    out = {}
    with tempfile.TemporaryDirectory() as root:
        store, corpus = build_store(root, n_docs=n_docs,
                                    n_versions=n_versions, seed=seed)
        facts = [f for f in corpus.facts]
        queries = [f"{rng.choice(facts).name} units recorded"
                   for _ in range(n_queries)]

        # warmup (jit compile of the search path)
        store.query(queries[0], k=5)
        cur_lat = []
        for q in queries:
            with Timer() as t:
                store.query(q, k=5)
            cur_lat.append(t.elapsed * 1000)
        out["current_hot_ms"] = percentiles(cur_lat)

        ts_lo, ts_hi = corpus.timestamps[0], corpus.timestamps[-1]
        hist_ts = rng.integers(ts_lo, ts_hi, n_queries)
        store.query(queries[0], k=5, at=int(hist_ts[0]))
        hist_lat = []
        for q, ts in zip(queries, hist_ts):
            with Timer() as t:
                store.query(q, k=5, at=int(ts))
            hist_lat.append(t.elapsed * 1000)
        out["historical_cold_ms"] = percentiles(hist_lat)

    # beyond-paper: device-resident full history + fused validity kernel
    with tempfile.TemporaryDirectory() as root:
        store2, corpus2 = build_store(root, seed=seed,
                                      device_resident=True)
        store2.query(queries[0], k=5, at=int(hist_ts[0]))   # warm
        res_lat = []
        for q, ts in zip(queries, hist_ts):
            with Timer() as t:
                store2.query(q, k=5, at=int(ts))
            res_lat.append(t.elapsed * 1000)
        out["historical_resident_ms"] = percentiles(res_lat)

    out["ordering_ok"] = (out["current_hot_ms"]["p50"]
                          < out["historical_cold_ms"]["p50"])
    out["resident_speedup"] = (out["historical_cold_ms"]["p50"]
                               / max(out["historical_resident_ms"]["p50"],
                                     1e-9))
    return out


def main(smoke: bool = False) -> list[tuple]:
    r = run(n_queries=12, n_docs=20, n_versions=3) if smoke else run()
    rows = []
    for k in ("current_hot_ms", "historical_cold_ms",
              "historical_resident_ms"):
        for p, v in r[k].items():
            note = {"current_hot_ms": "paper p50=65 p95=110 p99=145",
                    "historical_cold_ms": "paper p50=1200 p95=1890",
                    "historical_resident_ms": "beyond-paper fused kernel"
                    }[k]
            rows.append((f"query_latency/{k}/{p}", v, note))
    rows.append(("query_latency/hot_faster_than_cold",
                 float(r["ordering_ok"]), "paper invariant"))
    rows.append(("query_latency/resident_speedup_x",
                 r["resident_speedup"], "beyond-paper vs snapshot-load"))
    return rows


if __name__ == "__main__":
    for name, val, note in main():
        print(f"{name},{val:.3f},{note}")
