"""Quantized scan fabric: int8 asymmetric scans + exact fp32 rescoring
vs the fp32 path (DESIGN.md §11 acceptance — ISSUE 5).

Every scan in the system is memory-bandwidth-bound: it streams each
corpus row once per dispatch. This suite measures, at 20k/50k rows:

  - SCAN throughput: the fused exact top-k scan (the memtable + small-
    segment path) fp32 vs int8+rescore;
  - the TEMPORAL validity-masked scan fp32 vs int8+rescore over a
    synthetic full-history block (per-query windows, leakage asserted);
  - RESIDENT embedding bytes at the index level (memtable + segments +
    winners caches) fp32 vs quantized — the ~4x claim;
  - RECALL@10 of the quantized path vs the fp32 oracle on current,
    point-in-time, and window queries (store level, gate >= 0.99).

Gates: recall and bytes, in both modes. The scan and temporal rows'
``ref_ratio`` (fp32 ms over int8 ms) is reported, not gated: off a TPU
both scans are the jnp references of the chip's kernels, so the ratio
times XLA's CPU backend and is no speedup of the system.

  PYTHONPATH=src python -m benchmarks.quantized_scan [--smoke] [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

from repro.core.store import LiveVectorLake
from repro.core.types import ChunkRecord, VALID_TO_OPEN
from repro.data.corpus import generate_corpus
from repro.index.lsm import SegmentedIndex
from repro.index.quant import (data_scale, fixed_scale, pool_k,
                               quantize_rows, rescore_topk)
from repro.kernels.topk_search.ops import topk_search, topk_search_q8
from repro.kernels.temporal_mask_score.ops import (temporal_window_topk,
                                                   temporal_window_topk_q8)

from .common import Timer
from .search_scaling import make_corpus


def _median_ms(fn, repeats: int = 7) -> float:
    # settle: OpenBLAS worker threads busy-wait for ~2^26 cycles after a
    # gemm; letting them park isolates each implementation's timing from
    # the OTHER path's leftover spinners (measured 3x cross-talk on a
    # 2-core host — the int8 GEMM and fp32 BLAS use different pools)
    time.sleep(0.25)
    fn()                                     # warm-up (jit / cache)
    xs = []
    for _ in range(repeats):
        with Timer() as t:
            fn()
        xs.append(t.elapsed * 1e3)
    return float(np.median(xs))


def _recall(idx_a: np.ndarray, s_a: np.ndarray,
            idx_b: np.ndarray, s_b: np.ndarray) -> float:
    """recall@k of b vs a over finite slots."""
    vals = []
    for qi in range(idx_a.shape[0]):
        want = set(np.asarray(idx_a)[qi][np.isfinite(s_a[qi])].tolist())
        got = set(np.asarray(idx_b)[qi][np.isfinite(s_b[qi])].tolist())
        if want:
            vals.append(len(want & got) / len(want))
    return float(np.mean(vals)) if vals else 1.0


# ---------------------------------------------------------------------------
# 1. fused exact scan: fp32 kernel vs int8 + exact rescore
# ---------------------------------------------------------------------------
def scan_point(n: int, dim: int, nq: int, k: int, rescore_factor: int,
               seed: int = 0) -> dict:
    corpus, queries = make_corpus(n, dim, nq, seed)
    q = queries[:nq]
    mask = np.ones(n, bool)
    scale = data_scale(corpus)
    c8 = quantize_rows(corpus, scale)
    kp = pool_k(k, n, rescore_factor)

    fp32_ms = _median_ms(
        lambda: np.asarray(topk_search(q, corpus, mask, k)[0]))

    def q8_scan():
        _, pool = topk_search_q8(q, c8, scale, mask, kp)
        return rescore_topk(q, np.asarray(pool), corpus, k)

    q8_ms = _median_ms(q8_scan)
    s_f, i_f = topk_search(q, corpus, mask, k)
    s_f, i_f = np.asarray(s_f), np.asarray(i_f)
    s_q, i_q = q8_scan()
    return {
        "n": n, "dim": dim, "nq": nq, "k": k, "pool_k": kp,
        "fp32_ms": fp32_ms, "q8_ms": q8_ms,
        "ref_ratio": fp32_ms / max(q8_ms, 1e-9),
        "fp32_mrows_s": n * nq / max(fp32_ms, 1e-9) / 1e3,
        "q8_mrows_s": n * nq / max(q8_ms, 1e-9) / 1e3,
        "recall_at_k": _recall(i_f, s_f, i_q, s_q),
        "corpus_bytes_fp32": int(corpus.nbytes),
        "corpus_bytes_q8": int(c8.nbytes + scale.nbytes),
    }


# ---------------------------------------------------------------------------
# 2. temporal validity-masked scan over a synthetic full history
# ---------------------------------------------------------------------------
def temporal_point(n: int, dim: int, nq: int, k: int, rescore_factor: int,
                   seed: int = 0) -> dict:
    corpus, queries = make_corpus(n, dim, nq, seed + 1)
    q = queries[:nq]
    rng = np.random.default_rng(seed)
    base = 1_700_000_000_000_000
    vf = base + rng.integers(0, 10**9, n).astype(np.int64)
    vt = np.where(rng.random(n) < 0.5, VALID_TO_OPEN,
                  vf + rng.integers(1, 10**9, n)).astype(np.int64)
    # per-query windows: a mix of points and ranges across the history
    t0s = base + rng.integers(0, 10**9, nq).astype(np.int64)
    t1s = t0s + np.where(rng.random(nq) < 0.5, 1, 3 * 10**8)
    scale = fixed_scale(dim)
    c8 = quantize_rows(corpus, scale)
    kp = pool_k(k, n, rescore_factor)

    fp32_ms = _median_ms(lambda: np.asarray(
        temporal_window_topk(q, corpus, vf, vt, t0s, t1s, k)[0]))

    def q8_scan():
        _, pool = temporal_window_topk_q8(q, c8, scale, vf, vt,
                                          t0s, t1s, kp)
        return rescore_topk(q, np.asarray(pool), corpus, k)

    q8_ms = _median_ms(q8_scan)
    s_f, i_f = temporal_window_topk(q, corpus, vf, vt, t0s, t1s, k)
    s_f, i_f = np.asarray(s_f), np.asarray(i_f)
    s_q, i_q = q8_scan()
    # leakage audit: every quantized pick overlaps its query's window
    for qi in range(nq):
        for j in i_q[qi][np.isfinite(s_q[qi])]:
            assert vf[j] < t1s[qi] and t0s[qi] < vt[j], "temporal leakage"
    return {
        "n": n, "fp32_ms": fp32_ms, "q8_ms": q8_ms,
        "ref_ratio": fp32_ms / max(q8_ms, 1e-9),
        "recall_at_k": _recall(i_f, s_f, i_q, s_q),
    }


# ---------------------------------------------------------------------------
# 3. resident bytes at the index level
# ---------------------------------------------------------------------------
def bytes_point(n: int, dim: int, seed: int = 0) -> dict:
    corpus, queries = make_corpus(n, dim, 8, seed + 2)
    recs = [ChunkRecord(chunk_id=f"c{i}", doc_id=f"d{i}", position=0,
                        valid_from=1 + i, text=f"row {i}",
                        embedding=corpus[i]) for i in range(n)]
    out = {}
    for tag, quantized in (("fp32", False), ("q8", True)):
        with tempfile.TemporaryDirectory() as root:
            idx = SegmentedIndex(dim, mem_capacity=1024, root=root,
                                 ivf_min_rows=1024, quantized=quantized)
            idx.insert(recs)
            idx.search(queries, k=10)        # arm winners caches
            out[f"bytes_{tag}"] = idx.nbytes()
            out[f"seg_bytes_{tag}"] = sum(
                s.emb_nbytes() for s in idx.segments.values())
            # pure scan-corpus payload (no winners caches): what the
            # scans actually stream
            out[f"payload_{tag}"] = sum(
                (int(s.q8.nbytes + s.scale.nbytes) if s.q8 is not None
                 else int(s.emb.nbytes))
                for s in idx.segments.values())
            out[f"search_ms_{tag}"] = _median_ms(
                lambda: idx.search(queries, k=10), repeats=5)
    out["n"] = n
    # whole-index ratio includes the capacity-bounded fp32 memtable (the
    # exact-rescore source — a constant, not O(corpus)); the segment
    # ratio is the pure scan-corpus reduction (~4x by construction)
    out["bytes_reduction"] = out["bytes_fp32"] / max(out["bytes_q8"], 1)
    out["seg_bytes_reduction"] = (out["seg_bytes_fp32"]
                                  / max(out["seg_bytes_q8"], 1))
    out["payload_reduction"] = (out["payload_fp32"]
                                / max(out["payload_q8"], 1))
    out["index_speedup"] = (out["search_ms_fp32"]
                            / max(out["search_ms_q8"], 1e-9))
    return out


# ---------------------------------------------------------------------------
# 4. store-level recall gate: current / point-in-time / window
# ---------------------------------------------------------------------------
def store_recall_point(n_docs: int, n_versions: int, dim: int,
                       seed: int = 0) -> dict:
    corpus = generate_corpus(n_docs=n_docs, n_versions=n_versions,
                             seed=seed)
    with tempfile.TemporaryDirectory() as r1, \
            tempfile.TemporaryDirectory() as r2:
        fp = LiveVectorLake(r1, dim=dim)
        qz = LiveVectorLake(r2, dim=dim, quantized=True)
        for v in range(n_versions):
            for d in corpus.doc_ids():
                fp.ingest(d, corpus.versions[v][d],
                          ts=corpus.timestamps[v])
                qz.ingest(d, corpus.versions[v][d],
                          ts=corpus.timestamps[v])
        queries = [f"{f.name} units recorded"
                   for f in list(corpus.facts)[:8]]
        ts = int((corpus.timestamps[1] + corpus.timestamps[2]) // 2)
        w = (int(corpus.timestamps[1]),
             int(corpus.timestamps[n_versions - 1]))
        out = {"n_docs": n_docs, "n_versions": n_versions}
        for name, kw in (("current", {}), ("point", {"at": ts}),
                         ("window", {"window": w})):
            a = fp.query_batch(queries, k=10, **kw)
            b = qz.query_batch(queries, k=10, **kw)
            vals = []
            for ra, rb in zip(a, b):
                want = {r.chunk_id for r in ra}
                got = {r.chunk_id for r in rb}
                if want:
                    vals.append(len(want & got) / len(want))
            out[f"recall_{name}"] = float(np.mean(vals)) if vals else 1.0
        for row in qz.query_batch(queries, k=10, at=ts):
            qz.temporal.assert_no_leakage(row, ts)
        return out


def run(smoke: bool = False, seed: int = 0) -> dict:
    if smoke:
        sizes, dim, nq = (20_000,), 384, 8
        bytes_n, docs, versions = 20_000, 8, 3
        min_bytes = 2.8   # the memtable's fixed fp32 cost is a larger
        # share at smoke sizes
    else:
        sizes, dim, nq = (20_000, 50_000), 384, 8
        bytes_n, docs, versions = 50_000, 20, 4
        min_bytes = 3.3   # whole-index incl fp32 memtable; the
        # scan-corpus payload itself is ~4x (gated below)
    k, rescore_factor = 10, 4
    scan = [scan_point(n, dim, nq, k, rescore_factor, seed) for n in sizes]
    temporal = [temporal_point(n, dim, nq, k, rescore_factor, seed)
                for n in sizes]
    nbytes = bytes_point(bytes_n, dim, seed)
    store = store_recall_point(docs, versions, dim=64, seed=seed)
    big_scan, big_temporal = scan[-1], temporal[-1]
    recalls = ([p["recall_at_k"] for p in scan]
               + [p["recall_at_k"] for p in temporal]
               + [store["recall_current"], store["recall_point"],
                  store["recall_window"]])
    gate = {
        "min_recall": float(min(recalls)),
        "recall_pass": min(recalls) >= 0.99,
        "bytes_reduction": nbytes["bytes_reduction"],
        "seg_bytes_reduction": nbytes["seg_bytes_reduction"],
        "payload_reduction": nbytes["payload_reduction"],
        "bytes_pass": (nbytes["bytes_reduction"] >= min_bytes
                       and nbytes["payload_reduction"] >= 3.9),
        "scan_ref_ratio_at_gate": big_scan["ref_ratio"],
        "temporal_ref_ratio_at_gate": big_temporal["ref_ratio"],
        "rows_at_gate": big_scan["n"],
    }
    gate["pass"] = bool(gate["recall_pass"] and gate["bytes_pass"])
    return {"scan": scan, "temporal": temporal, "bytes": nbytes,
            "store": store, "gate": gate, "smoke": smoke,
            "rescore_factor": rescore_factor, "timestamp": time.time()}


def rows_from(result: dict) -> list[tuple]:
    rows = []
    for p in result["scan"]:
        tag = f"quantized_scan/n{p['n']}"
        rows.append((f"{tag}/fp32_ms", p["fp32_ms"],
                     f"{p['fp32_mrows_s']:.0f} Mrow/s"))
        rows.append((f"{tag}/q8_ms", p["q8_ms"],
                     f"{p['q8_mrows_s']:.0f} Mrow/s pool={p['pool_k']}"))
        rows.append((f"{tag}/ref_ratio", p["ref_ratio"],
                     "fp32 ms / int8 ms, reported, not gated"))
        rows.append((f"{tag}/recall_at_10", p["recall_at_k"],
                     "gate >=0.99"))
    for p in result["temporal"]:
        tag = f"quantized_scan/temporal_n{p['n']}"
        rows.append((f"{tag}/ref_ratio", p["ref_ratio"],
                     f"fp32 {p['fp32_ms']:.2f}ms -> q8 {p['q8_ms']:.2f}ms"))
        rows.append((f"{tag}/recall_at_10", p["recall_at_k"],
                     "gate >=0.99"))
    b = result["bytes"]
    rows.append((f"quantized_scan/bytes_n{b['n']}/reduction",
                 b["bytes_reduction"],
                 f"{b['bytes_fp32']} -> {b['bytes_q8']} B incl fp32 memtable"))
    rows.append((f"quantized_scan/bytes_n{b['n']}/segment_reduction",
                 b["seg_bytes_reduction"], "segments incl winners caches"))
    rows.append((f"quantized_scan/bytes_n{b['n']}/payload_reduction",
                 b["payload_reduction"], "scan-corpus payload, target ~4x"))
    rows.append((f"quantized_scan/bytes_n{b['n']}/index_speedup",
                 b["index_speedup"],
                 f"search {b['search_ms_fp32']:.1f} -> "
                 f"{b['search_ms_q8']:.1f} ms"))
    s = result["store"]
    for name in ("current", "point", "window"):
        rows.append((f"quantized_scan/store_recall_{name}",
                     s[f"recall_{name}"], "gate >=0.99"))
    g = result["gate"]
    rows.append(("quantized_scan/gate_pass", float(g["pass"]),
                 f"scan {g['scan_ref_ratio_at_gate']:.1f}x temporal "
                 f"{g['temporal_ref_ratio_at_gate']:.1f}x at "
                 f"{g['rows_at_gate']} rows, bytes "
                 f"{g['bytes_reduction']:.1f}x, min recall "
                 f"{g['min_recall']:.3f}"))
    return rows


def main(smoke: bool = False) -> list[tuple]:
    result = run(smoke=smoke)
    rows = rows_from(result)
    # fail the runner on gate violation so CI --smoke enforces it
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
        raise SystemExit(f"quantized_scan gate FAILED: {result['gate']}")
