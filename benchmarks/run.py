"""Benchmark runner — one function per paper table/figure plus the
system-scaling suites added since.

Prints ``name,value,derived`` CSV rows:
  Table II  -> update_performance
  Table III -> query_latency
  §V-B3     -> change_detection
  §V-B4     -> storage_efficiency
  §V-B5     -> temporal_accuracy
  DESIGN §7 -> streaming_churn, search_scaling
  DESIGN §8 -> query_throughput
  DESIGN §9 -> temporal_scaling
  DESIGN §10-> shard_scaling
  DESIGN §11-> quantized_scan
  DESIGN §12-> obs_overhead (trend diffing: ``python -m benchmarks.trend``)
  DESIGN §13-> load_slo
  DESIGN §14-> tenant_isolation

``--smoke`` shrinks every suite to CI sizes (each suite's ``main``
honors the flag); ``--only`` runs a comma-separated subset. ``--json
PATH`` additionally writes one consolidated record — every suite's
headline rows plus wall time — so each PR can commit its perf
trajectory point (BENCH_PR<N>.json) and CI can diff artifacts across
PRs.

The roofline/dry-run analysis (§Roofline) is a separate entry point
(``python -m benchmarks.roofline``) because it must force 512 host
devices before jax initializes.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (passed to every suite)")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--json", type=str, default=None,
                    help="write a consolidated per-suite record to PATH")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from . import (change_detection, load_slo, obs_overhead,
                   query_latency, query_throughput, quantized_scan,
                   scrub_overhead, search_scaling, shard_scaling,
                   storage_efficiency, streaming_churn,
                   temporal_accuracy, temporal_scaling,
                   tenant_isolation, update_performance)
    suites = [
        ("update_performance", update_performance),
        ("query_latency", query_latency),
        ("change_detection", change_detection),
        ("storage_efficiency", storage_efficiency),
        ("temporal_accuracy", temporal_accuracy),
        ("temporal_scaling", temporal_scaling),
        ("search_scaling", search_scaling),
        ("streaming_churn", streaming_churn),
        ("query_throughput", query_throughput),
        ("shard_scaling", shard_scaling),
        ("quantized_scan", quantized_scan),
        ("obs_overhead", obs_overhead),
        ("load_slo", load_slo),
        ("tenant_isolation", tenant_isolation),
        ("scrub_overhead", scrub_overhead),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        unknown = keep - {name for name, _ in suites}
        if unknown:
            sys.exit(f"unknown suite(s): {sorted(unknown)}")
        suites = [(n, m) for n, m in suites if n in keep]
    print("name,value,notes")
    record: dict = {
        "smoke": args.smoke,
        "timestamp": time.time(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "suites": {},
    }
    failures = 0
    for name, mod in suites:
        t0 = time.perf_counter()
        try:
            rows = mod.main(smoke=args.smoke)
            for row_name, val, note in rows:
                if isinstance(val, float):
                    print(f"{row_name},{val:.4f},{note}")
                else:
                    print(f"{row_name},{val},{note}")
            wall = time.perf_counter() - t0
            print(f"_meta/{name}/wall_s,{wall:.1f},")
            record["suites"][name] = {
                "wall_s": round(wall, 2),
                "rows": [[r, (round(v, 6) if isinstance(v, float) else v),
                          n] for r, v, n in rows],
            }
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"_meta/{name}/ERROR,{type(e).__name__}: {e},")
            record["suites"][name] = {
                "wall_s": round(time.perf_counter() - t0, 2),
                "error": f"{type(e).__name__}: {e}",
            }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
