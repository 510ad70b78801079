"""Benchmark harness — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (assignment contract).

  fig4_*   — dynamic workloads, write/read-heavy (paper Fig. 4)
  tab1_*   — hybrid query latency vs baseline strategies (paper Table 1)
  fig5a/b_* — continuous queries: budget / #queries sweeps (paper Fig. 5)
  ingest_* — ingestion throughput: columnar/pipelined write path vs the
             per-row baseline and the global in-memory index (paper §1),
             mixed read/write, index merge-vs-rebuild at compaction
  mq_*     — batched execute_many vs sequential execute throughput
  durability_* — WAL ingest overhead, recovery replay, snapshot/restore
  obs_*    — observability layer cost: tracing-off/on query overhead

``--scale`` shrinks/grows the workload (CPU container default 1.0).
``--json PATH`` additionally writes structured results for every section
that exposes a ``bench_json(scale)`` hook (ingestion does), plus a
``metrics`` key with the unified registry snapshot (histograms with
p50/p95/p99, counters) accumulated across every section that ran.
"""
import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,tab1,fig5,ingest,mq,sharded,"
                         "durability,obs")
    ap.add_argument("--json", default=None,
                    help="write structured per-section results to PATH")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (continuous_bench, durability_bench,
                            dynamic_workload, hybrid_latency, ingestion,
                            multi_query, obs_overhead, pq_study,
                            sharded_bench)
    sections = [
        ("tab1", hybrid_latency),
        ("fig4", dynamic_workload),
        ("fig5", continuous_bench),
        ("ingest", ingestion),
        ("pq", pq_study),
        ("mq", multi_query),
        ("sharded", sharded_bench),
        ("durability", durability_bench),
        ("obs", obs_overhead),
    ]
    structured = {}
    print("name,us_per_call,derived")
    for name, mod in sections:
        if only and name not in only:
            continue
        t0 = time.time()
        if args.json and hasattr(mod, "bench_json") and \
                hasattr(mod, "csv_from_json"):
            structured[name] = mod.bench_json(scale=args.scale)
            rows = mod.csv_from_json(structured[name])
        else:
            rows = mod.bench(scale=args.scale)
        for row in rows:
            print(row, flush=True)
        print(f"# section {name} took {time.time() - t0:.1f}s",
              file=sys.stderr)
    if args.json:
        # unified telemetry accumulated across every section that ran:
        # the process-wide registry (latency histograms with
        # p50/p95/p99, engine counters) + this thread's kernel totals
        from repro.kernels import ops as kops
        from repro.obs import REGISTRY
        kops.flush_registry_counters()
        launches, byts, misses = kops.stats_snapshot()
        structured["metrics"] = {
            "registry": REGISTRY.snapshot(),
            "kernels_thread": {"launches": launches,
                               "bytes_to_host": byts,
                               "shape_misses": misses},
        }
        with open(args.json, "w") as f:
            json.dump(structured, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# structured results -> {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
