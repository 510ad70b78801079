"""Observability overhead study: what the obs layer costs on the
query hot path, measured three ways on the same TRACY store.

  stripped  — the obs hooks are monkeypatched out in-process: the
              ``execute_many`` telemetry wrapper is bypassed and the
              kernel-dispatch registry mirror is replaced with no-op
              counters.  This approximates the pre-obs engine.
  disabled  — the shipped default: tracing off, metrics registry live.
  enabled   — ``set_tracing(True)``: full span trees recorded.

The three modes run back-to-back on identical query chunks with the
order rotating every triple, so clock drift and cache warmth cancel.
Scheduler noise is strictly additive, so each chunk's true per-mode
cost is the MIN over rounds (best-of-N); the gated ratio is the median
across chunks of those paired minima, and the reported p50s are
medians over all samples.  The machine-independent gates are

  disabled_over_stripped <= 1.02   (tracing off must cost <= 2%)
  enabled_over_disabled  <= 1.15   (tracing on must cost <= 15%)

A ``registry`` micro-section reports the raw cost of one counter
``inc`` and one histogram ``observe`` (ns; informational, no gate).

CLI:  python benchmarks/obs_overhead.py [--smoke] [--json PATH]
                                        [--baseline PATH]
With --baseline the ratios above are gated (CI obs-smoke job); the
committed JSON records the reference numbers the gate message cites.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

if __package__ in (None, ""):    # `python benchmarks/obs_overhead.py`
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import tracy
from repro.core.executor import Executor
from repro.kernels import ops as kops
from repro.obs import REGISTRY
from repro.obs import trace as obs_trace

DIM = 32
BATCH = 8                      # queries per timed execute_many call


class _NoopCounter:
    def inc(self, n: int = 1) -> None:
        pass


def _patch_stripped():
    """Remove the obs hooks from the hot path; returns restore state."""
    saved = (Executor.execute_many, kops._registry_counters)
    Executor.execute_many = Executor._execute_many
    noop = tuple(_NoopCounter() for _ in kops._registry_counters())
    kops._registry_counters = lambda: noop
    return saved


def _unpatch(saved) -> None:
    Executor.execute_many, kops._registry_counters = saved


MODES = ("stripped", "disabled", "enabled")


def _run_mode(mode: str, ex: Executor, chunk: List) -> float:
    """Per-query latency for one chunk under one obs mode."""
    if mode == "stripped":
        saved = _patch_stripped()
        try:
            return _run_mode("disabled", ex, chunk)
        finally:
            _unpatch(saved)
    if mode == "enabled":
        obs_trace.set_tracing(True)
        try:
            t = _run_mode("disabled", ex, chunk)
        finally:
            obs_trace.set_tracing(False)
            obs_trace.TRACER.clear()
        return t
    t0 = time.perf_counter()
    ex.execute_many(chunk)
    return (time.perf_counter() - t0) / len(chunk)


def run_query_overhead(n_rows: int = 4000, n_queries: int = 32,
                       rounds: int = 40) -> Dict[str, float]:
    cfg = tracy.TracyConfig(n_rows=n_rows, dim=DIM, seed=5,
                            flush_rows=max(256, n_rows // 8))
    store, data = tracy.build_store(cfg)
    ex = Executor(store)
    search, nn = tracy.make_templates(data)
    templates = search + nn
    data.rng = np.random.default_rng(17)
    queries = [templates[i % len(templates)]() for i in range(n_queries)]
    for _ in range(3):          # warm jit caches + segment readers
        ex.execute_many(queries)
    chunks = [queries[i:i + BATCH]
              for i in range(0, len(queries), BATCH)]
    # times[mode][ci] = per-query latency of chunk ci, one per round
    times: Dict[str, List[List[float]]] = {
        m: [[] for _ in chunks] for m in MODES}
    for r in range(rounds):
        for ci, chunk in enumerate(chunks):
            # the three modes run back-to-back on the SAME chunk so
            # clock drift and query-mix difficulty cancel; the order
            # rotates so position-in-triple effects cancel too
            rot = (r + ci) % len(MODES)
            for mode in MODES[rot:] + MODES[:rot]:
                times[mode][ci].append(_run_mode(mode, ex, chunk))
    # scheduler/GC noise is strictly additive, so the min over rounds
    # is the clean estimate of a chunk's true cost per mode; the gated
    # ratio is the median across chunks of those best-of-N pairs
    ratios_ds = [min(times["disabled"][ci]) / min(times["stripped"][ci])
                 for ci in range(len(chunks))]
    ratios_ed = [min(times["enabled"][ci]) / min(times["disabled"][ci])
                 for ci in range(len(chunks))]
    p50 = {m: float(np.median([t for per in v for t in per]))
           for m, v in times.items()}
    return {
        "p50_stripped_us": p50["stripped"] * 1e6,
        "p50_disabled_us": p50["disabled"] * 1e6,
        "p50_enabled_us": p50["enabled"] * 1e6,
        "disabled_over_stripped": float(np.median(ratios_ds)),
        "enabled_over_disabled": float(np.median(ratios_ed)),
        "rows": float(n_rows),
        "queries_per_round": float(n_queries),
        "rounds": float(rounds),
    }


def run_registry_cost(n: int = 200_000) -> Dict[str, float]:
    """Raw metric-op cost: ns per counter inc / histogram observe."""
    c = REGISTRY.counter("obs_bench.scratch_count")
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    inc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(n):
        REGISTRY.observe("obs_bench.scratch_s", i * 1e-6)
    obs_s = time.perf_counter() - t0
    return {"ns_per_inc": inc_s / n * 1e9,
            "ns_per_observe": obs_s / n * 1e9,
            "ops": float(n)}


def bench_json(scale: float = 1.0) -> Dict[str, Any]:
    return {
        "query": run_query_overhead(
            n_rows=max(1200, int(4000 * scale)),
            rounds=max(24, int(40 * scale))),
        "registry": run_registry_cost(n=max(20_000, int(200_000 * scale))),
    }


def csv_from_json(r: Dict[str, Any]) -> List[str]:
    """CSV rows for benchmarks/run.py from a ``bench_json`` result."""
    qr, reg = r["query"], r["registry"]
    return [
        f"obs_query_p50,{qr['p50_disabled_us']:.0f},"
        f"disabled_over_stripped={qr['disabled_over_stripped']:.3f}x;"
        f"enabled_over_disabled={qr['enabled_over_disabled']:.3f}x",
        f"obs_registry_ops,0.0,"
        f"ns_per_inc={reg['ns_per_inc']:.0f};"
        f"ns_per_observe={reg['ns_per_observe']:.0f}",
    ]


def bench(scale: float = 1.0) -> List[str]:
    return csv_from_json(bench_json(scale))


def check_baseline(result: Dict[str, Any], baseline: Dict[str, Any]
                   ) -> List[str]:
    """The obs cost contract (absolute, machine-independent ratios)."""
    errors = []
    qr = result["query"]
    ref = baseline.get("query", {})
    got = qr["disabled_over_stripped"]
    if got > 1.02:
        errors.append(
            f"tracing-off overhead above the 2% budget: {got:.3f}x "
            f"(baseline {ref.get('disabled_over_stripped', 0.0):.3f}x)")
    got = qr["enabled_over_disabled"]
    if got > 1.15:
        errors.append(
            f"tracing-on overhead above the 15% budget: {got:.3f}x "
            f"(baseline {ref.get('enabled_over_disabled', 0.0):.3f}x)")
    return errors


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run (CI)")
    ap.add_argument("--json", default=None,
                    help="write structured results to PATH ('-' = stdout)")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON to check ratios against")
    args = ap.parse_args(argv)
    scale = 0.33 if args.smoke else args.scale
    result = bench_json(scale)
    text = json.dumps(result, indent=2, sort_keys=True)
    if args.json == "-":
        print(text)
    elif args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    else:
        print(text)
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        errors = check_baseline(result, baseline)
        for e in errors:
            print(f"REGRESSION: {e}", file=sys.stderr)
        if errors:
            return 1
        print("baseline check passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
