"""Run one benchmark cell once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``: the store, its guarantees
and the limits of the comparison) and a traffic file
(``bench/traffic/<traffic>.json``, read by ``bench/generator.py``).

Set-up, from process start: the durable store built through the
``Database`` facade from ``--seed``, the traffic's standing subscriptions
registered on it (view selection and backfill included), and
``warmup_blocks`` blocks of the cell's own traffic, which compile or load
from JAX's persistent cache every program the window uses.  The window
then drives the facade (``put``, ``delete``, ``execute``, ``advance``) in
a closed loop for ``--seconds``.  After it the device's peak memory is
read, the store is closed, and every answer the window produced is
compared with the float64 reference in ``bench/reference.py``: each
query's, each SYNC refresh an advance returned, and every ASYNC
subscription's ``latest`` after each advance.

With ``--trace 0`` the result's metrics are the cell's end-to-end ones;
with ``--trace 1`` the window also runs under the JAX profiler and the
program's span tracer, and the metrics are the cell's per-layer ones,
each read by ``bench/metrics/<metric>.py``.  The last line of standard
output is one JSON object; the numbers compared end standard error.

Exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402
from bench import reference as ref_lib  # noqa: E402
from bench.generator import Generator  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
MAX_FAILURES = 8


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


def bytes_written() -> int:
    """Bytes this process has caused to be written to storage."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def process_start() -> float:
    """When this process started, on the ``time.time()`` clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


# ---------------------------------------------------------------------------
# what a cell is made of, found by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT,
              bench: Optional[Dict] = None) -> Dict[str, Any]:
    """The cell ``name`` of ``bench`` (by default ``BENCHMARK.json``)
    with its configuration, traffic, and the metrics it reports."""
    bench = bench or load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def metric_reader(name: str, root: Path = ROOT):
    """``read(records)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class Compiles:
    """Backend compiles (persistent-cache loads included) and cache hits,
    counted from JAX's monitoring events."""

    count = 0
    seconds = 0.0
    cache_hits = 0

    @classmethod
    def listen(cls, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            cls.count += 1
            cls.seconds += duration

    @classmethod
    def hit(cls, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            cls.cache_hits += 1

    @classmethod
    def snapshot(cls) -> Dict[str, float]:
        return {"compiles": cls.count, "compile_s": cls.seconds,
                "cache_hits": cls.cache_hits}


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or, where
    that is unset, at the fixed ``.jax_cache/`` of the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_duration_secs_listener(Compiles.listen)
    jax.monitoring.register_event_listener(Compiles.hit)
    return path


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Run:
    def __init__(self, found: Dict[str, Any], seed: int, seconds: float,
                 trace: bool, root: Path = ROOT):
        self.cell = found["cell"]
        self.config = found["config"]
        self.traffic = found["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.root = root
        self.gen = Generator(self.traffic, self.config, self.seed)
        self.path = root / self.config["store_path"] / self.cell["name"]
        self.log: List[tuple] = []       # op log replayed by the reference
        self.subs: List[tuple] = []      # (label, kind, spec, subscription)
        self.info: Dict[str, Any] = {}

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        shutil.rmtree(self.path, ignore_errors=True)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        t0 = time.perf_counter()
        self.db, self.table = program.open_table(self.config, str(self.path))
        for pks, batch in self.gen.preload():
            self.table.put(pks, batch)
            self.log.append(("write", pks, batch))
        self.table.flush()
        t1 = time.perf_counter()
        for i, (name, kind, interval, spec) in enumerate(
                self.gen.subscriptions()):
            self.subs.append((f"sub {name}#{i} {kind}", kind, spec,
                              program.subscribe(self.table, spec, kind,
                                                interval)))
        t2 = time.perf_counter()
        store = self.table.store.metrics
        flush0 = (store["flush_s"], store["flushes"], store["compactions"])
        warm: Dict[str, List[float]] = {}
        for _ in range(self.traffic["warmup_blocks"]):
            for op in self.gen.block():
                t = time.perf_counter()
                self.do(op, record=False)
                key = op[1] if op[0] == "query" else op[0]
                warm.setdefault(key, []).append(time.perf_counter() - t)
        self.info.update(
            preload_s=t1 - t0, subscribe_s=t2 - t1,
            warmup_s=time.perf_counter() - t2,
            warmup_flush_s=store["flush_s"] - flush0[0],
            warmup_flushes=store["flushes"] - flush0[1],
            warmup_compactions=store["compactions"] - flush0[2],
            warmup_ops={k: [len(v), sum(v), max(v)]
                         for k, v in sorted(warm.items())},
            rows=self.table.n_rows,
            segments=len(self.table.store.segments))

    # ---------------------------------------------------------------- ops
    def do(self, op: tuple, record: bool) -> tuple:
        """Run one op through the facade; returns (kind, seconds)."""
        kind = op[0]
        if kind == "query":
            _, name, spec = op
            query = program.to_query(spec)
            t0 = time.perf_counter()
            result, _ = self.table.execute(query)
            dt = time.perf_counter() - t0
            if record:
                self.log.append(("answer", spec, result, f"query {name}"))
            return ("query", dt)
        if kind == "write":
            _, ins, ins_b, upd, upd_b, dele = op
            t0 = time.perf_counter()
            self.table.put(ins, ins_b)
            self.table.put(upd, upd_b)
            self.table.delete(dele)
            dt = time.perf_counter() - t0
            self.log += [("write", ins, ins_b), ("write", upd, upd_b),
                         ("delete", dele)]
            return ("write", dt)
        if kind == "advance":
            t0 = time.perf_counter()
            due = self.table.advance(op[1])
            dt = time.perf_counter() - t0
            if record:
                self.log_subscriptions(due)
            return ("advance", dt)
        raise ValueError(f"unknown op {kind!r}")

    def log_subscriptions(self, due: Dict[int, Any]) -> None:
        """Log what the subscribers see after an advance: each SYNC
        refresh it returned, exact at this point of the log, and every
        ASYNC subscription's ``latest``, which has to follow every
        acknowledged write."""
        for what, kind, spec, sub in self.subs:
            got = due.get(sub.rid)
            if kind == "async":
                got = [] if sub.latest is None else sub.latest
            if got is not None:
                self.log.append(("answer", spec, got, what))

    # ------------------------------------------------------------- window
    def window(self) -> Dict[str, Any]:
        import jax
        lat: Dict[str, List[float]] = {"query": [], "write": [],
                                       "advance": []}
        by_template: Dict[str, List[float]] = {}
        attempted = failed = rows_acked = 0
        spans = SpanTotals()
        before = program.counters(self.table)
        c0 = Compiles.snapshot()
        ops = self.gen.ops()
        if self.trace:
            tdir = self.root / ".scratch" / "trace" / self.cell["name"]
            shutil.rmtree(tdir, ignore_errors=True)
            program.set_tracing(True)
            program.take_spans()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host TraceMe events only
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() - t0 < self.seconds \
                    and failed < MAX_FAILURES:
                op = next(ops)
                attempted += 1
                label = op[1] if op[0] == "query" else ""
                try:
                    with jax.profiler.TraceAnnotation(
                            f"bench.{op[0]}{'.' + label if label else ''}"):
                        kind, dt = self.do(op, record=True)
                except Exception:          # noqa: BLE001 - counted, shown
                    failed += 1
                    log(f"op {op[0]} {label} failed:\n"
                        + traceback.format_exc())
                    continue
                lat[kind].append(dt)
                if kind == "write":
                    rows_acked += len(op[1]) + len(op[3]) + len(op[5])
                if label:
                    by_template.setdefault(label, []).append(dt)
                if self.trace:
                    spans.add(program.take_spans())
            window_s = time.perf_counter() - t0
        after = program.counters(self.table)
        c1 = Compiles.snapshot()
        out = {"window_s": window_s, "lat": lat, "by_template": by_template,
               "attempted": attempted, "failed": failed,
               "rows_acked": rows_acked,
               "counters": {"before": before, "after": after},
               "compiles": {k: c1[k] - c0[k] for k in c0},
               "spans": spans.totals()}
        if self.trace:
            jax.profiler.stop_trace()
            program.set_tracing(False)
            out["trace_dir"] = str(tdir)
        return out

    # -------------------------------------------------------------- check
    def check(self) -> tuple:
        """Replay the op log against the float64 reference; returns
        (correct, checks, seconds)."""
        t0 = time.perf_counter()
        ref = ref_lib.Reference(self.config["dim"],
                                cap=self.gen.next_pk + 1)
        entries = (e if e[0] != "answer" else
                   ("answer", e[1], program.rows_of(e[2]), e[3])
                   for e in self.log)
        tally = ref_lib.replay(ref, entries, ref_lib.Tally())
        ok, checks = tally.verdict(ref_lib.limits_for(self.config))
        if tally.worst:
            log(f"largest departures first seen at: {tally.worst}")
        return ok, checks, time.perf_counter() - t0

    def close(self) -> None:
        self.db.close()
        self.db = self.table = None
        gc.collect()
        shutil.rmtree(self.path, ignore_errors=True)


class SpanTotals:
    """Self time and count per span name, folded in as spans finish."""

    def __init__(self):
        self.self_s: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    def add(self, roots) -> None:
        for root in roots:
            for sp in root.walk():
                child = sum(c.dur for c in sp.children)
                self.self_s[sp.name] = self.self_s.get(sp.name, 0.0) \
                    + max(0.0, sp.dur - child)
                self.count[sp.name] = self.count.get(sp.name, 0) + 1

    def totals(self) -> Dict[str, Dict]:
        return {"self_s": self.self_s, "count": self.count}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(w: Dict[str, Any], setup_s: float) -> Dict[str, float]:
    """Every end-to-end number this window can give.  The ingest rate is
    the rows the window's writes acknowledged (inserted, updated and
    deleted) over the time those writes took, inline flush, compaction
    and view maintenance included: in a closed loop the window's length
    would fold the queries' speed into it."""
    out = {"setup_s": setup_s}
    q = w["lat"]["query"]
    if q:
        out["query_p50_ms"] = statistics.median(q) * 1e3
        out["query_p95_ms"] = percentile(q, 95) * 1e3
    if w["lat"]["write"]:
        out["ingest_rows_per_s"] = w["rows_acked"] / sum(w["lat"]["write"])
    if w["lat"]["advance"]:
        out["cq_refresh_p50_ms"] = statistics.median(w["lat"]["advance"]) \
            * 1e3
    return out


def records(w: Dict[str, Any], device: Optional[Dict]) -> Dict[str, Any]:
    """What the per-layer readers read."""
    return {"queries": len(w["lat"]["query"]),
            "rows_acked": w["rows_acked"],
            "advances": len(w["lat"]["advance"]),
            "by_template": w["by_template"],
            "window_s": w["window_s"],
            "counters": w["counters"],
            "spans": w["spans"],
            "device": device}


def device_info(jax, chips: int) -> Dict[str, Any]:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             overrides: Optional[Dict[str, Any]] = None,
             t_start: Optional[float] = None,
             bench: Optional[Dict] = None) -> Optional[Dict[str, Any]]:
    """One run of one cell; returns the result object, or None when the
    device is not what the cell asks for.  ``overrides`` replaces keys of
    the configuration and traffic, and ``bench`` stands in for
    ``BENCHMARK.json`` (tests run tiny stores on the CPU)."""
    t_start = process_start() if t_start is None else t_start
    found = find_cell(workload, root, bench)
    for part, values in (overrides or {}).items():
        found[part].update(values)
    program.import_program()
    import jax
    from bench import peaks
    chips = int(found["cell"]["chips"])
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu" or len(devs) < chips:
            log(f"bench: {workload} needs {chips} TPU chip(s); JAX found "
                f"{len(devs)} {devs[0].platform!r} device(s)")
            return None
        peaks.for_kind(devs[0].device_kind)
    cache = enable_compile_cache(root)
    run = Run(found, seed, seconds, trace, root)
    run.setup()
    c_setup = Compiles.snapshot()
    setup_s = time.time() - t_start
    w = run.window()
    device = device_info(jax, chips)
    run.close()
    trace_summary = None
    if trace:
        from bench import trace_reduce
        xplane = trace_reduce.find_xplane(w["trace_dir"])
        if xplane is not None:
            trace_summary = trace_reduce.reduce(trace_reduce.load(xplane))
            device["busy_s"] = trace_summary["busy_s"]
            device["window_s"] = trace_summary["window_s"]
    ok, checks, ref_s = run.check()
    ok = ok and w["failed"] == 0
    if trace:
        rec = records(w, trace_summary)
        metrics = {}
        for m in found["per_layer"]:
            v = metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in found["end_to_end"] if m["name"] in e2e}
    result = {"correct": bool(ok), "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if trace_summary is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           trace_summary["device_ops"][:10]],
            "idle_gaps": [[n, s] for n, s in trace_summary["idle_gaps"]]}
    result["checks"] = checks
    summary = {"workload": workload, "seed": seed, "compile_cache": cache,
               "setup_s": setup_s, **run.info,
               "setup_compiles": c_setup, "window_compiles": w["compiles"],
               "window_s": w["window_s"],
               "ops": {k: len(v) for k, v in w["lat"].items()},
               "rows_acked": w["rows_acked"],
               "write_s": sum(w["lat"]["write"]),
               "query_ms_by_template": {
                   t: [len(v), statistics.median(v) * 1e3]
                   for t, v in sorted(w["by_template"].items())},
               "reference_s": ref_s,
               "bytes_written": bytes_written(),
               "counters": w["counters"]}
    if trace_summary is not None:
        summary["idle_by_annotation"] = trace_summary["idle_by_annotation"]
        summary["device_ops_all"] = trace_summary["device_ops"][:40]
    log("bench-summary " + json.dumps(summary, default=str))
    for line in ref_lib.describe(checks):
        log(line)
    return result


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    if result is None:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
