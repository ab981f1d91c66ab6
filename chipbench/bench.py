"""The harness: find a cell's files by name, run it once, print the result.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it is data, found by name:

    chipbench/workloads/<cell>.json    the cell's programs, checks, limits
    <config file from BENCHMARK.json>  the deployment and its cuts
    chipbench/traffic/<traffic>.json   the traffic mix: its generator and
                                       parameters (rates, mesh, ...)
    chipbench/traffic/<generator>.py   the generator that runs a mix
    chipbench/metrics/<metric>.py      one reader per per-layer metric

A generator module defines ``Traffic(config, params, seed, devices)``
with ``setup()``, ``window(seconds, traced)``, ``release()`` and
``check()`` (see ``traffic/sweep.py``); `params` is the mix merged with
the cell's file.  Nothing here branches on a cell or a configuration.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (metric files carry dots
    in their names, so they are not importable as modules)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    kind: str                 # the mix's generator
    params: dict              # the traffic mix and the workload file
    config: dict              # the configuration file
    end_to_end: list          # BENCHMARK.json metric entries of this cell
    per_layer: list


def _for_cell(metrics: list, name: str) -> list:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, bench: dict | None = None,
              root: str = ROOT) -> Cell:
    if bench is None:
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 f"{entry['traffic']}.json"))
    params = dict(mix, **load_json(os.path.join(BENCH_DIR, "workloads",
                                                f"{name}.json")))
    return Cell(name=name, chips=int(entry["chips"]), kind=mix["generator"],
                params=params,
                config=load_json(os.path.join(root, cfg["file"])),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def traffic_class(generator: str):
    return load_module(os.path.join(BENCH_DIR, "traffic", f"{generator}.py"),
                       f"chipbench_traffic_{generator}").Traffic


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                       "chipbench_metric_" + name.replace(".", "_")).read


def enable_compile_cache(min_compile_secs: float = 0.0) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set,
    else at the fixed ``<checkout>/.jax_cache``.  Every program is kept,
    not only those that took over a second to compile (JAX's default),
    so a warm run compiles nothing."""
    import jax
    path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    # -1: no size floor, and no backend override of it
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileMeter:
    """Seconds XLA spent compiling (or reading the persistent cache),
    programs compiled and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.programs, self.hits


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            st = d.memory_stats() or {}
        except Exception:
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class MetricContext:
    """What a per-layer metric reader sees: the reduced device trace of
    the traced window, the program's spans on that clock, the traffic's
    counters and the chip's peaks."""

    def __init__(self, trace, counters: dict, peaks: dict):
        self.trace = trace
        self.counters = counters
        self.peaks = peaks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, record_path: str | None = None,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result object (the last line
    of standard output).  `record_path` keeps the traced window's record;
    `control` puts the reference's lower-precision run in the program's
    place for the check (calibrate.py)."""
    from chipbench import roofline, tracing
    meter = CompileMeter()
    peaks = roofline.peaks_for(devices[0])
    tr = traffic_class(cell.kind)(cell.config, cell.params, seed, devices)
    tr.setup()
    setup_s = time.perf_counter() - t_start
    secs0, progs0, hits0 = meter.snapshot()
    log(f"[setup] {setup_s:.3f} s; compile {secs0:.2f} s, {progs0} programs,"
        f" {hits0} persistent-cache hits")

    capture = tracing.Capture() if trace else None
    if capture:
        capture.start()
    try:
        res = tr.window(float(seconds), capture)
    finally:
        if capture:
            capture.stop()
    secs1, progs1, hits1 = meter.snapshot()
    log(f"[window] compiles inside the window: {progs1 - progs0} programs "
        f"({secs1 - secs0:.3f} s, {hits1 - hits0} cache hits)")
    for line in res.get("notes", []):
        log(f"[window] {line}")
    mem = memory_peak(devices)
    device = device_info(devices)
    device["memory_peak_bytes"] = mem
    tr.release()
    tr.control = control
    checks = tr.check()

    metrics = {}
    breakdown = None
    if trace:
        reduced = capture.reduce()
        if record_path:
            with open(record_path, "w") as f:
                json.dump(capture.record, f)
        ctx = MetricContext(reduced, res.get("counters", {}), peaks)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        capture.cleanup()
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    correct = all(c["ok"] for c in checks) and bool(checks)
    result = {"correct": correct, "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    for c in checks:
        log(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cache-min-compile-secs", type=float, default=0.0,
                    help="JAX's persistent-cache threshold (JAX's own "
                         "default is 1.0); for measuring set-up only")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    cache = enable_compile_cache(args.cache_min_compile_secs)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: no TPU (first device is {devices[0].platform}); "
            f"nothing was run")
        return 2
    if len(devices) < cell.chips:
        log(f"chipbench: {cell.name} needs {cell.chips} chips, found "
            f"{len(devices)}")
        return 2
    devices = devices[:cell.chips]
    log(f"[device] {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, devices=devices)
    print(json.dumps(result), flush=True)
    return 0

