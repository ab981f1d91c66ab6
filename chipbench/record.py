#!/usr/bin/env python3
"""Run one cell once, traced, and keep what its result line leaves out.

    python3 chipbench/record.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

Runs the cell as ``run.py --trace 1`` does (``bench.run_cell``), prints its
result line last, and writes ``<dir>/<cell>-<seed>.json``:

- ``result``: the result line;
- ``breakdown``: ``xspace.Window.breakdown()`` of the window (ops by self
  time and scope; idle gaps split among the host spans covering them);
- ``uncovered_idle_share``: the share of the first chip's idle time that no
  program span covers;
- ``compiles``: every program the process obtained, by kind, as
  ``dist.compat.capture_compiles`` reported it, beside JAX's own counters
  (``bench.CompileMeter``): compiled + cache reads must equal its programs,
  and cache reads its hits;
- ``cut``: the window's ``xspace`` record cut to ``CUT_MS`` around the
  first ``sched/reduce`` span (``record``; host spans kept whole), with
  the traced window's bounds and the cell's unit programs: the layout of
  ``tests/data/xspace/``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import bench, tracing, xspace  # noqa: E402

# the cut of the window kept for tests/data/xspace/: a unit's end, one k's
# reduction and the next unit's start, in under 150 KB
CUT_MS = 150.0


def cut_around(xrec: dict, name: str, ms: float) -> dict | None:
    """`xrec` cut to `ms` around the first host span `name`: 40% before
    its start, the rest after; None without such a span."""
    first = next((s for s in sorted(xrec["host_spans"], key=lambda r: r[1])
                  if s[0] == name), None)
    if first is None:
        return None
    t0 = float(first[1]) - 0.4 * ms * 1e6
    return xspace.cut(xrec, t0, t0 + ms * 1e6)


def record_cell(cell, seed: int, seconds: float, *, devices,
                t_start: float) -> dict:
    from repro.dist.compat import capture_compiles
    meter = bench.CompileMeter()
    kinds: dict[str, int] = {}
    kept: list[str] = []
    cleanup = tracing.Capture.cleanup
    # run_cell deletes the profile once its readers have run: keep it
    tracing.Capture.cleanup = lambda capture: kept.append(capture.dir)
    fd, old = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        with capture_compiles(
                sink=lambda _, kind: kinds.update({kind: kinds.get(kind, 0)
                                                   + 1})):
            result = bench.run_cell(cell, seed, seconds, True,
                                    t_start=t_start, devices=devices,
                                    record_path=old)
        with open(old) as f:
            spans = json.load(f)["spans"]
        path = next(p for d in kept for p in glob.glob(
            os.path.join(d, "**", "*.xplane.pb"), recursive=True))
        raw = xspace.load(path)
    finally:
        tracing.Capture.cleanup = cleanup
        for d in kept:
            shutil.rmtree(d, ignore_errors=True)
        os.unlink(old)
    xrec = xspace.window_of(raw, (n for n, _, _ in spans))
    brk = xspace.Window(xrec).breakdown()
    idle = sum(brk["idle_by_span"].values())
    secs, progs, hits = meter.snapshot()
    return {"result": result, "breakdown": brk,
            "uncovered_idle_share": (brk["idle_by_span"].get(xspace.NO_SPAN,
                                                             0.0) / idle
                                     if idle else None),
            "compiles": {"by_kind": kinds, "meter_programs": progs,
                         "meter_cache_hits": hits},
            "cut": {"window": xrec["window"],
                    "unit_programs": cell.params["unit_programs"],
                    "record": cut_around(xrec, "sched/reduce", CUT_MS)}}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        bench.log(f"record: {cell.name} needs {cell.chips} TPU chips; "
                  f"found {devices}")
        return 2
    out = record_cell(cell, args.seed, args.seconds,
                      devices=devices[:cell.chips], t_start=t_start)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"{cell.name}-{args.seed}.json"), "w") as f:
        json.dump(out, f)
    bench.log(f"[record] compiles {out['compiles']}; uncovered idle share "
              f"{out['uncovered_idle_share']}; idle by span "
              f"{json.dumps(out['breakdown']['idle_by_span'])}")
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
