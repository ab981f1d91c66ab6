#!/usr/bin/env python3
"""Readings that set the benchmark's limits, on the chip.

The benchmark's own runs never call this.  Each subcommand runs in one
process, so the programs compile (or load from the cache) once:

  fit    --workload W --seeds 1,2,3 [--variants default,bf16]
         sweep cells: per seed, one sweep of the program and the
         reference's run of every member in each variant (default: the
         cell's reference and control); prints each member's residuals,
         the program's selected k and the k the plain selection rule
         picks from each side's members.  The limits come from these.
  trace  --workload W --seed N --seconds S --out PATH
         one traced run of the cell, keeping the flattened trace record.

Each prints JSON lines; `--log PATH` appends them to a file as well.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import bench  # noqa: E402


def emit(log, rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if log:
        with open(log, "a") as f:
            f.write(line + "\n")


def cmd_fit(args, cell, devices) -> None:
    variants = (args.variants.split(",") if args.variants else
                [cell.params["reference"], cell.params["control"]])
    for seed in args.seeds:
        t0 = time.perf_counter()
        tr = bench.traffic_class(cell.kind)(cell.config, cell.params, seed,
                                            devices)
        tr.setup()                 # one whole sweep; its members are read
        t1 = time.perf_counter()
        rows, factors = tr.members_and_fits(variants)
        t2 = time.perf_counter()
        plain = {who: tr.plain_selection(by_k)
                 for who, by_k in factors.items()}
        emit(args.log, {"cmd": "fit", "workload": cell.name, "seed": seed,
                        "k_selected": tr.k_selected,
                        "s_min": [float(v) for v in tr.s_min],
                        "plain_k": {w: sorted(k) for w, (k, _) in
                                    plain.items()},
                        "plain_s_min": {w: s for w, (_, s) in plain.items()},
                        "members": rows, "setup_s": t1 - t0,
                        "reference_s": t2 - t1,
                        "selection_s": time.perf_counter() - t2})
        del tr


def cmd_trace(args, cell, devices) -> None:
    res = bench.run_cell(cell, args.seeds[0], args.seconds, True,
                         t_start=time.perf_counter(), devices=devices,
                         record_path=args.out)
    emit(args.log, {"cmd": "trace", "workload": cell.name, "result": res})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cmd", choices=("fit", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--variants", default="")
    ap.add_argument("--out")
    ap.add_argument("--log")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        bench.log("calibrate: no TPU; nothing was run")
        return 2
    devices = devices[:cell.chips]
    {"fit": cmd_fit, "trace": cmd_trace}[args.cmd](args, cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
