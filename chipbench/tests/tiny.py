"""Small versions of the benchmark's cells, for tests on the CPU.

Each keeps its cell's traffic, metrics and checks and changes only sizes
(and, on the CPU, nothing of the arithmetic's kind)."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import bench  # noqa: E402

# Cells whose files are in chipbench/ but that BENCHMARK.json does not list
# yet (PERF.md, Open questions): their entries, as it will list them.
PENDING = [
    {"name": "dense_grid4", "config": "rescal-dense-3tb",
     "traffic": "sweep_grid2x2", "chips": 4},
]

SIZES = {
    "dense": {"n": 384},
    "bcsr": {"n": 768, "m": 4, "block_size": 16},
}


def tiny_cell(name: str, **params) -> "bench.Cell":
    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [w for w in PENDING if w["name"] not in listed]
    cell = bench.load_cell(name, spec)
    cfg = dict(cell.config)
    cfg.update(SIZES[cfg["operand"]["kind"]])
    if "mesh" in cell.params:            # n is one chip's block side
        cfg["n"] //= cell.params["mesh"]["shape"][0]
    cell.config = cfg
    cell.params = dict(cell.params, **params)
    return cell


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
