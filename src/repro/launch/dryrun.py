import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The XLA_FLAGS line above MUST run before any jax import (jax locks the
# device count on first init) and is deliberately NOT set globally —
# smoke tests and benchmarks see 1 device.
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, with no tensor allocation (ShapeDtypeStruct
inputs only).

Per cell this records, from the compiled per-device module:
  * memory_analysis()  — proves the cell fits 16 GiB/chip
  * cost_analysis()    — HLO FLOPs / bytes for the roofline compute and
                         memory terms
  * parsed HLO         — collective wire bytes (hlo_stats) for the
                         collective term, plus an op histogram

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --arch rescal-dense-3tb --multi-pod
  python -m repro.launch.dryrun --all --out artifacts/dryrun   # subprocesses
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, RESCAL_CONFIGS, SHAPES, RescalConfig,
                           get_config, input_specs)
from repro.dist import compat
from repro.configs.base import ShapeSpec
from repro.dist import sharding as shd
from repro.dist.engine import (DistRescalConfig, make_dist_step,
                               make_dist_step_sparse, make_ensemble_step,
                               make_ensemble_step_sparse)
from repro.launch import hlo_costs, hlo_stats
from repro.launch.mesh import CHIP_HBM_BYTES, make_production_mesh
from repro.models import model as model_lib
from repro.optim import AdamW
from repro.train import serve_step as serve_lib
from repro.train import train_step as train_lib

RESCAL_SHAPE = ShapeSpec("mu_iter", "rescal", 0, 0)


def _sds_with(shardings, shapes):
    """Attach shardings to a ShapeDtypeStruct tree."""
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _batch_sds(mesh, batch_shapes):
    sh = train_lib.batch_shardings(mesh, batch_shapes)
    return _sds_with(sh, batch_shapes)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_lm_cell(cfg, spec: ShapeSpec, mesh, *, remat=True,
                  moe_impl="einsum"):
    specs = input_specs(cfg, spec)
    if spec.kind == "train":
        opt = AdamW()
        fn = train_lib.make_train_step(cfg, mesh, optimizer=opt, remat=remat,
                                       moe_impl=moe_impl, donate=False)
        state = train_lib.state_shapes(cfg, opt)
        batch = _batch_sds(mesh, specs["batch"])
        return fn.lower(state, batch)
    from repro.models import transformer
    params = _sds_with(serve_lib.params_shardings(mesh, cfg),
                       transformer.param_shapes(cfg))
    if spec.kind == "prefill":
        fn = serve_lib.make_prefill_step(cfg, mesh, moe_impl=moe_impl)
        batch = _batch_sds(mesh, specs["batch"])
        return fn.lower(params, batch)
    # decode: cache buffers donated (production serving aliases the cache
    # in place; memory_analysis counts the alias once)
    fn = serve_lib.make_serve_step(cfg, mesh, moe_impl=moe_impl,
                                   donate=True)
    cache = _sds_with(shd.cache_shardings(mesh, specs["cache"]),
                      specs["cache"])
    tokens = _batch_sds(mesh, specs["tokens"])
    return fn.lower(params, cache, tokens, specs["pos"])


def lower_rescal_cell(rcfg: RescalConfig, mesh, *, multi_pod: bool,
                      ensemble_r: int = 2, comm_dtype: str | None = None):
    dcfg = DistRescalConfig(schedule=rcfg.schedule, comm_dtype=comm_dtype)
    f32 = jnp.float32
    n, m, k = rcfg.n, rcfg.m, rcfg.k
    A = jax.ShapeDtypeStruct((n, k), f32)
    R = jax.ShapeDtypeStruct((m, k, k), f32)
    if not rcfg.sparse:
        X = jax.ShapeDtypeStruct((m, n, n), f32)
        if multi_pod:
            A_e = jax.ShapeDtypeStruct((ensemble_r, n, k), f32)
            R_e = jax.ShapeDtypeStruct((ensemble_r, m, k, k), f32)
            fn = make_ensemble_step(mesh, dcfg, iters=1)
            return fn.lower(X, A_e, R_e)
        fn = make_dist_step(mesh, dcfg, iters=1)
        return fn.lower(X, A, R)
    # sparse: balanced BCSR shards
    g = mesh.shape["data"]
    bs = rcfg.block_size
    nb = n // bs
    nnzb_total = max(int(nb * nb * rcfg.block_density), g * g)
    nnzb_loc = max(nnzb_total // (g * g), 1)
    data = jax.ShapeDtypeStruct((g, g, m, nnzb_loc, bs, bs), f32)
    idx = jax.ShapeDtypeStruct((g, g, nnzb_loc), jnp.int32)
    if multi_pod:
        A_e = jax.ShapeDtypeStruct((ensemble_r, n, k), f32)
        R_e = jax.ShapeDtypeStruct((ensemble_r, m, k, k), f32)
        fn = make_ensemble_step_sparse(mesh, dcfg, n=n, iters=1)
        return fn.lower(data, idx, idx, A_e, R_e)
    fn = make_dist_step_sparse(mesh, dcfg, n=n, iters=1)
    return fn.lower(data, idx, idx, A, R)


def rescal_model_flops(rcfg: RescalConfig) -> float:
    """Useful FLOPs of one MU iteration (both X-sided products dominate)."""
    n, m, k = rcfg.n, rcfg.m, rcfg.k
    if rcfg.sparse:
        nb = n // rcfg.block_size
        nnz = (int(nb * nb * rcfg.block_density)
               * rcfg.block_size ** 2)
        x_terms = 4.0 * m * nnz * k
    else:
        x_terms = 4.0 * m * float(n) * n * k
    small = 8.0 * m * n * k * k + 6.0 * m * k ** 3
    return x_terms + small


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             remat: bool = True, moe_impl: str = "einsum",
             rescal_schedule: str | None = None,
             rescal_comm_dtype: str | None = None) -> dict:
    cfg = get_config(arch)
    if rescal_schedule and isinstance(cfg, RescalConfig):
        cfg = dataclasses.replace(cfg, schedule=rescal_schedule)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    base = {"arch": arch, "shape": shape,
            "mesh": "x".join(str(s) for s in mesh.devices.shape),
            "devices": n_dev, "multi_pod": multi_pod}

    if isinstance(cfg, RescalConfig):
        spec = RESCAL_SHAPE
        t0 = time.time()
        lowered = lower_rescal_cell(cfg, mesh, multi_pod=multi_pod,
                                    comm_dtype=rescal_comm_dtype)
        model_fl = rescal_model_flops(cfg)
    else:
        spec = SHAPES[shape]
        ok, reason = cfg.supports(spec)
        if not ok:
            return dict(base, skipped=reason)
        t0 = time.time()
        lowered = lower_lm_cell(cfg, spec, mesh, remat=remat,
                                moe_impl=moe_impl)
        model_fl = model_lib.model_flops(cfg, spec)
        if spec.kind == "train":
            model_fl *= 1.0   # fwd+bwd already in 6ND

    compiled = lowered.compile()
    compile_s = time.time() - t0
    cost = hlo_costs.xla_cost_analysis(compiled)
    # normalized by dist.compat; None = backend reported no
    # memory analysis — surfaced loudly below, never claimed as 0 bytes
    mem = compat.program_memory(compiled)
    hlo = compiled.as_text()
    loop_aware = hlo_costs.analyze(hlo)     # trip-count-corrected
    coll = loop_aware["collectives"]
    ops = hlo_stats.op_histogram(hlo)

    if mem is None:
        print(f"WARNING: backend reported no memory analysis for "
              f"{arch}/{shape}; the 16-GiB fit check cannot run",
              file=sys.stderr)
        memory = None
    else:
        memory = dict(mem,
                      fits_16gib=bool(mem["total"] <= CHIP_HBM_BYTES))
    return dict(
        base,
        skipped=False,
        kind=spec.kind,
        compile_s=round(compile_s, 1),
        flops_per_device=loop_aware["flops"],
        bytes_per_device=loop_aware["bytes"],
        xla_flops_raw=cost.get("flops", 0.0),     # while bodies counted 1x
        xla_bytes_raw=cost.get("bytes accessed", 0.0),
        model_flops_global=model_fl,
        memory=memory,
        collectives=coll,
        ops=ops,
    )


# ---------------------------------------------------------------------------
# CLI / batch driver
# ---------------------------------------------------------------------------

def all_cells() -> list[tuple[str, str]]:
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    cells += [(r, "mu_iter") for r in RESCAL_CONFIGS]
    return cells


def _run_subprocess(arch: str, shape: str, multi_pod: bool, out_dir: str,
                    timeout: int = 3600) -> str:
    tag = "multipod" if multi_pod else "pod"
    os.makedirs(os.path.join(out_dir, tag), exist_ok=True)
    out = os.path.join(out_dir, tag, f"{arch}__{shape}.json")
    if os.path.exists(out):
        return f"cached {out}"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", out]
    if multi_pod:
        cmd.append("--multi-pod")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        err = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
               "error": r.stderr[-4000:]}
        with open(out, "w") as f:
            json.dump(err, f, indent=1)
        return f"FAILED {arch} {shape} ({tag})"
    return f"ok {arch} {shape} ({tag})"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", default="mu_iter")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--moe-impl", default="einsum",
                    choices=("einsum", "scatter", "dense"))
    ap.add_argument("--rescal-schedule", default=None,
                    choices=(None, "batched", "sliced"))
    ap.add_argument("--rescal-comm-dtype", default=None)
    args = ap.parse_args()

    if args.all:
        out_dir = args.out or "artifacts/dryrun"
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        jobs = [(a, s, mp) for mp in meshes for (a, s) in all_cells()]
        with ThreadPoolExecutor(max_workers=args.jobs) as ex:
            for msg in ex.map(lambda j: _run_subprocess(
                    j[0], j[1], j[2], out_dir), jobs):
                print(msg, flush=True)
        return

    stats = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                     remat=not args.no_remat, moe_impl=args.moe_impl,
                     rescal_schedule=args.rescal_schedule,
                     rescal_comm_dtype=args.rescal_comm_dtype)
    js = json.dumps(stats, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    print(js)
    if not stats.get("skipped") and stats.get("memory") is not None:
        print(f"\nmemory/device: {stats['memory']['total']/2**30:.2f} GiB, "
              f"peak {stats['memory']['peak']/2**30:.2f} GiB "
              f"(fits 16 GiB: {stats['memory']['fits_16gib']})",
              file=sys.stderr)


if __name__ == "__main__":
    main()
