#!/usr/bin/env python3
"""Run the RESCALk main path on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the 2x2 mesh sweep only

One process drives the chip; it starts no other.  With one chip the
phases run in this order, and any failure raises (exit code != 0):

  device  the first device must be a TPU, or the script exits before
          any work.
  dense   a planted-rank dense tensor (m = 20, as the paper's §6.5 dense
          run) through ``SweepScheduler`` in batched mode; the planted
          k must be selected.
  sparse  a planted-rank block-sparse tensor (n = 49152, bs = 128) through
          ``SweepScheduler`` on the fused Pallas kernels; the planted k
          must be selected with no kernel fallback and a Pallas kernel in
          the compiled unit program.  One ``bcsr_xa_xta`` call is checked
          against its jnp reference.
  serve   the sparse phase's ``FactorBundle`` saved, reloaded and served
          by ``ServeEngine`` on the ``score_topk`` kernel, ``sro`` and
          ``sor`` zipf queries checked against ``ref_score_topk``.

``--four-chips`` runs only the dense sweep on a 2x2 ("data", "model")
mesh and its one-device twin, which replays the mesh's blocked noise.

Every diagnostic goes to an earlier line; the last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax_cache``; each phase line reports its
compile seconds and cache hits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

GiB = 2 ** 30
PLANTED_K = 5
# k_min..k_max around the planted rank: the threshold rule must find k=5
# stable and k=6 not
K_MIN, K_MAX = 4, 6
M = 20                   # relations, as RESCAL_DENSE_3TB (rescal_paper.py)
MEMBERS = 2              # perturbation ensemble size r per unit
ITERS = 200              # MU iterations per member
DENSE_N = 6144           # batched unit program peak ~11.3 GiB at k=6, r=2
SPARSE_N, SPARSE_BS = 49152, 128
SPARSE_COMMUNITY_BLOCKS = 8     # -> (5 * 8)^2 = 1600 stored blocks, 2 GB
QUERIES = 300

# Tolerances.  XLA runs an f32 matmul on the TPU at its default precision:
# one bf16 pass, each input rounded to 8 significant bits (relative error
# <= 2^-9 per input, <= 2^-8 per product).  The Pallas kernels run their
# products at fp32 contract precision (Precision.HIGHEST), and the
# references below run under default_matmul_precision("highest").
#
# Kernel vs reference, same f32 operands: both sides sum fp32 products in
# f32, in different orders.  Every term is non-negative, so the worst-case
# reordering error over the <= bs * (stored blocks per block-row) = 5120
# terms of one output element is 5120 * 2^-24 < 2^-11 of that element,
# hence of the largest output.
KERNEL_RTOL = 2.0 ** -11
# Served score vs exact score: the engine forms V = A[anchor] @ R_q with
# a default-precision einsum (every term non-negative, so V carries at most
# the 2^-8 product error, relative); the kernel's fp32 scoring adds
# ~2^-20.  Twice the product bound covers both.
SCORE_RTOL = 2.0 ** -7
# Mesh vs one-device twin: the same bf16-rounded products, reduced in a
# different order (psum over the 2x2 grid vs one device).  Once an f32
# partial sum differs in its last bit, a factor entry can round to a
# different bf16 value: a 2^-8 relative kick in that entry.  MU contracts
# toward the same fixed point from both sides, so the member errors at the
# end differ by a small multiple of that kick: allow 4 x 2^-8 relative.
TWIN_RTOL = 2.0 ** -6


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Seconds XLA spent compiling (or reading the persistent cache) and
    the persistent-cache hits, from JAX's monitoring events."""

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


class Phase:
    """Prints one timing line for a phase: wall, compile and the rest."""

    def __init__(self, name: str, meter: CompileMeter):
        self.name, self.meter = name, meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        wall = time.perf_counter() - self.t0
        secs, progs, hits = (a - b for a, b in
                             zip(self.meter.snapshot(), self.c0))
        status = "FAILED" if exc_type else "ok"
        print(f"[{self.name}] {status}: wall {wall:.1f} s = compile "
              f"{secs:.1f} s ({progs} programs, {hits} persistent-cache "
              f"hits) + run {wall - secs:.1f} s", flush=True)
        return False


def summary_lines(tag: str, res) -> None:
    for line in res.summary().splitlines():
        print(f"[{tag}] {line}")


def planted_bcsr(key, *, n: int, m: int, k: int, bs: int,
                 community_blocks: int, noise: float = 0.01):
    """A block-sparse tensor whose support is exactly that of a planted
    non-negative rank-k RESCAL model, so the planted k is recoverable:
    community a owns `community_blocks` contiguous block-rows, entities
    outside every community have no support, and the stored blocks are
    the community-pair blocks.  Built on the device from `key`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sparse import BCSR

    nb = n // bs
    stride = nb // k
    owner = np.full(nb, k, np.int32)           # k = no community
    for a in range(k):
        owner[a * stride:a * stride + community_blocks] = a
    blocks = np.flatnonzero(owner < k).astype(np.int32)
    rows = np.repeat(blocks, blocks.size)      # row-major sorted
    cols = np.tile(blocks, blocks.size)
    ka, kr, kn = jax.random.split(key, 3)
    member = jax.nn.one_hot(jnp.asarray(np.repeat(owner, bs)), k)
    A = member * jax.random.uniform(ka, (n, 1), jnp.float32, 0.5, 1.5)
    R = jax.random.exponential(kr, (m, k, k), jnp.float32)
    Ab = A.reshape(nb, bs, k)
    data = jnp.einsum("zak,mkl,zbl->mzab", Ab[rows], R, Ab[cols])
    data = data * jax.random.uniform(kn, data.shape, jnp.float32,
                                     1.0 - noise, 1.0 + noise)
    return BCSR(data=data, block_rows=jnp.asarray(rows),
                block_cols=jnp.asarray(cols), n=n)


def dense_operand(n: int, seed: int):
    from repro.io import VirtualSpec, virtual_dense_full
    spec = VirtualSpec(kind="dense", n=n, m=M, k=PLANTED_K, noise=0.01,
                       seed=seed)
    return spec, virtual_dense_full(spec)


def sweep_config(seed: int, kernel=None):
    from repro.selection import RescalkConfig
    return RescalkConfig(k_min=K_MIN, k_max=K_MAX, n_perturbations=MEMBERS,
                         rescal_iters=ITERS, seed=seed, kernel=kernel)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_dense(dev, seed: int) -> None:
    from repro.core.rescal import EPS_DEFAULT
    from repro.dist.compat import device_memory_stats, program_memory
    from repro.selection import SweepScheduler
    from repro.selection.ensemble import _batched_members, unit_keys

    spec, X = dense_operand(DENSE_N, seed)
    X.block_until_ready()
    print(f"[dense] operand {spec.spec_string()}: {tuple(X.shape)} f32, "
          f"{X.nbytes / GiB:.2f} GiB; r={MEMBERS} ks={K_MIN}..{K_MAX} "
          f"iters={ITERS}", flush=True)
    cfg = sweep_config(seed)
    sched = SweepScheduler(cfg)
    res = sched.run(X)
    summary_lines("dense", res)
    for u in sched.report.units:
        print(f"[dense] unit {u.uid}: {u.seconds:.2f} s")
    # the fullest program: the k_max unit (read back from the cache)
    compiled = _batched_members.lower(
        X, unit_keys(cfg, K_MAX, tuple(range(MEMBERS))), k=K_MAX,
        iters=ITERS, schedule=cfg.schedule, init=cfg.init,
        delta=cfg.perturbation_delta, eps=EPS_DEFAULT).compile()
    mem = program_memory(compiled)
    stats = device_memory_stats(dev)
    limit = stats.get("bytes_limit")
    if mem is None:
        figure = "no memory_analysis"
    else:
        figure = (f"memory_analysis peak {mem['peak'] / GiB:.2f} GiB "
                  f"(argument {mem['argument'] / GiB:.2f}, temp "
                  f"{mem['temp'] / GiB:.2f})")
        if limit:
            figure += (f" = {100 * mem['peak'] / limit:.1f}% of "
                       f"bytes_limit {limit / GiB:.2f} GiB")
    print(f"[dense] unit program k={K_MAX}: {figure}; device "
          f"peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 0) / GiB:.2f} GiB")
    print(f"[dense] selected k={res.k_opt} (planted {PLANTED_K})")
    check(res.k_opt == PLANTED_K,
          f"dense sweep selected k={res.k_opt}, planted {PLANTED_K}")


def phase_sparse(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.rescal import EPS_DEFAULT
    from repro.kernels import KernelPolicy, ops, ref
    from repro.selection import SweepScheduler
    from repro.selection.ensemble import _batched_members_bcsr, unit_keys

    sp = planted_bcsr(jax.random.PRNGKey(seed), n=SPARSE_N, m=M,
                      k=PLANTED_K, bs=SPARSE_BS,
                      community_blocks=SPARSE_COMMUNITY_BLOCKS)
    sp.data.block_until_ready()
    density = sp.nnzb / sp.nblocks ** 2
    print(f"[sparse] operand n={sp.n} m={sp.m} bs={sp.bs} nnzb={sp.nnzb} "
          f"(block density {density:.4f}): stored blocks "
          f"{sp.data.nbytes / GiB:.2f} GiB, x{MEMBERS + 1} with the "
          f"perturbed copies", flush=True)
    policy = KernelPolicy(use_fused=True, impl="pallas")
    cfg = sweep_config(seed, kernel=policy)
    fb0 = ops.kernel_fallbacks()
    sched = SweepScheduler(cfg)
    res = sched.run(sp)
    fallbacks = ops.kernel_fallbacks() - fb0
    summary_lines("sparse", res)
    for u in sched.report.units:
        print(f"[sparse] unit {u.uid}: {u.seconds:.2f} s, "
              f"{u.kernel_fallbacks} kernel fallbacks")
    hlo = _batched_members_bcsr.lower(
        sp, unit_keys(cfg, K_MAX, tuple(range(MEMBERS))), k=K_MAX,
        iters=ITERS, delta=cfg.perturbation_delta, eps=EPS_DEFAULT,
        use_fused=True, impl="pallas").compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"[sparse] selected k={res.k_opt} (planted {PLANTED_K}); kernel "
          f"fallbacks {fallbacks}; unit program k={K_MAX} holds "
          f"{n_kernels} tpu_custom_call")
    check(res.k_opt == PLANTED_K,
          f"sparse sweep selected k={res.k_opt}, planted {PLANTED_K}")
    check(fallbacks == 0, f"{fallbacks} kernel fallbacks in the sweep")
    check(n_kernels > 0, "no tpu_custom_call in the sparse unit program")

    # one kernel call against the jnp reference on the same operand
    B1 = jnp.asarray(res.per_k[PLANTED_K].A_median)
    B2 = jax.random.uniform(jax.random.PRNGKey(seed + 1), B1.shape)
    got = ops.bcsr_xa_xta(sp, B1, B2, impl="pallas")
    with jax.default_matmul_precision("highest"):
        want = ref.ref_bcsr_xa_xta(sp, B1, B2)
    for name, g, w in zip(("X@B1", "X^T@B2"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        err = float(np.abs(g - w).max() / np.abs(w).max())
        print(f"[sparse] bcsr_xa_xta {name} {g.shape}: max |kernel - ref| "
              f"/ max |ref| = {err:.3e} (limit {KERNEL_RTOL:.3e})")
        check(err <= KERNEL_RTOL, f"bcsr_xa_xta {name} off by {err:.3e}")
    check(ops.kernel_fallbacks() == fb0, "bcsr_xa_xta fell back")
    return res


def phase_serve(res, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import KernelPolicy, ops, ref
    from repro.serve import (FactorBundle, ServeConfig, ServeEngine,
                             random_queries)

    bundle = FactorBundle.from_sweep(res, meta={"criterion": "threshold"})
    with tempfile.TemporaryDirectory() as d:
        bundle.save(d)
        loaded = FactorBundle.load(d)
    check(loaded.digest() == bundle.digest(), "bundle digest changed")
    print(f"[serve] bundle n={loaded.n} m={loaded.m} k={loaded.k} "
          f"digest {loaded.digest()[:12]} saved and reloaded")
    cfg = ServeConfig(topk=10, kernel=KernelPolicy(impl="pallas"))
    engine = ServeEngine(loaded, cfg)
    A64 = loaded.A.astype(np.float64)
    R64 = loaded.R.astype(np.float64)
    fb0 = ops.kernel_fallbacks()
    for mode in ("sro", "sor"):
        queries = random_queries(loaded.n, loaded.m, QUERIES, seed=seed,
                                 mode=mode)
        t0 = time.perf_counter()
        answers = engine.query(queries)
        dt = time.perf_counter() - t0
        anchors = np.asarray([q.anchor for q in queries])
        rels = np.asarray([q.rel for q in queries])
        Rq = R64[rels] if mode == "sro" else R64[rels].transpose(0, 2, 1)
        V64 = np.einsum("bi,bij->bj", A64[anchors], Rq)
        with jax.default_matmul_precision("highest"):
            ref_s, ref_i = ref.ref_score_topk(
                jnp.asarray(V64, jnp.float32), engine.A, cfg.topk)
        ref_s, ref_i = np.asarray(ref_s), np.asarray(ref_i)
        worst, same = 0.0, 0
        for b, ans in enumerate(answers):
            check(not ans.shed, f"{mode} query {b} was shed")
            tol = SCORE_RTOL * np.abs(ref_s[b]) + 1e-30
            # the served j-th entity, scored exactly on the host, must be
            # a j-th best answer within the tolerance: indices agree
            # wherever reference scores are further apart than that
            exact = V64[b] @ A64[ans.indices].T
            off = np.maximum(np.abs(ans.scores - ref_s[b]),
                             np.abs(exact - ref_s[b])) / tol
            worst = max(worst, float(off.max()))
            same += int((ans.indices == ref_i[b]).all())
        print(f"[serve] {mode}: {len(answers)} queries in {dt:.3f} s; "
              f"{same}/{len(answers)} top-{cfg.topk} lists identical to "
              f"ref_score_topk, worst score gap {worst:.3f} x tolerance")
        check(worst <= 1.0, f"{mode} answers differ from the reference")
    st = engine.stats()
    print(f"[serve] engine: {st['batches']} device batches, "
          f"{st['hits']} cache hits / {st['misses']} misses")
    fallbacks = ops.kernel_fallbacks() - fb0
    zeros = jnp.zeros(cfg.batch, jnp.int32)
    hlo = engine._score.lower(engine.A, engine.R, zeros, zeros,
                              jnp.ones(cfg.batch, bool)).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"[serve] kernel fallbacks {fallbacks}; scoring program holds "
          f"{n_kernels} tpu_custom_call")
    check(fallbacks == 0, f"{fallbacks} score_topk fallbacks")
    check(n_kernels > 0, "no tpu_custom_call in the scoring program")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def residual_error(X, A, R) -> float:
    """||X - A R A^T|| / ||X|| from the residual itself, at fp32 precision.

    The sweep's in-program ``rel_error`` expands the square into
    ||X||^2 - 2<X, ARA^T> + ||ARA^T||^2, three f32 sums of ~||X||^2 whose
    difference near a good fit is below their rounding error (it clamps
    to 0 under ~1e-2): two runs of the same member cannot be compared on
    it.  The residual has no such cancellation."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        rec = jnp.einsum("ia,mab,jb->mij", A, R, A)
        return float(jnp.linalg.norm(X - rec) / jnp.linalg.norm(X))


def phase_mesh(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import compat
    from repro.dist.compat import device_memory_stats
    from repro.selection import SweepScheduler, criteria, run_ensemble
    from repro.selection.ensemble import run_ensemble_reference
    from repro.selection.scheduler import reduce_k

    devs = jax.devices()[:4]
    mesh = compat.make_mesh((2, 2), ("data", "model"), devices=devs)
    spec, X = dense_operand(DENSE_N, seed)
    X_mesh = jax.device_put(X, NamedSharding(mesh, P(None, "data",
                                                     "model")))
    X_mesh.block_until_ready()
    del X
    print(f"[mesh] operand {spec.spec_string()}: {tuple(X_mesh.shape)} "
          f"f32, {X_mesh.nbytes / GiB:.2f} GiB on a 2x2 (data, model) mesh; "
          f"r={MEMBERS} ks={K_MIN}..{K_MAX} iters={ITERS}", flush=True)
    for d in devs:
        print(f"[mesh] {d} bytes_in_use "
              f"{device_memory_stats(d).get('bytes_in_use', 0) / GiB:.3f} GiB")
    cfg = sweep_config(seed)
    t0 = time.perf_counter()
    res = SweepScheduler(cfg, mesh=mesh).run(X_mesh)
    print(f"[mesh] sweep {time.perf_counter() - t0:.1f} s")
    summary_lines("mesh", res)
    for d in devs:
        st = device_memory_stats(d)
        print(f"[mesh] {d} bytes_in_use {st.get('bytes_in_use', 0) / GiB:.3f}"
              f" GiB, peak {st.get('peak_bytes_in_use', 0) / GiB:.3f} GiB")
    # the sweep keeps only member errors; rerun each unit (its program
    # is compiled already) for the member factors
    mesh_members = {}
    for k in cfg.ks:
        ens = run_ensemble(X_mesh, k, cfg, mesh=mesh)
        mesh_members[k] = (np.asarray(ens.A), np.asarray(ens.R))

    # the one-device twin: the same members with the mesh's blocked noise
    # replayed on device 0, reduced and selected as the scheduler does
    X1 = jax.device_put(X_mesh, devs[0])
    del X_mesh
    t0 = time.perf_counter()
    per_k, twin_members = {}, {}
    for k in cfg.ks:
        ens = run_ensemble_reference(X1, k, cfg, grid=(2, 2))
        twin_members[k] = (np.asarray(ens.A), np.asarray(ens.R))
        per_k[k] = reduce_k(X1, cfg, k, *twin_members[k],
                            np.asarray(ens.errors))
    print(f"[mesh] one-device twin {time.perf_counter() - t0:.1f} s")
    ks = cfg.ks
    k_twin = criteria.select(
        "threshold", ks, np.array([per_k[k].s_min for k in ks]),
        np.array([per_k[k].s_mean for k in ks]),
        np.array([per_k[k].rel_err for k in ks]),
        sil_threshold=cfg.sil_threshold)
    worst = 0.0
    for k in ks:
        e_mesh = np.array([residual_error(X1, A, R)
                           for A, R in zip(*mesh_members[k])])
        e_twin = np.array([residual_error(X1, A, R)
                           for A, R in zip(*twin_members[k])])
        gap = np.abs(e_mesh - e_twin) / e_twin
        worst = max(worst, float(gap.max()))
        print(f"[mesh] k={k} member rel_error mesh {e_mesh.tolist()} twin "
              f"{e_twin.tolist()} (max relative gap {gap.max():.3e}); "
              f"in-program rel_error mesh "
              f"{np.asarray(res.per_k[k].member_errors).tolist()} twin "
              f"{np.asarray(per_k[k].member_errors).tolist()}; s_min mesh "
              f"{res.per_k[k].s_min:.3f} twin {per_k[k].s_min:.3f}")
    print(f"[mesh] selected k={res.k_opt} mesh, k={k_twin} one-device twin "
          f"(planted {PLANTED_K}); worst member error gap {worst:.3e} "
          f"(limit {TWIN_RTOL:.3e})")
    check(res.k_opt == k_twin, f"mesh k={res.k_opt} vs twin k={k_twin}")
    check(worst <= TWIN_RTOL, f"member errors differ by {worst:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh sweep and its one-device "
                         "twin (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)
    meter = CompileMeter()
    if args.four_chips:
        with Phase("mesh", meter):
            phase_mesh(args.seed)
    else:
        with Phase("dense", meter):
            phase_dense(dev, args.seed)
        with Phase("sparse", meter):
            res = phase_sparse(args.seed)
        with Phase("serve", meter):
            phase_serve(res, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
