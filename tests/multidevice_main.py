"""Multi-device correctness checks, run in a subprocess with 8 fake CPU
devices (never set xla_force_host_platform_device_count in the main pytest
process).  Invoked by test_multidevice.py:

    python tests/multidevice_main.py <check-name>
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

# All mesh construction goes through the version-tolerant compat helper —
# jax.sharding.AxisType does not exist on every supported JAX.
from repro.dist import compat   # noqa: E402


def mesh2x2():
    return compat.make_mesh((2, 2), ("data", "model"))


def mesh_pod():
    return compat.make_mesh((2, 2, 2), ("pod", "data", "model"))


def lowrank(key, n=32, m=3, k=4):
    A = jax.random.uniform(key, (n, k), minval=0.1, maxval=1.0)
    R = jax.random.uniform(jax.random.fold_in(key, 1), (m, k, k),
                           minval=0.1, maxval=1.0)
    return jnp.einsum("ia,mab,jb->mij", A, R, A)


def check_dist_rescal_equals_single():
    from repro.core import DistRescalConfig
    from repro.core.rescal import _run_iters, init_factors
    from repro.core.rescal_dist import make_dist_error, make_dist_step
    key = jax.random.PRNGKey(0)
    X = lowrank(key)
    init = init_factors(key, 32, 3, 4)
    mesh = mesh2x2()
    for schedule in ("batched", "sliced"):
        # _run_iters donates its state (dist.compat shim): pass a copy so
        # `init` stays alive for the dist step on accelerator backends
        st = _run_iters(X, jax.tree_util.tree_map(jnp.copy, init),
                        30, schedule, 1e-16)
        step = make_dist_step(mesh, DistRescalConfig(schedule=schedule),
                              iters=30)
        A, R = step(X, init.A, init.R)
        np.testing.assert_allclose(A, st.A, rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(R, st.R, rtol=5e-4, atol=1e-5)
    err = make_dist_error(mesh)(X, A, R)
    from repro.core.rescal import rel_error
    np.testing.assert_allclose(float(err), float(rel_error(X, A, R)),
                               rtol=1e-4)


def check_dist_rescal_sparse_equals_dense():
    from repro.core import DistRescalConfig
    from repro.core.rescal_dist import (make_dist_step,
                                        make_dist_step_sparse)
    from repro.core.rescal import init_factors
    key = jax.random.PRNGKey(1)
    n, m, bs = 64, 3, 16
    mesh = mesh2x2()
    g = 2
    # build a balanced sparse tensor: every device block gets equal nnzb
    n_loc = n // g
    nb_loc = n_loc // bs
    nnzb_loc = nb_loc * nb_loc          # fully dense blocks (exact compare)
    rows = jnp.tile(jnp.repeat(jnp.arange(nb_loc), nb_loc)[None, None],
                    (g, g, 1)).astype(jnp.int32)
    cols = jnp.tile(jnp.tile(jnp.arange(nb_loc), nb_loc)[None, None],
                    (g, g, 1)).astype(jnp.int32)
    X = lowrank(key, n=n, m=m)
    # pack X into the (g, g, m, nnzb, bs, bs) layout
    Xb = X.reshape(m, g, n_loc // bs, bs, g, n_loc // bs, bs)
    data = jnp.einsum("mirakcb->ikmrcab", Xb.transpose(0, 1, 2, 3, 4, 5, 6)
                      ) if False else None
    # simpler: loop-free gather
    blocks = X.reshape(m, g, nb_loc, bs, g, nb_loc, bs)
    blocks = blocks.transpose(1, 4, 0, 2, 5, 3, 6)  # (g,g,m,nbr,nbc,bs,bs)
    data = blocks.reshape(g, g, m, nnzb_loc, bs, bs)
    init = init_factors(key, n, m, 4)
    for schedule in ("batched", "sliced"):
        cfg = DistRescalConfig(schedule=schedule)
        dense_step = make_dist_step(mesh, DistRescalConfig(), iters=5)
        A_d, R_d = dense_step(X, init.A, init.R)
        sparse_step = make_dist_step_sparse(mesh, cfg, n=n, iters=5)
        A_s, R_s = sparse_step(data, rows, cols, init.A, init.R)
        np.testing.assert_allclose(A_s, A_d, rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(R_s, R_d, rtol=5e-4, atol=1e-5)


def check_ensemble_step_pods():
    from repro.core import DistRescalConfig
    from repro.core.rescal import _run_iters, init_factors
    from repro.core.rescal_dist import make_ensemble_step
    key = jax.random.PRNGKey(2)
    X = lowrank(key, n=16, m=2, k=3)
    mesh = mesh_pod()
    r = 4
    inits = [init_factors(jax.random.fold_in(key, q), 16, 2, 3)
             for q in range(r)]
    A_e = jnp.stack([s.A for s in inits])
    R_e = jnp.stack([s.R for s in inits])
    step = make_ensemble_step(mesh, DistRescalConfig(), iters=10)
    A_out, R_out = step(X, A_e, R_e)
    for q in range(r):
        st = _run_iters(X, inits[q], 10, "batched", 1e-16)
        np.testing.assert_allclose(A_out[q], st.A, rtol=5e-4, atol=1e-5)


def check_fused_engine_matches_reference():
    """use_fused_kernel=True must reproduce the reference einsum path:
    the engine's single-X-pass products feed the identical MU update via
    (X^T A) R == X^T (A R).  `fused_impl="ref"` exercises the jnp oracle
    (the CPU execution path), `"interpret"` the actual Pallas kernel body.
    """
    from repro.core.rescal import init_factors
    from repro.dist.engine import DistRescalConfig, make_mu_step
    key = jax.random.PRNGKey(7)
    n, m, k = 64, 3, 4
    X = lowrank(key, n=n, m=m, k=k)
    init = init_factors(key, n, m, k)
    mesh = mesh2x2()
    for schedule in ("batched", "sliced"):
        ref_step = make_mu_step(mesh, DistRescalConfig(schedule=schedule),
                                iters=10)
        A0, R0 = ref_step(X, init.A, init.R)
        for impl in ("ref", "interpret"):
            cfg = DistRescalConfig(schedule=schedule, use_fused_kernel=True,
                                   fused_impl=impl)
            A1, R1 = make_mu_step(mesh, cfg, iters=10)(X, init.A, init.R)
            np.testing.assert_allclose(A1, A0, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{schedule}/{impl}")
            np.testing.assert_allclose(R1, R0, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{schedule}/{impl}")


def check_fused_engine_matches_reference_bcsr():
    """The BCSR twin (ISSUE 5): both sparse engine iters (batched +
    sliced) with use_fused_kernel=True — ONE pass over the stored blocks
    via kernels/bcsr_fused — must match the spmm/spmm_t segment-sum
    oracle schedule at <= 1e-5 on the real 2x2 grid, under the jnp ref
    dispatch AND the actual Pallas kernel body (interpret)."""
    from repro.core.rescal import init_factors
    from repro.dist.engine import DistRescalConfig, make_dist_step_sparse
    key = jax.random.PRNGKey(8)
    n, m, bs, g = 64, 3, 16, 2
    mesh = mesh2x2()
    n_loc = n // g
    nb_loc = n_loc // bs
    nnzb_loc = nb_loc * nb_loc          # fully dense blocks (exact compare)
    rows = jnp.tile(jnp.repeat(jnp.arange(nb_loc), nb_loc)[None, None],
                    (g, g, 1)).astype(jnp.int32)
    cols = jnp.tile(jnp.tile(jnp.arange(nb_loc), nb_loc)[None, None],
                    (g, g, 1)).astype(jnp.int32)
    X = lowrank(key, n=n, m=m)
    blocks = X.reshape(m, g, nb_loc, bs, g, nb_loc, bs)
    blocks = blocks.transpose(1, 4, 0, 2, 5, 3, 6)
    data = blocks.reshape(g, g, m, nnzb_loc, bs, bs)
    init = init_factors(key, n, m, 4)
    for schedule in ("batched", "sliced"):
        ref_step = make_dist_step_sparse(
            mesh, DistRescalConfig(schedule=schedule), n=n, iters=5)
        A0, R0 = ref_step(data, rows, cols, init.A, init.R)
        for impl in ("ref", "interpret"):
            cfg = DistRescalConfig(schedule=schedule, use_fused_kernel=True,
                                   fused_impl=impl)
            step = make_dist_step_sparse(mesh, cfg, n=n, iters=5)
            A1, R1 = step(data, rows, cols, init.A, init.R)
            np.testing.assert_allclose(A1, A0, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{schedule}/{impl}")
            np.testing.assert_allclose(R1, R0, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{schedule}/{impl}")


def check_selection_mesh_ensemble_bcsr_fused():
    """The mesh BCSR ensemble with use_fused_kernel=True (ISSUE 5
    acceptance): every member of the fused sharded program — single-pass
    kernel inside the shard_map body — must match the oracle mesh run
    member-for-member, per-k AND cross-k grid."""
    import dataclasses
    from repro.io import partition_coo
    from repro.io.triples import COOBuilder
    from repro.selection import (RescalkConfig, run_ensemble,
                                 run_sweep_batched)

    rng = np.random.default_rng(0)
    n, m, nnz = 128, 2, 1500
    ii = np.minimum(rng.zipf(1.5, nnz) - 1, n - 1)
    jj = rng.integers(0, n, nnz)
    rr = rng.integers(0, m, nnz)
    vv = (rng.random(nnz) + 0.1).astype(np.float32)
    coo = COOBuilder().add(rr, ii, jj, vv).finalize(n=n, m=m)
    sharded = partition_coo(coo, bs=16, grid=2)

    cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=4,
                        rescal_iters=40, init="random", seed=4)
    mesh = mesh_pod()
    # single-ITERATION parity is <= 1e-5 (fused_engine_matches_reference_
    # bcsr and tests/test_sparse.py); over 40 compounding iterations the
    # float32 reduction-order difference (merged vs per-product
    # segment-sum) drifts a little further on zipf data — same reason the
    # oracle BCSR mesh checks above use widened bands.
    res_o = run_ensemble(sharded, 3, cfg, mesh=mesh)
    for impl in ("ref", "interpret"):
        cfg_f = dataclasses.replace(cfg, use_fused_kernel=True,
                                    fused_impl=impl)
        res_f = run_ensemble(sharded, 3, cfg_f, mesh=mesh)
        np.testing.assert_allclose(res_f.errors, res_o.errors, rtol=1e-5,
                                   atol=1e-6, err_msg=impl)
        np.testing.assert_allclose(res_f.A, res_o.A, rtol=1e-3, atol=1e-5,
                                   err_msg=impl)
        np.testing.assert_allclose(res_f.R, res_o.R, rtol=1e-3, atol=1e-5,
                                   err_msg=impl)

    # cross-k grid program, fused vs oracle member-for-member
    cells = [(k, q) for k in cfg.ks for q in range(2)]   # 4 cells % 2 pods
    g_o = run_sweep_batched(sharded, cells, cfg, mesh=mesh)
    cfg_f = dataclasses.replace(cfg, use_fused_kernel=True,
                                fused_impl="ref")
    g_f = run_sweep_batched(sharded, cells, cfg_f, mesh=mesh)
    np.testing.assert_allclose(g_f.errors, g_o.errors, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(g_f.A, g_o.A, rtol=1e-3, atol=1e-5)


def check_sharded_train_matches_single():
    from repro.configs import REDUCED_ARCHS
    from repro.data import TokenStreamConfig, batch_at
    from repro.optim import AdamW
    from repro.train import init_state, make_train_step
    cfg = REDUCED_ARCHS["llama3.2-1b"]
    opt = AdamW(lr=1e-3)
    ds = TokenStreamConfig(vocab=cfg.vocab, batch=4, seq=32, seed=0)
    key = jax.random.PRNGKey(0)

    state1 = init_state(key, cfg, opt)
    step1 = make_train_step(cfg, None, optimizer=opt, remat=False,
                            moe_impl="dense")
    state2 = init_state(key, cfg, opt)
    step2 = make_train_step(cfg, mesh2x2(), optimizer=opt, remat=False,
                            moe_impl="dense")
    for i in range(3):
        b = batch_at(ds, i)
        state1, m1 = step1(state1, b)
        state2, m2 = step2(state2, b)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-4)


def check_sharded_decode_matches_single():
    from repro.configs import REDUCED_ARCHS
    from repro.dist.sharding import cache_shardings
    from repro.models import transformer
    from repro.train import make_serve_step
    cfg = REDUCED_ARCHS["yi-9b"]
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    toks = jax.random.randint(key, (4, 6), 0, cfg.vocab)

    mesh = mesh2x2()
    cache_a = transformer.init_cache(cfg, 4, 16)
    cache_b = jax.device_put(transformer.init_cache(cfg, 4, 16),
                             cache_shardings(mesh, cache_shapes_tree(cfg)))
    step_a = make_serve_step(cfg, None, moe_impl="dense")
    step_b = make_serve_step(cfg, mesh, moe_impl="dense")
    for t in range(6):
        la, cache_a = step_a(params, cache_a, toks[:, t:t + 1],
                             jnp.int32(t))
        lb, cache_b = step_b(params, cache_b, toks[:, t:t + 1],
                             jnp.int32(t))
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=2e-3, atol=2e-3)


def cache_shapes_tree(cfg):
    from repro.models import transformer
    return transformer.cache_shapes(cfg, 4, 16)


def check_ef_psum():
    from repro.optim import compression
    from jax import shard_map
    mesh = compat.make_mesh((8,), ("data",))
    key = jax.random.PRNGKey(0)
    g_global = jax.random.normal(key, (8, 128))

    def local(g, err):
        return compression.ef_psum(g[0], err[0], "data")

    f = jax.jit(shard_map(local, mesh=mesh,
                          in_specs=(P("data"), P("data")),
                          out_specs=(P(), P("data")), check_vma=False))
    err = jnp.zeros((8, 128))
    exact_mean = g_global.mean(0)
    total_sent = jnp.zeros((128,))
    # over steps, error feedback drives the accumulated mean to exactness
    sent, err_out = f(g_global, err)
    # shared-scale int8: per-device error <= scale/2, mean error <= scale/2
    scale = float(np.abs(np.asarray(g_global)).max()) / 127.0
    np.testing.assert_allclose(np.asarray(sent), np.asarray(exact_mean),
                               atol=scale)
    # error-feedback invariant: contributed + err == target exactly
    recon = np.asarray(sent) * 8 / 8  # sanity use
    assert np.isfinite(np.asarray(err_out)).all()
    # int8 wire payload check
    c = compression.compress(g_global[0])
    assert c.q.dtype == jnp.int8


def check_selection_mesh_ensemble():
    """The selection subsystem's mesh-sharded ensemble program (members
    over the pod axis, perturbation fused in via perturb_shard) must match
    the single-host reference that replays the same blocked noise — and a
    full sweep through the scheduler must select the same k either way."""
    from repro.selection import ensemble as ens
    from repro.selection import scheduler as sched_mod
    from repro.selection.scheduler import RescalkConfig, SweepScheduler

    key = jax.random.PRNGKey(5)
    X = lowrank(key, n=32, m=2, k=3)
    mesh = mesh_pod()                      # (pod, data, model) = (2, 2, 2)
    cfg = RescalkConfig(k_min=3, k_max=3, n_perturbations=4,
                        rescal_iters=40, init="random", seed=4)

    res_mesh = ens.run_ensemble(X, 3, cfg, mesh=mesh)
    res_ref = ens.run_ensemble_reference(X, 3, cfg, grid=(2, 2))
    np.testing.assert_allclose(res_mesh.errors, res_ref.errors,
                               rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(res_mesh.A, res_ref.A, rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(res_mesh.R, res_ref.R, rtol=5e-4, atol=1e-5)

    # full sweep: mesh-sharded units vs a host scheduler replaying the
    # identical blocked noise (monkeypatched ensemble) -> same k_opt and
    # member errors
    cfg2 = RescalkConfig(k_min=2, k_max=4, n_perturbations=4,
                         rescal_iters=60, init="random", seed=4)
    r_mesh = SweepScheduler(cfg2, mesh=mesh).run(X)

    orig = sched_mod.run_ensemble
    sched_mod.run_ensemble = (
        lambda X_, k_, cfg_, members=None, mesh=None, mode="batched":
        ens.run_ensemble_reference(X_, k_, cfg_, grid=(2, 2),
                                   members=members))
    try:
        r_host = SweepScheduler(cfg2).run(X)
    finally:
        sched_mod.run_ensemble = orig
    assert r_mesh.k_opt == r_host.k_opt, (r_mesh.summary(), r_host.summary())
    for k in cfg2.ks:
        np.testing.assert_allclose(r_mesh.per_k[k].member_errors,
                                   r_host.per_k[k].member_errors,
                                   rtol=5e-4, atol=1e-5)


def check_selection_mesh_ensemble_bcsr():
    """The BCSR mesh ensemble (io.partition shards, stored-block
    perturbation fused in shard-locally) must match the single-host
    reference replaying the same blocked noise on the merged tensor —
    with and without a pod axis."""
    from repro.io import partition_coo
    from repro.io.triples import COOBuilder
    from repro.selection import (RescalkConfig, run_ensemble,
                                 run_ensemble_bcsr_sharded_reference)

    rng = np.random.default_rng(0)
    n, m, nnz = 128, 2, 1500
    ii = np.minimum(rng.zipf(1.5, nnz) - 1, n - 1)
    jj = rng.integers(0, n, nnz)
    rr = rng.integers(0, m, nnz)
    vv = (rng.random(nnz) + 0.1).astype(np.float32)
    coo = COOBuilder().add(rr, ii, jj, vv).finalize(n=n, m=m)
    sharded = partition_coo(coo, bs=16, grid=2)
    assert sharded.balance <= 1.5, sharded.balance

    cfg = RescalkConfig(k_min=3, k_max=3, n_perturbations=4,
                        rescal_iters=40, init="random", seed=4)
    # a partition built for a different grid must be rejected, not
    # silently re-split (shard_map would drop shards)
    wrong = partition_coo(coo, bs=16, grid=1)
    try:
        run_ensemble(wrong, 3, cfg, mesh=mesh2x2())
    except ValueError as e:
        assert "re-partition" in str(e), e
    else:
        raise AssertionError("grid mismatch was not rejected")

    res_ref = run_ensemble_bcsr_sharded_reference(sharded, 3, cfg)
    for mesh in (mesh_pod(), mesh2x2()):
        res_mesh = run_ensemble(sharded, 3, cfg, mesh=mesh)
        # float32 segment-sum order differs shard-local vs merged: keep a
        # slightly wider band than the dense check
        np.testing.assert_allclose(res_mesh.errors, res_ref.errors,
                                   rtol=1e-3, atol=5e-5)
        np.testing.assert_allclose(res_mesh.A, res_ref.A, rtol=2e-3,
                                   atol=5e-5)
        np.testing.assert_allclose(res_mesh.R, res_ref.R, rtol=2e-3,
                                   atol=5e-5)


def check_selection_grid_mesh():
    """The cross-k grid program on the mesh (ISSUE 4): the flattened
    (k, q) cell axis rides the pod axis, per-cell ranks are data, factors
    are padded to k_max — and every cell must match the per-k mesh
    ensemble member-for-member (same shard-local noise by construction,
    same reference-shape init draws), dense AND BCSR."""
    from repro.io import partition_coo
    from repro.io.triples import COOBuilder
    from repro.selection import (RescalkConfig, SweepScheduler,
                                 run_ensemble, run_sweep_batched)

    mesh = mesh_pod()                      # (pod, data, model) = (2, 2, 2)
    cfg = RescalkConfig(k_min=2, k_max=4, n_perturbations=2,
                        rescal_iters=40, init="random", seed=4)
    cells = [(k, q) for k in cfg.ks for q in range(2)]   # 6 cells % 2 pods

    # ---- dense ----
    X = lowrank(jax.random.PRNGKey(5), n=32, m=2, k=3)
    g = run_sweep_batched(X, cells, cfg, mesh=mesh)
    gA, gR = np.asarray(g.A), np.asarray(g.R)
    for k in cfg.ks:
        ref = run_ensemble(X, k, cfg, mesh=mesh)
        rows = [i for i, (kk, _) in enumerate(cells) if kk == k]
        np.testing.assert_allclose(np.asarray(g.errors)[rows], ref.errors,
                                   rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(gA[rows][:, :, :k], ref.A, rtol=5e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(gR[rows][:, :, :k, :k], ref.R,
                                   rtol=5e-4, atol=1e-5)
        assert (gA[rows][:, :, k:] == 0.0).all()   # masked cols exact 0

    # a chunking that does not divide the pod axis must be rejected at
    # construction (not after max_retries failed executions)
    try:
        SweepScheduler(cfg, mode="grid", mesh=mesh, grid_chunk=5)
    except ValueError as e:
        assert "pods" in str(e), e
    else:
        raise AssertionError("indivisible grid chunking was not rejected")

    # full sweep through the scheduler on the mesh, chunked so each chunk
    # still divides the pod axis
    r_grid = SweepScheduler(cfg, mode="grid", mesh=mesh,
                            grid_chunk=2).run(X)
    r_perk = SweepScheduler(cfg, mesh=mesh).run(X)
    assert r_grid.k_opt == r_perk.k_opt
    for k in cfg.ks:
        np.testing.assert_allclose(r_grid.per_k[k].member_errors,
                                   r_perk.per_k[k].member_errors,
                                   rtol=5e-4, atol=1e-5)

    # ---- BCSR (balanced shards, stored-block perturbation) ----
    rng = np.random.default_rng(0)
    n, m, nnz = 128, 2, 1500
    ii = np.minimum(rng.zipf(1.5, nnz) - 1, n - 1)
    jj = rng.integers(0, n, nnz)
    rr = rng.integers(0, m, nnz)
    vv = (rng.random(nnz) + 0.1).astype(np.float32)
    coo = COOBuilder().add(rr, ii, jj, vv).finalize(n=n, m=m)
    sharded = partition_coo(coo, bs=16, grid=2)
    gs = run_sweep_batched(sharded, cells, cfg, mesh=mesh)
    gsA = np.asarray(gs.A)
    for k in cfg.ks:
        ref = run_ensemble(sharded, k, cfg, mesh=mesh)
        rows = [i for i, (kk, _) in enumerate(cells) if kk == k]
        np.testing.assert_allclose(np.asarray(gs.errors)[rows],
                                   ref.errors, rtol=1e-3, atol=5e-5)
        np.testing.assert_allclose(gsA[rows][:, :, :k], ref.A, rtol=2e-3,
                                   atol=5e-5)
        assert (gsA[rows][:, :, k:] == 0.0).all()


def check_clustering_sharded_similarity():
    """The clustering similarity einsum under pjit == host einsum."""
    from repro.core.clustering import _similarity
    mesh = mesh2x2()
    key = jax.random.PRNGKey(3)
    M = jax.random.uniform(key, (32, 4))
    A_ens = jax.random.uniform(key, (5, 32, 4))
    from jax.sharding import NamedSharding
    Ms = jax.device_put(M, NamedSharding(mesh, P("data", None)))
    As = jax.device_put(A_ens, NamedSharding(mesh, P(None, "data", None)))
    np.testing.assert_allclose(_similarity(Ms, As), _similarity(M, A_ens),
                               rtol=1e-5)


def check_elastic_reshard():
    """Checkpoint on a (2, 2) mesh, restore onto (4, 2): global-layout
    checkpoints make mesh changes pure re-sharding (DESIGN.md §4)."""
    import tempfile
    from jax.sharding import NamedSharding
    from repro import ckpt
    from repro.configs import REDUCED_ARCHS
    from repro.data import TokenStreamConfig, batch_at
    from repro.optim import AdamW
    from repro.train import init_state, make_train_step, state_shardings
    cfg = REDUCED_ARCHS["llama3.2-1b"]
    opt = AdamW(lr=1e-3)
    ds = TokenStreamConfig(vocab=cfg.vocab, batch=8, seq=32, seed=0)

    mesh_a = compat.make_mesh((2, 2), ("data", "model"))
    mesh_b = compat.make_mesh((4, 2), ("data", "model"))

    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    step_a = make_train_step(cfg, mesh_a, optimizer=opt, remat=False,
                             moe_impl="dense", donate=False)
    for i in range(2):
        state, _ = step_a(state, batch_at(ds, i))

    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 2, state)
        like = jax.eval_shape(lambda: init_state(
            jax.random.PRNGKey(0), cfg, opt))
        shard_b = state_shardings(mesh_b, cfg, opt)
        restored, step_n = ckpt.restore(d, like, shardings=shard_b)
    assert step_n == 2

    # continue on the NEW mesh; loss must match the old-mesh continuation
    step_b = make_train_step(cfg, mesh_b, optimizer=opt, remat=False,
                             moe_impl="dense", donate=False)
    _, m_b = step_b(restored, batch_at(ds, 2))
    _, m_a = step_a(state, batch_at(ds, 2))
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=1e-4)


CHECKS = {name[len("check_"):]: fn for name, fn in list(globals().items())
          if name.startswith("check_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CHECKS[name]()
    print(f"OK {name}")
