"""Batched perturbation ensembles — the hot loop of model selection.

The paper calls the r perturbation members of a candidate rank k "naturally
independent"; the seed code nevertheless ran them as a sequential Python
loop (one trace/compile/dispatch per member).  This module runs all members
of one (k, member-set) work unit as **one jitted program**:

  * **Single-host batched** (``mode="batched"``, no mesh) — ``vmap`` of the
    whole member pipeline (perturb -> init -> MU fori_loop -> normalize ->
    rel_error) over a leading ensemble axis.  The perturbation is fused
    into the program: the jitted function takes the *unperturbed* X plus
    the (r, 2) member keys, so r perturbed copies of X are never
    materialized on host.  The key discipline is byte-identical to the
    historical sequential loop (split each member key into (pkey, fkey)),
    so batched and loop execution agree member-for-member to float
    tolerance — the parity contract tests/test_selection.py enforces.

  * **Mesh-sharded** (``mesh=...``) — a shard_map program over the
    ("pod", "data", "model") mesh built from the same per-device MU bodies
    as the distributed engine (dist.engine.get_mu_iter).  X is replicated
    across pods and block-sharded over the 2D grid; the member axis shards
    over the ensemble/pod axis (dist.sharding.ensemble_member_specs); each
    device perturbs its own X block with ``perturb_shard`` (seed folded
    from the member id and the device's linear grid index — the paper's
    per-rank seeding), so again no host-side member copies.
    ``run_ensemble_reference`` reproduces the exact same noise on a single
    host via ``perturb_blocked`` for the multi-device parity checks.

  * **Sequential loop** (``mode="loop"``) — the reference path and the
    memory-bound fallback: the batched program keeps all r perturbed
    tensors live on device, which for huge (m, n, n) can exceed HBM; the
    loop bounds residency to one member.

  * **Cross-k grid** (``run_sweep_batched``, ISSUE 4) — the per-k batched
    programs above still compile once per candidate rank; padding every
    cell's factors to k_max with a per-cell column mask (core.rescal
    masked MU) runs the entire flattened (k, q) grid — dense or BCSR,
    single-host vmap or mesh-sharded with the cell axis on the
    pod/ENSEMBLE_AXIS — as ONE compiled program, with results equal to the
    per-k batched programs member-for-member (the rank is data, not a
    static argument).  ``scripts/check_compiles.py`` guards the compile
    count in CI.

  * **BCSR operands** (ISSUE 3 / paper §4.2) — every mode also accepts
    block-sparse tensors: a plain ``core.sparse.BCSR`` runs the batched
    vmap (or loop) program with the perturbation applied to the *stored
    blocks only* (``perturb_bcsr`` — the sparsity pattern is data, not
    noise), and an ``io.partition.ShardedBCSR`` + mesh runs the sharded
    program built from ``dist.engine.get_mu_iter("bcsr", ...)`` with
    shard-local stored-block perturbation.  ``run_ensemble_bcsr_dense_
    reference`` replays the identical noise through the dense MU pipeline
    (sparse==dense member-for-member is the acceptance contract);
    ``run_ensemble_bcsr_sharded_reference`` replays the mesh path's
    blocked noise on a single host for multi-device parity.

Mesh limitation (ROADMAP open item): ``init="random"`` only (NNDSVD needs
a distributed eigensolve; randomized_eigh is distMM-compatible but not
wired up yet); BCSR operands are random-init only for the same reason
(NNDSVD eigensolves the dense tensor).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.perturb import ensemble_keys, perturb, perturb_shard
from repro.core.rescal import (EPS_DEFAULT, MU_SCHEDULES, RescalState,
                               column_mask, init_factors, masked_mu_step,
                               masked_normalize, normalize, pad_state,
                               rel_error)
from repro.dist.compat import donating_jit


class EnsembleResult(NamedTuple):
    """Factors and errors for the members of one work unit."""
    A: jax.Array        # (r_unit, n, k)
    R: jax.Array        # (r_unit, m, k, k)
    errors: jax.Array   # (r_unit,) rel. error vs the UNperturbed X


def member_keys(seed: int, k: int, r: int) -> jax.Array:
    """The sweep's PRNG discipline: fold the candidate k into the root key,
    then split one key per member.  Shared by every execution mode (and by
    the legacy core.rescalk loop), so modes agree draw-for-draw."""
    root = jax.random.PRNGKey(seed)
    return ensemble_keys(jax.random.fold_in(root, k), r)


def unit_keys(cfg, k: int, members: Sequence[int]) -> jax.Array:
    """Member keys for one (k, members) work unit — THE single home of the
    sweep's key selection.  Every execution mode (loop | batched | mesh |
    grid) and every parity oracle in this module derives its keys here, and
    the scheduler's unit types expose it as ``WorkUnit.keys`` /
    ``GridChunk.keys`` — so per-k and cross-k modes provably share one key
    discipline instead of re-deriving it per call site."""
    return member_keys(cfg.seed, k, cfg.n_perturbations)[jnp.asarray(members)]


def perturb_blocked(key: jax.Array, X: jax.Array, q, grid: tuple[int, int],
                    delta: float = 0.02) -> jax.Array:
    """Host-side emulation of the mesh path's shard-local perturbation:
    split X (m, n, n) into the (gr, gc) device grid and perturb each block
    with ``perturb_shard`` keyed by (member id q, linear grid index).
    Produces bit-identical noise to the sharded program, which is what
    makes mesh-vs-host parity exactly testable."""
    gr, gc = grid
    m, n, _ = X.shape
    nr, nc = n // gr, n // gc
    rows = []
    for i in range(gr):
        cols = []
        for j in range(gc):
            blk = X[:, i * nr:(i + 1) * nr, j * nc:(j + 1) * nc]
            # rescal-lint: disable=key-discipline -- `key` is a root, not a
            # stream: perturb_shard folds (q, grid index) in, and handing
            # every shard the same root is the mesh-parity contract
            cols.append(perturb_shard(key, blk, q, i * gc + j, delta))
        rows.append(jnp.concatenate(cols, axis=2))
    return jnp.concatenate(rows, axis=1)


# ---------------------------------------------------------------------------
# Single-host batched program (vmap over the member axis)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "iters", "schedule",
                                             "init", "delta", "eps",
                                             "sanitize", "trace_metrics"))
def _batched_members(X, keys, *, k: int, iters: int, schedule: str,
                     init: str, delta: float, eps: float,
                     sanitize: bool = False, trace_metrics: bool = False):
    m, n, _ = X.shape
    step = MU_SCHEDULES[schedule]

    def one_member(member_key):
        pkey, fkey = jax.random.split(member_key)
        X_q = perturb(pkey, X, delta)
        st = init_factors(fkey, n, m, k, dtype=X.dtype)
        if init == "nndsvd":
            from repro.core.nndsvd import nndsvd_init_A
            st = RescalState(A=nndsvd_init_A(X_q, k).astype(X.dtype),
                             R=st.R, step=st.step)

        def body(_, s):
            return step(X_q, s, eps, sanitize, trace_metrics)

        st = jax.lax.fori_loop(0, iters, body, st)
        st = normalize(st)
        return st.A, st.R, rel_error(X, st.A, st.R)

    A, R, errs = jax.vmap(one_member)(keys)
    return A, R, errs


# ---------------------------------------------------------------------------
# BCSR members (stored-block perturbation, paper §4.2)
# ---------------------------------------------------------------------------

def _is_sharded_bcsr(X) -> bool:
    from repro.io.partition import ShardedBCSR
    return isinstance(X, ShardedBCSR)


def _require_random_init(cfg, what: str):
    if cfg.init != "random":
        raise NotImplementedError(
            f"{what} supports init='random' only (NNDSVD eigensolves the "
            f"dense tensor; distributed/sparse NNDSVD is a ROADMAP item)")


def _fused_opts(cfg) -> dict:
    """The sweep config's fused-kernel selection.  Reads the unified
    ``kernel_policy`` (kernels.KernelPolicy — resolves the deprecated
    ``use_fused_kernel``/``fused_impl`` aliases itself); duck-typed so
    older RescalkConfig-shaped objects without any of the fields mean
    'oracle'."""
    kp = getattr(cfg, "kernel_policy", None)
    if kp is not None:
        return dict(use_fused=kp.use_fused, impl=kp.impl)
    return dict(use_fused=getattr(cfg, "use_fused_kernel", False),
                impl=getattr(cfg, "fused_impl", "auto"))


def _sanitize_opt(cfg) -> bool:
    """Runtime-sanitizer flag, duck-typed like ``_fused_opts`` (older
    config objects without the field mean 'off')."""
    return bool(getattr(cfg, "sanitize", False))


def _trace_opt(cfg) -> bool:
    """Per-iteration telemetry flag (repro.obs.metrics), duck-typed like
    ``_sanitize_opt`` (older config objects without the field mean 'off')."""
    return bool(getattr(cfg, "trace_metrics", False))


@functools.partial(jax.jit, static_argnames=("k", "iters", "delta", "eps",
                                             "use_fused", "impl",
                                             "sanitize", "trace_metrics"))
def _batched_members_bcsr(sp, keys, *, k: int, iters: int, delta: float,
                          eps: float, use_fused: bool = False,
                          impl: str = "auto", sanitize: bool = False,
                          trace_metrics: bool = False):
    """All members of one unit on a BCSR operand as one vmapped program.
    Same (pkey, fkey) split discipline as the dense program; the
    perturbation draws noise for the stored blocks only.  ``use_fused``
    routes every MU iteration's X-sided products through the single-pass
    kernels/bcsr_fused.py (ISSUE 5)."""
    from repro.core.sparse import (perturb_bcsr, sparse_mu_step,
                                   sparse_rel_error)
    n, m = sp.n, sp.m

    def one_member(member_key):
        pkey, fkey = jax.random.split(member_key)
        sp_q = perturb_bcsr(pkey, sp, delta)
        st = init_factors(fkey, n, m, k, dtype=sp.data.dtype)

        def body(_, c):
            return sparse_mu_step(sp_q, c[0], c[1], eps,
                                  use_fused=use_fused, impl=impl,
                                  sanitize=sanitize,
                                  trace_metrics=trace_metrics)

        A, R = jax.lax.fori_loop(0, iters, body, (st.A, st.R))
        st = normalize(RescalState(A=A, R=R, step=st.step))
        return st.A, st.R, sparse_rel_error(sp, st.A, st.R,
                                            use_fused=use_fused, impl=impl)

    return jax.vmap(one_member)(keys)


def _loop_members_bcsr(sp, keys, k: int, cfg) -> EnsembleResult:
    """Sequential BCSR members — the memory-bound fallback (one perturbed
    pattern's blocks live at a time, vs r copies in the batched program)."""
    from repro.core.sparse import (perturb_bcsr, sparse_mu_step,
                                   sparse_rel_error)
    from repro.core.rescal import EPS_DEFAULT as eps
    fused = _fused_opts(cfg)
    A_l, R_l, errs = [], [], []
    for mkey in keys:
        pkey, fkey = jax.random.split(mkey)
        sp_q = perturb_bcsr(pkey, sp, cfg.perturbation_delta)
        st = init_factors(fkey, sp.n, sp.m, k, dtype=sp.data.dtype)
        A, R = st.A, st.R
        for _ in range(cfg.rescal_iters):
            A, R = sparse_mu_step(sp_q, A, R, eps,
                                  sanitize=_sanitize_opt(cfg),
                                  trace_metrics=_trace_opt(cfg), **fused)
        st = normalize(RescalState(A=A, R=R, step=st.step))
        A_l.append(st.A)
        R_l.append(st.R)
        errs.append(sparse_rel_error(sp, st.A, st.R, **fused))
    return EnsembleResult(A=jnp.stack(A_l), R=jnp.stack(R_l),
                          errors=jnp.stack(errs))


def run_ensemble_bcsr_dense_reference(sp, k: int, cfg, *,
                                      members: Sequence[int] | None = None
                                      ) -> EnsembleResult:
    """The acceptance oracle: replay each BCSR member's exact stored-block
    noise through the DENSE member pipeline (densify the perturbed tensor,
    run the dense batched MU).  Same member keys, same init draws — so
    batched BCSR members must match this member-for-member to float
    tolerance."""
    from repro.core.rescal import EPS_DEFAULT as eps
    from repro.core.rescal import mu_step_batched, rel_error
    from repro.core.sparse import perturb_bcsr, to_dense
    members = tuple(members) if members is not None else \
        tuple(range(cfg.n_perturbations))
    keys = unit_keys(cfg, k, members)
    X_ref = to_dense(sp)
    A_l, R_l, errs = [], [], []
    for mkey in keys:
        pkey, fkey = jax.random.split(mkey)
        X_q = to_dense(perturb_bcsr(pkey, sp, cfg.perturbation_delta))
        st = init_factors(fkey, sp.n, sp.m, k, dtype=X_q.dtype)
        for _ in range(cfg.rescal_iters):
            st = mu_step_batched(X_q, st, eps)
        st = normalize(st)
        A_l.append(st.A)
        R_l.append(st.R)
        errs.append(rel_error(X_ref, st.A, st.R))
    return EnsembleResult(A=jnp.stack(A_l), R=jnp.stack(R_l),
                          errors=jnp.stack(errs))


def perturb_sharded_blocked(key: jax.Array, sharded, q,
                            delta: float = 0.02):
    """Host emulation of the BCSR mesh path's shard-local perturbation:
    perturb each (i, j) shard's stored blocks with ``perturb_shard`` keyed
    by (member id, linear grid index) — bit-identical noise to the sharded
    program (the sparse twin of ``perturb_blocked``)."""
    g = sharded.g
    rows = []
    for i in range(g):
        cols = []
        for j in range(g):
            # rescal-lint: disable=key-discipline -- same root-key contract
            # as perturb_blocked: perturb_shard folds (q, grid index) in
            cols.append(perturb_shard(key, sharded.data[i, j], q,
                                      i * g + j, delta))
        rows.append(jnp.stack(cols))
    return sharded.with_data(jnp.stack(rows))


def run_ensemble_bcsr_sharded_reference(sharded, k: int, cfg, *,
                                        members: Sequence[int] | None = None
                                        ) -> EnsembleResult:
    """Single-host sequential run replaying the mesh program's blocked
    noise on a ShardedBCSR — the oracle for BCSR mesh-vs-host parity."""
    from repro.core.rescal import EPS_DEFAULT as eps
    from repro.core.sparse import sparse_mu_step, sparse_rel_error
    members = tuple(members) if members is not None else \
        tuple(range(cfg.n_perturbations))
    keys = unit_keys(cfg, k, members)
    sp_ref = sharded.to_bcsr()
    A_l, R_l, errs = [], [], []
    for mkey, q in zip(keys, members):
        pkey, fkey = jax.random.split(mkey)
        sp_q = perturb_sharded_blocked(pkey, sharded, q,
                                       cfg.perturbation_delta).to_bcsr()
        st = init_factors(fkey, sharded.n_pad, sharded.m, k,
                          dtype=sp_q.data.dtype)
        A, R = st.A, st.R
        for _ in range(cfg.rescal_iters):
            A, R = sparse_mu_step(sp_q, A, R, eps)
        st = normalize(RescalState(A=A, R=R, step=st.step))
        A_l.append(st.A)
        R_l.append(st.R)
        errs.append(sparse_rel_error(sp_ref, st.A, st.R))
    return EnsembleResult(A=jnp.stack(A_l), R=jnp.stack(R_l),
                          errors=jnp.stack(errs))


@functools.lru_cache(maxsize=64)
def make_mesh_ensemble_bcsr(mesh, *, k: int, n_pad: int, m: int, r_run: int,
                            grid: int, schedule: str = "batched",
                            delta: float = 0.02, iters: int = 200,
                            dtype=jnp.float32, key_ndim: int = 2,
                            use_fused: bool = False, fused_impl: str = "auto",
                            sanitize: bool = False,
                            trace_metrics: bool = False):
    """The BCSR twin of ``make_mesh_ensemble``: a jitted sharded program
    ``(data, rows, cols, keys, ids) -> (A_ens, R_ens, errs)`` over the
    stacked shard layout of ``io.partition.ShardedBCSR``.  Each device
    holds only its (m, nnzb_loc, bs, bs) blocks; perturbation multiplies
    the stored blocks shard-locally (zero padding blocks stay zero), so
    neither the global tensor nor any member copy of it ever exists."""
    from jax import shard_map
    from repro.core.sparse import BCSR
    from repro.dist import sharding as sh
    from repro.dist.engine import (DistRescalConfig, get_mu_iter,
                                   local_normalize, local_rel_error_bcsr)

    gr = mesh.shape[sh.ROW_AXIS]
    gc = mesh.shape[sh.COL_AXIS]
    if gr != gc:
        raise ValueError(f"BCSR ensembles need a square grid, got "
                         f"({gr}, {gc})")
    if grid != gr:
        # shard_map would happily re-split a mismatched leading (g, g)
        # axis and the local body would keep only data[0, 0] — silently
        # dropping shards — so the layouts must match exactly
        raise ValueError(f"operand was partitioned for a {grid}x{grid} "
                         f"grid but the mesh grid is {gr}x{gc}; "
                         f"re-partition for this mesh")
    if n_pad % gr:
        raise ValueError(f"the grid side {gr} must divide n_pad={n_pad}")
    pods = dict(mesh.shape).get(sh.ENSEMBLE_AXIS, 1)
    if r_run % pods:
        raise ValueError(f"r_run={r_run} members are not divisible by "
                         f"pods={pods}")

    dcfg = DistRescalConfig(schedule=schedule, use_fused_kernel=use_fused,
                            fused_impl=fused_impl, sanitize=sanitize,
                            trace_metrics=trace_metrics)
    it = get_mu_iter("bcsr", schedule)
    mspecs = sh.ensemble_member_specs(mesh, key_ndim=key_ndim)
    x_spec, i_spec, _, _ = sh.bcsr_specs()
    n_loc = n_pad // gr

    def local(data, rows, cols, keys_l, ids_l):
        spl = BCSR(data=data[0, 0], block_rows=rows[0, 0],
                   block_cols=cols[0, 0], n=n_loc)
        i = jax.lax.axis_index(sh.ROW_AXIS)
        j = jax.lax.axis_index(sh.COL_AXIS)
        lin = i * gc + j

        def one_member(mkey, q):
            pkey, fkey = jax.random.split(mkey)
            sp_q = spl._replace(
                data=perturb_shard(pkey, spl.data, q, lin, delta))
            st0 = init_factors(fkey, n_pad, m, k, dtype=dtype)
            Ai = jax.lax.dynamic_slice_in_dim(st0.A, i * n_loc, n_loc,
                                              axis=0)

            def body(_, c):
                return it(sp_q, c[0], c[1], dcfg)

            Ai, R = jax.lax.fori_loop(0, iters, body, (Ai, st0.R))
            Ai, R = local_normalize(Ai, R)
            return Ai, R, local_rel_error_bcsr(spl, Ai, R)

        return jax.vmap(one_member)(keys_l, ids_l)

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, i_spec, i_spec, mspecs["keys"], mspecs["ids"]),
        out_specs=(mspecs["A"], mspecs["R"], mspecs["err"]),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Mesh-sharded program (shard_map over pod x data x model)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_mesh_ensemble(mesh, *, k: int, n: int, m: int, r_run: int,
                       schedule: str = "batched", delta: float = 0.02,
                       iters: int = 200, init: str = "random",
                       dtype=jnp.float32, key_ndim: int = 2,
                       use_fused: bool = False, fused_impl: str = "auto",
                       sanitize: bool = False, trace_metrics: bool = False):
    """Build the jitted sharded ensemble program ``(X, keys, ids) ->
    (A_ens, R_ens, errs)`` for `r_run` members on `mesh`.

    Memoized on exactly the fields the compiled program depends on (not a
    whole config object — seed / k-range / regress_iters churn would
    otherwise defeat the cache): a sweep split into many same-shaped units
    — and every retry — reuses one compiled program instead of re-tracing
    per scheduler call.

    Per-member init draws the global (n, k) factor on every device and
    slices the local row block — O(n k) redundant work that keeps the init
    bit-identical to the host reference; replacing it with per-shard init
    is a ROADMAP open item for exascale n.
    """
    from jax import shard_map
    from repro.dist import sharding as sh
    from repro.dist.engine import (DistRescalConfig, get_mu_iter,
                                   local_normalize, local_rel_error)

    if init != "random":
        raise NotImplementedError(
            "mesh ensemble supports init='random' only (distributed NNDSVD "
            "is a ROADMAP open item); use mode='loop' for nndsvd")
    gr = mesh.shape[sh.ROW_AXIS]
    gc = mesh.shape[sh.COL_AXIS]
    if n % gr or n % gc:
        raise ValueError(f"n={n} must divide the ({gr}, {gc}) grid")
    pods = dict(mesh.shape).get(sh.ENSEMBLE_AXIS, 1)
    if r_run % pods:
        raise ValueError(f"r_run={r_run} members are not divisible by "
                         f"pods={pods} (members shard evenly over the "
                         f"ensemble axis)")

    dcfg = DistRescalConfig(schedule=schedule, use_fused_kernel=use_fused,
                            fused_impl=fused_impl, sanitize=sanitize,
                            trace_metrics=trace_metrics)
    it = get_mu_iter("dense", schedule)
    specs = sh.ensemble_member_specs(mesh, key_ndim=key_ndim)
    n_loc = n // gr

    def local(Xl, keys_l, ids_l):
        i = jax.lax.axis_index(sh.ROW_AXIS)
        j = jax.lax.axis_index(sh.COL_AXIS)
        lin = i * gc + j

        def one_member(mkey, q):
            pkey, fkey = jax.random.split(mkey)
            X_q = perturb_shard(pkey, Xl, q, lin, delta)
            st0 = init_factors(fkey, n, m, k, dtype=dtype)
            Ai = jax.lax.dynamic_slice_in_dim(st0.A, i * n_loc, n_loc, axis=0)

            def body(_, c):
                return it(X_q, c[0], c[1], dcfg)

            Ai, R = jax.lax.fori_loop(0, iters, body, (Ai, st0.R))
            Ai, R = local_normalize(Ai, R)
            return Ai, R, local_rel_error(Xl, Ai, R)

        return jax.vmap(one_member)(keys_l, ids_l)

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(specs["X"], specs["keys"], specs["ids"]),
        out_specs=(specs["A"], specs["R"], specs["err"]),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Cross-k grid programs — the whole (k, q) grid as ONE device program
# ---------------------------------------------------------------------------
#
# Per-k batching (above) still traces and compiles one program per
# candidate rank, so a k_min..k_max sweep pays O(#k) XLA compiles and the
# scheduler serializes across ranks.  Padding every cell's factors to
# k_max = max(cfg.ks) with a per-cell column mask (core.rescal masked MU)
# collapses the entire flattened (k, q) grid into one vmapped program:
# the per-cell rank is DATA (an int32 vector), not a static argument, so
# any rank mix of the same chunk length reuses one compiled executable —
# the compile-count contract scripts/check_compiles.py guards in CI.

def grid_init(cells, cfg, n: int, m: int, k_max: int, dtype):
    """Per-cell (keys, ranks, padded init factors) for a grid chunk.
    ``cells`` is a sequence of flattened (k, q) grid cells.

    Init draws happen at the REFERENCE shape: the exact
    ``init_factors(fkey, n, m, k)`` draw the per-k batched program makes,
    zero-padded to k_max.  Drawing at (n, k_max) inside the program would
    change the random stream (uniform fills shapes row-major), breaking the
    member-for-member parity contract between grid and per-k modes — this
    is the grid twin of the mesh ensemble's draw-global-then-slice rule."""
    keys, kvals, A0, R0 = [], [], [], []
    per_k_keys: dict[int, jax.Array] = {}
    for k, q in cells:
        if k not in per_k_keys:      # one key-set derivation per rank
            per_k_keys[k] = unit_keys(
                cfg, k, tuple(range(cfg.n_perturbations)))
        mkey = per_k_keys[k][q]
        _, fkey = jax.random.split(mkey)
        st = pad_state(init_factors(fkey, n, m, k, dtype=dtype), k_max)
        keys.append(mkey)
        kvals.append(k)
        A0.append(st.A)
        R0.append(st.R)
    return (jnp.stack(keys), jnp.asarray(kvals, jnp.int32),
            jnp.stack(A0), jnp.stack(R0))


def _grid_members(X, keys, kvals, A0, R0, *, k_max: int, iters: int,
                  schedule: str, delta: float, eps: float,
                  sanitize: bool = False, trace_metrics: bool = False):
    """A chunk of flattened (k, q) cells as one jitted program over a dense
    operand.  Same (pkey, fkey) discipline as ``_batched_members`` (the
    fkey was consumed host-side by ``grid_init``); masked columns stay
    exactly zero through update/normalize, and ``rel_error`` needs no mask
    because zero columns contribute exactly zero to every contraction.
    The per-cell init factors A0/R0 are donated (dist.compat shim): they
    are built fresh per chunk by ``grid_init`` and never reused, and at
    (cells, n, k_max) they are the chunk's largest factor-sized buffers."""
    def one_cell(mkey, kv, A0u, R0u):
        mask = column_mask(kv, k_max, X.dtype)
        pkey, _ = jax.random.split(mkey)
        X_q = perturb(pkey, X, delta)
        st = RescalState(A=A0u, R=R0u, step=jnp.zeros((), jnp.int32))

        def body(_, s):
            return masked_mu_step(X_q, s, mask, eps, schedule, sanitize,
                                  trace_metrics)

        st = jax.lax.fori_loop(0, iters, body, st)
        st = masked_normalize(st, mask)
        return st.A, st.R, rel_error(X, st.A, st.R)

    return jax.vmap(one_cell)(keys, kvals, A0, R0)


_grid_members = donating_jit(
    _grid_members, donate_argnums=(3, 4),
    static_argnames=("k_max", "iters", "schedule", "delta", "eps",
                     "sanitize", "trace_metrics"))


def _grid_members_bcsr(sp, keys, kvals, A0, R0, *, k_max: int, iters: int,
                       delta: float, eps: float, use_fused: bool = False,
                       impl: str = "auto", sanitize: bool = False,
                       trace_metrics: bool = False):
    """The BCSR twin of ``_grid_members``: stored-block perturbation, masked
    sparse MU, one program for the whole rank mix.  ``use_fused`` swaps the
    spmm + spmm_t double sweep for the single-pass kernel (the masked-zero
    fixed point holds either way — see masked_sparse_mu_step)."""
    from repro.core.sparse import (masked_sparse_mu_step, perturb_bcsr,
                                   sparse_rel_error)

    def one_cell(mkey, kv, A0u, R0u):
        mask = column_mask(kv, k_max, sp.data.dtype)
        pkey, _ = jax.random.split(mkey)
        sp_q = perturb_bcsr(pkey, sp, delta)

        def body(_, c):
            return masked_sparse_mu_step(sp_q, c[0], c[1], mask, eps,
                                         use_fused=use_fused, impl=impl,
                                         sanitize=sanitize,
                                         trace_metrics=trace_metrics)

        A, R = jax.lax.fori_loop(0, iters, body, (A0u, R0u))
        st = masked_normalize(
            RescalState(A=A, R=R, step=jnp.zeros((), jnp.int32)), mask)
        return st.A, st.R, sparse_rel_error(sp, st.A, st.R,
                                            use_fused=use_fused, impl=impl)

    return jax.vmap(one_cell)(keys, kvals, A0, R0)


# the BCSR chunk program donates its per-cell init factors too (same
# contract as _grid_members: grid_init builds them fresh per chunk)
_grid_members_bcsr = donating_jit(
    _grid_members_bcsr, donate_argnums=(3, 4),
    static_argnames=("k_max", "iters", "delta", "eps", "use_fused",
                     "impl", "sanitize", "trace_metrics"))


@functools.lru_cache(maxsize=64)
def make_mesh_grid_ensemble(mesh, *, operand: str, k_max: int, n: int,
                            m: int, u_run: int, grid: int | None = None,
                            schedule: str = "batched", delta: float = 0.02,
                            iters: int = 200, dtype=jnp.float32,
                            key_ndim: int = 2, use_fused: bool = False,
                            fused_impl: str = "auto",
                            sanitize: bool = False,
                            trace_metrics: bool = False):
    """The cross-k grid program on the ("pod", "data", "model") mesh: one
    shard_map program whose flattened (k, q) cell axis rides the
    pod/`ENSEMBLE_AXIS`, built from the same ``dist.engine.get_mu_iter``
    per-device bodies as every other distributed path.

    ``operand`` dispatches "dense" (X (m, n, n), signature ``(X, keys,
    kvals, ids, A0, R0)``) vs "bcsr" (ShardedBCSR stacked shards,
    ``(data, rows, cols, keys, kvals, ids, A0, R0)``).  Per-cell init
    arrives row-sharded from ``grid_init`` (reference-shape draws padded to
    k_max — which also removes the per-k mesh path's redundant every-device
    global init draw) and per-cell ranks arrive as data, so one compiled
    program serves any rank mix of the same chunk length.  The perturbation
    stays shard-local (``perturb_shard`` keyed by member id q + linear grid
    index), i.e. noise is bit-identical to the per-k mesh ensemble's, which
    is what makes grid-vs-per-k mesh parity exactly testable."""
    from jax import shard_map
    from repro.core.sparse import BCSR
    from repro.dist import sharding as sh
    from repro.dist.engine import (DistRescalConfig, get_mu_iter,
                                   local_normalize, local_rel_error,
                                   local_rel_error_bcsr)

    gr = mesh.shape[sh.ROW_AXIS]
    gc = mesh.shape[sh.COL_AXIS]
    pods = dict(mesh.shape).get(sh.ENSEMBLE_AXIS, 1)
    if u_run % pods:
        raise ValueError(f"a grid chunk of {u_run} cells does not shard "
                         f"evenly over pods={pods}; pick a grid_chunk "
                         f"divisible by the pod count")
    if operand == "bcsr":
        if gr != gc:
            raise ValueError(f"BCSR ensembles need a square grid, got "
                             f"({gr}, {gc})")
        if grid != gr:
            raise ValueError(f"operand was partitioned for a {grid}x{grid} "
                             f"grid but the mesh grid is {gr}x{gc}; "
                             f"re-partition for this mesh")
    if n % gr or n % gc:
        raise ValueError(f"n={n} must divide the ({gr}, {gc}) grid")

    dcfg = DistRescalConfig(schedule=schedule, use_fused_kernel=use_fused,
                            fused_impl=fused_impl, sanitize=sanitize,
                            trace_metrics=trace_metrics)
    it = get_mu_iter(operand, schedule)
    mspecs = sh.ensemble_member_specs(mesh, key_ndim=key_ndim)
    n_loc = n // gr

    def cell_loop(op_local, keys_l, kv_l, ids_l, A0_l, R0_l, perturb_op,
                  err_fn):
        def one_cell(mkey, kv, q, A0u, R0u):
            mask = column_mask(kv, k_max, dtype)
            mask2 = mask[:, None] * mask[None, :]
            pkey, _ = jax.random.split(mkey)
            op_q = perturb_op(pkey, q)

            def body(_, c):
                Ai, R = it(op_q, c[0], c[1], dcfg)
                return Ai * mask, R * mask2

            Ai, R = jax.lax.fori_loop(0, iters, body, (A0u, R0u))
            Ai, R = local_normalize(Ai, R)
            Ai, R = Ai * mask, R * mask2
            return Ai, R, err_fn(op_local, Ai, R)

        return jax.vmap(one_cell)(keys_l, kv_l, ids_l, A0_l, R0_l)

    cell_specs = (mspecs["keys"], mspecs["ids"], mspecs["ids"],
                  mspecs["A"], mspecs["R"])
    out_specs = (mspecs["A"], mspecs["R"], mspecs["err"])

    if operand == "dense":
        def local(Xl, keys_l, kv_l, ids_l, A0_l, R0_l):
            i = jax.lax.axis_index(sh.ROW_AXIS)
            j = jax.lax.axis_index(sh.COL_AXIS)
            lin = i * gc + j
            return cell_loop(
                Xl, keys_l, kv_l, ids_l, A0_l, R0_l,
                lambda pkey, q: perturb_shard(pkey, Xl, q, lin, delta),
                local_rel_error)

        in_specs = (mspecs["X"],) + cell_specs
    else:
        x_spec, i_spec, _, _ = sh.bcsr_specs()

        def local(data, rows, cols, keys_l, kv_l, ids_l, A0_l, R0_l):
            spl = BCSR(data=data[0, 0], block_rows=rows[0, 0],
                       block_cols=cols[0, 0], n=n_loc)
            i = jax.lax.axis_index(sh.ROW_AXIS)
            j = jax.lax.axis_index(sh.COL_AXIS)
            lin = i * gc + j
            return cell_loop(
                spl, keys_l, kv_l, ids_l, A0_l, R0_l,
                lambda pkey, q: spl._replace(
                    data=perturb_shard(pkey, spl.data, q, lin, delta)),
                local_rel_error_bcsr)

        in_specs = (x_spec, i_spec, i_spec) + cell_specs

    sharded = shard_map(local, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    return jax.jit(sharded)


def run_sweep_batched(X, cells, cfg, *, mesh=None) -> EnsembleResult:
    """Execute a chunk of flattened (k, q) grid cells as ONE program — the
    cross-k tentpole.  ``cells`` is a sequence of (k, q) pairs; rows come
    back padded to k_max = max(cfg.ks) (the scheduler crops each row to its
    own k before reduction; masked columns are exact zeros).

    Operand dispatch mirrors ``run_ensemble``: dense array or
    ``core.sparse.BCSR`` on a single host (vmap programs), or with `mesh` a
    dense array / ``io.partition.ShardedBCSR`` through the sharded grid
    program (cell axis on the pod/ENSEMBLE_AXIS)."""
    from repro.core.sparse import BCSR
    cells = tuple(cells)
    k_max = max(cfg.ks)
    _require_random_init(cfg, "the cross-k grid program")
    fused = _fused_opts(cfg)
    sanitize = _sanitize_opt(cfg)
    trace_metrics = _trace_opt(cfg)
    mesh_fused = dict(use_fused=fused["use_fused"],
                      fused_impl=fused["impl"], sanitize=sanitize,
                      trace_metrics=trace_metrics)
    sharded = X if _is_sharded_bcsr(X) else None
    if mesh is not None:
        ids = jnp.asarray([q for _, q in cells], dtype=jnp.int32)
        if sharded is not None:
            keys, kvals, A0, R0 = grid_init(
                cells, cfg, sharded.n_pad, sharded.m, k_max,
                sharded.data.dtype)
            prog = make_mesh_grid_ensemble(
                mesh, operand="bcsr", k_max=k_max, n=sharded.n_pad,
                m=sharded.m, u_run=len(cells), grid=sharded.g,
                schedule=cfg.schedule, delta=cfg.perturbation_delta,
                iters=cfg.rescal_iters, dtype=sharded.data.dtype,
                key_ndim=keys.ndim, **mesh_fused)
            A, R, errs = prog(sharded.data, sharded.rows, sharded.cols,
                              keys, kvals, ids, A0, R0)
            return EnsembleResult(A=A, R=R, errors=errs)
        if isinstance(X, BCSR):
            raise ValueError(
                "a plain BCSR cannot be mesh-sharded — partition it "
                "(io.partition.partition_coo / partition_dense) and pass "
                "the ShardedBCSR")
        m, n, _ = X.shape
        keys, kvals, A0, R0 = grid_init(cells, cfg, n, m, k_max, X.dtype)
        prog = make_mesh_grid_ensemble(
            mesh, operand="dense", k_max=k_max, n=n, m=m, u_run=len(cells),
            schedule=cfg.schedule, delta=cfg.perturbation_delta,
            iters=cfg.rescal_iters, dtype=X.dtype, key_ndim=keys.ndim,
            **mesh_fused)
        A, R, errs = prog(X, keys, kvals, ids, A0, R0)
        return EnsembleResult(A=A, R=R, errors=errs)
    if sharded is not None or isinstance(X, BCSR):
        # single host: same merged-global-BCSR collapse as run_ensemble
        sp = sharded.to_bcsr() if sharded is not None else X
        keys, kvals, A0, R0 = grid_init(cells, cfg, sp.n, sp.m, k_max,
                                        sp.data.dtype)
        A, R, errs = _grid_members_bcsr(
            sp, keys, kvals, A0, R0, k_max=k_max, iters=cfg.rescal_iters,
            delta=cfg.perturbation_delta, eps=EPS_DEFAULT,
            sanitize=sanitize, trace_metrics=trace_metrics, **fused)
        return EnsembleResult(A=A, R=R, errors=errs)
    m, n, _ = X.shape
    keys, kvals, A0, R0 = grid_init(cells, cfg, n, m, k_max, X.dtype)
    A, R, errs = _grid_members(
        X, keys, kvals, A0, R0, k_max=k_max, iters=cfg.rescal_iters,
        schedule=cfg.schedule, delta=cfg.perturbation_delta,
        eps=EPS_DEFAULT, sanitize=sanitize, trace_metrics=trace_metrics)
    return EnsembleResult(A=A, R=R, errors=errs)


# ---------------------------------------------------------------------------
# Sequential reference loop (and the memory-bound fallback)
# ---------------------------------------------------------------------------

def _loop_members(X, keys, members: Sequence[int], k: int, cfg,
                  grid: tuple[int, int] | None = None,
                  runner=None) -> EnsembleResult:
    # Lazy import (runtime, cycle-safe): the per-member factorization body
    # is core.rescalk's default_member_runner — one init/MU discipline, not
    # a second copy that could drift from the compat path.  `runner`
    # overrides it for the legacy custom-member_runner path, which
    # delegates here so the split/perturb key discipline has ONE home.
    if runner is None:
        from repro.core.rescalk import default_member_runner
        runner = default_member_runner
    A_l, R_l, errs = [], [], []
    for mkey, q in zip(keys, members):
        pkey, fkey = jax.random.split(mkey)
        if grid is None:
            X_q = perturb(pkey, X, cfg.perturbation_delta)
        else:
            X_q = perturb_blocked(pkey, X, q, grid, cfg.perturbation_delta)
        state = runner(X_q, k, fkey, cfg)
        A_l.append(state.A)
        R_l.append(state.R)
        errs.append(rel_error(X, state.A, state.R))
    return EnsembleResult(A=jnp.stack(A_l), R=jnp.stack(R_l),
                          errors=jnp.stack(errs))


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def run_ensemble(X, k: int, cfg, *, members: Sequence[int] | None = None,
                 mesh=None, mode: str = "batched") -> EnsembleResult:
    """Run the perturbation-ensemble members of candidate rank k.

    `X` is the operand: a dense (m, n, n) array, a ``core.sparse.BCSR``
    (stored-block perturbation, single host), or an
    ``io.partition.ShardedBCSR`` (balanced shards; with `mesh` the fully
    sharded program, without it the merged single-host equivalent).
    `cfg` is a RescalkConfig-shaped object (duck-typed: n_perturbations,
    perturbation_delta, rescal_iters, schedule, init, seed).  `members`
    selects a subset of the r member ids (a scheduler work unit); default
    all.  `mesh` switches to the sharded program; `mode` selects batched
    vs sequential-loop execution on a single host.
    """
    from repro.core.sparse import BCSR
    members = tuple(members) if members is not None else \
        tuple(range(cfg.n_perturbations))
    keys = unit_keys(cfg, k, members)
    sharded = X if _is_sharded_bcsr(X) else None
    if mesh is not None:
        if mode != "batched":
            raise ValueError(
                f"mode={mode!r} is host-only; the mesh path is always the "
                f"batched sharded program (drop mesh= for the sequential "
                f"loop)")
        ids = jnp.asarray(members, dtype=jnp.int32)
        fused = _fused_opts(cfg)
        mesh_fused = dict(use_fused=fused["use_fused"],
                          fused_impl=fused["impl"],
                          sanitize=_sanitize_opt(cfg),
                          trace_metrics=_trace_opt(cfg))
        if sharded is not None:
            _require_random_init(cfg, "the BCSR mesh ensemble")
            prog = make_mesh_ensemble_bcsr(
                mesh, k=k, n_pad=sharded.n_pad, m=sharded.m,
                r_run=len(members), grid=sharded.g, schedule=cfg.schedule,
                delta=cfg.perturbation_delta, iters=cfg.rescal_iters,
                dtype=sharded.data.dtype, key_ndim=keys.ndim, **mesh_fused)
            A, R, errs = prog(sharded.data, sharded.rows, sharded.cols,
                              keys, ids)
            return EnsembleResult(A=A, R=R, errors=errs)
        if isinstance(X, BCSR):
            raise ValueError(
                "a plain BCSR cannot be mesh-sharded — partition it "
                "(io.partition.partition_coo / partition_dense) and pass "
                "the ShardedBCSR")
        m, n, _ = X.shape
        prog = make_mesh_ensemble(
            mesh, k=k, n=n, m=m, r_run=len(members),
            schedule=cfg.schedule, delta=cfg.perturbation_delta,
            iters=cfg.rescal_iters, init=cfg.init, dtype=X.dtype,
            key_ndim=keys.ndim, **mesh_fused)
        A, R, errs = prog(X, keys, ids)
        return EnsembleResult(A=A, R=R, errors=errs)
    if sharded is not None or isinstance(X, BCSR):
        # single host: a sharded operand collapses to its merged global
        # BCSR (permuted entity space — same space the mesh factors use)
        sp = sharded.to_bcsr() if sharded is not None else X
        _require_random_init(cfg, "BCSR ensembles")
        if mode == "batched":
            A, R, errs = _batched_members_bcsr(
                sp, keys, k=k, iters=cfg.rescal_iters,
                delta=cfg.perturbation_delta, eps=EPS_DEFAULT,
                sanitize=_sanitize_opt(cfg), trace_metrics=_trace_opt(cfg),
                **_fused_opts(cfg))
            return EnsembleResult(A=A, R=R, errors=errs)
        if mode == "loop":
            return _loop_members_bcsr(sp, keys, k, cfg)
        raise ValueError(f"unknown ensemble mode {mode!r}")
    if mode == "batched":
        A, R, errs = _batched_members(
            X, keys, k=k, iters=cfg.rescal_iters, schedule=cfg.schedule,
            init=cfg.init, delta=cfg.perturbation_delta, eps=EPS_DEFAULT,
            sanitize=_sanitize_opt(cfg), trace_metrics=_trace_opt(cfg))
        return EnsembleResult(A=A, R=R, errors=errs)
    if mode == "loop":
        return _loop_members(X, keys, members, k, cfg)
    raise ValueError(f"unknown ensemble mode {mode!r}")


def run_ensemble_reference(X, k: int, cfg, *, grid: tuple[int, int],
                           members: Sequence[int] | None = None
                           ) -> EnsembleResult:
    """Single-host sequential run with the mesh path's blocked perturbation
    — the oracle for mesh-vs-host parity tests (same noise by
    construction)."""
    members = tuple(members) if members is not None else \
        tuple(range(cfg.n_perturbations))
    keys = unit_keys(cfg, k, members)
    return _loop_members(X, keys, members, k, cfg, grid=grid)
