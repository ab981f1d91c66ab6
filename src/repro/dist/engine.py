"""The distributed RESCAL MU engine — one step factory for every operand.

This consolidates what used to be four near-duplicate shard_map factories
(`make_dist_step`, `make_ensemble_step`, `make_dist_step_sparse`,
`make_ensemble_step_sparse` in core/rescal_dist.py) behind a single
``make_mu_step(mesh, cfg, operand=..., pod_axis=..., n=...)`` that
dispatches on:

  operand   — "dense" (X (m, n, n) blocks) | "bcsr" (balanced block-sparse
              shards, core/sparse.py); the collective schedule is identical
              (paper §4.1: "communication requirements remain unchanged for
              sparse data").
  pod_axis  — None for one factorization, "pod" for the RESCALk ensemble
              (members vmapped, member axis sharded over pods, X replicated
              across pods).
  schedule  — cfg.schedule: "batched" (all m slices per collective, O(1)
              psums/iter, ours) | "sliced" (the paper's per-slice Alg. 3
              loop, O(m) psums/iter).

Fused-kernel path: ``cfg.kernel_policy.use_fused`` (a kernels.KernelPolicy;
the deprecated ``use_fused_kernel``/``fused_impl`` fields still resolve
through it) routes the two X-sided products
of each MU iteration through the single-X-pass kernels (via ops.py
dispatch) — dense operands through kernels/fused_bilinear, BCSR operands
through kernels/bcsr_fused — so one pass over the (stored blocks of) X
emits both X @ A^(j) and X^T @ A^(i).  The engine exploits associativity,
(X^T A) R == X^T (A R), so the single-pass products feed the exact
reference update; on the sparse side this additionally eliminates the
oracle's (m, nnzb, bs, k) gathered-AR intermediate (spmm_t with a
per-slice operand).  ``cfg.kernel_policy.impl`` selects pallas / interpret /
jnp-oracle execution (interpret validates the kernel body on CPU).  The
reference segment-sum/einsum path remains the default.

All module-level imports here stay inside repro.dist (jax + sharding);
repro.core / repro.kernels are imported lazily inside factories so that
``repro.core.rescal_dist`` can re-export this module without an import
cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the sanitizer and obs.metrics are dependency-light (jax + numpy, never
# repro.core / repro.kernels), so the lazy-import rule in the module
# docstring holds
from repro.analysis.sanitizer import sanitize_state
from repro.obs.metrics import record_metrics, update_ratio
from .sharding import (COL_AXIS, POD_AXIS, ROW_AXIS, bcsr_specs,
                       diag_broadcast_col_to_row, diag_broadcast_row_to_col,
                       ensemble_factor_specs, factor_specs, psum_cast)

EPS_DEFAULT = 1e-16   # matches core.rescal.EPS_DEFAULT (kept local: no cycle)


@dataclasses.dataclass(frozen=True)
class DistRescalConfig:
    schedule: str = "batched"        # "batched" | "sliced"
    eps: float = EPS_DEFAULT
    comm_dtype: str | None = None    # e.g. "bfloat16"
    # kernel: a kernels.KernelPolicy (the unified knob bundle, PR 9);
    # use_fused_kernel / fused_impl are its deprecated aliases, honored
    # when `kernel` is unset.  Engine code reads `kernel_policy` only.
    kernel: object | None = None
    use_fused_kernel: bool = False   # deprecated alias of kernel.use_fused
    fused_impl: str = "auto"         # deprecated alias of kernel.impl
    sanitize: bool = False           # runtime factor checks (repro.analysis)
    trace_metrics: bool = False      # per-iteration telemetry (repro.obs)

    @property
    def comm_jnp_dtype(self):
        return None if self.comm_dtype is None else jnp.dtype(self.comm_dtype)

    @property
    def kernel_policy(self):
        if self.kernel is not None:
            return self.kernel
        from repro.kernels.policy import KernelPolicy    # lazy: no cycle
        return KernelPolicy(use_fused=self.use_fused_kernel,
                            impl=self.fused_impl)


# ---------------------------------------------------------------------------
# X-sided products (the only part the fused kernel replaces)
# ---------------------------------------------------------------------------

def _fused_products(Xl, Aj, Ai, cfg: DistRescalConfig):
    """Single-X-pass local products via the fused bilinear kernel:
       XA^loc  = X^(i,j) @ A^(j)      (m, nr, k)  — row-indexed after psum
       XTA^loc = X^(i,j)^T @ A^(i)    (m, nc, k)  — col-indexed after psum
    """
    from repro.kernels import ops
    m = Xl.shape[0]
    B2 = jnp.broadcast_to(Ai[None], (m,) + Ai.shape)
    with jax.named_scope("products"):
        return ops.fused_xa_xtb(Xl, Aj, B2, impl=cfg.kernel_policy.impl)


@jax.named_scope("mu")
def _mu_iter_batched(Xl, Ai, R, cfg: DistRescalConfig):
    """One MU iteration, all m slices per collective (paper Alg. 3 math,
    our O(1)-collective schedule)."""
    cd = cfg.comm_jnp_dtype
    eps = cfg.eps
    Aj = diag_broadcast_row_to_col(Ai, cd)
    G = psum_cast(Ai.T @ Ai, ROW_AXIS, cd)                       # line 3

    if cfg.kernel_policy.use_fused:
        XA_loc, XTA_loc = _fused_products(Xl, Aj, Ai, cfg)
        XA = psum_cast(XA_loc, COL_AXIS, cd)                     # line 5
    else:
        with jax.named_scope("products"):
            XA_loc = jnp.einsum("mij,jk->mik", Xl, Aj)
        XA = psum_cast(XA_loc, COL_AXIS, cd)
        XTA_loc = None

    # ---- R update (paper lines 6-9), batched over m ----
    ATXA = psum_cast(jnp.einsum("ia,mib->mab", Ai, XA), ROW_AXIS, cd)
    R = R * ATXA / (jnp.einsum("ab,mbc,cd->mad", G, R, G) + eps)

    # ---- A update (paper lines 10-21), batched over m ----
    XART = jnp.einsum("mia,msa->is", XA, R)                      # line 10
    if XTA_loc is not None:
        # (X^T A) R == X^T (A R): the fused pass already produced X^T A, so
        # only a (k)-thin contraction with the fresh R remains — X is not
        # re-read.  psum after the contraction keeps wire bytes at (nc, k).
        XTAR_j = psum_cast(jnp.einsum("mja,mab->jb", XTA_loc, R),
                           ROW_AXIS, cd)
    else:
        AR = jnp.einsum("ia,mab->mib", Ai, R)                    # line 11
        # NOTE "mij,mik->mjk" + sum, NOT "mij,mik->jk": the joint (m, i)
        # contraction forces XLA to materialize a layout copy of the full X
        # block (verified: temp == bytes(X) in memory_analysis); keeping m
        # as a batch dim costs an (m, k, n_loc) temp instead.
        with jax.named_scope("products"):
            XTAR_m = jnp.einsum("mij,mik->mjk", Xl, AR)
        XTAR_j = psum_cast(XTAR_m.sum(0), ROW_AXIS, cd)
    XTAR = diag_broadcast_col_to_row(XTAR_j, cd)                 # lines 12-13
    num = XART + XTAR                                            # line 14
    S = (jnp.einsum("mab,bc,mdc->ad", R, G, R)
         + jnp.einsum("mba,bc,mcd->ad", R, G, R))                # lines 15-19
    Ai_new = Ai * num / (Ai @ S + eps)                           # line 21
    Ai_new, R = sanitize_state(Ai_new, R,
                               where="dist.engine._mu_iter_batched",
                               enabled=cfg.sanitize)
    if cfg.trace_metrics:  # shard-local norms only: no collectives added
        record_metrics("dist.engine._mu_iter_batched",
                       a_norm=jnp.linalg.norm(Ai_new),
                       r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(Ai, Ai_new))
    return Ai_new, R


@jax.named_scope("mu")
def _mu_iter_sliced(Xl, Ai, R, cfg: DistRescalConfig):
    """One MU iteration, explicit loop over m slices — the paper's exact
    schedule with per-slice collectives (O(m) psums)."""
    cd = cfg.comm_jnp_dtype
    eps = cfg.eps
    k = Ai.shape[1]
    m = Xl.shape[0]
    Aj = diag_broadcast_row_to_col(Ai, cd)
    G = psum_cast(Ai.T @ Ai, ROW_AXIS, cd)                       # line 3

    def body(t, carry):
        R_acc, num, S = carry
        with jax.named_scope("products"):
            Xt = jax.lax.dynamic_index_in_dim(Xl, t, 0, keepdims=False)
        Rt = jax.lax.dynamic_index_in_dim(R_acc, t, 0, keepdims=False)
        if cfg.kernel_policy.use_fused:
            XA_loc, XTA_loc = _fused_products(Xt[None], Aj, Ai, cfg)
            XA = psum_cast(XA_loc[0], COL_AXIS, cd)              # line 5
        else:
            with jax.named_scope("products"):
                XA_loc = Xt @ Aj
            XA = psum_cast(XA_loc, COL_AXIS, cd)                 # line 5
            XTA_loc = None
        ATXA = psum_cast(Ai.T @ XA, ROW_AXIS, cd)                # line 6
        Rt = Rt * ATXA / (G @ Rt @ G + eps)                      # lines 7-9
        R_new = jax.lax.dynamic_update_index_in_dim(R_acc, Rt, t, 0)
        XART = XA @ Rt.T                                         # line 10
        if XTA_loc is not None:
            XTAR_j = psum_cast(XTA_loc[0] @ Rt, ROW_AXIS, cd)    # line 12
        else:
            AR = Ai @ Rt                                         # line 11
            with jax.named_scope("products"):
                XTAR_loc = Xt.T @ AR                             # line 12
            XTAR_j = psum_cast(XTAR_loc, ROW_AXIS, cd)
        XTAR = diag_broadcast_col_to_row(XTAR_j, cd)             # line 13
        num = num + XART + XTAR                                  # line 14
        S = S + (Rt @ G @ Rt.T) + (Rt.T @ G @ Rt)                # lines 15-20
        return R_new, num, S

    R, num, S = jax.lax.fori_loop(
        0, m, body, (R, jnp.zeros_like(Ai), jnp.zeros((k, k), Xl.dtype)))
    Ai_new = Ai * num / (Ai @ S + eps)                           # line 21
    Ai_new, R = sanitize_state(Ai_new, R,
                               where="dist.engine._mu_iter_sliced",
                               enabled=cfg.sanitize)
    if cfg.trace_metrics:  # shard-local norms only: no collectives added
        record_metrics("dist.engine._mu_iter_sliced",
                       a_norm=jnp.linalg.norm(Ai_new),
                       r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(Ai, Ai_new))
    return Ai_new, R


@jax.named_scope("mu")
def _mu_iter_batched_sparse(spl, Ai, R, cfg: DistRescalConfig):
    """Batched MU iteration on a local BCSR block (core/sparse.py).
    Identical collective schedule to the dense batched iteration; with
    ``cfg.kernel_policy.use_fused`` the two X-sided products come from ONE pass
    over the stored blocks (core.sparse.sparse_products — the same
    dispatch the host sweep programs use — onto kernels/bcsr_fused.py),
    with no second block sweep and no (m, nnzb, bs, k) gathered
    intermediate."""
    from repro.core.sparse import sparse_products, spmm, spmm_t
    cd = cfg.comm_jnp_dtype
    eps = cfg.eps
    Aj = diag_broadcast_row_to_col(Ai, cd)
    G = psum_cast(Ai.T @ Ai, ROW_AXIS, cd)                       # line 3

    if cfg.kernel_policy.use_fused:
        XA_loc, XTA_loc = sparse_products(spl, Aj, Ai, use_fused=True,
                                          impl=cfg.kernel_policy.impl)
        XA = psum_cast(XA_loc, COL_AXIS, cd)                     # line 5
    else:
        with jax.named_scope("products"):
            XA_loc = spmm(spl, Aj)
        XA = psum_cast(XA_loc, COL_AXIS, cd)                     # line 5
        XTA_loc = None

    ATXA = psum_cast(jnp.einsum("ia,mib->mab", Ai, XA), ROW_AXIS, cd)
    R = R * ATXA / (jnp.einsum("ab,mbc,cd->mad", G, R, G) + eps)

    XART = jnp.einsum("mia,msa->is", XA, R)
    if XTA_loc is not None:
        # (X^T A) R == X^T (A R): the fused block pass already produced
        # X^T A, so only a (k)-thin contraction with the fresh R remains —
        # the stored blocks are not re-swept and the oracle's
        # (m, nnzb, bs, k) gathered-AR intermediate never exists.
        XTAR_j = psum_cast(jnp.einsum("mja,mab->jb", XTA_loc, R),
                           ROW_AXIS, cd)
    else:
        AR = jnp.einsum("ia,mab->mib", Ai, R)                    # (m, nr, k)
        with jax.named_scope("products"):
            XTAR_m = spmm_t(spl, AR)                             # (m, nr, k)
        XTAR_j = psum_cast(XTAR_m.sum(axis=0), ROW_AXIS, cd)
    XTAR = diag_broadcast_col_to_row(XTAR_j, cd)
    num = XART + XTAR
    S = (jnp.einsum("mab,bc,mdc->ad", R, G, R)
         + jnp.einsum("mba,bc,mcd->ad", R, G, R))
    Ai_new = Ai * num / (Ai @ S + eps)
    Ai_new, R = sanitize_state(Ai_new, R,
                               where="dist.engine._mu_iter_batched_sparse",
                               enabled=cfg.sanitize)
    if cfg.trace_metrics:  # shard-local norms only: no collectives added
        record_metrics("dist.engine._mu_iter_batched_sparse",
                       a_norm=jnp.linalg.norm(Ai_new),
                       r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(Ai, Ai_new))
    return Ai_new, R


@jax.named_scope("mu")
def _mu_iter_sliced_sparse(spl, Ai, R, cfg: DistRescalConfig):
    """Sparse MU iteration with the paper's per-slice schedule.  At
    exabyte-tier n the batched schedule's (m, n/√p, k) dense intermediates
    are m x larger than one A shard and blow the 16 GiB HBM budget; slicing
    bounds them to one slice's worth."""
    from repro.core.sparse import BCSR, sparse_products, spmm, spmm_t
    cd = cfg.comm_jnp_dtype
    eps = cfg.eps
    k = Ai.shape[1]
    m = spl.data.shape[0]
    Aj = diag_broadcast_row_to_col(Ai, cd)
    G = psum_cast(Ai.T @ Ai, ROW_AXIS, cd)

    def body(t, carry):
        R_acc, num, S = carry
        with jax.named_scope("products"):
            data_t = jax.lax.dynamic_index_in_dim(spl.data, t, 0,
                                                  keepdims=True)
        sp_t = BCSR(data=data_t, block_rows=spl.block_rows,
                    block_cols=spl.block_cols, n=spl.n)
        Rt = jax.lax.dynamic_index_in_dim(R_acc, t, 0, keepdims=False)
        if cfg.kernel_policy.use_fused:
            XA_loc, XTA_loc = sparse_products(sp_t, Aj, Ai, use_fused=True,
                                              impl=cfg.kernel_policy.impl)
            XA = psum_cast(XA_loc[0], COL_AXIS, cd)
        else:
            with jax.named_scope("products"):
                XA_loc = spmm(sp_t, Aj)[0]
            XA = psum_cast(XA_loc, COL_AXIS, cd)
            XTA_loc = None
        ATXA = psum_cast(Ai.T @ XA, ROW_AXIS, cd)
        Rt = Rt * ATXA / (G @ Rt @ G + eps)
        R_new = jax.lax.dynamic_update_index_in_dim(R_acc, Rt, t, 0)
        XART = XA @ Rt.T
        if XTA_loc is not None:
            XTAR_j = psum_cast(XTA_loc[0] @ Rt, ROW_AXIS, cd)
        else:
            AR = Ai @ Rt
            with jax.named_scope("products"):
                XTAR_loc = spmm_t(sp_t, AR[None])[0]
            XTAR_j = psum_cast(XTAR_loc, ROW_AXIS, cd)
        XTAR = diag_broadcast_col_to_row(XTAR_j, cd)
        num = num + XART + XTAR
        S = S + (Rt @ G @ Rt.T) + (Rt.T @ G @ Rt)
        return R_new, num, S

    R, num, S = jax.lax.fori_loop(
        0, m, body, (R, jnp.zeros_like(Ai), jnp.zeros((k, k), Ai.dtype)))
    Ai_new = Ai * num / (Ai @ S + eps)
    Ai_new, R = sanitize_state(Ai_new, R,
                               where="dist.engine._mu_iter_sliced_sparse",
                               enabled=cfg.sanitize)
    if cfg.trace_metrics:  # shard-local norms only: no collectives added
        record_metrics("dist.engine._mu_iter_sliced_sparse",
                       a_norm=jnp.linalg.norm(Ai_new),
                       r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(Ai, Ai_new))
    return Ai_new, R


_ITERS = {
    ("dense", "batched"): _mu_iter_batched,
    ("dense", "sliced"): _mu_iter_sliced,
    ("bcsr", "batched"): _mu_iter_batched_sparse,
    ("bcsr", "sliced"): _mu_iter_sliced_sparse,
}


def get_mu_iter(operand: str, schedule: str) -> Callable:
    """Local MU-iteration body ``(local_operand, Ai, R, cfg) -> (Ai, R)``.

    Public composition point: other subsystems build their own shard_map
    programs from the same per-device math (repro.selection fuses the
    perturbation ensemble around these bodies) without duplicating the
    collective schedule.
    """
    try:
        return _ITERS[(operand, schedule)]
    except KeyError:
        raise ValueError(f"unknown operand/schedule: "
                         f"{operand!r}/{schedule!r}") from None


def local_normalize(Ai, R, comm_dtype=None, eps: float = 1e-12):
    """Distributed factor normalization (||A_col|| = 1, scale folded into R)
    — the shard-local counterpart of core.rescal.normalize: the column
    norms need one psum over the row shards, everything else is local."""
    c2 = psum_cast((Ai * Ai).sum(axis=0), ROW_AXIS, comm_dtype)
    c = jnp.maximum(jnp.sqrt(c2), eps)
    return Ai / c, jnp.einsum("a,mab,b->mab", c, R, c)


# ---------------------------------------------------------------------------
# The unified step factory
# ---------------------------------------------------------------------------

def make_mu_step(mesh: Mesh, cfg: DistRescalConfig, *,
                 operand: str = "dense", pod_axis: str | None = None,
                 n: int | None = None, iters: int = 1) -> Callable:
    """jit'd MU step over global arrays on the ("data", "model") grid.

    Signatures by dispatch:
      dense              (X (m,n,n), A (n,k), R (m,k,k))        -> (A, R)
      dense  + pod_axis  (X, A_ens (r,n,k), R_ens (r,m,k,k))    -> ens
      bcsr               (data, rows, cols, A, R)               -> (A, R)
      bcsr   + pod_axis  (data, rows, cols, A_ens, R_ens)       -> ens

    `n` (global entity count) is required for bcsr operands.  `pod_axis`
    shards the ensemble-member axis over pods with X replicated per pod.
    """
    it = get_mu_iter(operand, cfg.schedule)

    def run_iters(local_operand, Ai, R):
        def body(_, c):
            return it(local_operand, c[0], c[1], cfg)
        return jax.lax.fori_loop(0, iters, body, (Ai, R))

    if operand == "dense":
        if pod_axis is None:
            x_spec, a_spec, r_spec = factor_specs(None)

            def local_step(Xl, Ai, R):
                return run_iters(Xl, Ai, R)
        else:
            x_spec, a_spec, r_spec = ensemble_factor_specs(pod_axis)

            def local_step(Xl, A_ens, R_ens):
                return jax.vmap(lambda a, r: run_iters(Xl, a, r))(
                    A_ens, R_ens)

        sharded = shard_map(
            local_step, mesh=mesh,
            in_specs=(x_spec, a_spec, r_spec),
            out_specs=(a_spec, r_spec),
            check_vma=False)
        return jax.jit(sharded)

    # ---- bcsr ----
    if n is None:
        raise ValueError("bcsr operand requires the global entity count n")
    from repro.core.sparse import BCSR
    gr = mesh.shape[ROW_AXIS]
    n_loc = n // gr
    x_spec, i_spec, a_spec, r_spec = bcsr_specs(ensemble=pod_axis is not None)

    def local_bcsr(data, rows, cols, A, R):
        spl = BCSR(data=data[0, 0], block_rows=rows[0, 0],
                   block_cols=cols[0, 0], n=n_loc)
        if pod_axis is None:
            return run_iters(spl, A, R)
        return jax.vmap(lambda a, r: run_iters(spl, a, r))(A, R)

    sharded = shard_map(
        local_bcsr, mesh=mesh,
        in_specs=(x_spec, i_spec, i_spec, a_spec, r_spec),
        out_specs=(a_spec, r_spec),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Distributed error / GSPMD alternative / driver
# ---------------------------------------------------------------------------

def _local_rel_error_body(Ai, R, xa_product, sqnorm_local, cd):
    """Shared tail of the distributed error: the small-intermediates
    identity (see core.rescal.rel_error) with only k-sized wire payloads.
    Operand specifics enter as callables: ``xa_product(Aj)`` -> the local
    X @ A^(j) block and ``sqnorm_local()`` -> the local ||X||^2 term."""
    Aj = diag_broadcast_row_to_col(Ai, cd)
    G = psum_cast(Ai.T @ Ai, ROW_AXIS, cd)
    XA = psum_cast(xa_product(Aj), COL_AXIS, cd)
    ATXA = psum_cast(jnp.einsum("ia,mib->mab", Ai, XA), ROW_AXIS, cd)
    x2 = jax.lax.psum(jax.lax.psum(sqnorm_local(), ROW_AXIS), COL_AXIS)
    cross = jnp.vdot(ATXA, R)
    fit2 = jnp.einsum("ab,mac,cd,mbd->", G, R, G, R)
    err2 = jnp.maximum(x2 - 2.0 * cross + fit2, 0.0)
    return jnp.sqrt(err2) / jnp.sqrt(x2)


def local_rel_error(Xl, Ai, R, cd=None):
    """Distributed relative error on a dense X block.  Shard-local body —
    callable inside any shard_map on the 2D grid (the selection ensemble
    vmaps it over members)."""
    return _local_rel_error_body(
        Ai, R, lambda Aj: jnp.einsum("mij,jk->mik", Xl, Aj),
        lambda: jnp.vdot(Xl, Xl), cd)


def local_rel_error_bcsr(spl, Ai, R, cd=None):
    """Shard-local relative error on a BCSR block — same collective
    schedule as the dense twin, X products via spmm.  Used by the
    selection subsystem's BCSR mesh ensemble."""
    from repro.core.sparse import spmm, sqnorm
    return _local_rel_error_body(
        Ai, R, lambda Aj: spmm(spl, Aj), lambda: sqnorm(spl), cd)


def make_dist_error(mesh: Mesh) -> Callable:
    x_spec, a_spec, r_spec = factor_specs(None)
    sharded = shard_map(
        lambda Xl, Ai, R: local_rel_error(Xl, Ai, R), mesh=mesh,
        in_specs=(x_spec, a_spec, r_spec), out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)


def make_gspmd_step(mesh: Mesh, cfg: DistRescalConfig, iters: int = 1
                    ) -> Callable:
    """Same math via sharding constraints only; XLA chooses the
    collectives.  Used by the roofline harness to compare schedules."""
    from repro.core.rescal import MU_SCHEDULES, RescalState
    x_spec, a_spec, r_spec = factor_specs(None)
    step = MU_SCHEDULES[cfg.schedule]

    def global_step(X, A, R):
        X = jax.lax.with_sharding_constraint(X, NamedSharding(mesh, x_spec))
        st = RescalState(A=A, R=R, step=jnp.zeros((), jnp.int32))
        def body(_, s):
            s2 = step(X, s, cfg.eps)
            return RescalState(
                A=jax.lax.with_sharding_constraint(
                    s2.A, NamedSharding(mesh, a_spec)),
                R=s2.R, step=s2.step)
        st = jax.lax.fori_loop(0, iters, body, st)
        return st.A, st.R

    return jax.jit(
        global_step,
        in_shardings=(NamedSharding(mesh, x_spec), NamedSharding(mesh, a_spec),
                      NamedSharding(mesh, r_spec)),
        out_shardings=(NamedSharding(mesh, a_spec), NamedSharding(mesh, r_spec)))


def dist_rescal(X: jax.Array, k: int, mesh: Mesh, *,
                key: jax.Array | None = None, iters: int = 200,
                cfg: DistRescalConfig | None = None,
                block_iters: int = 10):
    """Distributed factorization driver.  Places X / factors on the mesh
    and runs `iters` MU iterations in jitted blocks of `block_iters`."""
    from repro.core.rescal import RescalState
    cfg = cfg or DistRescalConfig()
    m, n, _ = X.shape
    if key is None:
        key = jax.random.PRNGKey(0)
    x_spec, a_spec, r_spec = factor_specs(None)
    X = jax.device_put(X, NamedSharding(mesh, x_spec))
    ka, kr = jax.random.split(key)
    A = jax.device_put(
        jax.random.uniform(ka, (n, k), X.dtype, 0.05, 1.0),
        NamedSharding(mesh, a_spec))
    R = jax.device_put(
        jax.random.uniform(kr, (m, k, k), X.dtype, 0.05, 1.0),
        NamedSharding(mesh, r_spec))
    step = make_mu_step(mesh, cfg, iters=block_iters)
    err_fn = make_dist_error(mesh)
    n_blocks, rem = divmod(iters, block_iters)
    for _ in range(n_blocks):
        A, R = step(X, A, R)
    if rem:
        A, R = make_mu_step(mesh, cfg, iters=rem)(X, A, R)
    return RescalState(A=A, R=R, step=jnp.asarray(iters)), err_fn(X, A, R)


# ---------------------------------------------------------------------------
# Named convenience factories (the historical four-factory API)
# ---------------------------------------------------------------------------

def make_dist_step(mesh: Mesh, cfg: DistRescalConfig, iters: int = 1
                   ) -> Callable:
    return make_mu_step(mesh, cfg, operand="dense", iters=iters)


def make_ensemble_step(mesh: Mesh, cfg: DistRescalConfig, iters: int = 1
                       ) -> Callable:
    return make_mu_step(mesh, cfg, operand="dense", pod_axis=POD_AXIS,
                        iters=iters)


def make_dist_step_sparse(mesh: Mesh, cfg: DistRescalConfig, *,
                          n: int, iters: int = 1) -> Callable:
    return make_mu_step(mesh, cfg, operand="bcsr", n=n, iters=iters)


def make_ensemble_step_sparse(mesh: Mesh, cfg: DistRescalConfig, *,
                              n: int, iters: int = 1) -> Callable:
    return make_mu_step(mesh, cfg, operand="bcsr", pod_axis=POD_AXIS,
                        n=n, iters=iters)
