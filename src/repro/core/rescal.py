"""Non-negative RESCAL multiplicative updates (paper Eq. 2 / Alg. 3 local math).

The model: X_t ~= A @ R_t @ A.T for t = 1..m, with A in R+^{n x k} and
R in R+^{m x k x k}. We store the relation axis *leading* (X: (m, n, n),
R: (m, k, k)) so the per-slice algebra batches cleanly with einsum/vmap.

Two update schedules are provided, both mathematically identical to Eq. 2:

  * ``batched``  — every relation slice in one einsum.  O(1) collectives per
    MU iteration when distributed (our beyond-paper schedule).
  * ``sliced``   — an explicit ``lax.fori_loop`` over the m slices, mirroring
    the paper's per-slice loop (Alg. 3 lines 4-21).  O(m) collectives when
    distributed.  Kept as the paper-faithful baseline.

Everything here is *local* math: no collectives.  ``rescal_dist.py`` wraps
these pieces in shard_map with the paper's 2D-grid communication schedule.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.sanitizer import sanitize_state
from repro.dist.compat import donating_jit
from repro.obs.metrics import record_metrics, update_ratio

EPS_DEFAULT = 1e-16


class RescalState(NamedTuple):
    """Factor state for one RESCAL factorization."""

    A: jax.Array  # (n, k)  non-negative
    R: jax.Array  # (m, k, k) non-negative
    step: jax.Array  # scalar int32


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_factors(key: jax.Array, n: int, m: int, k: int,
                 dtype=jnp.float32) -> RescalState:
    """Random non-negative init (paper's default; NNDSVD lives in nndsvd.py)."""
    ka, kr = jax.random.split(key)
    A = jax.random.uniform(ka, (n, k), dtype=dtype, minval=0.05, maxval=1.0)
    R = jax.random.uniform(kr, (m, k, k), dtype=dtype, minval=0.05, maxval=1.0)
    return RescalState(A=A, R=R, step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Core algebra (shared by both schedules, and by the distributed version)
# ---------------------------------------------------------------------------

def gram(A: jax.Array) -> jax.Array:
    """G = A.T @ A, the (k, k) Gram matrix.  Computed once per iteration and
    reused by the R update and both A-update denominator chains (the paper
    recomputes pieces per slice; this is beyond-paper optimization #3)."""
    return A.T @ A


def update_R(X: jax.Array, A: jax.Array, R: jax.Array, G: jax.Array,
             eps: float = EPS_DEFAULT) -> jax.Array:
    """R_t <- R_t * (A^T X_t A) / (G R_t G + eps), all t at once."""
    with jax.named_scope("products"):
        XA = jnp.einsum("mij,jk->mik", X, A)      # (m, n, k)
    ATXA = jnp.einsum("ia,mib->mab", A, XA)        # (m, k, k)
    deno = jnp.einsum("ab,mbc,cd->mad", G, R, G)   # (m, k, k)
    return R * ATXA / (deno + eps)


def update_A(X: jax.Array, A: jax.Array, R: jax.Array, G: jax.Array,
             eps: float = EPS_DEFAULT) -> jax.Array:
    """A <- A * NumA / (DenoA + eps) with

      NumA  = sum_t X_t A R_t^T + X_t^T A R_t
      DenoA = A @ sum_t (R_t G R_t^T + R_t^T G R_t)
    """
    with jax.named_scope("products"):
        XA = jnp.einsum("mij,jk->mik", X, A)       # (m, n, k)
        XTA = jnp.einsum("mji,jk->mik", X, A)      # (m, n, k)
    num = (jnp.einsum("mia,msa->is", XA, R)
           + jnp.einsum("mia,mas->is", XTA, R))    # (n, k)
    S = (jnp.einsum("mab,bc,mdc->ad", R, G, R)
         + jnp.einsum("mba,bc,mcd->ad", R, G, R))  # (k, k)
    return A * num / (A @ S + eps)


@jax.named_scope("mu")
def mu_step_batched(X: jax.Array, state: RescalState,
                    eps: float = EPS_DEFAULT,
                    sanitize: bool = False,
                    trace_metrics: bool = False) -> RescalState:
    """One MU iteration, all m slices tensorized (beyond-paper schedule)."""
    A, R = state.A, state.R
    G = gram(A)
    R = update_R(X, A, R, G, eps)
    A = update_A(X, A, R, G, eps)
    A, R = sanitize_state(A, R, where="core.rescal.mu_step_batched",
                          enabled=sanitize)
    if trace_metrics:  # static flag: the False build stages nothing
        record_metrics("core.rescal.mu_step_batched", step=state.step,
                       rel_error=rel_error(X, A, R),
                       a_norm=jnp.linalg.norm(A), r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(state.A, A))
    return RescalState(A=A, R=R, step=state.step + 1)


@jax.named_scope("mu")
def mu_step_sliced(X: jax.Array, state: RescalState,
                   eps: float = EPS_DEFAULT,
                   sanitize: bool = False,
                   trace_metrics: bool = False) -> RescalState:
    """One MU iteration with an explicit loop over the m relation slices,
    mirroring paper Alg. 3 lines 4-21 (R[t] updated then its contribution
    to NumA/DenoA accumulated, per slice)."""
    A, R = state.A, state.R
    n, k = A.shape
    m = X.shape[0]
    G = gram(A)

    def body(t, carry):
        R_acc, num, den = carry
        with jax.named_scope("products"):
            Xt = jax.lax.dynamic_index_in_dim(X, t, axis=0, keepdims=False)
            XA = Xt @ A                               # (n, k)
        Rt = jax.lax.dynamic_index_in_dim(R_acc, t, axis=0, keepdims=False)
        ATXA = A.T @ XA                               # (k, k)
        Rt = Rt * ATXA / (G @ Rt @ G + eps)           # paper line 9
        R_new = jax.lax.dynamic_update_index_in_dim(R_acc, Rt, t, axis=0)
        XART = XA @ Rt.T                              # line 10
        ARt = A @ Rt                                  # line 11
        with jax.named_scope("products"):
            XTAR = Xt.T @ ARt                         # line 12
        num = num + XART + XTAR                       # line 14
        den = den + (Rt @ G @ Rt.T) + (Rt.T @ G @ Rt)  # lines 15-20 (k,k form)
        return R_new, num, den

    R, num, den_kk = jax.lax.fori_loop(
        0, m, body,
        (R, jnp.zeros_like(A), jnp.zeros((k, k), X.dtype)))
    A = A * num / (A @ den_kk + eps)                  # line 22
    A, R = sanitize_state(A, R, where="core.rescal.mu_step_sliced",
                          enabled=sanitize)
    if trace_metrics:  # static flag: the False build stages nothing
        record_metrics("core.rescal.mu_step_sliced", step=state.step,
                       rel_error=rel_error(X, A, R),
                       a_norm=jnp.linalg.norm(A), r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(state.A, A))
    return RescalState(A=A, R=R, step=state.step + 1)


MU_SCHEDULES: dict[str, Callable] = {
    "batched": mu_step_batched,
    "sliced": mu_step_sliced,
}


# ---------------------------------------------------------------------------
# Masked (k_max-padded) factors — the cross-k batching primitives
# ---------------------------------------------------------------------------
#
# The model-selection sweep runs many candidate ranks k; padding every
# unit's factors to a common k_max lets the whole (k, q) grid execute as
# ONE device program (selection/ensemble.py vmaps over the flattened unit
# axis).  The invariant that makes padding sound: with A's masked columns
# and R's masked rows/cols exactly zero, every MU quantity they touch is
# exactly zero (G, ATXA, num, S all gain zero blocks) and the updates are
# multiplicative, so zeros are a fixed point — and the *active* block sees
# only additional exact-zero terms in its contractions, so padded results
# equal the unpadded reference bit-for-bit up to reduction order.  The
# explicit mask multiply after each step makes the invariant structural
# (masked entries are forced to 0.0 rather than proven to stay there).

def column_mask(k, k_max: int, dtype=jnp.float32) -> jax.Array:
    """(k_max,) mask: 1 for the first `k` (active) columns, 0 for padding.
    `k` may be a traced scalar — changing the rank mix never recompiles."""
    return (jnp.arange(k_max) < k).astype(dtype)


def mask_state(state: RescalState, mask: jax.Array) -> RescalState:
    """Force the masked columns of A (and rows+cols of R) to exact zero."""
    return RescalState(A=state.A * mask,
                       R=state.R * (mask[:, None] * mask[None, :]),
                       step=state.step)


def pad_state(state: RescalState, k_max: int) -> RescalState:
    """Zero-pad (n, k) / (m, k, k) factors to rank k_max.  The pad columns
    are exact zeros, so the padded state is already mask-invariant."""
    k = state.A.shape[1]
    if k == k_max:
        return state
    if k > k_max:
        raise ValueError(f"cannot pad rank {k} down to k_max={k_max}")
    A = jnp.pad(state.A, ((0, 0), (0, k_max - k)))
    R = jnp.pad(state.R, ((0, 0), (0, k_max - k), (0, k_max - k)))
    return RescalState(A=A, R=R, step=state.step)


def crop_state(state: RescalState, k: int) -> RescalState:
    """Drop the padding columns again: the inverse of ``pad_state``."""
    return RescalState(A=state.A[:, :k], R=state.R[:, :k, :k],
                       step=state.step)


@jax.named_scope("mu")
def masked_mu_step(X: jax.Array, state: RescalState, mask: jax.Array,
                   eps: float = EPS_DEFAULT,
                   schedule: str = "batched",
                   sanitize: bool = False,
                   trace_metrics: bool = False) -> RescalState:
    """One MU iteration on k_max-padded factors.  Same math as the plain
    schedules; the trailing mask multiply pins the padded columns to exact
    zero (multiplying active columns by 1.0 is exact, so active values are
    untouched)."""
    st = mask_state(MU_SCHEDULES[schedule](X, state, eps), mask)
    A, R = sanitize_state(st.A, st.R, mask=mask,
                          where="core.rescal.masked_mu_step",
                          enabled=sanitize)
    if trace_metrics:  # recorded post-mask (the unmasked inner step lies)
        record_metrics("core.rescal.masked_mu_step", step=st.step,
                       rel_error=rel_error(X, A, R),
                       a_norm=jnp.linalg.norm(A), r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(state.A * mask, A))
    return RescalState(A=A, R=R, step=st.step)


def masked_normalize(state: RescalState, mask: jax.Array,
                     eps: float = 1e-12) -> RescalState:
    """``normalize`` on padded factors.  Masked columns have zero norm; the
    eps clamp keeps the division finite and the mask restores exact zeros.
    Active columns normalize independently, identically to unpadded."""
    return mask_state(normalize(state, eps), mask)


# ---------------------------------------------------------------------------
# Normalization & error
# ---------------------------------------------------------------------------

def normalize(state: RescalState, eps: float = 1e-12) -> RescalState:
    """||A_col|| = 1 with inverse scaling folded into R (paper §2.2).
    Done once at the end of optimization."""
    c = jnp.linalg.norm(state.A, axis=0)
    c = jnp.maximum(c, eps)
    A = state.A / c
    R = jnp.einsum("a,mab,b->mab", c, state.R, c)
    return RescalState(A=A, R=R, step=state.step)


def rel_error(X: jax.Array, A: jax.Array, R: jax.Array) -> jax.Array:
    """Relative Frobenius error ||X - A R A^T||_F / ||X||_F.

    Uses the identity (beyond-paper efficiency — no n x n reconstruction):
      ||X - A R A^T||^2 = ||X||^2 - 2 sum_t <A^T X_t A, R_t>
                          + sum_t <G, R_t G R_t^T>
    """
    G = gram(A)
    ATXA = jnp.einsum("ia,mij,jb->mab", A, X, A)
    x2 = jnp.vdot(X, X)
    cross = jnp.vdot(ATXA, R)
    fit2 = jnp.einsum("ab,mac,cd,mbd->", G, R, G, R)
    err2 = jnp.maximum(x2 - 2.0 * cross + fit2, 0.0)
    return jnp.sqrt(err2) / jnp.sqrt(x2)


def reconstruct(A: jax.Array, R: jax.Array) -> jax.Array:
    """Dense reconstruction A R_t A^T, (m, n, n).  For tests/small data."""
    return jnp.einsum("ia,mab,jb->mij", A, R, A)


# ---------------------------------------------------------------------------
# Single-device driver
# ---------------------------------------------------------------------------

def _run_iters_impl(X, state, iters: int, schedule: str, eps: float,
                    sanitize: bool = False, trace_metrics: bool = False):
    step = MU_SCHEDULES[schedule]
    def body(_, s):
        return step(X, s, eps, sanitize, trace_metrics)
    return jax.lax.fori_loop(0, iters, body, state)


# The incoming factor state is donated (dist.compat shim: only on backends
# that implement aliasing, so CPU CI stays warning-clean): the MU block
# rewrites (n, k) + (m, k, k) in place instead of holding input AND output
# copies live.  Callers on accelerator backends must treat the passed
# state as consumed.
_run_iters = donating_jit(_run_iters_impl, donate_argnums=(1,),
                          static_argnames=("iters", "schedule", "eps",
                                           "sanitize", "trace_metrics"))


def rescal(X: jax.Array, k: int, *, key: jax.Array | None = None,
           iters: int = 200, schedule: str = "batched",
           eps: float = EPS_DEFAULT, init: RescalState | None = None,
           normalize_result: bool = True,
           sanitize: bool = False,
           trace_metrics: bool = False) -> tuple[RescalState, jax.Array]:
    """Factorize X (m, n, n) at rank k.  Returns (state, rel_error).

    NOTE: a passed ``init`` is donated to the MU program on backends that
    implement buffer aliasing (TPU/GPU) — treat it as consumed there and
    pass a copy if you need it afterwards (no-op on CPU)."""
    m, n, _ = X.shape
    if init is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        init = init_factors(key, n, m, k, dtype=X.dtype)
    state = _run_iters(X, init, iters, schedule, eps, sanitize,
                       trace_metrics)
    if normalize_result:
        state = normalize(state)
    return state, rel_error(X, state.A, state.R)
