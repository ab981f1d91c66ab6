"""Block-sparse (BCSR) relational tensors — the TPU adaptation of the
paper's CSR sparse path (DESIGN.md §2).

GPU CSR SpMM relies on fine-grained gather/scatter; TPUs want dense,
MXU-aligned tiles.  We therefore store the sparse adjacency tensor as
128x128 (configurable) dense blocks with a shared coordinate list across
the m relation slices:

  data        : (m, nnzb, bs, bs)   stored blocks (dense)
  block_rows  : (nnzb,) int32       block-row of each stored block
  block_cols  : (nnzb,) int32       block-col of each stored block

The element density delta maps to a block density delta_b >= delta; for the
paper's power-law-ish relational data most blocks stay empty and SpMM work
scales with nnzb, recovering the paper's O(m * delta * n^2 * k / p) compute
bound.  All products below are segment-sum matmuls — exactly the pattern
the Pallas kernel `kernels/bcsr_spmm.py` implements with explicit VMEM
tiling; these jnp versions are its oracle and the CPU execution path.

Edge cases (the ingest layer, repro.io, feeds arbitrary real data here):
``n`` is the *logical* entity count and need not divide the block size —
the tail block is zero-padded on construction and cropped on the way out
(`spmm`/`to_dense` return logical shapes); an empty pattern (nnzb == 0)
is a valid tensor whose products are zero.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.analysis.sanitizer import sanitize_state
from repro.obs.metrics import record_metrics, update_ratio
from .rescal import EPS_DEFAULT


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BCSR:
    data: jax.Array         # (m, nnzb, bs, bs)
    block_rows: jax.Array   # (nnzb,)
    block_cols: jax.Array   # (nnzb,)
    n: int = dataclasses.field(metadata=dict(static=True))  # global entities

    def _replace(self, **kw) -> "BCSR":
        return dataclasses.replace(self, **kw)

    @property
    def m(self) -> int:
        return self.data.shape[0]

    @property
    def bs(self) -> int:
        return self.data.shape[-1]

    @property
    def nnzb(self) -> int:
        return self.data.shape[1]

    @property
    def nblocks(self) -> int:
        return cdiv(self.n, self.bs)

    @property
    def n_pad(self) -> int:
        """Padded entity count (nblocks * bs >= n; == n when bs | n)."""
        return self.nblocks * self.bs


def _pad_rows(B: jax.Array, n: int, n_pad: int) -> jax.Array:
    """Zero-pad the leading (entity) axis of B from n to n_pad."""
    if n_pad == n:
        return B
    pad = [(0, n_pad - n)] + [(0, 0)] * (B.ndim - 1)
    return jnp.pad(B, pad)


def tail_mask(n: int, bs: int, nb: int, dtype=jnp.float32) -> jax.Array:
    """(nb * bs,) mask: 1 for logical entities, 0 for the padded tail."""
    return (jnp.arange(nb * bs) < n).astype(dtype)


def from_dense(X: jax.Array, bs: int = 128, threshold: float = 0.0) -> BCSR:
    """Blockify a dense (m, n, n) tensor, keeping blocks where any slice has
    |x| > threshold.  Pattern is shared across slices (superset).  `n` need
    not divide `bs`: the tail block is zero-padded (and cropped again by
    `to_dense`/`spmm`)."""
    m, n, _ = X.shape
    nb = cdiv(n, bs)
    if nb * bs != n:
        X = jnp.pad(X, ((0, 0), (0, nb * bs - n), (0, nb * bs - n)))
    Xb = X.reshape(m, nb, bs, nb, bs).transpose(1, 3, 0, 2, 4)  # (nb,nb,m,bs,bs)
    keep = jnp.abs(Xb).max(axis=(2, 3, 4)) > threshold          # (nb, nb)
    rows, cols = jnp.nonzero(keep)
    data = Xb[rows, cols].transpose(1, 0, 2, 3)                 # (m,nnzb,bs,bs)
    return BCSR(data=data, block_rows=rows.astype(jnp.int32),
                block_cols=cols.astype(jnp.int32), n=n)


def to_dense(sp: BCSR) -> jax.Array:
    nb, bs, m = sp.nblocks, sp.bs, sp.m
    out = jnp.zeros((m, nb, nb, bs, bs), sp.data.dtype)
    out = out.at[:, sp.block_rows, sp.block_cols].set(sp.data)
    out = out.transpose(0, 1, 3, 2, 4).reshape(m, nb * bs, nb * bs)
    return out[:, :sp.n, :sp.n]


def random_bcsr(key: jax.Array, m: int, n: int, bs: int = 128,
                block_density: float = 0.05, dtype=jnp.float32) -> BCSR:
    """Random non-negative BCSR tensor with ~block_density stored blocks
    (diagonal always stored so every entity has support).  Entries in the
    padded tail (when bs does not divide n) are zeroed so round-trips
    through `to_dense`/`from_dense` are exact."""
    nb = cdiv(n, bs)
    kp, kv = jax.random.split(key)
    keep = jax.random.uniform(kp, (nb, nb)) < block_density
    keep = keep | jnp.eye(nb, dtype=bool)
    rows, cols = jnp.nonzero(keep)
    nnzb = rows.shape[0]
    data = jax.random.uniform(kv, (m, nnzb, bs, bs), dtype, 0.0, 1.0)
    if nb * bs != n:
        mask = tail_mask(n, bs, nb, dtype).reshape(nb, bs)
        data = data * mask[rows][None, :, :, None] * mask[cols][None, :, None, :]
    return BCSR(data=data, block_rows=rows.astype(jnp.int32),
                block_cols=cols.astype(jnp.int32), n=n)


def perturb_bcsr(key: jax.Array, sp: BCSR, delta: float = 0.02) -> BCSR:
    """Alg. 4 for sparse data: only stored blocks are perturbed, preserving
    the sparsity pattern (paper §4.2)."""
    noise = jax.random.uniform(key, sp.data.shape, sp.data.dtype,
                               1.0 - delta, 1.0 + delta)
    return sp._replace(data=sp.data * noise)


# ---------------------------------------------------------------------------
# SpMM products (oracles for kernels/bcsr_spmm.py)
# ---------------------------------------------------------------------------

def spmm(sp: BCSR, B: jax.Array) -> jax.Array:
    """X_t @ B for all t.  B: (n, k) -> (m, n, k)."""
    nb, bs = sp.nblocks, sp.bs
    k = B.shape[1]
    Bb = _pad_rows(B, sp.n, nb * bs).reshape(nb, bs, k)[sp.block_cols]
    prod = jnp.einsum("mzab,zbk->mzak", sp.data, Bb)     # (m, nnzb, bs, k)
    out = jax.ops.segment_sum(prod.swapaxes(0, 1), sp.block_rows,
                              num_segments=nb)           # (nb, m, bs, k)
    return out.transpose(1, 0, 2, 3).reshape(sp.m, nb * bs, k)[:, :sp.n]


def spmm_t(sp: BCSR, B: jax.Array) -> jax.Array:
    """X_t^T @ B for all t (block transpose = swap row/col + transpose tiles).
    B may be (n, k) or (m, n, k) (per-slice operand, used for X^T(A R_t))."""
    nb, bs = sp.nblocks, sp.bs
    n_pad = nb * bs
    if B.ndim == 2:
        Bb = _pad_rows(B, sp.n, n_pad).reshape(nb, bs, -1)[sp.block_rows]
        prod = jnp.einsum("mzab,zak->mzbk", sp.data, Bb)  # (m, nnzb, bs, k)
    else:
        k = B.shape[-1]
        Bp = _pad_rows(B.swapaxes(0, 1), sp.n, n_pad).swapaxes(0, 1)
        Bb = Bp.reshape(sp.m, nb, bs, k)[:, sp.block_rows]  # (m, nnzb, bs, k)
        prod = jnp.einsum("mzab,mzak->mzbk", sp.data, Bb)
    out = jax.ops.segment_sum(prod.swapaxes(0, 1), sp.block_cols,
                              num_segments=nb)
    return out.transpose(1, 0, 2, 3).reshape(sp.m, n_pad, -1)[:, :sp.n]


def sqnorm(sp: BCSR) -> jax.Array:
    return jnp.vdot(sp.data, sp.data)


# ---------------------------------------------------------------------------
# Sparse MU step (local; mirrors rescal.mu_step_batched)
# ---------------------------------------------------------------------------

def _resolve_kernel_opts(policy, use_fused: bool, impl: str):
    """Merge a ``kernels.KernelPolicy`` with the deprecated
    ``use_fused=``/``impl=`` aliases (kept for one release).  Duck-typed
    (reads ``.use_fused``/``.impl``) so this module never imports
    repro.kernels at module scope — ops.py imports us."""
    if policy is None:
        return use_fused, impl
    if use_fused or impl != "auto":
        raise TypeError("pass either policy= or the deprecated "
                        "use_fused=/impl= aliases, not both")
    return policy.use_fused, policy.impl


def sparse_products(sp: BCSR, B1: jax.Array, B2: jax.Array, *,
                    use_fused: bool = False, impl: str = "auto",
                    policy=None):
    """Both X-sided products (X @ B1, X^T @ B2) for shared (n, k) operands
    — THE hot pair of every sparse MU iteration.  ``policy`` (a
    ``kernels.KernelPolicy``) routes through ``kernels.ops.bcsr_xa_xta``
    (ONE pass over the stored blocks, no (m, nnzb, bs, k) HBM
    intermediate); ``use_fused``/``impl`` are its deprecated aliases.
    The default is the two-pass segment-sum oracle."""
    use_fused, impl = _resolve_kernel_opts(policy, use_fused, impl)
    with jax.named_scope("products"):
        if use_fused:
            from repro.kernels import ops             # lazy: no cycle
            return ops.bcsr_xa_xta(sp, B1, B2, impl=impl)
        return spmm(sp, B1), spmm_t(sp, B2)


@jax.named_scope("mu")
def sparse_mu_step(sp: BCSR, A: jax.Array, R: jax.Array,
                   eps: float = EPS_DEFAULT, *, use_fused: bool = False,
                   impl: str = "auto", policy=None, sanitize: bool = False,
                   trace_metrics: bool = False):
    """One batched MU iteration on a BCSR tensor.  Identical math to the
    dense step; only the X products change — and with the fused policy they
    come from ONE pass over the stored blocks (kernels/bcsr_fused.py)
    instead of the spmm + spmm_t double sweep."""
    use_fused, impl = _resolve_kernel_opts(policy, use_fused, impl)
    A_in = A
    G = A.T @ A
    XA, XTA = sparse_products(sp, A, A, use_fused=use_fused, impl=impl)
    ATXA = jnp.einsum("ia,mib->mab", A, XA)
    R = R * ATXA / (jnp.einsum("ab,mbc,cd->mad", G, R, G) + eps)
    num = (jnp.einsum("mia,msa->is", XA, R)
           + jnp.einsum("mia,mas->is", XTA, R))
    S = (jnp.einsum("mab,bc,mdc->ad", R, G, R)
         + jnp.einsum("mba,bc,mcd->ad", R, G, R))
    A = A * num / (A @ S + eps)
    A, R = sanitize_state(A, R, where="core.sparse.sparse_mu_step",
                          enabled=sanitize)
    if trace_metrics:  # static flag: the False build stages nothing
        record_metrics("core.sparse.sparse_mu_step",
                       rel_error=sparse_rel_error(sp, A, R,
                                                  use_fused=use_fused,
                                                  impl=impl),
                       a_norm=jnp.linalg.norm(A), r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(A_in, A))
    return A, R


@jax.named_scope("mu")
def masked_sparse_mu_step(sp: BCSR, A: jax.Array, R: jax.Array,
                          mask: jax.Array, eps: float = EPS_DEFAULT, *,
                          use_fused: bool = False, impl: str = "auto",
                          policy=None, sanitize: bool = False,
                          trace_metrics: bool = False):
    """One MU iteration on k_max-padded factors (the BCSR twin of
    rescal.masked_mu_step): same algebra, with the padded columns of A and
    rows/cols of R pinned to exact zero after the update.  Zeros are a
    fixed point of the multiplicative updates, so active columns match the
    unpadded ``sparse_mu_step`` exactly (see the cross-k block comment in
    core/rescal.py).  The fused kernel preserves the fixed point: zero
    columns of A yield exact-zero panel columns (the panels are zeroed
    before accumulation and the tile products are plain matmuls)."""
    use_fused, impl = _resolve_kernel_opts(policy, use_fused, impl)
    A_in = A
    A, R = sparse_mu_step(sp, A, R, eps, use_fused=use_fused, impl=impl)
    A, R = A * mask, R * (mask[:, None] * mask[None, :])
    if trace_metrics:  # recorded post-mask (the unmasked inner step lies)
        record_metrics("core.sparse.masked_sparse_mu_step",
                       rel_error=sparse_rel_error(sp, A, R,
                                                  use_fused=use_fused,
                                                  impl=impl),
                       a_norm=jnp.linalg.norm(A), r_norm=jnp.linalg.norm(R),
                       mu_ratio=update_ratio(A_in * mask, A))
    return sanitize_state(A, R, mask=mask,
                          where="core.sparse.masked_sparse_mu_step",
                          enabled=sanitize)


def sparse_rel_error(sp: BCSR, A: jax.Array, R: jax.Array, *,
                     use_fused: bool = False,
                     impl: str = "auto", policy=None) -> jax.Array:
    """Relative error on a BCSR tensor.  Needs only the single X @ A
    product, so the fused path routes it through the ``bcsr_spmm`` kernel
    dispatch (one block sweep either way; the kernel removes the HBM
    product intermediate)."""
    use_fused, impl = _resolve_kernel_opts(policy, use_fused, impl)
    G = A.T @ A
    if use_fused:
        from repro.kernels import ops                 # lazy: no cycle
        XA = ops.bcsr_spmm(sp, A, impl=impl)
    else:
        XA = spmm(sp, A)
    ATXA = jnp.einsum("ia,mib->mab", A, XA)
    x2 = sqnorm(sp)
    cross = jnp.vdot(ATXA, R)
    fit2 = jnp.einsum("ab,mac,cd,mbd->", G, R, G, R)
    err2 = jnp.maximum(x2 - 2.0 * cross + fit2, 0.0)
    return jnp.sqrt(err2) / jnp.sqrt(x2)


# ---------------------------------------------------------------------------
# R regression with A fixed (the sparse twin of core/regression.py, used by
# the selection sweep's per-k reduction on BCSR operands)
# ---------------------------------------------------------------------------

def sparse_update_R(sp: BCSR, A: jax.Array, R: jax.Array, G: jax.Array,
                    eps: float = EPS_DEFAULT) -> jax.Array:
    """R_t <- R_t * (A^T X_t A) / (G R_t G + eps), X products via spmm."""
    XA = spmm(sp, A)                                      # (m, n, k)
    ATXA = jnp.einsum("ia,mib->mab", A, XA)               # (m, k, k)
    deno = jnp.einsum("ab,mbc,cd->mad", G, R, G)
    return R * ATXA / (deno + eps)


def sparse_regress_R(sp: BCSR, A: jax.Array, *, iters: int = 100,
                     eps: float = EPS_DEFAULT,
                     key: jax.Array | None = None) -> jax.Array:
    """Solve for R (m, k, k) >= 0 with A fixed — identical math (and init
    key discipline) to regression.regress_R, so a BCSR sweep's reduction
    matches the dense sweep on the densified tensor."""
    k = A.shape[1]
    if key is None:
        key = jax.random.PRNGKey(17)
    R = jax.random.uniform(key, (sp.m, k, k), dtype=sp.data.dtype,
                           minval=0.05, maxval=1.0)
    G = A.T @ A

    def body(_, R):
        return sparse_update_R(sp, A, R, G, eps)

    return jax.lax.fori_loop(0, iters, body, R)
