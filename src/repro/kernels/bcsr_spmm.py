"""Block-sparse SpMM Pallas kernel — the TPU adaptation of the paper's
CSR sparse path (DESIGN.md §2, "Sparse = block-sparse").

Computes  out_t = X_t @ B  where X is a BCSR tensor (core/sparse.py):
MXU-aligned (bs x bs) stored blocks with row/col coordinates sorted
row-major.  The coordinate lists ride in scalar-prefetch SMEM so the block
index maps can chase them (the canonical Pallas sparse pattern); compute
scales with the number of *stored* blocks, recovering the paper's
O(m * delta * n^2 * k) sparse bound on hardware that hates gather/scatter.

Grid: (m, nnzb).  Per step (t, z):
    data : (bs, bs)     stored block z of slice t
    b    : (k, bs)      block `cols[z]` of B^T   (gathered via prefetch)
    out  : (nb, k, bs)  full output panel of slice t, zeroed at z == 0;
                        block `rows[z]` accumulates b @ data^T, the
                        transpose of the (bs, k) tile product

Operands and panel are k-major (nb, k, bs) for the same lane-padding
reason as kernels/bcsr_fused.py, whose layout helpers this kernel shares.

The panel-resident output (window constant per t, so revisits are
consecutive) is what makes the empty-block-row guarantee KERNEL-side:
block-rows that own no stored block come out exact zero, with no
"every block-row stores >= 1 block" precondition — the soundness contract
io.partition's front-padded shards rely on (ISSUE 5; the per-row
(bs, k)-window variant this replaces left untouched rows undefined).
VMEM: the panel costs nb * roundup(k, 8) * bs * itemsize per buffer;
ops.py falls back to the jnp oracle past the panel budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import BCSR

from .bcsr_fused import HIGHEST, NT, from_kmajor, to_kmajor


def _kernel(rows_ref, cols_ref, data_ref, b_ref, out_ref):
    z = pl.program_id(1)

    # new slice t: zero the resident panel BEFORE the first accumulate, so
    # block-rows with no stored block yield exact-zero output rows
    @pl.when(z == 0)
    def _():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    part = jax.lax.dot_general(b_ref[0], data_ref[0, 0], NT,
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)
    out_ref[0, rows_ref[z]] += part.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bcsr_spmm(sp: BCSR, B: jax.Array, *, interpret: bool = False
              ) -> jax.Array:
    """sp: BCSR (m, nnzb, bs, bs) with row-major-sorted blocks; B: (n, k)
    -> (m, n, k).

    Ingest edge cases (ISSUE 3): an empty pattern short-circuits to zeros
    (a 0-sized grid axis is invalid), and a logical n that the block size
    does not divide is handled by zero-padding B's entity axis to the
    blocked extent and cropping the output back — the stored tail blocks
    are already zero-masked by construction (core/sparse.py).
    """
    m, nnzb, bs, _ = sp.data.shape
    nb = sp.nblocks
    k = B.shape[1]
    if nnzb == 0:
        return jnp.zeros((m, sp.n, k), B.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, nnzb),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda t, z, rows, cols: (t, z, 0, 0)),
            pl.BlockSpec((1, k, bs), lambda t, z, rows, cols: (cols[z], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, nb, k, bs), lambda t, z, rows, cols: (t, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, nb, k, bs), B.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bcsr_spmm",
    )(sp.block_rows, sp.block_cols, sp.data, to_kmajor(B, nb, bs))
    return from_kmajor(out, sp.n)
