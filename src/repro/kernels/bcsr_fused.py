"""Fused BCSR bilinear Pallas kernel — single-X-pass sparse MU (ISSUE 5).

Every sparse MU iteration needs BOTH X-sided products of the block-sparse
adjacency tensor (core/sparse.py layout, paper §4.2):

    XA_t  = X_t   @ B1        (B1 = A^(j), shared over the m slices)
    XTB_t = X_t^T @ B2        (B2 = A^(i), shared — the (X^T A) R == X^T (A R)
                               restructure keeps the per-slice R out of the
                               X-sided product, exactly like the dense
                               engine's fused path)

The segment-sum oracle (`core.sparse.spmm` / `spmm_t`) makes two sweeps
over the stored blocks and materializes an (m, nnzb, bs, k) product
intermediate in HBM before each reduction.  X's stored blocks are by far
the largest operand, so at sparse-RESCAL shapes the memory-roofline term
is ~2 * bytes(stored blocks) + 2 * the intermediate; this kernel tiles
each stored block through VMEM **once**, computes both tile products on
the MXU, and accumulates them straight into two VMEM-resident output
panels — no HBM intermediate at all.

Layout: the factor operands and the output panels are kept k-major,
(nb, k, bs), so the lane axis is the 128-wide block side and only k is
padded (to the 8-row sublane tile).  The (nb, bs, k) layout would pad k
to 128 lanes: at k = 10 that is 12.8x the VMEM, which the chip's compiler
refuses at real widths.  The wrapper transposes in and out.

Grid: (m, nnzb).  Per step (t, z):
    data : (bs, bs)       stored block z of slice t
    b1   : (k, bs)        block `cols[z]` of B1^T   (gathered via prefetch)
    b2   : (k, bs)        block `rows[z]` of B2^T   (gathered via prefetch)
    xa   : (nb, k, bs)    full output panel of slice t; block `rows[z]`
                          accumulates (data @ b1)^T = b1 @ data^T
    xtb  : (nb, k, bs)    full output panel of slice t; block `cols[z]`
                          accumulates (data^T @ b2)^T = b2 @ data

Both output windows are constant per t (revisits consecutive — the pallas
pipelining requirement) and are zeroed at z == 0, which is what makes the
empty-block-row guarantee *kernel-side*: rows that own no stored block
come out exact zero, with no "every block-row stores >= 1 block"
precondition.  io.partition's front-padded ShardedBCSR shards (all-zero
padding blocks at coordinates (0, 0)) and the masked cross-k step's
zero-column fixed point therefore stay sound on this path.

Products run at fp32 contract precision (``Precision.HIGHEST``), so the
kernel agrees with an fp32 reference to f32 accumulation error.

VMEM: each resident panel costs nb * roundup(k, 8) * bs * itemsize per
buffer; ops.py falls back to the jnp oracle when the panels exceed the
panel budget (panelizing the output like fused_bilinear's xtb window is a
ROADMAP follow-on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import BCSR

HIGHEST = jax.lax.Precision.HIGHEST
# (k, bs) x (bs, bs) contractions: A @ B^T and A @ B
NT = (((1,), (1,)), ((), ()))
NN = (((1,), (0,)), ((), ()))


def _kernel(rows_ref, cols_ref, data_ref, b1_ref, b2_ref, xa_ref, xtb_ref):
    z = pl.program_id(1)

    # new slice t: zero both resident panels BEFORE the first accumulate,
    # so block-rows/cols with no stored block yield exact-zero output rows
    @pl.when(z == 0)
    def _():
        xa_ref[0] = jnp.zeros_like(xa_ref[0])
        xtb_ref[0] = jnp.zeros_like(xtb_ref[0])

    blk = data_ref[0, 0]                               # (bs, bs), read ONCE
    part_a = jax.lax.dot_general(b1_ref[0], blk, NT, precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    part_t = jax.lax.dot_general(b2_ref[0], blk, NN, precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    xa_ref[0, rows_ref[z]] += part_a.astype(xa_ref.dtype)
    xtb_ref[0, cols_ref[z]] += part_t.astype(xtb_ref.dtype)


def to_kmajor(B: jax.Array, nb: int, bs: int) -> jax.Array:
    """(n, k) factor -> (nb, k, bs) blocks, zero-padding n to nb * bs."""
    n, k = B.shape
    if nb * bs != n:
        B = jnp.pad(B, ((0, nb * bs - n), (0, 0)))
    return B.reshape(nb, bs, k).transpose(0, 2, 1)


def from_kmajor(out: jax.Array, n: int) -> jax.Array:
    """(m, nb, k, bs) panels -> (m, n, k), cropping the padded tail."""
    m, nb, k, bs = out.shape
    return out.transpose(0, 1, 3, 2).reshape(m, nb * bs, k)[:, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bcsr_xa_xta(sp: BCSR, B1: jax.Array, B2: jax.Array, *,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """sp: BCSR (m, nnzb, bs, bs), row-major-sorted blocks; B1, B2: (n, k)
    -> (X @ B1 (m, n, k), X^T @ B2 (m, n, k)) in ONE pass over the blocks.

    Edge cases live kernel-side (or in this wrapper, which is the kernel's
    public face): an empty pattern short-circuits to zeros (a 0-sized grid
    axis is invalid), block-rows/cols without stored blocks come out exact
    zero (the panels are zeroed before accumulation), and a logical n the
    block size does not divide is handled by zero-padding the operands'
    entity axes and cropping the outputs (tail blocks are zero-masked by
    construction, core/sparse.py)."""
    m, nnzb, bs, _ = sp.data.shape
    nb = sp.nblocks
    k = B1.shape[1]
    if nnzb == 0:
        z = jnp.zeros((m, sp.n, k), B1.dtype)
        return z, z

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m, nnzb),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda t, z, rows, cols: (t, z, 0, 0)),
            pl.BlockSpec((1, k, bs), lambda t, z, rows, cols: (cols[z], 0, 0)),
            pl.BlockSpec((1, k, bs), lambda t, z, rows, cols: (rows[z], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nb, k, bs), lambda t, z, rows, cols: (t, 0, 0, 0)),
            pl.BlockSpec((1, nb, k, bs), lambda t, z, rows, cols: (t, 0, 0, 0)),
        ],
    )
    xa, xtb = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, nb, k, bs), B1.dtype),
            jax.ShapeDtypeStruct((m, nb, k, bs), B2.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bcsr_xa_xta",
    )(sp.block_rows, sp.block_cols, sp.data, to_kmajor(B1, nb, bs),
      to_kmajor(B2, nb, bs))
    return from_kmajor(xa, sp.n), from_kmajor(xtb, sp.n)
