"""Fused dual-product bilinear kernel — the RESCAL A-update hot spot.

For every relation slice t the A-update numerator (paper Alg. 3 lines
10-14) needs BOTH products of the local data block X_t:

    XA_t   = X_t   @ B1        (B1 = A^(j),    shared over t)
    XTB_t  = X_t^T @ B2_t      (B2_t = A R_t,  per slice)

A naive implementation streams X from HBM twice.  X is by far the largest
operand (n_loc^2 * m vs n_loc * k factors), so at RESCAL shapes the memory
roofline term is ~2 * bytes(X); this kernel tiles X through VMEM **once**
and emits both partial products, halving the dominant HBM term
(beyond-paper optimization #2, EXPERIMENTS.md §Perf).

Blocking (per grid step (t, i, j)):
    x    : (bm, bn)   VMEM tile of X_t
    b1   : (bn, k)    column-block of B1          (revisited over i)
    b2   : (bm, k)    row-block of B2_t           (revisited over j)
    xa   : (bm, k)    out row-panel, accumulated over j (consecutive)
    xtb  : (n2, k)    out full panel, accumulated over (i, j); its window is
                      constant per t so revisits are consecutive.

The MXU sees two (bm x bn) @ (bn x k) contractions per tile; bm = bn = 256
keeps the X tile at 256 KB and both matmul operands 128-aligned.
ops.fused_xa_xtb() panelizes n2 when n2 * k * 4B would exceed the VMEM
budget for the xtb window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST

DEFAULT_BM = 256
DEFAULT_BN = 256


def _kernel(x_ref, b1_ref, b2_ref, xa_ref, xtb_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    x = x_ref[0]                                   # (bm, bn)
    b1 = b1_ref[...]                               # (bn, k)
    b2 = b2_ref[0]                                 # (bm, k)

    # ---- XA row panel: init on first column block, then accumulate ----
    part_xa = jnp.dot(x, b1, precision=HIGHEST,
                      preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        xa_ref[0] = part_xa.astype(xa_ref.dtype)

    @pl.when(j != 0)
    def _():
        xa_ref[0] += part_xa.astype(xa_ref.dtype)

    # ---- XTB full panel: zero once per t, accumulate the (j) slice ----
    @pl.when((i == 0) & (j == 0))
    def _():
        xtb_ref[0] = jnp.zeros_like(xtb_ref[0])

    part_xtb = jnp.dot(x.T, b2, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
    bn = x.shape[1]
    rows = pl.ds(pl.multiple_of(j * bn, bn), bn)
    xtb_ref[0, rows] += part_xtb.astype(xtb_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def fused_xa_xtb(X: jax.Array, B1: jax.Array, B2: jax.Array,
                 *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 interpret: bool = False):
    """X: (m, n1, n2), B1: (n2, k), B2: (m, n1, k)
    -> (XA: (m, n1, k), XTB: (m, n2, k)), reading X once."""
    m, n1, n2 = X.shape
    k = B1.shape[1]
    bm = min(bm, n1)
    bn = min(bn, n2)
    assert n1 % bm == 0 and n2 % bn == 0, (n1, n2, bm, bn)
    grid = (m, n1 // bm, n2 // bn)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda t, i, j: (t, i, j)),
            pl.BlockSpec((bn, k), lambda t, i, j: (j, 0)),
            pl.BlockSpec((1, bm, k), lambda t, i, j: (t, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, k), lambda t, i, j: (t, i, 0)),
            pl.BlockSpec((1, n2, k), lambda t, i, j: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n1, k), X.dtype),
            jax.ShapeDtypeStruct((m, n2, k), X.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="fused_xa_xtb",
    )(X, B1, B2)
