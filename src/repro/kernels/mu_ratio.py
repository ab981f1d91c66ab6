"""Fused multiplicative-update ratio kernel (paper Eq. 2, A-row form).

Computes   A_out = A * Num / (A @ S + eps)   row-panel by row-panel,
fusing the (n, k) x (k, k) denominator matmul with the elementwise
multiply-ratio so the (n, k) denominator never round-trips through HBM.
XLA usually fuses the elementwise part but still materializes A @ S when it
feeds a multi-consumer graph (it does in the full MU step); this kernel
pins the whole update to one HBM read of A/Num and one write of A_out.

Blocking: grid (n // bm,); each step holds an (bm, k) panel of A and Num,
the full (k, k) S (k is the RESCAL rank — small), and writes one panel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 512


def _kernel(a_ref, num_ref, s_ref, eps_ref, out_ref):
    a = a_ref[...]
    den = jnp.dot(a, s_ref[...], preferred_element_type=jnp.float32)
    out = a * num_ref[...] / (den.astype(a.dtype) + eps_ref[0])
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def mu_update_a(A: jax.Array, Num: jax.Array, S: jax.Array,
                eps: float = 1e-16, *, bm: int = DEFAULT_BM,
                interpret: bool = False) -> jax.Array:
    """A, Num: (n, k); S: (k, k) -> A * Num / (A @ S + eps)."""
    n, k = A.shape
    bm = min(bm, n)
    assert n % bm == 0, (n, bm)
    eps_arr = jnp.full((1,), eps, A.dtype)
    return pl.pallas_call(
        _kernel,
        grid=(n // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mu_update_a",
    )(A, Num, S, eps_arr)
