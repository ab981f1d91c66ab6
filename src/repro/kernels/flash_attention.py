"""Flash (online-softmax) attention Pallas kernel — LM serving hot spot.

Blockwise attention with running max / normalizer so the (sq x skv) score
matrix never materializes in HBM; required for the 32k-prefill shapes and
the hybrid arch's global-attention layers.  GQA is handled by mapping each
query head to its KV group in the index maps (no KV head replication in
HBM).  Causal masking supports a query offset so the same kernel serves
both prefill (offset 0) and chunked/continuation prefill.

Grid (bh, iq, jk) = (batch * q_heads, sq / bq, skv / bk); scratch keeps the
running (m, l, acc) statistics in VMEM across the jk sweep; the output
window (bh, iq) is written once on the final jk step.

The pure-JAX chunked-attention in models/attention.py is the oracle and the
CPU/dry-run execution path (same math, XLA-scheduled).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, causal: bool, q_offset: int, sm_scale: float):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    njk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                       # (bq, d)
    k = k_ref[0]                                       # (bk, d)
    v = v_ref[0]                                       # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale

    if causal:
        bq, bk = s.shape
        q_ids = q_offset + iq * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_ids = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_cur = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(jk == njk - 1)
    def _():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "q_offset", "bq", "bk", "interpret", "sm_scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset: int = 0,
                    sm_scale: float | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: (b, hq, sq, d); k, v: (b, hkv, skv, d); hq % hkv == 0.
    Returns (b, hq, sq, d)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_map(bh, iq, jk):
        return ((bh // hq) * hkv + (bh % hq) // group, jk, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, q_offset=q_offset,
                          sm_scale=sm_scale),
        grid=(b * hq, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
