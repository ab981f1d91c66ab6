"""Panel-streaming top-k scoring kernel — the serve-tier hot spot.

A RESCAL link-prediction query `(s, r, ?)` scores every entity at once:

    scores = (A[s] @ R[r]) @ A^T          # one (n,)-wide row per query
    answer = top_k(scores)

(`(?, r, o)` is the same with R transposed.)  The engine batches queries
into V = A[anchor] @ R_q, so scoring is a (b, k) x (k, n) product whose
(b, n) result is immediately reduced to (b, topk).  At serving n (millions
of entities) that intermediate is the whole cost: materializing it to HBM
just to throw away all but k entries per row is pure waste.

This kernel streams A in (pn, k) row panels through VMEM — the same panel
discipline as `bcsr_fused` — and maintains the running top-k **inside**
the kernel: per grid step it scores one panel on the MXU, then merges the
(b, pn) panel scores into the resident (b, topk) best-so-far via `topk`
unrolled extract-max sweeps (max -> first-occurrence one-hot -> mask).
The (b, n) score matrix never exists in any memory space.

Tie-breaking matches `jax.lax.top_k` (equal scores -> lowest index
first): candidates are ordered [running | panel], the running buffer
inductively holds ties in ascending global index, and every panel element
has a larger global index than every running element, so first-occurrence
extraction preserves the global order.

`score_topk_stream` is the pure-jnp twin with identical semantics (a
`lax.scan` over the same panels, merged with `lax.top_k`) — it also never
materializes (b, n), and serves as the CPU execution path and the
dispatcher's fallback when the kernel's VMEM window would blow the panel
budget.  The materializing oracle lives in ref.py (`ref_score_topk`).

Outputs are always (f32 scores, i32 indices), both (b, topk), sorted by
descending score.  Rows past n (tail panels) and slots past n (topk > n)
come back as (-inf, -1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PN = 2048
HIGHEST = jax.lax.Precision.HIGHEST
_LANE = 128


def effective_pn(n: int, pn: int = DEFAULT_PN) -> int:
    """Shrink the requested panel length to the lane-aligned cover of n
    (small vocabularies should not pay for a 2048-wide panel)."""
    return max(_LANE, min(pn, -(-n // _LANE) * _LANE))


def _merge_topk(cand_s, cand_i, topk: int):
    """Extract the top `topk` of the candidate columns, first-occurrence
    tie-break (== lowest candidate position).  Pure jnp, lowers inside
    the kernel (max/where/iota only — no cumsum, no sort)."""
    b, c = cand_s.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    out_s, out_i = [], []
    for _ in range(topk):
        mx = jnp.max(cand_s, axis=1)
        eq = cand_s == mx[:, None]
        first_pos = jnp.min(jnp.where(eq, pos, c), axis=1)
        first = pos == first_pos[:, None]
        out_s.append(mx[:, None])
        # exactly one True per row; all-(-inf) rows pick candidate 0,
        # which is the running buffer's own (-inf, -1) padding slot
        out_i.append(jnp.sum(jnp.where(first, cand_i, 0), axis=1)[:, None])
        cand_s = jnp.where(first, -jnp.inf, cand_s)
    return (jnp.concatenate(out_s, axis=1),
            jnp.concatenate(out_i, axis=1))


def _kernel(v_ref, a_ref, s_ref, i_ref, *, n: int, pn: int, topk: int):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _():
        s_ref[...] = jnp.full_like(s_ref[...], -jnp.inf)
        i_ref[...] = jnp.full_like(i_ref[...], -1)

    v = v_ref[...]                                     # (b, k)
    a = a_ref[...]                                     # (pn, k)
    sp = jnp.dot(v, a.T, precision=HIGHEST,
                 preferred_element_type=jnp.float32)           # (b, pn)
    b = sp.shape[0]
    gidx = p * pn + jax.lax.broadcasted_iota(jnp.int32, (b, pn), 1)
    sp = jnp.where(gidx < n, sp, -jnp.inf)             # mask the pad tail

    cand_s = jnp.concatenate([s_ref[...], sp], axis=1)
    cand_i = jnp.concatenate([i_ref[...], gidx], axis=1)
    new_s, new_i = _merge_topk(cand_s, cand_i, topk)
    s_ref[...] = new_s
    i_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("topk", "pn", "interpret"))
def score_topk(V: jax.Array, A: jax.Array, *, topk: int,
               pn: int = DEFAULT_PN, interpret: bool = False):
    """V: (b, k) query vectors, A: (n, k) entity factors
    -> (scores (b, topk) f32, indices (b, topk) i32), top-k of V @ A^T
    without materializing the (b, n) score matrix."""
    b, k = V.shape
    n = A.shape[0]
    pn = effective_pn(n, pn)
    n_panels = -(-n // pn)
    pad = n_panels * pn - n
    A_pad = jnp.pad(A, ((0, pad), (0, 0))) if pad else A

    scores, idx = pl.pallas_call(
        functools.partial(_kernel, n=n, pn=pn, topk=topk),
        grid=(n_panels,),
        in_specs=[
            pl.BlockSpec((b, k), lambda p: (0, 0)),
            pl.BlockSpec((pn, k), lambda p: (p, 0)),
        ],
        out_specs=[
            # constant index_map: the running top-k stays VMEM-resident
            # across the whole panel sweep (ops.score_topk budget-gates)
            pl.BlockSpec((b, topk), lambda p: (0, 0)),
            pl.BlockSpec((b, topk), lambda p: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, topk), jnp.float32),
            jax.ShapeDtypeStruct((b, topk), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="score_topk",
    )(V.astype(jnp.float32), A_pad.astype(jnp.float32))
    return scores, idx


@functools.partial(jax.jit, static_argnames=("topk", "pn"))
def score_topk_stream(V: jax.Array, A: jax.Array, *, topk: int,
                      pn: int = DEFAULT_PN):
    """Pure-jnp panel stream with the kernel's exact semantics: a
    `lax.scan` over (pn, k) panels of A, running (b, topk) carry merged
    with `lax.top_k` over [running | panel] candidates.  Never builds the
    (b, n) score matrix — this is the production CPU path, not an oracle."""
    b, k = V.shape
    n = A.shape[0]
    pn = effective_pn(n, pn)
    n_panels = -(-n // pn)
    pad = n_panels * pn - n
    A_pad = jnp.pad(A, ((0, pad), (0, 0))) if pad else A
    panels = A_pad.astype(jnp.float32).reshape(n_panels, pn, k)
    Vf = V.astype(jnp.float32)
    base = jnp.arange(pn, dtype=jnp.int32)[None, :]

    def body(carry, xs):
        run_s, run_i = carry
        panel, p = xs
        sp = jnp.dot(Vf, panel.T, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
        gidx = jnp.broadcast_to(p * pn + base, sp.shape)
        sp = jnp.where(gidx < n, sp, -jnp.inf)
        cand_s = jnp.concatenate([run_s, sp], axis=1)
        cand_i = jnp.concatenate([run_i, gidx], axis=1)
        top_s, pos = jax.lax.top_k(cand_s, topk)
        top_i = jnp.take_along_axis(cand_i, pos, axis=1)
        return (top_s, top_i), None

    init = (jnp.full((b, topk), -jnp.inf, jnp.float32),
            jnp.full((b, topk), -1, jnp.int32))
    (run_s, run_i), _ = jax.lax.scan(
        body, init, (panels, jnp.arange(n_panels, dtype=jnp.int32)))
    return run_s, run_i
