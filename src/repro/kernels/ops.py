"""jit'd dispatch wrappers around the Pallas kernels.

impl selection:
  "auto"      — Pallas on TPU, jnp oracle elsewhere (CPU container, dry-run)
  "pallas"    — compiled Pallas (TPU)
  "interpret" — Pallas interpret mode (CPU validation of the kernel body)
  "ref"       — pure-jnp oracle

`fused_xa_xtb` additionally panelizes the n2 axis so the kernel's xtb VMEM
window (n2_panel * roundup(k, 128) * 4B, double-buffered) stays under the
budget.

Fallback telemetry: every pallas->ref downgrade (a VMEM budget overflow or
a degenerate tiling) runs through `_note_fallback`, which bumps a module
counter (`kernel_fallbacks()`) and — when a tracer is installed — emits a
`kernel/fallback` instant carrying the budget arithmetic.  Dispatch
happens at Python trace time, so the telemetry adds nothing to the
compiled programs and the untraced build stays bit-identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparse import BCSR
from repro.obs import trace as _obs
from repro.resilience import faults as _faults

from . import ref as _ref
from .bcsr_fused import bcsr_xa_xta as _bcsr_fused_pallas
from .bcsr_spmm import bcsr_spmm as _bcsr_pallas
from .flash_attention import flash_attention as _flash_pallas
from .fused_bilinear import fused_xa_xtb as _fused_pallas
from .mu_ratio import mu_update_a as _mu_pallas
from .policy import KernelPolicy, env_panel_bytes
from .score_topk import effective_pn as _effective_pn
from .score_topk import score_topk as _score_topk_pallas
from .score_topk import score_topk_stream as _score_topk_stream

__all__ = ["KernelPolicy", "VMEM_PANEL_BYTES", "kernel_fallbacks",
           "fused_xa_xtb", "mu_update_a", "bcsr_spmm", "bcsr_xa_xta",
           "flash_attention", "score_topk"]

# xtb window budget (pre double-buffer); RESCAL_VMEM_PANEL_BYTES overrides
# so CI can force the oracle fallback on any shard size.  KernelPolicy
# (kernels/policy.py, re-exported here as the public API surface) carries
# a per-policy override; this module constant is the process default.
VMEM_PANEL_BYTES = env_panel_bytes()

_n_fallbacks = 0


def kernel_fallbacks() -> int:
    """Process-lifetime count of pallas->oracle fallbacks.
    The scheduler diffs this around each unit to attribute fallbacks."""
    return _n_fallbacks


def _note_fallback(kernel: str, requested_bytes: int, *,
                   chosen: str = "ref") -> None:
    global _n_fallbacks
    _n_fallbacks += 1
    _obs.event("kernel/fallback", kernel=kernel,
               requested_bytes=int(requested_bytes),
               budget_bytes=int(VMEM_PANEL_BYTES), chosen=chosen)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dispatch(kernel: str, impl: str, *, cpu_impl: str = "ref") -> str:
    """Resolve `impl` ("auto" -> pallas on TPU, `cpu_impl` elsewhere) and
    probe the ONE kernel/dispatch fault seam.  A fired budget-overflow
    spec forces the documented oracle fallback — `_note_fallback`
    telemetry included — regardless of the real window arithmetic; the
    chaos drill uses this to exercise the fallback path end to end.
    Dispatch runs at Python trace time, so probes are per-compile and the
    no-plan path stays out of every compiled program."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else cpu_impl
    fired = _faults.fire("kernel/dispatch", kernel=kernel, impl=impl)
    if fired == "budget-overflow":
        _note_fallback(kernel, VMEM_PANEL_BYTES + 1, chosen=cpu_impl)
        impl = cpu_impl
    return impl


_LANES, _SUBLANES = 128, 8


def _round_up(x: int, tile: int) -> int:
    return -(-x // tile) * tile


def _largest_tile(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (the kernel requires exact
    tiling of both X axes)."""
    for t in range(min(cap, n), 0, -1):
        if n % t == 0:
            return t
    return 1


def fused_xa_xtb(X, B1, B2, *, impl: str = "auto", bm: int = 256,
                 bn: int = 256):
    """One-pass (X_t @ B1, X_t^T @ B2_t).  X: (m, n1, n2)."""
    impl = _dispatch("fused_xa_xtb", impl)
    if impl == "ref":
        return _ref.ref_fused_xa_xtb(X, B1, B2)
    interpret = impl == "interpret"
    m, n1, n2 = X.shape
    k = B1.shape[1]
    # shrink the requested tiles to exact divisors of the shard sides;
    # distributed shards (n/grid) are not generally 256-multiples
    bm = _largest_tile(n1, bm)
    bn = _largest_tile(n2, bn)
    if impl == "pallas" and min(bm, bn) < 8:
        # degenerate tiling (e.g. prime shard side) loses MXU sublane
        # alignment — the jnp oracle beats a 1-wide pallas grid
        _note_fallback("fused_xa_xtb", bm * bn * X.dtype.itemsize)
        return _ref.ref_fused_xa_xtb(X, B1, B2)
    # the (n2, k) xtb window pads k to whole 128-wide lane tiles in VMEM
    panel = max(bn, (VMEM_PANEL_BYTES // (_round_up(k, _LANES) * 4))
                // bn * bn)
    if n2 <= panel:
        return _fused_pallas(X, B1, B2, bm=bm, bn=bn, interpret=interpret)
    # panelize columns: XA sums partials, XTB concatenates panels
    xa = jnp.zeros((m, n1, k), X.dtype)
    xtb_panels = []
    for c0 in range(0, n2, panel):
        Xp = jax.lax.slice_in_dim(X, c0, c0 + panel, axis=2)
        B1p = jax.lax.slice_in_dim(B1, c0, c0 + panel, axis=0)
        xa_p, xtb_p = _fused_pallas(Xp, B1p, B2, bm=bm, bn=bn,
                                    interpret=interpret)
        xa = xa + xa_p
        xtb_panels.append(xtb_p)
    return xa, jnp.concatenate(xtb_panels, axis=1)


def mu_update_a(A, Num, S, eps: float = 1e-16, *, impl: str = "auto",
                bm: int = 512):
    impl = _dispatch("mu_update_a", impl)
    if impl == "ref":
        return _ref.ref_mu_update_a(A, Num, S, eps)
    return _mu_pallas(A, Num, S, eps, bm=bm, interpret=impl == "interpret")


def _panel_bytes(sp: BCSR, k: int, dtype, n_panels: int) -> int:
    """VMEM-resident bytes of the BCSR kernels' k-major (nb, k, bs) output
    panel(s), one buffer each: bs fills the lanes, k is padded to the
    8-row sublane tile."""
    return (n_panels * sp.nblocks * _round_up(k, _SUBLANES) * sp.bs
            * jnp.dtype(dtype).itemsize)


def _panel_overflow(sp: BCSR, k: int, dtype, n_panels: int) -> bool:
    """True when the BCSR kernels' VMEM-resident (nb, k, bs) output
    panel(s) exceed the panel budget (panelized outputs are a ROADMAP
    follow-on; until then the jnp oracle takes over)."""
    return _panel_bytes(sp, k, dtype, n_panels) > VMEM_PANEL_BYTES


def bcsr_spmm(sp: BCSR, B, *, impl: str = "auto"):
    impl = _dispatch("bcsr_spmm", impl)
    if impl == "pallas" and _panel_overflow(sp, B.shape[1], B.dtype, 1):
        _note_fallback("bcsr_spmm", _panel_bytes(sp, B.shape[1], B.dtype, 1))
        impl = "ref"
    if impl == "ref":
        return _ref.ref_bcsr_spmm(sp, B)
    return _bcsr_pallas(sp, B, interpret=impl == "interpret")


def bcsr_xa_xta(sp: BCSR, B1, B2, *, impl: str = "auto"):
    """One-pass (X @ B1, X^T @ B2) on a BCSR tensor, B1/B2 shared (n, k)
    — the sparse twin of `fused_xa_xtb` (kernels/bcsr_fused.py)."""
    impl = _dispatch("bcsr_xa_xta", impl)
    if impl == "pallas" and _panel_overflow(sp, B1.shape[1], B1.dtype, 2):
        _note_fallback("bcsr_xa_xta",
                       _panel_bytes(sp, B1.shape[1], B1.dtype, 2))
        impl = "ref"
    if impl == "ref":
        return _ref.ref_bcsr_xa_xta(sp, B1, B2)
    return _bcsr_fused_pallas(sp, B1, B2, interpret=impl == "interpret")


def _topk_window_bytes(b: int, k: int, topk: int, pn: int) -> int:
    """VMEM-resident window of the score_topk kernel per grid step: the
    (pn, k) A panel, the (b, pn) panel scores, and the two f32/i32
    (b, topk + pn) merge candidate planes."""
    return 4 * (pn * k + b * pn + 2 * b * (topk + pn))


def score_topk(V, A, *, topk: int, impl: str = "auto",
               pn: int | None = None):
    """Batched top-k of V @ A^T without materializing (b, n).

    impl: auto      — pallas on TPU, panelized jnp stream elsewhere
          pallas    — compiled kernel (budget-gated; falls back to stream)
          interpret — kernel body on the CPU interpreter
          stream    — panelized jnp path (lax.scan, no (b, n) buffer)
          ref       — materializing oracle (ref.ref_score_topk)
    """
    from .score_topk import DEFAULT_PN
    pn = DEFAULT_PN if pn is None else pn
    impl = _dispatch("score_topk", impl, cpu_impl="stream")
    if impl == "ref":
        return _ref.ref_score_topk(V, A, topk)
    if impl == "stream":
        return _score_topk_stream(V, A, topk=topk, pn=pn)
    b, k = V.shape
    pn_eff = _effective_pn(A.shape[0], pn)
    window = _topk_window_bytes(b, k, topk, pn_eff)
    if impl == "pallas" and window > VMEM_PANEL_BYTES:
        _note_fallback("score_topk", window, chosen="stream")
        return _score_topk_stream(V, A, topk=topk, pn=pn)
    return _score_topk_pallas(V, A, topk=topk, pn=pn,
                              interpret=impl == "interpret")


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    sm_scale: float | None = None, impl: str = "auto",
                    bq: int = 256, bk: int = 256):
    impl = _dispatch("flash_attention", impl)
    # VMEM-resident window per q-tile: the (bq, d) accumulator plus the
    # streamed (bk, d) k/v tiles — gate against the shared panel budget
    # like the BCSR dispatchers (oversized heads fall back to the oracle)
    d = q.shape[-1]
    itemsize = jnp.dtype(q.dtype).itemsize
    window = (bq + 2 * bk) * d * itemsize
    if impl == "pallas" and window > VMEM_PANEL_BYTES:
        _note_fallback("flash_attention", window)
        impl = "ref"
    if impl == "ref":
        return _ref.ref_attention(q, k, v, causal=causal, q_offset=q_offset,
                                  sm_scale=sm_scale)
    return _flash_pallas(q, k, v, causal=causal, q_offset=q_offset,
                         sm_scale=sm_scale, bq=bq, bk=bk,
                         interpret=impl == "interpret")
