"""Pallas kernels vs pure-jnp oracles, interpret mode (CPU).

Every kernel sweeps shapes x dtypes against ref.py per the deliverable.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sparse as sp
from repro.kernels import (bcsr_spmm, bcsr_xa_xta, flash_attention,
                           fused_xa_xtb, mu_update_a, ref)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-5)


class TestFusedBilinear:
    @pytest.mark.parametrize("m,n1,n2,k", [(1, 128, 128, 8), (2, 256, 128, 16),
                                           (3, 128, 256, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, key, m, n1, n2, k, dtype):
        X = jax.random.uniform(key, (m, n1, n2), dtype)
        B1 = jax.random.uniform(key, (n2, k), dtype)
        B2 = jax.random.uniform(key, (m, n1, k), dtype)
        xa, xtb = fused_xa_xtb(X, B1, B2, impl="interpret", bm=128, bn=128)
        xa_r, xtb_r = ref.ref_fused_xa_xtb(X, B1, B2)
        np.testing.assert_allclose(np.asarray(xa, np.float32),
                                   np.asarray(xa_r, np.float32), **tol(dtype))
        np.testing.assert_allclose(np.asarray(xtb, np.float32),
                                   np.asarray(xtb_r, np.float32), **tol(dtype))

    def test_panelized_path(self, key):
        """ops.py splits n2 panels when the VMEM window would overflow."""
        X = jax.random.uniform(key, (1, 128, 512))
        B1 = jax.random.uniform(key, (512, 8))
        B2 = jax.random.uniform(key, (1, 128, 8))
        import repro.kernels.ops as ops
        old = ops.VMEM_PANEL_BYTES
        try:
            ops.VMEM_PANEL_BYTES = 128 * 8 * 4      # force panel split
            xa, xtb = fused_xa_xtb(X, B1, B2, impl="interpret",
                                   bm=128, bn=128)
        finally:
            ops.VMEM_PANEL_BYTES = old
        xa_r, xtb_r = ref.ref_fused_xa_xtb(X, B1, B2)
        np.testing.assert_allclose(xa, xa_r, rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(xtb, xtb_r, rtol=2e-4, atol=1e-5)

    def test_degenerate_tile_fallback_is_counted(self, key):
        """A shard side with no tile >= 8 takes the oracle, and the
        downgrade is counted like every other fallback."""
        import repro.kernels.ops as ops
        X = jax.random.uniform(key, (2, 4, 4))
        B1 = jax.random.uniform(key, (4, 3))
        B2 = jax.random.uniform(key, (2, 4, 3))
        n0 = ops.kernel_fallbacks()
        xa, xtb = fused_xa_xtb(X, B1, B2, impl="pallas")
        assert ops.kernel_fallbacks() == n0 + 1
        xa_r, xtb_r = ref.ref_fused_xa_xtb(X, B1, B2)
        np.testing.assert_array_equal(xa, xa_r)
        np.testing.assert_array_equal(xtb, xtb_r)


class TestMuRatio:
    @pytest.mark.parametrize("n,k,bm", [(256, 8, 128), (512, 16, 256),
                                        (128, 40, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, key, n, k, bm, dtype):
        A = jax.random.uniform(key, (n, k), dtype, 0.1, 1.0)
        Num = jax.random.uniform(key, (n, k), dtype, 0.1, 1.0)
        S = jax.random.uniform(key, (k, k), dtype, 0.1, 1.0)
        out = mu_update_a(A, Num, S, impl="interpret", bm=bm)
        want = ref.ref_mu_update_a(A, Num, S)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   **tol(dtype))


def _no_support_bcsr(key, m=2, bs=32, nb=4):
    """A pattern with empty block-rows AND block-cols: blocks only at
    (0, 2) and (2, 0) — block-row/col 1 and 3 own nothing.  The kernels
    must emit exact-zero output rows there (the kernel-side guarantee
    io.partition's front-padded shards rely on)."""
    data = jax.random.uniform(key, (m, 2, bs, bs))
    return sp.BCSR(data=data, block_rows=jnp.array([0, 2], jnp.int32),
                   block_cols=jnp.array([2, 0], jnp.int32), n=nb * bs)


class TestBcsrSpmm:
    @pytest.mark.parametrize("bs,density", [(64, 0.2), (128, 0.4)])
    def test_vs_ref(self, key, bs, density):
        s = sp.random_bcsr(key, m=2, n=4 * bs, bs=bs, block_density=density)
        B = jax.random.uniform(key, (s.n, 16))
        out = bcsr_spmm(s, B, impl="interpret")
        np.testing.assert_allclose(out, ref.ref_bcsr_spmm(s, B),
                                   rtol=2e-4, atol=2e-4)

    def test_empty_block_rows_exact_zero(self, key):
        """The panel-resident rewrite (ISSUE 5): block-rows without stored
        blocks must come out exact zero, not undefined."""
        s = _no_support_bcsr(key)
        B = jax.random.uniform(key, (s.n, 8))
        out = np.asarray(bcsr_spmm(s, B, impl="interpret"))
        np.testing.assert_allclose(out, sp.spmm(s, B), rtol=1e-5, atol=1e-6)
        assert (out[:, 32:64] == 0.0).all() and (out[:, 96:] == 0.0).all()


class TestBcsrFused:
    """kernels/bcsr_fused.py — the single-pass (X @ B1, X^T @ B2) contract
    vs the two-pass segment-sum oracle, at <= 1e-5 (ISSUE 5)."""

    @pytest.mark.parametrize("bs,density,k", [(32, 0.3, 8), (64, 0.2, 16),
                                              (128, 0.4, 4)])
    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_vs_oracle(self, key, bs, density, k, impl):
        s = sp.random_bcsr(key, m=3, n=4 * bs, bs=bs, block_density=density)
        B1 = jax.random.uniform(jax.random.fold_in(key, 1), (s.n, k))
        B2 = jax.random.uniform(jax.random.fold_in(key, 2), (s.n, k))
        xa, xtb = bcsr_xa_xta(s, B1, B2, impl=impl)
        np.testing.assert_allclose(xa, sp.spmm(s, B1), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(xtb, sp.spmm_t(s, B2), rtol=1e-5,
                                   atol=1e-6)

    def test_dense_reference_roundtrip(self, key):
        """from_dense -> fused products == plain dense einsums."""
        X = jnp.abs(jax.random.normal(key, (2, 128, 128)))
        X = jnp.where(X > 1.0, X, 0.0)
        s = sp.from_dense(X, bs=32)
        B1 = jax.random.uniform(jax.random.fold_in(key, 1), (128, 8))
        B2 = jax.random.uniform(jax.random.fold_in(key, 2), (128, 8))
        xa, xtb = bcsr_xa_xta(s, B1, B2, impl="interpret")
        np.testing.assert_allclose(xa, jnp.einsum("mij,jk->mik", X, B1),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(xtb, jnp.einsum("mji,jk->mik", X, B2),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_empty_pattern_is_zero(self, key, impl):
        e = sp.BCSR(data=jnp.zeros((2, 0, 32, 32)),
                    block_rows=jnp.zeros((0,), jnp.int32),
                    block_cols=jnp.zeros((0,), jnp.int32), n=100)
        B = jax.random.uniform(key, (100, 5))
        xa, xtb = bcsr_xa_xta(e, B, B, impl=impl)
        assert xa.shape == xtb.shape == (2, 100, 5)
        assert float(jnp.abs(xa).max()) == 0.0
        assert float(jnp.abs(xtb).max()) == 0.0

    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_empty_block_rows_exact_zero(self, key, impl):
        """Rows/cols without stored blocks yield exact-zero output rows —
        kernel-side, no every-row-has-support precondition."""
        s = _no_support_bcsr(key)
        B1 = jax.random.uniform(jax.random.fold_in(key, 1), (s.n, 4))
        B2 = jax.random.uniform(jax.random.fold_in(key, 2), (s.n, 4))
        xa, xtb = bcsr_xa_xta(s, B1, B2, impl=impl)
        np.testing.assert_allclose(xa, sp.spmm(s, B1), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(xtb, sp.spmm_t(s, B2), rtol=1e-5,
                                   atol=1e-6)
        xa, xtb = np.asarray(xa), np.asarray(xtb)
        for out in (xa, xtb):          # block-rows/cols 1 and 3 are empty
            assert (out[:, 32:64] == 0.0).all()
            assert (out[:, 96:] == 0.0).all()

    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_tail_blocks(self, key, impl):
        """bs does not divide n: padded tails crop to exact logical
        shapes and products match the oracle."""
        s = sp.random_bcsr(key, m=2, n=70, bs=32, block_density=0.5)
        B1 = jax.random.uniform(jax.random.fold_in(key, 1), (70, 4))
        B2 = jax.random.uniform(jax.random.fold_in(key, 2), (70, 4))
        xa, xtb = bcsr_xa_xta(s, B1, B2, impl=impl)
        assert xa.shape == xtb.shape == (2, 70, 4)
        np.testing.assert_allclose(xa, sp.spmm(s, B1), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(xtb, sp.spmm_t(s, B2), rtol=1e-5,
                                   atol=1e-6)

    def test_pallas_panel_overflow_falls_back(self, key, monkeypatch):
        """Past the VMEM panel budget the compiled-pallas dispatch takes
        the oracle path instead of blowing VMEM."""
        import repro.kernels.ops as ops
        s = sp.random_bcsr(key, m=2, n=128, bs=32, block_density=0.5)
        B = jax.random.uniform(key, (s.n, 8))
        monkeypatch.setattr(ops, "VMEM_PANEL_BYTES", 16)
        calls = []
        orig = ref.ref_bcsr_xa_xta
        monkeypatch.setattr(ops._ref, "ref_bcsr_xa_xta",
                            lambda *a: calls.append(a) or orig(*a))
        xa, _ = ops.bcsr_xa_xta(s, B, B, impl="pallas")
        assert calls, "overflow did not fall back to the ref oracle"
        np.testing.assert_allclose(xa, sp.spmm(s, B), rtol=1e-5, atol=1e-6)

    def test_fallback_emits_event_with_budget_arithmetic(self, key,
                                                         monkeypatch):
        """A budget-driven downgrade must bump the fallback counter, leave
        a kernel/fallback instant carrying requested-vs-budget bytes, and
        still match the oracle numerically (ISSUE 8)."""
        import repro.kernels.ops as ops
        from repro.obs import trace as obs
        s = sp.random_bcsr(key, m=2, n=128, bs=32, block_density=0.5)
        B = jax.random.uniform(key, (s.n, 8))
        monkeypatch.setattr(ops, "VMEM_PANEL_BYTES", 16)
        n0 = ops.kernel_fallbacks()
        with obs.tracing() as t:
            xa, xtb = ops.bcsr_xa_xta(s, B, B, impl="pallas")
            out = ops.bcsr_spmm(s, B, impl="pallas")
        assert ops.kernel_fallbacks() - n0 == 2
        evs = [e for e in t.events if e["name"] == "kernel/fallback"]
        assert {e["args"]["kernel"] for e in evs} \
            == {"bcsr_xa_xta", "bcsr_spmm"}
        fused = next(e for e in evs
                     if e["args"]["kernel"] == "bcsr_xa_xta")
        itemsize = jnp.dtype(B.dtype).itemsize
        assert fused["args"]["requested_bytes"] \
            == 2 * s.nblocks * s.bs * 8 * itemsize
        assert fused["args"]["budget_bytes"] == 16
        assert fused["args"]["chosen"] == "ref"
        np.testing.assert_allclose(xa, sp.spmm(s, B), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(xtb, sp.spmm_t(s, B), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(out, sp.spmm(s, B), rtol=1e-5, atol=1e-6)

    def test_fallback_counts_without_tracer(self, key, monkeypatch):
        """Untraced dispatch still counts (the scheduler diffs the counter)
        but emits nothing — the zero-cost-off contract."""
        import repro.kernels.ops as ops
        from repro.obs import trace as obs
        assert obs.current() is None
        s = sp.random_bcsr(key, m=2, n=128, bs=32, block_density=0.5)
        B = jax.random.uniform(key, (s.n, 8))
        monkeypatch.setattr(ops, "VMEM_PANEL_BYTES", 16)
        n0 = ops.kernel_fallbacks()
        ops.bcsr_xa_xta(s, B, B, impl="pallas")
        assert ops.kernel_fallbacks() == n0 + 1


class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (5, 1)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_causal(self, key, hq, hkv, causal):
        q = jax.random.normal(key, (2, hq, 128, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, hkv, 128, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, hkv, 128, 32))
        out = flash_attention(q, k, v, causal=causal, impl="interpret",
                              bq=64, bk=64)
        want = ref.ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)

    def test_query_offset_continuation(self, key):
        """Chunked prefill: offset queries must mask exactly like the
        full-sequence reference."""
        q = jax.random.normal(key, (1, 2, 64, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))
        out = flash_attention(q, k, v, causal=True, q_offset=64,
                              impl="interpret", bq=64, bk=64)
        want = ref.ref_attention(q, k, v, causal=True, q_offset=64)
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 1000),
           sq=st.sampled_from([64, 128]), skv=st.sampled_from([64, 128]),
           d=st.sampled_from([16, 64]))
    def test_hypothesis_shapes(self, seed, sq, skv, d):
        key = jax.random.PRNGKey(seed)
        q = jax.random.normal(key, (1, 2, sq, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, skv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, skv, d))
        out = flash_attention(q, k, v, causal=False, impl="interpret",
                              bq=64, bk=64)
        want = ref.ref_attention(q, k, v, causal=False)
        np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)
