"""repro.selection: sweep planning, batched/loop parity, criteria edges,
checkpoint/resume, retry, and the JSON report."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rescal import (column_mask, crop_state, init_factors,
                               mask_state, masked_mu_step, masked_normalize,
                               mu_step_batched, mu_step_sliced, normalize,
                               pad_state, rel_error)
from repro.core.rescalk import rescalk
from repro.selection import (CRITERIA, GridChunk, RescalkConfig,
                             SelectionReport, SweepInterrupted,
                             SweepScheduler, WorkUnit, criteria, plan_sweep,
                             run_ensemble, run_sweep_batched, unit_keys)


def small_tensor(n=24, m=2, k=3, seed=0):
    key = jax.random.PRNGKey(seed)
    A = jax.random.uniform(key, (n, k), minval=0.1, maxval=1.0)
    R = jax.random.uniform(jax.random.fold_in(key, 1), (m, k, k),
                           minval=0.1, maxval=1.0)
    return jnp.einsum("ia,mab,jb->mij", A, R, A)


SMALL_CFG = RescalkConfig(k_min=2, k_max=4, n_perturbations=4,
                          rescal_iters=80, regress_iters=30, seed=3)


class TestPlanSweep:
    def test_batched_one_unit_per_k(self):
        units = plan_sweep(SMALL_CFG)
        assert len(units) == 3
        assert [u.k for u in units] == [2, 3, 4]
        assert all(u.members == (0, 1, 2, 3) for u in units)
        assert [u.index for u in units] == [0, 1, 2]

    def test_loop_one_unit_per_member(self):
        units = plan_sweep(SMALL_CFG, mode="loop")
        assert len(units) == 3 * 4
        assert {(u.k, u.members) for u in units} == {
            (k, (q,)) for k in (2, 3, 4) for q in range(4)}

    def test_pods_split_members(self):
        units = plan_sweep(SMALL_CFG, n_pods=2)
        assert len(units) == 6
        per_k = {k: sorted(m for u in units if u.k == k for m in u.members)
                 for k in (2, 3, 4)}
        assert all(v == [0, 1, 2, 3] for v in per_k.values())

    def test_uid_is_pure_grid_identity(self):
        # the checkpoint tag must derive from the (k, member-range) cell,
        # never from PRNG key internals (the old rescalk_run bug)
        u = WorkUnit(index=7, k=5, members=(2, 3))
        assert u.uid == "unit_k5_q2-3"
        assert plan_sweep(SMALL_CFG) == plan_sweep(SMALL_CFG)

    def test_unknown_mode(self):
        with pytest.raises(ValueError):
            plan_sweep(SMALL_CFG, mode="warp")


class TestCriteria:
    ks = [2, 3, 4, 5]

    def test_threshold_prefers_largest_stable(self):
        s = np.array([0.99, 0.98, 0.97, 0.3])
        e = np.array([0.5, 0.2, 0.05, 0.04])
        assert criteria.select("threshold", self.ks, s, None, e) == 4

    def test_threshold_fallback_when_nothing_stable(self):
        s = np.array([0.5, 0.4, 0.3, 0.2])
        e = np.array([0.4, 0.1, 0.3, 0.3])
        got = criteria.select("threshold", self.ks, s, None, e,
                              sil_threshold=0.9)
        assert got == criteria.select("stability_fit", self.ks, s, None, e)
        assert got == 3                   # argmax(s_min - rel_err)

    def test_single_candidate_every_criterion(self):
        for name in CRITERIA:
            assert criteria.select(name, [4], np.array([0.1]), None,
                                   np.array([0.9])) == 4

    def test_elbow_finds_knee(self):
        ks = [2, 3, 4, 5, 6, 7]
        e = np.array([1.0, 0.55, 0.12, 0.10, 0.09, 0.085])
        s = np.zeros(6)                   # stability irrelevant to the knee
        assert criteria.select("elbow", ks, s, None, e) == 4

    def test_elbow_monotone_linear_falls_back(self):
        ks = [2, 3, 4, 5]
        e = np.array([0.8, 0.6, 0.4, 0.2])       # no knee
        s = np.array([0.9, 0.9, 0.9, 0.1])
        assert criteria.select("elbow", ks, s, None, e) == \
            criteria.select("threshold", ks, s, None, e) == 4

    def test_elbow_increasing_curve_falls_back(self):
        ks = [2, 3, 4]
        e = np.array([0.1, 0.2, 0.3])
        s = np.array([0.9, 0.8, 0.2])
        assert criteria.select("elbow", ks, s, None, e) == \
            criteria.select("threshold", ks, s, None, e)

    def test_unknown_criterion_raises(self):
        with pytest.raises(ValueError, match="unknown selection criterion"):
            criteria.select("vibes", self.ks, np.zeros(4), None, np.zeros(4))
        with pytest.raises(ValueError):
            SweepScheduler(SMALL_CFG, criterion="vibes")


class TestBatchedLoopParity:
    """The acceptance contract: one batched program == the sequential loop,
    member for member, and the same k_opt."""

    def test_member_errors_match(self):
        X = small_tensor()
        rb = run_ensemble(X, 3, SMALL_CFG, mode="batched")
        rl = run_ensemble(X, 3, SMALL_CFG, mode="loop")
        np.testing.assert_allclose(rb.errors, rl.errors, rtol=1e-3,
                                   atol=1e-5)
        np.testing.assert_allclose(rb.A, rl.A, rtol=5e-3, atol=1e-4)
        np.testing.assert_allclose(rb.R, rl.R, rtol=5e-3, atol=1e-4)

    def test_member_subset_matches_full(self):
        X = small_tensor()
        full = run_ensemble(X, 3, SMALL_CFG, mode="batched")
        part = run_ensemble(X, 3, SMALL_CFG, members=(1, 2), mode="batched")
        np.testing.assert_allclose(part.errors, full.errors[1:3], rtol=1e-5)

    def test_full_sweep_same_k_opt(self):
        X = small_tensor()
        res_b = rescalk(X, SMALL_CFG)
        res_l = rescalk(X, SMALL_CFG, mode="loop")
        assert res_b.k_opt == res_l.k_opt
        for k in SMALL_CFG.ks:
            np.testing.assert_allclose(res_b.per_k[k].member_errors,
                                       res_l.per_k[k].member_errors,
                                       rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(res_b.s_min, res_l.s_min, atol=5e-3)

    def test_nndsvd_init_parity(self):
        X = small_tensor()
        cfg = RescalkConfig(k_min=3, k_max=3, n_perturbations=3,
                            rescal_iters=60, init="nndsvd", seed=5)
        rb = run_ensemble(X, 3, cfg, mode="batched")
        rl = run_ensemble(X, 3, cfg, mode="loop")
        np.testing.assert_allclose(rb.errors, rl.errors, rtol=1e-3,
                                   atol=1e-5)


class TestBCSREnsemble:
    """BCSR operands (ISSUE 3): stored-block perturbation members must
    match the dense reference member-for-member (acceptance: 1e-5)."""

    CFG = RescalkConfig(k_min=2, k_max=3, n_perturbations=3,
                        rescal_iters=60, regress_iters=20, seed=3)

    def small_bcsr(self, n=96, m=2, bs=16, seed=0):
        from repro.core import sparse as sp
        return sp.random_bcsr(jax.random.PRNGKey(seed), m=m, n=n, bs=bs,
                              block_density=0.3)

    def test_batched_matches_dense_reference_1e5(self):
        from repro.selection import run_ensemble_bcsr_dense_reference
        s = self.small_bcsr()
        rb = run_ensemble(s, 3, self.CFG, mode="batched")
        rd = run_ensemble_bcsr_dense_reference(s, 3, self.CFG)
        np.testing.assert_allclose(rb.errors, rd.errors, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(rb.A, rd.A, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(rb.R, rd.R, rtol=1e-4, atol=1e-5)

    def test_loop_matches_batched(self):
        s = self.small_bcsr()
        rb = run_ensemble(s, 3, self.CFG, mode="batched")
        rl = run_ensemble(s, 3, self.CFG, mode="loop")
        np.testing.assert_allclose(rb.errors, rl.errors, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(rb.A, rl.A, rtol=1e-3, atol=1e-5)

    def test_member_subset_matches_full(self):
        s = self.small_bcsr()
        full = run_ensemble(s, 3, self.CFG, mode="batched")
        part = run_ensemble(s, 3, self.CFG, members=(1, 2), mode="batched")
        np.testing.assert_allclose(part.errors, full.errors[1:3], rtol=1e-5)

    def test_full_sweep_on_bcsr(self):
        s = self.small_bcsr()
        res = SweepScheduler(self.CFG).run(s)
        assert res.k_opt in self.CFG.ks
        assert res.per_k[res.k_opt].A_median.shape == (96, res.k_opt)

    def test_full_sweep_on_sharded(self):
        """A ShardedBCSR operand sweeps in the permuted factor space."""
        from repro.io import partition_dense
        from repro.core import sparse as sp
        s = self.small_bcsr()
        sh = partition_dense(np.asarray(sp.to_dense(s)), bs=16, grid=2)
        res = SweepScheduler(self.CFG).run(sh)
        assert res.k_opt in self.CFG.ks
        assert res.per_k[res.k_opt].A_median.shape == (sh.n_pad, res.k_opt)

    def test_nndsvd_rejected_for_bcsr(self):
        s = self.small_bcsr()
        cfg = dataclasses.replace(self.CFG, init="nndsvd")
        with pytest.raises(NotImplementedError, match="random"):
            run_ensemble(s, 3, cfg, mode="batched")

    def test_plain_bcsr_with_mesh_rejected(self):
        s = self.small_bcsr()
        with pytest.raises(ValueError, match="partition"):
            run_ensemble(s, 3, self.CFG, mesh=object())


class TestFusedSweep:
    """cfg.use_fused_kernel on BCSR sweep programs (ISSUE 5): the fused
    single-pass members must match the oracle members at <= 1e-5 with no
    API change, in per-k batched, loop and cross-k grid modes."""

    CFG = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                        rescal_iters=40, regress_iters=20, seed=3)

    def small_bcsr(self, n=96, m=2, bs=16, seed=0):
        from repro.core import sparse as sp
        return sp.random_bcsr(jax.random.PRNGKey(seed), m=m, n=n, bs=bs,
                              block_density=0.3)

    @pytest.mark.parametrize("mode", ["batched", "loop"])
    def test_per_k_members_match_oracle(self, mode):
        s = self.small_bcsr()
        cfg_f = dataclasses.replace(self.CFG, use_fused_kernel=True,
                                    fused_impl="ref")
        r_o = run_ensemble(s, 3, self.CFG, mode=mode)
        r_f = run_ensemble(s, 3, cfg_f, mode=mode)
        np.testing.assert_allclose(r_f.errors, r_o.errors, rtol=1e-5)
        np.testing.assert_allclose(r_f.A, r_o.A, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(r_f.R, r_o.R, rtol=1e-5, atol=1e-7)

    def test_grid_cells_match_oracle(self):
        from repro.selection.ensemble import run_sweep_batched
        s = self.small_bcsr()
        cells = [(k, q) for k in self.CFG.ks for q in range(2)]
        cfg_f = dataclasses.replace(self.CFG, use_fused_kernel=True,
                                    fused_impl="ref")
        g_o = run_sweep_batched(s, cells, self.CFG)
        g_f = run_sweep_batched(s, cells, cfg_f)
        np.testing.assert_allclose(g_f.errors, g_o.errors, rtol=1e-5)
        np.testing.assert_allclose(g_f.A, g_o.A, rtol=1e-5, atol=1e-7)

    def test_full_sweep_selects_same_k(self):
        s = self.small_bcsr()
        cfg_f = dataclasses.replace(self.CFG, use_fused_kernel=True,
                                    fused_impl="ref")
        r_o = SweepScheduler(self.CFG).run(s)
        r_f = SweepScheduler(cfg_f).run(s)
        assert r_f.k_opt == r_o.k_opt
        for k in self.CFG.ks:
            np.testing.assert_allclose(r_f.per_k[k].member_errors,
                                       r_o.per_k[k].member_errors,
                                       rtol=1e-5)


class TestDonationClean:
    """Buffer donation on the hot drivers (ISSUE 5 satellite): the
    dist.compat shim enables donation only on backends that implement
    aliasing, so the donating drivers must run with NO no-alias /
    donation warnings — the contract CI asserts on CPU."""

    def test_run_iters_and_grid_programs_warning_clean(self):
        import warnings
        from repro.core.rescal import _run_iters, init_factors
        X = small_tensor()
        st = init_factors(jax.random.PRNGKey(0), 24, 2, 3)
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=10, seed=0)
        cells = [(k, q) for k in cfg.ks for q in range(2)]
        from repro.selection.ensemble import run_sweep_batched
        with warnings.catch_warnings():
            warnings.simplefilter("error")       # any warning -> failure
            out = _run_iters(X, st, 5, "batched", 1e-16)
            res = run_sweep_batched(X, cells, cfg)
            jax.block_until_ready((out.A, res.A))


class TestMaskedMU:
    """The cross-k padding primitives (ISSUE 4): masked columns stay
    exactly zero through update/normalize, and the active block matches
    the unpadded reference — what makes grid-mode results comparable to
    per-k results member-for-member."""

    K, K_MAX = 3, 5

    def setup_method(self, _):
        key = jax.random.PRNGKey(7)
        self.X = small_tensor(n=16, m=2, k=self.K, seed=7)
        self.state = init_factors(jax.random.fold_in(key, 1), 16, 2, self.K)
        self.mask = column_mask(self.K, self.K_MAX, self.X.dtype)

    def test_column_mask_and_pad_crop_roundtrip(self):
        np.testing.assert_array_equal(np.asarray(self.mask),
                                      [1, 1, 1, 0, 0])
        padded = pad_state(self.state, self.K_MAX)
        assert padded.A.shape == (16, self.K_MAX)
        assert padded.R.shape == (2, self.K_MAX, self.K_MAX)
        cropped = crop_state(padded, self.K)
        np.testing.assert_array_equal(cropped.A, self.state.A)
        np.testing.assert_array_equal(cropped.R, self.state.R)
        with pytest.raises(ValueError, match="pad rank"):
            pad_state(self.state, self.K - 1)

    def test_masked_step_matches_unpadded_and_zeros_stay_zero(self):
        ref = self.state
        padded = pad_state(self.state, self.K_MAX)
        for schedule in ("batched", "sliced"):
            st_ref, st_pad = ref, padded
            for _ in range(8):
                st_ref = (mu_step_batched if schedule == "batched"
                          else mu_step_sliced)(self.X, st_ref)
                st_pad = masked_mu_step(self.X, st_pad, self.mask,
                                        schedule=schedule)
            # padded active block == unpadded (identical arithmetic up to
            # reduction order; zeros contribute exact zeros)
            np.testing.assert_allclose(st_pad.A[:, :self.K], st_ref.A,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(st_pad.R[:, :self.K, :self.K],
                                       st_ref.R, rtol=1e-5, atol=1e-6)
            # masked region: exact zeros, not merely small
            assert (np.asarray(st_pad.A)[:, self.K:] == 0.0).all()
            assert (np.asarray(st_pad.R)[:, self.K:, :] == 0.0).all()
            assert (np.asarray(st_pad.R)[:, :, self.K:] == 0.0).all()

    def test_masked_normalize_and_rel_error(self):
        st_ref = normalize(mu_step_batched(self.X, self.state))
        st_pad = masked_normalize(
            masked_mu_step(self.X, pad_state(self.state, self.K_MAX),
                           self.mask), self.mask)
        np.testing.assert_allclose(st_pad.A[:, :self.K], st_ref.A,
                                   rtol=1e-6, atol=1e-7)
        assert (np.asarray(st_pad.A)[:, self.K:] == 0.0).all()
        # rel_error needs no mask: zero columns contribute exactly zero.
        # Padding changes only the f32 reduction order.  rel_error is
        # sqrt(err2 / x2) with err2 = x2 - 2 cross + fit2: terms worth
        # ~4 x2 in all that cancel down to rel^2 * x2.  A reorder error
        # of up to 4 ulps (4 * 2^-24) in them moves err2 by
        # 16 * 2^-24 * x2, i.e. by 16 * 2^-24 / rel^2 relative, and the
        # square root halves that: rtol = 8 * 2^-24 / rel^2 (2.0e-5 at
        # rel = 0.156)
        ref_err = float(rel_error(self.X, st_ref.A, st_ref.R))
        np.testing.assert_allclose(
            float(rel_error(self.X, st_pad.A, st_pad.R)), ref_err,
            rtol=8 * 2.0 ** -24 / ref_err ** 2)

    def test_mask_state_is_idempotent(self):
        st = mask_state(pad_state(self.state, self.K_MAX), self.mask)
        st2 = mask_state(st, self.mask)
        np.testing.assert_array_equal(st.A, st2.A)
        np.testing.assert_array_equal(st.R, st2.R)



class TestGridPlan:
    """Grid-mode planning: chunk layout, uid identity, and the shared key
    discipline (ISSUE 4 satellite: keys hoisted into unit identity)."""

    def test_default_is_one_chunk(self):
        chunks = plan_sweep(SMALL_CFG, mode="grid")
        assert len(chunks) == 1
        assert chunks[0].cells == tuple(
            (k, q) for k in (2, 3, 4) for q in range(4))
        assert chunks[0].k_max == 4

    def test_chunking_with_ragged_tail(self):
        chunks = plan_sweep(SMALL_CFG, mode="grid", grid_chunk=5)
        assert [len(c.cells) for c in chunks] == [5, 5, 2]
        flat = [c for ch in chunks for c in ch.cells]
        assert flat == [(k, q) for k in (2, 3, 4) for q in range(4)]
        assert plan_sweep(SMALL_CFG, mode="grid", grid_chunk=5) == chunks

    def test_uid_is_pure_grid_identity(self):
        ch = GridChunk(index=0, cells=((2, 1), (2, 2), (3, 0)), k_max=5)
        assert ch.uid == "grid_k2q1-k3q0"

    def test_n_pods_sets_default_chunk_count(self):
        chunks = plan_sweep(SMALL_CFG, mode="grid", n_pods=2)
        assert len(chunks) == 2
        assert [len(c.cells) for c in chunks] == [6, 6]

    def test_keys_share_one_discipline(self):
        """WorkUnit.keys and GridChunk.keys both resolve through
        unit_keys, so grid cells draw exactly the per-k unit's keys."""
        unit = WorkUnit(index=0, k=3, members=(0, 1, 2, 3))
        chunk = plan_sweep(SMALL_CFG, mode="grid")[0]
        uk = np.asarray(unit.keys(SMALL_CFG))
        ck = np.asarray(chunk.keys(SMALL_CFG))
        rows = [i for i, (k, _) in enumerate(chunk.cells) if k == 3]
        np.testing.assert_array_equal(ck[rows], uk)
        np.testing.assert_array_equal(uk, np.asarray(
            unit_keys(SMALL_CFG, 3, (0, 1, 2, 3))))

    def test_grid_chunk_rejected_outside_grid_mode(self):
        with pytest.raises(ValueError, match="grid_chunk"):
            plan_sweep(SMALL_CFG, mode="batched", grid_chunk=4)
        with pytest.raises(ValueError, match="positive"):
            plan_sweep(SMALL_CFG, mode="grid", grid_chunk=0)


class TestGridSweep:
    """The cross-k tentpole contract: padded-to-k_max grid results equal
    the per-k batched results member-for-member (<= 1e-5), masked columns
    are exact zeros, and the grid scheduler keeps the per-unit
    resume/report behaviour at chunk granularity."""

    # k_max = 5 with ks 2..5: 2, 3, 4 all fail to divide k_max — the
    # "k_max-indivisible" grid the padding must handle
    CFG = RescalkConfig(k_min=2, k_max=5, n_perturbations=3,
                        rescal_iters=60, regress_iters=20, seed=3)

    def _cells(self, cfg=None):
        cfg = cfg or self.CFG
        return [(k, q) for k in cfg.ks
                for q in range(cfg.n_perturbations)]

    def test_dense_matches_per_k_batched_1e5(self):
        X = small_tensor()
        g = run_sweep_batched(X, self._cells(), self.CFG)
        gA, gR = np.asarray(g.A), np.asarray(g.R)
        for k in self.CFG.ks:
            b = run_ensemble(X, k, self.CFG, mode="batched")
            rows = [i for i, (kk, _) in enumerate(self._cells())
                    if kk == k]
            np.testing.assert_allclose(np.asarray(g.errors)[rows],
                                       b.errors, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(gA[rows][:, :, :k], b.A,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(gR[rows][:, :, :k, :k], b.R,
                                       rtol=1e-5, atol=1e-5)

    def test_masked_columns_exactly_zero(self):
        X = small_tensor()
        g = run_sweep_batched(X, self._cells(), self.CFG)
        gA, gR = np.asarray(g.A), np.asarray(g.R)
        for i, (k, _) in enumerate(self._cells()):
            assert (gA[i][:, k:] == 0.0).all()
            assert (gR[i][:, k:, :] == 0.0).all()
            assert (gR[i][:, :, k:] == 0.0).all()

    def test_bcsr_matches_per_k_batched_1e5(self):
        from repro.core import sparse as sp
        s = sp.random_bcsr(jax.random.PRNGKey(0), m=2, n=40, bs=8,
                           block_density=0.3)
        g = run_sweep_batched(s, self._cells(), self.CFG)
        gA = np.asarray(g.A)
        for k in self.CFG.ks:
            b = run_ensemble(s, k, self.CFG, mode="batched")
            rows = [i for i, (kk, _) in enumerate(self._cells())
                    if kk == k]
            np.testing.assert_allclose(np.asarray(g.errors)[rows],
                                       b.errors, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(gA[rows][:, :, :k], b.A,
                                       rtol=1e-5, atol=1e-5)
            assert (gA[rows][:, :, k:] == 0.0).all()

    def test_grid_scheduler_matches_batched_scheduler(self):
        """Full sweep through mode='grid' (ragged chunks) == mode='batched'
        — same k_opt, same member errors, same medians."""
        X = small_tensor()
        res_g = SweepScheduler(self.CFG, mode="grid", grid_chunk=5).run(X)
        res_b = SweepScheduler(self.CFG, mode="batched").run(X)
        assert res_g.k_opt == res_b.k_opt
        for k in self.CFG.ks:
            np.testing.assert_allclose(res_g.per_k[k].member_errors,
                                       res_b.per_k[k].member_errors,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(res_g.per_k[k].A_median,
                                       res_b.per_k[k].A_median,
                                       rtol=1e-4, atol=1e-5)

    def test_grid_interrupt_then_resume(self, tmp_path):
        """Chunk-granular checkpoints keep the per-unit resume contract:
        interrupted chunks are reused, not recomputed, and the resumed
        result is identical to an uninterrupted run."""
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        with pytest.raises(SweepInterrupted) as ei:
            SweepScheduler(self.CFG, mode="grid", grid_chunk=5,
                           ckpt_dir=d, stop_after_units=1).run(X)
        assert ei.value.executed == 1

        sched = SweepScheduler(self.CFG, mode="grid", grid_chunk=5,
                               ckpt_dir=d)
        res = sched.run(X)
        executed = [u.uid for u in sched.report.units if not u.reused]
        assert len(executed) == 2            # 3 chunks, 1 checkpointed
        assert sched.report.n_reused == 1
        fresh = SweepScheduler(self.CFG, mode="grid", grid_chunk=5).run(X)
        assert res.k_opt == fresh.k_opt
        for k in self.CFG.ks:
            np.testing.assert_array_equal(res.per_k[k].member_errors,
                                          fresh.per_k[k].member_errors)

    def test_grid_report_records_chunks(self, tmp_path):
        X = small_tensor()
        path = str(tmp_path / "report.json")
        sched = SweepScheduler(self.CFG, mode="grid", grid_chunk=5,
                               report_path=path)
        sched.run(X)
        rep = SelectionReport.load(path)
        assert rep.mode == "grid"
        assert len(rep.units) == 3
        assert all(u.k == -1 and u.members == [] for u in rep.units)
        flat = [tuple(c) for u in rep.units for c in u.cells]
        assert flat == self._cells()

    def test_grid_nndsvd_rejected_early(self):
        cfg = dataclasses.replace(self.CFG, init="nndsvd")
        with pytest.raises(NotImplementedError, match="random"):
            SweepScheduler(cfg, mode="grid")

    def test_rechunked_sweep_reuses_coinciding_chunks(self, tmp_path):
        """grid_chunk is not in the checkpoint fingerprint: chunk uids
        encode their exact cell range, so a re-chunked resume reuses
        chunks whose contents coincide and recomputes the rest."""
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=30, regress_iters=20, seed=1)
        SweepScheduler(cfg, mode="grid", grid_chunk=2, ckpt_dir=d).run(X)
        # same cells, same chunking -> full reuse
        sched = SweepScheduler(cfg, mode="grid", grid_chunk=2, ckpt_dir=d)
        sched.run(X)
        assert sched.report.n_reused == 2
        # different chunking -> different ranges, recomputed from scratch
        sched = SweepScheduler(cfg, mode="grid", grid_chunk=3, ckpt_dir=d)
        sched.run(X)
        assert sched.report.n_reused == 0


class TestManifestGuard:
    """The scheduler's sweep.json fingerprint now comes from io.manifest:
    stale data — not just stale config — must reject a resume."""

    CFG = RescalkConfig(k_min=2, k_max=2, n_perturbations=2,
                        rescal_iters=30, regress_iters=20, seed=1)

    def test_stale_manifest_rejected_dense(self, tmp_path):
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        SweepScheduler(self.CFG, ckpt_dir=d).run(X)
        with pytest.raises(ValueError,
                           match="different sweep configuration"):
            SweepScheduler(self.CFG, ckpt_dir=d).run(X * 1.001)

    def test_stale_manifest_rejected_bcsr_pattern(self, tmp_path):
        """Same values, different sparsity pattern -> different manifest
        digest (the structural hash, not just the moments)."""
        from repro.core import sparse as sp
        s = sp.random_bcsr(jax.random.PRNGKey(0), m=2, n=64, bs=16,
                           block_density=0.3)
        d = str(tmp_path / "ckpt")
        SweepScheduler(self.CFG, ckpt_dir=d).run(s)
        moved = s._replace(block_rows=(s.block_rows + 1) % s.nblocks)
        with pytest.raises(ValueError,
                           match="different sweep configuration"):
            SweepScheduler(self.CFG, ckpt_dir=d).run(moved)
        # unchanged operand still resumes
        res = SweepScheduler(self.CFG, ckpt_dir=d).run(s)
        assert res.k_opt in self.CFG.ks

    def test_manifest_fingerprint_in_sweep_json(self, tmp_path):
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        SweepScheduler(self.CFG, ckpt_dir=d).run(X)
        import os
        with open(os.path.join(d, "sweep.json")) as f:
            fp = json.load(f)
        assert fp["manifest"]["kind"] == "dense"
        assert fp["manifest"]["n"] == X.shape[1]
        assert "digest" in fp["manifest"]


class TestSchedulerResume:
    CFG = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                        rescal_iters=30, regress_iters=20, seed=1)

    def test_interrupt_then_resume_skips_completed_units(self, tmp_path):
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        with pytest.raises(SweepInterrupted) as ei:
            SweepScheduler(self.CFG, ckpt_dir=d, stop_after_units=1).run(X)
        assert ei.value.executed == 1

        sched = SweepScheduler(self.CFG, ckpt_dir=d)
        res = sched.run(X)
        # 2 units total; the checkpointed one must NOT be recomputed
        executed = [u.uid for u in sched.report.units if not u.reused]
        assert len(executed) == 1
        assert sched.report.n_reused == 1
        # resilience accounting: a reused unit ran 0 attempts, a computed
        # one exactly 1 — the fields check_trace.py cross-checks
        assert {u.attempts for u in sched.report.units
                if u.reused} == {0}
        assert {u.attempts for u in sched.report.units
                if not u.reused} == {1}
        # resumed result identical to an uncheckpointed run (float32
        # checkpoints round-trip exactly)
        fresh = SweepScheduler(self.CFG).run(X)
        assert res.k_opt == fresh.k_opt
        for k in self.CFG.ks:
            np.testing.assert_array_equal(res.per_k[k].member_errors,
                                          fresh.per_k[k].member_errors)

    def test_resume_with_loop_granularity(self, tmp_path):
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        with pytest.raises(SweepInterrupted):
            SweepScheduler(self.CFG, mode="loop", ckpt_dir=d,
                           stop_after_units=3).run(X)
        sched = SweepScheduler(self.CFG, mode="loop", ckpt_dir=d)
        sched.run(X)
        executed = [u.uid for u in sched.report.units if not u.reused]
        assert len(executed) == 4 - 3     # 2 ks x 2 members, 3 done

    def test_stop_on_final_unit_completes(self, tmp_path):
        X = small_tensor()
        res = SweepScheduler(self.CFG, ckpt_dir=str(tmp_path / "c"),
                             stop_after_units=2).run(X)
        assert res.k_opt in self.CFG.ks   # no interrupt: nothing remained

    def test_config_change_invalidates_ckpt_dir(self, tmp_path):
        """Unit tags are config-blind by design; the sweep.json fingerprint
        is what stops a resume from silently reusing stale units."""
        X = small_tensor()
        d = str(tmp_path / "ckpt")
        with pytest.raises(SweepInterrupted):
            SweepScheduler(self.CFG, ckpt_dir=d, stop_after_units=1).run(X)
        changed = dataclasses.replace(self.CFG, rescal_iters=300)
        with pytest.raises(ValueError,
                           match="different sweep configuration"):
            SweepScheduler(changed, ckpt_dir=d).run(X)
        # a different same-shape tensor must invalidate the dir too
        with pytest.raises(ValueError,
                           match="different sweep configuration"):
            SweepScheduler(self.CFG, ckpt_dir=d).run(small_tensor(seed=9))
        # the unchanged config + tensor still resumes fine
        res = SweepScheduler(self.CFG, ckpt_dir=d).run(X)
        assert res.k_opt in self.CFG.ks

    def test_mesh_with_loop_mode_rejected(self):
        with pytest.raises(ValueError, match="host-only"):
            SweepScheduler(self.CFG, mode="loop", mesh=object())


class TestRetry:
    """Unit retry now goes through resilience.RetryPolicy, with faults
    injected at the `sched/unit` seam of a FaultPlan (the old ad-hoc
    failure_injector callable is gone)."""

    CFG = RescalkConfig(k_min=2, k_max=2, n_perturbations=2,
                        rescal_iters=30, regress_iters=20, seed=1)

    def _policy(self, max_retries):
        # near-zero backoff: these tests assert behaviour, not pacing
        from repro.resilience import RetryPolicy
        return RetryPolicy(max_attempts=max_retries + 1, base_delay=1e-4)

    def test_transient_failure_is_retried(self):
        from repro.resilience import FaultPlan, FaultSpec, faults
        X = small_tensor()
        plan = FaultPlan({"sched/unit": [
            FaultSpec(kind="raise-transient", at=(0,))]})
        sched = SweepScheduler(self.CFG, retry=self._policy(1))
        with faults.active(plan):
            res = sched.run(X)
        unit = sched.report.units[0]
        assert (unit.retries, unit.attempts) == (1, 2)
        assert unit.backoff_seconds > 0.0
        assert plan.hits["sched/unit"] == 2   # failed attempt + replay
        clean = SweepScheduler(self.CFG).run(X)
        np.testing.assert_array_equal(res.per_k[2].member_errors,
                                      clean.per_k[2].member_errors)

    def test_budget_exhausted_raises(self):
        from repro.resilience import FaultPlan, FaultSpec, TransientError
        from repro.resilience import faults
        X = small_tensor()
        plan = FaultPlan({"sched/unit": [
            FaultSpec(kind="raise-transient", always=True,
                      message="persistent")]})
        with faults.active(plan):
            with pytest.raises(TransientError, match="persistent"):
                SweepScheduler(self.CFG, retry=self._policy(2)).run(X)
        assert plan.hits["sched/unit"] == 3   # max_attempts, then raise

    def test_deterministic_fault_fails_fast(self):
        """A non-transient error must not burn the retry budget: one
        attempt, the original exception, no replays."""
        from repro.resilience import (DeterministicFault, FaultPlan,
                                      FaultSpec, faults)
        X = small_tensor()
        plan = FaultPlan({"sched/unit": [
            FaultSpec(kind="raise-deterministic", at=(0,))]})
        with faults.active(plan):
            with pytest.raises(DeterministicFault):
                SweepScheduler(self.CFG, retry=self._policy(3)).run(X)
        assert plan.hits["sched/unit"] == 1


class TestReport:
    def test_report_json_roundtrip(self, tmp_path):
        X = small_tensor()
        path = str(tmp_path / "sel" / "report.json")
        sched = SweepScheduler(SMALL_CFG, report_path=path)
        res = sched.run(X)

        with open(path) as f:
            raw = json.load(f)
        assert raw["k_opt"] == res.k_opt
        assert raw["criterion"] == "threshold"
        assert len(raw["units"]) == len(sched.units)
        assert all(not u["reused"] for u in raw["units"])
        assert raw["total_seconds"] > 0

        rep = SelectionReport.load(path)
        assert rep.k_opt == res.k_opt
        assert rep.ks == list(SMALL_CFG.ks)
        assert rep.n_reused == 0
        # criteria are re-runnable from the stored curves alone
        assert rep.reselect("threshold",
                            sil_threshold=SMALL_CFG.sil_threshold) \
            == res.k_opt

    def test_legacy_member_runner_falls_back_to_loop(self):
        X = small_tensor()
        calls = []

        def runner(X_q, k, key, cfg):
            from repro.core.rescalk import default_member_runner
            calls.append(k)
            return default_member_runner(X_q, k, key, cfg)

        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=30, regress_iters=20, seed=1)
        res = rescalk(X, cfg, member_runner=runner)
        assert calls == [2, 2, 3, 3]
        assert res.k_opt in (2, 3)

    def test_legacy_runner_rejects_scheduler_kwargs(self):
        """The legacy loop has no scheduler: silently dropping ckpt_dir /
        criterion / mesh / mode would lose checkpoints or apply the wrong
        selection rule, so the combination must refuse loudly."""
        X = small_tensor()

        def runner(X_q, k, key, cfg):
            from repro.core.rescalk import default_member_runner
            return default_member_runner(X_q, k, key, cfg)

        for kw in ({"criterion": "elbow"}, {"ckpt_dir": "/tmp/nope"},
                   {"mode": "loop"}):
            with pytest.raises(ValueError, match="legacy sequential loop"):
                rescalk(X, SMALL_CFG, member_runner=runner, **kw)
