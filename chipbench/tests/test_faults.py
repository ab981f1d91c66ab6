"""Each fault a cell can have, planted under the timed path, turns
``correct`` false.  The harness's look for a chip is skipped (``run_cell``
is driven directly on the CPU at a small size); everything else of a run
is as on the chip."""
import importlib
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from chipbench import bench


def clear():
    from repro.selection import ensemble
    jax.clear_caches()
    ensemble.make_mesh_ensemble.cache_clear()


@pytest.fixture(autouse=True)
def fresh_programs():
    clear()                     # a fault must reach the traced programs
    yield
    clear()


def run(name, seconds=0.1):
    cell = tiny.tiny_cell(name)
    return bench.run_cell(cell, 2**31 + 33, seconds, False,
                          t_start=time.perf_counter(),
                          devices=jax.devices()[:cell.chips])


def failed(res):
    return {n for n, c in res["checks"].items()
            if not c["value"] <= c["limit"]}


def test_sweep_state_unchanged_dense(monkeypatch, cpu_peaks):
    rescal = importlib.import_module("repro.core.rescal")
    monkeypatch.setitem(rescal.MU_SCHEDULES, "batched",
                        lambda X, st, *a, **k: st)
    res = run("dense_sweep")
    assert res["correct"] is False
    assert "fit_gap" in failed(res)


def test_sweep_state_unchanged_sparse(monkeypatch, cpu_peaks):
    sparse = importlib.import_module("repro.core.sparse")
    monkeypatch.setattr(sparse, "sparse_mu_step",
                        lambda sp, A, R, *a, **k: (A, R))
    res = run("sparse_sweep")
    assert res["correct"] is False
    assert "fit_gap" in failed(res)


def test_grid_state_unchanged(monkeypatch, cpu_peaks):
    engine = importlib.import_module("repro.dist.engine")
    monkeypatch.setattr(engine, "get_mu_iter",
                        lambda *a: lambda X, A, R, cfg: (A, R))
    res = run("dense_grid4")
    assert res["correct"] is False
    assert "fit_gap" in failed(res)


def test_grid_exchange_left_out(monkeypatch, cpu_peaks):
    """Every psum of the 2D grid returns the chip's own partial sum."""
    engine = importlib.import_module("repro.dist.engine")
    monkeypatch.setattr(engine, "psum_cast", lambda x, axis, cd=None: x)
    res = run("dense_grid4")
    assert res["correct"] is False
    assert "fit_gap" in failed(res)


@pytest.mark.parametrize("name", ["dense_sweep", "sparse_sweep",
                                  "dense_grid4"])
def test_sweep_selected_k_altered(name, monkeypatch, cpu_peaks):
    from repro.selection import criteria
    select = criteria.select
    monkeypatch.setattr(criteria, "select",
                        lambda *a, **k: select(*a, **k) + 1)
    res = run(name)
    assert res["correct"] is False
    assert failed(res) == {"k_gap"}


def one_member_replaced(monkeypatch, replace):
    """Member 1 of every unit, as the scheduler receives it, replaced by
    ``replace(res)`` -> (A1, R1); the other members as computed."""
    import repro.selection.scheduler as sched_mod
    run_ensemble = sched_mod.run_ensemble

    def altered(X, k, cfg, **kw):
        res = run_ensemble(X, k, cfg, **kw)
        A1, R1 = replace(res, k, cfg)
        return res._replace(A=res.A.at[1].set(A1), R=res.R.at[1].set(R1))

    monkeypatch.setattr(sched_mod, "run_ensemble", altered)


@pytest.mark.parametrize("name", ["dense_sweep", "sparse_sweep",
                                  "dense_grid4"])
def test_sweep_one_member_unchanged(name, monkeypatch, cpu_peaks):
    """One of the r members returns its initial factors (normalized, as
    the program leaves every member); the others are computed."""
    from chipbench import reference

    def initial(res, k, cfg):
        n, m = res.A.shape[1], res.R.shape[1]
        key = reference.member_keys(cfg.seed, k, cfg.n_perturbations)[1]
        A0, R0 = reference.member_init(jax.random.split(key)[1], n, m, k)
        c = jnp.linalg.norm(A0, axis=0)
        return A0 / c, jnp.einsum("a,mab,b->mab", c, R0, c)

    one_member_replaced(monkeypatch, initial)
    res = run(name)
    assert res["correct"] is False
    assert "fit_gap" in failed(res)


@pytest.mark.parametrize("name", ["dense_sweep", "sparse_sweep",
                                  "dense_grid4"])
def test_sweep_one_member_left_out(name, monkeypatch, cpu_peaks):
    """One of the r members is left out and the ensemble is made of the
    rest: its place holds a copy of member 0."""
    one_member_replaced(monkeypatch, lambda res, k, cfg: (res.A[0],
                                                          res.R[0]))
    res = run(name)
    assert res["correct"] is False
    assert failed(res) >= {"k_gap"}


@pytest.mark.parametrize("name", ["dense_sweep", "sparse_sweep",
                                  "dense_grid4"])
def test_sweep_member_factors_altered(name, monkeypatch, cpu_peaks):
    import repro.selection.scheduler as sched_mod
    run_ensemble = sched_mod.run_ensemble

    def altered(*a, **k):
        res = run_ensemble(*a, **k)
        return res._replace(A=res.A.at[:, ::7].multiply(1.5))

    monkeypatch.setattr(sched_mod, "run_ensemble", altered)
    res = run(name)
    assert res["correct"] is False
    assert "fit_gap" in failed(res)
