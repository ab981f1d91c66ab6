"""The copied generators are pure functions of the seed, and the sweep
operands select their planted k at a small size (CPU)."""

import jax
import numpy as np
import pytest

import tiny
from chipbench import bench, generators


def test_dense_operand_deterministic_in_seed():
    a = generators.planted_dense(2**31 + 5, n=64, m=3, k=5, noise=0.01)
    b = generators.planted_dense(2**31 + 5, n=64, m=3, k=5, noise=0.01)
    c = generators.planted_dense(6, n=64, m=3, k=5, noise=0.01)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert float(np.asarray(a).min()) > 0


def test_dense_operand_same_in_its_sharding():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = NamedSharding(mesh, P(None, "data", "model"))
    a = generators.planted_dense(2**31 + 5, n=64, m=3, k=5, noise=0.01)
    b = generators.planted_dense(2**31 + 5, n=64, m=3, k=5, noise=0.01,
                                 sharding=sh)
    assert b.sharding == sh
    # the same draws; only the rounding of the model's einsum may differ
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bcsr_operand_deterministic_in_seed():
    kw = dict(n=768, m=2, k=5, bs=16, community_blocks=8, noise=0.01)
    a, ra, ca = generators.planted_bcsr(9, **kw)
    b, rb, cb = generators.planted_bcsr(9, **kw)
    c, _, _ = generators.planted_bcsr(10, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.shape == (2, 1600, 16, 16)        # (5 x 8)^2 stored blocks


@pytest.mark.parametrize("name", ["dense_sweep", "sparse_sweep",
                                  "dense_grid4"])
def test_sweep_operand_selects_planted_k(name, cpu_peaks):
    """The program's sweep and the plain selection rule over the
    reference's members both pick the planted k, and the run is correct."""
    cell = tiny.tiny_cell(name)
    tr = bench.traffic_class(cell.kind)(cell.config, cell.params,
                                        2**31 + 21,
                                        jax.devices()[:cell.chips])
    tr.setup()
    tr.window(0.1)
    planted = cell.config["operand"]["planted_k"]
    assert set(tr.k_selected) == {planted}
    _, factors = tr.members_and_fits([cell.params["reference"]])
    assert tr.plain_selection(factors[cell.params["reference"]])[0] == {planted}
    assert all(c["ok"] for c in tr.check())
