"""The plain selection rule of ``reference.py`` against the program's own
clustering and silhouettes, on random ensembles (CPU), and the band of
thresholds that ``select_ks`` allows."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference


@pytest.mark.parametrize("r,k,seed", [(2, 4, 0), (2, 7, 1), (3, 5, 2),
                                      (5, 3, 3)])
def test_plain_rule_matches_the_program_on_random_ensembles(r, k, seed):
    from repro.core.clustering import custom_cluster
    from repro.core.silhouette import silhouettes
    rng = np.random.default_rng(seed)
    base = rng.uniform(0.0, 1.0, (60, k))
    # members: one loading matrix, its columns shuffled, plus noise
    A = np.stack([base[:, rng.permutation(k)]
                  + 0.3 * rng.uniform(0.0, 1.0, (60, k)) for _ in range(r)])
    R = np.ones((r, 2, k, k), np.float32)
    clus = custom_cluster(jnp.asarray(A, jnp.float32), jnp.asarray(R))
    want = float(silhouettes(clus.A_aligned).s_min)
    aligned, median = reference.align(A)
    assert reference.silhouette_min(aligned) == pytest.approx(want,
                                                              abs=1e-4)
    np.testing.assert_allclose(median, np.asarray(clus.A_median),
                               rtol=1e-5, atol=1e-6)


def test_select_ks_takes_every_threshold_within_the_band():
    def no_fit(k):
        raise AssertionError("a stable k exists: no fallback")

    ks, s = [2, 3, 4], [0.99, 0.76, 0.2]
    assert reference.select_k(ks, s, no_fit) == 3
    assert reference.select_ks(ks, s, no_fit, 0.0) == {3}
    assert reference.select_ks(ks, s, no_fit, 0.05) == {2, 3}
    assert reference.select_ks(ks, [0.99, 0.9, 0.2], no_fit, 0.05) == {3}
    # nothing clears the bar: the best s_min - fit
    assert reference.select_k(ks, [0.5, 0.6, 0.1],
                              lambda k: {2: 0.3, 3: 0.1, 4: 0.0}[k]) == 3
