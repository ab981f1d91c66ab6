"""Operands made from the seed.

Each is built on the device in one jitted call and is a pure function of
the seed and the sizes.  The arithmetic is copied from the program's own sound
generators, so that a later change there does not move the yardstick:

  planted_dense      io/virtual.virtual_dense_full at grid 1
                     (data/synthetic.gaussian_features bumps, exponential R,
                     uniform multiplicative noise)
  planted_bcsr       chip_smoke.planted_bcsr (support = the planted model's)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_seed(seed: int) -> int:
    """The seed's low 31 bits: what a PRNG key, or an int32 argument of a
    jitted call, can carry."""
    return int(seed) & 0x7FFFFFFF


def gaussian_features(key, n: int, k: int, *, width: float = 0.06,
                      floor: float = 0.01):
    """(n, k) non-negative Gaussian bumps along the entity axis."""
    kc, kw = jax.random.split(key)
    centers = (jnp.arange(k) + 0.5) / k + 0.1 / k * jax.random.normal(kc, (k,))
    widths = width * (0.5 + jax.random.uniform(kw, (k,)))
    t = jnp.linspace(0.0, 1.0, n)[:, None]
    return jnp.exp(-0.5 * ((t - centers[None, :]) / widths[None, :]) ** 2) \
        + floor


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "k", "noise", "sharding"))
def _planted_dense(seed, *, n, m, k, noise, sharding):
    ka, kr, _, kn = jax.random.split(jax.random.PRNGKey(seed), 4)
    A = gaussian_features(ka, n, k)
    R = jax.random.exponential(kr, (m, k, k), jnp.float32)
    X0 = jnp.einsum("ia,mab,jb->mij", A, R, A)
    if sharding is not None:
        X0 = jax.lax.with_sharding_constraint(X0, sharding)
    delta = jax.random.uniform(jax.random.fold_in(kn, 0), X0.shape,
                               jnp.float32, 1.0 - noise, 1.0 + noise)
    X = X0 * delta
    if sharding is not None:
        X = jax.lax.with_sharding_constraint(X, sharding)
    return X


def planted_dense(seed: int, *, n: int, m: int, k: int, noise: float,
                  sharding=None):
    """(m, n, n) f32: a rank-k non-negative RESCAL model times
    Uniform[1 - noise, 1 + noise] noise, built in its sharding."""
    return _planted_dense(key_seed(seed), n=n, m=m, k=k, noise=noise,
                          sharding=sharding)


def community_pattern(*, n: int, k: int, bs: int, community_blocks: int):
    """Block owners and the stored (row, col) block pairs: community a owns
    `community_blocks` contiguous block-rows, the rest own none; every
    pair of community blocks is stored, row-major."""
    nb = n // bs
    stride = nb // k
    owner = np.full(nb, k, np.int32)           # k = no community
    for a in range(k):
        owner[a * stride:a * stride + community_blocks] = a
    blocks = np.flatnonzero(owner < k).astype(np.int32)
    rows = np.repeat(blocks, blocks.size)
    cols = np.tile(blocks, blocks.size)
    return owner, rows, cols


@functools.partial(jax.jit,
                   static_argnames=("m", "k", "bs", "noise"))
def _planted_blocks(seed, owner_rows, rows, cols, *, m, k, bs, noise):
    ka, kr, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    member = jax.nn.one_hot(owner_rows, k)
    A = member * jax.random.uniform(ka, (owner_rows.shape[0], 1),
                                    jnp.float32, 0.5, 1.5)
    R = jax.random.exponential(kr, (m, k, k), jnp.float32)
    Ab = A.reshape(-1, bs, k)
    data = jnp.einsum("zak,mkl,zbl->mzab", Ab[rows], R, Ab[cols])
    return data * jax.random.uniform(kn, data.shape, jnp.float32,
                                     1.0 - noise, 1.0 + noise)


def planted_bcsr(seed: int, *, n: int, m: int, k: int, bs: int,
                 community_blocks: int, noise: float):
    """(data (m, nnzb, bs, bs), block_rows, block_cols) of a block-sparse
    tensor whose support is exactly that of a planted rank-k model."""
    owner, rows, cols = community_pattern(n=n, k=k, bs=bs,
                                          community_blocks=community_blocks)
    data = _planted_blocks(key_seed(seed), jnp.asarray(np.repeat(owner, bs)),
                           jnp.asarray(rows), jnp.asarray(cols),
                           m=m, k=k, bs=bs, noise=noise)
    return data, jnp.asarray(rows), jnp.asarray(cols)
