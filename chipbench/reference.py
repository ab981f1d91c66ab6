"""The plain reference: RESCAL MU members and the selection of k, in
straightforward ``jax.numpy`` and ``numpy``, independent of the program.

It imports nothing of the program and takes nothing the program made.
It starts each member from the same draws as the program, derived from
the seed by its own copy of the sweep's key discipline (root key, fold in
k, split one key per member, split that into perturbation and factor
keys; RescalkConfig's documented defaults: noise half-width 0.02, factor
draws Uniform[0.05, 1]).

``precision`` and ``dtype`` select the arithmetic (``ARITHMETIC``, by
name): the reference runs in the precision the configuration states, the
control in the nearest one below it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.optimize

EPS = 1e-16
DELTA = 0.02           # RescalkConfig.perturbation_delta default
INIT_LO, INIT_HI = 0.05, 1.0
HIGHEST = jax.lax.Precision.HIGHEST

# name -> (matmul precision, dtype of operands and results)
ARITHMETIC = {
    "default": (None, "float32"),                    # f32, one bf16 pass
    "bf16": (None, "bfloat16"),
}


def arithmetic(name: str):
    prec, dtype = ARITHMETIC[name]
    return prec, jnp.dtype(dtype)


def member_keys(seed32: int, k: int, r: int):
    root = jax.random.PRNGKey(seed32)
    return jax.random.split(jax.random.fold_in(root, k), r)


def member_init(factor_key, n: int, m: int, k: int):
    """The random initial factors (A0, R0) of one member."""
    ka, kr = jax.random.split(factor_key)
    A0 = jax.random.uniform(ka, (n, k), jnp.float32, INIT_LO, INIT_HI)
    R0 = jax.random.uniform(kr, (m, k, k), jnp.float32, INIT_LO, INIT_HI)
    return A0, R0


def member_start(member_key, data_shape, n: int, m: int, k: int):
    """(noise, A0, R0) of one member: the multiplicative perturbation of
    the stored entries and the random initial factors."""
    pkey, fkey = jax.random.split(member_key)
    noise = jax.random.uniform(pkey, data_shape, jnp.float32,
                               1.0 - DELTA, 1.0 + DELTA)
    return (noise, *member_init(fkey, n, m, k))


def perturb_on_mesh(X, perturb_key, q, mesh):
    """The perturbation of a dense X laid out (None, rows, cols) on a 2D
    mesh (axis names in that order): each chip's block draws its own
    noise from the key with the member id `q` and the block's linear grid
    index folded in, row-major over the grid."""
    from jax.sharding import PartitionSpec as P
    row, col = mesh.axis_names
    gc = mesh.shape[col]

    def local(Xl):
        lin = jax.lax.axis_index(row) * gc + jax.lax.axis_index(col)
        key = jax.random.fold_in(jax.random.fold_in(perturb_key, q), lin)
        return Xl * jax.random.uniform(key, Xl.shape, jnp.float32,
                                       1.0 - DELTA, 1.0 + DELTA)

    spec = P(None, row, col)
    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(X)


def _es(precision, dtype):
    """einsum in the arithmetic of `dtype`: its operands, its results."""
    def es(spec, *ops):
        return jnp.einsum(spec, *ops, precision=precision).astype(dtype)
    return es


def _mu_update(xa, xta, A, R, es):
    """One MU iteration given the X-sided products (paper Eq. 2)."""
    G = es("ia,ib->ab", A, A)
    ATXA = es("ia,mib->mab", A, xa)
    R = R * ATXA / (es("ab,mbc,cd->mad", G, R, G) + EPS)
    num = es("mia,msa->is", xa, R) + es("mia,mas->is", xta, R)
    S = es("mab,bc,mdc->ad", R, G, R) + es("mba,bc,mcd->ad", R, G, R)
    A = A * num / (es("ia,ab->ib", A, S) + EPS)
    return A, R


def _normalize(A, R):
    A, R = A.astype(jnp.float32), R.astype(jnp.float32)
    c = jnp.maximum(jnp.linalg.norm(A, axis=0), 1e-12)
    return A / c, jnp.einsum("a,mab,b->mab", c, R, c, precision=HIGHEST)


def _dense_member(X, member_key, q, *, k: int, iters: int, precision,
                  dtype, mesh):
    m, n, _ = X.shape
    if mesh is None:
        noise, A, R = member_start(member_key, X.shape, n, m, k)
        Xq = (X * noise).astype(dtype)
    else:
        pkey, fkey = jax.random.split(member_key)
        Xq = perturb_on_mesh(X, pkey, q, mesh).astype(dtype)
        A, R = member_init(fkey, n, m, k)
    es = _es(precision, dtype)

    def body(_, c):
        A, R = c
        xa = es("mij,jk->mik", Xq, A)
        xta = es("mji,jk->mik", Xq, A)
        return _mu_update(xa, xta, A, R, es)

    A, R = jax.lax.fori_loop(0, iters, body, (A.astype(dtype),
                                               R.astype(dtype)))
    return _normalize(A, R)


@functools.partial(jax.jit, static_argnames=("k", "iters", "precision",
                                             "dtype", "mesh"))
def dense_member(X, member_key, q, *, k: int, iters: int, precision, dtype,
                 mesh):
    """One member on a dense (m, n, n) X laid out on `mesh`: perturbed
    block by block (``perturb_on_mesh``) as member `q`, initialized, `iters`
    MU iterations, normalized; the products run where XLA places them.
    Returns (A, R) in f32."""
    return _dense_member(X, member_key, q, k=k, iters=iters,
                         precision=precision, dtype=dtype, mesh=mesh)


@functools.partial(jax.jit, static_argnames=("k", "iters", "precision",
                                             "dtype"))
def dense_members(X, member_keys, *, k: int, iters: int, precision, dtype):
    """The members of one k on a dense (m, n, n) X on one device, one per
    key, together (vmapped): each perturbed, initialized, `iters` MU
    iterations, normalized.  Returns (A (r, n, k), R (r, m, k, k))."""
    return jax.vmap(lambda key: _dense_member(
        X, key, 0, k=k, iters=iters, precision=precision, dtype=dtype,
        mesh=None))(member_keys)


def _bcsr_products(data, rows, cols, A, nb: int, es):
    """X_t A and X_t^T A for all t from the stored blocks: one product
    per block, summed into its block-row (block-column) with a
    segment sum."""
    bs = data.shape[-1]
    Ab = A.reshape(nb, bs, -1)
    xa = es("mzab,zbk->mzak", data, Ab[cols])
    xta = es("mzab,zak->mzbk", data, Ab[rows])
    xa = jax.ops.segment_sum(xa.swapaxes(0, 1), rows, num_segments=nb)
    xta = jax.ops.segment_sum(xta.swapaxes(0, 1), cols, num_segments=nb)
    m = data.shape[0]
    return (xa.transpose(1, 0, 2, 3).reshape(m, nb * bs, -1),
            xta.transpose(1, 0, 2, 3).reshape(m, nb * bs, -1))


def _bcsr_member(data, rows, cols, member_key, *, n: int, k: int,
                 iters: int, precision, dtype):
    m, bs = data.shape[0], data.shape[-1]
    nb = n // bs
    noise, A, R = member_start(member_key, data.shape, n, m, k)
    dq = (data * noise).astype(dtype)
    es = _es(precision, dtype)

    def body(_, c):
        A, R = c
        xa, xta = _bcsr_products(dq, rows, cols, A, nb, es)
        return _mu_update(xa, xta, A, R, es)

    A, R = jax.lax.fori_loop(0, iters, body, (A.astype(dtype),
                                               R.astype(dtype)))
    return _normalize(A, R)


@functools.partial(jax.jit, static_argnames=("n", "k", "iters", "precision",
                                             "dtype"))
def bcsr_members(data, rows, cols, member_keys, *, n: int, k: int,
                 iters: int, precision, dtype):
    """The members of one k on a block-sparse tensor, one per key,
    together (vmapped): only the stored blocks are perturbed; n is a
    multiple of the block size.  Returns (A (r, n, k), R (r, m, k, k))."""
    return jax.vmap(lambda key: _bcsr_member(
        data, rows, cols, key, n=n, k=k, iters=iters, precision=precision,
        dtype=dtype))(member_keys)


@jax.jit
def dense_residual(X, A, R):
    """||X - A R A^T|| / ||X|| from the residual itself, one relation
    slice at a time, at f32 ``highest``."""
    def body(t, acc):
        Xt = jax.lax.dynamic_index_in_dim(X, t, keepdims=False)
        Rt = jax.lax.dynamic_index_in_dim(R, t, keepdims=False)
        rec = jnp.einsum("ia,ab,jb->ij", A, Rt, A, precision=HIGHEST)
        return acc + jnp.stack([jnp.sum((Xt - rec) ** 2), jnp.sum(Xt ** 2)])

    e2, x2 = jax.lax.fori_loop(0, X.shape[0], body, jnp.zeros(2))
    return jnp.sqrt(e2 / x2)


@functools.partial(jax.jit, static_argnames=("n",))
def bcsr_residual(data, rows, cols, A, R, *, n: int):
    """||X - A R A^T|| / ||X|| on a block-sparse X: the residual over the
    stored blocks, plus the model's mass outside them (the whole model's
    squared norm, sum_t <G R_t G, R_t>, less its stored part)."""
    bs = data.shape[-1]
    Ab = A.reshape(n // bs, bs, -1)
    rec = jnp.einsum("zak,mkl,zbl->mzab", Ab[rows], R, Ab[cols],
                     precision=HIGHEST)
    stored = jnp.sum((data - rec) ** 2)
    G = jnp.einsum("ia,ib->ab", A, A, precision=HIGHEST)
    fit_all = jnp.einsum("ab,mbc,cd,mad->", G, R, G, R, precision=HIGHEST)
    outside = jnp.maximum(fit_all - jnp.sum(rec ** 2), 0.0)
    return jnp.sqrt((stored + outside) / jnp.sum(data ** 2))


# -- the selection of k (paper Alg. 5, Alg. 6, §3.3), in numpy ------------

SIL_THRESHOLD = 0.75   # RescalkConfig.sil_threshold default
REGRESS_ITERS = 100    # RescalkConfig.regress_iters default
REGRESS_SEED = 17      # the R regression's fixed initial draw


def _unit_columns(A):
    return A / (np.linalg.norm(A, axis=-2, keepdims=True) + 1e-12)


def align(A_ens, max_passes: int = 50):
    """Give every member of an ensemble (r, n, k) the column order that
    most resembles the others: from member 0 as the medians, each pass
    orders every member's columns by the assignment of largest summed
    cosine similarity to the medians, then takes the elementwise median
    over members, until no order changes.  Returns (aligned, median)."""
    A = np.array(A_ens, np.float64)
    r, _, k = A.shape
    M = A[0]
    for _ in range(max_passes):
        sim = np.einsum("na,qnb->qab", _unit_columns(M), _unit_columns(A))
        changed = False
        for q in range(r):
            _, order = scipy.optimize.linear_sum_assignment(sim[q],
                                                            maximize=True)
            changed |= bool(np.any(order != np.arange(k)))
            A[q] = A[q][:, order]
        M = np.median(A, axis=0)
        if not changed:
            break
    return A, M


def silhouette_min(A_aligned) -> float:
    """The least silhouette width over all points of the aligned ensemble,
    a cluster being one column of each member, under the cosine distance.
    1 for a single member."""
    r, _, k = A_aligned.shape
    if r == 1:
        return 1.0
    U = _unit_columns(np.asarray(A_aligned, np.float64))
    dist = 1.0 - np.einsum("qna,pnb->qapb", U, U)
    widths = []
    for q in range(r):
        for a in range(k):
            own = np.mean([dist[q, a, p, a] for p in range(r) if p != q])
            other = min(np.mean(dist[q, a, :, b]) for b in range(k) if b != a)
            widths.append((other - own) / max(own, other, 1e-12))
    return float(min(widths))


def select_k(ks, s_min, fit, threshold: float = SIL_THRESHOLD) -> int:
    """The largest k whose least silhouette clears `threshold`; where none
    does, the k of the largest s_min - fit(k), `fit(k)` being the relative
    error at k of the model regressed on the median."""
    stable = [k for k, s in zip(ks, s_min) if s >= threshold]
    if stable:
        return max(stable)
    score = [s - fit(k) for k, s in zip(ks, s_min)]
    return ks[int(np.argmax(score))]


def select_ks(ks, s_min, fit, band: float) -> set:
    """Every k that ``select_k`` picks at some threshold within `band` of
    ``SIL_THRESHOLD``: the ks that a least silhouette off by up to `band`
    could make the rule pick."""
    lo, hi = SIL_THRESHOLD - band, SIL_THRESHOLD + band
    cuts = [lo, hi] + [s for s in s_min if lo <= s <= hi]
    return {select_k(ks, s_min, fit, t) for t in cuts}


def regress(ATXA, A):
    """R (m, k, k) >= 0 for a fixed A by ``REGRESS_ITERS`` MU steps on R
    alone, from the given A^T X_t A, at f32 ``highest``."""
    m, k = ATXA.shape[0], A.shape[1]
    R = jax.random.uniform(jax.random.PRNGKey(REGRESS_SEED), (m, k, k),
                           jnp.float32, INIT_LO, INIT_HI)
    G = jnp.einsum("ia,ib->ab", A, A, precision=HIGHEST)
    for _ in range(REGRESS_ITERS):
        R = R * ATXA / (jnp.einsum("ab,mbc,cd->mad", G, R, G,
                                   precision=HIGHEST) + EPS)
    return R


@jax.jit
def dense_ATXA(X, A):
    return jnp.einsum("ia,mij,jb->mab", A, X, A, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("n",))
def bcsr_ATXA(data, rows, cols, A, *, n: int):
    bs = data.shape[-1]
    Ab = A.reshape(n // bs, bs, -1)
    return jnp.einsum("zak,mzab,zbl->mkl", Ab[rows], data, Ab[cols],
                      precision=HIGHEST)
