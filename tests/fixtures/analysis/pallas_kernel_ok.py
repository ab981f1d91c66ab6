"""Near miss: the same kernel shape done right — refs read and written by
indexing, dynamic windows through pl.ds (static slices and scalar indices
stay plain), every grid axis used by the out index_map, and compiler
params passed."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(x_ref, o_ref):
    j = pl.program_id(1)
    v = x_ref[0, pl.ds(j * 128, 128)]
    o_ref[0, 0:128] = v
    o_ref[:, :] += x_ref[...]


def copy(x):
    m, n = x.shape
    return pl.pallas_call(
        _copy_kernel,
        grid=(m, n // 128),
        in_specs=[pl.BlockSpec((1, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(x)
