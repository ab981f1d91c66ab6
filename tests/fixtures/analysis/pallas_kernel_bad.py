"""True positive: the removed pl.load/pl.store API, a ref slice with
computed bounds, and a kernel that accumulates a VMEM-resident output
panel with no budget-gated dispatcher anywhere."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accum_kernel(x_ref, o_ref):
    j = pl.program_id(1)
    # removed API: pl.load/pl.store no longer exist
    v = pl.load(x_ref, (0, pl.ds(0, 128)))
    # computed slice bounds on a ref: fails at trace time
    o_ref[0, j * 128:(j + 1) * 128] += v


def accum(x):
    m, n = x.shape
    return pl.pallas_call(
        _accum_kernel,
        grid=(m, n // 128),
        in_specs=[pl.BlockSpec((1, 128), lambda i, j: (i, j))],
        # index_map ignores grid axis i -> the out panel stays resident
        out_specs=pl.BlockSpec((1, 128), lambda i, j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(x)
