"""The MU-path and serve kernels compile for a TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but never meets the chip's
compiler, which refuses what the interpreter accepts: VMEM over the scoped
limit, unaligned tiles.  These tests AOT-compile each kernel for a
described (not attached) v5e at the widths ``chip_smoke.py`` runs, plain
and under ``jax.vmap`` (the batched ensemble calls them vmapped over its
members), and check the kernel is in the compiled program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest's workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparse import BCSR
from repro.kernels.bcsr_fused import bcsr_xa_xta
from repro.kernels.bcsr_spmm import bcsr_spmm
from repro.kernels.fused_bilinear import fused_xa_xtb
from repro.kernels.score_topk import score_topk

M, N, BS, NNZB, K = 20, 49152, 128, 1600, 6   # chip_smoke.py's sparse phase
N_SHARD = 3072            # one device's side of the 2x2-mesh dense operand
B, TOPK, K_SERVE = 32, 10, 5                   # chip_smoke.py's serve phase
MEMBERS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bcsr(data, rows, cols):
    return BCSR(data=data, block_rows=rows, block_cols=cols, n=N)


# name -> (fn, per-argument shapes/dtypes, vmap in_axes over members)
CASES = {
    "bcsr_xa_xta": (
        lambda d, r, c, b1, b2: bcsr_xa_xta(_bcsr(d, r, c), b1, b2),
        [((M, NNZB, BS, BS), jnp.float32), ((NNZB,), jnp.int32),
         ((NNZB,), jnp.int32), ((N, K), jnp.float32),
         ((N, K), jnp.float32)],
        (0, None, None, 0, 0)),
    "bcsr_spmm": (
        lambda d, r, c, b: bcsr_spmm(_bcsr(d, r, c), b),
        [((M, NNZB, BS, BS), jnp.float32), ((NNZB,), jnp.int32),
         ((NNZB,), jnp.int32), ((N, K), jnp.float32)],
        (0, None, None, 0)),
    "fused_xa_xtb": (
        fused_xa_xtb,
        [((M, N_SHARD, N_SHARD), jnp.float32), ((N_SHARD, K), jnp.float32),
         ((M, N_SHARD, K), jnp.float32)],
        (0, 0, 0)),
    "score_topk": (
        lambda v, a: score_topk(v, a, topk=TOPK),
        [((B, K_SERVE), jnp.float32), ((N, K_SERVE), jnp.float32)],
        (0, 0)),
}


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name, vmapped):
    fn, shapes, in_axes = CASES[name]
    args = []
    for (shape, dtype), axis in zip(shapes, in_axes):
        if vmapped and axis is not None:
            shape = (MEMBERS,) + shape
        args.append(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
    if vmapped:
        fn = jax.vmap(fn, in_axes=in_axes)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
