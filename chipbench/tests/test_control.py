"""The control: the reference, in the nearest precision below the one the
configuration states (bfloat16 for the sweeps' float32 at XLA's default
precision), put in the program's place, comes out not correct; the
program at the same size comes out correct.  At a size a test run holds,
on the CPU; the chip's readings, at the cells' own sizes, are in
PERF.md."""
import time

import jax
import pytest

import tiny
from chipbench import bench


def run(name, control, seed, **params):
    cell = tiny.tiny_cell(name, **params)
    return bench.run_cell(cell, seed, 1.0, False,
                          t_start=time.perf_counter(),
                          devices=jax.devices()[:cell.chips],
                          control=control)


@pytest.mark.parametrize("name,check", [("dense_sweep", "fit_gap"),
                                        ("sparse_sweep", "fit_gap"),
                                        ("dense_grid4", "fit_gap")])
def test_control_fails_where_program_passes(name, check, cpu_peaks):
    seed = 2**31 + 101
    prog = run(name, False, seed)
    assert prog["correct"] is True
    ctrl = run(name, True, seed)
    assert ctrl["correct"] is False
    c = ctrl["checks"][check]
    assert c["value"] > c["limit"]
