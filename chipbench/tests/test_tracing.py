"""The reduction from trace to metrics, on records with known answers and
on a small trace recorded on a TPU v5e (``data/``)."""
import glob
import json
import os

import numpy as np
import pytest

from chipbench import bench, roofline, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # ns


def record():
    """Two chips, a 100 ms window from t=0.  Chip 0: a unit program
    [10, 40) ms running ops [10, 30) and [20, 40) (overlapping), an
    all-reduce [40, 50) alone, and an op [60, 70) partly over the next
    all-reduce [65, 80).  Chip 1: one op [0, 50)."""
    return {
        "window": [0, 100 * MS],
        "devices": {
            "/device:TPU:0": {
                "ops": [["fusion.1", 10 * MS, 20 * MS],
                        ["fusion.2", 20 * MS, 20 * MS],
                        ["all-reduce.3", 40 * MS, 10 * MS],
                        ["fusion.1", 60 * MS, 10 * MS],
                        ["all-reduce-start.4", 65 * MS, 15 * MS],
                        ["fusion.9", -5 * MS, 8 * MS]],
                "modules": [["jit__batched_members(3)", 10 * MS, 30 * MS],
                            ["jit__other", 60 * MS, 20 * MS]]},
            "/device:TPU:1": {
                "ops": [["fusion.1", 0, 50 * MS]],
                "modules": [["jit__batched_members(3)", 0, 50 * MS]]}},
        "spans": [["sched/reduce", 80 * MS, 15 * MS],
                  ["sched/execute", 5 * MS, 40 * MS],
                  ["sched/reduce", 95 * MS, 10 * MS]]}


def test_union_and_subtract():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.length([(0, 3), (5, 9)]) == 7


def test_busy_and_idle_share():
    r = tracing.Reduced(record())
    # chip 0: [0,3) clipped + [10,50) + [60,80) = 63 ms; chip 1: 50 ms
    assert r.busy_intervals("/device:TPU:0") == [
        (0, 3 * MS), (10 * MS, 50 * MS), (60 * MS, 80 * MS)]
    assert r.busy_s() == pytest.approx((63 + 50) / 2 / 1e3)
    assert r.idle_share() == pytest.approx(1 - 0.0565 / 0.1)


def test_module_time_and_calls():
    r = tracing.Reduced(record())
    assert r.module_seconds(["jit__batched_members"]) == \
        pytest.approx((30 + 50) / 2 / 1e3)
    assert r.module_calls(["jit__batched_members"]) == 1.0
    assert r.module_seconds(["jit__other"]) == pytest.approx(20 / 2 / 1e3)
    assert r.module_seconds(["jit__nothing"]) == 0.0


def test_collective_exposed():
    r = tracing.Reduced(record())
    # chip 0: [40,50) alone, [70,80) alone of [65,80) -> 20 ms; chip 1: 0
    assert r.collective_exposed() == pytest.approx(20 / 100 / 2)
    one = record()
    del one["devices"]["/device:TPU:0"]
    assert tracing.Reduced(one).collective_exposed() is None


def test_spans_and_breakdown():
    r = tracing.Reduced(record())
    assert r.span_seconds("sched/reduce") == pytest.approx(0.015)
    b = r.breakdown()
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx((20 + 10 + 50) / 1e3)
    gaps = b["idle_gaps"]
    # chip 0's gaps: [3,10) under sched/execute, [50,60), [80,100)
    assert gaps[0] == ["sched/reduce", pytest.approx(0.020)]
    assert ["sched/execute", pytest.approx(0.007)] in gaps
    assert len(gaps) == 3


def test_metric_readers_on_the_record():
    r = tracing.Reduced(record())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = bench.MetricContext(r, {
        "sweeps": 2, "unit_programs": ["jit__batched_members"],
        "unit_iterations": 10, "least_unit_seconds": 0.004}, peaks)
    read = bench.metric_reader
    assert read("reduce_s")(ctx) == pytest.approx(0.015 / 2)
    assert read("mu_iter_ms")(ctx) == pytest.approx(1e3 * 0.040 / 10)
    assert read("mu_roofline")(ctx) == pytest.approx(100 * 0.004 / 0.040)
    assert read("idle_share.sweep")(ctx) == pytest.approx(43.5)
    assert read("collective_exposed")(ctx) == pytest.approx(10.0)
    empty = bench.MetricContext(r, {}, peaks)
    for name in ("reduce_s", "mu_iter_ms", "mu_roofline"):
        assert read(name)(empty) is None


def test_least_work_arithmetic():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # dense block (20, 6144, 6144) f32, k = 5, r = 2: one pass over X
    entries = 20 * 6144 ** 2
    flops, nbytes = roofline.mu_iteration_work(
        k=5, r=2, stored_entries=entries, operand_bytes=4 * entries)
    assert flops == 4 * entries * 5 * 2
    assert nbytes == 4 * entries
    t = roofline.least_seconds(flops, nbytes, peaks)
    assert t == pytest.approx(4 * entries / 819e9)        # bytes bound
    assert t == pytest.approx(3.687e-3, rel=1e-3)
    # per chip of four: a quarter of each
    f4, b4 = roofline.mu_iteration_work(k=5, r=2, stored_entries=entries,
                                        operand_bytes=4 * entries, chips=4)
    assert (f4, b4) == (flops / 4, nbytes / 4)


def test_peaks_table_knows_v5e_and_refuses_others():
    class Dev:
        device_kind = "TPU v5 lite"
    assert roofline.peaks_for(Dev)["hbm_bytes_per_s"] == 819e9
    Dev.device_kind = "cpu"
    with pytest.raises(KeyError):
        roofline.peaks_for(Dev)


def brute_busy_ns(ops, t0, t1, step=100):
    """Busy nanoseconds of a chip's ops in [t0, t1), on a timeline of
    `step`-ns bins: an independent check of the interval union."""
    bins = np.zeros(int((t1 - t0) // step) + 1, bool)
    for _, s, d in ops:
        a = max(int((float(s) - t0) // step), 0)
        b = min(int((float(s) + float(d) - t0) // step), bins.size)
        bins[a:b] = True
    return bins.sum() * step


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "*.json"))))
def test_recorded_tpu_trace(path):
    """A window cut from a traced run on a TPU v5e: only the chip's plane
    counts (the profiler's empty Megascale plane does not halve the busy
    time), busy time agrees with a brute-force timeline, and a program's
    device time is the sum of its clipped executions."""
    with open(path) as f:
        rec = json.load(f)["record"]
    r = tracing.Reduced(rec)
    assert list(r.devices) == ["/device:TPU:0"]
    assert len(rec["devices"]) > len(r.devices)
    t0, t1 = rec["window"]
    ops = rec["devices"]["/device:TPU:0"]["ops"]
    want = brute_busy_ns(ops, t0, t1) / 1e9
    assert r.busy_s() == pytest.approx(want, abs=2e-4 * r.window_s)
    assert r.idle_share() == pytest.approx(1 - want / r.window_s, abs=2e-4)
    assert 0 < r.busy_s() < r.window_s
    mods = rec["devices"]["/device:TPU:0"]["modules"]
    unit = sum(min(float(s) + float(d), t1) - max(float(s), t0)
               for n, s, d in mods if n.startswith("jit__batched_members"))
    assert unit > 0
    assert r.module_seconds(["jit__batched_members"]) == \
        pytest.approx(unit / 1e9, rel=1e-12)
    # gaps are labelled by what the host was doing; the reduction between
    # two units holds the most idle time
    gaps = r.breakdown()["idle_gaps"]
    labels = {n for n, _, _ in rec["spans"]} | {"no program span"}
    assert {g[0] for g in gaps} <= labels
    by = {}
    for n, v in gaps:
        by[n] = by.get(n, 0.0) + v
    assert max(by, key=by.get) == "sched/reduce"
