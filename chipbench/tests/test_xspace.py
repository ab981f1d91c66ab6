"""The second reading of the traced window (``xspace``): host spans on the
profiler's clock, op self time and scope paths, the idle gaps split among
the spans covering them, and the readers of the metrics built on them; on
records with known answers, on a window cut from a traced chip run
(``data/xspace/``), and through a traced run of a small cell on the CPU."""
import glob
import json
import os

import pytest

from chipbench import bench, tracing, xspace
from test_tracing import record as old_record

import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "xspace")
MS = 1_000_000  # ns
BODY = "jit(_batched_members)/vmap(while)/body/closed_call/mu"


def record():
    """A 100 ms window from t=0 on two chips.  Chip 0: a unit program's
    while [10, 60) around two product fusions [12, 30) and [30, 40), a
    factor fusion [40, 50) and an unscoped copy [50, 52), then a
    reduction op [70, 80).  Chip 1: a product fusion [0, 40) and a factor
    fusion [40, 50).  Host: a unit's execute [5, 62) (dispatch [5, 8),
    wait [8, 62)), its watermark [62, 64), then a reduction [64, 95)
    (cluster [64, 70) holding one compile instant at 66, regress
    [70, 90))."""
    prod, fac = f"{BODY}/products/dot_general", f"{BODY}/mul"
    return {
        "window": [0, 100 * MS],
        "host_spans": [["sched/execute", 5 * MS, 57 * MS],
                       ["sched/dispatch", 5 * MS, 3 * MS],
                       ["sched/wait", 8 * MS, 54 * MS],
                       ["sched/watermark", 62 * MS, 2 * MS],
                       ["sched/reduce", 64 * MS, 31 * MS],
                       ["reduce/cluster", 64 * MS, 6 * MS],
                       ["xla/compile", 66 * MS, 1000],
                       ["reduce/regress", 70 * MS, 20 * MS]],
        "devices": {
            "/device:TPU:0": {
                "ops": [["while.1", 10 * MS, 50 * MS,
                         "jit(_batched_members)/vmap(while)"],
                        ["fusion.1", 12 * MS, 18 * MS, prod],
                        ["fusion.2", 30 * MS, 10 * MS, prod],
                        ["fusion.3", 40 * MS, 10 * MS, fac],
                        ["copy.4", 50 * MS, 2 * MS, ""],
                        ["fusion.9", 70 * MS, 10 * MS,
                         "jit(_similarity)/dot_general"]],
                "modules": [["jit__batched_members(3)", 10 * MS, 50 * MS]]},
            "/device:TPU:1": {
                "ops": [["fusion.1", 0, 40 * MS, prod],
                        ["fusion.5", 40 * MS, 10 * MS, f"{BODY}/add"]],
                "modules": [["jit__batched_members(3)", 0, 50 * MS]]},
            "/host:CPU": {"ops": [], "modules": []}}}


def test_scope_parts_strip_transform_wrappers():
    assert xspace.scope_parts("jit(f)/vmap(jit(g))/while/body/mu/"
                              "products/ij,jk->ik/dot_general") == {
        "f", "g", "while", "body", "mu", "products", "ij,jk->ik",
        "dot_general"}
    assert "mu" in xspace.scope_parts("transpose(jvp(mu))/mul")
    assert xspace.instruction(
        "%fusion.287 = bf16[2,20]{1,0} fusion(bf16[2] %p), kind=kOutput"
    ) == "fusion.287"


def test_ops_take_their_scope_from_their_programs_hlo():
    """An op event is named by its HLO text only; its scope is the op_name
    of that instruction in the program whose execution holds it."""
    names = {"jit_a(1)": {"fusion.1": "jit(a)/mu/products/dot_general"},
             "jit_b(2)": {"fusion.1": "jit(b)/dot_general"}}
    modules = [["jit_a(1)", 0, 10], ["jit_b(2)", 20, 10]]
    ops = [["%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p)", 2, 3],
           ["%fusion.1 = f32[4]{0} fusion(f32[4]{0} %q)", 21, 3],
           ["%copy.2 = f32[4]{0} copy(f32[4]{0} %q)", 25, 1],
           ["%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p)", 15, 1]]
    assert xspace._scoped(ops, modules, names) == [
        ["fusion.1", 2, 3, "jit(a)/mu/products/dot_general"],
        ["fusion.1", 15, 1, ""],            # between programs
        ["fusion.1", 21, 3, "jit(b)/dot_general"],
        ["copy.2", 25, 1, ""]]              # not in the program's HLO


def test_program_op_names_from_a_cpu_profile(tmp_path):
    """The profiler keeps each program's HLO proto on its metadata plane;
    the op_names read from it hold the MU step's scopes."""
    import jax
    import jax.numpy as jnp

    def body(_, a):
        with jax.named_scope("mu"):
            with jax.named_scope("products"):
                p = jnp.ones((8, 8)) @ a
            return a * p / (a + 1.0)

    step = jax.jit(lambda a: jax.lax.fori_loop(0, 2, body, a))
    a = jnp.ones((8, 2))
    step(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(a).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        names = xspace.program_op_names(f.read())
    prog = next(n for n in names if n.startswith("jit__lambda"))
    parts = [xspace.scope_parts(op) for op in names[prog].values()]
    assert any({"mu", "products"} <= p for p in parts)
    assert any("mu" in p and "products" not in p for p in parts)


def test_self_time_takes_a_while_off_its_body():
    w = xspace.Window(record())
    own = {n: ns for n, _, ns in w.self_times("/device:TPU:0")}
    assert own["while.1"] == 10 * MS       # 50 less 18 + 10 + 10 + 2
    assert own["fusion.1"] == 18 * MS
    # nothing counted twice: self times add up to the busy time
    assert sum(own.values()) == tracing.length(
        tracing.union([(10 * MS, 60 * MS), (70 * MS, 80 * MS)]))


def test_scoped_seconds():
    w = xspace.Window(record())
    # products: chip 0 18 + 10, chip 1 40 -> 34 ms a chip
    assert w.scoped_seconds(within=("mu", "products")) == \
        pytest.approx(0.034)
    # factor algebra: chip 0 10, chip 1 10
    assert w.scoped_seconds(within=("mu",), outside=("products",)) == \
        pytest.approx(0.010)
    assert w.scoped_seconds(within=("nothing",)) is None


def test_breakdown_splits_gaps_among_their_spans():
    b = xspace.Window(record()).breakdown()
    ops = dict(b["device_ops"])
    assert ops[f"fusion.1 [{BODY}/products/dot_general]"] == \
        pytest.approx(0.058)
    assert ops["while.1 [jit(_batched_members)/vmap(while)]"] == \
        pytest.approx(0.010)
    # chip 0 idles in [0, 10), [60, 70), [80, 100)
    gaps = b["idle_gaps"]
    assert [g[0] for g in gaps] == pytest.approx([0.020, 0.010, 0.010])
    assert gaps[0][1] == [["reduce/regress", pytest.approx(0.010)],
                          ["sched/reduce", pytest.approx(0.005)],
                          [xspace.NO_SPAN, pytest.approx(0.005)]]
    first = next(g for g in gaps if dict(g[1]).get("sched/dispatch"))
    assert dict(first[1]) == pytest.approx({
        xspace.NO_SPAN: 0.005, "sched/dispatch": 0.003, "sched/wait": 0.002})
    assert b["idle_by_span"] == pytest.approx({
        xspace.NO_SPAN: 0.010, "reduce/regress": 0.010,
        "reduce/cluster": 0.005999, "sched/reduce": 0.005,
        "sched/wait": 0.004, "sched/dispatch": 0.003,
        "sched/watermark": 0.002, "xla/compile": 0.000001})


def test_records_without_the_new_keys_still_reduce():
    w = xspace.Window(old_record())
    assert w.host_spans == [] and w.spans("sched/reduce") == []
    assert w.scoped_seconds(within=("mu",)) is None
    b = w.breakdown()
    assert {n for g in b["idle_gaps"] for n, _ in g[1]} == {xspace.NO_SPAN}
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(0.080)


def test_cut_keeps_what_overlaps():
    c = xspace.cut(record(), 55 * MS, 75 * MS)
    assert c["window"] == [55 * MS, 75 * MS]
    assert [s[0] for s in c["host_spans"]] == [
        "sched/execute", "sched/wait", "sched/watermark", "sched/reduce",
        "reduce/cluster", "xla/compile", "reduce/regress"]
    assert [o[0] for o in c["devices"]["/device:TPU:0"]["ops"]] == [
        "while.1", "fusion.9"]


def ctx_for(rec, monkeypatch, counters):
    monkeypatch.setattr(xspace, "window_record", lambda ctx: rec)
    return bench.MetricContext(None, counters, tiny.CPU_PEAKS)


def test_readers_on_the_record(monkeypatch):
    ctx = ctx_for(record(), monkeypatch,
                  {"sweeps": 2, "unit_iterations": 10})
    read = bench.metric_reader
    assert read("mu_products_ms")(ctx) == pytest.approx(3.4)
    assert read("mu_factor_ms")(ctx) == pytest.approx(1.0)
    assert read("regress_s")(ctx) == pytest.approx(0.010)
    assert read("dispatch_ms")(ctx) == pytest.approx(3.0)
    assert read("window_programs")(ctx) == pytest.approx(0.5)


def test_readers_without_the_program_instrumentation(monkeypatch):
    """A program with no host spans and no MU scopes (the parent of this
    instrumentation) gives nothing, and no error."""
    rec = old_record()
    for dev in rec["devices"].values():
        dev["ops"] = [op + ["jit(f)/while/body/dot_general"]
                      for op in dev["ops"]]
    ctx = ctx_for(rec, monkeypatch, {"sweeps": 2, "unit_iterations": 10})
    for name in ("mu_products_ms", "mu_factor_ms", "regress_s",
                 "dispatch_ms", "window_programs"):
        assert bench.metric_reader(name)(ctx) is None
    ctx = ctx_for(None, monkeypatch, {"sweeps": 2, "unit_iterations": 10})
    for name in ("mu_products_ms", "regress_s", "window_programs"):
        assert bench.metric_reader(name)(ctx) is None


def test_window_programs_reads_zero(monkeypatch):
    rec = record()
    rec["host_spans"] = [s for s in rec["host_spans"]
                         if s[0] != "xla/compile"]
    ctx = ctx_for(rec, monkeypatch, {"sweeps": 2})
    assert bench.metric_reader("window_programs")(ctx) == 0.0


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA,
                                                                "*.json"))))
def test_recorded_tpu_window(path):
    """A cut of a traced chip run: every host span lies inside the traced
    window (one clock); the MU step's scoped ops hold no more than the
    unit programs' device time; the while is off its body; and no long
    idle gap lacks a program span."""
    with open(path) as f:
        doc = json.load(f)
    rec, (t0, t1) = doc["record"], doc["window"]
    assert rec["host_spans"]
    for name, s, d in rec["host_spans"]:
        assert t0 <= s and s + d <= t1, name
    w = xspace.Window(rec)
    prods = w.scoped_seconds(within=("mu", "products"))
    factor = w.scoped_seconds(within=("mu",), outside=("products",))
    assert prods > 0 and factor > 0
    units = tracing.Reduced(
        {"window": rec["window"],
         "devices": {d: {"ops": [o[:3] for o in v["ops"]],
                         "modules": v["modules"]}
                     for d, v in rec["devices"].items()}})
    assert prods + factor <= units.module_seconds(doc["unit_programs"])
    dev = sorted(w.devices)[0]
    assert sum(ns for *_, ns in w.self_times(dev)) == pytest.approx(
        units.busy_s() * 1e9, rel=1e-3)
    for length, pieces in w.breakdown()["idle_gaps"]:
        assert dict(pieces).get(xspace.NO_SPAN, 0.0) < length


def test_traced_cell_on_the_cpu(cpu_peaks):
    """A traced run of the small dense cell: the readers find the window's
    profile again, and the program's spans and compile count on it."""
    import jax
    cell = tiny.tiny_cell("dense_sweep")
    res = bench.run_cell(cell, 2147500001, 1.0, True, t_start=0.0,
                         devices=jax.devices()[:1])
    got = res["metrics"]
    assert got["regress_s"]["value"] > 0
    assert got["dispatch_ms"]["value"] > 0
    assert got["window_programs"]["value"] >= 0
    # the CPU has no chip plane: nothing device-side is read there
    assert "mu_products_ms" not in got and "mu_factor_ms" not in got
    assert xspace.find_profile(-1.0, -2.0) is None
