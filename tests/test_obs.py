"""repro.obs: tracer spans/export, the metrics channel, the zero-cost-off
contract (jaxpr identity + no extra compiles), compile-event capture, the
scheduler/straggler wiring, cost accounting, and the train-loop log fix.
"""
import contextlib
import dataclasses
import functools
import json
import logging
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse as spmod
from repro.core.rescal import (init_factors, masked_mu_step,
                               mu_step_batched, mu_step_sliced, rescal)
from repro.core.sparse import masked_sparse_mu_step, sparse_mu_step
from repro.data.synthetic import synthetic_rescal
from repro.dist.compat import (capture_compiles, device_memory_stats,
                               program_memory)
from repro.obs import costs as obs_costs
from repro.obs import memory as obs_memory
from repro.obs import trace as obs
from repro.obs.metrics import (MetricsBuffer, install_buffer,
                               record_metrics, update_ratio)
from repro.selection import (RescalkConfig, SweepScheduler, run_ensemble)
from repro.selection.scheduler import plan_sweep
from repro.selection.report import SelectionReport, UnitRecord

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def buffer():
    """A fresh installed MetricsBuffer, restored after the test."""
    buf = MetricsBuffer()
    prev = install_buffer(buf)
    yield buf
    install_buffer(prev)


# ---------------------------------------------------------------------------
# Tracer: spans, events, JSONL, Chrome export
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_records_begin_end_with_outcome(self):
        t = obs.Tracer()
        with t.span("sched/execute", uid="u1"):
            with t.span("inner"):
                pass
        phs = [(e["ph"], e["name"]) for e in t.events]
        assert phs == [("M", "trace_start"), ("B", "sched/execute"),
                       ("B", "inner"), ("E", "inner"),
                       ("E", "sched/execute")]
        end = t.events[-1]
        assert end["args"] == {"uid": "u1", "outcome": "ok"}
        assert end["dur"] >= 0

    def test_span_marks_error_outcome_and_reraises(self):
        t = obs.Tracer()
        with pytest.raises(ValueError):
            with t.span("sched/execute"):
                raise ValueError("boom")
        assert t.events[-1]["args"]["outcome"] == "error"

    def test_jsonl_flushed_incrementally(self, tmp_path):
        t = obs.Tracer(str(tmp_path))
        with t.span("a"):
            pass
        # readable BEFORE close: a killed run still leaves a trace
        lines = (tmp_path / "trace.jsonl").read_text().splitlines()
        assert [json.loads(ln)["ph"] for ln in lines] == ["M", "B", "E"]
        t.close()

    def test_chrome_export_renders_all_phases(self, tmp_path):
        t = obs.Tracer()
        with t.span("sched/execute", uid="u0"):
            t.event("sched/retry", attempt=1)
        out = tmp_path / "chrome.json"
        t.export_chrome(str(out))
        doc = json.loads(out.read_text())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert evs[0] == {"ph": "M", "name": "process_name",
                          "pid": t.events[0]["pid"], "tid": 0,
                          "args": {"name": "rescalk"}}
        by_ph = {e["ph"] for e in evs}
        assert {"B", "E", "i"} <= by_ph
        inst = next(e for e in evs if e["ph"] == "i")
        assert inst["s"] == "t" and inst["cat"] == "sched"

    def test_summarize_counts_spans_and_compiles(self):
        t = obs.Tracer()
        with t.span("ingest/tsv"):
            pass
        t.compile_event("_batched_members", "compile")
        s = t.summarize()
        assert "ingest/tsv" in s and "compile events: 1" in s


class TestModuleChannel:
    def test_span_is_noop_without_tracer(self):
        assert obs.current() is None
        ctx = obs.span("anything", uid=1)
        with ctx:
            pass
        obs.event("anything")          # must not raise

    def test_tracing_scopes_install_and_restore(self):
        assert obs.current() is None
        with obs.tracing() as t:
            assert obs.current() is t
            with obs.span("x"):
                pass
        assert obs.current() is None
        assert any(e["name"] == "x" for e in t.events)

    def test_timed_measures_with_and_without_tracer(self):
        with obs.timed("bench/call") as sw:
            pass
        assert sw.seconds >= 0
        with obs.tracing() as t:
            with obs.timed("bench/call", rep=0) as sw:
                pass
            assert sw.seconds >= 0
        assert [e["name"] for e in t.events if e["ph"] == "B"] \
            == ["bench/call"]


# ---------------------------------------------------------------------------
# One clock: tracer spans and instants on the profiler's host plane
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """name -> [(start_ns, end_ns), ...] of a profile's host-plane events."""
    from jax.profiler import ProfileData
    path, = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _profiled(trace_dir, traced: bool):
    """A span and an instant inside a window annotation, under a profiler
    capture, with or without an installed tracer."""
    with obs.tracing() if traced else contextlib.nullcontext():
        jax.profiler.start_trace(str(trace_dir))
        try:
            with jax.profiler.TraceAnnotation("test/window"):
                with obs.span("sched/dispatch", uid="u0"):
                    jnp.ones(3).block_until_ready()
                obs.event("xla/compile", program="p", kind="compile")
        finally:
            jax.profiler.stop_trace()
    return _host_events(trace_dir)


class TestProfilerClock:
    def test_span_and_instant_land_inside_the_window(self, tmp_path):
        host = _profiled(tmp_path, traced=True)
        (w0, w1), = host["test/window"]
        for name in ("sched/dispatch", "xla/compile"):
            (s, e), = host[name]
            assert w0 <= s <= e <= w1

    def test_untraced_span_stays_off_the_profile(self, tmp_path):
        host = _profiled(tmp_path, traced=False)
        assert "test/window" in host
        assert "sched/dispatch" not in host and "xla/compile" not in host


# ---------------------------------------------------------------------------
# Metrics buffer + jitted record_metrics
# ---------------------------------------------------------------------------

class TestMetricsBuffer:
    def test_trajectory_and_npz_layout(self, tmp_path):
        buf = MetricsBuffer()
        for i in range(3):
            buf.append("t.a", {"v": float(i), "w": np.ones(2) * i})
        np.testing.assert_allclose(buf.trajectory("t.a", "v"), [0, 1, 2])
        assert buf.trajectory("t.a", "w").shape == (3, 2)
        assert buf.trajectory("missing", "v").size == 0
        buf.save_npz(str(tmp_path / "m.npz"))
        with np.load(tmp_path / "m.npz") as d:
            assert sorted(d.files) == ["t.a.v", "t.a.w"]

    def test_ring_buffer_drops_oldest(self):
        buf = MetricsBuffer(capacity=3)
        for i in range(5):
            buf.append("t", {"v": float(i)})
        assert len(buf) == 3 and buf.dropped == 2
        np.testing.assert_allclose(buf.trajectory("t", "v"), [2, 3, 4])

    def test_callback_resolves_buffer_at_host_call_time(self, buffer):
        @functools.partial(jax.jit, static_argnames="tm")
        def g(x, tm=False):
            if tm:
                record_metrics("test.g", total=x.sum())
            return x + 1

        install_buffer(None)               # compile with NO buffer installed
        g(jnp.ones(3), tm=True).block_until_ready()
        jax.effects_barrier()
        install_buffer(buffer)             # same compiled program, new buffer
        g(jnp.ones(3), tm=True).block_until_ready()
        jax.effects_barrier()
        np.testing.assert_allclose(buffer.trajectory("test.g", "total"),
                                   [3.0])

    def test_vmap_unrolls_one_record_per_member(self, buffer):
        def member(x):
            record_metrics("test.vmap", v=x.sum())
            return x

        jax.jit(jax.vmap(member))(jnp.arange(6.0).reshape(3, 2))
        jax.effects_barrier()
        assert buffer.trajectory("test.vmap", "v").shape == (3,)

    def test_update_ratio_zero_at_fixed_point(self):
        A = jnp.ones((4, 2))
        assert float(update_ratio(A, A)) == 0.0
        assert float(update_ratio(A, 2 * A)) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Zero-cost-off: jaxpr identity + no extra compiles
# ---------------------------------------------------------------------------

def _dense_args(n=8, m=2, k=3):
    key = jax.random.PRNGKey(0)
    X, _, _ = synthetic_rescal(key, n=n, m=m, k=k)
    return X, init_factors(key, n, m, k)


class TestZeroCostOff:
    @pytest.mark.parametrize("step", [mu_step_batched, mu_step_sliced])
    def test_dense_step_jaxpr_bit_identical_off(self, step):
        X, st = _dense_args()
        default = jax.make_jaxpr(lambda x, s: step(x, s))(X, st)
        off = jax.make_jaxpr(
            lambda x, s: step(x, s, trace_metrics=False))(X, st)
        on = jax.make_jaxpr(
            lambda x, s: step(x, s, trace_metrics=True))(X, st)
        assert str(default) == str(off)
        assert "callback" not in str(off)
        assert "callback" in str(on)

    def test_masked_step_jaxpr_bit_identical_off(self):
        X, st = _dense_args(k=3)
        mask = jnp.ones((3,), jnp.float32)
        default = jax.make_jaxpr(
            lambda x, s, mk: masked_mu_step(x, s, mk))(X, st, mask)
        off = jax.make_jaxpr(
            lambda x, s, mk: masked_mu_step(x, s, mk, trace_metrics=False)
        )(X, st, mask)
        on = jax.make_jaxpr(
            lambda x, s, mk: masked_mu_step(x, s, mk, trace_metrics=True)
        )(X, st, mask)
        assert str(default) == str(off)
        assert "callback" not in str(off)
        assert "callback" in str(on)

    @pytest.mark.parametrize("step", [sparse_mu_step, masked_sparse_mu_step])
    def test_sparse_step_jaxpr_bit_identical_off(self, step):
        sp = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=32, bs=8,
                               block_density=0.5)
        st = init_factors(jax.random.PRNGKey(1), 32, 2, 3)
        extra = ((jnp.ones((3,), jnp.float32),)
                 if step is masked_sparse_mu_step else ())

        def call(A, R, trace_metrics):
            return step(sp, A, R, *extra, trace_metrics=trace_metrics)

        default = jax.make_jaxpr(
            lambda a, r: step(sp, a, r, *extra))(st.A, st.R)
        off = jax.make_jaxpr(
            functools.partial(call, trace_metrics=False))(st.A, st.R)
        on = jax.make_jaxpr(
            functools.partial(call, trace_metrics=True))(st.A, st.R)
        assert str(default) == str(off)
        assert "callback" not in str(off)
        assert "callback" in str(on)

    def test_rescal_entry_off_by_default(self):
        X, _ = _dense_args()
        jaxpr = jax.make_jaxpr(
            lambda x: rescal(x, 3, key=jax.random.PRNGKey(0), iters=2))(X)
        assert "callback" not in str(jaxpr)

    def test_default_cfg_shares_compile_cache_with_explicit_false(self):
        """trace_metrics=False must hit the SAME jit cache entry as the
        pre-obs default — zero extra ensemble programs compile."""
        key = jax.random.PRNGKey(0)
        X, _, _ = synthetic_rescal(key, n=12, m=2, k=2)
        cfg = RescalkConfig(k_min=2, k_max=2, n_perturbations=2,
                            rescal_iters=2)
        run_ensemble(X, 2, cfg, mode="batched")         # warm the cache
        with capture_compiles() as log:
            run_ensemble(X, 2, dataclasses.replace(cfg,
                                                   trace_metrics=False),
                         mode="batched")
        assert log.count("_batched_members") == 0
        # the traced build is a different (static-flag) cache entry and
        # actually reaches the host buffer
        buf = MetricsBuffer()
        prev = install_buffer(buf)
        try:
            with capture_compiles() as log_on:
                run_ensemble(X, 2, dataclasses.replace(cfg,
                                                       trace_metrics=True),
                             mode="batched")
            jax.effects_barrier()
        finally:
            install_buffer(prev)
        assert log_on.count("_batched_members") == 1
        traj = buf.trajectory("core.rescal.mu_step_batched", "rel_error")
        assert traj.shape[0] == cfg.rescal_iters * cfg.n_perturbations


# ---------------------------------------------------------------------------
# Device scopes: the MU step's phases in the compiled programs' op_name
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def _scope_sets(compiled) -> list[set]:
    """Each op's op_name path as a set of scope names, transform wrappers
    ('vmap(...)', 'jit(...)') removed."""
    out = []
    for path in _OP_NAME.findall(compiled.as_text()):
        parts = set()
        for part in path.split("/"):
            while (m := _WRAPPED.match(part)):
                part = m.group(1)
            parts.add(part)
        out.append(parts)
    return out


def _unit_program(kind):
    from repro.selection import ensemble
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    if kind == "bcsr":
        sp = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=32, bs=8,
                               block_density=0.5)
        return ensemble._batched_members_bcsr.lower(
            sp, keys, k=3, iters=2, delta=0.03, eps=1e-16)
    X, _ = _dense_args(n=16)
    return ensemble._batched_members.lower(
        X, keys, k=3, iters=2, schedule=kind, init="random", delta=0.03,
        eps=1e-16)


def _engine_program(schedule):
    from repro.dist import compat
    from repro.dist.engine import DistRescalConfig, make_mu_step
    from repro.dist.sharding import COL_AXIS, ROW_AXIS
    mesh = compat.make_mesh((1, 1), (ROW_AXIS, COL_AXIS))
    X, st = _dense_args(n=16)
    step = make_mu_step(mesh, DistRescalConfig(schedule=schedule), iters=2)
    return step.lower(X, st.A, st.R)


class TestDeviceScopes:
    """Every MU step runs under named_scope("mu") and its reads of the
    stored operand under "products": the device trace splits a unit
    iteration by these names (chipbench mu_products_ms / mu_factor_ms)."""

    @pytest.mark.parametrize("program", [
        pytest.param(lambda: _unit_program("batched"), id="dense-batched"),
        pytest.param(lambda: _unit_program("sliced"), id="dense-sliced"),
        pytest.param(lambda: _unit_program("bcsr"), id="bcsr"),
        pytest.param(lambda: _engine_program("batched"),
                     id="engine-batched"),
        pytest.param(lambda: _engine_program("sliced"), id="engine-sliced"),
    ])
    def test_program_names_products_and_factor_algebra(self, program):
        scopes = _scope_sets(program().compile())
        assert any({"mu", "products"} <= p for p in scopes)
        assert any("mu" in p and "products" not in p for p in scopes)

    def test_scopes_leave_the_arithmetic_alone(self):
        """The "mu" scope is metadata: the step compiles to the same
        instructions, and the same numbers, as its undecorated body."""
        X, st = _dense_args()

        def hlo(fn):
            text = jax.jit(fn).lower(X, st).compile().as_text()
            return [re.sub(r", metadata=\{[^}]*\}", "", ln)
                    for ln in text.splitlines()
                    if re.match(r"\s*(ROOT )?%?[\w.-]+ = ", ln)]

        assert hlo(mu_step_batched) == hlo(mu_step_batched.__wrapped__)
        out = jax.jit(mu_step_batched)(X, st)
        ref = jax.jit(mu_step_batched.__wrapped__)(X, st)
        np.testing.assert_array_equal(out.A, ref.A)
        np.testing.assert_array_equal(out.R, ref.R)


# ---------------------------------------------------------------------------
# Compile-event capture -> tracer
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _persistent_cache(path):
    """JAX's persistent compilation cache in `path`, every program kept;
    the process's settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(path), 0.0, -1)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _scoped_program(scope):
    """A new function object each call, so jit lowers and compiles it
    again: a second program can only come from the persistent cache."""
    def obs_cache_probe(x):
        with jax.named_scope(scope):
            return jnp.cos(x) * 3 + 1
    return jax.jit(obs_cache_probe)


def _compile_kinds(make) -> list[str]:
    seen = []
    with capture_compiles(sink=lambda n, k: seen.append((n, k))):
        make()(np.ones(7, np.float32)).block_until_ready()
    return [k for n, k in seen if n == "obs_cache_probe"]


class TestCompileEvents:
    def test_sink_feeds_tracer_and_restores_logger(self):
        logger = logging.getLogger("jax")
        before = (logger.handlers[:], logger.propagate, logger.level)
        tracer = obs.Tracer()

        @jax.jit
        def obs_probe(x):
            return x * 2 + 1

        with capture_compiles(sink=tracer.compile_event) as log:
            obs_probe(jnp.ones(4)).block_until_ready()
        after = (logger.handlers[:], logger.propagate, logger.level)
        assert before == after
        assert log.count("obs_probe") == 1
        names = [e["args"]["program"] for e in tracer.events
                 if e["name"] == "xla/compile"]
        assert "obs_probe" in names
        kinds = {e["args"]["kind"] for e in tracer.events
                 if e["name"] == "xla/compile"}
        assert kinds == {"compile"}

    def test_sink_exceptions_do_not_break_capture(self):
        def bad_sink(name, kind):
            raise RuntimeError("sink bug")

        @jax.jit
        def obs_probe2(x):
            return x - 1

        with capture_compiles(sink=bad_sink) as log:
            obs_probe2(jnp.ones(3)).block_until_ready()
        assert log.count("obs_probe2") == 1

    def test_nested_blocks_share_one_handler(self):
        logger = logging.getLogger("jax")
        before = (logger.handlers[:], logger.propagate, logger.level)
        tracer = obs.Tracer()

        @jax.jit
        def obs_probe3(x):
            return x + 3

        with capture_compiles(sink=tracer.compile_event) as outer:
            with capture_compiles(sink=tracer.compile_event) as inner:
                obs_probe3(jnp.ones(5)).block_until_ready()
        assert (logger.handlers[:], logger.propagate, logger.level) == before
        assert outer.count("obs_probe3") == inner.count("obs_probe3") == 1
        names = [e["args"]["program"] for e in tracer.events
                 if e["name"] == "xla/compile"]
        assert names.count("obs_probe3") == 1     # one sink, called once

    def test_persistent_cache_read_is_a_cache_hit(self, tmp_path):
        """A program read back from the persistent cache is one
        ``cache_hit``, never also a ``compile``."""
        with _persistent_cache(tmp_path):
            kinds = [_compile_kinds(lambda: _scoped_program("a"))
                     for _ in range(2)]
        assert kinds == [["compile"], ["cache_hit"]]

    def test_cache_keyed_on_metadata_compiles_new_scopes(self, tmp_path):
        """The same instructions under other scopes: the default key takes
        the cached executable (and its stale op_names); keyed on metadata,
        the program compiles with its own."""
        from repro.dist.compat import cache_keyed_on_metadata
        with _persistent_cache(tmp_path):
            first = _compile_kinds(lambda: _scoped_program("a"))
            stale = _compile_kinds(lambda: _scoped_program("b"))
            with cache_keyed_on_metadata():
                own = _compile_kinds(lambda: _scoped_program("b"))
        assert (first, stale, own) == (["compile"], ["cache_hit"],
                                       ["compile"])

    def test_compile_events_reach_chrome_export(self, tmp_path):
        t = obs.Tracer()
        t.compile_event("_grid_members", "compile")
        out = tmp_path / "c.json"
        t.export_chrome(str(out))
        evs = json.loads(out.read_text())["traceEvents"]
        comp = [e for e in evs if e["name"] == "xla/compile"]
        assert comp and comp[0]["cat"] == "xla"
        assert comp[0]["args"]["program"] == "_grid_members"


# ---------------------------------------------------------------------------
# Scheduler wiring: spans per unit + straggler flagging
# ---------------------------------------------------------------------------

class TestSchedulerObservability:
    def _run_sweep(self, straggler_factor=2.5):
        key = jax.random.PRNGKey(0)
        X, _, _ = synthetic_rescal(key, n=16, m=2, k=3)
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=3)
        sched = SweepScheduler(cfg, mode="batched",
                               straggler_factor=straggler_factor)
        sched.run(X)
        return sched

    def test_every_unit_gets_an_execute_span(self):
        with obs.tracing() as t:
            sched = self._run_sweep()
        spans = {(e["name"], e["args"].get("uid")) for e in t.events
                 if e["ph"] == "B"}
        for rec in sched.report.units:
            assert ("sched/execute", rec.uid) in spans
        names = {e["name"] for e in t.events if e["ph"] == "B"}
        assert {"sched/plan", "sched/reduce"} <= names

    def test_unit_programs_are_keyed_on_their_scopes(self, monkeypatch):
        """Unit programs compile (or are read from the persistent cache)
        keyed on their metadata, so their "mu" / "products" scopes reach
        device profiles; nothing else is."""
        import repro.selection.scheduler as sched_mod
        run, seen = sched_mod.run_ensemble, []

        def spy(*args, **kwargs):
            seen.append(
                jax.config.jax_compilation_cache_include_metadata_in_key)
            return run(*args, **kwargs)

        monkeypatch.setattr(sched_mod, "run_ensemble", spy)
        self._run_sweep()
        assert seen and all(seen)
        assert not jax.config.jax_compilation_cache_include_metadata_in_key

    @pytest.mark.parametrize("mode", ["batched", "grid"])
    def test_host_path_spans_nest(self, mode):
        """The scheduler's host path between and around units is covered:
        dispatch and wait inside each unit's execute span, the per-unit
        watermark reads, the fetch of each k's factors, the four stages of
        each k's reduction inside its reduce span, and the selection."""
        key = jax.random.PRNGKey(0)
        X, _, _ = synthetic_rescal(key, n=16, m=2, k=3)
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=3)
        with obs.tracing() as t:
            SweepScheduler(cfg, mode=mode).run(X)
        parent, stack = {}, []
        for e in t.events:
            if e["ph"] == "B":
                parent.setdefault(e["name"], set()).add(
                    stack[-1] if stack else None)
                stack.append(e["name"])
            elif e["ph"] == "E":
                assert stack.pop() == e["name"]
        assert not stack
        assert parent["sched/dispatch"] == {"sched/execute"}
        assert parent["sched/wait"] == {"sched/execute"}
        for name in ("reduce/cluster", "reduce/silhouette",
                     "reduce/regress", "reduce/error"):
            assert parent[name] == {"sched/reduce"}
        for name in ("sched/watermark", "sched/fetch", "sched/select"):
            assert parent[name] == {None}
        begins = [e["name"] for e in t.events if e["ph"] == "B"]
        n_units = len(plan_sweep(cfg, mode=mode))
        assert begins.count("sched/dispatch") == n_units
        assert begins.count("sched/watermark") == n_units
        assert begins.count("reduce/regress") == len(cfg.ks)
        assert begins.count("sched/select") == 1

    def test_straggler_flagged_in_report(self, capsys):
        # factor 0: every unit after the first exceeds 0 x baseline
        sched = self._run_sweep(straggler_factor=0.0)
        flags = [u.straggler for u in sched.report.units]
        assert flags == [False, True]
        flagged = sched.report.units[1]
        assert flagged.baseline_seconds is not None
        assert sched.report.meta["n_stragglers"] == 1
        assert "[straggler]" in capsys.readouterr().out

    def test_straggler_event_emitted(self):
        with obs.tracing() as t:
            self._run_sweep(straggler_factor=0.0)
        ev = [e for e in t.events if e["name"] == "sched/straggler"]
        assert len(ev) == 1 and ev[0]["args"]["seconds"] > 0

    def test_report_json_round_trips_straggler_fields(self, tmp_path):
        sched = self._run_sweep(straggler_factor=0.0)
        path = tmp_path / "r.json"
        sched.report.save(str(path))
        loaded = SelectionReport.load(str(path))
        assert [u.straggler for u in loaded.units] == [False, True]

    def test_pre_obs_report_json_still_loads(self, tmp_path):
        """Old reports lack straggler fields; defaults must fill in."""
        rec = {"uid": "unit_k2_q0-1", "k": 2, "members": [0, 1],
               "seconds": 1.0, "reused": False, "retries": 0,
               "cells": None}
        d = {"ks": [2], "s_min": [0.9], "s_mean": [0.9], "rel_err": [0.1],
             "k_opt": 2, "criterion": "threshold", "mode": "batched",
             "n_perturbations": 2, "units": [rec], "meta": {}}
        path = tmp_path / "old.json"
        path.write_text(json.dumps(d))
        loaded = SelectionReport.load(str(path))
        assert loaded.units[0].straggler is False
        assert loaded.units[0].baseline_seconds is None


# ---------------------------------------------------------------------------
# Cost accounting
# ---------------------------------------------------------------------------

class TestCosts:
    def test_models_scale_linearly_in_k(self):
        c1 = obs_costs.dense_mu_cost(64, 3, 2)
        c2 = obs_costs.dense_mu_cost(64, 3, 4)
        assert 0 < c1["flops"] < c2["flops"]
        b1 = obs_costs.bcsr_mu_cost(3, 10, 16, 2)
        b2 = obs_costs.bcsr_mu_cost(3, 10, 16, 4)
        assert b2["flops"] == pytest.approx(2 * b1["flops"])

    def test_operand_dispatch(self):
        sp = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=32, bs=8,
                               block_density=0.5)
        dense = jnp.zeros((2, 16, 16))
        assert obs_costs.operand_mu_cost(sp, 3) \
            == obs_costs.bcsr_mu_cost(sp.m, sp.nnzb, sp.bs, 3)
        assert obs_costs.operand_mu_cost(dense, 3) \
            == obs_costs.dense_mu_cost(16, 2, 3)

    def test_measure_mu_costs_returns_per_k_dicts(self):
        X = jnp.ones((2, 12, 12))
        out = obs_costs.measure_mu_costs(X, [2, 3])
        assert sorted(out) == [2, 3]
        assert all(isinstance(v, dict) for v in out.values())

    def test_cost_table_rows_and_formatting(self):
        recs = [UnitRecord(uid="unit_k2_q0-1", k=2, members=[0, 1],
                           seconds=0.5, reused=False, retries=0),
                UnitRecord(uid="grid_c0-3", k=-1, members=[],
                           seconds=0.0, reused=True, retries=0,
                           cells=[[2, 0], [2, 1], [3, 0]])]
        X = jnp.ones((2, 16, 16))
        rows = obs_costs.cost_table(recs, X, iters=10)
        assert rows[0]["cells"] == 2 and rows[1]["cells"] == 3
        assert rows[0]["achieved_gflops"] > 0
        assert rows[1]["achieved_gflops"] is None   # reused: no wall time
        text = obs_costs.format_cost_table(rows)
        assert "unit_k2_q0-1" in text and "reused" in text

    def test_unit_ks_grid_vs_per_k(self):
        per_k = UnitRecord(uid="u", k=4, members=[0, 1, 2], seconds=1,
                           reused=False, retries=0)
        grid = UnitRecord(uid="g", k=-1, members=[], seconds=1,
                          reused=False, retries=0, cells=[[2, 0], [5, 1]])
        assert obs_costs.unit_ks(per_k) == [4, 4, 4]
        assert obs_costs.unit_ks(grid) == [2, 5]


# ---------------------------------------------------------------------------
# Train-loop logging fix
# ---------------------------------------------------------------------------

class TestTrainLoopLogging:
    def _fake_loop(self, monkeypatch, metrics):
        from repro.train import loop as loop_mod
        monkeypatch.setattr(loop_mod, "init_state",
                            lambda key, cfg, opt: {"w": jnp.zeros(1)})

        def fake_make_step(cfg, mesh, *, optimizer, remat, moe_impl):
            def step_fn(state, batch):
                return state, dict(metrics)
            return step_fn

        monkeypatch.setattr(loop_mod, "make_train_step", fake_make_step)
        return loop_mod

    def test_no_loss_key_does_not_crash(self, monkeypatch, capsys):
        loop_mod = self._fake_loop(monkeypatch,
                                   {"aux_err": jnp.float32(0.5)})
        _, hist = loop_mod.train_loop(
            None, lambda s: None,
            loop_mod.LoopConfig(steps=2, log_every=1), verbose=True)
        out = capsys.readouterr().out
        assert "aux_err=0.5" in out and "loss" not in out
        assert len(hist) == 2

    def test_loss_key_prints_as_before(self, monkeypatch, capsys):
        loop_mod = self._fake_loop(monkeypatch, {"loss": jnp.float32(2.0)})
        loop_mod.train_loop(None, lambda s: None,
                            loop_mod.LoopConfig(steps=1, log_every=1),
                            verbose=True)
        assert "loss=2.0000" in capsys.readouterr().out

    def test_steps_routed_through_event_log(self, monkeypatch):
        loop_mod = self._fake_loop(monkeypatch, {"loss": jnp.float32(1.0)})
        with obs.tracing() as t:
            loop_mod.train_loop(None, lambda s: None,
                                loop_mod.LoopConfig(steps=2))
        steps = [e for e in t.events if e["name"] == "train/step"]
        assert len(steps) == 2
        assert steps[0]["args"]["loss"] == 1.0


# ---------------------------------------------------------------------------
# check_trace.py validator (imported, not subprocessed — CI runs the CLI)
# ---------------------------------------------------------------------------

def _load_check_trace():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "scripts" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCheckTrace:
    def test_balanced_trace_passes(self, tmp_path):
        ct = _load_check_trace()
        with obs.tracing(str(tmp_path)) as t:
            with obs.span("sched/execute", uid="u0"):
                obs.event("sched/retry")
            t.export_chrome(str(tmp_path / "trace_chrome.json"))
        assert ct.main([str(tmp_path)]) == 0

    def test_unbalanced_nesting_fails(self, tmp_path):
        ct = _load_check_trace()
        t = obs.Tracer(str(tmp_path))
        t._emit({"ph": "B", "name": "a", "ts": 1.0, "pid": 1, "tid": 1})
        t.export_chrome(str(tmp_path / "trace_chrome.json"))
        t.close()
        assert ct.main([str(tmp_path)]) == 1

    def test_missing_dir_is_exit_2(self, tmp_path):
        ct = _load_check_trace()
        assert ct.main([str(tmp_path / "nope")]) == 2

    def test_report_cross_check_finds_missing_span(self, tmp_path):
        ct = _load_check_trace()
        with obs.tracing(str(tmp_path)) as t:
            with obs.span("sched/execute", uid="unit_a"):
                pass
            t.export_chrome(str(tmp_path / "trace_chrome.json"))
        report = {"units": [{"uid": "unit_a", "reused": False},
                            {"uid": "unit_b", "reused": False}]}
        rp = tmp_path / "report.json"
        rp.write_text(json.dumps(report))
        assert ct.main([str(tmp_path), "--report", str(rp)]) == 1
        report["units"].pop()
        rp.write_text(json.dumps(report))
        assert ct.main([str(tmp_path), "--report", str(rp)]) == 0

    def test_expect_metrics(self, tmp_path):
        ct = _load_check_trace()
        with obs.tracing(str(tmp_path)) as t:
            with obs.span("a"):
                pass
            t.export_chrome(str(tmp_path / "trace_chrome.json"))
        np.savez(tmp_path / "metrics.npz", **{"t.rel_error": np.ones(3)})
        assert ct.main([str(tmp_path), "--expect-metrics"]) == 0
        np.savez(tmp_path / "metrics.npz", **{"t.other": np.ones(3)})
        assert ct.main([str(tmp_path), "--expect-metrics"]) == 1


# ---------------------------------------------------------------------------
# Memory observability (ISSUE 8): compat normalizer, host watermarks,
# AOT per-rank accounting, the ledger, scheduler fields, the validator
# ---------------------------------------------------------------------------

class _FakeMemStats:
    """Stand-in for CompiledMemoryStats with a controllable field set."""

    def __init__(self, **fields):
        for k, v in fields.items():
            setattr(self, k, v)


class _FakeCompiled:
    def __init__(self, mem):
        self._mem = mem

    def memory_analysis(self):
        if isinstance(self._mem, Exception):
            raise self._mem
        return self._mem


class TestProgramMemory:
    def test_real_compiled_program(self):
        pm = program_memory(jax.jit(lambda x: x * 2 + 1)
                            .lower(jnp.ones(8)).compile())
        assert pm is not None
        assert pm["total"] == (pm["argument"] + pm["output"] + pm["temp"]
                               - pm["alias"])
        assert pm["peak"] >= max(pm["argument"], pm["output"], pm["temp"])

    def test_reported_peak_passes_through(self):
        """A backend peak above argument+output+temp (as TPU reports) is
        the program's peak."""
        pm = program_memory(_FakeCompiled(_FakeMemStats(
            argument_size_in_bytes=100, output_size_in_bytes=20,
            temp_size_in_bytes=30, alias_size_in_bytes=0,
            peak_memory_in_bytes=999)))
        assert pm["peak"] == 999 and pm["total"] == 150

    @pytest.mark.parametrize("backend_peak", [1424, 0, None])
    def test_peak_never_below_total(self, backend_peak):
        """The CPU backend's liveness peak can sit below temp alone (1424
        vs temp 1552); the temp arena is resident for the whole run, so
        peak reads total there — and where the backend gives no peak."""
        fields = dict(argument_size_in_bytes=1280, output_size_in_bytes=144,
                      temp_size_in_bytes=1552, alias_size_in_bytes=0)
        if backend_peak is not None:
            fields["peak_memory_in_bytes"] = backend_peak
        pm = program_memory(_FakeCompiled(_FakeMemStats(**fields)))
        assert pm["total"] == 1280 + 144 + 1552
        assert pm["peak"] == pm["total"]

    def test_no_analysis_is_none_never_zero(self):
        """The dryrun silent-zero bug: unknown must be None, not 0."""
        assert program_memory(_FakeCompiled(None)) is None
        assert program_memory(_FakeCompiled(RuntimeError("n/a"))) is None
        assert program_memory(_FakeCompiled(_FakeMemStats())) is None

    def test_device_memory_stats_is_a_dict(self):
        # CPU backends report no stats -> {}, never an exception
        assert isinstance(device_memory_stats(), dict)


class TestHostMemory:
    def test_read_host_memory_positive(self):
        host = obs_memory.read_host_memory()
        assert host["rss_bytes"] > 0
        assert host["hwm_bytes"] >= host["rss_bytes"] - 64 * 2**20

    def test_sampler_tracks_peak_and_emits_events(self):
        with obs.tracing() as t:
            s = obs_memory.HostMemorySampler(interval=0.01).start()
            s.sample_once()
            s.stop()
        assert len(s.samples) >= 2
        assert s.peak_rss_bytes > 0
        assert s.peak_bytes >= s.peak_rss_bytes     # folds in kernel HWM
        assert any(e["name"] == "mem/sample" and e["args"]["rss_bytes"] > 0
                   for e in t.events)

    def test_sampler_silent_without_tracer(self):
        assert obs.current() is None
        s = obs_memory.HostMemorySampler(interval=0.01)
        s.sample_once()                              # no tracer: must not raise
        assert s.peak_rss_bytes > 0

    def test_tracing_owns_sampler_lifecycle(self):
        with obs.tracing(sample_memory=True, sample_interval=0.01) as t:
            assert t.memory_sampler is not None
        assert t.memory_sampler._thread is None      # stopped on exit
        assert t.memory_sampler.peak_bytes > 0


class TestMeasureMuMemory:
    def test_per_k_breakdown_dense_and_sparse(self):
        X = jnp.ones((2, 12, 12))
        s = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=32, bs=8,
                              block_density=0.5)
        for op in (X, s):
            out = obs_memory.measure_mu_memory(op, [2, 3])
            assert sorted(out) == [2, 3]
            for entry in out.values():
                if entry:            # {} allowed where backend has no analysis
                    assert entry["peak"] >= max(entry["argument"],
                                                entry["output"],
                                                entry["temp"])


class TestMemoryLedger:
    def _ledger(self, **kw):
        s = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=64, bs=16,
                              block_density=0.25)
        from repro.io import manifest_of
        return obs_memory.MemoryLedger.from_manifest(manifest_of(s), **kw)

    def test_from_manifest_and_compression(self):
        led = self._ledger()
        assert led.kind == "bcsr"
        assert led.compression == led.logical_bytes / led.resident_bytes

    def test_device_peak_prefers_runtime_then_aot(self):
        led = self._ledger(per_k={2: {"peak": 100}, 3: {"peak": 300}})
        assert led.device_peak() == 300              # AOT fallback: max per-k
        led.peak_device_bytes = 777
        assert led.device_peak() == 777              # runtime watermark wins
        assert self._ledger().device_peak() is None  # neither known

    def test_save_load_round_trip(self, tmp_path):
        led = self._ledger(per_k={2: {"argument": 1, "output": 2, "temp": 3,
                                      "alias": 0, "peak": 6, "total": 6}},
                           peak_host_bytes=10 * 2**20,
                           kernel_fallbacks=4)
        path = tmp_path / "memory.json"
        led.save(str(path))
        back = obs_memory.MemoryLedger.load(str(path))
        assert back.per_k[2]["peak"] == 6            # int keys restored
        assert back.kernel_fallbacks == 4
        assert back.peak_device_bytes is None        # unknown stays unknown
        assert back.compression == pytest.approx(led.compression)

    def test_summary_states_the_claim(self):
        led = self._ledger(peak_host_bytes=64 * 2**20, kernel_fallbacks=2)
        line = led.summary_line()
        assert "represented" in line and "resident" in line
        assert "2 kernel fallback(s)" in line
        assert "k" in led.summarize()

    def test_accounted_ensemble_bytes_formula(self):
        from repro.io import manifest_of
        s = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=64, bs=16,
                              block_density=0.25)
        man = manifest_of(s)
        got = obs_memory.accounted_ensemble_bytes(man, n_members=3, k_max=4)
        want = (man.resident_bytes * 4
                + 3 * (man.n_factor * 4 + man.m * 16) * 4)
        assert got == want


class TestSchedulerMemory:
    def _run_sweep(self, **cfg_kw):
        key = jax.random.PRNGKey(0)
        X, _, _ = synthetic_rescal(key, n=16, m=2, k=3)
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=3, **cfg_kw)
        sched = SweepScheduler(cfg, mode="batched")
        sched.run(X)
        return sched

    def test_unit_records_carry_watermarks(self):
        sched = self._run_sweep()
        for rec in sched.report.units:
            assert rec.peak_host_bytes is not None
            assert rec.peak_host_bytes > 0
            assert rec.kernel_fallbacks == 0         # dense sweep: no kernels
        assert sched.report.meta["n_kernel_fallbacks"] == 0

    def test_forced_fallback_sweep_counts_per_unit(self, monkeypatch):
        """The end-to-end fallback contract: a fused-kernel sweep forced
        onto a tiny panel budget must emit kernel/fallback instants, record
        nonzero per-unit counts, and still select a k."""
        import repro.kernels.ops as ops
        monkeypatch.setattr(ops, "VMEM_PANEL_BYTES", 16)
        s = spmod.random_bcsr(jax.random.PRNGKey(0), m=2, n=64, bs=16,
                              block_density=0.5)
        cfg = RescalkConfig(k_min=2, k_max=2, n_perturbations=2,
                            rescal_iters=3, use_fused_kernel=True,
                            fused_impl="pallas")
        with obs.tracing() as t:
            sched = SweepScheduler(cfg, mode="batched")
            res = sched.run(s)
        assert int(res.k_opt) == 2
        evs = [e for e in t.events if e["name"] == "kernel/fallback"]
        assert evs, "no kernel/fallback instants in the trace"
        assert evs[0]["args"]["budget_bytes"] == 16
        assert evs[0]["args"]["requested_bytes"] > 16
        assert all(u.kernel_fallbacks >= 1 for u in sched.report.units)
        assert sched.report.meta["n_kernel_fallbacks"] == len(evs)

    def test_report_round_trips_memory_fields(self, tmp_path):
        sched = self._run_sweep()
        path = tmp_path / "r.json"
        sched.report.save(str(path))
        loaded = SelectionReport.load(str(path))
        for rec in loaded.units:
            assert rec.peak_host_bytes > 0
            assert rec.peak_device_bytes is None     # CPU: unknown != 0
            assert rec.kernel_fallbacks == 0

    def test_pre_memory_report_json_still_loads(self, tmp_path):
        """PR 7-era reports lack the byte fields; defaults must fill in."""
        rec = {"uid": "unit_k2_q0-1", "k": 2, "members": [0, 1],
               "seconds": 1.0, "reused": False, "retries": 0,
               "cells": None, "straggler": False, "baseline_seconds": None}
        d = {"ks": [2], "s_min": [0.9], "s_mean": [0.9], "rel_err": [0.1],
             "k_opt": 2, "criterion": "threshold", "mode": "batched",
             "n_perturbations": 2, "units": [rec], "meta": {}}
        path = tmp_path / "old.json"
        path.write_text(json.dumps(d))
        loaded = SelectionReport.load(str(path))
        assert loaded.units[0].peak_host_bytes is None
        assert loaded.units[0].peak_device_bytes is None
        assert loaded.units[0].kernel_fallbacks == 0


class TestCheckTraceMemory:
    def _trace_dir(self, tmp_path, *, n_fallback_events=0):
        with obs.tracing(str(tmp_path)) as t:
            with obs.span("sched/execute", uid="u0"):
                for _ in range(n_fallback_events):
                    obs.event("kernel/fallback", kernel="bcsr_spmm",
                              requested_bytes=100, budget_bytes=16,
                              chosen="ref")
            t.export_chrome(str(tmp_path / "trace_chrome.json"))
        return tmp_path

    def _ledger_doc(self, **over):
        doc = {"ledger": {"kind": "bcsr", "logical_bytes": 1000,
                          "resident_bytes": 10, "compression": 100.0},
               "per_k": {"2": {"argument": 5, "output": 1, "temp": 2,
                               "alias": 0, "peak": 8, "total": 8}},
               "runtime": {"peak_host_bytes": 2**20,
                           "peak_device_bytes": None,
                           "accounted_sweep_bytes": 40},
               "fallbacks": {"count": 0}, "meta": {}}
        doc.update(over)
        return doc

    def test_valid_ledger_passes(self, tmp_path):
        ct = _load_check_trace()
        d = self._trace_dir(tmp_path)
        (d / "memory.json").write_text(json.dumps(self._ledger_doc()))
        assert ct.main([str(d), "--expect-memory"]) == 0

    def test_ratio_below_one_fails(self, tmp_path):
        ct = _load_check_trace()
        d = self._trace_dir(tmp_path)
        doc = self._ledger_doc(ledger={"kind": "bcsr", "logical_bytes": 10,
                                       "resident_bytes": 1000,
                                       "compression": 0.01})
        (d / "memory.json").write_text(json.dumps(doc))
        assert ct.main([str(d), "--expect-memory"]) == 1

    def test_missing_host_peak_fails(self, tmp_path):
        ct = _load_check_trace()
        d = self._trace_dir(tmp_path)
        doc = self._ledger_doc(runtime={"peak_host_bytes": None,
                                        "peak_device_bytes": None})
        (d / "memory.json").write_text(json.dumps(doc))
        assert ct.main([str(d), "--expect-memory"]) == 1

    def test_fallback_count_must_match_trace(self, tmp_path):
        ct = _load_check_trace()
        d = self._trace_dir(tmp_path, n_fallback_events=2)
        (d / "memory.json").write_text(
            json.dumps(self._ledger_doc(fallbacks={"count": 2})))
        assert ct.main([str(d), "--expect-memory"]) == 0
        (d / "memory.json").write_text(
            json.dumps(self._ledger_doc(fallbacks={"count": 5})))
        assert ct.main([str(d), "--expect-memory"]) == 1

    def test_truncated_ledger_is_exit_2(self, tmp_path):
        ct = _load_check_trace()
        d = self._trace_dir(tmp_path)
        (d / "memory.json").write_text('{"ledger": {"kind"')
        assert ct.main([str(d), "--expect-memory"]) == 2
        (d / "memory.json").write_text(json.dumps({"no": "ledger"}))
        assert ct.main([str(d), "--expect-memory"]) == 2
