"""Backend-tolerance layer for the JAX surfaces this repo wraps.

The repo targets one JAX release (the version pinned in CI); this module
holds the few wrappers whose behaviour differs by *backend* (TPU vs the
CPU test container) or that normalize a JAX result into the shape the
rest of the code reads.  Outside this module, code stays backend-blind:

  * ``donating_jit`` — buffer donation only where XLA implements it.
  * ``make_mesh`` — ``jax.make_mesh`` with every axis ``AxisType.Auto``
    (GSPMD propagation decides), the sharding model the engine's
    ``shard_map`` programs are written for; ``jax.make_mesh`` alone
    defaults to explicit axes.
  * ``capture_compiles`` — counts XLA compilations and persistent-cache
    reads from the ``jax.log_compiles`` log lines, so the compile-count CI
    guard (scripts/check_compiles.py) and the tracer have one parser.
  * ``cache_keyed_on_metadata`` — persistent-cache keys that include the
    programs' metadata (op_name scopes), a private JAX config state.
  * ``program_memory`` — ``compiled.memory_analysis()`` as one byte
    breakdown, ``None`` when the backend offers none.
  * ``device_memory_stats`` — allocator watermarks exist on TPU and
    return ``None`` on CPU; flattened to a plain int dict, ``{}`` when
    unsupported.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import re
from typing import Any, Sequence

import jax
import jax.sharding

__all__ = [
    "cache_keyed_on_metadata",
    "capture_compiles",
    "device_memory_stats",
    "donating_jit",
    "make_mesh",
    "program_memory",
]

# Backends where XLA implements input-output aliasing.  Donating on CPU
# aliases nothing and just spews a "Donation is not implemented" warning
# per call site, so the shim keeps donation off there.
_DONATING_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def donating_jit(fun, *, donate_argnums: Sequence[int] = (),
                 static_argnames: Sequence[str] = ()):
    """``jax.jit`` with buffer donation on backends that implement it.

    Buffer donation lets XLA alias an input buffer to an output (the MU
    hot loops rewrite factor state in place — donating the incoming state
    removes one live copy of (n, k) + (m, k, k) per program, which for
    large-n sweeps is the steady-state HBM difference between fitting and
    not).  Two things make this a compat concern rather than a plain
    ``donate_argnums=``:

      * CPU does not implement aliasing — XLA warns "Some donated
        buffers were not usable" / "Donation is not implemented" on every
        call site.  The CI contract is that those
        warnings stay CLEAN, so the shim resolves the backend lazily (at
        first call, never at import) and only enables donation where it
        works.
      * callers must treat donated operands as consumed on accelerator
        backends; the host path is unaffected.
    """
    plain = jax.jit(fun, static_argnames=static_argnames)
    donating = None

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        nonlocal donating
        if jax.default_backend() in _DONATING_BACKENDS:
            if donating is None:
                donating = jax.jit(fun, static_argnames=static_argnames,
                                   donate_argnums=tuple(donate_argnums))
            return donating(*args, **kwargs)
        return plain(*args, **kwargs)

    return wrapper


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Sequence | None = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    All mesh construction in this repo goes through here (or through
    ``launch.mesh``, which delegates here).
    """
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto,
                         devices=devices)


# "Finished XLA compilation of jit(_grid_members) in 0.1 sec": logged once
# per program the backend produced, whether compiled or read from the cache
_FINISHED_RE = re.compile(r"Finished XLA compilation of jit\(([^)\s]+)\) in")
# "Persistent compilation cache hit for 'jit__grid_members' with key ...":
# logged inside the same compile call, on the same thread, just before it
_CACHE_HIT_RE = re.compile(r"Persistent compilation cache hit for '")


class CompileLog:
    """Compile events observed inside a ``capture_compiles`` block.
    ``events`` holds one traced-function name per program XLA produced,
    compiled or read from the persistent cache (eager jnp ops appear under
    their primitive names, e.g. ``_pad`` — ``count()`` filters by name so
    guards can target specific programs)."""

    def __init__(self):
        self.events: list[str] = []

    def count(self, *names: str) -> int:
        """Number of compilations of the named traced functions; with no
        names, all compilations."""
        if not names:
            return len(self.events)
        return sum(1 for e in self.events if e in names)


# the open capture_compiles blocks, outermost first, as (log, sink)
_ACTIVE: list[tuple[CompileLog, Any]] = []


class _CompileHandler(logging.Handler):
    """Feeds every open block from JAX's compile log lines.  A cache-hit
    line marks its thread; that thread's next "Finished" line is then the
    cache read, so each program counts once, as one kind."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self._hit_threads: set[int] = set()

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if _CACHE_HIT_RE.search(msg):
            self._hit_threads.add(record.thread)
            return
        if not msg.startswith("Finished XLA compilation of "):
            return
        hit = record.thread in self._hit_threads
        self._hit_threads.discard(record.thread)
        m = _FINISHED_RE.search(msg)
        if not m:
            return               # not a jit program (pmap): not counted
        name = m.group(1)
        kind = "cache_hit" if hit else "compile"
        sinks: list = []
        for log, sink in list(_ACTIVE):
            log.events.append(name)
            if sink is not None and sink not in sinks:
                sinks.append(sink)
        for sink in sinks:
            try:
                sink(name, kind)
            except Exception:
                pass     # telemetry must never fail a compile


@contextlib.contextmanager
def capture_compiles(sink=None):
    """Record every program XLA produces in the block as a ``CompileLog``.

    Implemented on ``jax.log_compiles`` + a logging handler rather than
    any private counter — the one place the compile-count CI guard parses
    JAX's log wording.

    ``sink(program, kind)`` is additionally called once per program, with
    kind "compile" for a fresh compilation or "cache_hit" for a read from
    the persistent compilation cache — the live-event side channel the
    tracer uses (``obs.Tracer.compile_event`` has this signature).  Sink
    exceptions are swallowed: telemetry must never fail a compile.

    Blocks nest: an inner block sees what it compiles, the outer blocks
    see it too, and a sink open in several blocks is called once.
    """
    log = CompileLog()
    entry = (log, sink)
    logger = logging.getLogger("jax")
    outermost = not _ACTIVE
    if outermost:
        saved = (logger.level, logger.propagate, logger.handlers[:])
        # capture, don't spew: JAX installs its own stderr StreamHandler on
        # the "jax" logger at import, so swap the handler list rather than
        # stacking on top of it, and restore verbatim after
        logger.handlers[:] = [_CompileHandler()]
        logger.propagate = False
        if logger.getEffectiveLevel() > logging.WARNING:
            logger.setLevel(logging.WARNING)  # log_compiles emits at WARNING
    _ACTIVE.append(entry)
    try:
        with jax.log_compiles():
            yield log
    finally:
        _ACTIVE.remove(entry)
        if outermost:
            logger.setLevel(saved[0])
            logger.propagate = saved[1]
            logger.handlers[:] = saved[2]


def cache_keyed_on_metadata():
    """A context in which the programs compiled, or read from JAX's
    persistent compilation cache, are keyed on their metadata too.  By
    default the key strips it, so an executable cached from the same
    instructions under other op_name scopes (an older build of the
    program) stands in, and a profile shows its stale op_names.  The
    price: the metadata holds source locations, call sites included, so
    a program compiles again once wherever its code, its callers or the
    checkout's path moved."""
    from jax._src import config as jax_config
    return jax_config.compilation_cache_include_metadata_in_key(True)


def program_memory(compiled) -> dict[str, Any] | None:
    """``compiled.memory_analysis()`` as one byte-breakdown dict::

        {"argument": int, "output": int, "temp": int, "alias": int,
         "peak": int, "total": int}

    where ``total = argument + output + temp - alias``.  ``peak`` is the
    bytes the program holds at once while it runs: the larger of the
    backend's ``peak_memory_in_bytes`` and ``total``.  The temp arena is
    one allocation that lives for the whole execution, so every byte of
    ``total`` is resident together; the backend's own figure adds to it
    on TPU (it measures above ``total``) but on CPU it is a liveness
    estimate that can fall below ``temp`` alone.  Returns ``None`` when
    the backend offers no memory analysis at all — callers must treat
    that as "unknown", never as 0 bytes.
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None

    def _field(name):
        v = getattr(mem, name, None)
        return int(v) if isinstance(v, (int, float)) else None

    arg = _field("argument_size_in_bytes")
    out = _field("output_size_in_bytes")
    temp = _field("temp_size_in_bytes")
    if arg is None and out is None and temp is None:
        return None
    arg, out, temp = arg or 0, out or 0, temp or 0
    alias = _field("alias_size_in_bytes") or 0
    total = arg + out + temp - alias
    peak = max(_field("peak_memory_in_bytes") or 0, total)
    return {"argument": arg, "output": out, "temp": temp, "alias": alias,
            "peak": peak, "total": total}


def device_memory_stats(device=None) -> dict[str, int]:
    """Allocator statistics of one device as a flat int dict.

    TPU/GPU backends report ``bytes_in_use`` / ``peak_bytes_in_use`` et
    al.; CPU returns ``None`` — normalized here to ``{}`` so callers can
    record "no device watermark" instead of crashing or inventing zeros.
    """
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return {}
    if not isinstance(stats, dict):
        return {}
    return {str(k): int(v) for k, v in stats.items()
            if isinstance(v, (int, float))}
