"""Profiler capture of the measured window, and its reduction to numbers.

``Capture`` runs JAX's profiler over the window, marks the window with a
``TraceAnnotation`` and installs the program's own span tracer
(``repro.obs.trace``), whose perf_counter clock is mapped onto the
profiler's through the annotation's start.  ``Capture.record()`` flattens
the profile into a small JSON-able dict; ``Reduced`` computes every
trace-derived number from that dict alone, so the reduction can be
checked on a recorded trace without a chip (``tests/test_tracing.py``).

Record layout (all times in ns on the profiler's clock)::

    {"window": [t0, t1],
     "devices": {"<plane>": {"ops": [[name, start, dur], ...],
                             "modules": [[name, start, dur], ...]}},
     "spans": [[name, start, dur], ...]}     # program spans, same clock
"""
from __future__ import annotations

import glob
import os
import re
import time

WINDOW_ANNOTATION = "chipbench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO op names of cross-chip collectives (the async pairs included)
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all", "send", "recv")
TOP = 10
# a chip's plane; the profiler also writes planes such as
# "/device:CUSTOM:Megascale Trace" that hold no chip's operations
CHIP_PLANE = re.compile(r"/device:TPU:\d+$")


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint sorted intervals `a` not covered by `b`."""
    b = union(b)
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    base = name.lstrip("%").lower()
    return base.startswith(COLLECTIVE_PREFIXES)


class Reduced:
    """Trace-derived numbers of one traced window (see the module doc)."""

    def __init__(self, record: dict):
        self.t0, self.t1 = (float(t) for t in record["window"])
        self.window_s = (self.t1 - self.t0) / 1e9
        self.devices = {d: v for d, v in record["devices"].items()
                        if CHIP_PLANE.match(d)}
        self.spans = [(n, float(s), float(d)) for n, s, d in
                      record.get("spans", [])]

    def _ops(self, dev: str):
        return [(n, float(s), float(s) + float(d))
                for n, s, d in self.devices[dev]["ops"]]

    def busy_intervals(self, dev: str) -> list[tuple[float, float]]:
        return union(clip([(s, e) for _, s, e in self._ops(dev)],
                          self.t0, self.t1))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy_intervals(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def module_events(self, prefixes) -> list[tuple[str, float, float]]:
        """Executions of the XLA programs whose names start with one of
        `prefixes`, on every chip, clipped to the window."""
        out = []
        for dev in self.devices:
            for n, s, d in self.devices[dev].get("modules", []):
                s, e = float(s), float(s) + float(d)
                if n.startswith(tuple(prefixes)) and e > self.t0 \
                        and s < self.t1:
                    out.append((n, max(s, self.t0), min(e, self.t1)))
        return out

    def module_seconds(self, prefixes) -> float:
        """Device seconds of those programs, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(e - s for _, s, e in self.module_events(prefixes)) \
            / len(self.devices) / 1e9

    def module_calls(self, prefixes) -> float:
        if not self.devices:
            return 0.0
        return len(self.module_events(prefixes)) / len(self.devices)

    def collective_exposed(self) -> float | None:
        """Share of the window in which a collective runs and no other
        operation does, averaged over the chips; None with no collective."""
        shares, seen = [], False
        for dev in self.devices:
            ops = [(n, max(s, self.t0), min(e, self.t1))
                   for n, s, e in self._ops(dev)
                   if e > self.t0 and s < self.t1]
            coll = union([(s, e) for n, s, e in ops if is_collective(n)])
            comp = [(s, e) for n, s, e in ops if not is_collective(n)]
            seen = seen or bool(coll)
            shares.append(length(subtract(coll, comp)) / 1e9
                          / self.window_s)
        if not seen:
            return None
        return sum(shares) / len(shares)

    def span_seconds(self, name: str) -> float:
        return sum(d for n, s, d in self.spans if n == name
                   and s >= self.t0 and s + d <= self.t1) / 1e9

    def _label(self, s: float, e: float) -> str:
        """The innermost program span, else "host", covering the middle
        of a device gap."""
        mid = 0.5 * (s + e)
        best = None
        for n, ss, d in self.spans:
            if ss <= mid <= ss + d and (best is None or d < best[1]):
                best = (n, d)
        return best[0] if best else "no program span"

    def breakdown(self) -> dict:
        """The device operations that took most time (seconds summed over
        the chips) and the longest idle gaps of the first chip, each by
        the program span the host was in."""
        ops: dict[str, float] = {}
        for dev in self.devices:
            for n, s, e in self._ops(dev):
                s, e = max(s, self.t0), min(e, self.t1)
                if e > s:
                    ops[n] = ops.get(n, 0.0) + (e - s) / 1e9
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = []
        if self.devices:
            dev = sorted(self.devices)[0]
            busy = self.busy_intervals(dev)
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            for i in range(0, len(edges), 2):
                s, e = edges[i], edges[i + 1]
                if e > s:
                    gaps.append((self._label(s, e), (e - s) / 1e9))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[n, v] for n, v in top],
                "idle_gaps": [[n, v] for n, v in gaps[:TOP]]}


def record_from_xspace(path: str, window_pc: tuple[float, float],
                       spans: list) -> dict:
    """Flatten an ``.xplane.pb`` into the record layout.  `window_pc` is
    the window annotation's (enter, exit) on perf_counter, and `spans`
    the program's spans as (name, perf_counter start, seconds)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    devices: dict[str, dict] = {}
    for plane in pd.planes:
        name = plane.name
        if CHIP_PLANE.match(name):
            entry = devices.setdefault(name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = ("ops" if line.name == OPS_LINE else
                       "modules" if line.name == MODULES_LINE else None)
                if key is None:
                    continue
                entry[key].extend([ev.name, ev.start_ns, ev.duration_ns]
                                  for ev in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_ANNOTATION:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise RuntimeError("the window annotation is not in the trace")
    # perf_counter -> profiler clock, through the annotation's start
    offset = window[0] - window_pc[0] * 1e9
    rec_spans = [[n, s * 1e9 + offset, d * 1e9] for n, s, d in spans]
    return {"window": list(window), "devices": devices, "spans": rec_spans}


class Capture:
    """Profiler and program spans over the measured window."""

    def __init__(self):
        import tempfile
        self.dir = tempfile.mkdtemp(prefix="chipbench-profile-")
        self._annotation = None
        self._tracer = None
        self._prev = None
        self._pc = [0.0, 0.0]
        self.record: dict | None = None

    def start(self) -> None:
        import jax
        from repro.obs import trace as obs
        self._tracer = obs.Tracer()
        self._tracer_t0 = time.perf_counter()
        self._prev = obs.install(self._tracer)
        jax.profiler.start_trace(self.dir)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._annotation.__enter__()
        self._pc[0] = time.perf_counter()

    def stop(self) -> None:
        import jax
        from repro.obs import trace as obs
        self._pc[1] = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        obs.install(self._prev)

    def program_spans(self) -> list:
        """The program's closed spans as (name, perf_counter start, s)."""
        out = []
        for ev in self._tracer.events:
            if ev.get("ph") == "E":
                start_us = ev["ts"] - ev["dur"]
                out.append((ev["name"],
                            self._tracer_t0 + start_us / 1e6,
                            ev["dur"] / 1e6))
        return out

    def reduce(self) -> Reduced:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no profile written under {self.dir}")
        self.record = record_from_xspace(paths[0], tuple(self._pc),
                                         self.program_spans())
        return Reduced(self.record)

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
