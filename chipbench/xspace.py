"""The traced window's profile, read for what ``tracing.py`` leaves out: the
program's spans on the profiler's own clock, and each device operation's
scope path.

The program opens a ``jax.profiler.TraceAnnotation`` for every span and
instant of its tracer (``repro.obs.trace``), and names the phases of its MU
step on the device (``jax.named_scope``: ``mu``, and ``products`` around
the reads of the stored operand).  ``tracing.Capture`` writes the window's
profile to a directory of its own and keeps it until the per-layer readers
have run; ``window_record(ctx)`` finds that profile by the window
annotation whose start and end are the reduced trace's, and flattens it a
second time (cached per file) into

    {"window": [t0, t1],
     "host_spans": [[name, start, dur], ...],
     "devices": {"<plane>": {"ops": [[name, start, dur, scope], ...],
                             "modules": [[name, start, dur], ...]}}}

all in ns on the profiler's clock.  A host span is a host-plane event named
as one of the tracer's spans in the window, or as its compile event; an
op's name is its HLO instruction name and its scope the op_name path of
its HLO metadata.  The op events carry no metadata themselves: the scope
comes from the HLO proto of the program running the op, which the
profiler keeps on its metadata plane (``program_op_names``).
``Window`` computes every number from such a record alone, so it can be
checked on a recorded trace without a chip (``tests/test_xspace.py``).
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import tempfile

from chipbench import tracing

# the profiler's plane of per-program HLO protos, and their stat
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# repro.obs.trace.Tracer.compile_event's instant, one per program obtained
COMPILE_EVENT = "xla/compile"
NO_SPAN = "no program span"
PROFILE_GLOB = os.path.join("chipbench-profile-*", "**", "*.xplane.pb")
# "vmap(jit(f))" -> "jit(f)" -> "f": a transform's wrapper around a scope
WRAPPER = re.compile(r"^[\w.-]+\((.*)\)$")


def scope_parts(path: str) -> set[str]:
    """The scope names of an op_name path, transform wrappers removed:
    'jit(f)/vmap(mu)/products/dot_general' -> {'f', 'mu', 'products',
    'dot_general'}."""
    out = set()
    for part in path.split("/"):
        m = WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = WRAPPER.match(part)
        out.add(part)
    return out


def instruction(name: str) -> str:
    """'%fusion.287 = bf16[2,20]{...} fusion(...)' -> 'fusion.287'."""
    return name.split(" = ", 1)[0].lstrip("%")


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a serialized protobuf message: an int for
    a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped (none is read here)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} in a profile")
        yield field, value


def _first(buf, field: int):
    return next((v for f, v in _fields(buf) if f == field), None)


def _text(buf) -> str:
    return bytes(buf).decode() if buf is not None else ""


def _op_names(module) -> dict[str, str]:
    """{instruction: op_name} of a serialized HloModuleProto: its
    computations (3), their instructions (2), each one's name (1) and
    OpMetadata (7), whose op_name is field 2."""
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(inst):
                if h == 1:
                    name = _text(v)
                elif h == 7:
                    meta = v
            if name and meta is not None:
                out[name] = _text(_first(meta, 2))
    return out


def program_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """{program: {instruction: op_name}} from the HLO protos the profiler
    keeps on its metadata plane, one per program run in the capture, keyed
    as the programs' "XLA Modules" events are named.  In the XSpace proto:
    planes (1); a plane's name (2), event metadata (4: map entries whose
    value, 2, names the program, 2, and holds stats, 5) and stat metadata
    (5: id 1, name 2); a stat's metadata id (1) and bytes (6), an
    HloProto whose module is field 1."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_names = {}
        for g, entry in _fields(plane):
            if g == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1)] = _text(_first(meta, 2))
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            for h, stat in _fields(meta):
                if h == 5 and stat_names.get(_first(stat, 1)) == HLO_STAT:
                    out[_text(_first(meta, 2))] = _op_names(
                        _first(_first(stat, 6), 1))
    return out


def _scoped(ops, modules, names) -> list[list]:
    """[name, start, dur, op_name] of each op event, its op_name looked up
    in the program whose execution holds the op's start ("" where none
    does, or its program's HLO is not in the profile)."""
    mods = sorted((float(s), float(s) + float(d), n) for n, s, d in modules)
    out, j = [], 0
    for name, s, d in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        prog = mods[j][2] if j < len(mods) and mods[j][0] <= s else None
        inst = instruction(name)
        out.append([inst, s, d, names.get(prog, {}).get(inst, "")])
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """Flatten one ``.xplane.pb``: the window, every host-plane event, and
    each chip's ops (with scopes) and modules."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    names = program_op_names(data)
    window, host, devices = None, [], {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if tracing.CHIP_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                rows = (ops if line.name == tracing.OPS_LINE else
                        modules if line.name == tracing.MODULES_LINE else
                        None)
                if rows is not None:
                    rows.extend([ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events)
            devices[plane.name] = {"ops": _scoped(ops, modules, names),
                                   "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tracing.WINDOW_ANNOTATION:
                        window = [ev.start_ns, ev.start_ns + ev.duration_ns]
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"window": window, "host": host, "devices": devices}


def find_profile(t0: float, t1: float) -> str | None:
    """The profile in the temporary directory (where ``Capture`` writes)
    whose window annotation runs from `t0` to `t1`; newest first."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), PROFILE_GLOB),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        if load(path)["window"] == [t0, t1]:
            return path
    return None


def window_of(raw: dict, span_names) -> dict:
    """The record (module doc) of a flattened profile (``load``): its host
    events named as one of `span_names` or as the compile event."""
    names = set(span_names) | {COMPILE_EVENT}
    return {"window": raw["window"],
            "host_spans": [e for e in raw["host"] if e[0] in names],
            "devices": raw["devices"]}


def window_record(ctx) -> dict | None:
    """The window's record for a metric reader's context: the host events
    named as the tracer's spans in the window; None where its profile is
    gone."""
    trace = ctx.trace
    path = find_profile(trace.t0, trace.t1)
    if path is None:
        return None
    return window_of(load(path), (n for n, _, _ in trace.spans))


class Window:
    """Numbers of one window's record (module doc)."""

    def __init__(self, record: dict):
        self.t0, self.t1 = (float(t) for t in record["window"])
        self.devices = {d: v for d, v in record["devices"].items()
                        if tracing.CHIP_PLANE.match(d)}
        self.host_spans = [(n, float(s), float(s) + float(d))
                           for n, s, d in record.get("host_spans", [])]

    # -- host spans ------------------------------------------------------

    def spans(self, name: str) -> list[tuple[float, float]]:
        """(start, end) of the host spans named `name` inside the window."""
        return [(s, e) for n, s, e in self.host_spans
                if n == name and s >= self.t0 and e <= self.t1]

    def span_seconds(self, name: str) -> float:
        return sum(e - s for s, e in self.spans(name)) / 1e9

    # -- device ops ------------------------------------------------------

    def self_times(self, dev: str) -> list[tuple[str, str, float]]:
        """(name, scope, self ns) of each op of chip `dev`, clipped to the
        window: its time less that of the ops nested inside it (a
        ``while`` spans its body's ops)."""
        ops = []
        for op in self.devices[dev]["ops"]:
            name, s, d = op[0], float(op[1]), float(op[2])
            scope = op[3] if len(op) > 3 else ""
            ops.append((s, s + d, name, scope))
        ops.sort(key=lambda o: (o[0], -o[1]))
        children: list[list[tuple[float, float]]] = [[] for _ in ops]
        stack: list[int] = []
        for i, (s, e, _, _) in enumerate(ops):
            while stack and ops[stack[-1]][1] <= s:
                stack.pop()
            if stack and e <= ops[stack[-1]][1]:
                children[stack[-1]].append((s, e))
            stack.append(i)
        out = []
        for (s, e, name, scope), kids in zip(ops, children):
            own = tracing.clip([(s, e)], self.t0, self.t1)
            if not own:
                continue
            inner = tracing.clip(tracing.union(kids), self.t0, self.t1)
            out.append((name, scope,
                        tracing.length(own) - tracing.length(inner)))
        return out

    def scoped_seconds(self, within=(), outside=()) -> float | None:
        """Self seconds, averaged over the chips, of the ops whose scope
        path holds every name of `within` and none of `outside`; None
        where no op does."""
        total, found = 0.0, False
        for dev in self.devices:
            for _, scope, ns in self.self_times(dev):
                parts = scope_parts(scope)
                if parts.issuperset(within) and parts.isdisjoint(outside):
                    total += ns
                    found = True
        if not found:
            return None
        return total / len(self.devices) / 1e9

    # -- the breakdown ---------------------------------------------------

    def timeline(self) -> tuple[list[float], list[str]]:
        """The window cut at every host span's edges: the edges, and for
        each piece between two edges the innermost (shortest) host span
        covering it, else NO_SPAN."""
        edges = sorted({self.t0, self.t1} | {
            x for _, a, b in self.host_spans for x in (a, b)
            if self.t0 < x < self.t1})
        labels = []
        for a, b in zip(edges, edges[1:]):
            covering = [(e - s, n) for n, s, e in self.host_spans
                        if s <= a and e >= b]
            labels.append(min(covering)[1] if covering else NO_SPAN)
        return edges, labels

    @staticmethod
    def _cover(s: float, e: float, edges, labels) -> list[list]:
        """[[label, seconds], ...] of the interval [s, e) on a timeline,
        largest first."""
        out: dict[str, float] = {}
        i = max(bisect.bisect_right(edges, s) - 1, 0)
        while i < len(labels) and edges[i] < e:
            a, b = max(edges[i], s), min(edges[i + 1], e)
            if b > a:
                out[labels[i]] = out.get(labels[i], 0.0) + (b - a) / 1e9
            i += 1
        return sorted(([n, v] for n, v in out.items()), key=lambda x: -x[1])

    def idle_gaps(self, dev: str) -> list[tuple[float, float]]:
        busy = tracing.union(tracing.clip(
            [(float(o[1]), float(o[1]) + float(o[2]))
             for o in self.devices[dev]["ops"]], self.t0, self.t1))
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def breakdown(self) -> dict:
        """The device ops that took most self time (seconds summed over the
        chips; named 'instruction [scope]'), the longest idle gaps of the
        first chip, each split among the host spans covering it, and the
        first chip's idle seconds by covering span (``idle_by_span``)."""
        ops: dict[str, float] = {}
        for dev in self.devices:
            for name, scope, ns in self.self_times(dev):
                key = f"{name} [{scope}]" if scope else name
                ops[key] = ops.get(key, 0.0) + ns / 1e9
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:tracing.TOP]
        gaps, by_span = [], {}
        if self.devices:
            edges, labels = self.timeline()
            for s, e in self.idle_gaps(sorted(self.devices)[0]):
                pieces = self._cover(s, e, edges, labels)
                gaps.append([(e - s) / 1e9, pieces])
                for n, v in pieces:
                    by_span[n] = by_span.get(n, 0.0) + v
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[n, v] for n, v in top],
                "idle_gaps": gaps[:tracing.TOP],
                "idle_by_span": dict(sorted(by_span.items(),
                                            key=lambda kv: -kv[1]))}


def cut(record: dict, t0: float, t1: float) -> dict:
    """The part of a record that lies in [t0, t1), as a record whose window
    is [t0, t1): what ``tests/data/xspace/`` keeps of a chip run."""
    def keep(rows):
        return [r for r in rows
                if float(r[1]) < t1 and float(r[1]) + float(r[2]) > t0]
    return {"window": [t0, t1],
            "host_spans": keep(record["host_spans"]),
            "devices": {d: {"ops": keep(v["ops"]),
                            "modules": keep(v["modules"])}
                        for d, v in record["devices"].items()}}

