"""Memory observability — the byte half of `repro.obs` (ISSUE 8).

The paper's headline claim is a *memory* claim: model selection over a
tensor whose dense form never materializes, only its shards do.  This
module makes that claim a machine-checked artifact instead of a README
anecdote, in three layers joined into one ``MemoryLedger``:

* **represented vs resident** — the manifest's ``logical_bytes`` (the
  dense tensor the dataset stands for) against ``resident_bytes`` (what
  any host actually holds), via ``DatasetManifest.byte_ledger()`` — ONE
  accounting shared with ``benchmarks/ingest.py`` so the bench and the
  trace artifact can never disagree about the exascale ratio;
* **static device peaks** — per-rank AOT byte breakdowns
  (argument/output/temp/peak) of the same one-iteration MU program the
  cost tables interrogate (``obs.costs.aot_mu_program``), normalized by
  ``dist.compat.program_memory`` so a backend with no analysis reads as
  *unknown*, never 0;
* **runtime watermarks** — a stdlib host-RSS sampler (``/proc/self/status``
  + ``resource.getrusage`` high-water mark; background thread owned by the
  tracer) and the device allocator watermark behind
  ``dist.compat.device_memory_stats``.

Import discipline matches ``obs.trace``: the host half is stdlib-only
(``repro.io`` could depend on it for free); everything touching jax —
the AOT measurement and the device watermark — imports lazily inside the
function.
"""
from __future__ import annotations

import dataclasses
import json
import os
import resource
import sys
import threading
import time
from typing import Any

from repro.obs import trace as obs

__all__ = [
    "HostMemorySampler",
    "MemoryLedger",
    "accounted_ensemble_bytes",
    "device_watermark",
    "measure_mu_memory",
    "read_host_memory",
]

_KIB = 1024

# dtype-string -> itemsize for the stdlib-only accounting paths
_ITEMSIZE = {"float16": 2, "bfloat16": 2, "float32": 4, "float64": 8,
             "int8": 1, "int16": 2, "int32": 4, "int64": 8}


def _itemsize(dtype: str) -> int:
    return _ITEMSIZE.get(str(dtype), 4)


# ---------------------------------------------------------------------------
# Host watermarks
# ---------------------------------------------------------------------------

def read_host_memory() -> dict[str, int]:
    """Current host memory of this process: ``{"rss_bytes", "hwm_bytes"}``.

    Linux: ``/proc/self/status`` VmRSS (current resident set) and VmHWM
    (the kernel-maintained high-water mark — it cannot miss a spike the
    way a sampler can).  Elsewhere: ``resource.getrusage`` ``ru_maxrss``
    stands in for both (KiB on Linux, bytes on macOS).
    """
    out: dict[str, int] = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["rss_bytes"] = int(line.split()[1]) * _KIB
                elif line.startswith("VmHWM:"):
                    out["hwm_bytes"] = int(line.split()[1]) * _KIB
    except OSError:
        pass
    if "hwm_bytes" not in out:
        ru = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
        hwm = ru if sys.platform == "darwin" else ru * _KIB
        out["hwm_bytes"] = hwm
        out.setdefault("rss_bytes", hwm)
    return out


def device_watermark() -> int | None:
    """Peak device-allocator bytes via the compat probe, or ``None`` when
    the backend exposes no stats (CPU) — unknown is never reported as 0."""
    from repro.dist.compat import device_memory_stats
    stats = device_memory_stats()
    for key in ("peak_bytes_in_use", "bytes_in_use"):
        if key in stats:
            return stats[key]
    return None


class HostMemorySampler:
    """Background host-RSS watermark sampler (stdlib daemon thread).

    The tracer path (``rescalk_run --trace``) starts one for the run and
    stops it when artifacts flush.  Each tick reads ``/proc`` RSS, keeps
    ``(t_seconds, rss_bytes)`` samples plus the running peak, and — when
    a tracer is installed — emits a ``mem/sample`` instant so the
    Perfetto view carries an RSS track.  ``peak_bytes`` folds in the
    kernel VmHWM, so a spike between ticks is still accounted.
    """

    def __init__(self, interval: float = 0.25, *,
                 emit_events: bool = True):
        self.interval = float(interval)
        self.emit_events = emit_events
        self.samples: list[tuple[float, int]] = []
        self.peak_rss_bytes = 0
        self._t0 = time.perf_counter()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def sample_once(self) -> int:
        rss = read_host_memory().get("rss_bytes", 0)
        self.samples.append((time.perf_counter() - self._t0, rss))
        if rss > self.peak_rss_bytes:
            self.peak_rss_bytes = rss
        if self.emit_events:
            obs.event("mem/sample", rss_bytes=rss)
        return rss

    def start(self) -> "HostMemorySampler":
        if self._thread is not None:
            return self
        self.sample_once()
        self._thread = threading.Thread(target=self._loop,
                                        name="obs-mem-sampler", daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self.sample_once()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._thread = None
        self.sample_once()

    @property
    def peak_bytes(self) -> int:
        """max(sampled RSS, kernel high-water mark)."""
        return max(self.peak_rss_bytes,
                   read_host_memory().get("hwm_bytes", 0))


# ---------------------------------------------------------------------------
# Static (AOT) per-rank accounting
# ---------------------------------------------------------------------------

def measure_mu_memory(operand: Any, ks: list[int], *,
                      eps: float | None = None) -> dict[int, dict[str, Any]]:
    """AOT byte breakdown of a one-iteration, one-member MU program per
    rank — ``dist.compat.program_memory`` over the same compiled program
    ``obs.costs.measure_mu_costs`` interrogates (nothing executes, the
    sweep's jit caches are untouched).  Entries are ``{}`` where the
    backend reports no memory analysis: unknown, never 0.
    """
    from repro.dist.compat import program_memory
    from repro.obs.costs import aot_mu_program

    out: dict[int, dict[str, Any]] = {}
    for k in ks:
        try:
            pm = program_memory(aot_mu_program(operand, k, eps=eps))
        except Exception:           # lowering unavailable on this backend
            pm = None
        out[int(k)] = pm or {}
    return out


def accounted_ensemble_bytes(manifest: Any, *, n_members: int,
                             k_max: int) -> int:
    """Accounted peak residency of one batched ensemble program over the
    manifested operand: the unperturbed stored bytes plus ``n_members``
    live perturbed copies, plus the factor ensembles (A dominates R at
    sweep shapes).  This is the formula behind ``benchmarks/ingest.py``'s
    5-GiB virtual acceptance check — kept here so the bench and the trace
    ledger can never drift apart.
    """
    itemsize = _itemsize(manifest.dtype)
    factor_bytes = n_members * (manifest.n_factor * k_max
                                + manifest.m * k_max * k_max) * itemsize
    return int(manifest.resident_bytes) * (1 + n_members) + factor_bytes


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

def _atomic_json_dump(path: str, doc: Any) -> str:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


@dataclasses.dataclass
class MemoryLedger:
    """One sweep's byte ledger — represented vs resident vs peaks.

    Serialized as the ``memory.json`` trace artifact (validated by
    ``scripts/check_trace.py --expect-memory``):

    * ``logical_bytes``  — dense bytes the operand *represents*;
    * ``resident_bytes`` — bytes any host actually holds (stored blocks +
      indices, or per-shard generator state) — manifest-accounted;
    * ``per_k``          — AOT argument/output/temp/peak breakdown of the
      rank-k MU program (``measure_mu_memory``);
    * ``peak_host_bytes`` / ``peak_device_bytes`` — runtime watermarks
      (``None`` = backend reported nothing, never 0);
    * ``kernel_fallbacks`` — panel-budget oracle fallbacks observed
      during the sweep (``kernels/ops.py`` telemetry).
    """
    kind: str
    logical_bytes: int
    resident_bytes: int
    per_k: dict[int, dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    peak_host_bytes: int | None = None
    peak_device_bytes: int | None = None
    accounted_sweep_bytes: int | None = None
    kernel_fallbacks: int = 0
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def compression(self) -> float:
        """logical / resident — the exascale ratio."""
        return self.logical_bytes / max(self.resident_bytes, 1)

    @classmethod
    def from_manifest(cls, manifest: Any, **kw: Any) -> "MemoryLedger":
        """Start a ledger from the one byte accounting everything shares
        (``DatasetManifest.byte_ledger``)."""
        led = manifest.byte_ledger()
        return cls(kind=led["kind"], logical_bytes=led["logical_bytes"],
                   resident_bytes=led["resident_bytes"], **kw)

    def device_peak(self) -> int | None:
        """Best available device-side peak: the runtime allocator
        watermark when the backend reports one, else the largest per-rank
        AOT peak; ``None`` when neither exists."""
        if self.peak_device_bytes:
            return self.peak_device_bytes
        peaks = [e["peak"] for e in self.per_k.values() if "peak" in e]
        return max(peaks) if peaks else None

    # -- IO -----------------------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return {
            "ledger": {"kind": self.kind,
                       "logical_bytes": int(self.logical_bytes),
                       "resident_bytes": int(self.resident_bytes),
                       "compression": self.compression},
            "per_k": {str(k): dict(v) for k, v in sorted(self.per_k.items())},
            "runtime": {"peak_host_bytes": self.peak_host_bytes,
                        "peak_device_bytes": self.peak_device_bytes,
                        "accounted_sweep_bytes": self.accounted_sweep_bytes},
            "fallbacks": {"count": int(self.kernel_fallbacks)},
            "meta": dict(self.meta),
        }

    def save(self, path: str) -> str:
        return _atomic_json_dump(path, self.to_dict())

    @classmethod
    def load(cls, path: str) -> "MemoryLedger":
        with open(path) as f:
            d = json.load(f)
        led, rt = d["ledger"], d.get("runtime", {})
        return cls(kind=led["kind"], logical_bytes=led["logical_bytes"],
                   resident_bytes=led["resident_bytes"],
                   per_k={int(k): v for k, v in d.get("per_k", {}).items()},
                   peak_host_bytes=rt.get("peak_host_bytes"),
                   peak_device_bytes=rt.get("peak_device_bytes"),
                   accounted_sweep_bytes=rt.get("accounted_sweep_bytes"),
                   kernel_fallbacks=d.get("fallbacks", {}).get("count", 0),
                   meta=d.get("meta", {}))

    # -- rendering ----------------------------------------------------------

    def summary_line(self) -> str:
        """The one-line sweep statement (``[obs] memory: ...``)."""
        dev = self.device_peak()
        parts = [f"represented {self.logical_bytes / 2**30:.2f} GiB",
                 f"resident {self.resident_bytes / 2**20:.1f} MiB "
                 f"({self.compression:.0f}x)"]
        if self.peak_host_bytes is not None:
            parts.append(f"host peak {self.peak_host_bytes / 2**20:.1f} MiB")
        parts.append("device peak "
                     + (f"{dev / 2**20:.1f} MiB" if dev is not None
                        else "n/a"))
        if self.kernel_fallbacks:
            parts.append(f"{self.kernel_fallbacks} kernel fallback(s)")
        return ", ".join(parts)

    def summarize(self) -> str:
        """Multi-line ledger table for summary.txt."""
        lines = [f"memory ledger ({self.kind}): {self.summary_line()}"]
        if self.accounted_sweep_bytes is not None:
            lines.append(f"accounted sweep residency: "
                         f"{self.accounted_sweep_bytes / 2**20:.1f} MiB")
        if self.per_k:
            hdr = (f"{'k':>4} {'arg_MiB':>9} {'out_MiB':>9} "
                   f"{'temp_MiB':>9} {'peak_MiB':>9}")
            lines += [hdr, "-" * len(hdr)]
            for k, e in sorted(self.per_k.items()):
                if not e:
                    lines.append(f"{k:>4} {'(no memory analysis)':>38}")
                    continue
                lines.append(
                    f"{k:>4} {e['argument'] / 2**20:>9.3f} "
                    f"{e['output'] / 2**20:>9.3f} "
                    f"{e['temp'] / 2**20:>9.3f} "
                    f"{e['peak'] / 2**20:>9.3f}")
        return "\n".join(lines)
