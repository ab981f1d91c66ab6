"""The sweep scheduler — plans, executes, checkpoints the (k, q) grid.

Model selection (paper Alg. 1) is a grid of independent work units: for
every candidate rank k, r perturbation members q.  This module owns that
grid end to end:

  * ``plan_sweep`` lays the units out deterministically — in "batched"
    mode one unit covers a contiguous member group per k (grouped with
    ``dist.elastic.ensemble_plan`` when the sweep is split across
    ``n_pods`` hosts); in "loop" mode every (k, q) pair is its own unit
    (finest checkpoint granularity, the sequential reference); in "grid"
    mode the whole (k, q) grid flattens k-major into ``GridChunk``s —
    each chunk ONE cross-k padded device program and ONE checkpoint
    (coarsest granularity, fewest compiles).
  * ``SweepScheduler`` executes units via selection/ensemble.py (batched
    vmap program, mesh-sharded program, or sequential loop), with
    per-unit checkpoint/resume (repro.ckpt) and bounded retry.  Unit
    checkpoint tags derive from the (k, members) identity — NOT from PRNG
    key internals, which were collision-prone and version-dependent (the
    bug this subsystem absorbs from the old launch/rescalk_run closure).
  * After all units of a k complete, the per-k reduction (custom
    clustering -> silhouettes -> R regression -> reconstruction error)
    runs once, and the pluggable criterion (selection/criteria.py) picks
    k_opt.  A ``SelectionReport`` (selection/report.py) records curves,
    per-unit timings and reuse flags.

The historical ``repro.core.rescalk`` types (RescalkConfig / KResult /
RescalkResult) live in selection/types.py (dependency-free, cycle-safe)
and are re-exported both here and by the core compatibility wrapper.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro import ckpt
from repro.core.clustering import ClusterResult, custom_cluster
from repro.core.regression import regress_R
from repro.core.rescal import rel_error
from repro.core.silhouette import SilhouetteResult, silhouettes
from repro.dist.compat import cache_keyed_on_metadata, capture_compiles
from repro.dist.elastic import StragglerMonitor, ensemble_plan
from repro.obs import trace as obs
from repro.resilience import RetryPolicy, faults

from . import criteria
from .ensemble import EnsembleResult, run_ensemble, run_sweep_batched
from .report import SelectionReport, UnitRecord
from .types import KResult, RescalkConfig, RescalkResult

__all__ = ["GridChunk", "KResult", "RescalkConfig", "RescalkResult",
           "SweepInterrupted", "SweepScheduler", "UnitOutcome", "WorkUnit",
           "plan_sweep", "reduce_k"]


# ---------------------------------------------------------------------------
# Work-unit planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WorkUnit:
    """One schedulable cell of the (k, q) grid: a contiguous member group
    of one candidate rank.  ``uid`` is the checkpoint tag — a pure function
    of the unit's position in the grid, stable across JAX versions, PRNG
    implementations and restarts."""
    index: int
    k: int
    members: tuple[int, ...]

    @property
    def uid(self) -> str:
        return f"unit_k{self.k}_q{self.members[0]}-{self.members[-1]}"

    def keys(self, cfg) -> "jax.Array":
        """This unit's member keys — delegated to the sweep's single key
        home (``ensemble.unit_keys``), so every mode shares one
        discipline."""
        from .ensemble import unit_keys
        return unit_keys(cfg, self.k, self.members)


@dataclasses.dataclass(frozen=True)
class GridChunk:
    """One schedulable chunk of the flattened cross-k (k, q) grid (mode
    "grid"): a contiguous run of cells in the canonical k-major,
    member-minor order, executed as ONE padded-to-k_max device program
    (ensemble.run_sweep_batched).  Because the cells are a contiguous range
    of a deterministic order, the (first, last) cell pair fully determines
    the chunk's contents — so ``uid`` stays pure grid identity, and a
    re-chunked sweep (different grid_chunk) can still legitimately reuse
    any checkpointed chunk whose cell range coincides."""
    index: int
    cells: tuple[tuple[int, int], ...]   # ((k, q), ...)
    k_max: int

    @property
    def uid(self) -> str:
        (k0, q0), (k1, q1) = self.cells[0], self.cells[-1]
        return f"grid_k{k0}q{q0}-k{k1}q{q1}"

    def keys(self, cfg) -> "jax.Array":
        """Per-cell member keys, one per (k, q) — same key home as
        ``WorkUnit.keys``, which is what makes grid and per-k modes
        provably agree draw-for-draw.  Derived once per rank, then
        indexed per cell."""
        from .ensemble import unit_keys
        per_k = {k: unit_keys(cfg, k, tuple(range(cfg.n_perturbations)))
                 for k in dict.fromkeys(k for k, _ in self.cells)}
        return jax.numpy.stack([per_k[k][q] for k, q in self.cells])


def plan_sweep(cfg: RescalkConfig, *, mode: str = "batched",
               n_pods: int = 1, grid_chunk: int | None = None
               ) -> list[WorkUnit] | list[GridChunk]:
    """Deterministic unit grid for the sweep.  "batched": members of each k
    grouped contiguously over `n_pods` chunks (dist.elastic.ensemble_plan);
    "loop": one unit per (k, q); "grid": the whole (k, q) grid flattened
    k-major and split into chunks of `grid_chunk` cells (default: one
    chunk per pod), each chunk one cross-k device program and one
    checkpoint."""
    if mode == "grid":
        cells = [(k, q) for k in cfg.ks
                 for q in range(cfg.n_perturbations)]
        if grid_chunk is None:
            grid_chunk = -(-len(cells) // n_pods)
        if grid_chunk <= 0:
            raise ValueError(f"grid_chunk must be positive, got "
                             f"{grid_chunk}")
        k_max = max(cfg.ks)
        chunks: list[GridChunk] = []
        for i in range(0, len(cells), grid_chunk):
            chunks.append(GridChunk(index=len(chunks),
                                    cells=tuple(cells[i:i + grid_chunk]),
                                    k_max=k_max))
        return chunks
    if mode not in ("batched", "loop"):
        raise ValueError(f"unknown sweep mode {mode!r}")
    if grid_chunk is not None:
        raise ValueError("grid_chunk only applies to mode='grid'")
    units: list[WorkUnit] = []
    for k in cfg.ks:
        if mode == "loop":
            groups = [[q] for q in range(cfg.n_perturbations)]
        else:
            groups = ensemble_plan(cfg.n_perturbations, n_pods)
        for g in groups:
            if not g:
                continue
            units.append(WorkUnit(index=len(units), k=k, members=tuple(g)))
    return units


def reduce_k(X, cfg: RescalkConfig, k: int, A_ens, R_ens,
             member_errors: np.ndarray) -> KResult:
    """The per-k reduction of Alg. 1: align the ensemble (custom
    clustering), score stability (silhouettes), regress R against the
    median factor, and measure the robust reconstruction error.  Shared by
    the scheduler and the legacy core.rescalk loop so the two paths cannot
    drift.  `X` may be dense or a ``core.sparse.BCSR`` (the regression and
    error swap to their spmm twins; clustering is factor-only either way)."""
    from repro.core.sparse import BCSR, sparse_regress_R, sparse_rel_error
    # each stage's span ends when its result is on the host or ready on the
    # device: the next stage needs it anyway, so blocking adds no wait
    with obs.span("reduce/cluster", k=k):
        clus: ClusterResult = jax.block_until_ready(
            custom_cluster(A_ens, R_ens))
    with obs.span("reduce/silhouette", k=k):
        sil: SilhouetteResult = jax.block_until_ready(
            silhouettes(clus.A_aligned))
    sparse = isinstance(X, BCSR)
    with obs.span("reduce/regress", k=k):
        if sparse:
            A_med = jax.numpy.asarray(clus.A_median)
            R_reg = sparse_regress_R(X, A_med, iters=cfg.regress_iters)
        else:
            R_reg = regress_R(X, clus.A_median, iters=cfg.regress_iters)
        jax.block_until_ready(R_reg)
    with obs.span("reduce/error", k=k):
        if sparse:
            err = float(sparse_rel_error(X, A_med, R_reg))
        else:
            err = float(rel_error(X, clus.A_median, R_reg))
    return KResult(
        k=k, s_min=float(sil.s_min), s_mean=float(sil.s_mean),
        rel_err=err, A_median=np.asarray(clus.A_median),
        R_regress=np.asarray(R_reg),
        member_errors=np.asarray(member_errors))


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------

class SweepInterrupted(RuntimeError):
    """Raised when ``stop_after_units`` halts the sweep mid-run (the
    deterministic stand-in for a kill: completed units are checkpointed,
    the rest are not)."""

    def __init__(self, executed: int, completed: int, total: int,
                 resumable: bool = True):
        self.executed = executed     # units computed this run
        self.completed = completed   # units done overall (incl. reused)
        self.total = total
        self.resumable = resumable   # False when no ckpt_dir was set
        tail = ("rerun with the same ckpt_dir to resume" if resumable else
                "no ckpt_dir was set, so completed units were NOT "
                "checkpointed and a rerun recomputes everything")
        super().__init__(f"sweep interrupted after {executed} computed "
                         f"units ({completed}/{total} done; {tail})")


@dataclasses.dataclass
class UnitOutcome:
    unit: "WorkUnit | GridChunk"
    result: EnsembleResult | None   # dropped (None) once its k is reduced
    seconds: float
    reused: bool
    retries: int
    attempts: int = 1               # executions this run (0 when reused)
    backoff: float = 0.0            # total RetryPolicy sleep, seconds
    straggler: bool = False         # flagged by the StragglerMonitor
    baseline: float | None = None   # monitor's median seconds at flag time
    peak_host: int | None = None    # host HWM bytes when the unit finished
    peak_device: int | None = None  # device allocator peak (None on CPU)
    fallbacks: int = 0              # pallas->oracle fallbacks this unit


class SweepScheduler:
    """Drives the (k, q) unit grid over a tensor X.

    Parameters
    ----------
    cfg : RescalkConfig
    mode : "batched" (one program per unit, members vmapped) | "loop" |
        "grid" (the whole (k, q) grid padded to k_max and chunked into
        cross-k device programs — ensemble.run_sweep_batched)
    mesh : optional jax Mesh — routes units through the sharded ensemble
        program (members — or grid cells — spread over the pod/ensemble
        axis when present)
    ckpt_dir : per-unit checkpoint root; units found there are reused, not
        recomputed (the resume contract CI asserts).  In grid mode the
        granularity is per-grid-chunk; tags still derive from grid
        identity (GridChunk.uid) and reuse counting is unchanged
    criterion : key into selection.criteria.CRITERIA
    n_pods : split each k's members into this many host-level units
        (grid mode: the default chunk count)
    grid_chunk : cells per grid-mode chunk (default: one chunk per pod).
        Deliberately NOT part of the checkpoint fingerprint — chunk uids
        encode their exact cell range, so re-chunking a sweep reuses only
        chunks whose contents truly coincide
    retry : the unit RetryPolicy (resilience.policy) — classified
        transient-vs-deterministic errors, deterministic seeded backoff,
        optional per-attempt deadline (straggler-shrunk on retries).
        Fault injection goes through the `sched/unit` seam of a
        `resilience.faults.FaultPlan` (which replaced the old ad-hoc
        ``failure_injector`` callable)
    max_retries : back-compat alias — ``RetryPolicy(max_attempts=
        max_retries + 1)`` when ``retry`` is not given
    stop_after_units : compute at most this many units (checked before
        each execution; 0 = resume-only), then raise SweepInterrupted —
        the testing/CI hook for kill-and-resume drills
    async_ckpt : write unit checkpoints on a background thread; the
        previous write is joined (and any failure re-raised) at the next
        checkpoint boundary, so a failed save can never silently age the
        restore point
    report_path : write the SelectionReport JSON here after the sweep
    """

    def __init__(self, cfg: RescalkConfig, *, mode: str = "batched",
                 mesh=None, ckpt_dir: str | None = None,
                 criterion: str = "threshold", n_pods: int = 1,
                 grid_chunk: int | None = None,
                 retry: RetryPolicy | None = None,
                 max_retries: int = 1, stop_after_units: int | None = None,
                 async_ckpt: bool = False,
                 report_path: str | None = None, verbose: bool = False,
                 straggler_factor: float = 2.5):
        criteria.require(criterion)
        if mesh is not None and mode not in ("batched", "grid"):
            raise ValueError(
                "mode='loop' is host-only (the sequential reference / "
                "memory-bound fallback); drop mesh= or use mode='batched'")
        if mode == "grid" and cfg.init != "random":
            # fail before planning, not after max_retries wasted attempts
            raise NotImplementedError(
                "mode='grid' supports init='random' only (NNDSVD depends "
                "on the perturbed tensor, which only exists inside the "
                "grid program); use mode='batched' for nndsvd")
        self.cfg = cfg
        self.mode = mode
        self.mesh = mesh
        self.ckpt_dir = ckpt_dir
        self.criterion = criterion
        self.retry = (retry if retry is not None
                      else RetryPolicy(max_attempts=max_retries + 1))
        self.max_retries = self.retry.max_attempts - 1
        self.stop_after_units = stop_after_units
        self.async_ckpt = async_ckpt
        self._pending_save: ckpt.AsyncSave | None = None
        self.report_path = report_path
        self.verbose = verbose
        # flags units whose wall time blows past factor x the median of
        # previously executed units (dist.elastic; was train-loop-only)
        self.stragglers = StragglerMonitor(factor=straggler_factor)
        with obs.span("sched/plan", mode=mode):
            self.units = plan_sweep(cfg, mode=mode, n_pods=n_pods,
                                    grid_chunk=grid_chunk)
        if mesh is not None and mode == "grid":
            # deterministic config error: surface it here, not inside unit
            # execution after max_retries identical failures
            from repro.dist.sharding import ENSEMBLE_AXIS
            pods = dict(mesh.shape).get(ENSEMBLE_AXIS, 1)
            bad = [u.uid for u in self.units if len(u.cells) % pods]
            if bad:
                raise ValueError(
                    f"grid chunks {bad} do not shard evenly over "
                    f"pods={pods}; pick a grid_chunk (or n_pods) that "
                    f"keeps every chunk divisible by the pod count")
        self.report: SelectionReport | None = None

    # -- checkpoint-config guard --------------------------------------------

    def _fingerprint(self, X) -> dict:
        """What a unit checkpoint's validity depends on: the full sweep
        config, the execution mode (batched/loop agree to tolerance but the
        mesh's blocked noise does not), the mesh layout, and the operand's
        ``io.manifest`` fingerprint (shape + dtype + content digest +
        sparsity structure — the digest that used to be inlined here as an
        ad-hoc two-moment hash).  Unit tags alone are deliberately
        config-blind (pure grid identity), so this guard is what stops a
        resumed sweep from silently reusing units computed under a
        different configuration or against different data."""
        from repro.io.manifest import manifest_of
        fp = dataclasses.asdict(self.cfg)
        fp.update(mode=self.mode,
                  manifest=manifest_of(X).fingerprint(),
                  mesh=None if self.mesh is None else
                  {str(a): int(s) for a, s in dict(self.mesh.shape).items()})
        return fp

    def _check_ckpt_config(self, X) -> None:
        os.makedirs(self.ckpt_dir, exist_ok=True)
        path = os.path.join(self.ckpt_dir, "sweep.json")
        fp = self._fingerprint(X)
        if os.path.exists(path):
            with open(path) as f:
                stored = json.load(f)
            if stored != fp:
                bad = sorted(k for k in set(stored) | set(fp)
                             if stored.get(k) != fp.get(k))
                raise ValueError(
                    f"checkpoint dir {self.ckpt_dir!r} was written by a "
                    f"different sweep configuration (mismatched: {bad}); "
                    f"resuming would silently reuse stale units — use a "
                    f"fresh ckpt_dir or delete it")
            return
        ckpt.atomic_json_dump(path, fp, indent=1)

    # -- unit execution -----------------------------------------------------

    @staticmethod
    def _operand_dtype(X):
        return getattr(X, "dtype", None) or X.data.dtype

    def _unit_like(self, X, unit: WorkUnit | GridChunk) -> dict:
        from repro.io.manifest import operand_dims
        m, n = operand_dims(X)
        dtype = self._operand_dtype(X)
        sds = jax.ShapeDtypeStruct
        if isinstance(unit, GridChunk):
            c, km = len(unit.cells), unit.k_max
            return {"A": sds((c, n, km), dtype),
                    "R": sds((c, m, km, km), dtype),
                    "errors": sds((c,), dtype)}
        r_u, k = len(unit.members), unit.k
        return {"A": sds((r_u, n, k), dtype),
                "R": sds((r_u, m, k, k), dtype),
                "errors": sds((r_u,), dtype)}

    def _try_restore(self, X, unit: WorkUnit) -> UnitOutcome | None:
        if not self.ckpt_dir:
            return None
        tag = os.path.join(self.ckpt_dir, unit.uid)
        if ckpt.latest_step(tag) is None:
            return None
        with obs.span("sched/restore", uid=unit.uid):
            try:
                tree, _ = ckpt.restore(tag, self._unit_like(X, unit))
            except ckpt.CheckpointError:
                # every step of this unit's checkpoint failed verification
                # (restore quarantined them + emitted ckpt/quarantine);
                # fall through to recomputing the unit
                return None
        if self.verbose:
            print(f"  [ckpt] reused {unit.uid}")
        return UnitOutcome(unit=unit, result=EnsembleResult(**tree),
                           seconds=0.0, reused=True, retries=0, attempts=0)

    def _unit_deadline(self, attempt: int) -> float | None:
        """Per-attempt wall-clock budget.  The StragglerMonitor is a soft
        signal into the policy: once the sweep has a baseline, a RETRIED
        attempt's deadline shrinks to factor x the median unit time — a
        unit that was slow enough to need a second try doesn't get to
        wait out the full deadline again."""
        limit = self.retry.deadline
        if limit is None:
            return None
        base = self.stragglers.baseline
        if attempt > 0 and base is not None:
            limit = min(limit, self.stragglers.factor * base)
        return limit

    def _surface_pending_save(self) -> None:
        """Join the in-flight async checkpoint write, re-raising any
        background failure at this (the next) checkpoint boundary."""
        handle, self._pending_save = self._pending_save, None
        if handle is not None:
            handle.join()

    def _execute_unit(self, X, unit: WorkUnit) -> UnitOutcome:
        # kernel-fallback attribution: ops.py bumps a process counter on
        # every budget-driven pallas->oracle downgrade; the delta around
        # this unit's execution is its fallback count
        from repro.kernels.ops import kernel_fallbacks
        fb0 = kernel_fallbacks()
        timing: dict[str, float] = {}

        def _attempt(attempt: int):
            faults.fire("sched/unit", uid=unit.uid, attempt=attempt)
            with obs.span("sched/execute", uid=unit.uid, attempt=attempt):
                t0 = time.perf_counter()
                # keyed on metadata: a cached unit program carries this
                # build's "mu" / "products" scopes into device profiles
                with obs.span("sched/dispatch", uid=unit.uid), \
                        cache_keyed_on_metadata():
                    if isinstance(unit, GridChunk):
                        res = run_sweep_batched(X, unit.cells, self.cfg,
                                                mesh=self.mesh)
                    else:
                        res = run_ensemble(X, unit.k, self.cfg,
                                           members=unit.members,
                                           mesh=self.mesh, mode=self.mode)
                with obs.span("sched/wait", uid=unit.uid):
                    jax.block_until_ready(res.A)
                timing["dt"] = time.perf_counter() - t0
            return res

        def _on_retry(next_attempt: int, err: BaseException,
                      pause: float) -> None:
            obs.event("sched/retry", uid=unit.uid, attempt=next_attempt,
                      backoff=round(pause, 6), error=type(err).__name__)
            if self.verbose:
                print(f"  [retry] {unit.uid} attempt {next_attempt} after "
                      f"{type(err).__name__} (backoff {pause:.3f}s)")

        res, stats = self.retry.call(_attempt, key=unit.uid,
                                     on_retry=_on_retry,
                                     deadline_fn=self._unit_deadline)
        dt = timing["dt"]
        # straggler flagging against the median of prior units; flagged
        # durations stay OUT of the baseline so one slow unit doesn't
        # normalize slowness for the rest of the sweep
        straggler = self.stragglers.record(unit.index, dt)
        baseline = self.stragglers.baseline
        if straggler:
            print(f"  [straggler] {unit.uid} took {dt:.3f}s "
                  f"(baseline {baseline:.3f}s)")
            obs.event("sched/straggler", uid=unit.uid, seconds=dt,
                      baseline=baseline)
        if self.ckpt_dir:
            with obs.span("sched/checkpoint", uid=unit.uid):
                self._surface_pending_save()
                tag = os.path.join(self.ckpt_dir, unit.uid)
                if self.async_ckpt:
                    self._pending_save = ckpt.save_async(tag, 0,
                                                         res._asdict())
                else:
                    ckpt.save(tag, 0, res._asdict())
        # unit-boundary watermarks: kernel host HWM (cannot miss a spike)
        # + device allocator peak where the backend reports one.  Pure
        # host-side reads — nothing enters any traced program.
        from repro.obs.memory import device_watermark, read_host_memory
        with obs.span("sched/watermark", uid=unit.uid):
            peak_host = read_host_memory().get("hwm_bytes")
            peak_device = device_watermark()
            fallbacks = kernel_fallbacks() - fb0
        return UnitOutcome(unit=unit, result=res, seconds=dt, reused=False,
                           retries=stats.attempts - 1,
                           attempts=stats.attempts,
                           backoff=stats.backoff_seconds,
                           straggler=straggler,
                           baseline=baseline,
                           peak_host=peak_host,
                           peak_device=peak_device,
                           fallbacks=fallbacks)

    # -- the sweep ----------------------------------------------------------

    def run(self, X) -> RescalkResult:
        """Run (or resume) the sweep over X.  Under an installed tracer the
        sweep's compiles and persistent-cache reads become ``xla/compile``
        events of that tracer."""
        tracer = obs.current()
        if tracer is None:
            return self._run(X)
        with capture_compiles(sink=tracer.compile_event):
            return self._run(X)

    def _run(self, X) -> RescalkResult:
        from .ensemble import _is_sharded_bcsr
        cfg = self.cfg
        ks = cfg.ks
        if self.ckpt_dir:
            self._check_ckpt_config(X)
        # the per-k reduction runs on one host: a sharded operand collapses
        # to its merged global BCSR (same permuted factor space).  Without
        # a mesh the units execute on the merged tensor too — merged ONCE
        # here, not per unit (run_ensemble would otherwise re-merge on
        # every call).
        X_red = X.to_bcsr() if _is_sharded_bcsr(X) else X
        X_exec = X if self.mesh is not None else X_red
        grid = self.mode == "grid"
        if grid:
            # one cell per (k, q): a chunk may span several ks
            expected = {k: cfg.n_perturbations for k in ks}
        else:
            expected = {k: sum(1 for u in self.units if u.k == k)
                        for k in ks}
        # per-k accumulator: UnitOutcomes in unit modes, cropped
        # (q, A, R, err) cell rows in grid mode
        pending: dict[int, list] = {k: [] for k in ks}
        per_k: dict[int, KResult] = {}
        records: list[UnitRecord] = []
        executed = 0

        def reduce_ready(k: int) -> None:
            # all of k's members arrived: reduce now and DROP the factor
            # arrays — peak memory stays one k's ensemble, not the sweep's
            if grid:
                rows = sorted(pending.pop(k), key=lambda t: t[0])
                with obs.span("sched/fetch", k=k):
                    A_ens = np.stack([a for _, a, _, _ in rows])
                    R_ens = np.stack([r for _, _, r, _ in rows])
                    errs = np.asarray([e for _, _, _, e in rows])
            else:
                outs = sorted(pending.pop(k),
                              key=lambda o: o.unit.members[0])
                with obs.span("sched/fetch", k=k):
                    A_ens = np.concatenate([np.asarray(o.result.A)
                                            for o in outs])
                    R_ens = np.concatenate([np.asarray(o.result.R)
                                            for o in outs])
                    errs = np.concatenate([np.asarray(o.result.errors)
                                           for o in outs])
                for o in outs:
                    o.result = None
                records.extend(
                    UnitRecord(uid=o.unit.uid, k=k,
                               members=list(o.unit.members),
                               seconds=o.seconds, reused=o.reused,
                               retries=o.retries, attempts=o.attempts,
                               backoff_seconds=o.backoff,
                               straggler=o.straggler,
                               baseline_seconds=o.baseline,
                               peak_host_bytes=o.peak_host,
                               peak_device_bytes=o.peak_device,
                               kernel_fallbacks=o.fallbacks) for o in outs)
            with obs.span("sched/reduce", k=k):
                per_k[k] = reduce_k(X_red, cfg, k, A_ens, R_ens, errs)
            if self.verbose:
                r = per_k[k]
                print(f"[sweep] k={k:3d} s_min={r.s_min:6.3f} "
                      f"s_mean={r.s_mean:6.3f} err={r.rel_err:7.4f}")

        for pos, unit in enumerate(self.units):
            out = self._try_restore(X_exec, unit)
            if out is None:
                # cap checked BEFORE computing, so stop_after_units=N
                # really means "compute at most N" (0 = resume-only)
                if (self.stop_after_units is not None
                        and executed >= self.stop_after_units):
                    # make the last checkpoint durable before "dying":
                    # the resume contract depends on it
                    self._surface_pending_save()
                    raise SweepInterrupted(executed, pos, len(self.units),
                                           resumable=bool(self.ckpt_dir))
                out = self._execute_unit(X_exec, unit)
                executed += 1
            if grid:
                # crop each padded cell row to its own k and hand it to
                # that k's accumulator; the chunk's padded block is dropped
                with obs.span("sched/fetch", uid=unit.uid):
                    A = np.asarray(out.result.A)
                    R = np.asarray(out.result.R)
                    errs = np.asarray(out.result.errors)
                out.result = None
                records.append(UnitRecord(
                    uid=unit.uid, k=-1, members=[], seconds=out.seconds,
                    reused=out.reused, retries=out.retries,
                    attempts=out.attempts, backoff_seconds=out.backoff,
                    cells=[list(c) for c in unit.cells],
                    straggler=out.straggler,
                    baseline_seconds=out.baseline,
                    peak_host_bytes=out.peak_host,
                    peak_device_bytes=out.peak_device,
                    kernel_fallbacks=out.fallbacks))
                done: list[int] = []
                for row, (k, q) in enumerate(unit.cells):
                    # .copy(): a cropped VIEW would pin the whole padded
                    # chunk block until its last straddling k reduces
                    pending[k].append((q, A[row][:, :k].copy(),
                                       R[row][:, :k, :k].copy(),
                                       errs[row]))
                    if len(pending[k]) == expected[k]:
                        done.append(k)
                for k in done:
                    reduce_ready(k)
                continue
            pending[unit.k].append(out)
            if len(pending[unit.k]) == expected[unit.k]:
                reduce_ready(unit.k)
        self._surface_pending_save()

        with obs.span("sched/select"):
            s_min = np.array([per_k[k].s_min for k in ks])
            s_mean = np.array([per_k[k].s_mean for k in ks])
            rel = np.array([per_k[k].rel_err for k in ks])
            k_opt = criteria.select(self.criterion, ks, s_min, s_mean, rel,
                                    sil_threshold=cfg.sil_threshold)
            result = RescalkResult(ks=np.asarray(ks), s_min=s_min,
                                   s_mean=s_mean, rel_err=rel, k_opt=k_opt,
                                   per_k=per_k)

            meta = {"n_units": len(self.units),
                    "n_retries": sum(r.retries for r in records),
                    "n_stragglers": sum(1 for r in records if r.straggler),
                    "n_kernel_fallbacks": sum(r.kernel_fallbacks
                                              for r in records)}
            if self.mesh is not None:
                meta["mesh"] = {str(a): int(s)
                                for a, s in dict(self.mesh.shape).items()}
            self.report = SelectionReport(
                ks=[int(k) for k in ks], s_min=[float(v) for v in s_min],
                s_mean=[float(v) for v in s_mean],
                rel_err=[float(v) for v in rel], k_opt=int(k_opt),
                criterion=self.criterion, mode=self.mode,
                n_perturbations=cfg.n_perturbations, units=records, meta=meta)
            if self.report_path:
                self.report.save(self.report_path)
        if self.verbose and self.ckpt_dir:
            n_reused = self.report.n_reused
            print(f"[sweep] resumed {n_reused}/{len(self.units)} units from "
                  f"checkpoints in {self.ckpt_dir}")
        return result
