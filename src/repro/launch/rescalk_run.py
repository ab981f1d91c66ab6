"""Distributed RESCALk CLI — the paper's full pipeline on the selection
scheduler.

Runs model selection (Alg. 1) through repro.selection: the (k, q) work-unit
grid is planned by the scheduler, each unit executes as one batched
ensemble program (a sequential loop with ``--mode loop``, or the whole
grid padded to k_max as ONE cross-k device program with ``--mode grid`` —
at most two XLA compiles for any k range; see README "sweep execution
modes"), and per-unit checkpoints make an interrupted sweep resumable
without recomputing completed units (checkpoint tags derive from the
unit's (k, member-range) — or grid chunk's cell-range — identity, never
from PRNG key internals).

Data sources (``--data``, the repro.io ingest layer):

    (default)             synthetic dense tensor (data/synthetic.py)
    path.tsv              triple list -> vocab -> COO -> BCSR (--bs blocks)
    path.npz              pre-numbered COO arrays -> BCSR
    virtual:dense:n=...   shard-generated dense tensor (io/virtual.py)
    virtual:bcsr:n=...    shard-generated block-sparse tensor; the dense
                          tensor it represents never exists anywhere

Sparse operands run the stored-block perturbation ensemble (paper §4.2);
the printed manifest line shows logical vs resident bytes — the exascale
gap this layer exists to open.

    PYTHONPATH=src python -m repro.launch.rescalk_run \
        --n 256 --m 4 --k-true 5 --k-min 2 --k-max 7 --iters 300

    PYTHONPATH=src python -m repro.launch.rescalk_run \
        --data virtual:bcsr:n=4096,m=3,k=4,density=0.05 --k-min 3 --k-max 5

Interrupt/resume drill (what scripts/ci_test.sh exercises):

    ... rescalk_run --ckpt-dir /tmp/ck --stop-after-units 2   # "kill"
    ... rescalk_run --ckpt-dir /tmp/ck                        # resume
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.data.synthetic import synthetic_rescal
from repro.launch.compile_cache import enable_compile_cache
from repro.selection import (CRITERIA, RescalkConfig, SweepInterrupted,
                             SweepScheduler)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--k-true", type=int, default=5)
    ap.add_argument("--k-min", type=int, default=2)
    ap.add_argument("--k-max", type=int, default=7)
    ap.add_argument("--r", type=int, default=4)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--data", default=None,
                    help="dataset: a .tsv/.npz triple file or a "
                         "virtual:{dense|bcsr}:k=v,... spec (default: "
                         "synthetic dense from --n/--m/--k-true)")
    ap.add_argument("--bs", type=int, default=128,
                    help="BCSR block size for .tsv/.npz ingest")
    ap.add_argument("--schedule", default="batched",
                    choices=("batched", "sliced"))
    ap.add_argument("--init", default="random", choices=("random", "nndsvd"))
    ap.add_argument("--mode", default="batched",
                    choices=("batched", "loop", "grid"),
                    help="ensemble execution: one batched program per "
                         "(k, members) unit, the sequential per-member "
                         "loop, or the cross-k grid (the whole (k, q) "
                         "grid padded to k_max as one device program)")
    ap.add_argument("--grid-chunk", type=int, default=None,
                    help="mode=grid: cells per chunk (= per checkpoint; "
                         "default: the whole grid in one chunk)")
    ap.add_argument("--criterion", default="threshold",
                    choices=sorted(CRITERIA),
                    help="k-selection rule (selection/criteria.py)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="per-(k, q)-unit checkpoint directory")
    ap.add_argument("--report", default=None,
                    help="write the SelectionReport JSON here")
    ap.add_argument("--bundle", default=None, metavar="DIR",
                    help="persist the selected-k factors as a FactorBundle "
                         "(repro.serve) here; default: <report>.bundle "
                         "next to --report.  The report's meta gains a "
                         "'bundle' pointer that scripts/check_trace.py "
                         "validates")
    ap.add_argument("--stop-after-units", type=int, default=None,
                    help="compute at most this many units, then exit "
                         "(deterministic kill for resume drills)")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="per-unit transient-retry budget "
                         "(resilience.RetryPolicy max_attempts - 1; "
                         "deterministic errors always fail fast)")
    ap.add_argument("--retry-base-delay", type=float, default=0.05,
                    metavar="SEC",
                    help="first-retry backoff; doubles per attempt with "
                         "deterministic seeded jitter")
    ap.add_argument("--unit-deadline", type=float, default=None,
                    metavar="SEC",
                    help="per-attempt wall-clock budget for one unit; "
                         "overruns raise DeadlineExceeded (transient) and "
                         "retried attempts shrink to the straggler "
                         "baseline")
    ap.add_argument("--fault-plan", default=None, metavar="FILE",
                    help="JSON FaultPlan (resilience.faults) installed "
                         "for the run — the chaos-drill hook; every "
                         "firing emits a fault/inject trace event")
    ap.add_argument("--async-ckpt", action="store_true",
                    help="write unit checkpoints on a background thread "
                         "(failures surface at the next checkpoint "
                         "boundary)")
    ap.add_argument("--use-fused-kernel", action="store_true",
                    help="route the sparse MU sweep through the fused "
                         "single-X-pass BCSR kernel (kernels/ops.py "
                         "bcsr_xa_xta; falls back to the jnp oracle per "
                         "the VMEM panel budget, visibly when traced)")
    ap.add_argument("--fused-impl", default="auto",
                    choices=("auto", "pallas", "interpret", "ref"),
                    help="kernel impl for --use-fused-kernel (auto: "
                         "Pallas on TPU, oracle elsewhere)")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime factor sanitizer inside the MU programs "
                         "(finite / non-negative / masked-zero asserts; "
                         "repro.analysis.sanitizer)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="write trace artifacts to DIR (trace.jsonl, "
                         "trace_chrome.json, metrics.npz, summary.txt) and "
                         "stage per-iteration convergence metrics "
                         "(cfg.trace_metrics; repro.obs)")
    return ap


def load_operand(args):
    """Resolve --data into a sweep operand.

    Returns (operand, A_true | None, vocab | None): ground truth only
    exists for the default synthetic tensor (used for the correlation
    report); the vocab only for .tsv ingest (persisted into the
    FactorBundle so the serve CLI can resolve entity names)."""
    from repro.io import manifest_of
    if args.data is None:
        key = jax.random.PRNGKey(0)
        X, A_true, _ = synthetic_rescal(key, n=args.n, m=args.m,
                                        k=args.k_true)
        return X, A_true, None
    if args.data.startswith("virtual:"):
        from repro.io import (VirtualSpec, virtual_dense_full,
                              virtual_sharded_bcsr)
        spec = VirtualSpec.parse(args.data)
        man = manifest_of(spec)
        print(f"[io] {man.kind} logical "
              f"{man.logical_bytes / 2**30:.2f} GiB -> resident "
              f"{man.resident_bytes / 2**30:.3f} GiB "
              f"({man.compression:.0f}x)")
        if spec.kind == "dense":
            return virtual_dense_full(spec), None, None
        sharded = virtual_sharded_bcsr(spec)
        # single-host run: collapse one-shard layouts to the plain BCSR
        return (sharded.to_bcsr() if spec.grid == 1 else sharded), None, None
    from repro.io import coo_to_bcsr, ingest_npz, ingest_tsv
    vocab = None
    if args.data.endswith(".tsv"):
        coo, vocab = ingest_tsv(args.data)
        print(f"[io] {args.data}: {vocab.n} entities, {vocab.m} relations, "
              f"{coo.nnz} triples")
    elif args.data.endswith(".npz"):
        coo = ingest_npz(args.data)
        print(f"[io] {args.data}: n={coo.n} m={coo.m} nnz={coo.nnz}")
    else:
        raise SystemExit(f"--data must be .tsv, .npz or virtual:..., "
                         f"got {args.data!r}")
    sp = coo_to_bcsr(coo, bs=args.bs)
    man = manifest_of(sp)
    print(f"[io] bcsr bs={args.bs} nnzb={sp.nnzb} logical "
          f"{man.logical_bytes / 2**20:.1f} MiB -> resident "
          f"{man.resident_bytes / 2**20:.1f} MiB")
    return sp, None, vocab


def _run(args):
    """Plan and run the sweep; returns (operand, report | None) for the
    trace-artifact writer (report is whatever the scheduler produced — None
    when the sweep was interrupted before the reduce)."""
    X, A_true, vocab = load_operand(args)
    from repro.io import operand_dims
    from repro.kernels.policy import KernelPolicy
    m, n = operand_dims(X)
    print(f"operand m={m} n={n}, schedule={args.schedule}, "
          f"mode={args.mode}, criterion={args.criterion}")

    cfg = RescalkConfig(k_min=args.k_min, k_max=args.k_max,
                        n_perturbations=args.r, rescal_iters=args.iters,
                        schedule=args.schedule, init=args.init,
                        sanitize=args.sanitize,
                        kernel=KernelPolicy(use_fused=args.use_fused_kernel,
                                            impl=args.fused_impl),
                        trace_metrics=bool(args.trace))
    if args.grid_chunk is not None and args.mode != "grid":
        raise SystemExit("--grid-chunk requires --mode grid")
    from repro.resilience import RetryPolicy
    retry = RetryPolicy(max_attempts=args.max_retries + 1,
                        base_delay=args.retry_base_delay,
                        deadline=args.unit_deadline)
    sched = SweepScheduler(cfg, mode=args.mode, ckpt_dir=args.ckpt_dir,
                           criterion=args.criterion,
                           grid_chunk=args.grid_chunk,
                           retry=retry, async_ckpt=args.async_ckpt,
                           stop_after_units=args.stop_after_units,
                           report_path=args.report, verbose=True)
    try:
        res = sched.run(X)
    except SweepInterrupted as stop:
        # one source of truth: the exception formats its own resumable /
        # not-checkpointed wording (ci_test.sh greps this line)
        print(f"[sweep] {stop}")
        return X, sched.report

    print("\n" + res.summary())
    print(f"\nselected k_opt = {res.k_opt}"
          + (f" (planted {args.k_true})" if A_true is not None else ""))
    if sched.report is not None:
        rep = sched.report
        print(f"[sweep] {len(rep.units)} units, {rep.n_reused} reused, "
              f"{rep.total_seconds:.2f}s compute")
    if A_true is not None and res.k_opt == args.k_true:
        med = res.per_k[res.k_opt].A_median
        A = np.asarray(A_true)
        corrs = [max(abs(np.corrcoef(A[:, c], med[:, j])[0, 1])
                     for j in range(med.shape[1]))
                 for c in range(args.k_true)]
        print(f"feature correlation vs ground truth: "
              f"min={min(corrs):.3f} mean={np.mean(corrs):.3f}")
    _persist_bundle(args, X, res, vocab, sched.report)
    return X, sched.report


def _bundle_dir(args) -> str | None:
    if args.bundle is not None:
        return args.bundle
    if args.report is not None:
        import os
        return os.path.splitext(args.report)[0] + ".bundle"
    return None


def _persist_bundle(args, X, res, vocab, report):
    """The sweep's whole point of output: persist the selected-k best
    factors (member-median A + regressed R) as a versioned FactorBundle
    next to the report, and point the report's meta at it."""
    bundle_dir = _bundle_dir(args)
    if bundle_dir is None:
        return
    from repro.io import manifest_of
    from repro.serve import FactorBundle

    ents = rels = None
    if vocab is not None:
        ents = [w for w, _ in sorted(vocab.entities.items(),
                                     key=lambda kv: kv[1])]
        rels = [w for w, _ in sorted(vocab.relations.items(),
                                     key=lambda kv: kv[1])]
    bundle = FactorBundle.from_sweep(
        res, entities=ents, relations=rels,
        manifest=manifest_of(X).fingerprint(),
        meta={"criterion": args.criterion})
    bundle.save(bundle_dir)
    print(f"[bundle] {bundle_dir}: n={bundle.n} m={bundle.m} "
          f"k={bundle.k} digest={bundle.digest()[:12]}")
    if report is not None and args.report:
        report.meta["bundle"] = bundle_dir
        report.save(args.report)


def _memory_ledger(tracer, report, operand, op, ks, args):
    """Assemble the sweep's byte ledger (obs.memory.MemoryLedger): manifest
    accounting + per-rank AOT breakdowns + runtime watermarks.  The fallback
    count derives from the tracer's `kernel/fallback` instants — the same
    stream check_trace.py recounts, so the two cannot disagree."""
    from repro.io import manifest_of
    from repro.obs import memory as obs_memory

    man = manifest_of(operand)
    n_fb = sum(1 for e in tracer.events
               if e.get("ph") == "i" and e.get("name") == "kernel/fallback")
    sampler = tracer.memory_sampler
    peak_host = (sampler.peak_bytes if sampler is not None else
                 obs_memory.read_host_memory().get("hwm_bytes"))
    return obs_memory.MemoryLedger.from_manifest(
        man,
        per_k=obs_memory.measure_mu_memory(op, ks),
        peak_host_bytes=peak_host,
        peak_device_bytes=obs_memory.device_watermark(),
        accounted_sweep_bytes=obs_memory.accounted_ensemble_bytes(
            man, n_members=args.r, k_max=args.k_max),
        kernel_fallbacks=n_fb,
        meta={"n_units": 0 if report is None else len(report.units),
              "n_samples": 0 if sampler is None else len(sampler.samples)})


def _write_trace_artifacts(trace_dir, tracer, buf, report, operand, args):
    """Flush the sweep's trace into its on-disk artifact set (the contract
    README "Observability" documents and scripts/check_trace.py validates)."""
    import os

    from repro.obs import costs as obs_costs

    # drain in-flight debug callbacks so metrics.npz sees every iteration
    jax.effects_barrier()
    tracer.export_chrome(os.path.join(trace_dir, "trace_chrome.json"))
    buf.save_npz(os.path.join(trace_dir, "metrics.npz"))
    parts = [tracer.summarize(), "", buf.summarize()]
    artifacts = "trace.jsonl trace_chrome.json metrics.npz summary.txt"
    if operand is not None:
        op = operand.to_bcsr() if hasattr(operand, "to_bcsr") else operand
        ks = sorted({k for rec in (report.units if report else [])
                     for k in obs_costs.unit_ks(rec)})
        if ks:
            measured = obs_costs.measure_mu_costs(op, ks)
            rows = obs_costs.cost_table(report.units, op, iters=args.iters,
                                        measured=measured)
            parts += ["", obs_costs.format_cost_table(rows)]
        ledger = _memory_ledger(tracer, report, operand, op, ks, args)
        ledger.save(os.path.join(trace_dir, "memory.json"))
        parts += ["", ledger.summarize()]
        artifacts += " memory.json"
        print(f"[obs] memory: {ledger.summary_line()}")
    with open(os.path.join(trace_dir, "summary.txt"), "w") as f:
        f.write("\n".join(parts) + "\n")
    print(f"[obs] trace artifacts in {trace_dir}: {artifacts}")
    print(f"[obs] {len(tracer.events)} events, {len(buf)} metric records"
          + (f" ({buf.dropped} dropped)" if buf.dropped else ""))


def main():
    enable_compile_cache()
    args = build_parser().parse_args()
    if args.fault_plan is not None:
        # installed before the tracer so every fault/inject instant of
        # the run lands in the trace; process-wide, like the tracer
        from repro.resilience import faults
        plan = faults.FaultPlan.load(args.fault_plan)
        faults.install(plan)
        print(f"[faults] {args.fault_plan}: {plan.summary()}")
    if args.trace is None:
        _run(args)
        return

    import os

    from repro.dist.compat import capture_compiles
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs

    from repro.obs.memory import HostMemorySampler

    os.makedirs(args.trace, exist_ok=True)
    tracer = obs.Tracer(args.trace, meta={"argv": vars(args)})
    buf = obs_metrics.MetricsBuffer()
    prev_tracer = obs.install(tracer)
    prev_buf = obs_metrics.install_buffer(buf)
    # the tracer owns the host-RSS watermark sampler for the run; started
    # after install so its mem/sample instants land in this trace
    tracer.memory_sampler = HostMemorySampler().start()
    operand, report = None, None
    try:
        with capture_compiles(sink=tracer.compile_event):
            operand, report = _run(args)
    finally:
        # interrupted sweeps still get their artifacts (trace.jsonl is
        # already flushed incrementally; this adds the derived views)
        tracer.memory_sampler.stop()
        try:
            _write_trace_artifacts(args.trace, tracer, buf, report,
                                   operand, args)
        finally:
            obs_metrics.install_buffer(prev_buf)
            obs.install(prev_tracer)
            tracer.close()


if __name__ == "__main__":
    main()
