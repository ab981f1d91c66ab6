"""Traffic generator ``sweep``: whole RESCALk model-selection sweeps, back
to back, over one resident operand.

The configuration file names the operand (``"operand": "dense"`` or
``"bcsr"``) and its sizes, the sweep (ks, members r, MU iterations) and
the planted k; the traffic mix names the mesh (``mesh``, absent for one
chip); the workload file names the programs whose device time is the
ensemble layer (``unit_programs``), the arithmetic of the reference and
of the control, and the limits of the checks.  The configuration's ``n``
is the side of one chip's block: on a g x g mesh the operand is g n on a
side, built in its sharding, so every chip holds the block that one chip
holds alone.

Set-up builds the operand on the device from the seed and runs one whole
sweep, which compiles (or reads from the cache) every program a sweep
runs.  The window then starts sweeps while its time is not up; each is a
fresh ``SweepScheduler(cfg).run(X)`` with the program's defaults, as
``launch/rescalk_run.py`` runs it.  ``sweep_s`` is the window's wall time,
ending with the last sweep, over the sweeps run.

``correct``: every sweep of the window selects a k that the plain
selection rule picks from the plain reference's members (``reference.py``),
run from the same draws, and every member of the window's last sweep fits
X as its own reference member does: the relative gap of their residuals
||X - A R A^T|| / ||X|| (``check``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import generators, reference, roofline
from chipbench.bench import log


class Traffic:
    def __init__(self, config: dict, params: dict, seed: int, devices):
        self.config, self.params = config, params
        self.seed = seed
        self.seed32 = generators.key_seed(seed)
        self.devices = devices
        self.mesh = None
        self.members: dict = {}
        self.k_selected: list[int] = []
        self.s_min: list[float] = []
        self.control = False

    # -- operand ----------------------------------------------------------

    def _build_operand(self):
        op = self.config["operand"]
        n, m = self.n, self.config["m"]
        if op["kind"] == "dense":
            sharding = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                sharding = NamedSharding(self.mesh,
                                         P(None, *self.mesh.axis_names))
            X = generators.planted_dense(
                self.seed, n=n, m=m, k=op["planted_k"], noise=op["noise"],
                sharding=sharding)
            self.stored_entries = m * n ** 2
            self.operand_bytes = X.nbytes
            return X
        from repro.core.sparse import BCSR
        data, rows, cols = generators.planted_bcsr(
            self.seed, n=n, m=m, k=op["planted_k"],
            bs=self.config["block_size"],
            community_blocks=op["community_blocks"], noise=op["noise"])
        self.stored_entries = int(np.prod(data.shape))
        self.operand_bytes = data.nbytes + rows.nbytes + cols.nbytes
        return BCSR(data=data, block_rows=rows, block_cols=cols, n=n)

    def _capture_members(self):
        """Keep the factors of every unit the scheduler runs (the same
        call and the same arrays; only a reference to them is kept)."""
        import repro.selection.scheduler as sched_mod
        run = sched_mod.run_ensemble
        members = self.members

        def run_and_keep(X, k, cfg, **kw):
            res = run(X, k, cfg, **kw)
            members[(k, tuple(kw.get("members") or ()))] = res
            return res

        sched_mod.run_ensemble = run_and_keep

    # -- the traffic's interface -----------------------------------------

    def setup(self) -> None:
        from repro.selection import RescalkConfig
        if "mesh" in self.params:
            from repro.dist import compat
            shape = self.params["mesh"]
            self.mesh = compat.make_mesh(tuple(shape["shape"]),
                                         tuple(shape["axes"]),
                                         devices=self.devices)
        side = self.params["mesh"]["shape"][0] if self.mesh else 1
        self.n = self.config["n"] * side
        self.X = self._build_operand()
        jax.block_until_ready(self.X)
        c = self.config
        self.cfg = RescalkConfig(k_min=c["k_min"], k_max=c["k_max"],
                                 n_perturbations=c["n_perturbations"],
                                 rescal_iters=c["rescal_iters"],
                                 seed=self.seed32)
        self._capture_members()
        self._run_sweep()                       # warms every program

    def _run_sweep(self):
        from repro.selection import SweepScheduler
        self.members.clear()
        res = SweepScheduler(self.cfg, mesh=self.mesh).run(self.X)
        self.k_selected.append(int(res.k_opt))
        self.s_min = list(res.s_min)
        return res

    def window(self, seconds: float, capture=None) -> dict:
        self.k_selected.clear()
        sweeps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("chipbench/sweep"):
                self._run_sweep()
            sweeps += 1
        wall = time.perf_counter() - t0
        return {"metrics": {"sweep_s": wall / sweeps},
                "attempted": sweeps, "failed": 0,
                "counters": self._counters(sweeps),
                "notes": [f"{sweeps} sweeps in {wall:.3f} s; selected k "
                          f"{self.k_selected}"]}

    def _counters(self, sweeps: int) -> dict:
        c = self.config
        chips = len(self.devices)
        units = [(k, c["n_perturbations"])
                 for k in range(c["k_min"], c["k_max"] + 1)]
        peaks = roofline.peaks_for(self.devices[0])
        least = sum(roofline.least_seconds(*roofline.mu_iteration_work(
            k=k, r=r, stored_entries=self.stored_entries,
            operand_bytes=self.operand_bytes, chips=chips), peaks)
            for k, r in units)
        return {"sweeps": sweeps,
                "unit_programs": self.params["unit_programs"],
                "unit_iterations": sweeps * len(units) * c["rescal_iters"],
                "least_unit_seconds": sweeps * c["rescal_iters"] * least}

    def release(self) -> None:
        pass

    # -- correctness -----------------------------------------------------

    def _reference_unit(self, k: int, qs, variant: str) -> list:
        """The reference's run of members `qs` at k, in the arithmetic
        named `variant` (``reference.ARITHMETIC``), as [(A, R), ...].  On
        one device a unit's members run together, as the program runs
        them; on a mesh, one at a time."""
        iters = self.config["rescal_iters"]
        prec, dtype = reference.arithmetic(variant)
        keys = reference.member_keys(self.seed32, k,
                                     self.config["n_perturbations"])
        kw = dict(k=k, iters=iters, precision=prec, dtype=dtype)
        X = self.X
        if self.mesh is not None:
            return [reference.dense_member(X, keys[q], q, mesh=self.mesh,
                                           **kw) for q in qs]
        unit = keys[jax.numpy.asarray(qs)]
        if self.config["operand"]["kind"] == "dense":
            A, R = reference.dense_members(X, unit, **kw)
        else:
            A, R = reference.bcsr_members(X.data, X.block_rows, X.block_cols,
                                          unit, n=X.n, **kw)
        return list(zip(A, R))

    def _residual(self, A, R) -> float:
        X = self.X
        if self.config["operand"]["kind"] == "dense":
            return float(reference.dense_residual(X, A, R))
        return float(reference.bcsr_residual(X.data, X.block_rows,
                                             X.block_cols, A, R, n=X.n))

    def members_and_fits(self, variants=()):
        """The window's last sweep, member by member: each member's factors
        and residual ||X - A R A^T|| / ||X||, of the program and of the
        reference's run of the same member in each of `variants`.  Returns
        (rows, factors): a row per member, {"k", "q", "program", variant:
        residual, ...}; factors[who][k] lists the members' (A, R)."""
        r = self.config["n_perturbations"]
        rows = []
        factors = {who: {} for who in ("program", *variants)}
        for (k, members), res in sorted(self.members.items()):
            qs = list(members or range(r))
            ref = {v: self._reference_unit(k, qs, v) for v in variants}
            for i, q in enumerate(qs):
                made = {"program": (res.A[i], res.R[i])}
                made.update((v, ref[v][i]) for v in variants)
                row = {"k": k, "q": q}
                for who, (A, R) in made.items():
                    row[who] = self._residual(A, R)
                    factors[who].setdefault(k, []).append(
                        (np.asarray(A), np.asarray(R)))
                rows.append(row)
        return rows, factors

    def _regressed_fit(self, A_median) -> float:
        """The relative error at the median factor of R regressed on it."""
        X = self.X
        A = jax.numpy.asarray(A_median, jax.numpy.float32)
        if self.config["operand"]["kind"] == "dense":
            ATXA = reference.dense_ATXA(X, A)
        else:
            ATXA = reference.bcsr_ATXA(X.data, X.block_rows, X.block_cols,
                                       A, n=X.n)
        return self._residual(A, reference.regress(ATXA, A))

    def plain_selection(self, by_k: dict, band: float = 0.0):
        """The ks that the plain selection rule (``reference.select_ks``)
        picks from one ensemble per k, its threshold taken anywhere within
        `band`, and the least silhouettes by k."""
        ks = sorted(by_k)
        aligned = {k: reference.align(np.stack([A for A, _ in by_k[k]]))
                   for k in ks}
        s_min = [reference.silhouette_min(aligned[k][0]) for k in ks]
        picks = reference.select_ks(
            ks, s_min, lambda k: self._regressed_fit(aligned[k][1]), band)
        return picks, s_min

    def check(self) -> list[dict]:
        """Against the plain reference (``reference.py``), run from the
        same draws in the arithmetic the configuration states
        (``reference``).  k_gap: the largest distance of a window sweep's
        selected k from the ks that the plain selection rule picks from
        the reference's members, its threshold taken anywhere within
        ``silhouette_band`` (the program's least silhouettes carry the
        rounding of its arithmetic).  fit_gap: over every member of the
        window's last sweep, the largest relative gap between the
        program's residual and its own reference member's.  With
        ``control`` set, the reference's run in the arithmetic below
        (``control``) stands in for the program: its members, and the k
        the plain rule picks from them at the threshold itself."""
        lim = self.params["limits"]
        ref = self.params["reference"]
        got = self.params["control"] if self.control else "program"
        rows, factors = self.members_and_fits(
            sorted({ref, got} - {"program"}))
        for row in rows:
            log(f"[check] k={row['k']} member {row['q']}: residual "
                f"{row[got]!r}, reference ({ref}) {row[ref]!r}")
        k_ref, s_ref = self.plain_selection(factors[ref],
                                            self.params["silhouette_band"])
        if self.control:
            ks_got, s_got = self.plain_selection(factors[got])
        else:
            ks_got, s_got = self.k_selected, self.s_min
        log(f"[check] least silhouettes by k: {got} "
            f"{[round(float(v), 4) for v in s_got]}, reference "
            f"{[round(v, 4) for v in s_ref]}; k {sorted(ks_got)} vs "
            f"{sorted(k_ref)}")
        k_gap = max(min(abs(k - r) for r in k_ref) for k in ks_got)
        gap = max(abs(row[got] - row[ref]) / row[ref] for row in rows)
        return [{"name": "k_gap", "value": k_gap, "limit": lim["k_gap"],
                 "ok": k_gap <= lim["k_gap"]},
                {"name": "fit_gap", "value": gap,
                 "limit": lim["fit_gap"],
                 "ok": bool(gap <= lim["fit_gap"])}]
