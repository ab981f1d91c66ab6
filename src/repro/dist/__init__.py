"""repro.dist — the distribution subsystem.

Everything about *where tensors live and how devices talk* is this
package; the factorization math (core/), kernels (kernels/) and drivers
(launch/) stay distribution-blind.  Module map:

  compat.py   — backend-tolerance layer over the pinned JAX (Auto-axis
                ``make_mesh``, donation only where XLA aliases, compile-log
                parsing, memory-analysis normalization).  The only module
                allowed to probe JAX surfaces.
  sharding.py — placement rules + collectives: logical-axis specs
                (``logical_spec`` / ``constrain`` / ``param_specs`` /
                ``opt_state_specs`` / ``cache_specs``) for the LM
                workloads, and the RESCAL 2D-grid building blocks
                (``psum_cast``, the Alg. 3 diagonal broadcasts, factor
                PartitionSpecs).
  engine.py   — the unified distributed RESCAL MU engine:
                ``make_mu_step(mesh, cfg, operand=, pod_axis=)``
                dispatching dense/BCSR x single/ensemble, the fused
                bilinear-kernel path (``cfg.use_fused_kernel``), the
                distributed error, the GSPMD comparison path, and the
                ``dist_rescal`` driver.
  elastic.py  — host-side elasticity: straggler detection, square-grid
                sizing, ensemble->pod planning, checkpoint-replay retry.

``repro.core.rescal_dist`` re-exports the engine for backward
compatibility; new code should import from ``repro.dist`` directly.
``repro.selection`` composes this layer (``engine.get_mu_iter`` +
``sharding.ensemble_member_specs``) into its mesh-sharded model-selection
ensemble — the member axis rides the pod axis (``ENSEMBLE_AXIS``).
"""
from . import compat, elastic, engine, sharding

__all__ = ["compat", "elastic", "engine", "sharding"]
