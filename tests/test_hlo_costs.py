"""The trip-count-aware HLO cost model vs XLA's own analysis (unrolled)."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_costs, hlo_stats


class TestFlops:
    def test_scan_matches_unrolled_cost_analysis(self):
        N, L = 128, 6
        W = jnp.zeros((L, N, N))

        def body(x, w):
            return jnp.tanh(x @ w), None

        x = jax.ShapeDtypeStruct((N, N), jnp.float32)
        c_scan = jax.jit(lambda x: jax.lax.scan(body, x, W)[0]).lower(
            x).compile()
        c_unr = jax.jit(lambda x: jax.lax.scan(body, x, W, unroll=L)[0]
                        ).lower(x).compile()
        mine = hlo_costs.analyze(c_scan.as_text())["flops"]
        # through the normalizer: {} where the backend reports no costs
        xla = hlo_costs.xla_cost_analysis(c_unr)["flops"]
        assert abs(mine - xla) / xla < 0.05, (mine, xla)

    def test_plain_dot(self):
        a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
        b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
        c = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
        got = hlo_costs.analyze(c.as_text())["flops"]
        assert abs(got - 2 * 64 * 32 * 16) / (2 * 64 * 32 * 16) < 0.05

    def test_nested_scans_multiply(self):
        N, L1, L2 = 64, 3, 4
        W = jnp.zeros((L1, L2, N, N))

        def inner(x, w):
            return x @ w, None

        def outer(x, ws):
            return jax.lax.scan(inner, x, ws)[0], None

        x = jax.ShapeDtypeStruct((N, N), jnp.float32)
        c = jax.jit(lambda x: jax.lax.scan(outer, x, W)[0]).lower(
            x).compile()
        got = hlo_costs.analyze(c.as_text())["flops"]
        want = L1 * L2 * 2 * N ** 3
        assert abs(got - want) / want < 0.1, (got, want)


class TestLegacyParser:
    def test_collective_stats_shapes(self):
        hlo = ('  %ag = bf16[8,128]{1,0} all-gather(%x), channel_id=1, '
               'replica_groups=[4,4]<=[16], dimensions={0}\n')
        st = hlo_stats.collective_stats(hlo)
        assert st["all-gather"]["count"] == 1
        assert st["all-gather"]["result_bytes"] == 8 * 128 * 2

    def test_op_histogram(self):
        hlo = ("  %d = f32[4,4]{1,0} dot(%a, %b), lhs_contracting_dims={1}\n"
               "  %c = f32[4,4]{1,0} copy(%d)\n")
        h = hlo_stats.op_histogram(hlo)
        assert h == {"dot": 1, "copy": 1}


class TestCaptureCompiles:
    """dist.compat.capture_compiles — the surface the compile-count CI
    guard (scripts/check_compiles.py) stands on."""

    def test_counts_named_program_once(self):
        from repro.dist.compat import capture_compiles

        def freshly_named_probe(x):
            return x * 2.0 + 1.0

        f = jax.jit(freshly_named_probe)
        x = jnp.ones((5,))
        with capture_compiles() as log:
            f(x)          # compiles (new function identity)
            f(x)          # cached: must NOT count again
        assert log.count("freshly_named_probe") == 1
        assert log.count("freshly_named_probe", "no_such_prog") == 1
        assert log.count("no_such_prog") == 0
        assert log.count() >= 1

    def test_restores_logger_state(self):
        import logging
        from repro.dist.compat import capture_compiles
        logger = logging.getLogger("jax")
        before = (logger.level, logger.propagate, list(logger.handlers))
        with capture_compiles():
            jax.jit(lambda x: x + 1)(jnp.zeros(3))
        after = (logger.level, logger.propagate, list(logger.handlers))
        assert before == after
