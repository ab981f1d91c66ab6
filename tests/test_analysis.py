"""repro.analysis: the static-analysis framework, its nine rules against
the bad/ok fixture pairs, the CLI contract, and the runtime sanitizer.

Rule tests run ``run_lint`` directly on one fixture file with one rule
selected, so a finding from an unrelated rule can never mask a miss.
"""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import all_rules, run_lint
from repro.analysis.sanitizer import (FactorSanitizerError, check_factors,
                                      last_failure, reset_failures,
                                      sanitize_state)

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
LINT_CLI = REPO / "scripts" / "rescal_lint.py"

RULES = sorted(all_rules())        # registry: name -> Rule instance

# rule name -> fixture stem
STEMS = {
    "compat-isolation": "compat_isolation",
    "key-discipline": "key_discipline",
    "recompile-hazard": "recompile_hazard",
    "pallas-kernel": "pallas_kernel",
    "donation-safety": "donation_safety",
    "nonneg-sanitizer-coverage": "sanitizer_coverage",
    "obs-metrics-coverage": "obs_coverage",
    "obs-scope-coverage": "scope_coverage",
    "resilience-seam-coverage": "resilience_seams",
}


def lint_one(path, rule_name):
    assert rule_name in all_rules(), f"unknown rule {rule_name}"
    return run_lint([path], root=REPO, rules=[rule_name])


# ---------------------------------------------------------------------------
# every rule: fires on its bad fixture, silent on its near-miss twin
# ---------------------------------------------------------------------------

class TestRuleFixtures:
    def test_every_rule_has_a_fixture_pair(self):
        assert set(STEMS) == set(RULES)
        for stem in STEMS.values():
            assert (FIXTURES / f"{stem}_bad.py").exists()
            assert (FIXTURES / f"{stem}_ok.py").exists()

    @pytest.mark.parametrize("rule", sorted(STEMS))
    def test_fires_on_bad(self, rule):
        res = lint_one(FIXTURES / f"{STEMS[rule]}_bad.py", rule)
        assert res.errors, f"{rule} missed its true positive"
        assert all(f.rule == rule for f in res.findings)

    @pytest.mark.parametrize("rule", sorted(STEMS))
    def test_silent_on_ok(self, rule):
        res = lint_one(FIXTURES / f"{STEMS[rule]}_ok.py", rule)
        assert not res.findings, (
            f"{rule} false-positived on its near miss: "
            f"{[f.format() for f in res.findings]}")


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

class TestSuppressions:
    def _lint_source(self, tmp_path, text, rule="key-discipline"):
        p = tmp_path / "mod.py"
        p.write_text(text)
        return run_lint([p], root=tmp_path, rules=[rule])

    BAD = ("import jax\n\n\n"
           "def f(key):\n"
           "    a = jax.random.uniform(key, (2,))\n"
           "    b = jax.random.normal(key, (2,))\n"
           "    return a + b\n")

    def test_unsuppressed_fires(self, tmp_path):
        assert self._lint_source(tmp_path, self.BAD).errors

    def test_trailing_disable_with_justification(self, tmp_path):
        text = self.BAD.replace(
            "    b = jax.random.normal(key, (2,))",
            "    b = jax.random.normal(key, (2,))  "
            "# rescal-lint: disable=key-discipline -- fixture reuse is fine")
        res = self._lint_source(tmp_path, text)
        assert not res.findings

    def test_standalone_disable_covers_next_code_line(self, tmp_path):
        text = self.BAD.replace(
            "    b = jax.random.normal(key, (2,))",
            "    # rescal-lint: disable=key-discipline -- deliberate\n"
            "    # (spans a continuation comment line)\n"
            "    b = jax.random.normal(key, (2,))")
        res = self._lint_source(tmp_path, text)
        assert not res.findings

    def test_disable_without_justification_is_an_error(self, tmp_path):
        text = self.BAD.replace(
            "    b = jax.random.normal(key, (2,))",
            "    b = jax.random.normal(key, (2,))  "
            "# rescal-lint: disable=key-discipline")
        res = self._lint_source(tmp_path, text)
        # the reuse is suppressed but the naked directive itself fires
        assert any(f.rule == "suppression" for f in res.findings)

    def test_disable_file_scope(self, tmp_path):
        text = ("# rescal-lint: disable-file=key-discipline -- fixture\n"
                + self.BAD)
        res = self._lint_source(tmp_path, text)
        assert not res.findings

    def test_other_rules_not_suppressed(self, tmp_path):
        text = self.BAD.replace(
            "    b = jax.random.normal(key, (2,))",
            "    b = jax.random.normal(key, (2,))  "
            "# rescal-lint: disable=compat-isolation -- wrong rule")
        res = self._lint_source(tmp_path, text)
        assert any(f.rule == "key-discipline" for f in res.findings)


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def run_cli(*args):
    return subprocess.run([sys.executable, str(LINT_CLI), *args],
                          capture_output=True, text=True, cwd=REPO)


class TestCli:
    def test_src_tree_is_clean(self):
        # the acceptance bar: the merged tree lints clean, strictly
        cp = run_cli("--strict", "src")
        assert cp.returncode == 0, cp.stdout + cp.stderr

    @pytest.mark.parametrize("stem", sorted(STEMS.values()))
    def test_bad_fixture_exits_nonzero(self, stem):
        cp = run_cli(str(FIXTURES / f"{stem}_bad.py"))
        assert cp.returncode == 1, cp.stdout

    def test_json_output(self):
        cp = run_cli("--json", str(FIXTURES / "key_discipline_bad.py"))
        out = json.loads(cp.stdout)
        assert out["errors"] >= 1
        assert out["findings"][0]["rule"] == "key-discipline"
        assert cp.returncode == 1

    def test_unknown_rule_exits_2(self):
        cp = run_cli("--rules", "no-such-rule", "src")
        assert cp.returncode == 2

    def test_missing_path_exits_2(self):
        cp = run_cli("does/not/exist")
        assert cp.returncode == 2

    def test_list_rules(self):
        cp = run_cli("--list-rules")
        assert cp.returncode == 0
        for rule in RULES:
            assert rule in cp.stdout


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------

class TestSanitizer:
    def test_clean_factors_pass(self):
        A = np.full((4, 2), 0.5)
        R = np.full((3, 2, 2), 0.25)
        check_factors(A, R)            # no raise

    def test_negative_entry_caught(self):
        reset_failures()
        A = np.full((4, 2), 0.5)
        A[1, 0] = -0.125
        R = np.full((3, 2, 2), 0.25)
        with pytest.raises(FactorSanitizerError, match="negative"):
            check_factors(A, R, where="unit")
        assert "unit" in last_failure()

    def test_nan_entry_caught(self):
        A = np.full((4, 2), 0.5)
        R = np.full((3, 2, 2), 0.25)
        R[0, 1, 1] = np.nan
        with pytest.raises(FactorSanitizerError, match="non-finite"):
            check_factors(A, R)

    def test_masked_column_leak_caught(self):
        # column 1 is masked off but A carries mass there
        A = np.full((4, 2), 0.5)
        R = np.zeros((3, 2, 2))
        R[:, 0, 0] = 0.25
        mask = np.array([1.0, 0.0])
        with pytest.raises(FactorSanitizerError, match="masked"):
            check_factors(A, R, mask=mask)

    def test_disabled_hook_adds_no_callback(self):
        # the zero-cost contract: sanitize=False must stage NOTHING into
        # the jaxpr (check_compiles.py counts programs; a callback would
        # also break donation/async dispatch)
        def step(A, R):
            return sanitize_state(A, R, where="t", enabled=False)

        jaxpr = jax.make_jaxpr(step)(jnp.ones((3, 2)), jnp.ones((1, 2, 2)))
        assert "callback" not in str(jaxpr)

        def step_on(A, R):
            return sanitize_state(A, R, where="t", enabled=True)

        jaxpr_on = jax.make_jaxpr(step_on)(jnp.ones((3, 2)),
                                           jnp.ones((1, 2, 2)))
        assert "callback" in str(jaxpr_on)

    def test_rescal_sanitize_parity_and_catch(self):
        from repro.core.rescal import rescal
        from repro.data.synthetic import synthetic_rescal
        X, _, _ = synthetic_rescal(jax.random.PRNGKey(0), n=16, m=2, k=3)
        s0, _ = rescal(X, 3, key=jax.random.PRNGKey(1), iters=5)
        s1, _ = rescal(X, 3, key=jax.random.PRNGKey(1), iters=5,
                       sanitize=True)
        np.testing.assert_array_equal(np.asarray(s0.A), np.asarray(s1.A))
        np.testing.assert_array_equal(np.asarray(s0.R), np.asarray(s1.R))

        reset_failures()
        Xbad = X.at[0, 0, 0].set(jnp.nan)
        # depending on dispatch timing the callback error either raises an
        # XlaRuntimeError at the sync point or only lands in the failure
        # log — last_failure() keeps the precise report either way
        caught = ""
        try:
            s2, _ = rescal(Xbad, 3, key=jax.random.PRNGKey(1), iters=3,
                           sanitize=True)
            jax.block_until_ready(s2.A)
            jax.effects_barrier()      # drain pending callback effects
        except Exception as ex:
            caught = str(ex)
        report = (last_failure() or "") + caught
        assert "non-finite" in report, report

    def test_sweep_with_sanitizer_runs_clean(self):
        from repro.selection import RescalkConfig, SweepScheduler
        from repro.data.synthetic import synthetic_rescal
        X, _, _ = synthetic_rescal(jax.random.PRNGKey(0), n=16, m=2, k=3)
        cfg = RescalkConfig(k_min=2, k_max=3, n_perturbations=2,
                            rescal_iters=5, regress_iters=2, sanitize=True)
        res = SweepScheduler(cfg, mode="batched").run(X)
        assert res.k_opt in (2, 3)


# ---------------------------------------------------------------------------
# artifact-guard scripts: one-line errors, not tracebacks
# ---------------------------------------------------------------------------

class TestArtifactGuards:
    def _gate(self, *args):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "check_bench_gate.py"),
             *args], capture_output=True, text=True, cwd=REPO)

    def test_missing_artifact(self, tmp_path):
        cp = self._gate(str(tmp_path / "nope.json"))
        assert cp.returncode == 2
        assert "[bench-gate] ERROR:" in cp.stdout
        assert "Traceback" not in cp.stderr

    def test_malformed_json(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text("{not json")
        cp = self._gate(str(p))
        assert cp.returncode == 2
        assert "[bench-gate] ERROR:" in cp.stdout
        assert "Traceback" not in cp.stderr

    def test_malformed_case(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({"grid": [{"name": "x"}]}))
        cp = self._gate(str(p))
        assert cp.returncode == 2
        assert "malformed case" in cp.stdout

    def test_regression_still_exit_1(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps(
            {"grid": [{"name": "slow", "speedup": 0.5}]}))
        cp = self._gate(str(p))
        assert cp.returncode == 1

    def test_compile_guard_selftest(self):
        cp = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "check_compiles.py")],
            capture_output=True, text=True, cwd=REPO,
            env={"PATH": "/usr/local/bin:/usr/bin:/bin",
                 "RESCAL_CHECK_COMPILES_SELFTEST": "1"})
        assert cp.returncode == 2
        assert "[compile-guard] ERROR:" in cp.stdout
        assert "Traceback" not in cp.stderr
