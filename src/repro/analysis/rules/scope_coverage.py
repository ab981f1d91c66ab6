"""obs-scope-coverage: every MU step names its phases on the device.

The benchmark splits a unit MU iteration's device time by the op_name
scopes the step stages: ``mu`` around the whole step, and ``products``
around the operations that read the stored operand X (``mu_products_ms``
against ``mu_factor_ms``).  A step outside ``mu`` drops out of both
readings; a step that never opens ``products`` books its passes over X as
factor algebra.  So every function matching the MU-step pattern (as in
``nonneg-sanitizer-coverage``; ``make_*`` / ``get_*`` / ``build_*``
factories exempt) must

  * open ``jax.named_scope("mu")``, as a decorator or a ``with``, and
  * open ``jax.named_scope("products")`` itself, or call a function that
    does — directly, through a module-level table of such functions
    (``MU_SCHEDULES[schedule](...)``), or transitively.

Callees resolve by their last name component over every file linted
together, so a step may reach its products through a helper of another
module (``core.sparse.sparse_products``).
"""
from __future__ import annotations

import ast

from ..framework import ERROR, Finding, Rule, dotted, register
from .sanitizer_coverage import FACTORY_PREFIXES, MU_NAME_RE

STEP_SCOPE = "mu"
OPERAND_SCOPE = "products"


def _scope_of(node) -> str | None:
    """The literal name of a ``named_scope("...")`` call, else None."""
    if not isinstance(node, ast.Call) or not node.args:
        return None
    if (dotted(node.func) or "").split(".")[-1] != "named_scope":
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


def _opens(fn, scope: str) -> bool:
    if any(_scope_of(d) == scope for d in fn.decorator_list):
        return True
    return any(_scope_of(item.context_expr) == scope
               for node in ast.walk(fn)
               if isinstance(node, (ast.With, ast.AsyncWith))
               for item in node.items)


def _last(node) -> str | None:
    d = dotted(node)
    return d.split(".")[-1] if d else None


def _callees(fn) -> set[str]:
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func.value if isinstance(node.func, ast.Subscript) \
                else node.func
            name = _last(f)
            if name:
                out.add(name)
    return out


def _tables(tree) -> dict[str, set[str]]:
    """Module-level ``NAME = {key: function, ...}`` dispatch tables."""
    out = {}
    for node in tree.body:
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if not isinstance(value, ast.Dict):
            continue
        names = {n for n in map(_last, value.values) if n}
        for t in targets:
            if isinstance(t, ast.Name):
                out[t.id] = names
    return out


def _is_step(fn) -> bool:
    return (isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            and bool(MU_NAME_RE.search(fn.name))
            and not fn.name.startswith(FACTORY_PREFIXES))


@register
class ObsScopeCoverage(Rule):
    name = "obs-scope-coverage"
    description = ("every MU-step implementation must run under "
                   "named_scope('mu') and open named_scope('products') "
                   "around its reads of the stored operand")

    def check_project(self, ctx):
        calls: dict[str, set[str]] = {}
        covered: set[str] = set()
        tables: dict[str, set[str]] = {}
        for src in ctx.files:
            tables.update(_tables(src.tree))
            for fn in ast.walk(src.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    calls.setdefault(fn.name, set()).update(_callees(fn))
                    if _opens(fn, OPERAND_SCOPE):
                        covered.add(fn.name)
        reach = {**calls, **tables}
        grown = True
        while grown:
            grown = False
            for name, targets in reach.items():
                if name not in covered and targets & covered:
                    covered.add(name)
                    grown = True
        for src in ctx.files:
            for fn in ast.walk(src.tree):
                if not _is_step(fn):
                    continue
                if not _opens(fn, STEP_SCOPE):
                    yield Finding(
                        self.name, src.rel, fn.lineno, fn.col_offset,
                        f"MU step '{fn.name}' does not run under "
                        f"jax.named_scope(\"{STEP_SCOPE}\") — decorate it, "
                        f"so its device time reaches mu_products_ms / "
                        f"mu_factor_ms", ERROR)
                if fn.name not in covered:
                    yield Finding(
                        self.name, src.rel, fn.lineno, fn.col_offset,
                        f"MU step '{fn.name}' never opens "
                        f"jax.named_scope(\"{OPERAND_SCOPE}\") around its "
                        f"reads of the stored operand, itself or through "
                        f"a callee — its X passes would read as factor "
                        f"algebra", ERROR)
