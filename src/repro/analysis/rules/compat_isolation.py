"""compat-isolation: JAX feature detection lives ONLY in dist/compat.py.

PR 1 established the policy; PR 4 leaned on it (AxisType meshes); nothing
enforced it.  Outside ``repro/dist/compat.py`` this rule bans:

  * ``AxisType`` and ``log_compiles`` reached through any jax module
    alias (mesh axis types and compile-log parsing have one home each)
  * raw ``jax.__version__`` / ``jaxlib.__version__`` inspection
  * ``jax.make_mesh(...)`` (use ``repro.dist.compat.make_mesh``)
  * ``hasattr`` / ``getattr`` probes on jax modules
  * ``try: import jax...`` / ``except ImportError`` feature gates
"""
from __future__ import annotations

import ast

from ..framework import (
    ERROR,
    Finding,
    Rule,
    dotted,
    import_aliases,
    register,
    resolve_alias,
)

EXEMPT_SUFFIX = "repro/dist/compat.py"

VERSIONED_ATTRS = {
    "AxisType": "jax.sharding.AxisType is version-dependent",
    "log_compiles": "jax.log_compiles is a moving debug API",
}
VERSION_STRINGS = {"jax.__version__", "jaxlib.__version__"}
BANNED_CALLS = {
    "jax.make_mesh": "call repro.dist.compat.make_mesh instead",
}


def _is_jax_rooted(name: str) -> bool:
    return name == "jax" or name.startswith(("jax.", "jaxlib"))


@register
class CompatIsolation(Rule):
    name = "compat-isolation"
    description = ("version-dependent JAX APIs and feature probes belong "
                   "in dist/compat.py only")

    def check_file(self, src, ctx):
        if src.rel.endswith(EXEMPT_SUFFIX):
            return
        aliases = import_aliases(src.tree)

        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute):
                full = resolve_alias(dotted(node), aliases)
                if full in VERSION_STRINGS:
                    yield Finding(self.name, src.rel, node.lineno,
                                  node.col_offset,
                                  f"raw {full} check outside dist/compat.py",
                                  ERROR)
                elif node.attr in VERSIONED_ATTRS and _is_jax_rooted(full):
                    yield Finding(
                        self.name, src.rel, node.lineno, node.col_offset,
                        f"{VERSIONED_ATTRS[node.attr]}; import the shim "
                        f"from repro.dist.compat", ERROR)
            elif isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[0] == "jax":
                for a in node.names:
                    if a.name in VERSIONED_ATTRS:
                        yield Finding(
                            self.name, src.rel, node.lineno, node.col_offset,
                            f"importing {a.name} from {node.module}: "
                            f"{VERSIONED_ATTRS[a.name]}; use the "
                            f"repro.dist.compat shim", ERROR)
            elif isinstance(node, ast.Call):
                full = resolve_alias(dotted(node.func), aliases)
                if full in BANNED_CALLS:
                    yield Finding(self.name, src.rel, node.lineno,
                                  node.col_offset,
                                  f"{full}(): {BANNED_CALLS[full]}", ERROR)
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in ("hasattr", "getattr") and node.args:
                    target = resolve_alias(dotted(node.args[0]), aliases)
                    if _is_jax_rooted(target):
                        yield Finding(
                            self.name, src.rel, node.lineno, node.col_offset,
                            f"{node.func.id}() probe on {target}: feature "
                            f"detection belongs in dist/compat.py", ERROR)
            elif isinstance(node, ast.Try):
                yield from self._try_gate(node, src)

    def _try_gate(self, node: ast.Try, src):
        imports_jax = any(
            isinstance(stmt, (ast.Import, ast.ImportFrom)) and any(
                (a.name if isinstance(stmt, ast.Import)
                 else (stmt.module or "")).split(".")[0] == "jax"
                for a in stmt.names)
            for stmt in node.body)
        if not imports_jax:
            return
        for handler in node.handlers:
            names = []
            t = handler.type
            for e in (t.elts if isinstance(t, ast.Tuple) else [t]):
                d = dotted(e) if e is not None else None
                if d:
                    names.append(d)
            if any(n in ("ImportError", "ModuleNotFoundError",
                         "AttributeError") for n in names):
                yield Finding(
                    self.name, src.rel, node.lineno, node.col_offset,
                    "try/except import gate on a jax module: feature "
                    "detection belongs in dist/compat.py", ERROR)
                return
