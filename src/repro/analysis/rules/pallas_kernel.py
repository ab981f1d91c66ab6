"""pallas-kernel: panel budgets, ref-index idiom, compiler params.

Applies to any module importing ``jax.experimental.pallas``.  Three checks:

  * **ref indexing** — kernels read and write refs by indexing
    (``x = x_ref[i]``, ``o_ref[0, r] += v``).  ``pl.load``/``pl.store``
    no longer exist in the pinned JAX and are rejected; and a slice in a
    ``*_ref[...]`` index must have static (literal) bounds, because a
    ref slice with a computed start fails at trace time — a dynamic
    window is written ``pl.ds(start, size)``
  * **resident-panel budget** — a kernel whose out BlockSpec index_map
    ignores one or more grid axes keeps that output panel resident in
    VMEM across the ignored axes (it accumulates).  Such a kernel must be
    dispatched behind a static VMEM budget check (a caller referencing
    ``_panel_overflow`` / ``VMEM_PANEL_BYTES``, with a ref fallback —
    the PR 5 contract in kernels/ops.py)
  * **compiler params** — ``pallas_call`` should pass
    ``compiler_params=pltpu.CompilerParams(dimension_semantics=...)``;
    a call without them is a warning
"""
from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from ..framework import (
    ERROR,
    WARNING,
    Finding,
    Rule,
    dotted,
    import_aliases,
    register,
    resolve_alias,
)

PALLAS_MODULE = "jax.experimental.pallas"
REMOVED_REF_CALLS = (".load", ".store")
REF_SUFFIX = "_ref"
BUDGET_MARKERS = {"_panel_overflow", "VMEM_PANEL_BYTES"}


def _uses_pallas(aliases: Dict[str, str]) -> bool:
    return any(full.startswith(PALLAS_MODULE) for full in aliases.values())


def _lambda_unused_params(lam: ast.Lambda) -> List[str]:
    params = [a.arg for a in lam.args.args]
    used = {n.id for n in ast.walk(lam.body) if isinstance(n, ast.Name)}
    return [p for p in params if p not in used]


def _static_bytes(shape_node: ast.AST) -> Tuple[int, List[str]]:
    """(product of constant dims, names of symbolic dims) for a BlockSpec."""
    prod, symbolic = 1, []
    if isinstance(shape_node, (ast.Tuple, ast.List)):
        for e in shape_node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                prod *= e.value
            else:
                symbolic.append(ast.unparse(e) if hasattr(ast, "unparse")
                                else "?")
    return prod, symbolic


def _relative_aliases(tree: ast.AST) -> Dict[str, Tuple[str, str]]:
    """local name -> (module stem, original name) for relative imports."""
    out: Dict[str, Tuple[str, str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level > 0:
            stem = (node.module or "").split(".")[-1]
            for a in node.names:
                out[a.asname or a.name] = (stem, a.name)
    return out


class _KernelInfo:
    def __init__(self, rel: str, fn: ast.FunctionDef, module_stem: str):
        self.rel = rel
        self.fn = fn
        self.module_stem = module_stem
        self.resident_axes: List[str] = []
        self.panel_desc = ""


@register
class PallasKernel(Rule):
    name = "pallas-kernel"
    description = ("VMEM panel budgets, ref-index idiom, and compiler "
                   "params in Pallas kernels")

    def check_file(self, src, ctx):
        aliases = import_aliases(src.tree)
        if not _uses_pallas(aliases):
            return
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Subscript):
                yield from self._check_ref_index(node, src)
                continue
            if not isinstance(node, ast.Call):
                continue
            full = resolve_alias(dotted(node.func), aliases)
            if full.endswith(REMOVED_REF_CALLS) and \
                    full.startswith(PALLAS_MODULE):
                yield Finding(
                    self.name, src.rel, node.lineno, node.col_offset,
                    f"{dotted(node.func)}() is gone from the pinned JAX; "
                    f"index the ref instead (x = x_ref[idx], "
                    f"o_ref[idx] = v)", ERROR)
            elif full.endswith("pallas_call"):
                yield from self._check_compiler_params(node, src)

    # -- ref-index idiom --------------------------------------------------

    def _check_ref_index(self, sub: ast.Subscript, src):
        name = dotted(sub.value) or ""
        if not name.endswith(REF_SUFFIX):
            return
        idx = sub.slice
        elements = idx.elts if isinstance(idx, ast.Tuple) else [idx]
        for e in elements:
            if not isinstance(e, ast.Slice):
                continue
            bounds = [b for b in (e.lower, e.upper, e.step) if b is not None]
            if all(isinstance(b, ast.Constant) for b in bounds):
                continue
            yield Finding(
                self.name, src.rel, e.lineno, e.col_offset,
                f"ref slice '{_snippet(e)}' on {name} has computed bounds "
                f"— a ref slice must be static; write the dynamic window "
                f"as pl.ds(start, size)", ERROR)

    # -- compiler params --------------------------------------------------

    def _check_compiler_params(self, call: ast.Call, src):
        if any(kw.arg == "compiler_params" for kw in call.keywords):
            return
        # no compiler_params at all: acceptable for interpret-only kernels
        yield Finding(
            self.name, src.rel, call.lineno, call.col_offset,
            "pallas_call without compiler_params — pass "
            "pltpu.CompilerParams(dimension_semantics=...)", WARNING)

    # -- resident-panel budget (cross-file) -------------------------------

    def check_project(self, ctx):
        kernels: List[_KernelInfo] = []
        for src in ctx.files:
            aliases = import_aliases(src.tree)
            if not _uses_pallas(aliases):
                continue
            stem = src.rel.rsplit("/", 1)[-1][:-3]
            for fn in ast.walk(src.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                info = self._resident_info(fn, src.rel, stem)
                if info is not None:
                    kernels.append(info)
        if not kernels:
            return

        # which functions anywhere call each kernel, and are they
        # budget-aware (reference _panel_overflow / VMEM_PANEL_BYTES)?
        for kern in kernels:
            gated, callers = self._find_dispatch(kern, ctx)
            if callers and not gated:
                yield Finding(
                    self.name, kern.rel, kern.fn.lineno,
                    kern.fn.col_offset,
                    f"kernel '{kern.fn.name}' keeps an output panel "
                    f"resident in VMEM across grid axis(es) "
                    f"{kern.resident_axes} ({kern.panel_desc}) but no "
                    f"caller checks the panel budget — dispatch it behind "
                    f"_panel_overflow()/VMEM_PANEL_BYTES with a ref "
                    f"fallback (kernels/ops.py contract)", ERROR)
            elif not callers:
                yield Finding(
                    self.name, kern.rel, kern.fn.lineno,
                    kern.fn.col_offset,
                    f"kernel '{kern.fn.name}' accumulates a resident VMEM "
                    f"panel ({kern.panel_desc}) and has no budget-gated "
                    f"dispatcher at all", ERROR)

    def _resident_info(self, fn, rel, stem):
        has_pallas_call = False
        info = _KernelInfo(rel, fn, stem)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted(node.func) or ""
                if d.split(".")[-1] == "pallas_call":
                    has_pallas_call = True
                for kw in node.keywords:
                    if kw.arg != "out_specs":
                        continue
                    for spec in ast.walk(kw.value):
                        if not (isinstance(spec, ast.Call) and
                                (dotted(spec.func) or "").split(".")[-1]
                                == "BlockSpec"):
                            continue
                        if len(spec.args) < 2 or \
                                not isinstance(spec.args[1], ast.Lambda):
                            continue
                        unused = _lambda_unused_params(spec.args[1])
                        if unused:
                            info.resident_axes.extend(unused)
                            prod, sym = _static_bytes(spec.args[0])
                            desc = f"block >= {prod} elems"
                            if sym:
                                desc += f" x {' x '.join(sym)}"
                            info.panel_desc = desc
        if has_pallas_call and info.resident_axes:
            return info
        return None

    def _find_dispatch(self, kern: _KernelInfo, ctx):
        gated, callers = False, []
        for src in ctx.files:
            rel_aliases = _relative_aliases(src.tree)
            abs_aliases = import_aliases(src.tree)
            for fn in ast.walk(src.tree):
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) or \
                        fn is kern.fn:
                    continue
                calls_kernel = False
                for node in ast.walk(fn):
                    if not (isinstance(node, ast.Call) and
                            isinstance(node.func, ast.Name)):
                        continue
                    n = node.func.id
                    if src.rel == kern.rel and n == kern.fn.name:
                        calls_kernel = True
                    elif n in rel_aliases:
                        stem, orig = rel_aliases[n]
                        if stem == kern.module_stem and \
                                orig == kern.fn.name:
                            calls_kernel = True
                    elif abs_aliases.get(n, "").endswith(
                            f"{kern.module_stem}.{kern.fn.name}"):
                        calls_kernel = True
                if not calls_kernel:
                    continue
                callers.append((src.rel, fn.name))
                body_names = {x.id for x in ast.walk(fn)
                              if isinstance(x, ast.Name)}
                body_attrs = {x.attr for x in ast.walk(fn)
                              if isinstance(x, ast.Attribute)}
                if (body_names | body_attrs) & BUDGET_MARKERS:
                    gated = True
        return gated, callers


def _snippet(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return "<expr>"
