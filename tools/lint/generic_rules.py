"""Curated generic lint layer: the ruff subset this repo cares about.

When a ``ruff`` binary is on PATH the real tool runs with exactly these
rules (F401 unused import, F841 unused local, B006 mutable default
argument, F541 f-string without placeholders).  This container bakes no
ruff and nothing may be pip-installed, so a built-in AST fallback
implements the same four checks under the same ids — both engines emit
``GEN-Fxxx``/``GEN-B006`` findings so the baseline and the ``--json``
rule->count payload are engine-stable.

The fallback honors ``# noqa`` comments on the flagged line (the repo's
re-export surfaces are annotated ``# noqa: F401`` already) and skips
``__init__.py`` files for F401 (re-exports ARE the point there).
"""

from __future__ import annotations

import ast
import json
import os
import re
import shutil
import subprocess

RUFF_SELECT = "F401,F841,B006,F541"
_RULE_IDS = {"F401": "GEN-F401", "F841": "GEN-F841", "B006": "GEN-B006", "F541": "GEN-F541"}


def engine() -> str:
    return "ruff" if shutil.which("ruff") else "fallback"


def run_ruff(root: str, files: list[str]) -> list:
    """Real-ruff path: curated select list, JSON output mapped to Findings."""
    from .core import Finding

    proc = subprocess.run(
        ["ruff", "check", "--select", RUFF_SELECT, "--output-format", "json", *files],
        cwd=root,
        capture_output=True,
        text=True,
    )
    out = []
    try:
        rows = json.loads(proc.stdout or "[]")
    except json.JSONDecodeError:
        rows = []
    for row in rows:
        rel = os.path.relpath(row["filename"], root).replace(os.sep, "/")
        if rel.endswith("__init__.py") and row["code"] == "F401":
            continue
        out.append(
            Finding(
                rule=_RULE_IDS.get(row["code"], f"GEN-{row['code']}"),
                path=rel,
                line=row["location"]["row"],
                col=row["location"]["column"],
                message=row["message"],
                snippet="",
            )
        )
    return out


def _has_noqa(module, line: int) -> bool:
    text = module.lines[line - 1] if 1 <= line <= len(module.lines) else ""
    return "noqa" in text


# ------------------------------------------------------- GEN-F401


def rule_unused_import(module) -> list:
    if module.relpath.endswith("__init__.py"):
        return []
    imported: dict[str, ast.AST] = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                imported[alias.asname or alias.name.split(".")[0]] = node
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                imported[alias.asname or alias.name] = node
    if not imported:
        return []
    used: set = set()
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass  # root Name covered above
    # names referenced from string constants (quoted annotations, __all__)
    blob = "\n".join(
        n.value for n in ast.walk(module.tree)
        if isinstance(n, ast.Constant) and isinstance(n.value, str)
    )
    out = []
    for name, node in imported.items():
        if name in used:
            continue
        if re.search(rf"\b{re.escape(name)}\b", blob):
            continue
        if _has_noqa(module, node.lineno):
            continue
        out.append(
            module.finding("GEN-F401", node, f"unused import '{name}'")
        )
    return out


# ------------------------------------------------------- GEN-F841


def _scope_nodes(fn):
    """The function's OWN-scope nodes: nested classes/functions/lambdas are
    separate scopes (class attributes are not locals; nested defs get their
    own pass).  Loads still count from the whole subtree — closures read
    outer locals."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def rule_unused_local(module) -> list:
    from .project_rules import _functions

    out = []
    for qualname, fn in _functions(module.tree):
        if any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name)
            and n.func.id in ("locals", "vars", "eval", "exec")
            for n in ast.walk(fn)
        ):
            continue
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs + fn.args.posonlyargs}
        if fn.args.vararg:
            params.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            params.add(fn.args.kwarg.arg)
        assigned: dict[str, ast.AST] = {}
        declared: set = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                declared.update(node.names)
        for node in _scope_nodes(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and isinstance(
                node.targets[0], ast.Name
            ):
                name = node.targets[0].id
                if not name.startswith("_") and name not in params and name not in declared:
                    assigned.setdefault(name, node)
            elif isinstance(node, ast.ExceptHandler) and node.name:
                if not node.name.startswith("_"):
                    assigned.setdefault(node.name, node)
            elif isinstance(node, ast.withitem) and isinstance(
                node.optional_vars, ast.Name
            ):
                name = node.optional_vars.id
                if not name.startswith("_") and name not in params:
                    assigned.setdefault(name, node.optional_vars)
        if not assigned:
            continue
        loaded: set = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                loaded.add(node.id)
            elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Del):
                loaded.add(node.id)
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                loaded.update(node.names)
        # except-handler names are also "loaded" via re-raise idioms the AST
        # shows as Name loads; nothing special needed
        for name, node in assigned.items():
            if name in loaded:
                continue
            line = getattr(node, "lineno", fn.lineno)
            if _has_noqa(module, line):
                continue
            # context stays "<module>" (not the qualname) so the baseline
            # key is identical whichever engine produced the finding
            out.append(
                module.finding(
                    "GEN-F841",
                    node,
                    f"local '{name}' assigned but never used (in {qualname})",
                )
            )
    return out


# ------------------------------------------------------- GEN-B006


def rule_mutable_default(module) -> list:
    from .project_rules import _functions

    out = []
    for qualname, fn in _functions(module.tree):
        for default in list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None
        ]:
            mutable = isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set")
            )
            if mutable and not _has_noqa(module, default.lineno):
                out.append(
                    module.finding(
                        "GEN-B006",
                        default,
                        "mutable default argument — shared across calls; use "
                        f"None + in-body construction (in {qualname})",
                    )
                )
    return out


# ------------------------------------------------------- GEN-F541


def rule_fstring_no_placeholder(module) -> list:
    out = []
    # a FormattedValue's format_spec (":.3e") parses as a nested JoinedStr
    # of constants — those are not f-strings in the source, skip them
    spec_ids = {
        id(n.format_spec)
        for n in ast.walk(module.tree)
        if isinstance(n, ast.FormattedValue) and n.format_spec is not None
    }
    for node in ast.walk(module.tree):
        if id(node) in spec_ids:
            continue
        if isinstance(node, ast.JoinedStr) and not any(
            isinstance(v, ast.FormattedValue) for v in node.values
        ):
            if not _has_noqa(module, node.lineno):
                out.append(
                    module.finding(
                        "GEN-F541", node, "f-string without any placeholders"
                    )
                )
    return out


RULES = (
    rule_unused_import,
    rule_unused_local,
    rule_mutable_default,
    rule_fstring_no_placeholder,
)
