"""Lint engine: file collection, suppression, baseline, runner.

Findings are matched against the baseline by ``(rule, path, context,
snippet)`` — deliberately line-number-free, so unrelated edits above a
grandfathered finding don't resurrect it, while any change to the flagged
line itself re-reports it for a fresh look.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(REPO_ROOT, "tools", "lint", "baseline.json")

#: repo-relative roots scanned by default.  ``__graft_entry__.py`` is the
#: external harness shim and stays out of scope.
DEFAULT_SCOPE = (
    "rustpde_mpi_tpu",
    "scripts",
    "tools",
    "tests",
    "examples",
    "plot",
)
_EXCLUDE_PARTS = {"__pycache__", ".jax_cache", "data"}

_SUPPRESS_RE = re.compile(r"#\s*lint-ok:\s*(\S.*)$")
_RULE_TOKEN_RE = re.compile(r"^(RPD\d+|GEN-[A-Z0-9]+|all)$")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    col: int
    message: str
    snippet: str = ""
    context: str = "<module>"

    def key(self) -> tuple:
        return (self.rule, self.path, self.context, self.snippet)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclasses.dataclass
class LintResult:
    new: list  # unsuppressed, un-baselined findings (these fail the run)
    baselined: list
    suppressed: int
    files: int
    engine: str  # "ruff" | "fallback" for the generic layer
    stale_baseline: list  # baseline entries that no longer match anything

    @property
    def counts(self) -> dict:
        out: dict[str, int] = {}
        for f in self.new:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    @property
    def baselined_counts(self) -> dict:
        out: dict[str, int] = {}
        for f in self.baselined:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


class Module:
    """One parsed source file handed to every rule."""

    def __init__(self, relpath: str, source: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=relpath)

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def finding(self, rule: str, node, message: str, context: str = "<module>") -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(
            rule=rule,
            path=self.relpath,
            line=line,
            col=getattr(node, "col_offset", 0),
            message=message,
            snippet=self.snippet(line),
            context=context,
        )


def collect_files(root: str = REPO_ROOT, paths=None) -> list[str]:
    """Repo-relative .py files in scope (sorted, deterministic)."""
    rels: list[str] = []
    scope = paths if paths else DEFAULT_SCOPE
    for entry in scope:
        full = os.path.join(root, entry)
        if os.path.isfile(full) and entry.endswith(".py"):
            rels.append(entry)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames if d not in _EXCLUDE_PARTS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rels.append(os.path.relpath(os.path.join(dirpath, fn), root))
    return sorted(set(r.replace(os.sep, "/") for r in rels))


def _suppressions(module: Module) -> tuple[dict[int, set], list[Finding]]:
    """Per-line ``# lint-ok: <RULES> <reason>`` suppressions.  A suppression
    without a reason is itself a finding (RPD000) — grandfathering demands
    a written why, inline or in the baseline."""
    table: dict[int, set] = {}
    bad: list[Finding] = []
    for i, text in enumerate(module.lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        # leading rule-shaped tokens (comma- or space-separated) are the
        # rule list; everything after the first non-rule token is the reason
        tokens = m.group(1).split()
        rules: set = set()
        reason_at = len(tokens)
        for j, tok in enumerate(tokens):
            tok = tok.rstrip(",")
            if _RULE_TOKEN_RE.match(tok):
                rules.add(tok)
            else:
                reason_at = j
                break
        if not rules:
            continue  # prose mentioning the marker, not a suppression attempt
        if reason_at >= len(tokens):
            bad.append(
                Finding(
                    rule="RPD000",
                    path=module.relpath,
                    line=i,
                    col=0,
                    message="lint-ok suppression without a reason",
                    snippet=module.snippet(i),
                )
            )
            continue
        table[i] = rules
    return table, bad


def load_baseline(path: str = DEFAULT_BASELINE) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as fh:
        payload = json.load(fh)
    return payload.get("entries", [])


def save_baseline(entries: list[dict], path: str = DEFAULT_BASELINE) -> None:
    payload = {
        "comment": (
            "Grandfathered lint findings: every entry carries a written "
            "reason.  Matched by (rule, path, context, snippet) — editing "
            "the flagged line re-reports the finding for a fresh look."
        ),
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=False)
        fh.write("\n")


def lint_source(source: str, relpath: str) -> list[Finding]:
    """Run every rule over one in-memory module (the test-fixture entry
    point: ``relpath`` decides rule scoping).  Inline suppressions apply;
    no baseline."""
    from . import generic_rules, project_rules

    module = Module(relpath, source)
    table, bad = _suppressions(module)
    findings = list(bad)
    for rule_fn in project_rules.RULES + generic_rules.RULES:
        findings.extend(rule_fn(module))
    return [
        f
        for f in _dedupe(findings)
        if not (f.rule in table.get(f.line, ()) or "all" in table.get(f.line, ()))
    ]


def _dedupe(findings):
    """Nested functions are visited from every enclosing scope — keep the
    first (outermost-context) finding per (rule, line, col)."""
    seen, out = set(), []
    for f in findings:
        k = (f.rule, f.path, f.line, f.col)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


def run_lint(
    root: str = REPO_ROOT,
    paths=None,
    baseline_path: str = DEFAULT_BASELINE,
) -> LintResult:
    from . import generic_rules, project_rules

    files = collect_files(root, paths)
    findings: list[Finding] = []
    suppressed = 0
    parse_failures: list[Finding] = []
    modules: list[Module] = []
    for rel in files:
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as fh:
                module = Module(rel, fh.read())
        except SyntaxError as exc:
            parse_failures.append(
                Finding(rule="RPD999", path=rel, line=exc.lineno or 1, col=0,
                        message=f"syntax error: {exc.msg}")
            )
            continue
        modules.append(module)

    engine = generic_rules.engine()
    # ruff-engine findings are folded into the per-module stream so inline
    # suppressions apply identically, and their snippet/context are filled
    # from the parsed module so baseline keys stay ENGINE-STABLE (a
    # baseline written on a ruff machine must match on a ruff-less one)
    ruff_by_file: dict[str, list[Finding]] = {}
    if engine == "ruff":
        for f in generic_rules.run_ruff(root, files):
            ruff_by_file.setdefault(f.path, []).append(f)
    for module in modules:
        table, bad = _suppressions(module)
        raw = list(bad)
        for rule_fn in project_rules.RULES:
            raw.extend(rule_fn(module))
        if engine == "fallback":
            for rule_fn in generic_rules.RULES:
                raw.extend(rule_fn(module))
        else:
            for f in ruff_by_file.get(module.relpath, ()):
                f.snippet = module.snippet(f.line)
                raw.append(f)
        for f in _dedupe(raw):
            if f.rule in table.get(f.line, ()) or "all" in table.get(f.line, ()):
                suppressed += 1
            else:
                findings.append(f)
    findings.extend(parse_failures)

    baseline = load_baseline(baseline_path)
    base_keys = {
        (e["rule"], e["path"], e.get("context", "<module>"), e.get("snippet", "")): e
        for e in baseline
    }
    new, baselined, matched = [], [], set()
    for f in findings:
        if f.key() in base_keys:
            baselined.append(f)
            matched.add(f.key())
        else:
            new.append(f)
    stale = [e for k, e in base_keys.items() if k not in matched]
    new.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(
        new=new,
        baselined=baselined,
        suppressed=suppressed,
        files=len(files),
        engine=engine,
        stale_baseline=stale,
    )
