"""enginelint: AST-based engine-specific lint for spark_rapids_tpu.

The engine's correctness rests on conventions no general linter knows:
terminal lifecycle exceptions must never be swallowed, every
module-level jit must route through the compile cache's guarded
wrappers, hot exec paths must not sync to host, dispatch/drain/retry
loops must hit a cancellation checkpoint, and fault-injection point
names must match the registry.  Each rule here encodes one of those
contracts over the Python AST — stdlib only, no engine import, so the
lint runs in any environment (before, or without, jax).

Usage::

    python -m tools.enginelint spark_rapids_tpu/ [--strict]

Per-line suppression (same line as the finding, or the immediately
preceding comment-only line)::

    except Exception:  # enginelint: disable=RL001 (diag is best-effort)

``--strict`` additionally fails any suppression that carries no written
reason, so every accepted violation documents WHY it is safe.  The rule
catalog lives in tools/enginelint/rules.py and the invariant each rule
enforces in docs/developer-guide.md.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

__all__ = ["Finding", "FileContext", "lint_file", "lint_source",
           "run_lint", "iter_py_files", "SUPPRESS_RE"]

SUPPRESS_RE = re.compile(
    r"#\s*enginelint:\s*disable=([A-Za-z0-9_,]+)\s*(?:\(([^)]*)\))?")


@dataclass
class Finding:
    """One rule violation (or, in strict mode, one bad suppression)."""
    rule: str
    path: str          # repo-relative path
    line: int
    message: str
    suppressed: bool = False
    reason: str | None = None

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.rule}{tag} {self.message}"


@dataclass
class FileContext:
    """Parsed view of one source file handed to every rule."""
    path: str                      # absolute
    rel: str                       # repo-relative, forward slashes
    source: str
    tree: ast.AST
    lines: list[str] = field(default_factory=list)
    # line -> {rule_or_ALL: reason_or_None}
    suppressions: dict = field(default_factory=dict)

    @classmethod
    def parse(cls, path: str, rel: str, source: str) -> "FileContext":
        ctx = cls(path=path, rel=rel, source=source,
                  tree=ast.parse(source, filename=rel),
                  lines=source.splitlines())
        for i, text in enumerate(ctx.lines, start=1):
            m = SUPPRESS_RE.search(text)
            if not m:
                continue
            reason = (m.group(2) or "").strip() or None
            per = ctx.suppressions.setdefault(i, {})
            for rule in m.group(1).split(","):
                per[rule.strip().upper()] = reason
        return ctx

    def suppression_for(self, rule: str, line: int):
        """(found, reason) for ``rule`` at ``line``: same line, or an
        immediately preceding comment-only line."""
        for cand in (line, line - 1):
            per = self.suppressions.get(cand)
            if per is None:
                continue
            if cand == line - 1 and \
                    not self.lines[cand - 1].lstrip().startswith("#"):
                continue  # trailing comment of the PREVIOUS statement
            for key in (rule, "ALL"):
                if key in per:
                    return True, per[key]
        return False, None


def iter_py_files(paths) -> list[str]:
    out = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(os.path.abspath(p))
            continue
        for base, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs
                             if d not in ("__pycache__", ".git"))
            out.extend(os.path.join(base, f) for f in sorted(files)
                       if f.endswith(".py"))
    return out


def _relpath(path: str, root: str | None) -> str:
    root = root or os.getcwd()
    try:
        rel = os.path.relpath(path, root)
    except ValueError:
        rel = path
    return rel.replace(os.sep, "/")


def lint_source(source: str, rel: str, rules=None,
                registry=None) -> list[Finding]:
    """Lint one in-memory source blob (unit tests); suppressions are
    applied, suppressed findings returned with ``suppressed=True``."""
    from tools.enginelint.rules import RULES
    ctx = FileContext.parse(rel, rel, source)
    return _apply(ctx, rules or RULES, registry)


def lint_file(path: str, rel: str, rules=None, registry=None) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    ctx = FileContext.parse(path, rel, source)
    from tools.enginelint.rules import RULES
    return _apply(ctx, rules or RULES, registry)


def _apply(ctx: FileContext, rules, registry) -> list[Finding]:
    out: list[Finding] = []
    for rule in rules.values():
        for f in rule(ctx, registry):
            f.suppressed, f.reason = ctx.suppression_for(f.rule, f.line)
            out.append(f)
    out.sort(key=lambda f: (f.line, f.rule))
    return out


def run_lint(paths, root: str | None = None,
             rules=None) -> list[Finding]:
    """Lint every .py file under ``paths``.  Returns ALL findings —
    callers filter on ``suppressed`` / ``reason``.  Cross-file state
    (the fault-point registry for RL005) is collected in a first pass
    over the same file set."""
    from tools.enginelint.rules import RULES, collect_registry
    rules = rules or RULES
    files = iter_py_files(paths)
    ctxs = []
    for path in files:
        rel = _relpath(path, root)
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            ctxs.append(FileContext.parse(path, rel, source))
        except SyntaxError as e:
            raise SystemExit(f"enginelint: cannot parse {rel}: {e}")
    registry = collect_registry(ctxs)
    findings: list[Finding] = []
    for ctx in ctxs:
        findings.extend(_apply(ctx, rules, registry))
    return findings
