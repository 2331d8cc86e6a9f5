"""The enginelint rule catalog (RL001-RL005).

Every rule encodes ONE engine contract (docs/developer-guide.md has the
catalog with rationale).  A rule is a callable
``rule(ctx: FileContext, registry) -> list[Finding]``; ``registry`` is
the cross-file state from :func:`collect_registry` (today: the fault
point registry for RL005).  Rules are heuristic by design — a correct
site a heuristic cannot prove safe takes a per-line suppression WITH a
written reason, which is itself enforced by ``--strict``.
"""
from __future__ import annotations

import ast
import re

from tools.enginelint import FileContext, Finding

__all__ = ["RULES", "collect_registry"]

_ENGINE_PREFIX = "spark_rapids_tpu/"


def _in_engine(ctx: FileContext) -> bool:
    return _ENGINE_PREFIX in ctx.rel or ctx.rel.startswith("spark_rapids_tpu")


def _engine_rel(ctx: FileContext) -> str:
    """Path relative to the spark_rapids_tpu package root ('' outside)."""
    i = ctx.rel.find("spark_rapids_tpu/")
    return ctx.rel[i + len("spark_rapids_tpu/"):] if i >= 0 else ""


# ---------------------------------------------------------------------------
# RL001: broad except that can swallow a terminal lifecycle exception
# ---------------------------------------------------------------------------

_BROAD = ("Exception", "BaseException")


def _names_broad(expr) -> bool:
    if isinstance(expr, ast.Name):
        return expr.id in _BROAD
    if isinstance(expr, ast.Attribute):
        return expr.attr in _BROAD
    if isinstance(expr, ast.Tuple):
        return any(_names_broad(e) for e in expr.elts)
    return False


def _handler_guarded(handler: ast.ExceptHandler) -> bool:
    """True when the handler body provably re-raises or discriminates on
    terminality: any ``raise``, any reference to ``terminal`` /
    ``is_terminal`` (getattr string, attribute, or name), or a call to a
    ``*reraise*`` helper."""
    for node in ast.walk(ast.Module(body=handler.body, type_ignores=[])):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Constant) and node.value == "terminal":
            return True
        if isinstance(node, ast.Attribute) and "terminal" in node.attr:
            return True
        if isinstance(node, ast.Name) and (
                "terminal" in node.id or "reraise" in node.id):
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else \
                fn.attr if isinstance(fn, ast.Attribute) else ""
            if "reraise" in name or "terminal" in name:
                return True
    return False


def rl001(ctx: FileContext, registry) -> list:
    if not _in_engine(ctx):
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is not None and not _names_broad(node.type):
            continue
        if _handler_guarded(node):
            continue
        out.append(Finding(
            "RL001", ctx.rel, node.lineno,
            "broad except may swallow a terminal lifecycle exception "
            "(QueryCancelled/QueryDeadlineExceeded/MapOutputLostError): "
            "re-raise, guard on getattr(e, 'terminal', False), or "
            "suppress with the reason the swallow is safe"))
    return out


# ---------------------------------------------------------------------------
# RL002: raw jax.jit at module/class scope outside compile_cache.py
# ---------------------------------------------------------------------------

def rl002(ctx: FileContext, registry) -> list:
    """jax.jit evaluated at import time (module or class scope,
    including decorators on top-level defs) builds an unguarded wrapper:
    it bypasses the CPU compile guard and the map-pressure purge."""
    if not _in_engine(ctx) or _engine_rel(ctx) == "exec/compile_cache.py":
        return []
    aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            aliases.update(a.asname or a.name for a in node.names
                           if a.name == "jit")

    def is_jit(expr) -> bool:
        if isinstance(expr, ast.Attribute) and expr.attr == "jit" and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id in ("jax", "_jax"):
            return True
        return isinstance(expr, ast.Name) and expr.id in aliases

    hits: list[int] = []

    def scan(node) -> None:
        """Import-time expression scan: descend everywhere EXCEPT into
        function/lambda bodies (those run at call time)."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in child.decorator_list:
                    if is_jit(dec) or (isinstance(dec, ast.Call)
                                       and is_jit(dec.func)):
                        hits.append(dec.lineno)
                continue
            if isinstance(child, ast.Lambda):
                continue
            if isinstance(child, ast.Call) and is_jit(child.func):
                hits.append(child.lineno)
            scan(child)

    scan(ctx.tree)
    return [Finding(
        "RL002", ctx.rel, line,
        "raw jax.jit at module/class scope: route through "
        "compile_cache.guarded_jit/shared_jit so the kernel passes the "
        "CPU compile guard and the map-pressure purge (the PR 7 "
        "SIGSEGV fix silently regresses otherwise)")
        for line in sorted(set(hits))]


# ---------------------------------------------------------------------------
# RL003: host-sync calls in exec hot paths outside transition modules
# ---------------------------------------------------------------------------

#: modules whose PURPOSE is the host<->device boundary
_RL003_WHITELIST = {"exec/core.py", "exec/transitions.py",
                    "exec/compile_cache.py"}


def rl003(ctx: FileContext, registry) -> list:
    rel = _engine_rel(ctx)
    if not rel.startswith("exec/") or rel in _RL003_WHITELIST:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "block_until_ready":
            what = ".block_until_ready()"
        elif isinstance(fn, ast.Attribute) and fn.attr == "device_get" and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id in ("jax", "_jax"):
            what = "jax.device_get()"
        elif isinstance(fn, ast.Name) and fn.id == "fetch_to_host":
            # exec/core.py's counted wrapper of jax.device_get
            what = "fetch_to_host()"
        else:
            continue
        out.append(Finding(
            "RL003", ctx.rel, node.lineno,
            f"host sync ({what}) in an exec hot path: each call stalls "
            "the dispatch pipeline; batch syncs into one stacked "
            "transfer or suppress documenting why this single sync is "
            "load-bearing"))
    return out


# ---------------------------------------------------------------------------
# RL004: unbounded loops without a lifecycle/cancel checkpoint
# ---------------------------------------------------------------------------

#: dispatch/drain/retry surface; exec/lifecycle.py IMPLEMENTS the
#: checkpoints so its own wait loops are excluded
_RL004_SCOPE = ("exec/", "shuffle/", "memory/")
_RL004_EXCLUDED = {"exec/lifecycle.py"}
_BUDGET_NAME = re.compile(r"retries|attempt", re.I)


def _loop_checkpointed(loop: ast.While) -> bool:
    has_raise = False
    has_budget_name = False
    for node in ast.walk(loop):
        if isinstance(node, ast.Raise):
            has_raise = True
        if isinstance(node, ast.Name):
            if "lifecycle" in node.id or node.id == "lc":
                return True
            if _BUDGET_NAME.search(node.id):
                has_budget_name = True
        if isinstance(node, ast.Attribute):
            if node.attr in ("check_cancel", "lifecycle"):
                return True
            if _BUDGET_NAME.search(node.attr):
                has_budget_name = True
    # a retry ladder bounded by an attempt budget that raises past it
    return has_raise and has_budget_name


def rl004(ctx: FileContext, registry) -> list:
    rel = _engine_rel(ctx)
    if not rel.startswith(_RL004_SCOPE) or rel in _RL004_EXCLUDED:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.While):
            continue
        t = node.test
        unbounded = isinstance(t, ast.Constant) and t.value in (True, 1)
        if not unbounded or _loop_checkpointed(node):
            continue
        out.append(Finding(
            "RL004", ctx.rel, node.lineno,
            "unbounded loop in a dispatch/drain/retry path with no "
            "lifecycle/cancel checkpoint: a cancelled or "
            "deadline-exceeded query cannot interrupt it; call "
            "lifecycle.check()/ctx.check_cancel() per iteration, bound "
            "it by a retry budget, or suppress with the reason it "
            "terminates"))
    return out


# ---------------------------------------------------------------------------
# RL005: fault-injection point names vs the faults.py registry
# ---------------------------------------------------------------------------

def collect_registry(ctxs) -> dict:
    """Cross-file pre-pass: KNOWN_POINTS from faults.py plus every
    ``*.check("point", ...)`` call site in the scanned set."""
    known: dict[str, tuple] = {}   # point -> (rel, line) of declaration
    used: dict[str, list] = {}     # point -> [(rel, line), ...]
    faults_file = None
    for ctx in ctxs:
        if _engine_rel(ctx) == "faults.py":
            faults_file = ctx
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "check" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                used.setdefault(node.args[0].value, []).append(
                    (ctx.rel, node.lineno))
    if faults_file is not None:
        for node in ast.walk(faults_file.tree):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "KNOWN_POINTS"
                    for t in node.targets):
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        known[sub.value] = (faults_file.rel, sub.lineno)
    return {"known": known, "used": used,
            "have_faults_file": faults_file is not None}


def rl005(ctx: FileContext, registry) -> list:
    if registry is None or not registry.get("have_faults_file") or \
            not _in_engine(ctx):
        return []
    known = registry["known"]
    used = registry["used"]
    out = []
    for point, sites in used.items():
        for rel, line in sites:
            if rel == ctx.rel and point not in known:
                out.append(Finding(
                    "RL005", ctx.rel, line,
                    f"fault-injection point '{point}' is not registered "
                    "in faults.KNOWN_POINTS: a fault plan naming it "
                    "would silently never fire"))
    if _engine_rel(ctx) == "faults.py":
        for point, (rel, line) in sorted(known.items()):
            if point not in used:
                out.append(Finding(
                    "RL005", ctx.rel, line,
                    f"registered fault point '{point}' has no "
                    "faults.check() call site: dead registry entry or a "
                    "renamed injection site"))
    return out


RULES = {"RL001": rl001, "RL002": rl002, "RL003": rl003,
         "RL004": rl004, "RL005": rl005}
