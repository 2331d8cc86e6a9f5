"""enginelint (tools/enginelint): rule catalog, suppression syntax, and
the live-tree meta-gate.

Each rule gets a positive (flagged) and negative (clean) synthetic
snippet through :func:`lint_source` with an engine-looking path — the
rules scope themselves by path, so the snippets never touch real
engine files.  The meta-test lints the REAL spark_rapids_tpu tree and
asserts it is clean under ``--strict`` semantics: zero unsuppressed
findings and zero suppressions without a written reason.
"""
import os
import textwrap

from tools.enginelint import lint_source, run_lint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _active(findings, rule=None):
    return [f for f in findings
            if not f.suppressed and (rule is None or f.rule == rule)]


def _lint(src, rel="spark_rapids_tpu/exec/snippet.py"):
    return lint_source(textwrap.dedent(src), rel)


# ---------------------------------------------------------------------------
# RL001: broad except swallowing terminal lifecycle exceptions
# ---------------------------------------------------------------------------

def test_rl001_flags_bare_and_broad_except():
    src = """
    def f():
        try:
            g()
        except Exception:
            pass
        try:
            g()
        except (ValueError, BaseException):
            log()
        try:
            g()
        except:
            pass
    """
    hits = _active(_lint(src), "RL001")
    assert len(hits) == 3


def test_rl001_passes_guarded_handlers():
    src = """
    def f():
        try:
            g()
        except Exception as e:
            if getattr(e, "terminal", False):
                raise
            log(e)
        try:
            g()
        except ValueError:
            pass
        try:
            g()
        except Exception as e:
            reraise_terminal(e)
    """
    assert _active(_lint(src), "RL001") == []


def test_rl001_outside_engine_tree_ignored():
    src = "try:\n    g()\nexcept Exception:\n    pass\n"
    assert _active(lint_source(src, "tools/somewhere.py"), "RL001") == []


# ---------------------------------------------------------------------------
# RL002: raw jax.jit at import time
# ---------------------------------------------------------------------------

def test_rl002_flags_module_scope_and_decorator_jit():
    src = """
    import jax
    from jax import jit

    _k = jax.jit(lambda x: x + 1)

    @jax.jit
    def f(x):
        return x

    @jit
    def g(x):
        return x

    class C:
        h = jax.jit(lambda x: x)
    """
    hits = _active(_lint(src), "RL002")
    assert len(hits) == 4


def test_rl002_passes_call_time_and_compile_cache():
    src = """
    import jax

    def build():
        return jax.jit(lambda x: x)  # call time: guarded by the caller
    """
    assert _active(_lint(src), "RL002") == []
    modscope = "import jax\n_k = jax.jit(lambda x: x)\n"
    assert _active(lint_source(
        modscope, "spark_rapids_tpu/exec/compile_cache.py"), "RL002") == []


# ---------------------------------------------------------------------------
# RL003: host syncs in exec hot paths
# ---------------------------------------------------------------------------

def test_rl003_flags_sync_calls_in_exec():
    src = """
    import jax

    def pull(batches):
        n = jax.device_get(batches[0])
        batches[1].block_until_ready()
        return n
    """
    assert len(_active(_lint(src), "RL003")) == 2


def test_rl003_flags_the_counted_fetch_helper():
    src = """
    from spark_rapids_tpu.exec.core import fetch_to_host

    def pull(batches):
        return fetch_to_host(batches[0], "fetch@SomeExec")
    """
    assert len(_active(_lint(src), "RL003")) == 1


def test_rl003_whitelisted_modules_and_other_layers_pass():
    src = "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
    for rel in ("spark_rapids_tpu/exec/transitions.py",
                "spark_rapids_tpu/exec/core.py",
                "spark_rapids_tpu/shuffle/tcp.py"):
        assert _active(lint_source(src, rel), "RL003") == []


# ---------------------------------------------------------------------------
# RL004: unbounded loops without a cancellation checkpoint
# ---------------------------------------------------------------------------

def test_rl004_flags_unbounded_dispatch_loop():
    src = """
    def drain(q):
        while True:
            item = q.get()
            handle(item)
    """
    assert len(_active(_lint(src), "RL004")) == 1


def test_rl004_passes_checkpointed_and_budgeted_loops():
    src = """
    def drain(q, lifecycle):
        while True:
            lifecycle.check()
            handle(q.get())

    def pull(ctx):
        while True:
            ctx.check_cancel()
            step()

    def retry(fn):
        attempts = 0
        while True:
            try:
                return fn()
            except OSError:
                attempts += 1
                if attempts > 3:
                    raise
    """
    assert _active(_lint(src), "RL004") == []


def test_rl004_scoped_to_dispatch_layers():
    src = "def f():\n    while True:\n        step()\n"
    assert _active(lint_source(
        src, "spark_rapids_tpu/plan/overrides.py"), "RL004") == []
    assert _active(lint_source(
        src, "spark_rapids_tpu/exec/lifecycle.py"), "RL004") == []


# ---------------------------------------------------------------------------
# RL005: fault point names vs the faults.py registry (cross-file)
# ---------------------------------------------------------------------------

def test_rl005_both_directions(tmp_path):
    pkg = tmp_path / "spark_rapids_tpu"
    pkg.mkdir()
    (pkg / "faults.py").write_text(
        'KNOWN_POINTS = frozenset({"tcp.reset", "never.fired"})\n')
    (pkg / "shuffle.py").write_text(textwrap.dedent("""
        def serve(faults):
            faults.check("tcp.reset", {})
            faults.check("tcp.tpyo", {})
    """))
    findings = _active(run_lint([str(tmp_path)], root=str(tmp_path)),
                       "RL005")
    assert len(findings) == 2
    blob = "\n".join(f.message for f in findings)
    assert "tcp.tpyo" in blob and "not registered" in blob
    assert "never.fired" in blob and "no faults.check() call" in blob


def test_rl005_silent_without_faults_file(tmp_path):
    pkg = tmp_path / "spark_rapids_tpu"
    pkg.mkdir()
    (pkg / "shuffle.py").write_text(
        'def serve(faults):\n    faults.check("tcp.reset", {})\n')
    assert _active(run_lint([str(tmp_path)], root=str(tmp_path)),
                   "RL005") == []


# ---------------------------------------------------------------------------
# suppression syntax
# ---------------------------------------------------------------------------

def test_suppression_same_line_with_reason():
    src = """
    def f():
        try:
            g()
        except Exception:  # enginelint: disable=RL001 (diag best-effort)
            pass
    """
    findings = _lint(src)
    (f,) = [f for f in findings if f.rule == "RL001"]
    assert f.suppressed and f.reason == "diag best-effort"


def test_suppression_preceding_comment_line():
    src = """
    def f():
        try:
            g()
        # enginelint: disable=RL001 (cleanup must not mask)
        except Exception:
            pass
    """
    (f,) = [f for f in _lint(src) if f.rule == "RL001"]
    assert f.suppressed and f.reason == "cleanup must not mask"


def test_suppression_without_reason_is_tracked():
    src = """
    def f():
        try:
            g()
        except Exception:  # enginelint: disable=RL001
            pass
    """
    (f,) = [f for f in _lint(src) if f.rule == "RL001"]
    assert f.suppressed and f.reason is None  # --strict fails this


def test_suppression_is_per_rule():
    src = """
    import jax

    def f(x):
        try:
            return jax.device_get(x)
        except Exception:  # enginelint: disable=RL003 (wrong rule)
            pass
    """
    findings = _lint(src)
    rl001 = [f for f in findings if f.rule == "RL001"]
    assert rl001 and not rl001[0].suppressed


def test_trailing_comment_of_previous_statement_does_not_leak():
    src = """
    def f():
        g()  # enginelint: disable=RL004 (about g, not the loop)
        while True:
            step()
    """
    (f,) = [f for f in _lint(src) if f.rule == "RL004"]
    assert not f.suppressed


# ---------------------------------------------------------------------------
# meta-gate: the live tree lints clean under --strict semantics
# ---------------------------------------------------------------------------

def test_live_tree_lints_clean_strict():
    findings = run_lint([os.path.join(_REPO, "spark_rapids_tpu")],
                        root=_REPO)
    assert findings, "lint saw no files — wrong path?"
    active = [f.render() for f in findings if not f.suppressed]
    assert active == [], "\n".join(active)
    unreasoned = [f.render() for f in findings
                  if f.suppressed and not f.reason]
    assert unreasoned == [], "\n".join(unreasoned)


def test_cli_strict_exits_zero_on_live_tree():
    from tools.enginelint.__main__ import main
    assert main([os.path.join(_REPO, "spark_rapids_tpu"),
                 "--strict"]) == 0
