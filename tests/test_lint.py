"""graft-lint engine tests + the tier-1 gate.

Three layers:

* fixture tests — one positive + one negative snippet per rule, run
  through the real engine against a tmp tree;
* machinery tests — suppression pragmas, baseline round-trip/staleness,
  CLI exit codes and JSON schema;
* the gate — ``run_lint()`` over the shipped tree must be clean against
  the checked-in baseline, every baseline entry must carry a real reason
  (no TODOs), and no entry may be stale.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import (  # noqa: E402
    RULES, default_baseline_path, load_baseline, match_baseline, run_lint,
    update_baseline,
)
from tools.lint.engine import save_baseline  # noqa: E402

EXPECTED_RULES = {"trace-impurity", "silent-swallow", "hot-path-import",
                  "unguarded-global", "host-sync",
                  # graft-lint 2.0 whole-program rules
                  "cross-trace-impurity", "cross-host-sync",
                  "lock-order", "import-layering",
                  # PR 5 (resilience): retry loops belong to the policies
                  "naked-retry",
                  # PR 6 (backend fallback): placement belongs to
                  # device.py / core/fallback.py
                  "device-access",
                  # ISSUE 12 (tracing): spans only via the span() context
                  # manager; guarded construction on the dispatch fast path
                  "span-discipline",
                  # ISSUE 14 (graft-lint 3.0): whole-program race detector —
                  # thread-root discovery + lock domination over shared state
                  "shared-state-race",
                  # ISSUE 18 (graft-lint 4.0): CFG-backed exception/resource
                  # flow — typed failure surfaces at declared entry roots,
                  # and all-paths release of configured acquire/release pairs
                  "exception-contract", "resource-discipline",
                  # ISSUE 19 (graft-lint 5.0): interprocedural blocking —
                  # lock-hold stalls, unbounded waits at serving roots, and
                  # stall classes reachable from the dispatch fast path
                  "blocking-under-lock", "unbounded-wait", "hot-path-stall"}


def _lint_snippet(tmp_path, code, rule, filename="snippet.py", config=None):
    f = tmp_path / filename
    f.write_text(textwrap.dedent(code))
    return run_lint(paths=[str(f)], rules=[rule], config=config,
                    root=str(tmp_path)).new


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

def test_all_eighteen_rules_registered():
    assert len(EXPECTED_RULES) == 18
    assert EXPECTED_RULES <= set(RULES)


# ---------------------------------------------------------------------------
# silent-swallow
# ---------------------------------------------------------------------------

def test_silent_swallow_positive(tmp_path):
    found = _lint_snippet(tmp_path, """\
        try:
            x = 1
        except Exception:
            pass
        """, "silent-swallow")
    assert len(found) == 1 and found[0].line == 3


def test_silent_swallow_negative(tmp_path):
    found = _lint_snippet(tmp_path, """\
        try:
            x = 1
        except Exception:
            pass  # why: probe failure means feature absent, default is fine
        """, "silent-swallow")
    assert found == []


# ---------------------------------------------------------------------------
# hot-path-import
# ---------------------------------------------------------------------------

HOT_CFG = {"hot_path_modules": ["hot.py"]}


def test_hot_path_import_positive(tmp_path):
    found = _lint_snippet(tmp_path, """\
        def dispatch(x):
            import numpy as np
            return np.asarray(x)
        """, "hot-path-import", filename="hot.py", config=HOT_CFG)
    assert len(found) == 1 and found[0].line == 2
    assert "dispatch" in found[0].message


def test_hot_path_import_negative_module_scope_and_unlisted(tmp_path):
    clean = """\
        import numpy as np

        def dispatch(x):
            return np.asarray(x)
        """
    assert _lint_snippet(tmp_path, clean, "hot-path-import",
                         filename="hot.py", config=HOT_CFG) == []
    # same function-level import in a module NOT in the hot-path set: ok
    dirty = """\
        def helper(x):
            import numpy as np
            return np.asarray(x)
        """
    assert _lint_snippet(tmp_path, dirty, "hot-path-import",
                         filename="cold.py", config=HOT_CFG) == []


# ---------------------------------------------------------------------------
# trace-impurity
# ---------------------------------------------------------------------------

def test_trace_impurity_positive_clock_and_mutable_global(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import time
        import jax

        SCALES = {"a": 2.0}

        def fwd(x):
            return x * time.time() * SCALES["a"]

        fwd_c = jax.jit(fwd)
        """, "trace-impurity")
    kinds = {(f.line, f.message.split(" ")[0]) for f in found}
    assert (7, "'time.time(...)'") in kinds
    assert any("SCALES" in f.message for f in found)


def test_trace_impurity_reaches_helpers_and_apply_roots(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import os

        def apply(name, fn, *xs):
            return fn(*xs)

        def _helper(x):
            return x if os.environ.get("FAST") else x * 2

        def op(x):
            return apply("op", lambda a: _helper(a), x)
        """, "trace-impurity")
    assert len(found) == 1 and found[0].line == 7
    assert "os.environ" in found[0].message


def test_trace_impurity_negative_keyed_rng_and_untraced(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import time
        import jax

        def fwd(x, key):
            return x + jax.random.normal(key, x.shape)

        fwd_c = jax.jit(fwd)

        def untraced_host_helper():
            return time.time()
        """, "trace-impurity")
    assert found == []


# ---------------------------------------------------------------------------
# unguarded-global
# ---------------------------------------------------------------------------

def test_unguarded_global_positive_including_alias(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import threading

        _LOCK = threading.Lock()
        _REG = {}

        def put(k, v):
            _REG[k] = v

        def bump(k):
            d = _REG
            d.setdefault(k, 0)
        """, "unguarded-global")
    assert [f.line for f in found] == [7, 11]


def test_unguarded_global_negative_lock_and_locked_suffix(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import threading

        _LOCK = threading.Lock()
        _REG = {}

        def put(k, v):
            with _LOCK:
                _REG[k] = v

        def _insert_locked(k, v):
            _REG[k] = v

        _REG["module-scope"] = "import runs single-threaded"
        """, "unguarded-global")
    assert found == []


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_host_sync_positive(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import jax.numpy as jnp
        import numpy as np

        def norms(params):
            out = []
            for p in params:
                out.append(float(jnp.sum(p._data)))
            return out

        def items(xs):
            return [x.item() for x in xs]  # comprehension: not a loop stmt

        def drain(ts):
            while True:
                if bool(np.asarray(ts[0]._data).all()):
                    break
        """, "host-sync")
    assert [f.line for f in found] == [7, 15]


def test_host_sync_negative_metadata_and_outside_loop(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import numpy as np

        def shapes(params):
            return [int(np.prod(p._data.shape)) for p in params]

        def sizes(params):
            out = []
            for p in params:
                out.append(int(np.prod(p._data.shape)))
            return out

        def one_sync(t):
            return t.item()
        """, "host-sync")
    assert found == []


# ---------------------------------------------------------------------------
# naked-retry
# ---------------------------------------------------------------------------

def test_naked_retry_positive_alias_and_except_loop(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import time as _time

        def call_with_retry(fn):
            while True:
                try:
                    return fn()
                except ConnectionError:
                    _time.sleep(0.2)
        """, "naked-retry")
    assert len(found) == 1 and found[0].line == 8
    assert "call_with_retry" in found[0].message


def test_naked_retry_negative_plain_poll_and_allowed_path(tmp_path):
    # a sleep in a loop WITHOUT exception handling is a plain poll loop,
    # not a hand-rolled retry — out of scope for this rule
    clean = """\
        import time

        def wait_for(flag):
            while not flag():
                time.sleep(0.1)
        """
    assert _lint_snippet(tmp_path, clean, "naked-retry") == []
    # the same retry idiom inside the resilience package itself is the
    # implementation, not a violation
    dirty = """\
        import time

        def backoff(fn):
            while True:
                try:
                    return fn()
                except OSError:
                    time.sleep(0.2)
        """
    assert _lint_snippet(
        tmp_path, dirty, "naked-retry", filename="policy.py",
        config={"retry_allowed_paths": ["policy.py"]}) == []


# ---------------------------------------------------------------------------
# device-access
# ---------------------------------------------------------------------------

def test_device_access_positive_call_alias_and_from_import(tmp_path):
    found = _lint_snippet(tmp_path, """\
        import jax as j
        from jax import device_put

        def move(arr):
            dev = j.devices("cpu")[0]
            return device_put(arr, dev)
        """, "device-access")
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "jax.devices" in msgs and "from jax import device_put" in msgs
    # `import jax.numpy` (no asname) also binds the top-level `jax` name
    found = _lint_snippet(tmp_path, """\
        import jax.numpy

        def move(arr, dev):
            return jax.device_put(arr, dev)
        """, "device-access")
    assert len(found) == 1 and "jax.device_put" in found[0].message


def test_device_access_negative_allowed_paths_and_unrelated_attrs(tmp_path):
    # the sanctioned owners are exempt (config default covers the real
    # tree; fixture passes its own allowed list)
    dirty = """\
        import jax

        def put(arr, dev):
            return jax.device_put(arr, dev)
        """
    assert _lint_snippet(
        tmp_path, dirty, "device-access", filename="fallback.py",
        config={"device_access_allowed_paths": ["fallback.py"]}) == []
    # an unrelated attr named devices on a non-jax object is not a finding
    clean = """\
        import jax

        def shapes(mesh):
            return mesh.devices()  # Mesh.devices, not jax.devices

        def grids(x):
            return jax.numpy.asarray(x)
        """
    assert _lint_snippet(tmp_path, clean, "device-access") == []


def test_naked_retry_strict_poll_loop_paths(tmp_path):
    # poll_loop_paths modules (serving) get the strict tier: a plain
    # poll-loop sleep WITHOUT try/except is a finding there — watchdog/
    # drain threads must ride resilience.jitter_sleep
    poll = """\
        import time

        def wait_for(flag):
            while not flag():
                time.sleep(0.1)
        """
    found = _lint_snippet(
        tmp_path, poll, "naked-retry", filename="watchdog.py",
        config={"poll_loop_paths": ["watchdog.py"]})
    assert len(found) == 1 and "jitter_sleep" in found[0].message
    # the same file outside poll_loop_paths stays clean (non-strict tier)
    assert _lint_snippet(tmp_path, poll, "naked-retry") == []
    # jitter_sleep-based polling in a strict module is the sanctioned form
    clean = """\
        from paddle_tpu.resilience import jitter_sleep

        def wait_for(flag):
            while not flag():
                jitter_sleep(0.1)
        """
    assert _lint_snippet(
        tmp_path, clean, "naked-retry", filename="watchdog.py",
        config={"poll_loop_paths": ["watchdog.py"]}) == []


def test_naked_retry_strict_outranks_retry_allowed(tmp_path):
    # ISSUE 10: the watchdog moved INTO paddle_tpu/resilience (which is
    # retry_allowed). Its poll loops must still ride jitter_sleep — a
    # module in poll_loop_paths keeps the strict tier even when it is
    # also under retry_allowed_paths.
    poll = """\
        import time

        def loop(flag):
            while not flag():
                time.sleep(0.1)
        """
    found = _lint_snippet(
        tmp_path, poll, "naked-retry", filename="watchdog.py",
        config={"retry_allowed_paths": ["watchdog.py"],
                "poll_loop_paths": ["watchdog.py"]})
    assert len(found) == 1 and "jitter_sleep" in found[0].message
    # the shipped config actually covers the extracted modules
    from tools.lint.engine import DEFAULT_CONFIG
    assert "paddle_tpu/resilience/watchdog.py" in \
        DEFAULT_CONFIG["poll_loop_paths"]
    assert "paddle_tpu/resilience/trainer.py" in \
        DEFAULT_CONFIG["poll_loop_paths"]


def test_naked_retry_nested_def_does_not_inherit_loop(tmp_path):
    # a function DEFINED inside a loop starts its own context: its sleep
    # is not "in" the enclosing loop
    found = _lint_snippet(tmp_path, """\
        import time

        def outer(items):
            for it in items:
                try:
                    it.go()
                except ValueError:
                    pass  # why: optional feature probe
                def helper():
                    time.sleep(0.1)
                helper()
        """, "naked-retry")
    assert found == []


# ---------------------------------------------------------------------------
# span-discipline (ISSUE 12)
# ---------------------------------------------------------------------------

def test_span_discipline_flags_manual_pairing(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace

        def f():
            s = trace.begin_span("x")
            trace.end_span(s)
        """, "span-discipline")
    assert len(found) == 2
    assert "manual span pairing" in found[0].message


def test_span_discipline_flags_span_outside_with(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace as _trace

        def f():
            s = _trace.span("x")
            s.__enter__()
        """, "span-discipline")
    assert len(found) == 1 and "outside a `with`" in found[0].message


def test_span_discipline_holds_phase_spans_to_the_same_rules(tmp_path):
    """ISSUE 25: ``phase(...)`` is a span constructor too — only as a
    ``with`` item, and guarded in the hot modules like ``phase_instant``."""
    found = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace as _trace

        def f():
            p = _trace.phase("serving.decode.build")
            with _trace.phase("serving.decode.wait"):
                _trace.phase_instant("serving.http.token", rid=1)
        """, "span-discipline")
    assert len(found) == 1 and "`phase(...)` used outside" in found[0].message
    hot = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace as _trace

        def dispatch():
            with _trace.phase("jit.call"):
                _trace.phase_instant("tick")
        """, "span-discipline", filename="hot.py",
        config={"span_hot_modules": ["hot.py"]})
    assert len(hot) == 2 and all("enabled() guard" in f.message for f in hot)


def test_span_discipline_with_statement_is_clean(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace as _trace

        def f(ctx):
            with _trace.span("serving.prefill", parent=ctx, rid=1):
                _trace.instant("tick")
        """, "span-discipline")
    assert found == []


def test_span_discipline_hot_module_needs_enabled_guard(tmp_path):
    hot = """\
        from paddle_tpu.observability import trace as _trace

        def dispatch():
            with _trace.span("op"):
                pass
        """
    cfg = {"span_hot_modules": ["hot.py"]}
    found = _lint_snippet(tmp_path, hot, "span-discipline",
                          filename="hot.py", config=cfg)
    assert len(found) == 1 and "enabled() guard" in found[0].message
    # the same file NOT in span_hot_modules is fine
    assert _lint_snippet(tmp_path, hot, "span-discipline",
                         filename="warm.py", config=cfg) == []


def test_span_discipline_guarded_hot_module_is_clean(tmp_path):
    found = _lint_snippet(tmp_path, """\
        from paddle_tpu.observability import trace as _trace

        def dispatch():
            if _trace.enabled():
                with _trace.span("op"):
                    pass
            else:
                pass
        """, "span-discipline", filename="hot.py",
        config={"span_hot_modules": ["hot.py"]})
    assert found == []


def test_span_discipline_shipped_tree_is_clean():
    # the acceptance pin: 0 findings over paddle_tpu/ with no baseline
    # allowance — the step_capture fast-path span stays guarded
    result = run_lint(rules=["span-discipline"])
    assert [f.text() for f in result.new] == []


# ---------------------------------------------------------------------------
# suppression pragmas
# ---------------------------------------------------------------------------

def test_pragma_same_line_suppresses(tmp_path):
    found = _lint_snippet(tmp_path, """\
        def items(xs):
            out = []
            for x in xs:
                out.append(x.item())  # graft-lint: disable=host-sync
            return out
        """, "host-sync")
    assert found == []


def test_pragma_comment_line_above_suppresses(tmp_path):
    found = _lint_snippet(tmp_path, """\
        def items(xs):
            out = []
            for x in xs:
                # graft-lint: disable=host-sync
                out.append(x.item())
            return out
        """, "host-sync")
    assert found == []


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    found = _lint_snippet(tmp_path, """\
        def items(xs):
            out = []
            for x in xs:
                out.append(x.item())  # graft-lint: disable=silent-swallow
            return out
        """, "host-sync")
    assert len(found) == 1


def test_pragma_disable_file(tmp_path):
    found = _lint_snippet(tmp_path, """\
        # graft-lint: disable-file=host-sync
        def items(xs):
            out = []
            for x in xs:
                out.append(x.item())
            return out
        """, "host-sync")
    assert found == []


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

BAD = """\
try:
    x = 1
except Exception:
    pass
"""


def test_baseline_round_trip(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(BAD)
    first = run_lint(paths=[str(f)], rules=["silent-swallow"],
                     root=str(tmp_path))
    assert len(first.new) == 1
    entries = update_baseline(first.new, [])
    assert entries[0]["count"] == 1
    assert entries[0]["reason"].startswith("TODO")
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), entries)
    again = run_lint(paths=[str(f)], rules=["silent-swallow"],
                     baseline_entries=load_baseline(str(bl)),
                     root=str(tmp_path))
    assert again.clean and len(again.baselined) == 1 and again.stale == []


def test_baseline_reports_stale_after_fix(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(BAD)
    first = run_lint(paths=[str(f)], rules=["silent-swallow"],
                     root=str(tmp_path))
    entries = update_baseline(first.new, [])
    f.write_text(BAD.replace("pass", "pass  # why: benign"))
    fixed = run_lint(paths=[str(f)], rules=["silent-swallow"],
                     baseline_entries=entries, root=str(tmp_path))
    assert fixed.clean and len(fixed.stale) == 1
    # --update-baseline semantics prune it while keeping live reasons
    assert update_baseline(fixed.new, entries) == []


def test_update_baseline_preserves_reasons(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(BAD)
    first = run_lint(paths=[str(f)], rules=["silent-swallow"],
                     root=str(tmp_path))
    entries = update_baseline(first.new, [])
    entries[0]["reason"] = "teardown path, nothing to signal to"
    again = update_baseline(first.new, entries)
    assert again[0]["reason"] == "teardown path, nothing to signal to"


def test_baseline_count_absorbs_exactly(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(BAD + "\n" + BAD)
    findings = run_lint(paths=[str(f)], rules=["silent-swallow"],
                        root=str(tmp_path)).new
    assert len(findings) == 2
    one = update_baseline(findings[:1], [])
    new, baselined, stale = match_baseline(findings, one)
    assert len(new) == 1 and len(baselined) == 1 and stale == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.lint", *args],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_cli_list_rules():
    p = _cli("--list-rules")
    assert p.returncode == 0
    for r in EXPECTED_RULES:
        assert r in p.stdout


def test_cli_unknown_rule_is_usage_error():
    p = _cli("--rules=no-such-rule")
    assert p.returncode == 2


@pytest.mark.slow
def test_cli_json_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD)
    p = _cli(str(bad), "--format=json", "--no-baseline")
    assert p.returncode == 1
    report = json.loads(p.stdout)
    assert report["clean"] is False
    assert report["counts_by_rule"] == {"silent-swallow": 1}
    assert report["findings"][0]["rule"] == "silent-swallow"

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    p = _cli(str(good), "--format=json", "--no-baseline")
    assert p.returncode == 0 and json.loads(p.stdout)["clean"] is True


def test_cli_nonexistent_path_is_usage_error(tmp_path, capsys):
    # a renamed/typo'd path must not silently report "ok: 0 files"
    from tools.lint.cli import main
    assert main([str(tmp_path / "no_such_dir")]) == 2
    assert "no python files" in capsys.readouterr().err


def test_cli_scoped_update_baseline_preserves_out_of_scope(tmp_path, capsys):
    # --update-baseline narrowed to one file/rule must NOT delete the
    # other files' entries (and their human-written reasons)
    from tools.lint.cli import main
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text(BAD)
    b.write_text(BAD)
    bl = tmp_path / "baseline.json"
    assert main([str(a), str(b), f"--baseline={bl}",
                 "--update-baseline"]) == 0
    entries = load_baseline(str(bl))
    assert len(entries) == 2
    for e in entries:
        e["reason"] = "reviewed: teardown path"
    save_baseline(str(bl), entries)
    # scoped regeneration over a.py only: b.py's entry + reason survive
    assert main([str(a), f"--baseline={bl}", "--update-baseline"]) == 0
    after = {e["path"]: e for e in load_baseline(str(bl))}
    assert len(after) == 2
    b_rel = os.path.relpath(str(b), REPO).replace(os.sep, "/")
    assert after[b_rel]["reason"] == "reviewed: teardown path"
    # scoping by rule keeps entries of other rules too
    assert main([str(a), str(b), f"--baseline={bl}",
                 "--rules=host-sync", "--update-baseline"]) == 0
    assert len(load_baseline(str(bl))) == 2
    capsys.readouterr()


@pytest.mark.slow
def test_cli_update_baseline_flow(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD)
    bl = tmp_path / "baseline.json"
    p = _cli(str(bad), f"--baseline={bl}", "--update-baseline")
    assert p.returncode == 0 and bl.exists()
    assert "TODO" in p.stdout  # new grandfathering demands a reviewed reason
    # a TODO-stamped reason is a drafting state: shipping it fails the run
    p = _cli(str(bad), f"--baseline={bl}")
    assert p.returncode == 1 and "TODO" in p.stderr
    p = _cli(str(bad), f"--baseline={bl}", "--allow-todo")
    assert p.returncode == 0  # baselined + drafting escape hatch -> clean


def test_cli_prune_baseline_removes_only_dead_entries(tmp_path, capsys):
    # ISSUE 18: --prune-baseline deletes entries that no longer fire and
    # lowers over-counted ones, leaving live entries (and their reasons).
    # Doctor a copy of the SHIPPED baseline — it is exactly-firing (the
    # tier-1 gate asserts zero stale entries), so the one inflated count
    # and the one fabricated entry are the only prunable budget.
    from tools.lint.cli import main
    real = load_baseline(default_baseline_path())
    assert real
    doctored = [dict(e) for e in real]
    doctored[0]["count"] = int(doctored[0].get("count", 1)) + 2
    doctored.append({"path": "paddle_tpu/no_such_file.py",
                     "rule": "host-sync", "message": "never fires",
                     "count": 1, "reason": "reviewed: dead"})
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), doctored)
    assert main([f"--baseline={bl}", "--prune-baseline"]) == 0
    out = capsys.readouterr().out
    assert "pruned:" in out and "lowered:" in out
    after = load_baseline(str(bl))
    key = lambda e: (e["path"], e["rule"], e["message"])  # noqa: E731
    assert {key(e) for e in after} == {key(e) for e in real}
    by_key = {key(e): e for e in after}
    k0 = key(real[0])
    assert by_key[k0]["count"] == int(real[0].get("count", 1))
    assert by_key[k0].get("reason") == real[0].get("reason")


def test_cli_prune_baseline_requires_full_run(tmp_path, capsys):
    # a narrowed run cannot tell "fixed" from "not scanned": usage error
    from tools.lint.cli import main
    assert main(["--prune-baseline", str(tmp_path)]) == 2
    assert main(["--prune-baseline", "--changed-only"]) == 2
    assert main(["--prune-baseline", "--rules=host-sync"]) == 2
    assert main(["--prune-baseline", "--no-baseline"]) == 2
    assert main(["--prune-baseline", "--update-baseline"]) == 2
    assert "full default run" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the tier-1 gate: shipped tree is clean, baseline fully justified
# ---------------------------------------------------------------------------

def test_shipped_tree_is_clean_against_baseline():
    # all ten rules — the four whole-program rules (call graph, lock
    # order, layer DAG) run against the full tree right here in tier 1
    result = run_lint(baseline_entries=load_baseline(default_baseline_path()))
    assert result.errors == []
    assert [f.text() for f in result.new] == [], (
        "new graft-lint findings — fix them or (with a written reason) "
        "run `python -m tools.lint --update-baseline`")
    assert result.stale == [], (
        "stale baseline entries — the code improved, run "
        "`python -m tools.lint --update-baseline` to prune them")


def test_baseline_is_fully_justified():
    entries = load_baseline(default_baseline_path())
    assert entries, "expected grandfathered findings from the initial rollout"
    for e in entries:
        reason = str(e.get("reason", ""))
        assert reason and not reason.startswith("TODO"), (
            f"baseline entry without a real justification: {e}")


def test_every_rule_is_exercised_by_tree_or_baseline():
    # each rule must have teeth on THIS tree: either a baselined real
    # finding or (for rules whose findings were all fixed) a fixture;
    # assert the baseline covers the rules we grandfathered — including
    # the whole-program rules' deliberate findings (the fused/np-scalar
    # fast-path syncs, the two load-bearing package import cycles)
    rules_in_baseline = {e["rule"]
                        for e in load_baseline(default_baseline_path())}
    assert {"hot-path-import", "host-sync", "unguarded-global",
            "cross-host-sync", "import-layering", "naked-retry",
            # ISSUE 14: the race detector's reasoned survivors (lock-free
            # flight ring, GIL-atomic endpoint refresh, the engine's
            # single-consumer step state)
            "shared-state-race",
            # ISSUE 19: the blocking analysis' reasoned survivors (the
            # native-build lock, the by-design serialized push RPCs, the
            # cache-miss jit under the dispatch root, the resolved-by-
            # protocol future waits in http/router)
            "blocking-under-lock", "unbounded-wait",
            "hot-path-stall"} <= rules_in_baseline


# ---------------------------------------------------------------------------
# dogfood (ISSUE 19): the linter lints itself
# ---------------------------------------------------------------------------

def test_linter_tree_lints_itself_clean():
    # tools/lint under its own rules, no baseline allowance: no silent
    # except-pass, no unlocked module-global mutation, and no function-
    # level imports in the scan hot loop (the one reviewed cycle-break in
    # build_summary carries a pragma). Scoped to the three rules that are
    # meaningful for a stdlib-only single-threaded tool — thread/device
    # rules have nothing to bite on here.
    res = run_lint(paths=["tools/lint"],
                   rules=["silent-swallow", "unguarded-global",
                          "hot-path-import"],
                   config={"hot_path_modules": [
                       "tools/lint/wholeprogram/summary.py",
                       "tools/lint/wholeprogram/project.py",
                       "tools/lint/astutil.py"]},
                   baseline_entries=[])
    assert res.errors == []
    assert [f.text() for f in res.new] == []
    # a renamed tree must fail loudly, not lint zero files to green
    assert res.files_checked >= 20
