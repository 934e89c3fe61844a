"""The benchmark of record must defend its own capture.

Pins the pure logic bench.py uses: the schema-stable counter blocks of the
row of record and the rules that decide when a run publishes
``"suspect": true``.
"""

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def test_telemetry_detail_is_schema_stable():
    # every bench JSON row must carry the full telemetry field set, zeros
    # included, so bench rows stay diffable across rounds
    detail = bench._telemetry_detail({})
    assert set(detail) == set(bench.TELEMETRY_FIELDS)
    assert all(v == 0 for v in detail.values())
    assert "dispatch.ops_total" in detail and "jit.compiles_total" in detail


def test_telemetry_detail_selects_counters():
    snap = {"dispatch.ops_total": 123.0, "jit.compiles_total": 2.0,
            "dispatch.latency_seconds": {"count": 123},  # ignored: not selected
            "jit.cache_hits_total": 7.0}
    detail = bench._telemetry_detail(snap)
    assert detail["dispatch.ops_total"] == 123
    assert detail["jit.compiles_total"] == 2
    assert detail["jit.cache_hits_total"] == 7
    assert detail["jit.graph_breaks_total"] == 0


def test_bench_main_emits_telemetry():
    # main() must wire _telemetry_detail into the JSON "detail" block (the
    # full main() needs a device-sized run; pin the wiring statically)
    import inspect
    src = inspect.getsource(bench.main)
    assert "_telemetry_detail" in src and '"telemetry"' in src
    assert "obs.enable()" in src


# ---------------------------------------------------------------------------
# training-under-fire counter block (ISSUE 10)
# ---------------------------------------------------------------------------

def test_train_resilience_detail_is_schema_stable():
    # the row of record pins the train.* recovery counters; all-zero on a
    # healthy run IS the claim — a nonzero diff means the measured run
    # itself retried/skipped/rolled back
    detail = bench._train_resilience_detail({})
    assert set(detail) == set(bench.TRAIN_RESILIENCE_FIELDS)
    assert set(bench.TRAIN_RESILIENCE_FIELDS) == {
        "retries", "restarts", "skipped_batches", "watchdog_trips"}
    assert all(v == 0 for v in detail.values())


def test_train_resilience_detail_sums_labeled_families():
    # train.retries_total carries a site label and the watchdog a kind
    # label — the bench block reports family totals
    snap = {"train.retries_total": {"site=train.step": 2.0,
                                    "site=train.data": 1.0},
            "train.restarts_total": 1.0,
            "train.watchdog_trips_total": {"kind=hung": 1.0}}
    detail = bench._train_resilience_detail(snap)
    assert detail["retries"] == 3
    assert detail["restarts"] == 1
    assert detail["watchdog_trips"] == 1
    assert detail["skipped_batches"] == 0


def test_bench_main_emits_train_resilience():
    import inspect
    src = inspect.getsource(bench.main)
    assert "_train_resilience_detail" in src and '"train_resilience"' in src
    assert "TRAIN_RESILIENCE_FIELDS" in src


# ---------------------------------------------------------------------------
# whole-step capture block (ISSUE 11)
# ---------------------------------------------------------------------------

def test_step_capture_detail_is_schema_stable():
    # the row of record pins the train.capture_* counters; hits > 0 with
    # zero bypasses on a healthy run IS the claim — all-bypass means the
    # measured run was the eager debug tier, not the compiled step
    detail = bench._step_capture_detail({}, "auto")
    assert set(detail) == set(bench.STEP_CAPTURE_FIELDS)
    assert set(bench.STEP_CAPTURE_FIELDS) == {
        "mode", "hits", "retraces", "bypasses", "donated_bytes"}
    assert detail["mode"] == "auto"
    assert detail["hits"] == 0 and detail["donated_bytes"] == 0


def test_step_capture_detail_sums_labeled_bypasses():
    snap = {"train.capture_hits_total": 20.0,
            "train.capture_retraces_total": 1.0,
            "train.capture_bypasses_total": {"reason=capture_seam": 2.0,
                                             "reason=untraceable": 1.0},
            "train.capture_donated_bytes": 7383052.0}
    detail = bench._step_capture_detail(snap, "auto")
    assert detail["hits"] == 20
    assert detail["retraces"] == 1
    assert detail["bypasses"] == 3
    assert detail["donated_bytes"] == 7383052


def test_all_bypass_run_is_suspect():
    cap = {"mode": "auto", "hits": 0, "retraces": 0, "bypasses": 6,
           "donated_bytes": 0}
    reasons = bench._capture_suspect_reasons(cap)
    assert reasons and "bypassed" in reasons[0]


def test_capture_off_run_is_suspect_and_healthy_is_clean():
    # mode=off means the number of record measured the eager debug tier —
    # e.g. the test suite's PADDLE_TPU_STEP_CAPTURE=off leaking into the
    # bench environment — which must read as suspect, not silently stand
    reasons = bench._capture_suspect_reasons(
        {"mode": "off", "hits": 0, "retraces": 0, "bypasses": 0,
         "donated_bytes": 0})
    assert reasons and "eager debug tier" in reasons[0]
    assert bench._capture_suspect_reasons(
        {"mode": "auto", "hits": 5, "retraces": 1, "bypasses": 0,
         "donated_bytes": 123}) == []


def test_bench_main_emits_step_capture_and_warm_compile():
    # main() must route the train step over capture_step, report the
    # step-capture counter block, and pin cold vs warm compile seconds
    # (the persistent-compilation-cache win of record)
    import inspect
    src = inspect.getsource(bench.main)
    assert "capture_step" in src
    assert "_step_capture_detail" in src and '"step_capture"' in src
    assert "_capture_suspect_reasons" in src
    assert '"compile_warm_s"' in src and '"compile_s"' in src
    assert '"step_ms_p50"' in src  # the structural perf pin stays
    # a CPU run never prints under the chip's metric name, and the peak
    # comes from the one table (tests/test_bring_up.py pins the resolver)
    assert "llama_train_cpu_smoke_tokens_per_sec" in src
    assert "device_peaks" in src and "197e12" not in src


def test_cross_host_sync_roots_cover_captured_step():
    # the captured-step entry joins the dispatch fast-path reachability
    # roots: a .item()/.numpy() anywhere a captured call can reach is a
    # per-STEP stall now, flagged by the same whole-program rule
    import sys
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.engine import DEFAULT_CONFIG
    assert "paddle_tpu/core/step_capture.py::__call__" in \
        DEFAULT_CONFIG["fast_path_roots"]


def test_eager_dispatch_bench_pins_captured_leg():
    mod = _load_bench_eager_dispatch()
    assert {"captured_step_ms", "captured_dispatches_per_step",
            "captured_speedup_x"} <= set(mod.RESULT_FIELDS)
    import inspect
    src = inspect.getsource(mod.main)
    assert "--captured-step" in src and "_captured_leg" in src


# ---------------------------------------------------------------------------
# tracing-overhead block (ISSUE 12)
# ---------------------------------------------------------------------------

def test_trace_overhead_detail_is_schema_stable():
    # the row of record pins the off/flight/on captured-step p50s: the
    # always-on flight recorder must be near-free on the hot path
    block = bench._trace_overhead_detail(10.0, 10.1, 10.5)
    assert set(block) == set(bench.TRACE_OVERHEAD_FIELDS)
    assert set(bench.TRACE_OVERHEAD_FIELDS) == {
        "step_ms_p50_off", "step_ms_p50_flight", "step_ms_p50_on",
        "flight_overhead_pct", "on_overhead_pct"}
    assert block["flight_overhead_pct"] == 1.0
    assert block["on_overhead_pct"] == 5.0


def test_trace_overhead_zero_off_p50_is_safe():
    block = bench._trace_overhead_detail(0.0, 0.0, 0.0)
    assert block["flight_overhead_pct"] == 0.0


def test_flight_overhead_over_two_percent_is_suspect():
    # >2% flight-vs-off p50 delta disqualifies the run: every number of
    # record ships with the recorder on, so its cost must stay invisible
    bad = bench._trace_overhead_detail(10.0, 10.3, 10.3)
    reasons = bench._trace_suspect_reasons(bad)
    assert reasons and "flight-recorder" in reasons[0]
    good = bench._trace_overhead_detail(10.0, 10.1, 12.0)
    assert bench._trace_suspect_reasons(good) == []   # "on" is debug tier


def test_bench_main_emits_trace_overhead():
    import inspect
    src = inspect.getsource(bench.main)
    assert "_trace_overhead_detail" in src and '"trace_overhead"' in src
    assert "_trace_suspect_reasons" in src
    assert "set_mode" in src      # measured under real mode switches
    for m in ('"off"', '"flight"', '"on"'):
        assert m in src, m


# ---------------------------------------------------------------------------
# eager-dispatch bench schema + dispatch fast-path hygiene (ISSUE 2)
# ---------------------------------------------------------------------------

def _load_bench_eager_dispatch():
    spec = importlib.util.spec_from_file_location(
        "bench_eager_dispatch",
        os.path.join(REPO, "benchmarks", "bench_eager_dispatch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_eager_dispatch_bench_pins_cache_fields():
    # the JSON row of record must carry the cache-vs-cold comparison; these
    # names are what bench-row diffs key on
    mod = _load_bench_eager_dispatch()
    assert {"cached_ms", "cold_ms", "hit_rate", "speedup_x"} <= \
        set(mod.RESULT_FIELDS)
    import inspect
    src = inspect.getsource(mod.main)
    # main() must build the row from exactly the pinned schema
    assert "RESULT_FIELDS" in src
    for field in mod.RESULT_FIELDS:
        assert f'"{field}"' in src, field


def test_dispatch_fast_path_has_no_per_call_imports():
    # bridge: the per-call-import ban is graft-lint's ``hot-path-import``
    # rule now (tools/lint/rules/hot_path_import.py), configured over the
    # whole core/{tensor,dispatch_cache,autograd}.py set instead of three
    # hardcoded functions. core/tensor.py must stay at ZERO findings with
    # no baseline allowance — the dispatch fast path pays that import per
    # op, not per backward walk.
    import ast
    import sys
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import run_lint
    result = run_lint(paths=["paddle_tpu/core/tensor.py",
                             "paddle_tpu/core/dispatch_cache.py"],
                      rules=["hot-path-import"])
    assert [f.text() for f in result.new] == []
    # structural pin: the fast-path functions this protects still exist
    with open(os.path.join(REPO, "paddle_tpu", "core", "tensor.py")) as f:
        tree = ast.parse(f.read())
    names = {n.name for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)}
    assert {"apply", "_apply_impl", "_apply_cached"} <= names


# ---------------------------------------------------------------------------
# graft-lint machine formats: --format=json (PR 3) + --format=sarif
# (ISSUE 14) — CI consumers key on these schemas
# ---------------------------------------------------------------------------

def _lint_cli_doc(tmp_path, fmt):
    import io
    import contextlib
    import json
    import textwrap
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.cli import main
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "w.py").write_text(textwrap.dedent("""\
        import threading

        class Worker:
            def start(self):
                threading.Thread(target=self._a, daemon=True).start()
                threading.Thread(target=self._b, daemon=True).start()

            def _a(self):
                self.n = 1

            def _b(self):
                self.n = 2
        """))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(pkg), f"--format={fmt}", "--no-baseline",
                   "--no-cache"])
    return rc, json.loads(buf.getvalue())


def test_lint_json_format_schema_pin(tmp_path):
    rc, doc = _lint_cli_doc(tmp_path, "json")
    assert rc == 1 and doc["clean"] is False
    assert {"files_checked", "findings", "counts_by_rule", "cache",
            "run_seconds", "errors"} <= set(doc)
    assert doc["counts_by_rule"] == {"shared-state-race": 1}
    # ISSUE 18: witness chains ride along in the JSON rows too
    assert set(doc["findings"][0]) == {"path", "line", "rule", "message",
                                       "related"}


def test_lint_sarif_format_schema_pin(tmp_path):
    # GitHub code scanning loads exactly this shape: version 2.1.0, one
    # run, driver rule metadata for EVERY registered rule, results with
    # ruleId/message/locations, witness paths as relatedLocations
    from tools.lint import RULES
    from tools.lint.cli import SARIF_VERSION
    rc, doc = _lint_cli_doc(tmp_path, "sarif")
    assert rc == 1
    assert doc["version"] == SARIF_VERSION == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graft-lint"
    assert [r["id"] for r in driver["rules"]] == sorted(RULES)
    assert all({"id", "shortDescription", "defaultConfiguration"}
               <= set(r) for r in driver["rules"])
    (res,) = run["results"]
    assert res["ruleId"] == "shared-state-race"
    assert res["ruleIndex"] == sorted(RULES).index("shared-state-race")
    assert res["level"] == "warning" and res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("pkg/w.py")
    assert loc["region"]["startLine"] > 0
    # the race finding's witness chain (root -> ... -> access), per side
    rel = res["relatedLocations"]
    assert len(rel) >= 2
    for r in rel:
        assert r["message"]["text"].startswith("witness:")
        assert r["physicalLocation"]["region"]["startLine"] > 0


def test_lint_sarif_clean_run_has_empty_results(tmp_path):
    import io
    import contextlib
    import json
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.cli import main
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(f), "--format=sarif", "--no-baseline", "--no-cache"])
    doc = json.loads(buf.getvalue())
    assert rc == 0 and doc["runs"][0]["results"] == []


# ---------------------------------------------------------------------------
# graft-lint 4.0 (ISSUE 18): the CFG rules in the machine formats —
# exception-contract and resource-discipline ship witness paths, and the
# DEFAULT_CONFIG breaker-probe pair is live even outside the repo tree
# ---------------------------------------------------------------------------

def _probe_leak_pkg(tmp_path):
    import textwrap
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    # DEFAULT_CONFIG's handleless breaker-probe pair: before_call() takes
    # the half-open probe, nothing ever returns it
    (pkg / "c.py").write_text(textwrap.dedent("""\
        class Client:
            def call(self, breaker, srv):
                breaker.before_call()
                return srv.send()
        """))
    return pkg


def test_lint_json_resource_discipline_carries_witnesses(tmp_path):
    import io
    import contextlib
    import json
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.cli import main
    pkg = _probe_leak_pkg(tmp_path)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(pkg), "--format=json", "--no-baseline", "--no-cache"])
    doc = json.loads(buf.getvalue())
    assert rc == 1
    assert doc["counts_by_rule"] == {"resource-discipline": 1}
    (f,) = doc["findings"]
    assert set(f) == {"path", "line", "rule", "message", "related"}
    assert "'breaker-probe'" in f["message"]
    msgs = [r["message"] for r in f["related"]]
    assert any("acquired here" in m for m in msgs)
    assert all(m.startswith("witness:") for m in msgs)
    assert all(r["line"] > 0 for r in f["related"])


def test_lint_sarif_resource_discipline_related_locations(tmp_path):
    import io
    import contextlib
    import json
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import RULES
    from tools.lint.cli import main
    pkg = _probe_leak_pkg(tmp_path)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(pkg), "--format=sarif", "--no-baseline", "--no-cache"])
    doc = json.loads(buf.getvalue())
    assert rc == 1
    (run,) = doc["runs"]
    # both CFG rules ship driver metadata (the sorted-RULES pin above
    # covers this implicitly; keep the names explicit here)
    ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert "exception-contract" in ids and "resource-discipline" in ids
    (res,) = run["results"]
    assert res["ruleId"] == "resource-discipline"
    assert res["ruleIndex"] == sorted(RULES).index("resource-discipline")
    rel = res["relatedLocations"]
    assert rel and all(
        r["message"]["text"].startswith("witness:") and
        r["physicalLocation"]["region"]["startLine"] > 0 for r in rel)


def test_lint_sarif_exception_contract_witness_chain(tmp_path):
    # exception-contract is path-scoped in DEFAULT_CONFIG, so drive
    # sarif_report() off a run with an explicit contract table
    import textwrap
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import run_lint
    from tools.lint.cli import sarif_report
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "entry.py").write_text(textwrap.dedent("""\
        def work():
            raise KeyError("missing")

        class Door:
            def do_call(self, req):
                return work()
        """))
    res = run_lint(paths=["."], rules=["exception-contract"],
                   root=str(tmp_path),
                   config={"exception_contracts": {
                       "pkg/entry.py": {"Door.do_call": ["ValueError"]}}})
    (f,) = res.new
    assert f.rule == "exception-contract" and "KeyError" in f.message
    doc = sarif_report(res)
    (sres,) = doc["runs"][0]["results"]
    assert sres["ruleId"] == "exception-contract"
    rel = sres["relatedLocations"]
    # the witness chain walks root -> raising function, each hop named
    assert [r["message"]["text"] for r in rel] == \
        ["witness: 'Door.do_call'", "witness: 'work'"]
    assert rel[-1]["physicalLocation"]["region"]["startLine"] == 2


# ---------------------------------------------------------------------------
# graft-lint 5.0 (ISSUE 19): the blocking rules in the machine formats —
# witness chains name the root, the acquire site, and the blocking call,
# and the latency-invariant config tables are pinned against silent edits
# ---------------------------------------------------------------------------

def test_lint_json_blocking_under_lock_carries_witnesses(tmp_path):
    import io
    import contextlib
    import json
    import textwrap
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.cli import main
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "w.py").write_text(textwrap.dedent("""\
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.jobs = None

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    return self.jobs.get()
        """))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main([str(pkg), "--format=json", "--no-baseline", "--no-cache"])
    doc = json.loads(buf.getvalue())
    assert rc == 1
    assert doc["counts_by_rule"] == {"blocking-under-lock": 1}
    (f,) = doc["findings"]
    assert set(f) == {"path", "line", "rule", "message", "related"}
    assert "while holding" in f["message"]
    msgs = [r["message"] for r in f["related"]]
    # root -> ... witness hops, then the acquire site, then the block
    assert msgs[0].startswith("witness:")
    assert any(m.startswith("acquires") for m in msgs)
    assert msgs[-1].startswith("blocks: queue")
    assert all(r["line"] > 0 for r in f["related"])


def test_lint_sarif_unbounded_wait_related_locations(tmp_path):
    # unbounded-wait is config-scoped, so drive sarif_report() off a
    # run with explicit bounded_wait tables
    import textwrap
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import run_lint
    from tools.lint.cli import sarif_report
    pkg = tmp_path / "pkg" / "srv"
    pkg.mkdir(parents=True)
    (tmp_path / "pkg" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "loop.py").write_text(textwrap.dedent("""\
        class Pump:
            def __init__(self, q):
                self.jobs = q

            def _poll_loop(self):
                return self._pull()

            def _pull(self):
                return self.jobs.get()
        """))
    res = run_lint(paths=["."], rules=["unbounded-wait"],
                   root=str(tmp_path),
                   config={"bounded_wait_paths": ["pkg/srv"],
                           "bounded_wait_roots": {
                               "pkg/srv/loop.py": ["Pump._poll_loop"]}})
    (f,) = res.new
    assert f.rule == "unbounded-wait" and "poll thread" in f.message
    doc = sarif_report(res)
    (sres,) = doc["runs"][0]["results"]
    assert sres["ruleId"] == "unbounded-wait"
    rel = sres["relatedLocations"]
    # the chain walks root -> waiting function, then names the wait
    assert [r["message"]["text"] for r in rel] == \
        ["witness: 'Pump._poll_loop'", "witness: 'Pump._pull'",
         "waits: queue 'self.jobs.get'"]
    assert rel[-1]["physicalLocation"]["region"]["startLine"] == 9


def test_lint_sarif_hot_path_stall_related_locations(tmp_path):
    import textwrap
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import run_lint
    from tools.lint.cli import sarif_report
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "hot.py").write_text(textwrap.dedent("""\
        import time

        def dispatch(x):
            return _helper(x)

        def _helper(x):
            time.sleep(0.01)
            return x
        """))
    res = run_lint(paths=["."], rules=["hot-path-stall"],
                   root=str(tmp_path),
                   config={"fast_path_roots": ["pkg/hot.py::dispatch"]})
    (f,) = res.new
    assert f.rule == "hot-path-stall"
    doc = sarif_report(res)
    (sres,) = doc["runs"][0]["results"]
    assert sres["ruleId"] == "hot-path-stall"
    rel = sres["relatedLocations"]
    assert [r["message"]["text"] for r in rel] == \
        ["witness: 'dispatch'", "witness: '_helper'",
         "stalls: sleep 'time.sleep'"]
    assert rel[-1]["physicalLocation"]["region"]["startLine"] == 7


def test_default_config_pins_latency_invariant_tables():
    # MIGRATING "Latency invariants": the strict bounded-wait tier and
    # the reviewed fast-path lock exemptions are part of the contract of
    # record — membership drift must be a conscious, reviewed edit here
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.engine import DEFAULT_CONFIG
    assert {"paddle_tpu/serving", "paddle_tpu/serving/http.py",
            "paddle_tpu/serving/router.py",
            "paddle_tpu/resilience/watchdog.py",
            "paddle_tpu/resilience/trainer.py",
            "paddle_tpu/distributed/ps_service.py"} <= \
        set(DEFAULT_CONFIG["bounded_wait_paths"])
    # the bounded-wait poll roots name real long-lived threads
    roots = DEFAULT_CONFIG["bounded_wait_roots"]
    assert roots["paddle_tpu/serving/router.py"] == ["Router._poll_loop"]
    assert roots["paddle_tpu/resilience/watchdog.py"] == \
        ["StepWatchdog._loop"]
    # every fast-path lock exemption is a reviewed short-critical-section
    # lock, spelled as the analysis' dotted lock id
    exempt = DEFAULT_CONFIG["hot_path_lock_exempt"]
    assert {"paddle_tpu.core.dispatch_cache._LOCK",
            "paddle_tpu.core.fallback._LOCK"} <= set(exempt)
    assert all(e.split(".")[-1].startswith("_") for e in exempt)
    # and the strict wait tier rides the SAME modules the poll-loop tier
    # already guards — the two latency tiers cannot silently diverge
    poll = set(DEFAULT_CONFIG["poll_loop_paths"])
    assert {"paddle_tpu/serving", "paddle_tpu/resilience/watchdog.py",
            "paddle_tpu/resilience/trainer.py"} <= poll


# ---------------------------------------------------------------------------
# serving bench schema (ISSUE 7)
# ---------------------------------------------------------------------------

def _load_bench_generation():
    spec = importlib.util.spec_from_file_location(
        "bench_generation",
        os.path.join(REPO, "benchmarks", "bench_generation.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_bench_pins_schema():
    # the --serving JSON row of record: per-batch rows + the aggregate
    # payload bench-row diffs key on; drift must fail here, not in a diff
    mod = _load_bench_generation()
    # queue_wait_ms joined in ISSUE 12 (the SLO-bucketed histogram the
    # front door scrapes, surfaced per batch row)
    assert set(mod.SERVING_ROW_FIELDS) == {
        "aggregate_tokens_per_sec", "ttft_ms", "tpot_ms", "queue_wait_ms",
        "scan_greedy_parity", "match_frac", "batch_utilization"}
    assert {"benchmark", "kv_dtype", "page_size",
            "single_stream_tokens_per_sec", "serving", "resilience",
            "speedup_vs_single_stream", "device"} <= \
        set(mod.SERVING_RESULT_FIELDS)
    # the serving-under-fire counters (ISSUE 8): shed/deadline/watchdog
    # visibility is part of the row of record — a bench diff showing
    # nonzero here means the run itself degraded
    assert set(mod.SERVING_RESILIENCE_FIELDS) == {
        "rejected_queue_full", "rejected_deadline", "rejected_shed",
        "watchdog_trips", "replays"}
    import inspect
    src = inspect.getsource(mod._run_serving)
    # rows/payload are asserted against the pinned schema at emit time
    assert "SERVING_ROW_FIELDS" in src and "SERVING_RESULT_FIELDS" in src
    assert "SERVING_RESILIENCE_FIELDS" in src
    for field in (mod.SERVING_ROW_FIELDS + mod.SERVING_RESULT_FIELDS
                  + mod.SERVING_RESILIENCE_FIELDS):
        assert f'"{field}"' in src, field
    # greedy-parity failure is a hard exit: no numbers without the gate
    assert "sys.exit(1)" in src


def test_serving_bench_wired_into_main():
    mod = _load_bench_generation()
    import inspect
    src = inspect.getsource(mod.main)
    assert "--serving" in src and "_run_serving" in src
    assert "--kv-dtype" in src        # the int8 leg is reachable from CLI
    assert "--context-sweep" in src   # the long-context leg (ISSUE 13)
    assert "--http" in src            # the front-door leg (ISSUE 15)
    assert "--fleet" in src           # the fleet-tier leg (ISSUE 20)


def test_http_bench_pins_schema():
    # the --serving --http front-door leg (ISSUE 15): e2e latency through
    # the router + streaming HTTP tier vs in-process submit(), with the
    # router's resilience counters — all-zero-on-healthy is the claim of
    # record, so a bench diff showing retries/failovers/hedges/rejections
    # means the measured run itself degraded
    mod = _load_bench_generation()
    assert set(mod.HTTP_RESULT_FIELDS) == {
        "replicas", "requests", "clients", "aggregate_tokens_per_sec",
        "e2e_p50_ms", "e2e_p99_ms", "inproc_p50_ms", "overhead_p50_ms",
        "router"}
    assert set(mod.HTTP_ROUTER_FIELDS) == {
        "retries", "failovers", "hedges", "rejected"}
    assert "http" in mod.SERVING_RESULT_FIELDS
    import inspect
    src = inspect.getsource(mod._run_http)
    # the block is asserted against the pinned schema at emit time, and
    # every pinned field is actually emitted
    assert "HTTP_RESULT_FIELDS" in src and "HTTP_ROUTER_FIELDS" in src
    for field in mod.HTTP_RESULT_FIELDS + mod.HTTP_ROUTER_FIELDS:
        assert f'"{field}"' in src, field
    # the front-door overhead is DERIVED from the two measured p50s, and
    # the leg measures both paths over the same router + prompts
    assert "overhead_p50_ms" in src and "inproc" in src
    assert "FrontDoor" in src and "Router" in src
    # wired: _run_serving emits the block (None without --http)
    serving_src = inspect.getsource(mod._run_serving)
    assert "_run_http" in serving_src and "args.http" in serving_src


def test_fleet_bench_pins_schema():
    # the --serving --fleet leg (ISSUE 20): e2e latency through a
    # 2-worker OUT-OF-PROCESS FleetSupervisor vs in-process submit(),
    # with the supervisor's crash counters — all-zero-on-healthy is the
    # claim of record, so a bench diff showing respawns/worker_deaths/
    # failovers/rejections means the measured run itself degraded (a
    # worker died and was respawned mid-measurement)
    mod = _load_bench_generation()
    assert set(mod.FLEET_RESULT_FIELDS) == {
        "workers", "requests", "clients", "aggregate_tokens_per_sec",
        "e2e_p50_ms", "e2e_p99_ms", "inproc_p50_ms", "overhead_p50_ms",
        "supervisor"}
    assert set(mod.FLEET_SUPERVISOR_FIELDS) == {
        "respawns", "worker_deaths", "failovers", "rejected"}
    assert "fleet" in mod.SERVING_RESULT_FIELDS
    import inspect
    src = inspect.getsource(mod._run_fleet)
    # the block is asserted against the pinned schema at emit time, and
    # every pinned field is actually emitted
    assert "FLEET_RESULT_FIELDS" in src and "FLEET_SUPERVISOR_FIELDS" in src
    for field in mod.FLEET_RESULT_FIELDS + mod.FLEET_SUPERVISOR_FIELDS:
        assert f'"{field}"' in src, field
    # the overhead is DERIVED from the two measured p50s over the same
    # prompts, and the fleet path really is the out-of-process tier
    assert "overhead_p50_ms" in src and "inproc" in src
    assert "FleetSupervisor" in src and "FleetWorkerSpec" in src
    # a degraded leg (short response, dead worker) fails the bench run
    # instead of printing numbers
    assert "degraded" in src
    # the worker factory ships in the bench module itself, importable as
    # bench_generation:make_fleet_engine by the worker process, and
    # rebuilds under the parent's seed so weights are bit-identical
    factory_src = inspect.getsource(mod.make_fleet_engine)
    assert "seed(0)" in factory_src and "ServingConfig" in factory_src
    # wired: _run_serving emits the block (None without --fleet)
    serving_src = inspect.getsource(mod._run_serving)
    assert "_run_fleet" in serving_src and "args.fleet" in serving_src


# ---------------------------------------------------------------------------
# paged-attention block + context sweep (ISSUE 13)
# ---------------------------------------------------------------------------

def test_paged_attention_block_schema():
    mod = _load_bench_generation()
    assert set(mod.PAGED_ATTENTION_FIELDS) == {
        "mode", "kernel_steps", "dense_steps", "attn_bytes_per_token_live",
        "attn_bytes_per_token_dense", "attn_bytes_source",
        "suspect_reasons"}
    assert set(mod.CONTEXT_SWEEP_FIELDS) == {
        "context", "decode_tokens_per_sec", "attn_bytes_per_token_live",
        "attn_bytes_per_token_dense"}
    # the paged block lands in the payload of record
    assert "paged_attention" in mod.SERVING_RESULT_FIELDS
    assert "context_sweep" in mod.SERVING_RESULT_FIELDS
    import inspect
    src = inspect.getsource(mod._run_serving)
    assert "PAGED_ATTENTION_FIELDS" in src and "_paged_suspect_reasons" \
        in src


def test_paged_bytes_model_tracks_live_pages_not_max_len():
    # the acceptance claim in miniature: the modeled kernel traffic grows
    # with the CONTEXT, the dense traffic with max_len — at a short
    # context in a long cache the two must diverge by ~max_len/context
    mod = _load_bench_generation()
    kw = dict(layers=2, heads=4, head_dim=64, page_size=64,
              storage_bytes=2, n_new=8)
    live_short, dense_short = mod._paged_attn_bytes_per_token(
        max_len=8192, prompt=256, **kw)
    live_long, dense_long = mod._paged_attn_bytes_per_token(
        max_len=8192, prompt=4096, **kw)
    assert dense_short == dense_long          # max_len-bound, context-blind
    assert live_long > live_short * 10        # context-bound
    assert live_short < dense_short / 10      # the short-context win
    # at full context the kernel converges to the dense bound, never above
    live_full, dense_full = mod._paged_attn_bytes_per_token(
        max_len=8192, prompt=8192 - 9, **kw)
    assert live_full <= dense_full


def test_all_dense_on_tpu_is_suspect():
    mod = _load_bench_generation()
    block = {"mode": "auto", "kernel_steps": 0, "dense_steps": 40,
             "attn_bytes_per_token_live": 1, "attn_bytes_per_token_dense": 2}
    reasons = mod._paged_suspect_reasons(block, on_tpu=True)
    assert reasons and "dense" in reasons[0]
    # the same counters are healthy on CPU (auto = dense tier there), when
    # the kernel actually ran, and when the operator forced mode=off
    assert mod._paged_suspect_reasons(block, on_tpu=False) == []
    assert mod._paged_suspect_reasons(
        dict(block, kernel_steps=40, dense_steps=0), on_tpu=True) == []
    assert mod._paged_suspect_reasons(
        dict(block, mode="off"), on_tpu=True) == []


def test_paged_measured_bytes_come_from_cost_registry():
    # ISSUE 16: the tier that ran reports the cost registry's measured
    # per-token bytes (largest warmed bucket's bytes_accessed / bucket);
    # no measured record -> None -> the block stays on the model
    mod = _load_bench_generation()
    recs = {1: {"bytes_accessed": 1000.0}, 4: {"bytes_accessed": 8000.0}}
    assert mod._measured_decode_bytes_per_token(recs) == 2000
    assert mod._measured_decode_bytes_per_token({}) is None
    assert mod._measured_decode_bytes_per_token(
        {4: {"bytes_accessed": None}}) is None
    import inspect
    src = inspect.getsource(mod._run_serving)
    assert "_measured_decode_bytes_per_token" in src
    assert "decode_bucket_records" in src and '"attn_bytes_source"' in src


def test_paged_formula_cross_checks_measurement():
    # one-sided 10% cross-check: the modeled attention-only bytes of the
    # tier that ran must not exceed the measured whole-program traffic
    mod = _load_bench_generation()
    base = {"mode": "auto", "kernel_steps": 0, "dense_steps": 40,
            "attn_bytes_per_token_live": 100,
            "attn_bytes_per_token_dense": 5000,
            "attn_bytes_source": "measured"}
    # formula (6000) > measured dense (5000) * 1.10 -> flagged
    reasons = mod._paged_suspect_reasons(base, on_tpu=False,
                                         formula_live=100,
                                         formula_dense=6000)
    assert reasons and "disagree" in reasons[0]
    # formula within the one-sided envelope -> clean
    assert mod._paged_suspect_reasons(base, on_tpu=False, formula_live=100,
                                      formula_dense=4000) == []
    # source=model (no measurement): no cross-check to run
    assert mod._paged_suspect_reasons(
        dict(base, attn_bytes_source="model"), on_tpu=False,
        formula_live=100, formula_dense=6000) == []
    # kernel tier ran -> the live formula is the one checked
    kblock = dict(base, kernel_steps=40, dense_steps=0,
                  attn_bytes_per_token_live=5000)
    assert mod._paged_suspect_reasons(kblock, on_tpu=False,
                                      formula_live=6000,
                                      formula_dense=100) != []


# ---------------------------------------------------------------------------
# program cost accounting block (ISSUE 16)
# ---------------------------------------------------------------------------

def test_cost_detail_is_schema_stable():
    # the row of record pins the cost block: XLA's modeled step
    # flops/bytes, the modeled MFU from the measured step time, and the
    # HBM ledger's peak/headroom
    assert set(bench.COST_FIELDS) == {
        "model_source", "step_flops", "step_bytes", "mfu_modeled",
        "peak_hbm_bytes", "hbm_headroom_bytes"}
    doc = {"records": [
        {"site": "dispatch", "flops": 1.0, "bytes_accessed": 2.0,
         "model_source": "xla"},
        {"site": "train.step", "flops": 2e12, "bytes_accessed": 1e10,
         "model_source": "xla"}],
        "hbm": {"peak_hbm_bytes": 8 << 30, "headroom_bytes": 8 << 30}}
    block = bench._cost_detail(doc, analytic_step_flops=9e9,
                               step_seconds=0.5, peak_flops=1e13)
    assert set(block) == set(bench.COST_FIELDS)
    assert block["model_source"] == "xla"
    assert block["step_flops"] == 2e12 and block["step_bytes"] == 1e10
    # mfu = flops / (seconds * peak): 2e12 / (0.5 * 1e13) = 0.4
    assert block["mfu_modeled"] == 0.4
    assert block["peak_hbm_bytes"] == 8 << 30


def test_cost_detail_analytic_fallback_and_all_null_suspect():
    # no train.step record -> the analytic flops estimate stands in,
    # labeled as such; nothing at all -> all-null block -> suspect
    block = bench._cost_detail({"records": [], "hbm": {}},
                               analytic_step_flops=1e12,
                               step_seconds=0.5, peak_flops=1e13)
    assert block["model_source"] == "analytic"
    assert block["step_flops"] == 1e12 and block["step_bytes"] is None
    assert block["mfu_modeled"] == 0.2
    assert bench._cost_suspect_reasons(block) == []

    empty = bench._cost_detail({"records": [], "hbm": {}},
                               analytic_step_flops=0.0,
                               step_seconds=0.5, peak_flops=1e13)
    assert empty["model_source"] == "none"
    assert all(empty[k] is None for k in
               ("step_flops", "step_bytes", "mfu_modeled",
                "peak_hbm_bytes", "hbm_headroom_bytes"))
    reasons = bench._cost_suspect_reasons(empty)
    assert reasons and "cost accounting empty" in reasons[0]


def test_bench_main_emits_cost_block():
    import inspect
    src = inspect.getsource(bench.main)
    assert "_cost_detail" in src and '"cost"' in src
    assert "_cost_suspect_reasons" in src
    assert "debug_doc" in src


def test_cross_host_sync_roots_cover_cost_hooks():
    # the cost hook call-sites join the fast-path reachability roots: a
    # host sync reachable from capture would stall every dispatch/compile
    import sys
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.engine import DEFAULT_CONFIG
    roots = DEFAULT_CONFIG["fast_path_roots"]
    assert "paddle_tpu/observability/cost.py::_on_static_build" in roots
    assert "paddle_tpu/observability/cost.py::_on_dispatch_event" in roots
    assert "paddle_tpu/observability/cost.py" in \
        DEFAULT_CONFIG["span_hot_modules"]


def test_prefix_sharing_block_schema():
    # the --prompt-overlap leg (ISSUE 17): prefill-savings-of-record for
    # refcounted COW page sharing; schema drift must fail here
    mod = _load_bench_generation()
    assert "prefix_sharing" in mod.SERVING_RESULT_FIELDS
    assert set(mod.PREFIX_SHARING_FIELDS) == {
        "page_size", "prompt", "tokens", "requests", "legs",
        "suspect_reasons"}
    assert set(mod.PREFIX_SHARING_LEG_FIELDS) == {
        "overlap_pct", "shared_prefix_tokens",
        "aggregate_tokens_per_sec", "baseline_tokens_per_sec",
        "ttft_ms_p50", "ttft_ms_p99",
        "prefill_tokens_requested", "prefill_tokens_computed",
        "pages_shared_ratio", "prefix_hit_rate", "transcripts_match"}
    import inspect
    src = inspect.getsource(mod._run_prefix_sharing)
    assert "PREFIX_SHARING_FIELDS" in src
    assert "PREFIX_SHARING_LEG_FIELDS" in src
    for field in mod.PREFIX_SHARING_FIELDS + mod.PREFIX_SHARING_LEG_FIELDS:
        assert f'"{field}"' in src, field
    # the leg must compare bit-exact transcripts between sharing modes
    assert "_prefix_suspect_reasons" in src


def test_prefix_sharing_zero_sharing_at_90_is_suspect():
    mod = _load_bench_generation()
    healthy = {"overlap_pct": 90, "pages_shared_ratio": 0.7,
               "transcripts_match": True}
    legs = {"overlap0": dict(healthy, overlap_pct=0, pages_shared_ratio=0),
            "overlap90": dict(healthy)}
    assert mod._prefix_suspect_reasons(legs) == []
    # all-zero sharing at 90% overlap = the feature never ran: suspect
    broken = dict(legs, overlap90=dict(healthy, pages_shared_ratio=0))
    reasons = mod._prefix_suspect_reasons(broken)
    assert reasons and "ZERO pages" in reasons[0]
    # a transcript mismatch on ANY leg means COW leaked K/V: suspect
    leaked = dict(legs, overlap0=dict(
        healthy, overlap_pct=0, pages_shared_ratio=0,
        transcripts_match=False))
    reasons = mod._prefix_suspect_reasons(leaked)
    assert reasons and "COW" in reasons[0]


def test_prefix_sharing_wired_into_main():
    mod = _load_bench_generation()
    import inspect
    assert "--prompt-overlap" in inspect.getsource(mod.main)
    src = inspect.getsource(mod._run_serving)
    assert "_run_prefix_sharing" in src and "prompt_overlap" in src
    # a suspect prefix-sharing block is a hard exit, like greedy parity
    assert "PREFIX SHARING SUSPECT" in src and "sys.exit(1)" in src
