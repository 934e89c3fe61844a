"""End-to-end tracing + crash flight recorder (ISSUE 12).

The acceptance surface for ``paddle_tpu.observability.trace`` / ``http``:

* span trees — thread-local nesting, explicit cross-thread handoff via
  ``SpanContext``, balance on every exit path (the ``span_problems``
  validator the chaos suites reuse);
* Chrome trace-event export — a serving ``submit()`` under load and a
  supervised training run each produce a Perfetto-loadable document with
  a CONNECTED span tree per request/step (verified structurally);
* the always-on flight recorder — ring wrap-around, dump-on-abort with
  the injected fault site in the tail, the ``TrainAborted.flight_dump``
  handle;
* the ``/metrics`` + ``/healthz`` + ``/debug`` scrape endpoint;
* the SLO-shaped serving histogram boundaries (the bucket satellite);
* near-zero disabled-mode overhead (structural: the shared no-op span,
  the uninstalled per-op hook).
"""

import json
import os
import threading
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.observability import http as obs_http
from paddle_tpu.observability import trace
from paddle_tpu.resilience import faults, reset_policies
from paddle_tpu.resilience.trainer import TrainAborted, TrainingSupervisor

from test_serving import PROMPTS, dense_reference, make_engine


@pytest.fixture(autouse=True)
def _fast_retry_policies(monkeypatch):
    for site in ("STEP", "DATA", "SAVE"):
        monkeypatch.setenv(f"PADDLE_TPU_RETRY_TRAIN_{site}_BASE_DELAY",
                           "0.001")
        monkeypatch.setenv(f"PADDLE_TPU_RETRY_TRAIN_{site}_MAX_DELAY",
                           "0.002")
    reset_policies()
    yield
    reset_policies()


def _attrs(e):
    return e.get("attrs") or {}


def _req_events(evs, rid):
    return [e for e in evs if _attrs(e).get("rid") == rid]


# ---------------------------------------------------------------------------
# span core
# ---------------------------------------------------------------------------

class TestSpanCore:
    def test_disabled_span_is_shared_noop(self):
        assert trace.mode() == "off"
        s1 = trace.span("a", x=1)
        s2 = trace.span("b")
        assert s1 is s2                      # one shared object, no alloc
        with s1:
            pass
        assert trace.events() == []
        assert trace.new_trace("t") is None
        assert trace.current() is None

    def test_thread_local_nesting(self, tracing):
        with trace.span("outer"):
            with trace.span("inner"):
                cur = trace.current()
        evs = trace.events()
        assert trace.span_problems(evs) == []
        b = {e["name"]: e for e in evs if e["kind"] == "B"}
        assert b["inner"]["parent"] == b["outer"]["span"]
        assert b["inner"]["trace"] == b["outer"]["trace"]
        assert cur is not None and cur.span == b["inner"]["span"]
        assert trace.current() is None       # stack unwound

    def test_cross_thread_handoff(self, tracing):
        ctx = trace.new_trace("job-1", rid=1)
        out = {}

        def worker():
            with trace.span("phase", parent=ctx) as sp:
                out["ctx"] = sp.ctx

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert out["ctx"].trace == ctx.trace
        evs = trace.events()
        assert trace.span_problems(evs) == []
        b = [e for e in evs if e["kind"] == "B"][0]
        assert b["trace"] == ctx.trace and b["parent"] == 0

    def test_span_balanced_through_exceptions(self, tracing):
        with pytest.raises(faults.KillPoint):
            with trace.span("doomed"):
                raise faults.KillPoint("simulated death")
        assert trace.span_problems() == []
        end = [e for e in trace.events() if e["kind"] == "E"][0]
        assert end["attrs"]["error"] == "KillPoint"

    def test_instant_attaches_to_current_span(self, tracing):
        with trace.span("s") as sp:
            trace.instant("tick", n=1)
        ev = [e for e in trace.events() if e["kind"] == "i"][0]
        assert ev["trace"] == sp.ctx.trace and ev["parent"] == sp.ctx.span
        assert ev["attrs"] == {"n": 1}

    def test_span_problems_detects_imbalance(self, tracing):
        with trace.span("ok"):
            pass
        evs = trace.events()
        # drop the end event: the validator must notice
        broken = [e for e in evs if e["kind"] != "E"]
        assert trace.span_problems(broken) != []
        assert trace.span_problems(evs) == []

    def test_make_event_envelope(self):
        ev = trace.make_event("step", "telemetry", attrs={"step": 3})
        assert {"ts", "kind", "name", "attrs"} <= set(ev)
        assert ev["kind"] == "step" and ev["attrs"]["step"] == 3

    def test_unknown_env_mode_stays_off(self, monkeypatch):
        # a typo of "flight" must not silently enable the most expensive
        # tier (per-op hook + 500k-event buffer) on a production host
        monkeypatch.setenv("PADDLE_TPU_TRACE", "fligth")
        assert trace._env_mode() == "off"
        monkeypatch.setenv("PADDLE_TPU_TRACE", "flight")
        assert trace._env_mode() == "flight"
        monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
        assert trace._env_mode() == "on"

    def test_flight_mode_does_not_grow_track_labels(self):
        # flight mode is the bounded tier: per-request new_trace calls
        # must not leak label-map entries (the exporter never reads them)
        trace.set_mode("flight")
        try:
            before = len(trace._STATE.tracks)
            for _ in range(50):
                trace.new_trace("request-x")
            assert len(trace._STATE.tracks) == before
        finally:
            trace.set_mode("off")
            trace.flight_recorder().clear()

    def test_per_op_hook_only_in_on_mode(self, tracing):
        from paddle_tpu.core import tensor as tensor_mod
        assert tensor_mod._op_trace_hook is not None
        x = paddle.to_tensor([1.0, 2.0])
        _ = x + x
        assert any(e["kind"] == "O" for e in trace.events())
        trace.set_mode("flight")
        assert tensor_mod._op_trace_hook is None
        trace.set_mode("off")
        assert tensor_mod._op_trace_hook is None


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

class TestChromeExport:
    def test_export_structure_and_json(self, tracing, tmp_path):
        ctx = trace.new_trace("request-9", rid=9)
        with trace.span("serving.submit", parent=ctx, rid=9):
            trace.instant("serving.queued", parent=ctx, rid=9)
        doc = trace.export_chrome()
        json.dumps(doc)                      # serializable as-is
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = doc["traceEvents"]
        phases = {e["ph"] for e in evs}
        assert "X" in phases and "i" in phases and "M" in phases
        for e in evs:
            assert {"name", "ph", "pid"} <= set(e)
            if e["ph"] in ("X", "i"):
                assert e["ts"] >= 0
            if e["ph"] == "X":
                assert e["dur"] >= 0
        # track metadata names the request
        tracks = [e for e in evs if e["ph"] == "M"
                  and e["name"] == "thread_name"]
        assert any(t["args"]["name"] == "request-9" for t in tracks)
        # file form
        p = trace.export_chrome(str(tmp_path / "t.json"))
        assert json.load(open(p))["traceEvents"]

    def test_crash_open_span_exports_as_begin(self, tracing):
        evs = []
        with trace.span("outer"):
            evs = list(trace.events())       # B emitted, E not yet
        doc = trace.export_chrome(evs=evs)
        assert [e for e in doc["traceEvents"] if e["ph"] == "B"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_wraps_keeping_latest(self):
        fr = trace.FlightRecorder(capacity=8)
        for i in range(20):
            fr.record(trace.make_event("ev", f"e{i}"))
        snap = fr.snapshot()
        assert [e["name"] for e in snap] == [f"e{i}" for i in range(12, 20)]

    def test_capacity_from_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_EVENTS", "32")
        assert trace.FlightRecorder().capacity == 32
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_EVENTS", "bogus")
        assert trace.FlightRecorder().capacity == 512

    def test_record_lands_in_ring_even_when_tracing_off(self):
        assert trace.mode() == "off"
        trace.flight_recorder().clear()
        trace.record("fault", site="x.y")
        trace.instant("lifecycle", rid=1)
        names = [e["name"] for e in trace.flight_recorder().snapshot()]
        assert names == ["fault", "lifecycle"]
        assert trace.events() == []          # buffer untouched
        trace.flight_recorder().clear()

    def test_dump_is_parseable_and_atomic(self, tracing, tmp_path):
        trace.record("fault", site="train.step", injected="error")
        p = trace.flight_dump("unit_test", extra="info")
        assert p and os.path.dirname(p) == str(tmp_path)
        doc = json.load(open(p))
        assert doc["reason"] == "unit_test" and doc["pid"] == os.getpid()
        assert doc["info"]["extra"] == "info"
        assert doc["events"][-1]["attrs"]["site"] == "train.step"
        assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]

    def test_dump_failure_is_swallowed(self, tracing, tmp_path):
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("x")              # a FILE where a dir is needed
        p = trace.flight_recorder().dump(
            "nope", path=str(blocker / "deeper" / "f.json"))
        assert p is None                     # logged, never raised


# ---------------------------------------------------------------------------
# scrape endpoint
# ---------------------------------------------------------------------------

class TestHTTPEndpoint:
    def test_routes(self, tracing, metrics):
        # isolate from beacons earlier suites left behind (an engine test
        # that never stop()s leaves its beacon to go stale minutes later)
        trace._HEALTH.beats.clear()
        obs.inc("http.test_total")
        trace.heartbeat("test.engine", ttl_s=60.0)
        with trace.span("s"):
            pass
        srv = obs_http.start_http_server(0)
        try:
            body = urllib.request.urlopen(
                srv.url + "/metrics", timeout=5).read().decode()
            assert "http_test_total 1" in body
            r = urllib.request.urlopen(srv.url + "/healthz", timeout=5)
            h = json.load(r)
            assert r.status == 200 and h["status"] == "ok"
            assert h["components"]["test.engine"]["ok"]
            f = json.load(urllib.request.urlopen(
                srv.url + "/debug/flight", timeout=5))
            assert "events" in f and f["capacity"] >= 8
            t = json.load(urllib.request.urlopen(
                srv.url + "/debug/trace", timeout=5))
            assert t["traceEvents"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/nope", timeout=5)
            assert ei.value.code == 404
        finally:
            srv.close()
            trace.heartbeat_clear("test.engine")

    def test_healthz_503_on_stale_beacon(self):
        trace._HEALTH.beats.clear()
        trace.heartbeat("stale.engine", ttl_s=0.0)
        srv = obs_http.start_http_server(0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/healthz", timeout=5)
            assert ei.value.code == 503
            doc = json.load(ei.value)
            assert doc["status"] == "unhealthy"
            assert not doc["components"]["stale.engine"]["ok"]
        finally:
            srv.close()
            trace.heartbeat_clear("stale.engine")

    def test_env_opt_in_is_singleton_and_off_by_default(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_OBS_HTTP_PORT", raising=False)
        assert obs_http.maybe_serve_from_env() is None
        monkeypatch.setenv("PADDLE_TPU_OBS_HTTP_PORT", "0")
        monkeypatch.setattr(obs_http, "_GLOBAL", None)
        monkeypatch.setattr(obs_http, "_DISABLED", False)
        srv = obs_http.maybe_serve_from_env()
        try:
            assert srv is not None
            assert obs_http.maybe_serve_from_env() is srv   # one per process
        finally:
            srv.close()
            monkeypatch.setattr(obs_http, "_GLOBAL", None)

    def test_env_bad_port_disables_quietly_and_latches(self, monkeypatch,
                                                       caplog):
        import logging
        monkeypatch.setenv("PADDLE_TPU_OBS_HTTP_PORT", "not-a-port")
        monkeypatch.setattr(obs_http, "_GLOBAL", None)
        monkeypatch.setattr(obs_http, "_DISABLED", False)
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.observability.http"):
            assert obs_http.maybe_serve_from_env() is None
            # latched: the second opt-in attempt neither retries nor
            # re-warns (an engine is constructed per request batch)
            assert obs_http.maybe_serve_from_env() is None
        assert len([r for r in caplog.records
                    if "disabled" in r.message]) == 1
        assert obs_http._DISABLED


# ---------------------------------------------------------------------------
# serving integration: the request's connected span tree
# ---------------------------------------------------------------------------

class TestServingTrace:
    def test_request_trace_connected_across_threads(self, tracing, metrics):
        eng = make_engine(max_batch=4)
        reqs = [serving.GenerationRequest(p, max_new_tokens=10)
                for p in PROMPTS[:3]]
        futs = [eng.submit(r) for r in reqs]
        eng.start()                          # submit() thread != step thread
        try:
            for f in futs:
                f.result(timeout=60)
        finally:
            eng.stop(drain=True, timeout=10)
        evs = trace.events()
        assert trace.span_problems(evs) == []
        for r, f, p in zip(reqs, futs, PROMPTS[:3]):
            assert f.result().tokens == dense_reference(p, 10)
            mine = _req_events(evs, r.request_id)
            names = {e["name"] for e in mine}
            assert {"serving.submit", "serving.queued", "serving.prefill",
                    "serving.complete"} <= names
            # CONNECTED: every event of this request shares one trace id,
            # and every span parents to the request root or a sibling span
            trace_ids = {e["trace"] for e in mine}
            assert len(trace_ids) == 1
            spans = {e["span"] for e in mine if e["kind"] == "B"}
            for e in mine:
                par = e.get("parent", 0)
                assert par == 0 or par in spans
        # the engine's own decode spans live on their own track
        assert any(e["name"] == "serving.decode" for e in evs)

    def test_faulted_request_trace_carries_fault_event(self, tracing,
                                                       metrics):
        sched = faults.FaultSchedule()
        sched.error("serving.step", on=(1, 5))   # slot 0 faults twice
        eng = make_engine(max_batch=4)
        reqs = [serving.GenerationRequest(p, max_new_tokens=4)
                for p in PROMPTS[:2]]
        with faults.installed(sched):
            futs = [eng.submit(r) for r in reqs]
            eng.run()
            eng.stop(drain=True, timeout=10)
        failed = [r for r, f in zip(reqs, futs)
                  if f.exception(timeout=0) is not None]
        assert failed, "schedule should fail at least one request"
        evs = trace.events()
        for r in failed:
            fevs = [e for e in _req_events(evs, r.request_id)
                    if e["name"] == "serving.fault"]
            assert fevs, "faulted request's trace lost its fault event"
            assert fevs[-1]["attrs"]["error"] == "FaultInjected"
        assert trace.span_problems(evs) == []

    def test_recovery_dumps_flight_with_fault_site(self, tracing, metrics,
                                                   tmp_path):
        sched = faults.FaultSchedule()
        sched.error("serving.watchdog", on=(1, 2))   # attempt + retry ->
        eng = make_engine(max_batch=4, max_replays=2)  # crash-recovery
        with faults.installed(sched):
            futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=3)) for p in PROMPTS[:2]]
            eng.run()
            eng.stop(drain=True, timeout=10)
        for f, p in zip(futs, PROMPTS[:2]):   # replay finished the work
            assert f.result(timeout=0).tokens == dense_reference(p, 3)
        path = os.path.join(
            str(tmp_path), f"flight-{os.getpid()}-serving_recover.json")
        assert os.path.exists(path)
        doc = json.load(open(path))
        assert doc["reason"] == "serving_recover"
        fault_sites = [e["attrs"].get("site") for e in doc["events"]
                       if e["name"] == "fault"]
        assert fault_sites and fault_sites[-1] == "serving.watchdog"

    def test_slo_bucket_boundaries_registered(self):
        reg = obs.default_registry()
        ttft = reg.get("serving.ttft_seconds")
        tpot = reg.get("serving.tpot_seconds")
        qw = reg.get("serving.queue_wait_seconds")
        from paddle_tpu.serving.engine import TPOT_BUCKETS, TTFT_BUCKETS
        from paddle_tpu.serving.scheduler import QUEUE_WAIT_BUCKETS
        assert ttft.boundaries == TTFT_BUCKETS
        assert tpot.boundaries == TPOT_BUCKETS
        assert qw.boundaries == QUEUE_WAIT_BUCKETS
        # the satellite's point: sub-10ms decode steps resolve into
        # several buckets instead of clipping into one or two
        assert sum(1 for b in TPOT_BUCKETS if b < 0.01) >= 5
        assert sum(1 for b in QUEUE_WAIT_BUCKETS if b <= 0.025) >= 4

    def test_tracing_off_serving_still_correct_and_bufferless(self, metrics):
        assert trace.mode() == "off"
        buf_before = len(trace.events())
        eng = make_engine(max_batch=4)
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=4))
        eng.run()
        eng.stop(drain=True, timeout=5)
        assert fut.result(timeout=0).tokens == dense_reference(PROMPTS[0], 4)
        assert len(trace.events()) == buf_before


# ---------------------------------------------------------------------------
# phase spans + the profiler mirror (ISSUE 25)
# ---------------------------------------------------------------------------

_DECODE_PHASES = ["serving.decode.build", "serving.decode.launch",
                  "serving.decode.wait", "serving.decode.emit",
                  "serving.decode.release"]


def _serve(n_requests=2, new_tokens=4, stream=None):
    """A tiny engine through ``run()``: admission, a few batched decode
    steps, drain. Returns the engine (stopped)."""
    eng = make_engine(max_batch=4)
    futs = [eng.submit(serving.GenerationRequest(
        p, max_new_tokens=new_tokens, stream=stream))
        for p in PROMPTS[:n_requests]]
    eng.run()
    eng.stop(drain=True, timeout=10)
    for f, p in zip(futs, PROMPTS):
        assert f.result(timeout=0).tokens == dense_reference(p, new_tokens)
    return eng


def _span_table(evs):
    begins = {e["span"]: e for e in evs if e["kind"] == "B"}
    ends = {e["span"]: e for e in evs if e["kind"] == "E"}
    kids = {}
    for b in begins.values():
        kids.setdefault(b["parent"], []).append(b)
    return begins, ends, kids


class TestPhaseSpans:
    def test_decode_step_has_its_phases(self, tracing, metrics):
        _serve()
        evs = trace.events()
        assert trace.span_problems(evs) == []
        begins, ends, kids = _span_table(evs)
        decodes = [b for b in begins.values()
                   if b["name"] == "serving.decode"]
        assert len(decodes) >= 3
        # ISSUE 28: a span is the step READ at its boundary; the build and
        # launch in it are the NEXT step's. So the last span of a pipe has
        # none, and the pipe's first launch rides the engine's track
        assert [k["name"] for k in kids.get(decodes[-1]["span"], [])] == \
            _DECODE_PHASES[2:]
        assert [b["name"] for b in kids.get(decodes[0]["parent"], [])
                if b["ts"] < decodes[0]["ts"]
                and b["name"].startswith("serving.decode.")] == \
            _DECODE_PHASES[:2]
        assert [d["attrs"]["ahead"] for d in decodes] == \
            [0] + [1] * (len(decodes) - 1)
        for d in decodes:
            mine = kids.get(d["span"], [])
            assert [k["name"] for k in mine] == (
                _DECODE_PHASES if d is not decodes[-1]
                else _DECODE_PHASES[2:])
            # in order, inside the step, not overlapping
            t = d["ts"]
            for k in mine:
                assert t <= k["ts"] <= ends[k["span"]]["ts"]
                t = ends[k["span"]]["ts"]
            assert t <= ends[d["span"]]["ts"]

    def test_jit_dispatch_inside_jit_call_inside_launch(self, tracing,
                                                         metrics):
        _serve()
        begins, ends, kids = _span_table(trace.events())
        launches = [b for b in begins.values()
                    if b["name"] == "serving.decode.launch"]
        assert launches
        for la in launches:
            calls = kids.get(la["span"], [])
            assert [c["name"] for c in calls] == ["jit.call"]
            inner = kids.get(calls[0]["span"], [])
            assert [c["name"] for c in inner] == ["jit.dispatch"]
            assert (la["ts"] <= calls[0]["ts"] <= inner[0]["ts"]
                    <= ends[inner[0]["span"]]["ts"]
                    <= ends[calls[0]["span"]]["ts"]
                    <= ends[la["span"]]["ts"])
        # every dispatch has a jit.call around it, wherever it ran
        for b in begins.values():
            if b["name"] == "jit.dispatch":
                assert begins[b["parent"]]["name"] == "jit.call"

    def test_step_phases_ride_the_engine_track(self, tracing, metrics):
        eng = make_engine(max_batch=4)
        fut = eng.submit(serving.GenerationRequest(PROMPTS[0],
                                                   max_new_tokens=3))
        eng.start()              # the loop thread: idle waits get a span
        try:
            fut.result(timeout=60)
            time.sleep(0.05)     # let the loop go idle at least once
        finally:
            eng.stop(drain=True, timeout=10)
        evs = trace.events()
        assert trace.span_problems(evs) == []
        track = eng._engine_trace.trace
        names = {e["name"] for e in evs
                 if e["kind"] == "B" and e["trace"] == track}
        assert {"serving.cancel", "serving.admit", "serving.decode",
                "serving.publish", "serving.idle"} <= names
        # the request's own track keeps its prefill; the engine's admit
        # phase covers it in time
        begins, ends, _ = _span_table(evs)
        pre = next(b for b in begins.values()
                   if b["name"] == "serving.prefill")
        assert pre["trace"] != track
        assert any(b["name"] == "serving.admit"
                   and b["ts"] <= pre["ts"]
                   and ends[pre["span"]]["ts"] <= ends[b["span"]]["ts"]
                   for b in begins.values())

    def test_prefill_span_closes_after_the_first_token_exists(
            self, tracing, metrics, monkeypatch):
        """The pool swap and the first-token host read happen INSIDE
        ``serving.prefill``; the token reaches the stream after it."""
        seen = []
        from paddle_tpu.serving.programs import Programs
        real = Programs._adopt

        def spy(self, outs):
            seen.append(trace.current())
            return real(self, outs)

        monkeypatch.setattr(Programs, "_adopt", spy)
        first_token_at = {}

        def stream(rid, tok):
            first_token_at.setdefault(rid, trace.make_event("x", "x")["ts"])

        _serve(n_requests=1, stream=stream)
        begins, ends, kids = _span_table(trace.events())
        pre = next(b for b in begins.values()
                   if b["name"] == "serving.prefill")
        assert seen[0] is not None and seen[0].span == pre["span"]
        assert set(pre["attrs"]) == {"rid", "prompt", "shared_pages",
                                     "replay"}
        # the program call is a complete child, the read-back follows it
        call = next(k for k in kids[pre["span"]] if k["name"] == "jit.call")
        assert ends[call["span"]]["ts"] <= ends[pre["span"]]["ts"]
        assert ends[pre["span"]]["ts"] <= first_token_at[
            pre["attrs"]["rid"]]

    def test_compiled_call_nests_under_the_open_span(self, tracing):
        """What ``train.captured_step`` relies on: a compiled call opened
        under any span becomes its child, with the dispatch inside."""
        @paddle.jit.to_static
        def double(x):
            return x * 2

        with trace.span("train.captured_step", label="t"):
            out = double(paddle.to_tensor([1.0, 2.0]))
        assert out.numpy().tolist() == [2.0, 4.0]
        evs = trace.events()
        assert trace.span_problems(evs) == []
        begins, ends, kids = _span_table(evs)
        step = next(b for b in begins.values()
                    if b["name"] == "train.captured_step")
        call, = kids[step["span"]]
        assert call["name"] == "jit.call"
        assert [k["name"] for k in kids[call["span"]]] == ["jit.dispatch"]

    def test_off_mode_buffers_nothing_and_builds_no_annotation(
            self, metrics, monkeypatch):
        assert trace.mode() == "off"
        built = []
        monkeypatch.setattr(trace, "_annotation",
                            lambda *a: built.append(a))
        assert trace.phase("serving.decode.build") is trace.span("x")
        trace.phase_instant("serving.http.token", rid=0)
        before = len(trace.events())
        _serve()
        assert len(trace.events()) == before
        assert built == []

    def test_flight_mode_keeps_phases_out_of_the_ring(self, metrics,
                                                       monkeypatch):
        built = []
        monkeypatch.setattr(trace, "_annotation",
                            lambda *a: built.append(a))
        trace.flight_recorder().clear()
        with trace.tracing("flight"):
            assert trace.phase("jit.call") is trace._NOOP
            _serve()
        names = {e["name"] for e in trace.flight_recorder().snapshot()}
        trace.flight_recorder().clear()
        assert "serving.decode" in names and "serving.prefill" in names
        assert not {n for n in names
                    if n.startswith(("serving.decode.", "jit."))
                    or n in ("serving.cancel", "serving.admit",
                             "serving.publish", "serving.idle",
                             "serving.http.token")}
        assert built == [] and trace.events() == []

    def test_on_mode_phases_skip_the_ring(self, tracing):
        with trace.span("serving.decode", batch=1):
            with trace.phase("serving.decode.build"):
                trace.phase_instant("serving.http.token", rid=1, lag_ms=0.0)
        ring = {e["name"] for e in trace.flight_recorder().snapshot()}
        assert ring == {"serving.decode"}
        assert {e["name"] for e in trace.events()} == {
            "serving.decode", "serving.decode.build", "serving.http.token"}

    def test_mirror_carries_numbers_and_short_strings(self, tracing,
                                                      monkeypatch):
        made = []

        class Fake:
            def __init__(self, name, **kw):
                made.append((name, kw))

            def __enter__(self):
                made.append("enter")

            def __exit__(self, *exc):
                made.append("exit")

        monkeypatch.setattr(trace, "_ANNOTATION", Fake)
        with trace.span("serving.decode", batch=3, label="r0", ratio=0.5,
                        blob="x" * 200, obj=object()):
            pass
        assert made == [("serving.decode",
                         {"batch": 3, "label": "r0", "ratio": 0.5}),
                        "enter", "exit"]

    def test_real_profiler_slice_holds_the_phase_names(self, tracing,
                                                       metrics, tmp_path):
        """One real ``jax.profiler`` slice on the CPU, as the benchmark's
        ``ProfilerSlice`` takes it: the program's spans land in the host
        plane of the ``.xplane.pb``, named as in the buffer, nested on the
        step thread's line. Bounded by its own time limit."""
        found, errors = {}, []

        def body():
            try:
                import glob

                import jax.profiler
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(tmp_path),
                                         profiler_options=opts)
                try:
                    _serve()
                finally:
                    jax.profiler.stop_trace()
                path, = glob.glob(str(
                    tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
                data = jax.profiler.ProfileData.from_file(path)
                for plane in data.planes:
                    if not plane.name.startswith("/host:"):
                        continue
                    for line in plane.lines:
                        evs = [(e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)) for e in line.events
                               if e.name.startswith(("serving.", "jit."))]
                        if evs:
                            found[line.name] = evs
            except Exception as exc:        # reported by the assert below
                errors.append(exc)

        t = threading.Thread(target=body, daemon=True)
        t.start()
        t.join(timeout=120)
        assert not t.is_alive(), "profiler slice did not finish in 120 s"
        assert errors == []
        evs, = found.values()               # run(): one thread did it all
        names = {e[0] for e in evs}
        assert set(_DECODE_PHASES) | {
            "serving.decode", "serving.prefill", "serving.admit",
            "serving.cancel", "serving.publish", "jit.call",
            "jit.dispatch"} <= names
        # attributes arrive as stats, not in the name
        assert any(e[0] == "serving.decode" and e[3].get("batch") == 2
                   for e in evs)
        # one clock: a launch lies inside a decode step on that line (the
        # step read while it runs) — but the pipe's first, which has none
        steps = [e for e in evs if e[0] == "serving.decode"]
        launches = sorted((e for e in evs
                           if e[0] == "serving.decode.launch"),
                          key=lambda e: e[1])
        assert len(launches) == len(steps) >= 3
        assert launches[0][1] + launches[0][2] <= min(s[1] for s in steps)
        for la in launches[1:]:
            assert any(s[1] <= la[1] and la[1] + la[2] <= s[1] + s[2]
                       for s in steps)

    def test_front_door_stamps_every_streamed_token(self, tracing, metrics):
        from test_serving_http import stream_generate
        eng = make_engine(max_batch=4).start()
        fd = serving.FrontDoor(eng)
        try:
            tokens, terminals = stream_generate(fd, PROMPTS[0],
                                                max_new_tokens=6)
        finally:
            eng.stop(drain=True, timeout=10)
            fd.close()
        assert tokens == dense_reference(PROMPTS[0], 6)
        assert [t[0] for t in terminals] == ["done"]
        stamps = [e for e in trace.events()
                  if e["name"] == "serving.http.token"]
        assert [e["attrs"]["index"] for e in stamps] == list(range(6))
        assert len({e["attrs"]["rid"] for e in stamps}) == 1
        assert all(e["kind"] == "i" and e["attrs"]["lag_ms"] >= 0
                   for e in stamps)

    def test_front_door_untraced_stream_is_unstamped(self, metrics):
        from test_serving_http import stream_generate
        assert trace.mode() == "off"
        before = len(trace.events())
        eng = make_engine(max_batch=4).start()
        fd = serving.FrontDoor(eng)
        try:
            tokens, _ = stream_generate(fd, PROMPTS[0], max_new_tokens=4)
        finally:
            eng.stop(drain=True, timeout=10)
            fd.close()
        assert tokens == dense_reference(PROMPTS[0], 4)
        assert len(trace.events()) == before


# ---------------------------------------------------------------------------
# training integration: the step's span tree + abort dumps
# ---------------------------------------------------------------------------

def _build_run(seed=7, n=16, batch_size=8):
    from paddle_tpu.core.tensor import Parameter
    Parameter._param_counter = 0
    paddle.seed(seed)
    net = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.Adam(learning_rate=0.05,
                                parameters=net.parameters())
    rng = np.random.default_rng(seed)
    ds = paddle.io.TensorDataset(
        [paddle.to_tensor(rng.normal(size=(n, 8)).astype(np.float32)),
         paddle.to_tensor(rng.normal(size=(n, 4)).astype(np.float32))])
    # no buffered reader (ROADMAP C0): these runs abort or are killed
    # mid-epoch, which abandons the loader's iterator inside the
    # exception's traceback cycle. With the native prefetch queue in that
    # cycle, the next test's gc pass may finalize the queue before the
    # suspended generator, whose ``finally`` then closes a freed handle
    # and takes the worker process down — in an order that only shows
    # beside other workers. The traces pinned here do not need a prefetch
    # thread; the queue's own fault is ROADMAP C0's.
    loader = paddle.io.DataLoader(ds, batch_size=batch_size, shuffle=True,
                                  use_buffer_reader=False)
    loss_fn = paddle.nn.MSELoss()

    def step_fn(batch):
        x, y = batch
        loss = loss_fn(net(x), y)
        loss.backward()
        return loss

    def update_fn():
        opt.step()
        opt.clear_grad()

    return SimpleNamespace(net=net, opt=opt, loader=loader, step=step_fn,
                           update=update_fn)


class TestTrainingTrace:
    def test_supervised_run_has_connected_step_tree(self, tracing):
        r = _build_run()
        sup = TrainingSupervisor(r.net, r.opt, r.loader)
        rep = sup.run(r.step, r.loader, epochs=1, update_fn=r.update)
        assert rep.steps == 2
        evs = trace.events()
        assert trace.span_problems(evs) == []
        b = [e for e in evs if e["kind"] == "B"]
        by_name = {}
        for e in b:
            by_name.setdefault(e["name"], []).append(e)
        assert len(by_name["train.step"]) >= 2
        run_span = by_name["train.run"][0]
        for step_ev in by_name["train.step"]:
            assert step_ev["parent"] == run_span["span"]
            assert step_ev["trace"] == run_span["trace"]
        # fetch/fwd_bwd/update are children of SOME train.step
        step_ids = {e["span"] for e in by_name["train.step"]}
        for name in ("train.fetch", "train.fwd_bwd", "train.update"):
            assert all(e["parent"] in step_ids for e in by_name[name]), name
        doc = trace.export_chrome()
        json.dumps(doc)
        assert [e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"] == "train.step"]

    def test_retry_event_attached_inside_step(self, tracing):
        r = _build_run()
        sched = faults.FaultSchedule().error("train.step", on=(2,))
        sup = TrainingSupervisor(r.net, r.opt, r.loader)
        with faults.installed(sched):
            rep = sup.run(r.step, r.loader, epochs=1, update_fn=r.update)
        assert rep.retries == 1
        evs = trace.events()
        retries = [e for e in evs if e["name"] == "train.retry"]
        assert retries and retries[0]["attrs"]["site"] == "train.step"
        step_spans = {e["span"] for e in evs if e["kind"] == "B"
                      and e["name"] == "train.step"}
        assert retries[0]["parent"] in step_spans
        assert trace.span_problems(evs) == []

    def test_abort_dump_tail_names_fault_site(self, tracing, tmp_path):
        r = _build_run()
        sched = faults.FaultSchedule().error("train.step", on=(1, 2, 3))
        sup = TrainingSupervisor(r.net, r.opt, r.loader)   # no ckpt_dir
        with faults.installed(sched):
            with pytest.raises(TrainAborted) as ei:
                sup.run(r.step, r.loader, epochs=1, update_fn=r.update)
        dump = ei.value.flight_dump
        assert dump and os.path.exists(dump)
        doc = json.load(open(dump))
        assert doc["reason"] == "train_aborted"
        fevs = [e for e in doc["events"] if e["name"] == "fault"]
        assert fevs and fevs[-1]["attrs"]["site"] == "train.step"
        assert trace.span_problems() == []   # balanced through the abort

    def test_kill_dump_written_on_supervisor_exit(self, tracing, tmp_path):
        r = _build_run()
        sched = faults.FaultSchedule().kill("train.step", on=(2,))
        sup = TrainingSupervisor(r.net, r.opt, r.loader,
                                 ckpt_dir=str(tmp_path / "ck"), save_every=1)
        with faults.installed(sched):
            with pytest.raises(faults.KillPoint):
                sup.run(r.step, r.loader, epochs=1, update_fn=r.update)
        path = os.path.join(
            str(tmp_path), f"flight-{os.getpid()}-supervisor_exit.json")
        doc = json.load(open(path))
        assert doc["info"]["error"] == "KillPoint"
        fevs = [e for e in doc["events"] if e["name"] == "fault"]
        assert fevs[-1]["attrs"]["site"] == "train.step"
        assert trace.span_problems() == []   # spans unwound by the kill


# ---------------------------------------------------------------------------
# envelope unification + hapi
# ---------------------------------------------------------------------------

class TestEnvelopeUnification:
    def test_step_telemetry_record_is_envelope_and_rings(self, tmp_path,
                                                         metrics):
        trace.flight_recorder().clear()
        obs.counter("tt.n_total").inc(2)
        path = str(tmp_path / "s.jsonl")
        w = obs.StepTelemetryWriter(path, baseline="zero")
        rec = w.write(1, loss=0.5)
        w.close()
        assert {"ts", "kind", "name", "attrs"} <= set(rec)
        assert rec["kind"] == "step" and rec["name"] == "telemetry"
        assert rec["attrs"]["counters"]["tt.n_total"] == 2
        assert rec["attrs"]["loss"] == 0.5
        # mirrored into the flight ring: a crash dump's tail carries the
        # last steps' telemetry
        ring = trace.flight_recorder().snapshot()
        assert ring and ring[-1]["kind"] == "step"
        assert obs.read_jsonl(path)[0]["attrs"]["step"] == 1
        trace.flight_recorder().clear()

    def test_hapi_fit_spans(self, tracing):
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=paddle.nn.MSELoss())
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32)),
             paddle.to_tensor(rng.normal(size=(8, 2)).astype(np.float32))])
        model.fit(ds, batch_size=4, epochs=1, verbose=0)
        evs = trace.events()
        assert trace.span_problems(evs) == []
        names = {e["name"] for e in evs if e["kind"] == "B"}
        assert {"hapi.fit", "hapi.train_batch"} <= names


# ---------------------------------------------------------------------------
# set-up names itself (ISSUE 36): completed phases, jax's trace / lower /
# compile as spans of the program, the warm-up as a span around them
# ---------------------------------------------------------------------------

class TestCompletedPhase:
    def test_pair_is_balanced_and_parented_to_the_open_span(self, tracing):
        with trace.span("serving.warmup") as warm:
            trace.phase_done("jit.trace", 0.25, program="r0.decode")
        evs = trace.events()
        assert trace.span_problems(evs) == []
        b, e = [x for x in evs if x["name"] == "jit.trace"]
        assert (b["kind"], e["kind"]) == ("B", "E")
        assert (b["trace"], b["parent"]) == tuple(warm.ctx)
        assert (e["trace"], e["span"]) == (b["trace"], b["span"])
        assert e["ts"] - b["ts"] == pytest.approx(0.25)
        assert b["attrs"] == {"program": "r0.decode"}
        # _Span's envelope, key for key
        outer = next(x for x in evs if x["name"] == "serving.warmup")
        assert set(b) == set(outer)
        # it ended inside the span that was open, and never saw the ring
        assert e["ts"] <= evs[-1]["ts"] and evs[-1]["kind"] == "E"
        assert {x["name"] for x in trace.flight_recorder().snapshot()} == {
            "serving.warmup"}
        # a reader of the Chrome export sees one complete event
        doc = trace.export_chrome(evs=evs)
        done, = [x for x in doc["traceEvents"] if x["name"] == "jit.trace"]
        assert done["ph"] == "X" and done["dur"] == pytest.approx(250e3)

    def test_with_no_span_open_it_rides_track_zero(self, tracing):
        trace.phase_done("jit.compile", 0.5)
        b, e = trace.events()
        assert (b["trace"], b["parent"]) == (0, 0)
        assert trace.span_problems() == []

    def test_at_the_buffer_cap_both_events_are_dropped(self, tracing,
                                                       monkeypatch):
        with trace.span("serving.warmup"):
            monkeypatch.setattr(trace, "_BUFFER_CAP", len(trace.events()) + 1)
            trace.phase_done("jit.trace", 0.25)
            assert trace.dropped() == 2
            monkeypatch.setattr(trace, "_BUFFER_CAP", 500_000)
        assert trace.span_problems() == []
        assert "jit.trace" not in {e["name"] for e in trace.events()}

    @pytest.mark.parametrize("m", ["off", "flight"])
    def test_off_and_flight_write_nothing(self, m):
        trace.clear()
        trace.flight_recorder().clear()
        with trace.tracing(m):
            with trace.span("serving.warmup"):
                trace.phase_done("jit.trace", 0.25, program="r0.decode")
        assert trace.events() == []
        assert "jit.trace" not in {
            x["name"] for x in trace.flight_recorder().snapshot()}
        trace.flight_recorder().clear()

    def test_parent_is_this_threads_innermost_open_span(self, tracing):
        """Whose work a finished phase is reads off ``parent``: the span
        open on the thread that reports it, never another thread's."""
        with trace.span("jit.dispatch", program="outer") as outer:
            with trace.phase("jit.dispatch", program="inner") as inner:
                trace.phase_done("jit.trace", 0.01, fun="f")
            trace.phase_done("jit.lower", 0.01, fun="f")
            th = threading.Thread(
                target=lambda: trace.phase_done("jit.compile", 0.01, fun="f"))
            th.start()
            th.join(timeout=10)
        parent = {e["name"]: (e["trace"], e["parent"]) for e in trace.events()
                  if e["kind"] == "B" and e["name"] != "jit.dispatch"}
        assert parent == {"jit.trace": tuple(inner.ctx),
                          "jit.lower": tuple(outer.ctx),
                          "jit.compile": (0, 0)}
        assert trace.span_problems() == []


_JIT_PHASES = ("jit.trace", "jit.lower", "jit.compile")


@pytest.fixture()
def every_event_a_span(monkeypatch):
    """The CPU traces a toy function in well under the millisecond below
    which an event feeds the counters only."""
    from paddle_tpu.observability import compile_events
    monkeypatch.setattr(compile_events, "MIN_SPAN_S", 0.0)


def _jit_phases(evs, since=0):
    """name -> (begin event, program) of the jit.* spans written after
    buffer index ``since``; the program is the one its parent names where
    that is a jit.dispatch, None for what jax compiled for eager code."""
    begins = {e["span"]: e for e in evs if e["kind"] == "B"}
    out = {}
    for e in evs[since:]:
        if e["kind"] == "B" and e["name"] in _JIT_PHASES:
            asked = begins.get(e["parent"], {"name": None})
            out.setdefault(e["name"], []).append(
                (e, asked["attrs"]["program"]
                 if asked["name"] == "jit.dispatch" else None))
    return out


class TestSetupSpans:
    @pytest.mark.parametrize("label", ["r0.decode", None])
    def test_first_call_and_new_shape_name_their_three_phases(
            self, tracing, metrics, every_event_a_span, label):
        @paddle.jit.to_static
        def triple(x):
            return x * 3.0

        triple.cost_label = label
        program = label or "triple"

        def call(n):
            at = len(trace.events())
            out = triple(paddle.to_tensor(np.ones((n,), np.float32)))
            assert out.numpy().tolist() == [3.0] * n
            return _jit_phases(trace.events(), at)

        first = call(4)
        assert set(first) == set(_JIT_PHASES)
        # the program's own lowering and compile, once each; its trace may
        # hold traces of jitted helpers, children by time containment
        assert len(first["jit.lower"]) == len(first["jit.compile"]) == 1
        for evs in first.values():
            assert {prog for _, prog in evs} == {program}
        own = [e for e, _ in first["jit.trace"]
               if e["attrs"]["fun"] == "pure_fn"]
        assert len(own) == 1
        assert "cache_hit" not in first["jit.compile"][0][0]["attrs"]
        # same shape: jax's own cache, no event; a new shape under the SAME
        # StaticFunction entry: all three again (how buckets arrive)
        assert call(4) == {}
        assert len(triple.program_cache) == 1
        again = call(8)
        assert set(again) == set(_JIT_PHASES)
        assert len(triple.program_cache) == 1
        assert {prog for _, prog in again["jit.compile"]} == {program}
        assert trace.span_problems() == []

    def test_events_under_a_millisecond_feed_the_counters_only(
            self, tracing, metrics, monkeypatch):
        from paddle_tpu.observability import compile_events
        monkeypatch.setattr(compile_events, "MIN_SPAN_S", 3600.0)

        @paddle.jit.to_static
        def triple(x):
            return x * 3.0

        triple(paddle.to_tensor(np.ones((4,), np.float32)))
        assert _jit_phases(trace.events()) == {}
        assert set(metrics.snapshot()["jit.compile_seconds_total"]) == {
            "phase=trace", "phase=lower", "phase=compile"}

    def test_listener_is_silent_without_obs_enable(self, tracing,
                                                   every_event_a_span):
        from paddle_tpu import observability as obs
        assert not obs.enabled()

        @paddle.jit.to_static
        def triple(x):
            return x * 3.0

        triple(paddle.to_tensor(np.ones((4,), np.float32)))
        assert _jit_phases(trace.events()) == {}

    @pytest.mark.parametrize("lens", [(), (8, 5)])
    def test_warmup_holds_one_compiled_call_per_bucket_and_length(
            self, tracing, metrics, lens):
        eng = make_engine(max_batch=4)          # buckets (1, 4)
        eng.warmup(prompt_lens=lens)
        evs = trace.events()
        assert trace.span_problems(evs) == []
        begins, ends, kids = _span_table(evs)
        warm, = [b for b in begins.values() if b["name"] == "serving.warmup"]
        assert warm["attrs"]["programs"] == 2 + len(lens)
        calls = [k for k in kids[warm["span"]] if k["name"] == "jit.call"]
        asked = []
        for call in calls:
            disp, = kids[call["span"]]
            asked.append(disp["attrs"]["program"])
            # what jax did for this program lies inside its dispatch
            phases = kids.get(disp["span"], [])
            assert {k["name"] for k in phases} == set(_JIT_PHASES)
            for k in phases:
                assert disp["ts"] <= k["ts"] <= ends[k["span"]]["ts"] \
                    <= ends[disp["span"]]["ts"]
        assert asked == ["engine.decode"] * 2 + ["engine.prefill"] * len(lens)
        # the lifecycle span reaches the flight ring, the compiled call's
        # phases and jax's events never do
        ring = {e["name"] for e in trace.flight_recorder().snapshot()}
        assert "serving.warmup" in ring
        assert not {n for n in ring if n.startswith("jit.")}

    def test_warmup_names_a_tail_program(self, tracing, metrics,
                                         tier_engine):
        eng = tier_engine("dense", buckets=(2,), max_batch=2)
        eng.warmup(prompt_lens=(8,), tails=((16, 8),))
        evs = trace.events()
        assert trace.span_problems(evs) == []
        begins, ends, _ = _span_table(evs)
        warm, = [b for b in begins.values() if b["name"] == "serving.warmup"]
        assert warm["attrs"]["programs"] == 3
        programs = [b["attrs"]["program"] for b in begins.values()
                    if b["name"] == "jit.dispatch"]
        assert programs == ["engine.decode", "engine.prefill",
                            "engine.prefill_tail16"]
        # each compiled under its own name, inside the warm-up (a tiny
        # Llama's compile is well over a millisecond)
        compiles = _jit_phases(evs)["jit.compile"]
        assert {prog for _, prog in compiles} >= set(programs)
        for e, prog in compiles:
            if prog is None:                # the engine's build, eagerly
                continue
            assert warm["ts"] <= e["ts"] <= ends[e["span"]]["ts"] \
                <= ends[warm["span"]]["ts"]
