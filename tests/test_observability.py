"""paddle_tpu.observability: registry semantics, hot-seam integration,
exporter round-trips, and the zero-overhead-when-disabled guard."""

import io
import json
import threading
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.core import tensor as core_tensor
from paddle_tpu.observability.registry import Counter, Gauge, Histogram, Registry


@pytest.fixture(autouse=True)
def _isolated_metrics():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_inc_and_value(self):
        r = Registry()
        c = r.counter("x.things_total")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5

    def test_counter_rejects_negative(self):
        r = Registry()
        with pytest.raises(ValueError):
            r.counter("x.n_total").inc(-1)

    def test_get_or_create_returns_same_family(self):
        r = Registry()
        assert r.counter("a") is r.counter("a")

    def test_kind_conflict_raises(self):
        r = Registry()
        r.counter("a")
        with pytest.raises(TypeError):
            r.gauge("a")

    def test_label_set_conflict_raises(self):
        r = Registry()
        r.counter("a", labelnames=("op",))
        with pytest.raises(ValueError):
            r.counter("a", labelnames=("kind",))

    def test_labeled_series_are_independent(self):
        r = Registry()
        c = r.counter("ops_total", labelnames=("op",))
        c.inc(op="add")
        c.inc(op="add")
        c.inc(op="mul")
        assert c.value(op="add") == 2
        assert c.value(op="mul") == 1
        assert c.value(op="sub") == 0

    def test_wrong_labels_raise(self):
        r = Registry()
        c = r.counter("ops_total", labelnames=("op",))
        with pytest.raises(ValueError):
            c.inc(kind="add")
        with pytest.raises(ValueError):
            c.inc()  # missing the declared label

    def test_gauge_set_and_add(self):
        r = Registry()
        g = r.gauge("depth")
        g.set(4)
        assert g.value() == 4
        g.add(-1.5)
        assert g.value() == 2.5

    def test_histogram_buckets_are_cumulative(self):
        r = Registry()
        h = r.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        st = h.stats()
        # cumulative: <=0.1 -> 1, <=1.0 -> 3, <=10.0 -> 4, +Inf -> 5
        assert st["buckets"] == [1, 3, 4, 5]
        assert st["count"] == 5
        assert st["sum"] == pytest.approx(56.05)

    def test_histogram_boundaries_sorted_and_fixed(self):
        r = Registry()
        h = r.histogram("lat2", buckets=(1.0, 0.1))
        assert h.boundaries == (0.1, 1.0)

    def test_histogram_bucket_mismatch_raises(self):
        r = Registry()
        r.histogram("lat3", buckets=(0.1, 1.0))
        with pytest.raises(ValueError):
            r.histogram("lat3", buckets=(30.0, 60.0))
        # omitting buckets accepts whatever the family was created with
        assert r.histogram("lat3").boundaries == (0.1, 1.0)

    def test_snapshot_shapes(self):
        r = Registry()
        r.counter("plain_total").inc(3)
        c = r.counter("by_op_total", labelnames=("op",))
        c.inc(op="add")
        r.histogram("lat", buckets=(1.0,)).observe(0.5)
        snap = r.snapshot()
        assert snap["plain_total"] == 3
        assert snap["by_op_total"] == {"op=add": 1}
        assert snap["lat"]["count"] == 1

    def test_reset_zeroes_but_keeps_families(self):
        r = Registry()
        c = r.counter("n_total")
        c.inc(7)
        r.reset()
        assert c.value() == 0
        assert r.get("n_total") is c

    def test_thread_safety_exact_counts(self):
        r = Registry()
        c = r.counter("n_total")
        h = r.histogram("lat", buckets=(0.5,))
        N, T = 5000, 8

        def work():
            for _ in range(N):
                c.inc()
                h.observe(0.1)

        threads = [threading.Thread(target=work) for _ in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == N * T
        assert h.stats()["count"] == N * T
        assert h.stats()["buckets"] == [N * T, N * T]


# ---------------------------------------------------------------------------
# dispatch-seam integration + zero-overhead guard
# ---------------------------------------------------------------------------

class TestDispatchIntegration:
    def test_disabled_installs_no_hook(self):
        # the zero-overhead contract: while disabled, apply() carries only
        # the is-None probe it already had — there is no hook to call
        assert core_tensor._op_metrics_hook is None
        x = paddle.to_tensor([1.0, 2.0])
        (x + x).numpy()
        assert obs.snapshot().get("dispatch.ops_total") is None

    def test_enable_counts_ops_and_latency(self):
        obs.enable()
        assert core_tensor._op_metrics_hook is not None
        x = paddle.to_tensor([1.0, 2.0])
        y = x * 2.0
        z = y + 1.0
        snap = obs.snapshot()
        assert snap["dispatch.ops_total"] >= 2
        assert snap["dispatch.latency_seconds"]["count"] == \
            snap["dispatch.ops_total"]
        by_op = snap["dispatch.ops_by_name_total"]
        assert any("multiply" in k or "mul" in k for k in by_op)

    def test_disable_stops_counting(self):
        obs.enable()
        x = paddle.to_tensor([1.0])
        _ = x + 1.0
        before = obs.snapshot()["dispatch.ops_total"]
        obs.disable()
        assert core_tensor._op_metrics_hook is None
        _ = x + 1.0
        assert obs.snapshot()["dispatch.ops_total"] == before

    def test_helpers_are_noops_while_disabled(self):
        obs.inc("some.counter_total")
        obs.set_gauge("some.depth", 3)
        obs.observe("some.lat_seconds", 0.1)
        with obs.scoped_timer("some.timer_seconds"):
            pass
        snap = obs.snapshot()
        assert not any(k.startswith("some.") for k in snap)


class TestJitCounters:
    def test_compile_then_cache_hits(self):
        obs.enable()

        @paddle.jit.to_static
        def f(a):
            return a * 2.0 + 1.0

        x = paddle.to_tensor(np.ones((4,), np.float32))
        f(x)
        f(x)
        f(x)
        snap = obs.snapshot()
        assert snap["jit.compiles_total"] == 1
        assert snap["jit.traces_total"] == 1
        assert snap["jit.cache_hits_total"] == 2
        assert snap["jit.cache_misses_total"] == 1

    def test_graph_break_does_not_count_as_compile(self):
        obs.enable()

        @paddle.jit.to_static(full_graph=False)
        def f(a):
            if float(a.sum()) > 0:  # concrete read -> trace failure
                return a * 2.0
            return a

        x = paddle.to_tensor(np.ones((4,), np.float32))
        f(x)
        snap = obs.snapshot()
        assert snap["jit.graph_breaks_total"] == 1
        assert snap["jit.traces_total"] == 1  # the trace was attempted
        assert snap.get("jit.compiles_total") is None  # but nothing compiled

    def test_small_train_loop_reports_dispatch_and_compiles(self):
        # the acceptance shape: after a small train loop with a to_static
        # step, BOTH dispatch.ops_total and jit.compiles_total are nonzero
        obs.enable()
        paddle.seed(0)
        lin = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())

        @paddle.jit.to_static
        def step(xb):
            loss = (lin(xb) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.random.default_rng(0)
                             .normal(size=(8, 4)).astype(np.float32))
        for _ in range(3):
            step(x)
        snap = obs.snapshot()
        assert snap["dispatch.ops_total"] > 0
        assert snap["jit.compiles_total"] >= 1
        assert snap["jit.cache_hits_total"] >= 1


_TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EV = "/jax/core/compile/backend_compile_duration"
_CACHE_EV = "/jax/compilation_cache/"


def _compile_seconds():
    return obs.snapshot().get("jit.compile_seconds_total", {})


class TestCompileEvents:
    """ISSUE 36: jax's own trace / lower / compile events as counters (and,
    in trace mode ``on``, as spans) — one listener, behind obs.enable()."""

    def test_phases_grow_when_jax_compiles_and_only_then(self):
        obs.enable()

        @paddle.jit.to_static
        def f(a):
            return a * 2.0 + 1.0

        x = paddle.to_tensor(np.ones((4,), np.float32))
        f(x)
        first = _compile_seconds()
        assert set(first) == {"phase=trace", "phase=lower", "phase=compile"}
        assert all(v > 0 for v in first.values())
        f(x)
        f(x)
        assert _compile_seconds() == first
        # a new shape under the same entry: jax compiles, jit.compiles_total
        # (fresh StaticFunction builds) does not move
        f(paddle.to_tensor(np.ones((8,), np.float32)))
        grown = _compile_seconds()
        assert all(grown[k] > first[k] for k in first)
        snap = obs.snapshot()
        assert snap["jit.compiles_total"] == 1
        assert "NOT one per XLA compile" in \
            obs.default_registry().get("jit.compiles_total").help
        assert 'jit_compile_seconds_total{phase="lower"}' in \
            obs.prometheus_text()

    def test_nested_events_are_counted_once(self):
        import jax.monitoring
        from paddle_tpu.observability import compile_events
        obs.enable()
        # what this thread's earlier compiles covered is not this test's
        compile_events._TLS.__dict__.clear()
        # a kernel body traced inside a program's trace ends first
        jax.monitoring.record_event_duration_secs(_TRACE_EV, 0.2)
        jax.monitoring.record_event_duration_secs(_TRACE_EV, 0.1)
        jax.monitoring.record_event_duration_secs(_TRACE_EV, 0.5)
        assert _compile_seconds()["phase=trace"] == pytest.approx(0.5,
                                                                  abs=5e-3)
        # the next program's trace overlaps nothing
        import time
        time.sleep(0.02)
        jax.monitoring.record_event_duration_secs(_TRACE_EV, 0.01)
        assert _compile_seconds()["phase=trace"] == pytest.approx(0.51,
                                                                  abs=5e-3)

    def test_cache_counters_read_zero_before_any_compile(self):
        obs.enable()
        snap = obs.snapshot()
        assert snap["jit.persistent_cache_hits_total"] == 0
        assert snap["jit.persistent_cache_misses_total"] == 0

    @pytest.mark.parametrize("event,hit", [("cache_hits", 1),
                                           ("cache_misses", 0),
                                           (None, None)])
    def test_cache_event_inside_a_compile(self, tracing, event, hit):
        """Driven with jax's own calls, as ``compiler.compile_or_get_cached``
        makes them: no cache directory, nothing the CPU backend must admit."""
        import jax.monitoring
        obs.enable()
        with tracing.span("jit.dispatch", program="r0.decode") as asked:
            if event:
                jax.monitoring.record_event(_CACHE_EV + event)
            if hit:
                jax.monitoring.record_event_duration_secs(
                    _CACHE_EV + "compile_time_saved_sec", 9.0)
                jax.monitoring.record_event_duration_secs(
                    _CACHE_EV + "cache_retrieval_time_sec", 0.125)
            jax.monitoring.record_event_duration_secs(
                _COMPILE_EV, 0.5, fun_name="jit(pure_fn)")
            # the next compile on this thread met no cache
            jax.monitoring.record_event_duration_secs(
                _COMPILE_EV, 0.25, fun_name="jit(other)")
        assert tracing.span_problems() == []
        first, second = [e for e in tracing.events()
                         if e["kind"] == "B" and e["name"] == "jit.compile"]
        # whose compile: the dispatch that ``parent`` names
        assert first["parent"] == second["parent"] == asked.ctx.span
        want = {"fun": "jit(pure_fn)"}
        if hit is not None:
            want["cache_hit"] = hit
        if hit:
            want["load_s"] = 0.125
        assert first["attrs"] == want
        assert second["attrs"] == {"fun": "jit(other)"}
        snap = obs.snapshot()
        assert snap["jit.persistent_cache_hits_total"] == (1 if hit else 0)
        assert snap["jit.persistent_cache_misses_total"] == (
            1 if hit == 0 else 0)

    def test_disabled_listener_counts_nothing(self):
        import jax.monitoring
        obs.enable()                  # registers; stays registered
        obs.disable()
        obs.reset()
        jax.monitoring.record_event(_CACHE_EV + "cache_misses")
        jax.monitoring.record_event_duration_secs(_COMPILE_EV, 0.5)
        snap = obs.snapshot()
        assert "jit.compile_seconds_total" not in snap
        assert "jit.persistent_cache_misses_total" not in snap
        # and enabling twice registered one listener, not two
        obs.enable()
        obs.enable()
        jax.monitoring.record_event(_CACHE_EV + "cache_misses")
        assert obs.snapshot()["jit.persistent_cache_misses_total"] == 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestPrometheusExport:
    def test_round_trip_counters_and_gauges(self):
        obs.enable()
        obs.inc("rt.things_total", 5)
        obs.inc("rt.by_op_total", 2, op="add")
        obs.inc("rt.by_op_total", 3, op="mul")
        obs.set_gauge("rt.depth", 7)
        parsed = obs.parse_prometheus_text(obs.prometheus_text())
        assert parsed["rt_things_total"][""] == 5
        assert parsed["rt_depth"][""] == 7
        by_op = parsed["rt_by_op_total"]
        assert by_op['{op="add"}'] == 2
        assert by_op['{op="mul"}'] == 3

    def test_round_trip_histogram(self):
        h = obs.histogram("rt.lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        parsed = obs.parse_prometheus_text(obs.prometheus_text())
        buckets = parsed["rt_lat_seconds_bucket"]
        assert buckets['{le="0.1"}'] == 1
        assert buckets['{le="1.0"}'] == 2
        assert buckets['{le="+Inf"}'] == 3
        assert parsed["rt_lat_seconds_count"][""] == 3
        assert parsed["rt_lat_seconds_sum"][""] == pytest.approx(5.55)

    def test_label_values_are_escaped(self):
        obs.enable()
        obs.inc("esc.n_total", 1, name='load "train"\nshard\\x')
        text = obs.prometheus_text()
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("esc_n_total{"))
        assert '\\"train\\"' in line
        assert "\\n" in line and "\n" not in line[:-1].split(" ")[0]
        assert "\\\\x" in line

    def test_non_finite_values_render_not_raise(self):
        obs.enable()
        obs.set_gauge("nf.loss", float("nan"))
        obs.set_gauge("nf.peak", float("inf"))
        text = obs.prometheus_text()  # must not raise
        assert "nf_loss NaN" in text
        assert "nf_peak +Inf" in text

    def test_value_keyword_is_rejected_not_mislabeled(self):
        obs.enable()
        with pytest.raises(TypeError, match="positional-only"):
            obs.inc("vk.n_total", value=5)
        assert "vk.n_total" not in obs.snapshot()

    def test_type_headers_present(self):
        obs.counter("t.c_total").inc()
        obs.gauge("t.g").set(1)
        text = obs.prometheus_text()
        assert "# TYPE t_c_total counter" in text
        assert "# TYPE t_g gauge" in text

    def test_dispatch_counters_round_trip(self):
        # acceptance: the exporters round-trip the dispatch counters
        obs.enable()
        x = paddle.to_tensor([1.0, 2.0])
        _ = x + x
        snap = obs.snapshot()
        parsed = obs.parse_prometheus_text(obs.prometheus_text())
        assert parsed["dispatch_ops_total"][""] == snap["dispatch.ops_total"]


class TestJsonlExport:
    def test_step_deltas_and_round_trip(self, tmp_path):
        obs.enable()
        path = str(tmp_path / "steps.jsonl")
        c = obs.counter("jl.ops_total")
        w = obs.StepTelemetryWriter(path)
        c.inc(3)
        obs.set_gauge("jl.depth", 2)
        w.write(1, loss=0.9)
        c.inc(4)
        w.write(2, loss=0.7)
        w.close()
        recs = obs.read_jsonl(path)
        # ISSUE 12: records are the shared trace envelope (ts/kind/name/
        # attrs), the step payload inside attrs
        for r in recs:
            assert {"ts", "kind", "name", "attrs"} <= set(r)
            assert r["kind"] == "step" and r["name"] == "telemetry"
        assert [r["attrs"]["step"] for r in recs] == [1, 2]
        assert recs[0]["attrs"]["counters"]["jl.ops_total"] == 3
        # DELTA, not total
        assert recs[1]["attrs"]["counters"]["jl.ops_total"] == 4
        assert recs[0]["attrs"]["gauges"]["jl.depth"] == 2
        assert recs[0]["attrs"]["loss"] == pytest.approx(0.9)

    def test_dispatch_counters_round_trip_via_jsonl(self, tmp_path):
        obs.enable()
        path = str(tmp_path / "t.jsonl")
        w = obs.StepTelemetryWriter(path)
        x = paddle.to_tensor([1.0])
        _ = x + x
        w.write(1)
        w.close()
        rec = obs.read_jsonl(path)[0]
        assert rec["attrs"]["counters"]["dispatch.ops_total"] >= 1
        # histogram rides along as .count/.sum samples
        assert rec["attrs"]["counters"]["dispatch.latency_seconds.count"] >= 1

    def test_writer_accepts_file_object(self):
        obs.enable()
        obs.counter("fo.n_total").inc()
        buf = io.StringIO()
        w = obs.StepTelemetryWriter(buf, baseline="zero")
        w.write(1)
        rec = json.loads(buf.getvalue())
        assert rec["attrs"]["counters"]["fo.n_total"] == 1


class TestScopedTimer:
    def test_records_into_histogram(self):
        obs.enable()
        with obs.scoped_timer("st.block_seconds", what="x"):
            pass
        snap = obs.snapshot()
        assert snap["st.block_seconds"]["what=x"]["count"] == 1

    def test_free_when_disabled(self):
        with obs.scoped_timer("st.block_seconds"):
            pass
        assert "st.block_seconds" not in obs.snapshot()


# ---------------------------------------------------------------------------
# subsystem integrations
# ---------------------------------------------------------------------------

class TestDataLoaderMetrics:
    def test_batch_and_wait_metrics(self):
        obs.enable()
        xs = np.arange(32, dtype=np.float32).reshape(16, 2)
        ds = paddle.io.TensorDataset([paddle.to_tensor(xs)])
        loader = paddle.io.DataLoader(ds, batch_size=4, shuffle=False)
        n = sum(1 for _ in loader)
        snap = obs.snapshot()
        total = sum(snap["dataloader.batches_total"].values())
        assert n == 4 and total == 4
        assert snap["dataloader.wait_seconds"]["count"] >= 1

    def test_no_metrics_when_disabled(self):
        xs = np.zeros((8, 2), np.float32)
        ds = paddle.io.TensorDataset([paddle.to_tensor(xs)])
        loader = paddle.io.DataLoader(ds, batch_size=4)
        _ = [b for b in loader]
        assert "dataloader.batches_total" not in obs.snapshot()


class TestProfilerBridge:
    def test_record_event_emits_histogram_sample(self):
        obs.enable()
        from paddle_tpu import profiler as prof
        with prof.RecordEvent("aug"):
            pass
        snap = obs.snapshot()
        assert snap["profiler.record_event_seconds"]["name=aug"]["count"] == 1


class TestHapiStepTelemetry:
    def test_fit_writes_jsonl_with_telemetry(self, tmp_path):
        from paddle_tpu.hapi.callbacks import StepTelemetry

        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=paddle.nn.CrossEntropyLoss())
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(16, 4)).astype(np.float32)),
             paddle.to_tensor(rng.integers(0, 2, 16).astype(np.int64))])
        path = str(tmp_path / "telemetry.jsonl")
        model.fit(ds, batch_size=8, epochs=1, verbose=0,
                  callbacks=[StepTelemetry(path)])
        recs = obs.read_jsonl(path)
        assert len(recs) == 2  # 16 samples / batch 8
        for rec in recs:
            assert rec["attrs"]["counters"].get("dispatch.ops_total", 0) > 0
            assert "loss" in rec["attrs"]
        # the callback turned metrics off again at train end (they were
        # off before fit)
        assert not obs.enabled()

    def test_fit_restores_user_enabled_metrics(self, tmp_path):
        from paddle_tpu.hapi.callbacks import StepTelemetry

        obs.enable()  # the USER's process-wide collection
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=paddle.nn.CrossEntropyLoss())
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32)),
             paddle.to_tensor(rng.integers(0, 2, 8).astype(np.int64))])
        model.fit(ds, batch_size=8, epochs=1, verbose=0,
                  callbacks=[StepTelemetry(str(tmp_path / "t.jsonl"))])
        assert obs.enabled()  # fit must not clobber the user's enable

    def test_train_end_cleanup_runs_when_training_raises(self, tmp_path):
        from paddle_tpu.hapi.callbacks import StepTelemetry

        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)

        def exploding_loss(*a):
            raise RuntimeError("boom")
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=exploding_loss)
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32)),
             paddle.to_tensor(rng.integers(0, 2, 8).astype(np.int64))])
        cb = StepTelemetry(str(tmp_path / "t.jsonl"))
        with pytest.raises(RuntimeError, match="boom"):
            model.fit(ds, batch_size=8, epochs=1, verbose=0, callbacks=[cb])
        # on_train_end ran on the exception path: metrics state restored
        # (it was off before fit) and the writer handle closed
        assert not obs.enabled()
        assert cb._writer is None

    def test_success_path_teardown_runs_all_callbacks(self, tmp_path):
        # a broken sibling's on_train_end must neither rob StepTelemetry
        # of cleanup nor be swallowed: all teardowns run, first error
        # propagates
        from paddle_tpu.hapi.callbacks import Callback, StepTelemetry

        class BadEnd(Callback):
            def on_train_end(self, logs=None):
                raise RuntimeError("end boom")

        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=paddle.nn.CrossEntropyLoss())
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32)),
             paddle.to_tensor(rng.integers(0, 2, 8).astype(np.int64))])
        st = StepTelemetry(str(tmp_path / "t.jsonl"))
        with pytest.raises(RuntimeError, match="end boom"):
            model.fit(ds, batch_size=8, epochs=1, verbose=0,
                      callbacks=[BadEnd(), st])
        assert st._writer is None  # StepTelemetry still tore down
        assert not obs.enabled()

    def test_crashed_fit_does_not_write_final_checkpoint(self, tmp_path):
        import os
        from paddle_tpu.hapi.callbacks import ModelCheckpoint

        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)

        def exploding_loss(*a):
            raise RuntimeError("boom")
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
            loss=exploding_loss)
        rng = np.random.default_rng(0)
        ds = paddle.io.TensorDataset(
            [paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32)),
             paddle.to_tensor(rng.integers(0, 2, 8).astype(np.int64))])
        ck = str(tmp_path / "ck")
        with pytest.raises(RuntimeError, match="boom"):
            model.fit(ds, batch_size=8, epochs=1, verbose=0,
                      callbacks=[ModelCheckpoint(save_dir=ck)])
        # the crashed run must not be indistinguishable from a finished one
        assert not os.path.exists(os.path.join(ck, "final.pdparams"))


class TestPsAsyncPushAccounting:
    def test_dropped_async_push_is_counted_and_logged(self, caplog):
        import logging
        from paddle_tpu.distributed.ps_service import PsClient

        obs.enable()
        client = PsClient("srv", retry_timeout=0.01)

        def failing_call(server, fn, args):
            raise RuntimeError("transport down")
        client._call = failing_call

        with caplog.at_level(logging.ERROR,
                             logger="paddle_tpu.distributed.ps_service"):
            fut = client.push("t", [0], [[1.0]], wait=False)
            with pytest.raises(RuntimeError):
                fut.wait(timeout=10)
        assert obs.snapshot()["ps.dropped_async_pushes_total"] == 1
        assert any("async push" in r.message for r in caplog.records)

    def test_async_push_resolves_through_retry_wrapper(self):
        from paddle_tpu.distributed.ps_service import PsClient

        client = PsClient("srv", retry_timeout=0.01)
        calls = []

        def ok_call(server, fn, args):
            calls.append((server, fn))
            return True
        client._call = ok_call
        fut = client.push("t", [0], [[1.0]], wait=False)
        assert fut.wait(timeout=10) is True
        assert calls and calls[0][0] == "srv"
        client.close()

    def test_close_stops_drain_thread(self):
        from paddle_tpu.distributed.ps_service import PsClient

        client = PsClient("srv", retry_timeout=0.01)
        client._call = lambda server, fn, args: True
        fut = client.push("t", [0], [[1.0]], wait=False)
        fut.wait(timeout=10)
        q_t = client._async_pool
        assert q_t is not None
        client.close(wait=True, timeout=5)
        assert client._async_pool is None
        assert not q_t[1].is_alive()
        client.close()  # idempotent

    def test_queue_cap_drops_oldest_and_counts(self):
        import threading as th
        from paddle_tpu.distributed.ps_service import PsClient

        obs.enable()
        client = PsClient("srv", retry_timeout=0.01, max_pending_async=2)
        gate = th.Event()
        client._call = lambda server, fn, args: gate.wait(5) or True
        for _ in range(6):
            client.push("t", [0], [[1.0]], wait=False)
        gate.set()
        client.close(wait=True, timeout=10)
        # at least pushes 2..4-ish were evicted by the cap, all counted
        assert obs.snapshot()["ps.dropped_async_pushes_total"] >= 2

    def test_async_pushes_use_their_own_dedup_stream(self):
        from paddle_tpu.distributed.ps_service import PsClient

        client = PsClient("srv", retry_timeout=0.01)
        seen = []
        client._call = lambda server, fn, args: seen.append(args) or True
        client.push("t", [0], [[1.0]], wait=True)
        client.push("t", [0], [[1.0]], wait=False).wait(timeout=10)
        client.close()
        sync_key, async_key = seen[0][6], seen[1][6]
        assert async_key == sync_key + "/async1"

    def test_server_does_not_dedup_across_streams(self):
        # the silent-drop scenario: sync push (seq 6) overtakes an async
        # retry (seq 5); with per-stream keys the late push still applies
        from paddle_tpu.distributed import ps_service as pss

        pss.reset_server_state()
        arr = np.zeros((4, 2), np.float32)
        pss._srv_create("t", arr.tobytes(), (4, 2), "float32")
        ids = np.array([0], np.int64)
        g = np.ones((1, 2), np.float32)
        pss._srv_push("t", ids.tobytes(), g.tobytes(), 1, 2, 1.0, "ck", 6)
        pss._srv_push("t", ids.tobytes(), g.tobytes(), 1, 2, 1.0,
                      "ck/async", 5)
        raw, shape, dtype = pss._srv_table_snapshot("t")
        table = np.frombuffer(raw, dtype).reshape(shape)
        assert table[0, 0] == -2.0  # BOTH pushes applied (sgd: -lr*g each)
        # same stream still dedups
        pss._srv_push("t", ids.tobytes(), g.tobytes(), 1, 2, 1.0, "ck", 6)
        raw, shape, dtype = pss._srv_table_snapshot("t")
        assert np.frombuffer(raw, dtype).reshape(shape)[0, 0] == -2.0
        pss.reset_server_state()


class TestElasticStoreHealth:
    class _DeadStore:
        def check(self, key):
            raise ConnectionError("store down")

        def get(self, key, timeout=None):
            raise ConnectionError("store down")

        def set(self, key, val):
            raise ConnectionError("store down")

    def _agent(self, deadline):
        from paddle_tpu.distributed.fleet.elastic.manager import (
            ElasticManager, MultiNodeElasticAgent)
        # bypass __init__ plumbing that builds a local TCPStore
        agent = MultiNodeElasticAgent.__new__(MultiNodeElasticAgent)
        agent.store = self._DeadStore()
        agent.store_lost_deadline = deadline
        agent.store_lost = False
        agent._store_fail_first = None
        agent._store_fail_count = 0
        agent._read_fail_throttle = obs.LogThrottle()
        agent._write_fail_throttle = obs.LogThrottle()
        agent._key_fail_first = {}
        agent.node_timeout = 10.0
        return agent

    def test_read_failure_counts_and_stays_fresh_before_deadline(self):
        obs.enable()
        agent = self._agent(deadline=3600.0)
        assert agent._node_age(0) == 0.0  # transient blip still reads fresh
        assert not agent.store_lost
        assert obs.snapshot()["elastic.store_read_failures_total"] == 1

    def test_store_declared_lost_after_deadline(self, caplog):
        import logging
        import time
        obs.enable()
        agent = self._agent(deadline=0.0)
        with caplog.at_level(
                logging.ERROR,
                logger="paddle_tpu.distributed.fleet.elastic.manager"):
            agent._node_age(0)
            time.sleep(0.01)
            agent._node_age(0)  # second consecutive failure, past deadline
        assert agent.store_lost
        assert obs.snapshot()["elastic.store_read_failures_total"] == 2
        assert any("LOST" in r.message for r in caplog.records)

    def test_single_unreadable_lease_reads_lost_after_deadline(self):
        import time

        agent = self._agent(deadline=0.05)
        # other nodes read fine: global window keeps resetting, but node
        # 3's per-node window persists and eventually reads as lost
        assert agent._node_age(3) == 0.0  # fresh within deadline
        agent._store_read_ok()            # a healthy sibling read
        time.sleep(0.06)
        assert agent._node_age(3) is None  # unreadable lease == lost lease
        assert not agent.store_lost  # the STORE is not declared lost

    def test_unreadable_coordination_key_escalates_to_store_lost(self):
        import time

        # node leases read fine (resetting the global window) but the
        # fault flag is permanently unreadable: coordination is broken,
        # so the per-key deadline must still trip store-LOST
        agent = self._agent(deadline=0.03)

        class FaultDeadStore:
            def check(self, k):
                if "fault" in k:
                    raise TimeoutError("key timeout")
                return False
        agent.store = FaultDeadStore()
        assert agent._fault_epoch(2) == -1
        agent._node_age(0)  # healthy lease read resets the GLOBAL window
        time.sleep(0.04)
        agent._fault_epoch(2)
        assert agent.store_lost

    def test_success_resets_failure_window(self):
        agent = self._agent(deadline=0.0)
        agent._node_age(0)

        class _OkStore:
            def check(self, key):
                return False
        agent.store = _OkStore()
        assert agent._node_age(0) is None  # never leased
        assert agent._store_fail_first is None
        assert agent._store_fail_count == 0


class TestPipelineSegMethodWarning:
    def _entries(self, names):
        classes = {}
        out = []
        for n in names:
            cls = classes.setdefault(n, type(n, (), {}))
            out.append((cls(), None))
        return out

    def test_too_few_named_blocks_warns_and_counts(self):
        from paddle_tpu.distributed.fleet.tpu_pipeline import \
            _refine_run_bounds

        obs.enable()
        entries = self._entries(["Embed", "Block", "Head"])
        keys = ["k0", "k1", "k2"]
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            lo, hi = _refine_run_bounds(entries, keys, 0, 3, 2,
                                        "layer:Block")
        assert any("seg_method" in str(x.message) for x in w)
        assert obs.snapshot()["pipeline.seg_method_fallbacks_total"] == 1
        assert (lo, hi) == (0, 3)  # heuristic kept the whole run (no
        #                            repeating inward neighbor to trim to)

    def test_enough_named_blocks_bound_the_run_silently(self):
        from paddle_tpu.distributed.fleet.tpu_pipeline import \
            _refine_run_bounds

        entries = self._entries(["Embed", "Block", "Block", "Head"])
        keys = ["k0", "k1", "k1", "k2"]
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            lo, hi = _refine_run_bounds(entries, keys, 0, 4, 2,
                                        "layer:Block")
        assert not w
        assert (lo, hi) == (1, 3)


class TestNamingConvention:
    def test_builtin_families_follow_convention(self):
        # counters end in _total; histograms in _seconds; all are
        # subsystem.name shaped (README "metric naming convention")
        for m in obs.default_registry().families():
            assert "." in m.name, m.name
            if isinstance(m, Counter):
                assert m.name.endswith("_total"), m.name
            elif isinstance(m, Histogram):
                assert m.name.endswith("_seconds"), m.name
