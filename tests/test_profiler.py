"""Profiler / RecordEvent / memory-stats tests (SURVEY.md §5 aux parity)."""

import json
import os

import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (
    Profiler, ProfilerState, ProfilerTarget, RecordEvent, make_scheduler,
    export_chrome_tracing,
)


def _work():
    x = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]], stop_gradient=False)
    with RecordEvent("user_block"):
        y = (x @ x).sum()
    y.backward()
    return y


def test_profiler_records_ops_and_user_events():
    p = Profiler(targets=[ProfilerTarget.CPU])
    p.start()
    _work()
    p.stop()
    names = {e["name"] for e in p.events()}
    assert "user_block" in names
    assert "matmul" in names or any("matmul" in n for n in names)
    # op hook must be uninstalled after stop
    from paddle_tpu.core import tensor as tmod
    assert tmod._op_profile_hook is None


def test_profiler_summary_and_chrome_export(tmp_path):
    p = Profiler(targets=[ProfilerTarget.CPU])
    with p:
        _work()
        p.step()
        _work()
    s = p.summary()
    assert "Calls" in s and "user_block" in s
    path = str(tmp_path / "trace.json")
    p.export_chrome_tracing(path)
    data = json.load(open(path))
    assert len(data["traceEvents"]) >= 2
    assert all(ev["ph"] == "X" for ev in data["traceEvents"])


def test_make_scheduler_windows():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                           skip_first=1)
    states = [sched(i) for i in range(6)]
    assert states[0] == ProfilerState.CLOSED          # skip_first
    assert states[1] == ProfilerState.CLOSED          # closed
    assert states[2] == ProfilerState.READY
    assert states[3] == ProfilerState.RECORD
    assert states[4] == ProfilerState.RECORD_AND_RETURN
    assert states[5] == ProfilerState.CLOSED          # repeat exhausted


def test_scheduler_gates_recording():
    sched = make_scheduler(closed=1, ready=0, record=1)
    p = Profiler(targets=[ProfilerTarget.CPU], scheduler=sched)
    p.start()                      # step 0: CLOSED — nothing recorded
    _work()
    p.step()                       # step 1: RECORD_AND_RETURN
    _work()
    p.stop()
    names = [e["name"] for e in p.events()]
    # only one window of work recorded (one user_block, not two)
    assert names.count("user_block") == 1


def test_on_trace_ready_handler(tmp_path):
    d = str(tmp_path / "traces")
    fired = []
    handler = export_chrome_tracing(d)

    def on_ready(prof):
        fired.append(prof.step_num)
        handler(prof)

    p = Profiler(targets=[ProfilerTarget.CPU], on_trace_ready=on_ready)
    with p:
        _work()
    assert fired
    assert os.listdir(d)


def test_timer_only_benchmark():
    p = Profiler(timer_only=True)
    p.start()
    _work()
    p.step()
    _work()
    p.stop()
    b = p.benchmark_summary()
    assert b["steps"] >= 2 and b["avg_step_s"] > 0
    assert p.events() == []  # no tracing in timer_only mode


def test_memory_stats_api():
    # CPU PJRT may not report stats — the API must still return ints ≥ 0.
    assert paddle.device.memory_allocated() >= 0
    assert paddle.device.max_memory_allocated() >= 0
    assert paddle.device.tpu.max_memory_reserved() >= 0
    assert paddle.device.cuda.memory_reserved() >= 0
    paddle.device.empty_cache()
    paddle.device.synchronize()


def test_record_event_explicit_begin_end():
    p = Profiler(targets=[ProfilerTarget.CPU])
    p.start()
    ev = RecordEvent("manual")
    ev.begin()
    ev.end()
    ev.end()  # double-end is a no-op
    p.stop()
    assert any(e["name"] == "manual" for e in p.events())


@pytest.mark.tpu
def test_memory_stats_on_real_chip():
    """The PJRT memory-stats parity surface against real HBM: allocate a
    known-size buffer on the chip and check the counters move accordingly.
    In-process — the pytest process of the ``-m tpu`` tier holds the chip,
    so a child could never get it."""
    import jax
    import jax.numpy as jnp

    assert jax.devices()[0].memory_stats() is not None
    base = paddle.device.memory_allocated()
    big = jax.device_put(jnp.zeros((64, 1024, 1024), jnp.float32))  # 256MB
    jax.block_until_ready(big)
    after = paddle.device.memory_allocated()
    peak = paddle.device.max_memory_allocated()
    assert after - base >= 200 * 1024 * 1024, (base, after)
    assert peak >= after, (peak, after)
