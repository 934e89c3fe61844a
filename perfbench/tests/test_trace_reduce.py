"""``trace_reduce`` on the small recorded trace kept beside it, and on a
hand-made one where the answers are known."""

import json
import os

import pytest

from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _hand():
    ops = [["copy.1 bf16[4]", 0.0, 10.0], ["fusion f32[2]", 5.0, 10.0],
           ["paged_attention_decode.7 f32[1]", 30.0, 5.0],
           ["copy.1 bf16[4]", 100.0, 10.0]]
    modules = [["jit_a(1)", 0.0, 35.0], ["jit_b(2)", 100.0, 10.0]]
    return {"planes": [{"name": "/device:TPU:0", "ops": ops,
                        "modules": modules}]}


def test_busy_is_the_union_not_the_sum():
    t = _hand()
    assert trace_reduce.busy_intervals(t["planes"][0]["ops"]) == \
        [(0.0, 15.0), (30.0, 35.0), (100.0, 110.0)]
    assert trace_reduce.busy_seconds(t) == [pytest.approx(30e-9)]


def test_gaps_are_named_by_the_program_before_them():
    gaps = trace_reduce.idle_gaps(_hand())
    assert gaps[0][1] == pytest.approx(65e-9)
    assert gaps[0][0].startswith("after_jit_a_1")
    assert gaps[1][1] == pytest.approx(15e-9)


def test_kernel_time_by_stable_name_and_top_ops():
    t = _hand()
    assert trace_reduce.kernel_seconds(t, ["paged_attention_decode"]) == \
        [pytest.approx(5e-9)]
    top = trace_reduce.top_ops(t)
    assert top[0][0].startswith("copy.1_bf16_4_x1_in_jit_a")
    assert sum(sec for _n, sec in top) == pytest.approx(35e-9)


def test_self_time_leaves_out_nested_ops():
    ops = [["while.9 s32[]", 0.0, 100.0], ["fusion.1 f32[2]", 10.0, 30.0],
           ["fusion.2 f32[2]", 50.0, 40.0], ["copy.3 f32[2]", 120.0, 5.0]]
    assert trace_reduce.self_times(ops) == [30.0, 30.0, 40.0, 5.0]
    t = {"planes": [{"name": "/device:TPU:0", "ops": ops, "modules": []}]}
    assert trace_reduce.top_ops(t)[0][0].startswith("fusion.2")


def test_recorded_sample():
    with open(os.path.join(HERE, "trace_sample.json")) as f:
        t = json.load(f)
    s = trace_reduce.summary(dict(t, window_s=1.0))
    assert len(s["busy_s_per_chip"]) == 1
    assert 0 < s["busy_s"] < 1e-3
    k = trace_reduce.kernel_seconds(t, ["paged_attention_decode"])[0]
    assert 0 < k <= s["busy_s"]
    assert s["device_ops"][0][0].startswith("paged_attention_decode")
    assert all(len(n) <= 64 for n, _ in s["device_ops"] + s["idle_gaps"])
