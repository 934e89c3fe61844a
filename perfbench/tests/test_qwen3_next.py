"""The Qwen3-Next cell's own pieces: its readers on hand-made records where
the answer is known (a roofline of exactly 100% when the time equals the
need, nothing when the kernel, the instants or the keys are absent — the
parent's program), its metric files, and the runner rehearsed at the tiny
size against the reference, with every control."""

import json

import pytest

from perfbench import harness, run as prun
from perfbench.readers import (counter_share, event_attr_ratio,
                               gated_delta_decode_roofline,
                               moe_experts_held_roofline,
                               paged_attention_gated_roofline)
from perfbench.tests import tiny, tiny_qwen3_next

PEAKS = {"peak_flops": 100e12, "hbm_bw_bytes": 1e12}
MODEL = {"hidden_size": 200, "intermediate_size": 999,
         "moe_intermediate_size": 50, "linear_num_value_heads": 10,
         "linear_num_key_heads": 5, "linear_key_head_dim": 20,
         "linear_value_head_dim": 30, "linear_conv_kernel_dim": 4,
         "head_dim": 50, "num_attention_heads": 40, "num_key_value_heads": 10,
         "full_attention_interval": 4,
         "serve": {"dtype": "bfloat16", "kv_dtype": "bf16",
                   "layers_run": [0, 1, 2, 3, 4, 5, 6, 7]}}
CELL = "longform-decode"


def _trace(ops, t0=100.0, window_s=2.0):
    return {"planes": [{"name": "/device:TPU:0", "modules": [],
                        "ops": [[n, 0.0, s * 1e9] for n, s in ops]}],
            "t0": t0, "window_s": window_s}


def _instant(name, ts, **attrs):
    return {"kind": "i", "name": name, "ts": ts, "attrs": attrs}


def test_gated_delta_decode_roofline_is_100_when_time_equals_need():
    spans = [_instant("serving.linear.decode", 100.5, rows=3, layers=6),
             _instant("serving.linear.decode", 99.0, rows=30, layers=6)]
    # a row and layer: the state 10 x 20 x 30 and the tail 3 x (2 x 5 x 20 +
    # 10 x 30), float32, read and written
    need_s = 18 * 2 * 4 * (6000 + 3 * 500) / 1e12
    rec = {"trace": _trace([("gated_delta_decode f32[8]", need_s / 2),
                            ("gated_delta_decode_conv.7 f32[8]", need_s / 2),
                            ("linear_state_decode f32[8]", 1.0)]),
           "peaks": PEAKS, "spans": spans, "model": MODEL}
    args = dict(kernel="gated_delta_decode", event="serving.linear.decode")
    assert gated_delta_decode_roofline.read(rec, **args) == \
        pytest.approx(100.0)
    for gone in (dict(rec, spans=[]), dict(rec, trace={"planes": []}),
                 dict(rec, trace=_trace([("fusion.1 f32[8]", 1.0)]))):
        assert gated_delta_decode_roofline.read(gone, **args) is None


def test_paged_attention_gated_roofline_counts_the_attention_layers_only():
    # one request, prompt 150: tokens 1 and 2 arrive in the slice as decode
    # rows with contexts 151 and 152; layers 3 and 7 of the eight are
    # attention, K and V of 10 heads x 50 in bf16
    requests = [{"prompt_len": 150, "tokens": [99.0, 100.5, 101.0, 103.0]}]
    assert paged_attention_gated_roofline.attention_layers(MODEL) == 2
    need_s = 2 * 10 * 50 * (151 + 152) * 2 * 2 / 1e12
    rec = {"trace": _trace([("paged_attention_decode.4 f32[8]", need_s),
                            ("gated_delta_decode f32[8]", 1.0)]),
           "peaks": PEAKS, "requests": requests, "model": MODEL}
    args = dict(kernel="paged_attention_decode")
    assert paged_attention_gated_roofline.read(rec, **args) == \
        pytest.approx(100.0)
    other = dict(MODEL)
    del other["full_attention_interval"]   # another configuration's file
    for gone in (dict(rec, requests=[]), dict(rec, trace={"planes": []}),
                 dict(rec, trace=_trace([("fusion.1 f32[8]", 1.0)])),
                 dict(rec, model=other)):
        assert paged_attention_gated_roofline.read(gone, **args) is None


def test_moe_experts_held_roofline_reads_the_experts_own_width():
    spans = [_instant("serving.moe.decode", 100.5, rows=40,
                      experts_touched=30, experts_held=64, batch=4),
             _instant("serving.moe.prefill", 101.0, rows=10,
                      experts_touched=10, experts_held=64, batch=1),
             _instant("serving.moe.decode", 99.0, rows=999,
                      experts_touched=999, experts_held=64, batch=4)]
    nbytes = (40 * 3 * 200 * 50 + 50 * 3 * 250) * 2
    rec = {"trace": _trace([("ragged-dot.3 bf16[8]", nbytes / 1e12)]),
           "peaks": PEAKS, "spans": spans, "model": MODEL}
    args = dict(kernel="ragged-dot",
                events=["serving.moe.decode", "serving.moe.prefill"])
    assert moe_experts_held_roofline.read(rec, **args) == \
        pytest.approx(100.0)
    other = dict(MODEL)
    del other["moe_intermediate_size"]     # another configuration's file
    for gone in (dict(rec, spans=[]), dict(rec, trace=None),
                 dict(rec, model=other)):
        assert moe_experts_held_roofline.read(gone, **args) is None
    share = event_attr_ratio.read(
        {"window": [100.0, 102.0], "spans": spans},
        event="serving.moe.decode", num="experts_touched",
        den="experts_held")
    assert share == pytest.approx(30 / 64)
    # the parent's instants carry no experts_held: nothing to read
    old = [_instant("serving.moe.decode", 100.5, rows=40, experts_touched=30,
                    batch=4)]
    assert event_attr_ratio.read(
        {"window": [100.0, 102.0], "spans": old}, event="serving.moe.decode",
        num="experts_touched", den="experts_held") is None


def test_the_cells_metric_files_resolve():
    manifest = json.load(open(harness.HERE + "/../BENCHMARK.json"))
    names = [m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())]
    for new in ("gated_delta_decode_roofline", "moe_experts_held_roofline",
                "moe_experts_touched_share", "paged_attention_gated_roofline",
                "state_snapshot_hit_share",
                "moe_rows_per_step_p50", "decode_step_p50_ms",
                "decode_ahead_share", "device_idle_share.serve"):
        assert new in names
    for absent in ("moe_experts_roofline", "paged_attention_kinds_roofline",
                   "paged_attention_decode_roofline",
                   "linear_state_decode_roofline", "ttft_p90_ms"):
        assert absent not in names
    ends = [m["name"] for m in manifest["end_to_end"]
            if CELL in m.get("workloads", (CELL,))]
    assert ends == ["tpot_p50_ms", "setup_s"]
    record = {"spans": [], "values": {}, "requests": [], "trace": None,
              "peaks": None, "window": [0.0, 1.0],
              "counters": {"start": {}, "end": {}}, "model": MODEL}
    # every reader of the cell answers (here: with nothing) and none raises
    assert prun.read_metrics(manifest, "per_layer", CELL, record) == {}
    conf = harness.load_json("configs", "qwen3-next-80b-serve-1c.json")
    traffic = harness.load_json("traffic", "longform_answers_2k.json")
    assert conf["runner"] == "serve_open_loop_qwen3_next"
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (8, 128, 37984)
    assert conf["serve"]["experts_held"] == [0, conf["num_experts"]]
    assert conf["serve"]["vocab_held"] == [0, conf["vocab_size"]]
    assert traffic["session"]["doc_lens"] == [2048, 4096, 8192]


@pytest.fixture(scope="module")
def rehearsal():
    from perfbench.runners import serve_open_loop_qwen3_next as runner
    return runner, tiny.ctx(tiny_qwen3_next.SERVE, tiny_qwen3_next.SESSIONS,
                            trace=1, workload="tiny-qwen3-next")


def test_the_runner_rehearsed_at_the_tiny_size_is_correct(rehearsal):
    runner, ctx = rehearsal
    rec = runner.run(ctx)
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] > 0
    names = {e["name"] for e in rec["spans"]}
    assert {"serving.moe.decode", "serving.moe.prefill",
            "serving.linear.decode", "serving.state.snapshot",
            "serving.state.restore"} <= names
    hit = counter_share.read(
        rec, num="serving.state.snapshot_hits_total",
        rest=["serving.state.snapshot_misses_total"])
    assert hit is not None and hit > 0.5
    share = event_attr_ratio.read(
        rec, event="serving.moe.decode", num="experts_touched",
        den="experts_held")
    assert 0.0 < share <= 1.0


def test_every_control_goes_through_the_check_at_the_tiny_size(
        rehearsal, monkeypatch):
    """The sound reference first (it decides ``correct``), then every
    control: each comparison is computed and logged. At this size float32
    meets float32, so the chip's limits tell nothing apart; that each control
    reads ``correct: false`` is the chip's to show (PERF.md section 2)."""
    runner, ctx = rehearsal
    monkeypatch.setenv("PERFBENCH_CHECK_CONTROL",
                       "none,fp8_weights,bf16_state,no_delta")
    assert runner.run(ctx)["correct"]
