"""The Command A+ cell's own pieces: its three readers on hand-made records
where the answer is known (a roofline of exactly 100% when the time equals
the need, nothing when the kernel is absent), its metric files, and the
runner rehearsed at the tiny size against the reference."""

import json

import pytest

from perfbench import harness, run as prun
from perfbench.readers import (counter_max_over_mean, moe_experts_roofline,
                               paged_attention_kinds_roofline)
from perfbench.tests import tiny, tiny_cohere2_moe

PEAKS = {"peak_flops": 100e12, "hbm_bw_bytes": 1e12}
EVENTS = ["serving.moe.decode", "serving.moe.prefill"]
MODEL = {"hidden_size": 1000, "intermediate_size": 500, "head_dim": 50,
         "num_attention_heads": 40, "num_key_value_heads": 10,
         "num_hidden_layers": 4, "sliding_window": 100,
         "layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 5,
         "serve": {"dtype": "bfloat16", "kv_dtype": "bf16"}}


def _trace(ops, t0=100.0, window_s=2.0):
    return {"planes": [{"name": "/device:TPU:0", "modules": [],
                        "ops": [[n, 0.0, s * 1e9] for n, s in ops]}],
            "t0": t0, "window_s": window_s}


def _instant(name, ts, **attrs):
    return {"kind": "i", "name": name, "ts": ts, "attrs": attrs}


def test_moe_experts_roofline_is_100_when_time_equals_need():
    # 10 rows on 4 expert touches in the slice (an instant outside it is
    # not counted): bytes = (4 * 3 * 1000 * 500 + 10 * 3 * 1500) * 2
    spans = [_instant("serving.moe.decode", 100.5, rows=6,
                      experts_touched=3, batch=2),
             _instant("serving.moe.prefill", 101.0, rows=4,
                      experts_touched=1, batch=1),
             _instant("serving.moe.decode", 103.0, rows=99,
                      experts_touched=9, batch=2)]
    need_s = (4 * 3 * 1000 * 500 + 10 * 3 * 1500) * 2 / 1e12
    rec = {"trace": _trace([("ragged-dot-none.3 f32[8,1000]", need_s / 2),
                            ("ragged-dot-none f32[8,500]", need_s / 2),
                            ("fusion.7 bf16[8]", 1.0)]),
           "peaks": PEAKS, "spans": spans, "model": MODEL}
    assert moe_experts_roofline.read(rec, kernel="ragged-dot", events=EVENTS) == \
        pytest.approx(100.0)
    # FLOPs-bound once the rows are many: 6 * 1000 * 500 a row
    spans[0]["attrs"]["rows"] = 10 ** 6
    flops_s = (10 ** 6 + 4) * 6 * 1000 * 500 / 100e12
    rec["trace"] = _trace([("ragged-dot-none f32[8,500]", flops_s)])
    assert moe_experts_roofline.read(rec, kernel="ragged-dot", events=EVENTS) == \
        pytest.approx(100.0)


def test_moe_experts_roofline_reads_nothing_without_the_kernel():
    rec = {"trace": _trace([("fusion.7 bf16[8]", 1.0)]), "peaks": PEAKS,
           "spans": [_instant("serving.moe.decode", 100.5, rows=6,
                              experts_touched=3, batch=2)], "model": MODEL}
    assert moe_experts_roofline.read(rec, kernel="ragged-dot", events=EVENTS) is None
    assert moe_experts_roofline.read(
        dict(rec, trace=None), kernel="ragged-dot", events=EVENTS) is None
    # the parent's program has the kernel's name nowhere and no instants
    rec = dict(rec, trace=_trace([("ragged-dot-none f32[8]", 1.0)]), spans=[])
    assert moe_experts_roofline.read(rec, kernel="ragged-dot", events=EVENTS) is None


def test_paged_attention_kinds_roofline_is_100_when_time_equals_need():
    # one request, prompt 150: tokens 1 and 2 arrive in the slice as decode
    # rows with contexts 151 and 152. Three sliding layers read
    # min(t, 100) = 100 each, the one full layer t: 451 + 452 tokens
    requests = [{"prompt_len": 150, "tokens": [99.0, 100.5, 101.0, 103.0]}]
    tokens = 3 * 100 + 151 + 3 * 100 + 152
    need_s = 2 * 10 * 50 * tokens * 2 / 1e12
    rec = {"trace": _trace([("paged_attention_decode.4 f32[8]", need_s)]),
           "peaks": PEAKS, "requests": requests, "model": MODEL}
    assert paged_attention_kinds_roofline.read(
        rec, kernel="paged_attention_decode") == pytest.approx(100.0)
    # without the window's bound the same time would read above 100%
    flat = dict(MODEL, sliding_window=10 ** 6)
    assert paged_attention_kinds_roofline.read(
        dict(rec, model=flat), kernel="paged_attention_decode") > 100.0


def test_paged_attention_kinds_roofline_reads_nothing_without_the_kernel():
    rec = {"trace": _trace([("fusion.1 f32[8]", 1.0)]), "peaks": PEAKS,
           "requests": [], "model": MODEL}
    assert paged_attention_kinds_roofline.read(
        rec, kernel="paged_attention_decode") is None
    assert paged_attention_kinds_roofline.read(
        dict(rec, trace={"planes": []}), kernel="paged_attention_decode") \
        is None


def test_counter_max_over_mean():
    name = "serving.moe.rows_by_expert_total"
    rec = {"counters": {
        "start": {name: {"expert=0,layer=0": 10.0, "expert=1,layer=0": 5.0}},
        "end": {name: {"expert=0,layer=0": 40.0, "expert=1,layer=0": 15.0,
                       "expert=2,layer=0": 20.0}}}}
    # growth 30, 10, 20: the busiest over the mean of 20
    assert counter_max_over_mean.read(rec, counter=name) == pytest.approx(1.5)
    assert counter_max_over_mean.read(
        {"counters": {"start": {}, "end": {}}}, counter=name) is None
    assert counter_max_over_mean.read(
        {"counters": {"start": rec["counters"]["end"],
                      "end": rec["counters"]["end"]}}, counter=name) is None


def test_the_cells_metric_files_resolve():
    manifest = json.load(open(harness.HERE + "/../BENCHMARK.json"))
    cell = "longdoc-sessions"
    names = [m["name"] for m in manifest["per_layer"]
             if cell in m.get("workloads", ())]
    assert "paged_attention_decode_roofline" not in names
    for new in ("moe_experts_roofline", "paged_attention_kinds_roofline",
                "moe_rows_per_step_p50", "moe_load_max_over_mean",
                "kv_window_pages_per_slot_peak"):
        assert new in names
        spec = harness.load_json("layer_metrics", new + ".json")
        assert spec["name"] == new and spec["reader"]
    conf = harness.load_json("configs", "command-a-plus-serve-1c.json")
    assert conf["runner"] == "serve_open_loop_moe"
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]


def test_the_runner_serves_the_tiny_configuration_against_the_reference():
    """``serve_open_loop_moe`` end to end on the CPU: the reference check
    (a full prefill and the shared-prefix tail of one document, both past
    the window), then sessions through the front door; the new metrics
    read from its record."""
    from perfbench.runners import serve_open_loop_moe as runner
    from perfbench import reference_cohere2_moe as reference
    runner_doc = (runner.CHECK_DOC, runner.CHECK_QUESTION,
                  runner.CHECK_BESIDE_TOKENS, reference.ROUTER_MARGIN_MIN)
    # at hidden 64 and std 0.02 every router score lies within 0.01 of 0.5
    reference.ROUTER_MARGIN_MIN = 1e-4
    runner.CHECK_DOC, runner.CHECK_QUESTION = 24, 4
    runner.CHECK_BESIDE_TOKENS = 60     # max_len 96: 28 + 60, and they must
                                        # outlast the two checked requests
    try:
        rec = runner.run(tiny.ctx(tiny_cohere2_moe.SERVE,
                                  tiny_cohere2_moe.SESSIONS, seconds=2.0,
                                  trace=1, workload="tiny-moe"))
    finally:
        (runner.CHECK_DOC, runner.CHECK_QUESTION, runner.CHECK_BESIDE_TOKENS,
         reference.ROUTER_MARGIN_MIN) = runner_doc
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] > 0
    assert rec["values"]["kv_window_pages_per_slot_peak"] <= 8 // 4 + 2
    manifest = json.load(open(harness.HERE + "/../BENCHMARK.json"))
    got = prun.read_metrics(manifest, "per_layer", "longdoc-sessions", rec)
    assert got["moe_rows_per_step_p50"]["value"] > 0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert "prefill_computed_share" not in got     # moves itl_p95_ms
    assert "moe_experts_roofline" not in got       # no device trace here


def _random_reference(seed=0, e=64, f=32, heads=8, kv=2, d=16, vocab=96,
                      experts=8, held=4, shared=2):
    """Reference weights at the published widths' scale (h W has std 1.28,
    as std 0.02 gives at hidden 4096), so logits and router scores spread
    as they do on the chip."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(0, 1.28 / e ** 0.5, shape), jnp.float32)
    layers = [dict(norm=jnp.ones(e), q=n(e, heads * d), k=n(e, kv * d),
                   v=n(e, kv * d), o=n(heads * d, e), router=n(e, experts),
                   gate=n(held, e, f), up=n(held, e, f), down=n(held, f, e),
                   shared_gate=n(e, shared * f), shared_up=n(e, shared * f),
                   shared_down=n(shared * f, e)) for _ in range(4)]
    cfg = dict(tiny_cohere2_moe.MODEL, num_experts=experts,
               layer_types=tiny_cohere2_moe.MODEL["layer_types"][:4])
    return dict(embed=n(vocab, e), norm=jnp.ones(e), layers=layers), cfg


@pytest.mark.parametrize("control", ["fp8_weights", "int8_kv", "bf16_router"])
def test_a_control_is_the_reference_a_precision_lower(control):
    """Each control moves the logits and leaves them finite; float8 weights
    also choose other tokens than the exact reference does, which is what
    the harness's switch shows as ``correct: false`` on the chip."""
    import jax.numpy as jnp
    import numpy as np
    from perfbench import reference_cohere2_moe as reference
    params, cfg = _random_reference()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 96, 40), jnp.int32)
    held = range(2, 6)
    exact = np.asarray(reference.logits(params, ids, cfg, held))
    low = np.asarray(reference.logits(params, ids, dict(cfg, control=control),
                                      held))
    assert np.all(np.isfinite(low)) and np.abs(low - exact).max() > 1e-4
    gaps, margin = reference.chosen_logit_gaps(
        params, ids, 24, jnp.asarray(exact[23:39].argmax(-1), jnp.int32),
        dict(cfg, control=control), held)
    assert gaps.shape == margin.shape == (16,)
    if control == "fp8_weights":
        assert float(np.asarray(gaps).max()) > 0.0
    gaps, _ = reference.chosen_logit_gaps(
        params, ids, 24, jnp.asarray(exact[23:39].argmax(-1), jnp.int32), cfg,
        held)
    assert float(np.asarray(gaps).max()) == 0.0     # its own tokens


def test_router_margin_counts_only_experts_held_here():
    import jax.numpy as jnp
    import numpy as np
    from perfbench import reference_cohere2_moe as reference
    params, cfg = _random_reference()
    h = jnp.asarray(np.random.default_rng(2).normal(size=(32, 64)),
                    jnp.float32)
    router = params["layers"][0]["router"]
    every = np.asarray(reference.router_margin(h, router, cfg, range(8)))
    none = np.asarray(reference.router_margin(h, router, cfg, []))
    some = np.asarray(reference.router_margin(h, router, cfg, range(2, 6)))
    assert np.all(np.isfinite(every)) and np.all(every >= 0)
    assert np.all(np.isinf(none))
    assert np.all((some == every) | np.isinf(some))
    assert np.isinf(some).any() and np.isfinite(some).any()
