"""The Mellum 2 cell's own pieces: its metric files and its readers on
hand-made records where the answer is known (nothing when the counters or
the gauge are absent — the parent's program), the configuration file, and
the runner rehearsed at the tiny size against the reference, with every
control."""

import json

import pytest

from perfbench import harness, run as prun
from perfbench.readers import counter_share, value
from perfbench.tests import tiny, tiny_mellum

CELL = "code-assist-96k"


def test_the_window_metrics_read_the_new_counters_and_gauge():
    rec = {"counters": {
        "start": {"serving.kv.window_prefix_hits_total": 2.0},
        "end": {"serving.kv.window_prefix_hits_total": 20.0,
                "serving.kv.window_prefix_misses_total": 2.0}},
        "values": {"window_boundary_pages_peak": 51.0}}
    hit = harness.load_json("layer_metrics", "window_prefix_hit_share.json")
    peak = harness.load_json("layer_metrics", "window_boundary_pages_peak.json")
    assert counter_share.read(rec, **hit["args"]) == pytest.approx(0.9)
    assert value.read(rec, **peak["args"]) == 51.0
    # the parent's program has neither: nothing to read, nothing raised
    old = {"counters": {"start": {}, "end": {}}, "values": {}}
    assert counter_share.read(old, **hit["args"]) is None
    assert value.read(old, **peak["args"]) is None


def test_the_cells_metric_files_resolve():
    manifest = json.load(open(harness.HERE + "/../BENCHMARK.json"))
    names = [m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())]
    for new in ("window_prefix_hit_share", "window_boundary_pages_peak",
                "moe_experts_held_roofline", "moe_experts_touched_share",
                "paged_attention_kinds_roofline",
                "kv_window_pages_per_slot_peak", "decode_step_p50_ms",
                "setup_trace_s", "device_idle_share.serve"):
        assert new in names
    for absent in ("moe_experts_roofline", "paged_attention_gated_roofline",
                   "paged_attention_decode_roofline", "ttft_p90_ms"):
        assert absent not in names
    ends = [m["name"] for m in manifest["end_to_end"]
            if CELL in m.get("workloads", (CELL,))]
    assert ends == ["tpot_p50_ms", "setup_s"]
    conf = harness.load_json("configs", "mellum2-12b-serve-1c.json")
    record = {"spans": [], "values": {}, "requests": [], "trace": None,
              "peaks": None, "window": [0.0, 1.0],
              "counters": {"start": {}, "end": {}}, "model": conf}
    assert prun.read_metrics(manifest, "per_layer", CELL, record) == {}


def test_the_configuration_cuts_depth_alone():
    conf = harness.load_json("configs", "mellum2-12b-serve-1c.json")
    assert conf["runner"] == "serve_open_loop_mellum"
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == 4 and len(conf["layer_types"]) == 28
    assert conf["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert (conf["hidden_size"], conf["moe_intermediate_size"],
            conf["num_experts"], conf["vocab_size"]) == (2304, 896, 64, 98304)
    dep = conf["serve"]
    assert dep["max_len"] == 98304 + 2048 + 512
    assert dep["max_len"] % dep["page_size"] == 0
    traffic = harness.load_json("traffic", "code_assist_mixed_96k.json")
    assert traffic["session"]["doc_lens"] == [0, 32768, 98304]
    # the boundaries' spacing is fitted to this traffic's documents: a
    # follow-up shares up to the snapshot's end, a kept boundary
    assert all(d % dep["window_boundary_tokens"] == 0
               for d in traffic["session"]["doc_lens"])


@pytest.fixture(scope="module")
def rehearsal():
    from perfbench.runners import serve_open_loop_mellum as runner
    return runner, tiny.ctx(tiny_mellum.SERVE, tiny_mellum.SESSIONS,
                            trace=1, workload="tiny-mellum")


def test_the_runner_rehearsed_at_the_tiny_size_is_correct(rehearsal,
                                                          monkeypatch):
    from paddle_tpu import observability as obs
    from perfbench.runners import serve_open_loop_mellum as runner_mod
    runner, ctx = rehearsal
    samples = []
    real = runner_mod._window_sample

    def spy(*a, **k):
        samples.append(real(*a, **k))
        return samples[-1]
    monkeypatch.setattr(runner_mod, "_window_sample", spy)
    rec = runner.run(ctx)
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] > 0
    # the window's own requests were held to the reference: chat turns and
    # a follow-up over a document, their first tokens each
    (sample,) = samples
    assert sample["correct"] and {d > 0 for _, d, _ in sample["requests"]} \
        == {False, True}
    assert sample["tokens"] == 4 * len(sample["requests"])
    names = {e["name"] for e in rec["spans"]}
    assert {"serving.moe.decode", "serving.moe.prefill",
            "serving.kv.window_keep"} <= names
    assert rec["values"]["window_boundary_pages_peak"] > 0
    assert rec["values"]["kv_window_pages_per_slot_peak"] <= 8 // 4 + 2
    # the check's follow-up shared its document through a kept boundary
    assert obs.snapshot()["serving.kv.window_prefix_hits_total"] >= 1


def test_every_control_goes_through_the_check_at_the_tiny_size(
        rehearsal, monkeypatch):
    """The sound reference first (it decides ``correct``), then every
    control: each comparison is computed and logged. ``no_yarn`` moves the
    full layer's stored keys past the limit already here; that
    ``fp8_weights`` reads ``correct: false`` is the chip's to show (PERF.md
    section 2): at this size float32 meets float32."""
    from perfbench import reference_mellum as reference
    from perfbench.runners import serve_open_loop_mellum as runner_mod
    runner, ctx = rehearsal
    seen = []
    real = runner_mod._compare

    def spy(*a, **k):
        out = real(*a, **k)
        seen.append(out)
        return out
    monkeypatch.setattr(runner_mod, "_compare", spy)
    monkeypatch.setenv("PERFBENCH_CHECK_CONTROL", "none,fp8_weights,no_yarn")
    assert runner.run(ctx)["correct"]
    assert [o["control"] for o in seen] == ["", "fp8_weights", "no_yarn"]
    assert seen[2]["cache_err"] > reference.SERVE_CACHE_TOL_MEL
    assert not seen[2]["correct"]
