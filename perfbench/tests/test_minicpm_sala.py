"""The MiniCPM-SALA cell's own pieces: its readers on hand-made records
where the answer is known (a roofline of exactly 100% when the time equals
the need, nothing when the kernel, the instants or the counters are absent
— the parent's program), its metric files, and the runner rehearsed at the
tiny size against the reference."""

import json

import pytest

from perfbench import harness, run as prun
from perfbench.readers import (counter_share, event_attr_ratio,
                               linear_state_decode_roofline,
                               sparse_attention_decode_roofline)
from perfbench.tests import tiny, tiny_minicpm_sala

PEAKS = {"peak_flops": 100e12, "hbm_bw_bytes": 1e12}
MODEL = {"num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 100,
         "lightning_nh": 10, "lightning_head_dim": 50,
         "mixer_types": ["minicpm4", "lightning-attn", "minicpm4"],
         "serve": {"page_size": 50, "kv_dtype": "bf16"}}
CELL = "repo-agent-64k"


def _trace(ops, t0=100.0, window_s=2.0):
    return {"planes": [{"name": "/device:TPU:0", "modules": [],
                        "ops": [[n, 0.0, s * 1e9] for n, s in ops]}],
            "t0": t0, "window_s": window_s}


def _instant(name, ts, **attrs):
    return {"kind": "i", "name": name, "ts": ts, "attrs": attrs}


def test_sparse_attention_decode_roofline_is_100_when_time_equals_need():
    spans = [_instant("serving.sparse.decode", 100.5, rows=2,
                      pages_resident=900, pages_read=128),
             _instant("serving.sparse.decode", 103.0, rows=2,
                      pages_resident=900, pages_read=999)]
    # 128 pages (over rows, KV heads and layers) x 50 tokens, K and V of
    # 100, 2 bytes
    need_s = 2 * 100 * (128 * 50) * 2 / 1e12
    rec = {"trace": _trace([("sparse_attention_decode.3 f32[8]", need_s),
                            ("paged_attention_decode f32[8]", 1.0)]),
           "peaks": PEAKS, "spans": spans, "model": MODEL}
    args = dict(kernel="sparse_attention_decode",
                event="serving.sparse.decode")
    assert sparse_attention_decode_roofline.read(rec, **args) == \
        pytest.approx(100.0)
    for gone in (dict(rec, spans=[]), dict(rec, trace=None),
                 dict(rec, trace=_trace([("fusion.1 f32[8]", 1.0)]))):
        assert sparse_attention_decode_roofline.read(gone, **args) is None
    gone = dict(rec)
    del gone["spans"]                      # a record with no program spans
    assert sparse_attention_decode_roofline.read(gone, **args) is None


def test_linear_state_decode_roofline_is_100_when_time_equals_need():
    spans = [_instant("serving.linear.decode", 100.5, rows=3, layers=6),
             _instant("serving.linear.decode", 99.0, rows=30, layers=6)]
    need_s = 18 * 2 * (10 * 50 * 50) * 4 / 1e12
    rec = {"trace": _trace([("linear_state_decode f32[8]", need_s / 2),
                            ("linear_state_decode.7 f32[8]", need_s / 2)]),
           "peaks": PEAKS, "spans": spans, "model": MODEL}
    args = dict(kernel="linear_state_decode", event="serving.linear.decode")
    assert linear_state_decode_roofline.read(rec, **args) == \
        pytest.approx(100.0)
    for gone in (dict(rec, spans=[]), dict(rec, trace={"planes": []}),
                 dict(rec, trace=_trace([("fusion.1 f32[8]", 1.0)]))):
        assert linear_state_decode_roofline.read(gone, **args) is None


def test_event_attr_ratio_and_counter_share():
    spans = [_instant("serving.sparse.decode", 10.5, pages_read=64,
                      pages_resident=512),
             _instant("serving.sparse.decode", 11.0, pages_read=128,
                      pages_resident=1024),
             _instant("serving.sparse.decode", 99.0, pages_read=1,
                      pages_resident=1)]
    rec = {"window": [10.0, 12.0], "spans": spans}
    args = dict(event="serving.sparse.decode", num="pages_read",
                den="pages_resident")
    assert event_attr_ratio.read(rec, **args) == pytest.approx(0.125)
    assert event_attr_ratio.read(dict(rec, spans=[]), **args) is None
    hits, misses = ("serving.state.snapshot_hits_total",
                    "serving.state.snapshot_misses_total")
    rec = {"counters": {"start": {hits: 10.0}, "end": {hits: 19.0,
                                                       misses: 1.0}}}
    assert counter_share.read(rec, num=hits, rest=[misses]) == \
        pytest.approx(0.9)
    rec["counters"]["end"].pop(misses)     # never incremented: counts as 0
    assert counter_share.read(rec, num=hits, rest=[misses]) == 1.0
    # the parent's program has neither counter
    assert counter_share.read({"counters": {"start": {}, "end": {}}},
                              num=hits, rest=[misses]) is None


def test_the_cells_metric_files_resolve():
    manifest = json.load(open(harness.HERE + "/../BENCHMARK.json"))
    names = [m["name"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())]
    for new in ("sparse_attention_decode_roofline",
                "linear_state_decode_roofline", "sparse_pages_read_share",
                "state_snapshot_hit_share", "decode_step_p50_ms",
                "decode_ahead_share"):
        assert new in names
    for absent in ("moe_experts_roofline", "paged_attention_kinds_roofline",
                   "kv_window_pages_per_slot_peak", "ttft_p90_ms"):
        assert absent not in names
    ends = [m["name"] for m in manifest["end_to_end"]
            if CELL in m.get("workloads", (CELL,))]
    assert ends == ["tpot_p50_ms", "setup_s"]
    record = {"spans": [], "values": {}, "requests": [], "trace": None,
              "peaks": None, "window": [0.0, 1.0],
              "counters": {"start": {}, "end": {}}, "model": MODEL}
    # every reader of the cell answers (here: with nothing) and none raises
    assert prun.read_metrics(manifest, "per_layer", CELL, record) == {}
    conf = harness.load_json("configs", "minicpm-sala-serve-1c.json")
    traffic = harness.load_json("traffic", "agent_sessions_64k.json")
    assert conf["runner"] == "serve_open_loop_sala"
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert conf["mixer_types"] == \
        conf["serve"]["mixer_types_published"][9:17]
    assert traffic["session"]["doc_lens"] == [16384, 32768, 65536]


def test_the_runner_rehearsed_at_the_tiny_size_is_correct():
    from perfbench.runners import serve_open_loop_sala as runner
    rec = runner.run(tiny.ctx(tiny_minicpm_sala.SERVE,
                              tiny_minicpm_sala.SESSIONS, trace=1,
                              workload="tiny-sala"))
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] > 0
    names = {e["name"] for e in rec["spans"]}
    assert {"serving.sparse.decode", "serving.linear.decode",
            "serving.state.snapshot", "serving.state.restore"} <= names
    hit = counter_share.read(
        rec, num="serving.state.snapshot_hits_total",
        rest=["serving.state.snapshot_misses_total"])
    assert hit is not None and hit > 0.5
    share = event_attr_ratio.read(
        rec, event="serving.sparse.decode", num="pages_read",
        den="pages_resident")
    assert 0.0 < share < 1.0               # blocks were really dropped
