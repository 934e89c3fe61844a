"""Each reader on a hand-made record where the answer is known, and the
manifest's metrics all resolve to a definition file and a reader."""

import importlib
import json
import os

import pytest

from perfbench import harness, run as prun
from perfbench.readers import (counter_ratio, device_idle,
                               flash_attention_roofline,
                               paged_attention_roofline, span_gap_percentile,
                               span_stat, value)

PEAKS = {"peak_flops": 100e12, "hbm_bw_bytes": 1e12}


def _span(kind, name, ts, sid, **attrs):
    return {"kind": kind, "name": name, "ts": ts, "span": sid, "attrs": attrs}


RECORD = {
    "window": [10.0, 20.0],
    "spans": [
        _span("B", "serving.submit", 9.0, 1, rid=1),      # before the window
        _span("B", "serving.submit", 11.0, 2, rid=2),
        _span("B", "serving.prefill", 11.25, 3, rid=2),
        _span("B", "serving.submit", 12.0, 4, rid=3),
        _span("B", "serving.prefill", 12.75, 5, rid=3),
        _span("B", "serving.decode", 13.0, 6, batch=4),
        _span("E", "serving.decode", 13.05, 6),
        _span("B", "serving.decode", 14.0, 7, batch=8),
        _span("E", "serving.decode", 14.07, 7),
        _span("B", "serving.decode", 25.0, 8, batch=16),  # after the window
    ],
    "counters": {"start": {"computed": 100, "requested": 100},
                 "end": {"computed": 130, "requested": 200}},
    "values": {"step_ms": [1.0, 2.0, 9.0], "hbm_peak_bytes": 5e9,
               "slice_steps": 2},
    "peaks": PEAKS,
}


def test_span_readers():
    assert span_gap_percentile.read(
        RECORD, start="serving.submit", end="serving.prefill", key="rid",
        q=50) == pytest.approx(500.0)
    assert span_stat.read(RECORD, span="serving.decode", q=50) == \
        pytest.approx(60.0)
    assert span_stat.read(RECORD, span="serving.decode", attr="batch") == 6.0


def test_counter_and_value_readers():
    assert counter_ratio.read(RECORD, num="computed", den="requested",
                              scale=100.0) == pytest.approx(30.0)
    assert counter_ratio.read(RECORD, num="nope", den="requested") is None
    assert value.read(RECORD, key="step_ms", q=50) == 2.0
    assert value.read(RECORD, key="hbm_peak_bytes", scale=1e-9) == 5.0
    assert value.read(RECORD, key="absent") is None


def _trace(ops, window_s=1.0, t0=100.0):
    return {"window_s": window_s, "t0": t0, "planes": [
        {"name": "/device:TPU:0", "ops": ops, "modules": []}]}


def test_device_idle_and_nothing_to_read():
    rec = dict(RECORD, trace=_trace([["a f32[1]", 0.0, 0.25e9],
                                     ["b f32[1]", 0.5e9, 0.25e9]]))
    assert device_idle.read(rec) == pytest.approx(50.0)
    assert device_idle.read(dict(RECORD, trace=None)) is None


def test_paged_attention_roofline_is_bytes_over_time():
    model = {"hidden_size": 512, "num_attention_heads": 4,
             "num_key_value_heads": 1, "num_hidden_layers": 2,
             "serve": {"kv_dtype": "bf16"}}
    # one request, prompt 100, tokens 1..3 inside the slice: contexts 101+102
    reqs = [{"prompt_len": 100, "tokens": [99.0, 100.2, 100.4, 101.5]}]
    rec = dict(RECORD, model=model, requests=reqs, trace=_trace(
        [["paged_attention_decode.3 f32[4]", 0.0, 1e3]]))
    nbytes = 2 * 1 * 128 * (101 + 102) * 2 * 2
    assert paged_attention_roofline.read(
        rec, kernel="paged_attention_decode") == \
        pytest.approx(nbytes / 1e12 / 1e-6 * 100.0)


def test_flash_roofline_is_flops_over_time():
    model = {"hidden_size": 512, "num_attention_heads": 4,
             "num_key_value_heads": 1, "num_hidden_layers": 2}
    rec = dict(RECORD, model=model, traffic={"seq": 1024, "batch": 1},
               trace=_trace([["flash_fwd_lse.1 f32[4]", 0.0, 2e6],
                             ["flash_bwd_dq.1 f32[4]", 3e6, 1e6],
                             ["fusion.9 f32[4]", 5e6, 1e6]]))
    flops = 6 * (2 * 1024 * 1024 // 2 * 128) * 4 * 1 * 2 * 2
    assert flash_attention_roofline.read(
        rec, kernels=["flash_fwd_lse", "flash_bwd_dq", "flash_bwd_dkv"]) == \
        pytest.approx(flops / 100e12 / 3e-3 * 100.0)


def test_manifest_resolves():
    with open(os.path.join(prun.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    for kind, folder in prun._KIND_DIR.items():
        for m in manifest[kind]:
            spec = harness.load_json(folder, m["name"] + ".json")
            assert spec["unit"] == m["unit"]
            importlib.import_module(f"perfbench.readers.{spec['reader']}")
            assert set(m.get("workloads", cells)) <= cells
            if kind == "per_layer":
                assert spec["layer"] == m["layer"]
                assert spec["moves"] == m["moves"]
    for c in manifest["configs"]:
        conf = json.load(open(os.path.join(prun.ROOT, c["file"])))
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        importlib.import_module(f"perfbench.runners.{conf['runner']}")
    for w in manifest["workloads"]:
        harness.load_json("traffic", w["traffic"] + ".json")
