"""Benchmark: Llama decoder train-step throughput on the available device.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric of record (BASELINE.json): tokens/sec/chip on a Llama-2-style decoder.
A single TPU v5 lite chip cannot hold 7B for training, so the bench runs
1.59B params at seq 4096 using the reduced-footprint optimizer (int8
block-quantized moments via the fused Pallas update, master-weight-free
bf16 params with stochastic rounding; ~4 bytes/param of state),
scan-over-layers and activation recompute. ``vs_baseline`` is
achieved-MFU / 0.45 (the A100-class MFU target recorded in BASELINE.md —
the reference published no numbers).

On a CPU the script runs a seconds-long smoke of the same code path under
its own metric name (``llama_train_cpu_smoke_tokens_per_sec``): a CPU rate
is never printed under the chip's metric, and carries no MFU.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def smoke() -> None:
    """On-chip regression surface beyond the headline number: run every
    example entry point (the five BASELINE configs) on the real device and
    report one JSON line. ``python bench.py --smoke``."""
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    cases = [
        ("train_resnet.py", ["--steps", "2", "--batch", "8",
                             "--image-size", "32", "--arch", "resnet18"]),
        ("finetune_bert.py", ["--steps", "2"]),
        ("train_ppyoloe.py", ["--steps", "1", "--image-size", "64"]),
        ("train_llama_hybrid.py", ["--dp", "1", "--mp", "1", "--steps", "2"]),
        ("train_deepfm.py", ["--steps", "2", "--batch", "32"]),
    ]
    # this parent never imports jax: each child gets the chip in turn
    env = dict(os.environ)
    results = {}
    ok = True
    for script, args in cases:
        t0 = time.perf_counter()
        try:
            out = subprocess.run(
                [sys.executable, os.path.join(root, "examples", script),
                 *args],
                capture_output=True, text=True, timeout=900, env=env,
                cwd=root)
            passed = out.returncode == 0 and "loss" in out.stdout
        except subprocess.TimeoutExpired:
            out = None
            passed = False
        ok = ok and passed
        results[script] = {"ok": passed,
                           "secs": round(time.perf_counter() - t0, 1)}
        if not passed:
            results[script]["tail"] = "timeout" if out is None else \
                (out.stdout + out.stderr)[-400:]
    print(json.dumps({"metric": "examples_on_chip_smoke",
                      "value": sum(r["ok"] for r in results.values()),
                      "unit": "examples_passing", "vs_baseline": 1.0 if ok
                      else 0.0, "detail": results}))
    sys.exit(0 if ok else 1)


TELEMETRY_FIELDS = ("dispatch.ops_total", "jit.traces_total",
                    "jit.compiles_total", "jit.cache_hits_total",
                    "jit.graph_breaks_total")

# training-under-fire counters (ISSUE 10): the claim of record is that a
# healthy bench run needed NONE of the recovery machinery — every field
# zero. A diff showing nonzero here means the measured run itself
# retried, skipped, rolled back, or tripped the watchdog.
TRAIN_RESILIENCE_FIELDS = ("retries", "restarts", "skipped_batches",
                           "watchdog_trips")

# whole-step capture counters (ISSUE 11): the row of record pins that the
# measured steps actually ran as ONE compiled donated-buffer program —
# hits > 0 with zero bypasses on a healthy run. A run whose every step
# bypassed capture measured the eager debug tier and must read as suspect.
STEP_CAPTURE_FIELDS = ("mode", "hits", "retraces", "bypasses",
                       "donated_bytes")

# tracing overhead (ISSUE 12): the flight recorder is ALWAYS on, so its
# cost on the captured hot path is part of every number of record — the
# row pins the captured-step p50 with tracing off vs flight-recorder-only
# vs fully on, and >2% flight-vs-off delta disqualifies the run.
TRACE_OVERHEAD_FIELDS = ("step_ms_p50_off", "step_ms_p50_flight",
                         "step_ms_p50_on", "flight_overhead_pct",
                         "on_overhead_pct")
_TRACE_OVERHEAD_MAX_PCT = 2.0


def _counter_total(snap: dict, name: str) -> int:
    """Sum a counter family out of a snapshot: unlabeled families are a
    plain number, labeled ones a {'k=v': value} dict."""
    v = snap.get(name, 0)
    if isinstance(v, dict):
        return int(sum(v.values()))
    return int(v)


def _train_resilience_detail(snap: dict) -> dict:
    """Select the train.* recovery counters; schema pinned by
    TRAIN_RESILIENCE_FIELDS (all fields always present, zeros included)."""
    return {
        "retries": _counter_total(snap, "train.retries_total"),
        "restarts": _counter_total(snap, "train.restarts_total"),
        "skipped_batches": _counter_total(snap,
                                          "train.skipped_batches_total"),
        "watchdog_trips": _counter_total(snap,
                                         "train.watchdog_trips_total"),
    }


def _step_capture_detail(snap: dict, mode: str) -> dict:
    """Select the train.capture_* counters; schema pinned by
    STEP_CAPTURE_FIELDS (all fields always present, zeros included)."""
    return {
        "mode": mode,
        "hits": _counter_total(snap, "train.capture_hits_total"),
        "retraces": _counter_total(snap, "train.capture_retraces_total"),
        "bypasses": _counter_total(snap, "train.capture_bypasses_total"),
        "donated_bytes": int(snap.get("train.capture_donated_bytes", 0)),
    }


def _capture_suspect_reasons(cap: dict) -> list[str]:
    """Why the capture block disqualifies this run ([] = healthy): a run
    whose steps ran the per-op eager tier — capture off (e.g. the test
    suite's PADDLE_TPU_STEP_CAPTURE=off inherited into the bench env), or
    every step bypassed — measured a structurally different (and ~8x
    slower) program than the number of record claims."""
    if cap["mode"] == "off":
        return ["step capture disabled (PADDLE_TPU_STEP_CAPTURE=off): the "
                "run measured the eager debug tier, not the compiled step"]
    if cap["hits"] == 0 and cap["bypasses"] > 0:
        return [f"step capture enabled but all {cap['bypasses']} steps "
                "bypassed to the eager tier (train.capture_bypasses_total "
                "has the reasons)"]
    return []


def _trace_overhead_detail(off_p50: float, flight_p50: float,
                           on_p50: float) -> dict:
    """Build the pinned trace_overhead block (schema:
    TRACE_OVERHEAD_FIELDS) from the three measured per-step p50s (ms)."""
    def pct(x: float) -> float:
        return round(100.0 * (x - off_p50) / off_p50, 2) if off_p50 else 0.0

    return {
        "step_ms_p50_off": round(off_p50, 3),
        "step_ms_p50_flight": round(flight_p50, 3),
        "step_ms_p50_on": round(on_p50, 3),
        "flight_overhead_pct": pct(flight_p50),
        "on_overhead_pct": pct(on_p50),
    }


def _trace_suspect_reasons(block: dict) -> list[str]:
    """Why the trace_overhead block disqualifies this run ([] = healthy):
    the always-on flight recorder must be near-free on the captured hot
    path — a >2% p50 delta vs tracing-off means every number of record is
    quietly paying for observability. (Full 'on' mode is an opt-in debug
    tier; its cost is reported but not gated.)"""
    if block["flight_overhead_pct"] > _TRACE_OVERHEAD_MAX_PCT:
        return [f"flight-recorder-only tracing cost "
                f"{block['flight_overhead_pct']}% of the off-mode step "
                f"p50 (> {_TRACE_OVERHEAD_MAX_PCT}% budget)"]
    return []


def _telemetry_detail(snap: dict) -> dict:
    """Select the bench-relevant counters out of an observability snapshot.

    Every field in ``TELEMETRY_FIELDS`` is always present (0 when never
    bumped) so BENCH JSON rows stay schema-stable across rounds."""
    return {k: int(snap.get(k, 0)) for k in TELEMETRY_FIELDS}


# program cost accounting (ISSUE 16): the row of record carries XLA's own
# cost/memory analysis of the measured step program — flops and bytes as
# the compiler modeled them, the modeled MFU recomputed from the measured
# per-call step time, and the HBM ledger's peak/headroom. model_source
# records whether XLA's cost model or the analytic flops counter produced
# the figure; an all-null cost block means the registry never saw the
# measured program and the MFU claim has no model behind it.
COST_FIELDS = ("model_source", "step_flops", "step_bytes", "mfu_modeled",
               "peak_hbm_bytes", "hbm_headroom_bytes")


def _cost_detail(doc: dict, analytic_step_flops: float,
                 step_seconds: float, peak_flops: float) -> dict:
    """Build the pinned cost block (schema: COST_FIELDS) from one
    ``cost.debug_doc()`` snapshot plus the measured per-CALL seconds of
    the captured step program (the same program the train.step record
    describes — both cover ``scan_k`` scanned steps).

    Prefers the XLA-measured train.step record; falls back to the analytic
    estimate (model_source="analytic") when the compiler returned no cost
    model, and to all-null (model_source="none") when the registry never
    saw the step program at all."""
    rec = None
    for r in doc.get("records", []):
        if r.get("site") == "train.step":
            rec = r
            break
    flops = rec.get("flops") if rec else None
    nbytes = rec.get("bytes_accessed") if rec else None
    source = rec.get("model_source") if rec else None
    if flops is None and analytic_step_flops:
        flops, source = float(analytic_step_flops), "analytic"
    mfu_modeled = None
    if flops and step_seconds and peak_flops:
        mfu_modeled = round(flops / (step_seconds * peak_flops), 4)
    hbm = doc.get("hbm") or {}
    out = {
        "model_source": source or "none",
        "step_flops": flops,
        "step_bytes": nbytes,
        "mfu_modeled": mfu_modeled,
        "peak_hbm_bytes": hbm.get("peak_hbm_bytes"),
        "hbm_headroom_bytes": hbm.get("headroom_bytes"),
    }
    assert set(out) == set(COST_FIELDS)
    return out


def _cost_suspect_reasons(block: dict) -> list[str]:
    """Why the cost block disqualifies this run ([] = healthy): an
    entirely empty cost accounting means the registry never captured the
    measured program AND the analytic fallback was unavailable — the MFU
    of record has no cost model behind it."""
    if (block["step_flops"] is None and block["step_bytes"] is None
            and block["peak_hbm_bytes"] is None):
        return ["cost accounting empty: no program record and no analytic "
                "fallback (PADDLE_TPU_COST=off inherited into the bench "
                "env?)"]
    return []


def main() -> None:
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import cost as _cost_mod

    # dispatch/compile telemetry rides along in the JSON: per-op dispatch
    # cost inside the timed loop is one counter bump + histogram insert,
    # noise next to the ~seconds-scale compiled steps being measured
    obs.enable()

    device = paddle.device.describe()
    on_tpu = device["platform"] == "tpu"

    if on_tpu:
        # 1.59B params at batch 6 on one 16GB v5e — enabled by int8 m/v
        # (fused Pallas update) + master-free bf16 AdamW (~4 B/param state)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2560,
                          intermediate_size=6912, num_hidden_layers=18,
                          num_attention_heads=20, num_key_value_heads=20,
                          max_position_embeddings=4096,
                          scan_layers=True, recompute=True)
        # int8 moments (the fused Pallas update) free ~3GB vs bf16 state, so
        # batch 6 fits (rate against batch size: not measured on today's
        # code); 24 steps = 6 timed calls, enough samples for p50/p90
        batch, seq, steps, scan_k = 6, 4096, 24, 4
        metric = "llama_train_tokens_per_sec_per_chip"
        # the one peaks table; a chip it does not know is an error
        peak_flops = _cost_mod.device_peaks(device["kind"])["peak_flops"]
    elif device["platform"] == "cpu":
        # seconds-long smoke of the same code path, under its own name
        cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=4,
                               kv_heads=4, inter=256, max_pos=256)
        batch, seq, steps, scan_k = 4, 128, 4, 2
        metric = "llama_train_cpu_smoke_tokens_per_sec"
        peak_flops = None  # no device peak: a CPU run carries no MFU
    else:
        raise SystemExit(f"bench.py: no configuration for platform "
                         f"{device['platform']!r}")

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    # big scan-stacked params: on TPU the int8-state update runs as ONE
    # fused Pallas kernel per param (ops/q8_adam_pallas.py); the
    # master-free bf16 write-back uses stochastic rounding
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 use_multi_tensor=not on_tpu,
                                 moment_dtype="int8" if on_tpu else "float32",
                                 use_master_weights=False if on_tpu else None)
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16", master_weight=False)

    # whole-step static capture (ISSUE 11): the train step — fwd, bwd,
    # optimizer update — is ONE donated-buffer compiled program, scanned
    # over scan_k steps per call (the standard TPU trainer pattern —
    # amortizes per-dispatch overhead); the body fn stays a plain per-step
    # train step, and train.capture_* counters ride into the row of record
    cap_mode = paddle.core.step_capture.mode()

    def train_step_body(ids):
        with paddle.amp.auto_cast(enable=on_tpu, level="O2", dtype="bfloat16"):
            loss, _ = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.capture_step(train_step_body,
                                         iters_per_call=scan_k)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                        (scan_k, batch, seq), dtype=np.int32))

    # warmup / compile (twice: a second call would catch any lazy-state
    # retrace, so the timed loop never eats a recompile). The first call's
    # wall time is the compile+first-run split the JSON reports.
    t0 = time.perf_counter()
    loss = train_step(ids)
    _ = np.asarray(loss._data)
    compile_s = time.perf_counter() - t0
    loss = train_step(ids)
    _ = np.asarray(loss._data)
    steps_run = (steps // scan_k) * scan_k  # what the timed loop executes

    def timed_loop():
        """One timed pass; returns (tok/s, per-call ms list, final loss)."""
        call_ms = []
        nonlocal_loss = None
        t_all = time.perf_counter()
        for _ in range(steps_run // scan_k):
            t0 = time.perf_counter()
            nonlocal_loss = train_step(ids)
            _ = np.asarray(nonlocal_loss._data)  # per-call sync: honest
            call_ms.append((time.perf_counter() - t0) * 1e3)
        dt = time.perf_counter() - t_all
        return (batch * seq * steps_run) / dt, call_ms, nonlocal_loss

    tok_per_sec, call_ms, loss = timed_loop()
    suspect_reasons = []

    loss = loss[-1]  # last step's loss for reporting
    flops_per_token = model.flops_per_token(seq)
    mfu = tok_per_sec * flops_per_token / peak_flops if peak_flops else None

    # warm-start compile: drop the in-memory executable cache and rebuild
    # the SAME program — the re-lower now deserializes from the persistent
    # compilation cache instead of re-running XLA, which is what a fleet
    # rollout / crash-restart (PR 8/10 recovery) pays. compile_s stays the
    # cold number of record; the cold-vs-warm delta is the pinned win.
    compile_warm_s = None
    if paddle.compile_cache_dir():
        jax.clear_caches()
        t0 = time.perf_counter()
        _w = train_step(ids)
        _ = np.asarray(_w._data)
        compile_warm_s = round(time.perf_counter() - t0, 1)

    # tracing overhead (ISSUE 12): re-run the SAME compiled executable a
    # few calls per trace mode — spans/ring writes are host-side only, so
    # no retrace — and pin the per-step p50 deltas. Restore the ambient
    # mode afterwards so the block never perturbs later measurement.
    from paddle_tpu.observability import trace as _trace_mod

    def _p50_under_mode(m: str) -> float:
        _trace_mod.set_mode(m)
        ms = []
        for _ in range(max(3, steps_run // scan_k)):
            t0 = time.perf_counter()
            l_ = train_step(ids)
            _ = np.asarray(l_._data)
            ms.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(ms, 50)) / scan_k

    ambient_trace_mode = _trace_mod.mode()
    try:
        trace_block = _trace_overhead_detail(
            _p50_under_mode("off"), _p50_under_mode("flight"),
            _p50_under_mode("on"))
    finally:
        _trace_mod.set_mode(ambient_trace_mode)
    # CPU runs are shared-core CI smoke: sub-ms jitter there routinely
    # exceeds 2% and is not a capture-integrity signal
    if on_tpu:
        suspect_reasons = suspect_reasons + _trace_suspect_reasons(
            trace_block)

    out = {
        "metric": metric,
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4) if mfu is not None else None,
        "detail": {
            "device": device, "params": model.num_params(),
            "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
            "batch": batch, "seq": seq, "steps": steps_run,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "final_loss": round(float(loss), 4),
            "step_ms_p50": round(float(np.percentile(call_ms, 50)) / scan_k, 1),
            "step_ms_p90": round(float(np.percentile(call_ms, 90)) / scan_k, 1),
            "compile_s": round(compile_s, 1),
            "compile_warm_s": compile_warm_s,
        },
    }
    # one snapshot feeds every counter block: the row of record must not
    # mix two points in time (schemas pinned by TRAIN_RESILIENCE_FIELDS /
    # STEP_CAPTURE_FIELDS in test_bench_selfdefense)
    snap = obs.snapshot()
    out["detail"]["telemetry"] = _telemetry_detail(snap)
    out["detail"]["train_resilience"] = _train_resilience_detail(snap)
    cap_detail = _step_capture_detail(snap, cap_mode)
    out["detail"]["step_capture"] = cap_detail
    out["detail"]["trace_overhead"] = trace_block
    # cost accounting (ISSUE 16): one debug_doc() snapshot, same point in
    # time as `snap`; the step program's record joins the measured per-call
    # p50 into the modeled MFU (both cover one scan_k-step call)
    cost_detail = _cost_detail(
        _cost_mod.debug_doc(),
        flops_per_token * batch * seq * scan_k,
        float(np.percentile(call_ms, 50)) / 1e3,
        peak_flops)
    out["detail"]["cost"] = cost_detail
    suspect_reasons = suspect_reasons + _capture_suspect_reasons(cap_detail)
    suspect_reasons = suspect_reasons + _cost_suspect_reasons(cost_detail)
    if suspect_reasons:
        out["suspect"] = True
        out["detail"]["suspect_reasons"] = suspect_reasons
    print(json.dumps(out))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()
