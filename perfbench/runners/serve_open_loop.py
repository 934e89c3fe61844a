"""Runner: one engine behind the router and the front door, loaded as an
open loop by a child process.

    model.serving_callables -> serving.Engine.warmup -> serving.Router
        -> serving.FrontDoor  <- HTTP, loopback -  perfbench.loadgen (child)

The parent (this process) holds the chip. Set-up builds the model from
``--seed``, warms exactly the shapes the cell's plan uses (the decode
buckets, its full prompt lengths, its prefix-tail programs), checks a seeded
sample against ``reference.py``, then starts the child. The child's offset
zero is ``T0``; the window is ``[T0 + lead_in, T0 + lead_in + seconds)`` and
the lead-in counts as set-up.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List

import numpy as np

from .. import harness, reference, schedule, stats
from ..harness import log

CHECK_REQUESTS, CHECK_NEW_TOKENS = 3, 16
SLICE_S = 3.0                      # the profiler's slice, mid-window


def _post(port: int, prompt, new_tokens: int) -> List[int]:
    """One unary ``POST /v1/generate`` -> the tokens."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps({
            "prompt": [int(t) for t in prompt],
            "max_new_tokens": new_tokens, "stream": False}).encode())
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"front door answered {resp.status}: {raw[:300]!r}")
    return json.loads(raw)["tokens"]


def _sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - time.monotonic()))


def _warm_tails(engine, tails) -> None:
    """``Engine.warmup`` builds no prefix-tail program, so build and load one
    per (prefix, tail) pair of the plan the way it builds the full prefills:
    through the engine's own builder, against the scratch page, before any
    request runs. (Warmed by real requests after the router had started, the
    first tail program failed to load on the chip — PERF.md, Open questions.
    Until ``warmup`` takes tails, this reaches into the engine.)"""
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    slots = engine.kv.config.pages_per_slot
    for doc_len, tail_len in tails:
        engine._tail_program(doc_len)(
            Tensor(jnp.zeros((1, tail_len), jnp.int32)),
            Tensor(jnp.zeros((slots,), jnp.int32)),
            Tensor(jnp.asarray(doc_len + tail_len, jnp.int32)),
            Tensor(engine.kv.pool), *engine._scales_args())


def _check(port: int, engine, model, conf: Dict, prompt_lens, seed: int
           ) -> Dict:
    """A seeded sample through the front door against the reference: every
    token the engine chose must lie within ``SERVE_LOGIT_TOL`` of the
    reference's largest logit there. One jitted function, one length."""
    import jax

    fn = jax.jit(lambda p, i, n, a: reference.chosen_logit_gaps(p, i, n, a,
                                                                conf))
    lens = prompt_lens[:CHECK_REQUESTS]
    padded = max(lens) + CHECK_NEW_TOKENS - 1
    worst, agree, n = 0.0, 0, 0
    for i, plen in enumerate(lens):
        prompt = np.random.default_rng([seed, 4, i]).integers(
            0, conf["vocab_size"], plen)
        tokens = _post(port, prompt, CHECK_NEW_TOKENS)
        # every compiled call of the engine donates the weights and rebinds
        # them: take them only while the step thread is idle
        _settle(engine)
        ids = np.zeros(padded, np.int32)
        ids[:plen + len(tokens) - 1] = np.concatenate([prompt, tokens[:-1]])
        gaps = np.asarray(fn(harness.reference_params(model), ids,
                             np.int32(plen), np.asarray(tokens, np.int32)))
        ok = len(tokens) == CHECK_NEW_TOKENS and np.all(np.isfinite(gaps))
        worst = max(worst, float(gaps.max()) if ok else float("inf"))
        agree += int((gaps == 0).sum())
        n += len(tokens)
    out = {"max_logit_gap": worst, "tolerance": reference.SERVE_LOGIT_TOL,
           "tokens_agreeing": agree, "tokens": n,
           "correct": bool(worst <= reference.SERVE_LOGIT_TOL)}
    log("reference check:", json.dumps(out))
    return out


def _drive(engine, port: int, requests: List[Dict], traffic: Dict,
           seconds: float, tag: str, compiles, trace: bool, chips: int
           ) -> Dict:
    """Start the child on ``requests``, cut the window at its instants, wait
    for it. Returns the client stamps and what the parent saw."""
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    sched = os.path.join(harness.OUT_DIR, f"{tag}.schedule.json")
    out = os.path.join(harness.OUT_DIR, f"{tag}.stamps.json")
    lead_in = float(traffic["lead_in_s"])
    with open(sched, "w") as f:
        json.dump({"requests": requests, "window_end": lead_in + seconds,
                   "drain_limit_s": float(traffic["drain_limit_s"])}, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "perfbench.loadgen", sched, str(port), out],
        stdout=subprocess.PIPE, text=True, cwd=os.path.dirname(harness.HERE))
    seen: Dict = {}
    try:
        t0 = float(child.stdout.readline().split()[1])
        w0, w1 = t0 + lead_in, t0 + lead_in + seconds
        prof = harness.ProfilerSlice(tag) if trace else None

        def cut() -> None:
            _sleep_until(w0)
            seen["start"] = harness.snapshot_counters(engine)
            seen["compiles0"] = compiles.count
            if prof is not None:
                length = min(SLICE_S, seconds / 2)
                _sleep_until(w0 + (seconds - length) / 2)
                prof.start()
                _sleep_until(prof.t0 + length)
                prof.stop()
            _sleep_until(w1)
            seen["end"] = harness.snapshot_counters(engine)
            seen["compiles1"] = compiles.count
            seen["hbm_peak_bytes"] = harness.hbm_peak(chips)

        cutter = threading.Thread(target=cut, daemon=True)
        cutter.start()
        rc = child.wait(timeout=lead_in + seconds
                        + float(traffic["drain_limit_s"]) + 60)
        cutter.join(timeout=60)
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out) as f:
        stamps = json.load(f)["requests"]
    return {"requests": stamps, "window": [w0, w1],
            "counters": {"start": seen["start"], "end": seen["end"]},
            "values": {"compiles_in_window":
                       seen["compiles1"] - seen["compiles0"],
                       "hbm_peak_bytes": seen["hbm_peak_bytes"]},
            "trace": prof.load() if prof is not None else None}


def _settle(engine, limit_s: float = 60.0) -> None:
    """Until the engine holds no request (abandoned lead-out streams are
    cancelled by the front door as their sockets close)."""
    end = time.monotonic() + limit_s
    while (engine.active_requests or engine.queue_depth) \
            and time.monotonic() < end:
        time.sleep(0.05)


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.observability import trace as ptrace

    compiles = harness.CompileCounter()
    obs.enable()
    tracing = bool(ctx["trace"]) or bool(ctx.get("sweep"))
    if tracing:
        ptrace.set_mode("on")          # the program's spans, traced run only
    dep = conf["serve"]
    cfg = harness.llama_config(conf, scan_layers=False, recompute=False)
    paddle.seed(harness.fold_seed(seed))
    model = LlamaForCausalLM(cfg)
    model.to(dtype=dep["dtype"])
    model.eval()
    prefill_fn, step_fn = model.serving_callables(dep["max_len"])
    engine = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=cfg.num_key_value_heads,
        head_dim=cfg.hidden_size // cfg.num_attention_heads,
        max_len=dep["max_len"], name="r0", max_batch=dep["slots"],
        buckets=tuple(dep["buckets"]), page_size=dep["page_size"],
        num_pages=dep["num_pages"], compute_dtype=dep["dtype"],
        kv_dtype=dep["kv_dtype"], max_queue=dep["max_queue"]))
    log(f"built: {model.num_params():,} parameters, decode tier "
        f"{engine._paged_path}; {harness.hbm_line()}")

    requests = schedule.fill(schedule.plan(traffic, seconds), seed,
                             cfg.vocab_size)
    shapes = schedule.prompt_shapes(requests)
    engine.warmup(prompt_lens=shapes["prompt_lens"])
    _warm_tails(engine, shapes["tails"])
    log(f"warmup returned: {harness.hbm_line()}")
    # warmup enqueues every program without reading a result back, and each
    # holds a pool-sized output until it has run
    harness.device_barrier()
    log(f"warmup ran: {harness.hbm_line()}")
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        check = _check(fd.port, engine, model, conf, shapes["prompt_lens"], seed)
        log(f"warm: {compiles.count} backend compiles; {harness.hbm_line()}; "
            f"plan {len(requests)} requests, shapes {json.dumps(shapes)}")
        if ctx.get("sweep"):
            _sweep(ctx, engine, fd.port, cfg, compiles)
            return {"sweep": True}
        rec = _drive(engine, fd.port, requests, traffic, seconds,
                     ctx["workload"], compiles, bool(ctx["trace"]), chips)
    finally:
        try:
            router.stop(drain=True, timeout=30)
        except Exception as exc:                  # reported, not fatal
            log(f"router.stop: {type(exc).__name__}: {exc}")
        fd.close()
    rec["spans"] = ptrace.events() if tracing else []
    rec["values"]["setup_s"] = rec["window"][0] - ctx["t_start"]
    rec.update(correct=check["correct"],
               attempted=len(stats.counted(rec["requests"])),
               failed=stats.failed_count(rec["requests"]),
               model=conf, peaks=dev["peaks"], device=dev["device"])
    return rec


def _sweep(ctx, engine, port, cfg, compiles) -> None:
    """Find the knee: the cell's traffic at each of the given request rates,
    one set-up for all. Prints a table; no result line."""
    from paddle_tpu.observability import trace as ptrace
    from ..readers import span_gap_percentile

    traffic, seconds = ctx["traffic"], ctx["seconds"]
    for rate in ctx["sweep"]:
        ptrace.clear()
        requests = schedule.fill(
            schedule.plan(dict(traffic, rate_rps=rate), seconds),
            ctx["seed"], cfg.vocab_size)
        rec = _drive(engine, port, requests, traffic, seconds,
                     f"{ctx['workload']}.sweep", compiles, False,
                     ctx["chips"])
        _settle(engine)
        rec["spans"] = ptrace.events()
        w0, w1 = rec["window"]
        mid = (w0 + w1) / 2
        halves = []
        for a, b in ((w0, mid), (mid, w1)):
            part = dict(rec, window=[a, b], requests=[
                dict(r, counted=r["counted"] and a <= r["due"] < b)
                for r in rec["requests"]])
            halves.append({
                "queue_wait_p50_ms": span_gap_percentile.read(
                    part, start="serving.submit", end="serving.prefill",
                    key="rid", q=50),
                "ttft_p50_ms": stats.percentile(
                    stats.quantity(part["requests"], "ttft_ms"), 50)})
        rs = rec["requests"]
        log("SWEEP " + json.dumps({
            "rate_rps": rate, "attempted": len(stats.counted(rs)),
            "failed": stats.failed_count(rs),
            "errors": sorted({r["error"][:400] for r in rs if "error" in r}),
            "first_half": halves[0], "second_half": halves[1],
            "tpot_p50_ms": stats.percentile(stats.quantity(rs, "tpot_ms"), 50),
            "itl_p95_ms": stats.percentile(stats.quantity(rs, "itl_ms"), 95),
            "ttft_p90_ms": stats.percentile(stats.quantity(rs, "ttft_ms"), 90),
            "late_p99_ms": stats.percentile(stats.quantity(rs, "late_ms"), 99),
            "compiles_in_window": rec["values"]["compiles_in_window"]}))
