"""Runner: ``serve_open_loop`` for the ``qwen3_next`` configuration — one of
four chips that share each layer, eight layers (six Gated DeltaNet, two gated
attention) over 128 of 512 experts, behind the same router, front door and
load generator.

    Qwen3NextForCausalLM.serving_callables -> serving.Engine.warmup
        -> serving.Router -> serving.FrontDoor  <- HTTP -  perfbench.loadgen

What differs from ``serve_open_loop`` (whose ``_drive``, ``_post``,
``_settle`` and ``_sweep`` it imports as they are, as it does
``serve_open_loop_sala``'s ``_log_prefills``): the model is built in its
serving dtype as the share ``serve`` names (layers, experts, vocabulary); the
engine keeps one page pool for the attention layers, a state row a slot in
two parts (delta state, convolution tail) for the Gated DeltaNet layers and
snapshots of both at prefix boundaries; ``Engine.warmup`` takes the prefix
tails; the slot count is the largest the chip holds with a tenth of its
memory free; and the reference check sends one 8192-token brief through a
full prefill and through a 256-token tail from its snapshot, 32 new tokens
each, while three other slots decode 512-token answers, then holds the state
and the tail the engine filed at the brief's end to the reference's — all
under ``reference_qwen3_next``'s limits. The reference's own seconds are not
in ``setup_s``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import types
from typing import Dict

import numpy as np

from .. import harness, reference_qwen3_next as reference, schedule, stats
from ..harness import log
from .serve_open_loop import _drive, _post, _settle, _sweep
from .serve_open_loop_sala import _log_prefills

# the reference check's sizes, unless the configuration's ``serve.check``
# names others (the tiny preset): a brief, its prompt, new tokens an ask;
# other slots decoding meanwhile, their briefs, prompts and new tokens
CHECK = {"doc": 8192, "question": 256, "new_tokens": 32, "beside": 3,
         "beside_doc": 2048, "beside_question": 64, "beside_tokens": 512}


def model_config(conf: Dict):
    """The program's config object from the file's published keys and the
    share it runs (``serve.layers_run`` of ``serve.layers_published``,
    ``serve.experts_held`` of ``serve.experts_published``, ...)."""
    import dataclasses

    from paddle_tpu.models.qwen3_next import Qwen3NextConfig
    dep = conf["serve"]
    fields = {f.name for f in dataclasses.fields(Qwen3NextConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(num_hidden_layers=dep["layers_published"],
              layers_run=tuple(dep["layers_run"]),
              num_experts=dep["experts_published"],
              experts_held=tuple(dep["experts_held"]),
              vocab_size=dep["vocab_published"],
              vocab_held=tuple(dep["vocab_held"]), dtype=dep["dtype"])
    return Qwen3NextConfig(**kw)


def reference_config(conf: Dict) -> Dict:
    dep = conf["serve"]
    first, count = dep["experts_held"]
    return reference.reference_config(
        conf, dep["layers_run"], dep["experts_published"],
        range(first, first + count))


def pool_bytes(dep: Dict, cfg, slots: int) -> Dict[str, float]:
    """What the engine holds for ``slots`` slots, by cache."""
    kinds = cfg.layer_kinds
    pages = slots * (dep["max_len"] // dep["page_size"]) + 1
    page = 2 * cfg.num_key_value_heads * dep["page_size"] * cfg.head_dim * 2
    row = 4 * sum(int(np.prod(s)) for s in cfg.state_shapes)
    return {"pages": page * kinds.count("full") * pages,
            "state": row * kinds.count("linear") * (slots + 1),
            "snapshots": dep["state_snapshot_gb"] * 1e9}


def pick_slots(dep: Dict, cfg, weights_bytes: int, limit_bytes: int) -> int:
    """The largest slot count tried whose caches leave ``hbm_free_share`` of
    the chip free beside the weights and the prefill's workspace."""
    for slots in dep["slots_tried"]:
        parts = pool_bytes(dep, cfg, slots)
        total = weights_bytes + sum(parts.values()) \
            + dep["workspace_gb"] * 1e9
        log(f"slots {slots}: " + ", ".join(
            f"{k} {v / 1e9:.2f}" for k, v in parts.items())
            + f" GB; with weights and workspace {total / 1e9:.2f} of "
            f"{limit_bytes / 1e9:.2f} GB")
        if total <= (1.0 - dep["hbm_free_share"]) * limit_bytes:
            return slots
    raise SystemExit("perfbench: no slot count tried fits this chip")


def serving_config(dep: Dict, cfg, slots: int, name: str):
    from paddle_tpu import serving
    return serving.ServingConfig(
        num_layers=len(cfg.layers_run), num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=dep["max_len"], name=name,
        max_batch=slots, buckets=tuple(b for b in dep["buckets"]
                                       if b < slots) + (slots,),
        page_size=dep["page_size"], compute_dtype=dep["dtype"],
        kv_dtype=dep["kv_dtype"], max_queue=dep["max_queue"],
        layer_kinds=cfg.layer_kinds, state_shape=cfg.state_shapes,
        state_snapshot_tokens=dep["state_snapshot_tokens"],
        state_snapshot_bytes=int(dep["state_snapshot_gb"] * 1e9))


def _scale_mixer_out(model, scale: float) -> None:
    """The benchmark's weights, not the model's: every matrix is drawn at
    std 0.02, and the mixers' output projections are then scaled by
    ``serve.o_proj_init_scale`` (1.0: nothing is)."""
    if scale == 1.0:
        return
    for layer in model.layers:
        w = layer.o_proj if layer.full else layer.out_proj
        w._set_data((w._data.astype("float32") * scale).astype(w._data.dtype))


def _check(port: int, engine, model, ref_conf: Dict, seed: int,
           sizes: Dict) -> Dict:
    """One seeded brief through the front door twice — a full prefill, then
    the tail from the snapshot at the brief's end — while ``sizes["beside"]``
    other requests decode long answers in other slots, against the reference
    under ``reference_qwen3_next``'s limits; then the delta state and the
    convolution tail the engine filed at the brief's end (float32, from its
    snapshot store) against the reference's own after the same tokens.
    ``PERFBENCH_CHECK_CONTROL`` names ``reference.CONTROLS``
    (comma-separated; ``none`` is the sound reference) to compare against
    the reference computed a precision lower, or with part of its
    mathematics left out, instead — the builder's switch for the second
    reading a limit is set from; the driver never sets it. Every comparison
    named is logged; the first one's is the run's."""
    controls = [c for c in os.environ.get("PERFBENCH_CHECK_CONTROL", ""
                                          ).split(",") if c] or ["none"]
    if set(controls) - set(reference.CONTROLS) - {"none"}:
        raise SystemExit(f"perfbench: PERFBENCH_CHECK_CONTROL {controls}: "
                         f"not among {reference.CONTROLS}")
    vocab = model.config.vocab_held[1]
    n_beside, question = sizes["beside"], sizes["question"]
    rng = np.random.default_rng([seed, 4])
    doc = rng.integers(0, vocab, sizes["doc"])
    plen = sizes["doc"] + question
    beside_len = sizes["beside_doc"] + sizes["beside_question"]
    others = []
    beside = [threading.Thread(
        target=lambda p: others.append(_post(port, p,
                                             sizes["beside_tokens"])),
        daemon=True, args=(rng.integers(0, vocab, beside_len),))
        for _ in range(n_beside)]
    before = engine.prefill_token_stats()
    for th in beside:
        th.start()
    end = time.monotonic() + 120.0
    while engine.active_requests < n_beside and time.monotonic() < end:
        time.sleep(0.05)
    asked = []
    for _ in ("full prefill", "snapshot tail"):
        prompt = np.concatenate([doc, rng.integers(0, vocab, question)])
        asked.append((prompt, _post(port, prompt, sizes["new_tokens"])))
    still_beside = sum(th.is_alive() for th in beside)
    log(f"reference check: asked twice with {still_beside} decoding beside")
    for th in beside:
        th.join(timeout=300)
    req, comp = (a - b for a, b in zip(engine.prefill_token_stats(), before))
    # every compiled call of the engine donates the weights and rebinds
    # them: take them only while the step thread is idle
    _settle(engine)
    from paddle_tpu.serving import kv_cache
    kept = engine.snapshots.get_parts(kv_cache.prefix_chain_digests(
        asked[0][0], engine.config.page_size,
        limit=sizes["doc"] // engine.config.page_size)[-1])
    kept = None if kept is None else tuple(np.asarray(a) for a in kept)
    params = reference.params_of(model)
    seen = {"decoding_beside": still_beside,
            "beside_distinct_last_64": [len(set(t[-64:])) for t in others],
            "prefill_tokens_computed": comp, "prefill_tokens_requested": req,
            "distinct_tokens": len({t for _, toks in asked for t in toks})}
    # the second request must have been a tail from the snapshot, and the
    # others must have been decoding beside both
    saw = still_beside == n_beside and comp == \
        n_beside * beside_len + plen + question
    outs = []
    for control in controls:
        out = dict(_compare(params, asked, plen, sizes, dict(
            ref_conf, control="" if control == "none" else control), kept),
            **seen)
        out["correct"] = out["correct"] and saw
        log("reference check:", json.dumps(out))
        outs.append(out)
    return dict(outs[0], reference_s=sum(o["reference_s"] for o in outs))


def _compare(params, asked, plen: int, sizes: Dict, ref_conf: Dict,
             kept) -> Dict:
    """What the engine chose in ``asked`` [(prompt, tokens)] and the state
    and tail it ``kept`` at the brief's end against the reference as
    ``ref_conf`` has it (``control``: a precision lower). ``reference_s``:
    the seconds the reference itself took."""
    import jax

    fn = jax.jit(lambda p, i, n, a: reference.answer_rows(
        p, i, n, a, ref_conf, sizes["doc"]))
    refs, gaps, reference_s = [], [], 0.0
    for prompt, tokens in asked:
        ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        t0 = time.monotonic()
        ref = {k: np.asarray(v) for k, v in fn(
            params, ids, np.int32(plen), np.asarray(tokens, np.int32)
        ).items()}
        reference_s += time.monotonic() - t0
        ok = len(tokens) == sizes["new_tokens"] \
            and np.all(np.isfinite(ref["gap"]))
        log(f"reference check: {len(ids)} tokens through the reference")
        gaps.append(ref["gap"] if ok else np.full(len(tokens), np.inf))
        refs.append(ref)
    gap = np.concatenate(gaps)
    margin = np.concatenate([r["margin"] for r in refs])
    steady = reference.steady(margin)
    worst = float(gap[steady].max()) if steady.any() else 0.0
    agree, n = int((gap == 0).sum()), int(gap.size)
    # the state and the tail kept at the brief's end against the
    # reference's: the worst head's distance, what the state's own rounding
    # adds, the worst layer's tail
    first = refs[0]
    by_head, state_err, rounding, tail_err = [[np.inf]], np.inf, np.inf, \
        np.inf
    if kept is not None and kept[0].shape == first["states"].shape:
        by_head, state_err, rounding = reference.state_distance(
            kept[0], first["states"],
            np.stack([np.asarray(p["A_log"], np.float32)
                      for p in params["layers"] if "A_log" in p]))
        tail_err = reference.tail_distance(kept[1], first["tails"])
    return {"control": ref_conf["control"], "max_gap_steady": worst,
            "steady": int(steady.sum()), "router_ties": int((~steady).sum()),
            "agreeing_steady": int((gap[steady] == 0).sum()),
            "max_gap_all": float(gap.max()),
            "gap_full_prefill": float(gaps[0].max()),
            "gap_snapshot_tail": float(gaps[1].max()),
            "tokens_agreeing": agree, "tokens": n,
            "tolerance": reference.SERVE_LOGIT_TOL_Q3N,
            "min_steady": reference.SERVE_MIN_STEADY_Q3N,
            "min_agreeing": reference.SERVE_MIN_AGREEING_Q3N,
            "router_margin": reference.ROUTER_MARGIN_MIN_Q3N,
            "state_err": float(state_err),
            "state_tolerance": reference.SERVE_STATE_TOL_Q3N,
            "state_err_first": float(np.max(by_head[0])),
            "state_first_tolerance": reference.SERVE_STATE_FIRST_TOL_Q3N,
            "state_rounding": float(rounding),
            "state_rounding_tolerance":
            reference.SERVE_STATE_ROUNDING_TOL_Q3N,
            "tail_err": float(tail_err),
            "tail_tolerance": reference.SERVE_TAIL_TOL_Q3N,
            "state_err_by_head": [[round(float(x), 5) for x in row]
                                  for row in by_head],
            "gaps": [round(float(x), 4) for x in gap],
            "margins": [round(float(x), 4) for x in np.minimum(margin, 9.0)],
            "reference_s": reference_s,
            "correct": bool(worst <= reference.SERVE_LOGIT_TOL_Q3N
                            and steady.sum() >= reference.SERVE_MIN_STEADY_Q3N
                            and agree >= reference.SERVE_MIN_AGREEING_Q3N * n
                            and state_err <= reference.SERVE_STATE_TOL_Q3N
                            and np.max(by_head[0])
                            <= reference.SERVE_STATE_FIRST_TOL_Q3N
                            and rounding
                            <= reference.SERVE_STATE_ROUNDING_TOL_Q3N
                            and tail_err <= reference.SERVE_TAIL_TOL_Q3N)}


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.qwen3_next import Qwen3NextForCausalLM
    from paddle_tpu.observability import trace as ptrace

    compiles = harness.CompileCounter()
    obs.enable()
    tracing = bool(ctx["trace"]) or bool(ctx.get("sweep"))
    if tracing:
        ptrace.set_mode("on")          # the program's spans, traced run only
    dep = conf["serve"]
    cfg = model_config(conf)
    paddle.seed(harness.fold_seed(seed))
    model = Qwen3NextForCausalLM(cfg)      # in its serving dtype
    _scale_mixer_out(model, dep["o_proj_init_scale"])
    model.eval()
    harness.device_barrier()
    st = jax.devices()[0].memory_stats() or {}
    slots = pick_slots(dep, cfg, int(st.get("bytes_in_use", 0)),
                       int(st.get("bytes_limit", 0)) or 2 ** 62)
    engine = serving.Engine(*model.serving_callables(
        dep["max_len"], block=dep["state_snapshot_tokens"]),
        serving_config(dep, cfg, slots, "r0"))
    log(f"built: {model.num_params():,} parameters, {slots} slots, decode "
        f"tier {engine._paged_path}, pool {tuple(engine.kv.pool.shape)}, "
        f"state parts {[p.shape for p in engine.state.parts]}; "
        f"{harness.hbm_line()}")

    vocab = cfg.vocab_held[1]
    requests = schedule.fill(schedule.plan(traffic, seconds), seed, vocab)
    shapes = schedule.prompt_shapes(requests)
    # every shape the traffic file can ask for, not only this plan's: a
    # sweep at another rate draws other briefs
    sizes = dict(CHECK, **dep.get("check", {}))
    tails = {(d, q) for d in traffic["session"]["doc_lens"]
             for q in traffic["prompt_lens"] if d} \
        | {(sizes["doc"], sizes["question"])}
    lens = {d + q for d, q in tails} | set(shapes["prompt_lens"]) \
        | {sizes["beside_doc"] + sizes["beside_question"]}
    engine.warmup(prompt_lens=sorted(lens), tails=sorted(tails))
    log(f"warmup returned: {harness.hbm_line()}")
    harness.device_barrier()
    log(f"warmup ran: {harness.hbm_line()}")
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        check = _check(fd.port, engine, model, reference_config(conf), seed,
                       sizes)
        log(f"warm: {compiles.count} backend compiles; {harness.hbm_line()}; "
            f"plan {len(requests)} requests, shapes {json.dumps(shapes)}")
        if ctx.get("sweep"):
            # _sweep reads the vocabulary size of a model config and no more
            _sweep(ctx, engine, fd.port,
                   types.SimpleNamespace(vocab_size=vocab), compiles)
            return {"sweep": True}
        rec = _drive(engine, fd.port, requests, traffic, seconds,
                     ctx["workload"], compiles, bool(ctx["trace"]), chips)
    finally:
        try:
            router.stop(drain=True, timeout=30)
        except Exception as exc:                  # reported, not fatal
            log(f"router.stop: {type(exc).__name__}: {exc}")
        fd.close()
    # what the cell's metric list cannot carry since it reports no
    # itl_p95_ms (PERF.md section 4): said here, for the builder's record
    ends = rec["counters"]
    grown = {k: ends["end"].get(k, 0) - ends["start"].get(k, 0)
             for k in ("prefill_tokens_computed", "prefill_tokens_requested",
                       "serving.state.snapshot_hits_total",
                       "serving.state.snapshot_misses_total",
                       "serving.state.snapshot_evictions_total",
                       "serving.moe.rows_total",
                       "serving.moe.experts_touched_total")}
    log(f"window: compiles_in_window {rec['values']['compiles_in_window']}, "
        f"{json.dumps(grown)}, snapshots kept {len(engine.snapshots)} "
        f"({engine.snapshots.nbytes / 1e9:.2f} GB); {harness.hbm_line()}")
    rec["spans"] = ptrace.events() if tracing else []
    _log_prefills(rec["spans"], dep["page_size"])
    counted = stats.counted(rec["requests"])
    log("counted requests: " + json.dumps({
        f"{name}_p{q}": stats.percentile(stats.quantity(counted, name), q)
        for name, q in (("ttft_ms", 50), ("ttft_ms", 90), ("tpot_ms", 50),
                        ("late_ms", 99))}))
    # the reference's own forwards are the yardstick's time, not the
    # program's set-up
    rec["values"]["setup_s"] = rec["window"][0] - ctx["t_start"] \
        - check["reference_s"]
    log(f"set-up {rec['values']['setup_s']:.1f} s without the reference's "
        f"{check['reference_s']:.1f} s")
    rec["values"]["slots"] = slots
    rec.update(correct=check["correct"],
               attempted=len(stats.counted(rec["requests"])),
               failed=stats.failed_count(rec["requests"]),
               model=conf, peaks=dev["peaks"], device=dev["device"])
    return rec
