"""Runner: ``serve_open_loop`` for the ``cohere2_moe`` configuration — one
chip's share of an expert-parallel deployment behind the same router, front
door and load generator.

    Cohere2MoeForCausalLM.serving_callables -> serving.Engine.warmup
        -> serving.Router -> serving.FrontDoor  <- HTTP -  perfbench.loadgen

What differs from ``serve_open_loop`` (whose ``_drive``, ``_post``,
``_settle`` and ``_sweep`` it imports as they are): the model is built in
its serving dtype (a float32 build of 9.46 GB of bf16 weights would not
fit), the engine keeps pages by layer kind, ``Engine.warmup`` takes the
prefix tails, the slot count is the largest the chip holds with a tenth of
its memory free, the seeded weights get an attention output projection
scaled down so that greedy decoding does not repeat one token, and the
reference check sends one document through a full prefill and through the
shared-prefix tail, both past the window, while other slots decode.
"""

from __future__ import annotations

import json
import os
import threading
import time
import types
from typing import Dict

import numpy as np

from .. import harness, reference_cohere2_moe as reference, schedule, stats
from ..harness import log
from .serve_open_loop import _drive, _post, _settle, _sweep

CHECK_DOC, CHECK_QUESTION, CHECK_NEW_TOKENS = 6144, 64, 16
CHECK_BESIDE, CHECK_BESIDE_TOKENS = 3, 192    # other slots, decoding meanwhile


def model_config(conf: Dict):
    """The program's config object from the file's published keys and its
    share (``serve.experts_held`` of ``serve.experts_published``, ...)."""
    import dataclasses

    from paddle_tpu.models.cohere2_moe import Cohere2MoeConfig
    dep = conf["serve"]
    fields = {f.name for f in dataclasses.fields(Cohere2MoeConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(num_experts=dep["experts_published"],
              experts_held=tuple(dep["experts_held"]),
              vocab_size=dep["vocab_published"],
              vocab_held=tuple(dep["vocab_held"]),
              layer_types=tuple(conf["layer_types"]
                                [:conf["num_hidden_layers"]]),
              max_position_embeddings=max(dep["max_len"], 256),
              dtype=dep["dtype"])
    return Cohere2MoeConfig(**kw)


def reference_config(conf: Dict, cfg) -> Dict:
    """``reference_cohere2_moe``'s view: the published keys, the layers run
    and the router's full width."""
    return dict(conf, layer_types=list(cfg.layer_types),
                num_experts=cfg.num_experts)


def pick_slots(dep: Dict, cfg, weights_bytes: int, limit_bytes: int) -> int:
    """The largest slot count tried whose pools leave ``hbm_free_share`` of
    the chip free beside the weights and the prefill's workspace."""
    from paddle_tpu.ops.paged_attention import window_table_pages
    page = 2 * cfg.num_key_value_heads * dep["page_size"] * cfg.head_dim * 2
    per_slot = {"full": dep["max_len"] // dep["page_size"],
                "window": window_table_pages(cfg.sliding_window,
                                             dep["page_size"])}
    for slots in dep["slots_tried"]:
        pools = sum(page * cfg.layer_kinds.count(kind) * (slots * n + 1)
                    for kind, n in per_slot.items())
        total = weights_bytes + pools + dep["workspace_gb"] * 1e9
        log(f"slots {slots}: pools {pools / 1e9:.2f} GB, with weights and "
            f"workspace {total / 1e9:.2f} of {limit_bytes / 1e9:.2f} GB")
        if total <= (1.0 - dep["hbm_free_share"]) * limit_bytes:
            return slots
    raise SystemExit("perfbench: no slot count tried fits this chip")


def _scale_attention_out(model, scale: float) -> None:
    """The benchmark's weights, not the model's: every matrix is drawn at
    std 0.02, and ``o_proj`` is then scaled by ``serve.o_proj_init_scale``
    (the configuration file's ``assumed.weights`` says why: at 0.02 the
    128 x 128 attention outputs outweigh the experts, the next token hardly
    depends on the last, and greedy decoding repeats one token)."""
    if scale == 1.0:
        return
    for layer in model.layers:
        w = layer.o_proj._data
        layer.o_proj._set_data((w.astype("float32") * scale).astype(w.dtype))


def _check(port: int, engine, model, ref_conf: Dict, experts, seed: int
           ) -> Dict:
    """One seeded document through the front door twice — a full prefill,
    then the shared-prefix tail of the same document — while
    ``CHECK_BESIDE`` other documents decode in other slots, against the
    reference under ``reference_cohere2_moe``'s limits (its docstring has
    them and their reasons). Both prompts end past the window, so the band,
    both page kinds, the page release and the tail are all in what is
    compared. ``PERFBENCH_CHECK_CONTROL`` names ``reference.CONTROLS``
    (comma-separated) to compare against the reference computed a precision
    lower instead, one after the other until one is not correct — the
    builder's switch for the second reading a limit is set from; the driver
    never sets it."""
    controls = [c for c in os.environ.get("PERFBENCH_CHECK_CONTROL", ""
                                          ).split(",") if c]
    if set(controls) - set(reference.CONTROLS):
        raise SystemExit(f"perfbench: PERFBENCH_CHECK_CONTROL {controls}: "
                         f"not among {reference.CONTROLS}")
    vocab = model.config.vocab_held[1]
    rng = np.random.default_rng([seed, 4])
    doc = rng.integers(0, vocab, CHECK_DOC)
    plen = CHECK_DOC + CHECK_QUESTION
    others = []
    beside = [threading.Thread(
        target=lambda p: others.append(_post(port, p, CHECK_BESIDE_TOKENS)),
        daemon=True, args=(rng.integers(0, vocab, plen),))
        for _ in range(CHECK_BESIDE)]
    before = engine.prefill_token_stats()
    for th in beside:
        th.start()
    end = time.monotonic() + 60.0
    while engine.active_requests < CHECK_BESIDE and time.monotonic() < end:
        time.sleep(0.05)
    asked = []
    for _ in ("full prefill", "shared-prefix tail"):
        prompt = np.concatenate([doc, rng.integers(0, vocab, CHECK_QUESTION)])
        asked.append((prompt, _post(port, prompt, CHECK_NEW_TOKENS)))
    still_beside = sum(th.is_alive() for th in beside)
    for th in beside:
        th.join(timeout=120)
    req, comp = (a - b for a, b in zip(engine.prefill_token_stats(), before))
    # every compiled call of the engine donates the weights and rebinds
    # them: take them only while the step thread is idle
    _settle(engine)
    params = reference.params_of(model)
    seen = {"decoding_beside": still_beside,
            "beside_distinct_last_64": [len(set(t[-64:])) for t in others],
            "prefill_tokens_computed": comp, "prefill_tokens_requested": req,
            "distinct_tokens": len({t for _, toks in asked for t in toks})}
    # the second request must have been a tail, and the others must have
    # been decoding beside both: else the check did not see what it is
    # there to see
    saw = still_beside == CHECK_BESIDE and \
        comp == (CHECK_BESIDE + 1) * plen + CHECK_QUESTION
    out = {}
    for control in controls or [""]:
        out = dict(_compare(params, asked, plen,
                            dict(ref_conf, control=control), experts), **seen)
        out["correct"] = out["correct"] and saw
        log("reference check:", json.dumps(out))
        if not out["correct"]:
            break
    return out


def _compare(params, asked, plen: int, ref_conf: Dict, experts) -> Dict:
    """What the engine chose in ``asked`` [(prompt, tokens)] against the
    reference as ``ref_conf`` has it (``control``: a precision lower)."""
    import jax

    fn = jax.jit(lambda p, i, n, a: reference.chosen_logit_gaps(
        p, i, n, a, ref_conf, experts))
    gaps, margins = [], []
    for prompt, tokens in asked:
        ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        g, m = fn(params, ids, np.int32(plen), np.asarray(tokens, np.int32))
        ok = len(tokens) == CHECK_NEW_TOKENS and np.all(np.isfinite(g))
        gaps.append(np.asarray(g) if ok else np.full(len(tokens), np.inf))
        margins.append(np.asarray(m))
    gap, margin = np.concatenate(gaps), np.concatenate(margins)
    steady = margin >= reference.ROUTER_MARGIN_MIN
    worst = float(gap[steady].max()) if steady.any() else 0.0
    agree, n = int((gap == 0).sum()), int(gap.size)
    return {"control": ref_conf["control"], "max_gap_steady": worst,
            "steady": int(steady.sum()),
            "agreeing_steady": int((gap[steady] == 0).sum()),
            "max_gap_all": float(gap.max()),
            "gap_full_prefill": float(gaps[0].max()),
            "gap_shared_tail": float(gaps[1].max()),
            "tokens_agreeing": agree, "tokens": n,
            "tolerance": reference.SERVE_LOGIT_TOL_MOE,
            "min_steady": reference.SERVE_MIN_STEADY,
            "min_agreeing": reference.SERVE_MIN_AGREEING_MOE,
            "gaps": [round(float(x), 4) for x in gap],
            "margins": [round(float(x), 4) for x in np.minimum(margin, 9.0)],
            "correct": bool(worst <= reference.SERVE_LOGIT_TOL_MOE
                            and steady.sum() >= reference.SERVE_MIN_STEADY
                            and agree >= reference.SERVE_MIN_AGREEING_MOE * n)}


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.cohere2_moe import Cohere2MoeForCausalLM
    from paddle_tpu.observability import trace as ptrace

    compiles = harness.CompileCounter()
    obs.enable()
    tracing = bool(ctx["trace"]) or bool(ctx.get("sweep"))
    if tracing:
        ptrace.set_mode("on")          # the program's spans, traced run only
    dep = conf["serve"]
    cfg = model_config(conf)
    paddle.seed(harness.fold_seed(seed))
    model = Cohere2MoeForCausalLM(cfg)     # in its serving dtype
    _scale_attention_out(model, dep["o_proj_init_scale"])
    model.eval()
    harness.device_barrier()
    st = jax.devices()[0].memory_stats() or {}
    slots = pick_slots(dep, cfg, int(st.get("bytes_in_use", 0)),
                       int(st.get("bytes_limit", 0)) or 2 ** 62)
    prefill_fn, step_fn = model.serving_callables(dep["max_len"])
    engine = serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=dep["max_len"], name="r0",
        max_batch=slots, buckets=tuple(b for b in dep["buckets"]
                                       if b < slots) + (slots,),
        page_size=dep["page_size"], compute_dtype=dep["dtype"],
        kv_dtype=dep["kv_dtype"], max_queue=dep["max_queue"],
        layer_kinds=cfg.layer_kinds, window=cfg.sliding_window))
    log(f"built: {model.num_params():,} parameters, {slots} slots, decode "
        f"tier {engine._paged_path}, pools "
        f"{[tuple(kv.pool.shape) for kv in engine.kvs]}; "
        f"{harness.hbm_line()}")

    vocab = cfg.vocab_held[1]
    requests = schedule.fill(schedule.plan(traffic, seconds), seed, vocab)
    shapes = schedule.prompt_shapes(requests)
    # every shape the traffic file can ask for, not only this plan's: a
    # sweep at another rate draws other documents
    tails = {(d, q) for d in traffic["session"]["doc_lens"]
             for q in traffic["prompt_lens"] if d} \
        | {(CHECK_DOC, CHECK_QUESTION)}
    lens = {d + q for d, q in tails} | set(shapes["prompt_lens"])
    engine.warmup(prompt_lens=sorted(lens), tails=sorted(tails))
    log(f"warmup returned: {harness.hbm_line()}")
    harness.device_barrier()
    log(f"warmup ran: {harness.hbm_line()}")
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        check = _check(fd.port, engine, model, reference_config(conf, cfg),
                       range(cfg.experts_held[0], sum(cfg.experts_held)),
                       seed)
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
             for k in ("prefill_tokens_computed", "prefill_tokens_requested")}
    log(f"window: compiles_in_window {rec['values']['compiles_in_window']}, "
        f"{json.dumps(grown)}")
    rec["spans"] = ptrace.events() if tracing else []
    rec["values"]["setup_s"] = rec["window"][0] - ctx["t_start"]
    rec["values"]["slots"] = slots
    high = obs.snapshot().get("serving.kv.window_pages_per_slot_high_water")
    if high is not None:
        rec["values"]["kv_window_pages_per_slot_peak"] = high
    rec.update(correct=check["correct"],
               attempted=len(stats.counted(rec["requests"])),
               failed=stats.failed_count(rec["requests"]),
               model=conf, peaks=dev["peaks"], device=dev["device"])
    return rec

