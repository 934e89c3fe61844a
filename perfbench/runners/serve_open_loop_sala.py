"""Runner: ``serve_open_loop`` for the ``minicpm_sala`` configuration — one
pipeline stage of eight layers (two sparse-attention, six lightning) behind
the same router, front door and load generator.

    MiniCPMSalaForCausalLM.serving_callables -> serving.Engine.warmup
        -> serving.Router -> serving.FrontDoor  <- HTTP -  perfbench.loadgen

What differs from ``serve_open_loop`` (whose ``_drive``, ``_post``,
``_settle`` and ``_sweep`` it imports as they are): the model is built in its
serving dtype as the stage of published layers ``serve.layers_run`` (with
the embedding and the head, so that it runs end to end); the engine keeps
one page pool for the sparse layers, the compressed keys beside it, a state
row a slot for the lightning layers and state snapshots at prefix
boundaries; ``Engine.warmup`` takes the prefix tails; the slot count is the
largest the chip holds with a tenth of its memory free; and the reference
check sends one 32,768-token document through a full prefill and through
the tail from its state snapshot, 16 new tokens each, while other slots
decode, then holds the two mechanisms themselves to the reference at the
same sizes — the float32 state the engine kept at the document's end, and
the blocks every decode step's selection chose (the first ask once more,
straight through the compiled programs of a one-slot engine whose callables
also return them) — all under ``reference_minicpm_sala``'s limits. The
reference's own seconds are not in ``setup_s``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import types
from typing import Dict

import numpy as np

from .. import harness, reference_minicpm_sala as reference, schedule, stats
from ..harness import log
from .serve_open_loop import _drive, _post, _settle, _sweep

# the reference check's sizes, unless the configuration's ``serve.check``
# names others (the tiny preset): a document, its question, new tokens an
# ask; other slots decoding meanwhile, their documents and new tokens
CHECK = {"doc": 32768, "question": 64, "new_tokens": 16, "beside": 3,
         "beside_doc": 16384, "beside_tokens": 256}


def model_config(conf: Dict):
    """The program's config object from the file's published keys and the
    stage it runs (``serve.layers_run`` of ``serve.layers_published``)."""
    import dataclasses

    from paddle_tpu.models.minicpm_sala import MiniCPMSalaConfig
    dep = conf["serve"]
    fields = {f.name for f in dataclasses.fields(MiniCPMSalaConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(num_hidden_layers=dep["layers_published"],
              mixer_types=tuple(dep["mixer_types_published"]),
              layers_run=tuple(dep["layers_run"]),
              sparse=dict(conf["sparse_config"]), dtype=dep["dtype"])
    return MiniCPMSalaConfig(**kw)


def reference_config(conf: Dict) -> Dict:
    dep = conf["serve"]
    return reference.reference_config(
        conf, dep["layers_run"], dep["layers_published"],
        dep["mixer_types_published"])


def pool_bytes(dep: Dict, cfg, slots: int) -> Dict[str, float]:
    """What the engine holds for ``slots`` slots, by cache."""
    kinds = cfg.layer_kinds
    per_slot = dep["max_len"] // dep["page_size"]
    pages = slots * per_slot + 1
    page = 2 * cfg.num_key_value_heads * dep["page_size"] * cfg.head_dim * 2
    n_sparse, n_linear = kinds.count("sparse"), kinds.count("linear")
    return {"pages": page * n_sparse * pages,
            "index": page // 2 * n_sparse * pages
            * cfg.sparse.per_block // dep["page_size"],
            "state": 4 * int(np.prod(cfg.state_shape)) * n_linear
            * (slots + 1),
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
        layer_kinds=cfg.layer_kinds, state_shape=cfg.state_shape,
        index_per_page=cfg.sparse.per_block,
        state_snapshot_tokens=dep["state_snapshot_tokens"],
        state_snapshot_bytes=int(dep["state_snapshot_gb"] * 1e9))


def _direct(model, dep: Dict, prompt, tokens) -> Dict:
    """The check's first ask once more, straight through the compiled
    programs of a one-slot engine whose callables also return their logits
    and the blocks each sparse layer chose: a full prefill, then one decode
    step for every token the serving engine chose (teacher-forced). ->
    ``logits`` [A, V] at the answer's positions, ``blocks`` [A - 1,
    L_sparse, Hkv, K] of the decode steps (-1: none)."""
    import jax.numpy as jnp

    from paddle_tpu import serving
    from paddle_tpu.core.tensor import Tensor as T

    def i32(x):
        return T(jnp.asarray(x, jnp.int32))

    eng = serving.Engine(*model.serving_callables(
        dep["max_len"], block=dep["state_snapshot_tokens"], with_logits=True),
        serving_config(dep, model.config, 1, "check"))
    p, kv = eng.programs, eng.kv
    pages = kv.table_row(kv.alloc(kv.pages_for(len(prompt) + len(tokens))))
    row = eng.state.alloc()
    step = p.prefill(i32(prompt[None]), [i32(pages)], i32(len(prompt)), 0,
                     i32(row))
    logits, blocks = [step.read()[1].view(np.float32)], []
    for i, tok in enumerate(tokens[:-1]):
        step = p.decode(i32([[tok]]), [i32(pages[None])],
                        i32([len(prompt) + i]), p.no_carry, i32([-1]),
                        i32([row]))
        _, chose, lg = model.split_step_extras(step.read()[1], 1)
        logits.append(lg[0])
        blocks.append(chose[0])
    return {"logits": np.stack(logits), "blocks": np.stack(blocks)}


def _check(port: int, engine, model, dep: Dict, ref_conf: Dict, seed: int,
           sizes: Dict) -> Dict:
    """One seeded document through the front door twice — a full prefill,
    then the tail from the state snapshot at the document's end — while
    ``sizes["beside"]`` other documents decode in other slots, against the
    reference under ``reference_minicpm_sala``'s limits. Every compared
    position is past ``dense_len``: pages, compressed keys, the chosen
    tables, the state pool and the snapshot are all in what is compared.
    Then the state the engine kept at the document's end (float32, from its
    snapshot store) and the blocks its decode steps choose (:func:`_direct`)
    against the reference's own.
    ``PERFBENCH_CHECK_CONTROL`` names ``reference.CONTROLS``
    (comma-separated; ``none`` is the sound reference) to compare against
    the reference computed a precision lower instead — the builder's switch
    for the second reading a limit is set from; the driver never sets it.
    Every comparison named is logged; the first one's is the run's."""
    controls = [c for c in os.environ.get("PERFBENCH_CHECK_CONTROL", ""
                                          ).split(",") if c] or ["none"]
    if set(controls) - set(reference.CONTROLS) - {"none"}:
        raise SystemExit(f"perfbench: PERFBENCH_CHECK_CONTROL {controls}: "
                         f"not among {reference.CONTROLS}")
    vocab = model.config.vocab_size
    n_beside, question = sizes["beside"], sizes["question"]
    rng = np.random.default_rng([seed, 4])
    doc = rng.integers(0, vocab, sizes["doc"])
    plen = sizes["doc"] + question
    beside_len = sizes["beside_doc"] + question
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
    kept = engine.snapshots.get(kv_cache.prefix_chain_digests(
        asked[0][0], engine.config.page_size,
        limit=sizes["doc"] // engine.config.page_size)[-1])
    direct = dict(_direct(model, dep, *asked[0]), state_at=sizes["doc"],
                  state=None if kept is None else np.asarray(kept))
    log("reference check: the first ask went straight through the programs")
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
        out = dict(_compare(params, asked, plen, sizes["new_tokens"], dict(
            ref_conf, control="" if control == "none" else control), direct),
            **seen)
        out["correct"] = out["correct"] and saw
        log("reference check:", json.dumps(out))
        outs.append(out)
    return dict(outs[0], reference_s=sum(o["reference_s"] for o in outs))


def _compare(params, asked, plen: int, new_tokens: int, ref_conf: Dict,
             direct: Dict) -> Dict:
    """What the engine chose in ``asked`` [(prompt, tokens)], the state it
    kept and the blocks and logits of ``direct`` (the first ask's) against
    the reference as ``ref_conf`` has it (``control``: a precision lower).
    ``reference_s``: the seconds the reference itself took."""
    import jax

    fn = jax.jit(lambda p, i, n, a: reference.answer_rows(
        p, i, n, a, ref_conf, direct["state_at"]))
    refs, gaps, reference_s = [], [], 0.0
    for prompt, tokens in asked:
        ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        t0 = time.monotonic()
        ref = {k: np.asarray(v) for k, v in fn(
            params, ids, np.int32(plen), np.asarray(tokens, np.int32)
        ).items()}
        reference_s += time.monotonic() - t0
        ok = len(tokens) == new_tokens and np.all(np.isfinite(ref["gap"]))
        log(f"reference check: {len(ids)} tokens through the reference")
        gaps.append(ref["gap"] if ok else np.full(len(tokens), np.inf))
        refs.append(ref)
    gap = np.concatenate(gaps)
    margin = np.concatenate([r["margin"] for r in refs])
    steady = reference.steady(margin)
    worst = float(gap[steady].max()) if steady.any() else 0.0
    agree, n = int((gap == 0).sum()), int(gap.size)
    # the state kept at the document's end against the reference's: the
    # worst head's distance, and what the state's own rounding adds
    first, kept = refs[0], direct["state"]
    by_head, state_err, state_rounding = [[]], np.inf, np.inf
    if kept is not None and kept.shape == first["states"].shape:
        by_head, state_err, state_rounding = (
            np.asarray(x) for x in reference.state_distance(
                kept, first["states"], ref_conf))
    # the blocks the decode steps chose: a block may differ from the
    # reference's only where its score lies near the last chosen one's
    chosen, away = first["chosen"][1:], first["away"][1:]
    took = np.zeros_like(chosen)
    at = np.nonzero(direct["blocks"] >= 0)
    took[at[:3] + (direct["blocks"][at],)] = True
    differ = took ^ chosen
    wrong = differ & ~(away <= reference.BLOCK_MARGIN_MIN)
    logit_diff = np.abs(direct["logits"] - first["logits"]).max(-1)
    return {"control": ref_conf["control"], "max_gap_steady": worst,
            "steady": int(steady.sum()), "block_ties": int((~steady).sum()),
            "max_gap_all": float(gap.max()),
            "gap_full_prefill": float(gaps[0].max()),
            "gap_snapshot_tail": float(gaps[1].max()),
            "tokens_agreeing": agree, "tokens": n,
            "tolerance": reference.SERVE_LOGIT_TOL_SALA,
            "min_steady": reference.SERVE_MIN_STEADY,
            "min_agreeing": reference.SERVE_MIN_AGREEING_SALA,
            "state_err": float(state_err),
            "state_tolerance": reference.SERVE_STATE_TOL_SALA,
            "state_rounding": float(state_rounding),
            "state_rounding_tolerance":
            reference.SERVE_STATE_ROUNDING_TOL_SALA,
            "state_err_by_head": [[round(float(x), 5) for x in row]
                                  for row in by_head],
            "blocks_chosen": int(chosen.sum()),
            "blocks_differing": int(differ.sum()),
            "blocks_wrong": int(wrong.sum()),
            "away_max_differing": float(away[differ].max())
            if differ.any() else 0.0,
            "block_margin": reference.BLOCK_MARGIN_MIN,
            "direct_logit_diff": [round(float(x), 5) for x in logit_diff],
            "direct_tokens_agreeing": int((direct["logits"].argmax(-1)
                                           == asked[0][1]).sum()),
            "gaps": [round(float(x), 5) for x in gap],
            "margins": [round(float(x), 4) for x in np.minimum(margin, 9.0)],
            "reference_s": reference_s,
            "correct": bool(worst <= reference.SERVE_LOGIT_TOL_SALA
                            and steady.sum() >= reference.SERVE_MIN_STEADY
                            and agree >= reference.SERVE_MIN_AGREEING_SALA
                            * n
                            and state_err <= reference.SERVE_STATE_TOL_SALA
                            and state_rounding
                            <= reference.SERVE_STATE_ROUNDING_TOL_SALA
                            and not wrong.any())}


def _log_prefills(spans, page_size: int) -> None:
    """How long a prefill held the step loop, by the tokens it computed
    (a traced run's ``serving.prefill`` spans, lead-in included): what a
    first ask and a tail cost every live row."""
    begun = {e["span"]: e for e in spans
             if e["kind"] == "B" and e["name"] == "serving.prefill"}
    by_size: Dict[int, list] = {}
    for e in spans:
        if e["kind"] == "E" and e.get("span") in begun:
            b = begun[e["span"]]
            size = b["attrs"]["prompt"] - b["attrs"]["shared_pages"] * page_size
            by_size.setdefault(size, []).append((e["ts"] - b["ts"]) * 1e3)
    if by_size:
        log("prefills, computed tokens -> [count, median ms, max ms]: "
            + json.dumps({k: [len(v), round(stats.percentile(v, 50), 1),
                              round(max(v), 1)]
                          for k, v in sorted(by_size.items())}))


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.minicpm_sala import MiniCPMSalaForCausalLM
    from paddle_tpu.observability import trace as ptrace

    compiles = harness.CompileCounter()
    obs.enable()
    tracing = bool(ctx["trace"]) or bool(ctx.get("sweep"))
    if tracing:
        ptrace.set_mode("on")          # the program's spans, traced run only
    dep = conf["serve"]
    cfg = model_config(conf)
    paddle.seed(harness.fold_seed(seed))
    model = MiniCPMSalaForCausalLM(cfg)    # in its serving dtype
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
        f"compressed keys {engine.index.shape}, states {engine.state.shape}; "
        f"{harness.hbm_line()}")

    vocab = cfg.vocab_size
    requests = schedule.fill(schedule.plan(traffic, seconds), seed, vocab)
    shapes = schedule.prompt_shapes(requests)
    # every shape the traffic file can ask for, not only this plan's: a
    # sweep at another rate draws other documents
    sizes = dict(CHECK, **dep.get("check", {}))
    tails = {(d, q) for d in traffic["session"]["doc_lens"]
             for q in traffic["prompt_lens"] if d} \
        | {(sizes["doc"], sizes["question"])}
    lens = {d + q for d, q in tails} | set(shapes["prompt_lens"]) \
        | {sizes["beside_doc"] + sizes["question"]}
    engine.warmup(prompt_lens=sorted(lens), tails=sorted(tails))
    log(f"warmup returned: {harness.hbm_line()}")
    harness.device_barrier()
    log(f"warmup ran: {harness.hbm_line()}")
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        check = _check(fd.port, engine, model, dep, reference_config(conf),
                       seed, sizes)
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
                       "serving.state.snapshot_evictions_total")}
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
