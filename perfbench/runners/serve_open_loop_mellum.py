"""Runner: ``serve_open_loop`` for the ``mellum`` configuration — one of seven
pipeline stages of four layers (sliding window x3, then YaRN full attention),
every one of the 64 experts and the whole vocabulary held, behind the same
router, front door and load generator.

    MellumForCausalLM.serving_callables -> serving.Engine.warmup
        -> serving.Router -> serving.FrontDoor  <- HTTP -  perfbench.loadgen

What differs from ``serve_open_loop`` (whose ``_drive``, ``_post``,
``_settle`` and ``_sweep`` it imports as they are, as it does
``serve_open_loop_sala``'s ``_log_prefills`` and ``serve_open_loop_moe``'s
``_scale_attention_out``): the model is built in its serving dtype, its
attention output projections scaled as the configuration's ``assumed``
says; the engine keeps pages by layer kind and keeps the window
pool's pages at prefix boundaries (``serve.window_boundary_tokens``), so a
follow-up whose question is longer than the window still prefills its tail
only; ``Engine.warmup`` takes the prefix tails; the slot count is the largest
the chip holds with a tenth of its memory free; and the reference check sends
one 32,768-token document through a full prefill and through a 2048-token
tail from its boundary, 16 new tokens each, while three other slots decode,
then holds the K and V the engine stored for the document's last pages —
both kinds of pool — to the reference's; after the window, a seeded sample
of the requests the window served (chat turns and a follow-up over a
document) is held to the reference too, all under ``reference_mellum``'s
limits. The reference's own seconds are not in ``setup_s``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import types
from typing import Dict, List

import numpy as np

# a program without the model stops here, before the device is opened
from paddle_tpu.models import mellum as _mellum

from .. import harness, reference_mellum as reference, schedule, stats
from ..harness import log
from .serve_open_loop import _drive, _post, _settle, _sweep
from .serve_open_loop_moe import _scale_attention_out
from .serve_open_loop_sala import _log_prefills

# the reference check's sizes, unless the configuration's ``serve.check``
# names others (the tiny preset): a document, its question, new tokens an
# ask; other slots decoding meanwhile, their prompts and new tokens; the
# document's last pages whose stored K and V are compared; of the window's
# finished requests, the chat turns and the follow-ups over a document no
# longer than the check's that are compared, and the first tokens of each
CHECK = {"doc": 32768, "question": 2048, "new_tokens": 16, "beside": 3,
         "beside_prompt": 2048, "beside_tokens": 512, "cache_pages": 4,
         "sample_chat": 6, "sample_doc": 1, "sample_tokens": 48}


def model_config(conf: Dict):
    """The program's config object from the file's published keys and the
    layers it runs (``num_hidden_layers`` of ``layer_types``)."""
    import dataclasses

    fields = {f.name for f in dataclasses.fields(_mellum.MellumConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    kw.update(layer_types=tuple(conf["layer_types"]
                                [:conf["num_hidden_layers"]]),
              dtype=conf["serve"]["dtype"])
    return _mellum.MellumConfig(**kw)


def reference_config(conf: Dict, cfg) -> Dict:
    """``reference_mellum``'s view: the published keys and the layers run."""
    return dict(conf, layer_types=list(cfg.layer_types))


def pool_bytes(dep: Dict, cfg, slots: int) -> Dict[str, float]:
    """What the engine holds for ``slots`` slots, by pool."""
    from paddle_tpu.ops.paged_attention import window_table_pages
    ps = dep["page_size"]
    page = 2 * cfg.num_key_value_heads * ps * cfg.head_dim * 2
    kinds = cfg.layer_kinds
    window = slots * window_table_pages(cfg.sliding_window, ps) + 1 \
        + dep["window_boundary_pages"]
    return {"full": page * kinds.count("full")
            * (slots * (dep["max_len"] // ps) + 1),
            "window": page * kinds.count("window") * window}


def pick_slots(dep: Dict, cfg, weights_bytes: int, limit_bytes: int) -> int:
    """The largest slot count tried whose pools leave ``hbm_free_share`` of
    the chip free beside the weights and the prefill's workspace."""
    for slots in dep["slots_tried"]:
        parts = pool_bytes(dep, cfg, slots)
        total = weights_bytes + sum(parts.values()) \
            + dep["workspace_gb"] * 1e9
        log(f"slots {slots}: " + ", ".join(
            f"{k} pool {v / 1e9:.2f}" for k, v in parts.items())
            + f" GB; with weights and workspace {total / 1e9:.2f} of "
            f"{limit_bytes / 1e9:.2f} GB")
        if total <= (1.0 - dep["hbm_free_share"]) * limit_bytes:
            return slots
    raise SystemExit("perfbench: no slot count tried fits this chip")


def serving_config(dep: Dict, cfg, slots: int, name: str):
    from paddle_tpu import serving
    return serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=dep["max_len"], name=name,
        max_batch=slots, buckets=tuple(b for b in dep["buckets"]
                                       if b < slots) + (slots,),
        page_size=dep["page_size"], compute_dtype=dep["dtype"],
        kv_dtype=dep["kv_dtype"], max_queue=dep["max_queue"],
        layer_kinds=cfg.layer_kinds, window=cfg.sliding_window,
        window_boundary_tokens=dep["window_boundary_tokens"],
        window_boundary_pages=dep["window_boundary_pages"])


def stored_kv(engine, prompt: np.ndarray, doc: int, pages: int
              ) -> np.ndarray:
    """The K and V the engine holds for the last ``pages`` pages of
    ``prompt``'s first ``doc`` tokens, every layer from its own kind's pool:
    [L, 2, pages * page_size, Hkv, D] float32 (``None`` if a pool holds them
    no longer). Each pool's pages are claimed while they are read."""
    ps = engine.config.page_size
    last = doc // ps
    out = []
    for k, j in engine._layer_pool:
        kv = engine.kvs[k]
        ids = kv.acquire_prefix(prompt, first=last - pages, count=last,
                                quiet=True)
        if len(ids) < pages:
            kv.free(ids)
            return None
        try:
            x = np.asarray(kv.pool[np.asarray(ids)][:, j], np.float32)
        finally:
            kv.free(ids)
        # (pages, 2, Hkv, ps, D) -> (2, pages * ps, Hkv, D)
        out.append(x.transpose(1, 0, 3, 2, 4).reshape(
            2, pages * ps, x.shape[2], x.shape[4]))
    return np.stack(out)


def _controls():
    """``PERFBENCH_CHECK_CONTROL``'s list (``["none"]`` when unset)."""
    controls = [c for c in os.environ.get("PERFBENCH_CHECK_CONTROL", ""
                                          ).split(",") if c] or ["none"]
    if set(controls) - set(reference.CONTROLS) - {"none"}:
        raise SystemExit(f"perfbench: PERFBENCH_CHECK_CONTROL {controls}: "
                         f"not among {reference.CONTROLS}")
    return controls


def _as_controlled(ref_conf: Dict, control: str) -> Dict:
    return dict(ref_conf, control="" if control == "none" else control)


def _check(port: int, engine, model, ref_conf: Dict, seed: int,
           sizes: Dict) -> Dict:
    """One seeded document through the front door twice — a full prefill,
    then the tail from the window pages kept at the document's end — while
    ``sizes["beside"]`` other requests decode in other slots, against the
    reference under ``reference_mellum``'s limits; then the K and V the
    engine stored for the document's last pages against the reference's.
    ``PERFBENCH_CHECK_CONTROL`` names ``reference.CONTROLS``
    (comma-separated; ``none`` is the sound reference) to compare against
    the reference computed a precision lower, or with part of its
    mathematics left out, instead — the switch for the second reading a
    limit is set from; a benchmark run leaves it unset. Every comparison
    named is logged; the first one's is the run's."""
    controls = _controls()
    vocab = model.config.vocab_size
    n_beside, question = sizes["beside"], sizes["question"]
    rng = np.random.default_rng([seed, 4])
    doc = rng.integers(0, vocab, sizes["doc"])
    plen = sizes["doc"] + question
    others = []
    beside = [threading.Thread(
        target=lambda p: others.append(_post(port, p,
                                             sizes["beside_tokens"])),
        daemon=True, args=(rng.integers(0, vocab, sizes["beside_prompt"]),))
        for _ in range(n_beside)]
    before = engine.prefill_token_stats()
    for th in beside:
        th.start()
    end = time.monotonic() + 120.0
    while engine.active_requests < n_beside and time.monotonic() < end:
        time.sleep(0.05)
    asked = []
    for _ in ("full prefill", "tail from the boundary"):
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
    kept = stored_kv(engine, asked[0][0], sizes["doc"], sizes["cache_pages"])
    params = reference.params_of(model)
    seen = {"decoding_beside": still_beside,
            "beside_distinct_last_64": [len(set(t[-64:])) for t in others],
            "prefill_tokens_computed": comp, "prefill_tokens_requested": req,
            "distinct_tokens": len({t for _, toks in asked for t in toks})}
    # the second request must have been a tail from the kept boundary, and
    # the others must have been decoding beside both
    saw = still_beside == n_beside and comp == \
        n_beside * sizes["beside_prompt"] + plen + question
    outs = []
    for control in controls:
        out = dict(_compare(params, asked, plen, sizes,
                            _as_controlled(ref_conf, control), kept), **seen)
        out["correct"] = out["correct"] and saw
        log("reference check:", json.dumps(out))
        outs.append(out)
    return dict(outs[0], reference_s=sum(o["reference_s"] for o in outs))


def _compare(params, asked, plen: int, sizes: Dict, ref_conf: Dict,
             kept) -> Dict:
    """What the engine chose in ``asked`` [(prompt, tokens)] and the K and
    V it ``kept`` for the document's last pages against the reference as
    ``ref_conf`` has it. ``reference_s``: the seconds the reference itself
    took."""
    import jax

    rows = kept.shape[2] if kept is not None else 1
    fn = jax.jit(lambda p, i, n, a, f: reference.answer_rows(
        p, i, n, a, ref_conf, f, rows))
    refs, gaps, reference_s = [], [], 0.0
    for prompt, tokens in asked:
        ids = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        t0 = time.monotonic()
        ref = {k: np.asarray(v) for k, v in fn(
            params, ids, np.int32(plen), np.asarray(tokens, np.int32),
            np.int32(sizes["doc"] - rows)).items()}
        reference_s += time.monotonic() - t0
        ok = len(tokens) == sizes["new_tokens"] \
            and np.all(np.isfinite(ref["gap"]))
        log(f"reference check: {len(ids)} tokens through the reference")
        gaps.append(ref["gap"] if ok else np.full(len(tokens), np.inf))
        refs.append(ref)
    gap = np.concatenate(gaps)
    margin = np.concatenate([r["margin"] for r in refs])
    by_layer = np.full((len(params["layers"]), 2), np.inf)
    if kept is not None:
        by_layer = reference.cache_distance(kept, refs[0]["kv"])
    cache_err = float(by_layer.max())
    tokens = _token_stats(gap, margin)
    return dict(
        tokens, control=ref_conf["control"],
        gap_full_prefill=float(gaps[0].max()),
        gap_boundary_tail=float(gaps[1].max()),
        cache_err=cache_err, cache_tolerance=reference.SERVE_CACHE_TOL_MEL,
        cache_err_by_layer=[[round(float(x), 5) for x in row]
                            for row in by_layer],
        gaps=[round(float(x), 4) for x in gap],
        margins=[round(float(x), 4) for x in np.minimum(margin, 9.0)],
        reference_s=reference_s,
        correct=tokens["agrees"]
        and cache_err <= reference.SERVE_CACHE_TOL_MEL)


def _token_stats(gap: np.ndarray, margin: np.ndarray) -> Dict:
    """The readings of the chosen tokens' ``gap`` [N] and their router
    ``margin`` [N] (``reference_mellum``'s docstring); the agreement alone
    is a limit (``agrees``)."""
    steady = reference.steady(margin)
    agree = int((gap == 0).sum())
    return {"max_gap_steady": float(gap[steady].max()) if steady.any()
            else 0.0,
            "steady": int(steady.sum()), "router_ties": int((~steady).sum()),
            "agreeing_steady": int((gap[steady] == 0).sum()),
            "max_gap_all": float(gap.max()) if gap.size else 0.0,
            "tokens_agreeing": agree, "tokens": int(gap.size),
            "min_agreeing": reference.SERVE_MIN_AGREEING_MEL,
            "router_margin": reference.ROUTER_MARGIN_MIN_MEL,
            "agrees": bool(gap.size and agree
                           >= reference.SERVE_MIN_AGREEING_MEL * gap.size)}


def _record_submits(engine) -> List:
    """From now on, every ``(request, future)`` the engine is handed, for
    :func:`_window_sample`: one list append a request, on the thread that
    submits it."""
    seen = []
    submit = engine.submit

    def recording(request):
        fut = submit(request)
        seen.append((request, fut))
        return fut
    engine.submit = recording
    return seen


def _window_sample(model, submitted, requests, stamps, traffic: Dict,
                   ref_conf: Dict, seed: int, sizes: Dict) -> Dict:
    """A seeded sample of the counted requests the window finished —
    ``sample_chat`` chat turns and ``sample_doc`` follow-ups over a document
    no longer than the check's, served among the window's own traffic — each
    one's first ``sample_tokens`` tokens against the reference,
    teacher-forced, under the same agreement limit. A kind's prompts are
    padded to its longest, so each kind compiles the reference once."""
    import jax

    n_tok = sizes["sample_tokens"]
    done = {r["id"] for r in stamps if r["counted"] and r["ok"]}
    answers = {}
    for request, fut in submitted:
        if fut.done() and fut.exception() is None:
            answers.setdefault(int(request.prompt.size), []).append(
                (np.asarray(request.prompt), fut.result().tokens))

    def answer(r):
        prompt = np.asarray(r["prompt"])
        return next((toks for p, toks in answers.get(prompt.size, ())
                     if np.array_equal(p, prompt)), None)

    question = max(traffic["prompt_lens"])
    fn = jax.jit(lambda p, i, n, a: reference.answer_rows(p, i, n, a,
                                                          ref_conf))
    params = reference.params_of(model)
    rng = np.random.default_rng([seed, 5])
    gaps, margins, picked, t0 = [], [], [], time.monotonic()
    for kind, longest, fits in (
            ("chat", question, lambda d: d == 0),
            ("doc", sizes["doc"] + question, lambda d: 0 < d <= sizes["doc"])):
        pool = [r for r in requests if r["id"] in done
                and fits(r["doc_len"]) and r["new_tokens"] >= n_tok]
        for i in rng.permutation(len(pool))[:sizes[f"sample_{kind}"]]:
            r = pool[i]
            tokens = answer(r)
            if tokens is None or len(tokens) < n_tok:
                continue
            ids = np.zeros(longest + n_tok - 1, np.int32)
            ids[:r["prompt_len"] + n_tok - 1] = np.concatenate(
                [r["prompt"], tokens[:n_tok - 1]])
            ref = fn(params, ids, np.int32(r["prompt_len"]),
                     np.asarray(tokens[:n_tok], np.int32))
            gap = np.asarray(ref["gap"])
            gaps.append(np.where(np.isfinite(gap), gap, np.inf))
            margins.append(np.asarray(ref["margin"]))
            picked.append([r["id"], r["doc_len"], r["prompt_len"]])
    out = _token_stats(np.concatenate(gaps) if gaps else np.zeros(0),
                       np.concatenate(margins) if margins else np.zeros(0))
    out.update(control=ref_conf["control"], requests=picked,
               reference_s=time.monotonic() - t0, correct=out["agrees"])
    log("window sample:", json.dumps(out))
    return out


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.observability import trace as ptrace

    compiles = harness.CompileCounter()
    obs.enable()
    tracing = bool(ctx["trace"]) or bool(ctx.get("sweep"))
    if tracing:
        ptrace.set_mode("on")          # the program's spans, traced run only
    dep = conf["serve"]
    cfg = model_config(conf)
    paddle.seed(harness.fold_seed(seed))
    model = _mellum.MellumForCausalLM(cfg)     # in its serving dtype
    _scale_attention_out(model, dep["o_proj_init_scale"])
    model.eval()
    harness.device_barrier()
    st = jax.devices()[0].memory_stats() or {}
    slots = pick_slots(dep, cfg, int(st.get("bytes_in_use", 0)),
                       int(st.get("bytes_limit", 0)) or 2 ** 62)
    engine = serving.Engine(*model.serving_callables(dep["max_len"]),
                            serving_config(dep, cfg, slots, "r0"))
    log(f"built: {model.num_params():,} parameters, {slots} slots, decode "
        f"tier {engine._paged_path}, pools "
        f"{[tuple(kv.pool.shape) for kv in engine.kvs]}; "
        f"{harness.hbm_line()}")

    vocab = cfg.vocab_size
    requests = schedule.fill(schedule.plan(traffic, seconds), seed, vocab)
    shapes = schedule.prompt_shapes(requests)
    # every shape the traffic file can ask for, not only this plan's: a
    # sweep at another rate draws other documents
    sizes = dict(CHECK, **dep.get("check", {}))
    docs = traffic["session"]["doc_lens"]
    tails = {(d, q) for d in docs for q in traffic["prompt_lens"] if d} \
        | {(sizes["doc"], sizes["question"])}
    lens = {d + q for d in docs for q in traffic["prompt_lens"]} \
        | set(shapes["prompt_lens"]) | {sizes["doc"] + sizes["question"],
                                        sizes["beside_prompt"]}
    engine.warmup(prompt_lens=sorted(lens), tails=sorted(tails))
    log(f"warmup returned: {harness.hbm_line()}")
    harness.device_barrier()
    log(f"warmup ran: {harness.hbm_line()}")
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        check = _check(fd.port, engine, model, reference_config(conf, cfg),
                       seed, sizes)
        log(f"warm: {compiles.count} backend compiles; {harness.hbm_line()}; "
            f"plan {len(requests)} requests, shapes {json.dumps(shapes)}")
        if ctx.get("sweep"):
            # _sweep reads the vocabulary size of a model config and no more
            _sweep(ctx, engine, fd.port,
                   types.SimpleNamespace(vocab_size=vocab), compiles)
            return {"sweep": True}
        submitted = _record_submits(engine)
        rec = _drive(engine, fd.port, requests, traffic, seconds,
                     ctx["workload"], compiles, bool(ctx["trace"]), chips)
    finally:
        try:
            router.stop(drain=True, timeout=30)
        except Exception as exc:                  # reported, not fatal
            log(f"router.stop: {type(exc).__name__}: {exc}")
        fd.close()
    # what the cell's metric list cannot carry since it reports no
    # itl_p95_ms (PERF.md section 4): said here, for the record
    ends = rec["counters"]
    grown = {k: ends["end"].get(k, 0) - ends["start"].get(k, 0)
             for k in ("prefill_tokens_computed", "prefill_tokens_requested",
                       "serving.kv.window_prefix_hits_total",
                       "serving.kv.window_prefix_misses_total",
                       "serving.kv.window_boundary_evictions_total",
                       "serving.moe.rows_total",
                       "serving.moe.experts_touched_total")}
    snap = obs.snapshot()
    log(f"window: compiles_in_window {rec['values']['compiles_in_window']}, "
        f"{json.dumps(grown)}, boundary pages held for sharers at most "
        f"{snap.get('serving.kv.window_boundary_pages_high_water')}; "
        f"{harness.hbm_line()}")
    sample = _window_sample(
        model, submitted, requests, rec["requests"], traffic,
        _as_controlled(reference_config(conf, cfg), _controls()[0]), seed,
        sizes)
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
    for gauge, key in (("serving.kv.window_pages_per_slot_high_water",
                        "kv_window_pages_per_slot_peak"),
                       ("serving.kv.window_boundary_pages_high_water",
                        "window_boundary_pages_peak")):
        if snap.get(gauge) is not None:
            rec["values"][key] = snap[gauge]
    rec.update(correct=check["correct"] and sample["correct"],
               attempted=len(stats.counted(rec["requests"])),
               failed=stats.failed_count(rec["requests"]),
               model=conf, peaks=dev["peaks"], device=dev["device"])
    return rec
