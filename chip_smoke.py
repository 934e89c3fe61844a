"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a machine with a TPU; exit 0 = proven

Drives the two hot paths once through the entry points a user calls, at the
full width of Llama-2-7B (hidden 4096, 32 heads x 128, FFN 11008, vocab
32000; only the depth is cut, weights are seeded random), and checks what
comes out by the repo's own means:

* ``train``  — the ``bench.py`` path: bf16 ``amp.decorate(level="O2")``,
  ``AdamW(moment_dtype="int8", use_master_weights=False)``, scan-over-layers
  with recompute, sequence 4096, the step wrapped by
  ``paddle.jit.capture_step``. Loss finite and falling on a repeated batch;
  every step on the captured tier, no graph break, no CPU fallback, and the
  flash forward/backward and q8 Adam Pallas kernels present in the compiled
  step.
* ``serve``  — ``model.serving_callables`` -> ``serving.Engine.warmup`` ->
  ``serving.Router`` -> ``serving.FrontDoor``; ``POST /v1/generate`` over
  loopback, unary and streaming, three prompt lengths, enough in flight to
  fill the largest batch bucket. Every decode step on the paged-attention
  kernel, nothing compiled after warm-up, no page leaked by the drain, and
  the compiled kernel pinned against ``paged_attention_dense`` at the leg's
  shapes on both kv storage legs.
* ``hybrid`` — only where four chips are visible: ``fleet.init`` +
  ``fleet.distributed_model`` at dp2 x mp2, a few steps, every device
  holding its shard.

Process structure: TWO CHILDREN (three with four chips) of a parent that
never imports jax or paddle_tpu. A chip belongs to one process, and a leg's
peak HBM is only its own when the leg owns the process: each child takes the
chip, runs one leg, prints one ``RESULT`` line and exits; the parent starts
them one after another, stops whatever it started, and fails if any leg
failed. The children share JAX's persistent compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``): each leg
reports its compile seconds and how many entries the cache held when it
started, so a second invocation on the same directory shows the warm ones.

Without a TPU (``jax.devices()[0].platform != "tpu"``) the first child says
which platform it found and the run exits non-zero with no result line; so
does a run from a directory that holds this file and nothing else of the
repo. A passing run ends with two lines: ``SUMMARY {"legs": ..., "claim":
null}`` — what each leg observed; rates in it are observations of one run,
not claims — and then, as the last line of standard output, the result
object with exactly these keys, the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

# -- the model, by its published widths ------------------------------------
HIDDEN, HEADS, HEAD_DIM, FFN, VOCAB = 4096, 32, 128, 11008, 32000
PARAMS_LAYER = 4 * HIDDEN * HIDDEN + 3 * HIDDEN * FFN + 2 * HIDDEN
PARAMS_FIXED = 2 * VOCAB * HIDDEN + HIDDEN        # embedding, head, norm

# -- the run ----------------------------------------------------------------
TOTAL_BUDGET_S = 1150.0                  # the contract allows 1200
LEG_BUDGET_S = {"train": 450.0, "serve": 600.0, "hybrid": 300.0}
HBM_FREE_FRACTION = 0.10                 # each leg leaves this much free

TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 4096, 1, 5
SERVE_MAX_LEN, SERVE_PAGE, SERVE_MAX_BATCH = 2048, 64, 16
SERVE_BUCKETS = (1, 4, 16)
SERVE_PROMPT_LENS = (40, 100, 200)       # three lengths, none page-aligned
SERVE_NEW_TOKENS = 16
# the tolerance tests/test_tpu_smoke.py::_smoke_paged_attention states
PAGED_KERNEL_TOL = 2e-2

KERNELS_IN_STEP = ("flash_fwd_lse", "flash_bwd_dq", "flash_bwd_dkv",
                   "q8_adam_update")


def log(*a) -> None:
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# depth: the largest that leaves HBM_FREE_FRACTION of the chip free
# ---------------------------------------------------------------------------

def train_bytes(depth: int, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ) -> int:
    """Modeled peak HBM of the train leg (checked against ``memory_stats``
    after the run — the leg fails when the chip disagrees). Two humps: the
    fp32 build, where ``scan_layers`` stacks the per-layer parameters while
    the originals are still alive; and the step, at 6 bytes a parameter
    (bf16 weight, bf16 gradient, two int8 moments) plus activations —
    logits and their gradient in fp32, one layer's working set, and what
    each scanned layer's checkpoint keeps in bf16 (ISSUE 30): its carry,
    q, k, v, flash's output and fp32 log-sum-exp, the post-attention
    residual, and the gate and up projections. At depth 7 the build was
    the higher hump before ISSUE 30 (12.51 GB measured on a TPU v5e
    against 12.38 modeled); with the kept values the step is, and the
    model, which sums what XLA partly overlaps, runs ahead of the chip
    (Mistral's widths, depth 7: 15.95 GB modeled, 14.71 by the compiled
    step's own ``peak_memory_in_bytes``; PERF.md)."""
    n = PARAMS_FIXED + depth * PARAMS_LAYER
    build = 4 * PARAMS_FIXED + 2 * 4 * depth * PARAMS_LAYER
    tokens = batch * seq
    kept = (2 * 6 * HIDDEN      # carry, q, k, v (32 heads each), out, residual
            + 2 * 2 * FFN       # gate, up
            + 4 * HEADS)        # log-sum-exp, fp32
    acts = (2 * 4 * tokens * VOCAB             # logits and d(logits)
            + 12 * 2 * tokens * FFN            # one layer's MLP tensors
            + depth * tokens * kept)
    return max(build, 6 * n + acts)


def serve_bytes(depth: int) -> int:
    """Modeled peak HBM of the serve leg: bf16 weights, the prefill's dense
    single-slot cache, workspace, and the page pool, sized for every slot's
    full ``max_len`` — once: every serving program takes the pool donated
    and writes it in place (ISSUE 26; before it the pool was a plain
    argument that came back as a new array, and the peak held four).
    Measured on a TPU v5e at depth 5: 5.31 GB in use after warm-up against
    2.55 of weights + 2.69 of pool; the peak, 5.36, was the fp32 build.
    At depth 13, what this model picks on 16 GB: peak 12.95 GB of the
    14.73 modeled."""
    n = PARAMS_FIXED + depth * PARAMS_LAYER
    pages = SERVE_MAX_BATCH * (SERVE_MAX_LEN // SERVE_PAGE) + 1
    pool = pages * depth * 2 * HEADS * SERVE_PAGE * HEAD_DIM * 2
    dense_slot = depth * 2 * HEADS * SERVE_MAX_LEN * HEAD_DIM * 2
    build = 4 * n                              # fp32 init before the cast
    run = 2 * n + pool + 2 * dense_slot + (1 << 30)
    return max(build, run)


def pick_depth(bytes_of, limit: int) -> int:
    fit = [d for d in range(1, 33)
           if bytes_of(d) <= (1.0 - HBM_FREE_FRACTION) * limit]
    if not fit:
        raise RuntimeError(f"no depth fits {limit} bytes of HBM")
    return max(fit)


# ---------------------------------------------------------------------------
# what every leg does first and last
# ---------------------------------------------------------------------------

def open_leg(require_tpu: bool = True) -> dict:
    """Take the device, refuse anything but a TPU, print what was found."""
    import jax
    import jaxlib

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, jax found platform {d.platform!r} "
            f"(device_kind {d.device_kind!r}, {len(devs)} device(s))")
    import paddle_tpu as paddle
    from paddle_tpu import _native
    from paddle_tpu.observability import cost
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    device = paddle.device.describe()
    # the one peaks table; an unknown kind is an error, not a default
    peaks = cost.device_peaks(device["kind"]) if require_tpu else None
    cache_dir = paddle.compile_cache_dir()
    entries = len(os.listdir(cache_dir)) \
        if cache_dir and os.path.isdir(cache_dir) else 0
    info = {
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "native_available": bool(_native.available()),
        "compile_cache": {"dir": cache_dir, "entries_at_start": entries},
        "peaks": peaks,
    }
    log("leg opens:", json.dumps(info))
    return info


def hbm(device_index: int = 0) -> dict:
    import jax
    st = jax.devices()[device_index].memory_stats() or {}
    return {"limit": int(st.get("bytes_limit", 0)),
            "in_use": int(st.get("bytes_in_use", 0)),
            "peak": int(st.get("peak_bytes_in_use", 0))}


def check_hbm(tag: str, modeled: int) -> dict:
    m = hbm()
    if not m["limit"]:                       # no allocator stats (CPU)
        return {"peak_bytes": None, "modeled_bytes": modeled}
    free = 1.0 - m["peak"] / m["limit"]
    log(f"{tag}: peak HBM {m['peak']:,} of {m['limit']:,} bytes "
        f"(free {free:.1%}; modeled {modeled:,})")
    assert free >= HBM_FREE_FRACTION, \
        f"{tag}: only {free:.1%} of HBM left free at the chosen depth"
    return {"peak_bytes": m["peak"], "limit_bytes": m["limit"],
            "free_fraction": round(free, 4), "modeled_bytes": modeled}


class CompileCounter:
    """Counts XLA backend compilations through jax's own monitoring
    events — every one, whichever layer of this repo asked for it."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# leg: train
# ---------------------------------------------------------------------------

def leg_train(depth=None, *, seq=TRAIN_SEQ, batch=TRAIN_BATCH,
              steps=TRAIN_STEPS, config=None, on_chip=True) -> dict:
    """``config``/``on_chip=False`` exist for the CPU dry run of this
    function in tests — ``main`` always runs the real widths on a TPU."""
    info = open_leg(require_tpu=on_chip)
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.core import fallback, step_capture
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    compiles = CompileCounter()
    obs.enable()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    limit = hbm()["limit"]
    if depth is None:
        depth = pick_depth(train_bytes, limit)
    cfg = config or LlamaConfig.llama2_7b()
    cfg.num_hidden_layers = depth
    cfg.max_position_embeddings = max(seq, 128)
    cfg.scan_layers = cfg.recompute = True
    log(f"train: depth {depth} of 32, batch {batch}, seq {seq}")

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        use_multi_tensor=False, moment_dtype="int8",
        use_master_weights=False)
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16", master_weight=False)
    n_params = model.num_params()
    log(f"train: {n_params:,} parameters; after build {hbm()}")

    def body(ids):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss, _ = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.capture_step(body)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32))

    t0 = time.perf_counter()
    losses = [float(np.asarray(step(ids)._data))]     # compile + step 1
    first_call_s = time.perf_counter() - t0
    step_ms = []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(np.asarray(step(ids)._data)))   # host sync
        step_ms.append((time.perf_counter() - t0) * 1e3)
    log(f"train: losses {[round(x, 4) for x in losses]}")
    log(f"train: first call {first_call_s:.1f}s ({compiles.count} backend "
        f"compiles, {compiles.seconds:.1f}s), steps {np.round(step_ms, 1)}")

    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # what proves the device did the work
    cap = step_capture.capture_info()
    assert cap["hits"] > 0 and cap["bypasses"] == {}, cap
    snap = obs.snapshot()
    assert int(snap.get("jit.graph_breaks_total", 0) or 0) == 0, snap
    assert not fallback.fallback_ops(), fallback.fallback_ops()
    text = step.compiled_text()
    found = [k for k in KERNELS_IN_STEP if k in text]
    log(f"train: compiled step has {text.count('tpu_custom_call')} "
        f"tpu_custom_call(s); kernels by name: {found}")
    if on_chip:
        assert found == list(KERNELS_IN_STEP), \
            f"Pallas kernels missing from the compiled step: {found}"

    mem = check_hbm("train", train_bytes(depth, batch, seq))
    p50 = float(np.percentile(step_ms, 50))
    tok_s = batch * seq / (p50 / 1e3)
    result = {
        "leg": "train", "depth": depth, "params": n_params, "batch": batch,
        "seq": seq, "losses": [round(x, 4) for x in losses],
        "first_call_s": round(first_call_s, 1),
        "compile_s": round(compiles.seconds, 1),
        "step_ms_p50": round(p50, 1), "tokens_per_s": round(tok_s, 1),
        "capture": cap, "kernels": found, "hbm": mem, **info,
    }
    if info["peaks"]:
        result["mfu"] = round(tok_s * model.flops_per_token(seq)
                              / info["peaks"]["peak_flops"], 4)
    return result


# ---------------------------------------------------------------------------
# leg: serve
# ---------------------------------------------------------------------------

def _post(fd, prompt, *, stream: bool, max_new_tokens: int):
    """One ``POST /v1/generate`` over the loopback socket -> token list."""
    import http.client

    conn = http.client.HTTPConnection(fd.host, fd.port, timeout=300)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps({
            "prompt": [int(t) for t in prompt],
            "max_new_tokens": max_new_tokens, "stream": stream}).encode())
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    assert resp.status == 200, (resp.status, raw[:300])
    if not stream:
        return json.loads(raw)["tokens"]
    tokens, done, event = [], None, "message"
    for line in raw.decode().splitlines():
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            doc = json.loads(line[len("data: "):])
            if event == "done":
                done = doc
            elif event == "error":
                raise AssertionError(f"stream ended in error: {doc}")
            else:
                tokens.append(doc["token"])
        elif not line:
            event = "message"
    assert done is not None and done["tokens"] == tokens, (done, tokens)
    return tokens


def _paged_kernel_check(*, on_chip=True) -> dict:
    """The compiled kernel against ``paged_attention_dense`` at the serve
    leg's own shapes, on both storage legs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.serving.kv_cache import quantize_pages

    b, s, ps = SERVE_MAX_BATCH, SERVE_MAX_LEN // SERVE_PAGE, SERVE_PAGE
    pages = b * s + 1
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(7), 4)
    poolf = jax.random.normal(k1, (pages, 1, 2, HEADS, ps, HEAD_DIM),
                              jnp.float32)
    q = jax.random.normal(k2, (b, HEADS, HEAD_DIM), jnp.bfloat16)
    kn = jax.random.normal(k3, (b, HEADS, HEAD_DIM), jnp.bfloat16)
    vn = jax.random.normal(k4, (b, HEADS, HEAD_DIM), jnp.bfloat16)
    rng = np.random.default_rng(7)
    t_np = rng.integers(0, SERVE_MAX_LEN - 1, size=b)
    t_np[0], t_np[1], t_np[2] = SERVE_MAX_LEN - 2, ps - 1, ps
    tables = np.zeros((b, s), np.int32)
    nxt = 1
    for i in range(b):
        for j in range(int(t_np[i]) // ps + 1):
            tables[i, j] = nxt
            nxt += 1
    tables, t = jnp.asarray(tables), jnp.asarray(t_np, jnp.int32)
    layer = jnp.asarray(0, jnp.int32)
    q8, sc = quantize_pages(poolf)
    errs = {}
    for name, pool, scales in (("bf16", poolf.astype(jnp.bfloat16), None),
                               ("int8", q8, sc)):
        assert pa.kernel_eligible(ps, HEAD_DIM, pool.dtype, HEADS)
        got = pa.paged_attention(q, kn, vn, pool, scales, tables, t, layer,
                                 page_size=ps, impl="kernel",
                                 interpret=not on_chip)
        with jax.default_matmul_precision("highest"):
            want = pa.paged_attention_dense(q, kn, vn, pool, scales, tables,
                                            t, layer, page_size=ps)
        err = float(np.abs(np.asarray(got, np.float32)
                           - np.asarray(want, np.float32)).max())
        errs[name] = err
        assert err <= PAGED_KERNEL_TOL, (name, err)
    log(f"serve: compiled kernel vs paged_attention_dense, max |err| {errs}")
    return errs


def pool_copies(text: str, pool_shape) -> int:
    """How many ``copy`` operations of a compiled program's text produce
    an array of the page pool's shape, in whatever layout. The decode
    program takes the pool donated and writes it in place: it should hold
    none (the program before ISSUE 26 held 1 / 8 / 8 in buckets 1 / 4 /
    16: the argument was not donated, and each layer's scatter ran in a
    layout of its own, copied back for the next layer's kernel)."""
    import re
    dims = ",".join(str(int(d)) for d in pool_shape)
    return len(re.findall(
        r"= \w+\[" + re.escape(dims) + r"\]\S* copy\(", text))


def leg_serve(depth=None, *, config=None, on_chip=True) -> dict:
    info = open_leg(require_tpu=on_chip)
    import concurrent.futures as cf
    import gc

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    compiles = CompileCounter()
    obs.enable()
    limit = hbm()["limit"]
    if depth is None:
        depth = pick_depth(serve_bytes, limit)
    cfg = config or LlamaConfig.llama2_7b()
    cfg.num_hidden_layers = depth
    cfg.scan_layers = cfg.recompute = False
    heads = cfg.num_key_value_heads
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    max_len = min(SERVE_MAX_LEN, cfg.max_position_embeddings)
    log(f"serve: depth {depth} of 32, max_len {max_len}, page {SERVE_PAGE}, "
        f"max_batch {SERVE_MAX_BATCH}, buckets {SERVE_BUCKETS}")

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    prefill_fn, step_fn = model.serving_callables(max_len)
    log(f"serve: {model.num_params():,} parameters; after build {hbm()}")

    def build_engine(name, paged, max_batch, buckets):
        return serving.Engine(prefill_fn, step_fn, serving.ServingConfig(
            num_layers=depth, num_heads=heads, head_dim=head_dim,
            max_len=max_len, name=name, max_batch=max_batch,
            buckets=buckets, page_size=SERVE_PAGE,
            num_pages=SERVE_MAX_BATCH * (max_len // SERVE_PAGE) + 1,
            compute_dtype="bfloat16", paged_attention=paged))

    rng = np.random.default_rng(1)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)

    solo = [prompt(n) for n in SERVE_PROMPT_LENS]          # A, B, C
    crowd = [prompt(SERVE_PROMPT_LENS[i % 3]) for i in range(6)]
    n_new = SERVE_NEW_TOKENS

    # -- the engine under test: auto resolves the decode tier ------------
    t0 = time.perf_counter()
    engine = build_engine("r0", "auto", SERVE_MAX_BATCH, SERVE_BUCKETS)
    want_path = "kernel" if on_chip else "dense"
    assert engine._paged_path == want_path, engine._paged_path
    engine.warmup(prompt_lens=SERVE_PROMPT_LENS)
    warmup_s = time.perf_counter() - t0
    # what only the chip's compiler can say: no decode bucket copies the
    # page pool (each compiled_text() compiles once more, before the
    # no-compile window opens)
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    copies = {}
    for b in SERVE_BUCKETS:
        engine.programs.warm(buckets=[b])
        copies[b] = pool_copies(
            engine.programs.decode_program.compiled_text(),
            engine.kv.pool.shape)
    paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
    log(f"serve: pool-shaped copies in the compiled decode programs, by "
        f"bucket: {copies}")
    if on_chip:
        assert not any(copies.values()), \
            f"a decode program copies the page pool: {copies}"
    warm_compiles, warm_compile_s = compiles.count, compiles.seconds
    log(f"serve: warmup {warmup_s:.1f}s ({warm_compiles} backend compiles, "
        f"{warm_compile_s:.1f}s); {jax.devices()[0].memory_stats()}")

    # from here to the end of the no-compile window, jax names whatever
    # it compiles
    jax.config.update("jax_log_compiles", True)
    router = serving.Router([("r0", engine)]).start()
    fd = serving.FrontDoor(router)
    try:
        # one at a time (bucket 1), unary: the transcripts of record
        t0 = time.perf_counter()
        transcripts = [_post(fd, p, stream=False, max_new_tokens=n_new)
                       for p in solo]
        solo_s = time.perf_counter() - t0
        # six in flight, unary and streaming mixed: fills the 16 bucket
        with cf.ThreadPoolExecutor(len(crowd)) as pool:
            futs = [pool.submit(_post, fd, p, stream=bool(i % 2),
                                max_new_tokens=n_new)
                    for i, p in enumerate(crowd)]
            crowd_out = [f.result(timeout=600) for f in futs]
        # the first request again, streamed this time: the same tokens
        again = _post(fd, solo[0], stream=True, max_new_tokens=n_new)
        for toks in transcripts + crowd_out + [again]:
            assert len(toks) == n_new, toks
            assert all(0 <= t < cfg.vocab_size for t in toks), toks
        assert again == transcripts[0], (again, transcripts[0])
        after_warmup = compiles.count - warm_compiles
        assert after_warmup == 0, \
            f"{after_warmup} compilation(s) after warmup() on warmed shapes"
        jax.config.update("jax_log_compiles", False)

        # outside the no-compile window: the longest prompt once more maps
        # its resident prefix pages and prefills only the tail (one more
        # program, compiled on first use by design)
        req0, comp0 = engine.prefill_token_stats()
        shared = _post(fd, solo[2], stream=False, max_new_tokens=n_new)
        req1, comp1 = engine.prefill_token_stats()
        assert len(shared) == n_new
        assert comp1 - comp0 < req1 - req0, \
            "prefix sharing computed the whole repeated prompt"
        log(f"serve: repeated {SERVE_PROMPT_LENS[2]}-token prompt prefilled "
            f"{comp1 - comp0} of {req1 - req0} tokens; same transcript: "
            f"{shared == transcripts[2]}")
    finally:
        router.stop(drain=True, timeout=120)
        fd.close()
    assert engine.kv.outstanding_pages == 0, engine.kv.outstanding_pages
    steps = obs.snapshot().get("serving.paged_attention_steps_total", {})
    other = "dense" if want_path == "kernel" else "kernel"
    assert steps.get(f"path={want_path}", 0) > 0 and \
        steps.get(f"path={other}", 0) == 0, steps
    mem = check_hbm("serve", serve_bytes(depth))
    log(f"serve: decode steps by tier {steps}; solo requests "
        f"{solo_s / len(solo) * 1e3 / n_new:.1f} ms/token end to end")

    # -- beside the engine ------------------------------------------------
    del router, fd, engine
    gc.collect()
    kernel_errs = _paged_kernel_check(on_chip=on_chip)
    # not gated: greedy transcripts against the same model on the dense tier
    off = build_engine("off", "off", 1, (1,)).warmup(SERVE_PROMPT_LENS)
    futs = [off.submit(serving.GenerationRequest(p, max_new_tokens=n_new))
            for p in solo]
    off.run()
    off_tokens = [f.result(timeout=600).tokens for f in futs]
    off.stop(drain=True, timeout=60)           # retires its beacon too
    same = sum(a == b for x, y in zip(transcripts, off_tokens)
               for a, b in zip(x, y))
    match = same / (len(solo) * n_new)
    log(f"serve: transcript match vs paged_attention='off': {match:.3f}")

    return {
        "leg": "serve", "depth": depth, "params": model.num_params(),
        "decode_tier": want_path, "decode_steps": steps,
        "warmup_s": round(warmup_s, 1),
        "compile_s": round(warm_compile_s, 1),
        "compiles_in_warmup": warm_compiles,
        "compiles_after_warmup": after_warmup,
        "pool_copies_by_bucket": {str(b): n for b, n in copies.items()},
        "kernel_vs_dense_max_err": kernel_errs,
        "transcript_match_vs_off": round(match, 3),
        "prefix_shared_prefill": [comp1 - comp0, req1 - req0],
        "hbm": mem, **info,
    }


# ---------------------------------------------------------------------------
# leg: hybrid (four chips)
# ---------------------------------------------------------------------------

def leg_hybrid(depth=2, *, seq=2048, batch=2, steps=3, config=None,
               on_chip=True) -> dict:
    info = open_leg(require_tpu=on_chip)
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    assert len(jax.devices()) >= 4, jax.devices()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    log(f"hybrid: {mesh}")

    cfg = config or LlamaConfig.llama2_7b()
    cfg.num_hidden_layers = depth
    cfg.max_position_embeddings = max(seq, 128)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)          # mp layers: fleet is initialised
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16", master_weight=False)
    wrapped = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    ids = wrapped.shard_input(paddle.to_tensor(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq),
                                          dtype=np.int32)))

    def body(ids):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss, _ = wrapped(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.capture_step(body)
    t0 = time.perf_counter()
    losses = [float(np.asarray(step(ids)._data))]
    first_call_s = time.perf_counter() - t0
    for _ in range(steps):
        losses.append(float(np.asarray(step(ids)._data)))
    log(f"hybrid: first call {first_call_s:.1f}s, losses "
        f"{[round(x, 4) for x in losses]}")
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    if on_chip:
        # Mosaic kernels cannot be partitioned automatically: under the
        # mesh they are in the step because they run per shard
        text = step.compiled_text()
        for kernel in KERNELS_IN_STEP[:3]:
            assert kernel in text, f"{kernel} missing from the hybrid step"

    # every device holds its shard — nothing piled on the first
    w = model.model.layers[0].mlp.gate_proj.weight._data
    shards = {s.device.id: tuple(s.data.shape) for s in w.addressable_shards}
    full = tuple(w.shape)
    assert len(shards) == 4, shards
    assert all(sh == (full[0], full[1] // 2) for sh in shards.values()), \
        (full, shards)
    in_use = [hbm(i)["in_use"] for i in range(4)]
    log(f"hybrid: gate_proj {full} shards {shards}; bytes in use {in_use}")
    if on_chip:
        assert min(in_use) > 0.5 * max(in_use), in_use
    return {"leg": "hybrid", "depth": depth, "mesh": str(mesh),
            "losses": [round(x, 4) for x in losses],
            "first_call_s": round(first_call_s, 1),
            "shard_shapes": {str(k): v for k, v in shards.items()},
            "bytes_in_use": in_use, **info}


LEGS = {"train": leg_train, "serve": leg_serve, "hybrid": leg_hybrid}


# ---------------------------------------------------------------------------
# the parent: never imports jax
# ---------------------------------------------------------------------------

def run_leg(name: str, budget_s: float):
    """Run one leg in a child; echo its output; return its RESULT dict, or
    None when it failed. The child's whole process group dies with it."""
    log(f"== leg {name}: starting (budget {budget_s:.0f}s)")
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--leg", name],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        start_new_session=True)
    result = None

    def on_alarm(signum, frame):
        raise TimeoutError(f"leg {name} exceeded {budget_s:.0f}s")

    try:
        # the read loop ends at the child's EOF; the alarm bounds a child
        # that neither prints nor exits
        signal.signal(signal.SIGALRM, on_alarm)
        signal.alarm(max(1, int(budget_s)))
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
            else:
                log(f"[{name}] {line}")
        rc = proc.wait()
        signal.alarm(0)
    except TimeoutError as e:
        log(f"== leg {name}: {e}")
        rc = -1
    finally:
        signal.alarm(0)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    log(f"== leg {name}: exit {rc} after {time.monotonic() - t0:.1f}s")
    return result if rc == 0 else None


def result_line(device: dict) -> str:
    """The last line of a passing run, to the letter of the contract: the
    keys ``ok`` and ``device``, and in ``device`` the keys ``platform``,
    ``kind`` (text) and ``count`` (a whole number) — nothing else. What the
    legs observed goes on the ``SUMMARY`` line above it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    deadline = time.monotonic() + TOTAL_BUDGET_S
    results = {}
    for name in LEGS:
        if name == "hybrid":
            count = results["train"]["device"]["count"]
            if count < 4:
                log(f"== leg hybrid: not run — four chips needed, "
                    f"{count} visible")
                continue
        budget = min(LEG_BUDGET_S[name], deadline - time.monotonic())
        res = run_leg(name, budget) if budget > 5 else None
        if res is None:
            log(f"chip_smoke: leg {name} failed")
            return 1
        results[name] = res
    log("SUMMARY " + json.dumps({"legs": results, "claim": None}))
    log(result_line(results["train"]["device"]))
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--leg":
        print("RESULT " + json.dumps(LEGS[sys.argv[2]]()), flush=True)
        sys.exit(0)
    sys.exit(main())
