"""Qwen3-Next (ISSUE 33) on the CPU at a tiny size — two periods of (delta,
delta, full), 8 experts chosen 3 at a time with a gated shared expert —
against ``perfbench/reference_qwen3_next.py``, the plain float32 forward
that shares nothing with ``paddle_tpu`` and runs the delta rule token by
token:

* the model's full forward, logits, at lengths that are no multiple of the
  chunk or the block; the reference's controls are told from it;
* the chunked delta-rule prefill against the token-by-token recurrence from
  a zero and a non-zero state; ``gated_delta_decode`` and the convolution's
  tail in interpret mode against their dense forms, padding rows leaving the
  real rows' states untouched;
* the share: the four shares of the experts, the shared expert counted once,
  add up to the uncut layer, and the four vocabulary slices concatenate to
  the whole logits;
* softmax / ``gated sum`` and sigmoid / ``average`` both through
  ``DroplessMoE``;
* through the compiled programs — pages, the state pool's two parts, both
  decode tiers: prefill, a tail from a snapshot of state AND tail, and
  decode steps, LOGITS against the reference's full forward;
* through the engine: two slots decode while a third is admitted; a
  follow-up ask prefills only its tail and equals a full prefill; the state
  and the tail kept at the boundary are the reference's.

Tolerances: float32 on both sides, so ``TOL`` = 3e-5 is rounding in a
different order of summation; the controls move logits by 1e-3 and more.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.core.tensor import Tensor as T
from paddle_tpu.incubate.moe import DroplessMoE
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                          Qwen3NextForCausalLM)
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.serving import kv_cache as kvc

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench import reference_qwen3_next as ref  # noqa: E402

TOL = 3e-5
MAX_LEN, BLOCK, PAGE, V = 96, 8, 4, 96


def ref_cfg(c, **over):
    d = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    first, count = c.experts_held
    return dict(ref.reference_config(d, c.layers_run, c.num_experts,
                                     range(first, first + count)), **over)


@pytest.fixture(scope="module")
def model():
    paddle.seed(33)
    m = Qwen3NextForCausalLM(Qwen3NextConfig.tiny())
    m.eval()
    return m


def _ids(seed, n, vocab=V):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _ref_logits(m, ids, **over):
    cfg = ref_cfg(m.config, **over)
    return np.asarray(jax.jit(lambda p, i: ref.logits(p, i, cfg))(
        ref.params_of(m), jnp.asarray(ids)))


def _gaps(m, prompt, tokens):
    full = np.concatenate([prompt, tokens])
    rows = _ref_logits(m, full[:-1])[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(tokens)), tokens]


@pytest.mark.parametrize("length", [5, 13, 45, 70])
def test_forward_agrees_with_the_reference(model, length):
    ids = _ids(length, length)
    got = np.asarray(model.forward(paddle.to_tensor(ids), block=BLOCK)._data)
    assert np.abs(got - _ref_logits(model, ids)).max() < TOL


@pytest.mark.parametrize("control", ["bf16_state", "no_delta",
                                     "fp8_weights"])
def test_the_references_controls_are_told_from_the_sound_forward(model,
                                                                 control):
    ids = _ids(70, 70)
    got = np.asarray(model.forward(paddle.to_tensor(ids), block=BLOCK)._data)
    assert np.abs(got - _ref_logits(model, ids, control=control)).max() \
        > 5 * TOL


# ---------------------------------------------------------------------------
# the delta rule's own functions
# ---------------------------------------------------------------------------

def _delta_inputs(seed, t, h=4, d=16):
    rng = np.random.default_rng(seed)

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = l2(rng.normal(size=(t, h, d))) / np.sqrt(d)
    k = l2(rng.normal(size=(t, h, d)))
    v = rng.normal(size=(t, h, d))
    g = -rng.uniform(0, 3, size=(t, h))
    beta = rng.uniform(0, 1, size=(t, h))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("start", ["zero", "non-zero"])
@pytest.mark.parametrize("length", [150, 64, 7])
def test_chunked_delta_rule_is_the_token_by_token_recurrence(start, length):
    args = _delta_inputs(length, length)
    h, d = args[0].shape[1:]
    S0 = jnp.zeros((h, d, d), jnp.float32) if start == "zero" else \
        jnp.asarray(np.random.default_rng(9).normal(size=(h, d, d)) * 0.3,
                    jnp.float32)
    out, S = la.chunked_gated_delta_rule(*args, S0, chunk=64)
    want, St = [], S0[None]
    for t in range(length):
        o, St = la.gated_delta_dense(*(a[t:t + 1] for a in args), St)
        want.append(o[0])
    assert np.abs(np.asarray(out) - np.stack(want)).max() < 1e-5
    assert np.abs(np.asarray(S) - np.asarray(St[0])).max() < 1e-5


def test_delta_decode_kernel_is_its_dense_form_and_padding_touches_nothing():
    b, layers = 4, 2
    args = _delta_inputs(3, b)
    h, d = args[0].shape[1:]
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(6, layers, h, d, d)), jnp.float32)
    rows = jnp.asarray([3, 0, 5, 0], jnp.int32)      # two padding rows
    out, new = la.gated_delta_decode(*args, pool, rows, 1, impl="kernel",
                                     interpret=True)
    want, dense = la.gated_delta_dense(*args, pool[rows, 1])
    for i in (0, 2):
        assert np.abs(np.asarray(out[i]) - np.asarray(want[i])).max() < 1e-6
        assert np.abs(np.asarray(new[rows[i], 1])
                      - np.asarray(dense[i])).max() < 1e-6
    # every row not named, every other layer, is as it was
    kept = np.ones(pool.shape[:2], bool)
    kept[[3, 0, 5], 1] = False
    assert (np.asarray(new)[kept] == np.asarray(pool)[kept]).all()
    out2, new2 = la.gated_delta_decode(*args, pool, rows, 1, impl="dense")
    assert np.abs(np.asarray(out2[0]) - np.asarray(out[0])).max() < 1e-6
    assert np.abs(np.asarray(new2[3]) - np.asarray(new[3])).max() < 1e-6


def test_conv_tail_kernel_is_its_dense_form_and_shifts_in_place():
    rng = np.random.default_rng(6)
    b, taps, r, c, layers = 3, 4, 2, 32, 2
    pool = jnp.asarray(rng.normal(size=(5, layers, taps - 1, r, c)),
                       jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, r, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, r, c)), jnp.float32)
    rows = jnp.asarray([2, 0, 4], jnp.int32)
    y, new = la.conv_tail_decode(x, w, pool, rows, 1, impl="kernel",
                                 interpret=True)
    y2, new2 = la.conv_tail_decode(x, w, pool, rows, 1, impl="dense")
    assert np.abs(np.asarray(y) - np.asarray(y2)).max() < 1e-5
    assert (np.asarray(new)[[2, 4]] == np.asarray(new2)[[2, 4]]).all()
    assert (np.asarray(new)[2, 1, -1] == np.asarray(x[0])).all()
    assert (np.asarray(new)[2, 1, 0] == np.asarray(pool)[2, 1, 1]).all()
    assert (np.asarray(new)[[1, 3]] == np.asarray(pool)[[1, 3]]).all()
    assert (np.asarray(new)[:, 0] == np.asarray(pool)[:, 0]).all()


# ---------------------------------------------------------------------------
# the share, and the expert layer's two forms
# ---------------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer_and_vocabulary(model):
    """One layer's expert block: what the four chips that share it compute
    — each its quarter of the routed experts, and alike the shared expert,
    counted once — adds up to the uncut reference; the four vocabulary
    slices of the head concatenate to the whole logits."""
    c = model.config
    p = ref.params_of(model)["layers"][0]
    h = jnp.asarray(np.random.default_rng(8).normal(size=(21, c.hidden_size)),
                    jnp.float32)
    whole = ref_cfg(c)
    with jax.default_matmul_precision("highest"):
        want, over = ref.moe(h, p, whole)
        assert int(over) == 0
        zero_shared = dict(p, shared_down=jnp.zeros_like(p["shared_down"]))
        shared = np.asarray(want) - np.asarray(ref.moe(h, zero_shared,
                                                       whole)[0])
    per = c.num_experts // 4
    total = np.zeros_like(shared)
    for chip in range(4):
        moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                          c.num_experts, c.num_experts_per_tok,
                          experts_held=(chip * per, per), num_shared=1,
                          score="softmax", shared="gated sum",
                          d_ff_shared=c.shared_expert_intermediate_size)
        cut = slice(chip * per, (chip + 1) * per)
        for name, value in (("router", p["router"]),
                            ("w_gate", p["gate"][cut]),
                            ("w_up", p["up"][cut]),
                            ("w_down", p["down"][cut]),
                            ("shared_gate", p["shared_gate"]),
                            ("shared_up", p["shared_up"]),
                            ("shared_down", p["shared_down"]),
                            ("shared_score", p["shared_score"])):
            getattr(moe, name)._set_data(value)
        out, rows = moe(T(h))
        assert int(np.asarray(rows._data).sum()) > 0
        total += np.asarray(out._data) - shared      # the routed part
    assert np.abs(total + shared - np.asarray(want)).max() < TOL
    # the vocabulary: each chip's slice of the head is its columns
    ids = _ids(17, 30)
    full = _ref_logits(model, ids)
    quarter = V // 4
    params = ref.params_of(model)
    parts = [np.asarray(jax.jit(lambda pp, i: ref.logits(pp, i, whole))(
        dict(params, head=params["head"][:, n * quarter:(n + 1) * quarter]),
        jnp.asarray(ids))) for n in range(4)]
    assert np.abs(np.concatenate(parts, -1) - full).max() < TOL


@pytest.mark.parametrize("score,shared", [("softmax", "gated sum"),
                                          ("sigmoid", "average")])
def test_both_forms_of_the_expert_layer_through_dropless_moe(score, shared):
    """``DroplessMoE`` by hand: scores over all experts, the top k
    renormalised, every pair computed; the shared experts averaged, or added
    under their sigmoid gate."""
    paddle.seed(5)
    e, f, n, k, s = 16, 8, 6, 2, 2
    moe = DroplessMoE(e, f, n, k, num_shared=s, score=score, shared=shared)
    assert (moe.shared_score is not None) == (shared == "gated sum")
    x = np.random.default_rng(3).normal(size=(9, e)).astype(np.float32)
    out, rows = moe(T(jnp.asarray(x)))
    w = {name: np.asarray(getattr(moe, name)._data, np.float64)
         for name in ("router", "w_gate", "w_up", "w_down", "shared_gate",
                      "shared_up", "shared_down")}

    def silu(a):
        return a / (1 + np.exp(-a))
    logits = x.astype(np.float64) @ w["router"]
    sc = 1 / (1 + np.exp(-logits)) if score == "sigmoid" else \
        np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.zeros_like(x, np.float64)
    for i in range(x.shape[0]):
        top = np.argsort(-sc[i])[:k]
        for j in top:
            y = (silu(x[i] @ w["w_gate"][j]) * (x[i] @ w["w_up"][j])) \
                @ w["w_down"][j]
            want[i] += sc[i, j] / sc[i, top].sum() * y
    sh = (silu(x @ w["shared_gate"]) * (x @ w["shared_up"])) \
        @ w["shared_down"]
    if shared == "average":
        want += sh / s
    else:
        gate = x @ np.asarray(moe.shared_score._data, np.float64)
        want += sh / (1 + np.exp(-gate))
    assert np.abs(np.asarray(out._data) - want).max() < 1e-5
    assert int(np.asarray(rows._data).sum()) == x.shape[0] * k
    with pytest.raises(ValueError, match="score must be"):
        DroplessMoE(e, f, n, k, score="tanh")


# ---------------------------------------------------------------------------
# through the compiled programs and the engine
# ---------------------------------------------------------------------------

def _engine(m, tier="off", with_logits=False, **over):
    c = m.config
    prefill_fn, step_fn = m.serving_callables(MAX_LEN, block=BLOCK,
                                              with_logits=with_logits)
    kw = dict(num_layers=len(c.layers_run), num_heads=c.num_key_value_heads,
              head_dim=c.head_dim, max_len=MAX_LEN, max_batch=3,
              buckets=(1, 3), page_size=PAGE, compute_dtype="float32",
              layer_kinds=c.layer_kinds, state_shape=c.state_shapes,
              state_snapshot_tokens=BLOCK, paged_attention=tier)
    kw.update(over)
    return serving.Engine(prefill_fn, step_fn, serving.ServingConfig(**kw))


def _i32(x):
    return T(jnp.asarray(x, jnp.int32))


def test_full_and_linear_is_a_legal_model_with_a_two_part_state(model):
    eng = _engine(model)
    c = model.config
    assert eng.index is None and eng.snapshots is not None
    assert [p.shape for p in eng.state.parts] == [
        (4, 4) + c.state_shapes[0], (4, 4) + c.state_shapes[1]]
    assert eng.kv.pool.shape[1] == 2                 # the full layers' pages
    assert eng.state.row_bytes == 4 * 4 * (
        int(np.prod(c.state_shapes[0])) + int(np.prod(c.state_shapes[1])))
    with pytest.raises(ValueError, match="one kind of pages"):
        serving.ServingConfig(
            num_layers=3, num_heads=2, head_dim=8, max_len=32, page_size=4,
            layer_kinds=("full", "window", "linear"), window=8,
            state_shape=(2, 2))


@pytest.mark.parametrize("tier", ["off", "on"])
def test_logits_through_pages_state_and_tail(model, tier):
    """Prefill two prompts, a third as a tail from the first one's snapshot
    of state and tail, then decode all three in one bucket: every program's
    logits against the reference's full forward of what the row has seen,
    and the expert rows the step counted."""
    eng = _engine(model, tier, with_logits=True)
    p, kv = eng.programs, eng.kv
    c = model.config
    doc = _ids(1, 40)                       # 5 snapshot boundaries
    prompts = [np.concatenate([doc, _ids(2, 5)]), _ids(3, 70),
               np.concatenate([doc, _ids(4, 7)])]
    seen, rows, pages = [], [], []
    got = [[], [], []]                      # per row: (length seen, logits)
    snaps = None
    for n, prompt in enumerate(prompts):
        start = 40 if n == 2 else 0
        ids = kv.alloc(kv.pages_for(len(prompt) + 8) - start // PAGE)
        if start:                           # map the first prompt's pages
            ids = pages[0][:start // PAGE] + ids
        row = eng.state.alloc()
        step = p.prefill(
            _i32(prompt[None, start:]), [_i32(kv.table_row(ids))],
            _i32(len(prompt)), start, _i32(row),
            tuple(T(s[start // BLOCK - 1]) for s in snaps) if start
            else None)
        (tok,), extras = step.read()
        counted, lg = model.split_extras(extras, 1)
        assert counted.sum() == (len(prompt) - start) \
            * c.num_experts_per_tok * len(c.layers_run)
        got[n].append((len(prompt), lg[0]))
        if n == 0:
            snaps = [part._data for part in step.extra]
            assert [s.shape[0] for s in snaps] == [len(prompt) // BLOCK] * 2
        seen.append(np.append(prompt, tok))
        rows.append(row)
        pages.append(ids)
    carry = p.no_carry
    for _ in range(6):
        t = [len(s) - 1 for s in seen]
        step = p.decode(
            _i32([[s[-1]] for s in seen]),
            [_i32(np.stack([kv.table_row(i) for i in pages]))],
            _i32(t), carry, _i32([-1] * 3), _i32(rows))
        toks, extras = step.read()
        counted, lg = model.split_extras(extras, 3)
        assert counted.sum() == 3 * c.num_experts_per_tok * len(c.layers_run)
        for n in range(3):
            got[n].append((len(seen[n]), lg[n]))
            seen[n] = np.append(seen[n], toks[n])
    assert not p.pools_lost()
    for n in range(3):                      # teacher-forced, once a row
        want = _ref_logits(model, seen[n][:-1])
        assert len(got[n]) == 7
        for length, logits in got[n]:
            assert np.abs(logits - want[length - 1]).max() < TOL


def _serve(eng, prompts, n, hand=()):
    futs = [eng.submit(serving.GenerationRequest(prompt=p, max_new_tokens=n))
            for p in prompts]
    for _ in range(4 if len(hand) else 0):
        eng.step()
    futs += [eng.submit(serving.GenerationRequest(prompt=p, max_new_tokens=n))
             for p in hand]
    eng.run()
    return [np.asarray(f.result(timeout=60).tokens) for f in futs]


@pytest.mark.parametrize("tier", ["off", "on"])
def test_two_slots_decode_while_a_third_is_admitted(model, tier):
    eng = _engine(model, tier)
    prompts = [_ids(11, 45), _ids(12, 70)]
    late = _ids(13, 33)
    outs = _serve(eng, prompts, 12, hand=[late])
    for prompt, toks in zip(prompts + [late], outs):
        assert len(toks) == 12 and _gaps(model, prompt, toks).max() < TOL
    assert eng.kv.outstanding_pages == 0
    assert eng.state.free_rows == eng.config.max_batch


def test_a_follow_up_starts_from_the_snapshot_of_state_and_tail(model):
    obs.enable()
    doc = _ids(21, 48)
    asks = [np.concatenate([doc, _ids(22 + i, 6)]) for i in range(2)]
    before = dict(obs.snapshot())
    shared = _engine(model)
    first, follow = (_serve(shared, [a], 8)[0] for a in asks)
    assert shared.prefill_token_stats() == (2 * 54, 54 + 6)
    after = obs.snapshot()
    assert after.get("serving.state.snapshot_hits_total", 0) \
        - before.get("serving.state.snapshot_hits_total", 0) == 1
    assert after["serving.state.snapshot_bytes"] == shared.snapshots.nbytes
    assert after["serving.state.row_bytes"] == shared.state.row_bytes
    assert shared.snapshots.nbytes == 6 * shared.state.row_bytes
    assert after.get("serving.moe.rows_total", 0) \
        > before.get("serving.moe.rows_total", 0)
    alone = _engine(model, prefix_sharing="off")
    assert (_serve(alone, [asks[1]], 8)[0] == follow).all()
    assert alone.prefill_token_stats() == (54, 54)
    for ask, toks in zip(asks, (first, follow)):
        assert _gaps(model, ask, toks).max() < TOL
    # the state and the tail kept at the document's end are the reference's;
    # a bfloat16 state and an update without its correction are told from it
    state, tail = shared.snapshots.get_parts(kvc.prefix_chain_digests(
        asks[0], shared.config.page_size, limit=48 // PAGE)[-1])
    a_log = np.stack([np.asarray(layer.A_log._data)
                      for layer in model.layers if not layer.full])
    for control, told in (("", False), ("bf16_state", True),
                          ("no_delta", True)):
        want = jax.jit(lambda p_, i: ref.answer_rows(
            p_, i, 54, i[:1], ref_cfg(model.config, control=control), 48))(
            ref.params_of(model), jnp.asarray(asks[0]))
        _, worst, _ = ref.state_distance(state, want["states"], a_log)
        assert (worst > 1e-4) == told
        if not control:
            assert ref.tail_distance(tail, want["tails"]) < 1e-5
