"""Command A+ (``cohere2_moe``, ISSUE 27) at a tiny size on the CPU, against
the benchmark's plain reference (``perfbench/reference_cohere2_moe.py``,
imported: one reference, not two).

The tiny configuration keeps every mechanism: hidden 64, 8 query heads on 2
KV heads with ``head_dim`` 16 != 64 / 8, 8 experts top-2 with 2 shared,
window 8 on pages of 4, 4 layers of the published pattern (window x3, full).
Weights are seeded random float32.

Tolerances, and why: model, engine and reference compute the same float32
sums in other orders (sorted grouped matmul against dense masked experts;
online softmax over pages against one softmax), so logits agree to a few
float32 ulps of values of order 1: ``LOGIT_TOL`` 2e-5 is a hundred times
what was seen (2e-7) and a thousand times under the gap between two
different tokens' logits. Greedy tokens are compared exactly: at these
sizes the two largest logits lie thousandths apart at the closest, far
above 2e-5. The kernel tier under the Pallas interpreter against the dense
tier is held to the same exact tokens.
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
from paddle_tpu.incubate.moe import DroplessMoE
from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                           Cohere2MoeForCausalLM)
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.serving.scheduler import GenerationRequest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench import reference_cohere2_moe as ref  # noqa: E402

LOGIT_TOL = 2e-5
MAX_LEN, PAGE, WINDOW = 64, 4, 8
HELD = (2, 4)                      # experts 2..5 of 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(1)
    m = Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny(experts_held=HELD))
    m.eval()
    return m


def _ref_logits(m, ids, experts=None):
    cfg = dataclasses.asdict(m.config)
    first, count = m.config.experts_held
    experts = range(first, first + count) if experts is None else experts
    return np.asarray(ref.logits(ref.params_of(m), jnp.asarray(ids), cfg,
                                 experts))


def _is_ref_greedy(m, prompt, tokens):
    """Whether ``tokens`` is the reference's greedy continuation of
    ``prompt``: one teacher-forced pass over prompt + tokens — each token
    must be the reference's argmax at its position (attention is causal, so
    this is the token-by-token loop, without its recompiles)."""
    ids = np.concatenate([prompt, tokens]).astype(np.int64)
    rows = _ref_logits(m, ids)[len(prompt) - 1:-1]
    return len(tokens) > 0 and rows.argmax(-1).tolist() == list(tokens)


def _engine(m, tier, **over):
    pf, sf = m.serving_callables(MAX_LEN)
    c = m.config
    kw = dict(num_layers=c.num_hidden_layers, num_heads=c.num_key_value_heads,
              head_dim=c.head_dim, max_len=MAX_LEN, max_batch=4,
              buckets=(1, 4), page_size=PAGE, layer_kinds=c.layer_kinds,
              window=c.sliding_window, paged_attention=tier)
    kw.update(over)
    return serving.Engine(pf, sf, serving.ServingConfig(**kw))


def _serve(eng, prompts, n):
    futs = [eng.submit(GenerationRequest(np.asarray(p, np.int32),
                                         max_new_tokens=n)) for p in prompts]
    eng.run()
    return [f.result().tokens for f in futs]


# -- the model ------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 40, 100])
def test_forward_agrees_with_the_reference(model, length):
    ids = np.random.default_rng(length).integers(0, 96, length)
    got = np.asarray(model(paddle.to_tensor(ids.astype("int32")))._data)
    assert np.abs(got - _ref_logits(model, ids)).max() < LOGIT_TOL


def test_forward_through_the_flash_band_agrees_with_the_reference():
    """A run long enough for the padded flash path (512 rows and more),
    the window far shorter than the run."""
    paddle.seed(2)
    m = Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny(
        experts_held=HELD, num_hidden_layers=2, sliding_window=200,
        max_position_embeddings=1024))
    m.eval()
    ids = np.random.default_rng(0).integers(0, 96, 600)
    got = np.asarray(m(paddle.to_tensor(ids.astype("int32")))._data)
    assert np.abs(got - _ref_logits(m, ids)).max() < 5 * LOGIT_TOL


def test_generate_is_the_references_greedy(model):
    prompt = np.random.default_rng(3).integers(0, 96, 12)
    out = np.asarray(model.generate(
        paddle.to_tensor(prompt[None].astype("int32")), 3)._data)[0]
    assert len(out) == 15 and _is_ref_greedy(model, prompt, out[12:])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Routed parts of all eight shares + attention and the shared experts
    counted once = the reference's layer with every expert."""
    paddle.seed(4)
    base = Cohere2MoeConfig.tiny(num_experts=16, num_hidden_layers=1,
                                 layer_types=("sliding_attention",))
    full = Cohere2MoeForCausalLM(base)
    full.eval()
    layer = full.layers[0]
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (24, 64)).astype("float32"))
    cfg = dataclasses.asdict(base)
    p = ref.params_of(full)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.layer(x._data, p, cfg, "sliding_attention",
                                    range(16)))
        h = ref._layer_norm(x._data, p["norm"], 1e-5)
        once = np.asarray(x._data + ref.attention(
            h, p, cfg, "sliding_attention") + ref.moe(h, p, cfg, []))
    routed = np.zeros_like(want)
    rows = 0
    for share in range(8):
        moe = DroplessMoE(64, 32, 16, 2, experts_held=(2 * share, 2))
        moe.router._set_data(layer.moe.router._data)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(moe, name)._set_data(
                getattr(layer.moe, name)._data[2 * share:2 * share + 2])
        out, n = moe(paddle.to_tensor(np.asarray(h)))
        routed += np.asarray(out._data)
        rows += int(np.asarray(n._data).sum())
    assert rows == 24 * 2                       # every pair, exactly once
    assert np.abs(once + routed - want).max() < LOGIT_TOL


@pytest.mark.parametrize("chunk", [64, 5])
def test_dropless_when_every_token_routes_to_one_expert(chunk):
    """No capacity: with a router that sends all tokens to experts 3 and 5,
    both held, every pair is computed — 40 rows each."""
    paddle.seed(5)
    moe = DroplessMoE(16, 8, 8, 2, experts_held=(2, 4), chunk_tokens=chunk)
    router = np.full((16, 8), -1.0, np.float32)
    router[:, 3] = router[:, 5] = 0.0           # after |x|: the two largest
    moe.router._set_data(jnp.asarray(router))
    x = np.abs(np.random.default_rng(0).standard_normal((40, 16))).astype(
        "float32")
    out, rows = moe(paddle.to_tensor(x))
    assert np.asarray(rows._data).tolist() == [0, 40, 0, 40]

    def expert(e):
        g, u, d = (np.asarray(w._data)[e - 2] for w in
                   (moe.w_gate, moe.w_up, moe.w_down))
        a = x @ g
        return ((a / (1 + np.exp(-a))) * (x @ u)) @ d
    want = 0.5 * expert(3) + 0.5 * expert(5)    # equal scores: weights 1/2
    assert np.abs(np.asarray(out._data) - want).max() < LOGIT_TOL


def test_padding_rows_route_nowhere():
    paddle.seed(6)
    moe = DroplessMoE(16, 8, 4, 2)
    x = paddle.to_tensor(np.ones((6, 16), "float32"))
    valid = paddle.to_tensor(np.array([1, 1, 0, 0, 1, 0], bool))
    out, rows = moe(x, valid)
    assert int(np.asarray(rows._data).sum()) == 3 * 2
    assert np.abs(np.asarray(out._data)[[2, 3, 5]]).max() == 0.0


# -- the engine: both decode tiers, both page kinds -------------------------

@pytest.mark.parametrize("tier", ["off", "on"])
def test_engine_decodes_the_references_tokens_past_the_window(model, tier):
    """Prefill then decode through the engine against the reference's full
    forward: prompts three windows long, 12 new tokens — every decode step
    is past the window and three page releases happen on the way."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, n) for n in (24, 29, 17)]
    eng = _engine(model, tier)
    assert eng._paged_path == ("kernel" if tier == "on" else "dense")
    assert [kv.config.kind for kv in eng.kvs] == ["full", "window"]
    got = _serve(eng, prompts, 12)
    assert all(len(g) == 12 and _is_ref_greedy(model, p, g)
               for p, g in zip(prompts, got))
    # a window slot held window / page + 2 pages at most, and gave back
    # what fell out of the window; nothing is left claimed
    assert 0 < eng._window_high_water <= WINDOW // PAGE + 2
    assert [kv.outstanding_pages for kv in eng.kvs] == [0, 0]
    assert eng._window_committed == [0, 0]


@pytest.mark.parametrize("tier", ["off", "on"])
def test_shared_prefix_tail_equals_full_prefill_with_two_page_kinds(
        model, tier):
    """A second question over the same document maps the full pool's pages
    for the whole document and the window pool's for the last window before
    the tail, computes the tail only — and decodes what a full prefill
    does."""
    rng = np.random.default_rng(8)
    doc = rng.integers(0, 96, 24)                  # 6 pages
    first = np.concatenate([doc, rng.integers(0, 96, PAGE)])
    second = np.concatenate([doc, rng.integers(0, 96, PAGE)])
    eng = _engine(model, tier)
    assert _is_ref_greedy(model, first, _serve(eng, [first], 10)[0])
    before = eng.prefill_token_stats()
    shared = _serve(eng, [second], 10)[0]
    assert len(shared) == 10 and _is_ref_greedy(model, second, shared)
    req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
    assert (req, comp) == (28, PAGE)               # the tail alone
    assert [kv.outstanding_pages for kv in eng.kvs] == [0, 0]
    alone = _engine(model, tier, prefix_sharing="off")
    assert _serve(alone, [second], 10)[0] == shared


def test_a_window_pool_without_the_pages_falls_back_to_a_full_prefill(model):
    """A sharer whose tail would read window pages nobody kept (the first
    asker holds only what a sharer of its WHOLE prompt reads) prefills in
    full, and still decodes the reference's tokens."""
    rng = np.random.default_rng(9)
    doc = rng.integers(0, 96, 24)
    first = np.concatenate([doc, rng.integers(0, 96, 9)])
    second = np.concatenate([doc, rng.integers(0, 96, 3)])
    eng = _engine(model, "off")
    _serve(eng, [first], 4)
    before = eng.prefill_token_stats()
    assert _is_ref_greedy(model, second, _serve(eng, [second], 6)[0])
    req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
    assert req == comp == 27
    assert [kv.outstanding_pages for kv in eng.kvs] == [0, 0]


def test_admission_counts_both_kinds(model):
    """A window pool too small for a second slot's most keeps it queued
    until the first has gone, however many full-attention pages are free."""
    eng = _engine(model, "off", num_pages_window=1 + WINDOW // PAGE + 2)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, 96, 20) for _ in range(2)]
    futs = [eng.submit(GenerationRequest(np.asarray(p, np.int32),
                                         max_new_tokens=6)) for p in prompts]
    eng.step()
    assert eng.active_requests == 1 and eng.queue_depth == 1
    eng.run()
    assert all(_is_ref_greedy(model, p, f.result().tokens)
               for p, f in zip(prompts, futs))


def test_expert_counters_and_gauges(model):
    obs.enable()
    obs.reset()
    try:
        eng = _engine(model, "off")
        prompt = np.random.default_rng(11).integers(0, 96, 20)
        _serve(eng, [prompt], 5)
        snap = obs.snapshot()
    finally:
        obs.disable()
    rows = snap["serving.moe.rows_total"]
    by_expert = snap["serving.moe.rows_by_expert_total"]
    assert rows == sum(by_expert.values()) > 0
    assert rows <= (20 + 4) * 2 * 4             # pairs x layers, at most
    assert all(k.startswith("expert=") for k in by_expert)
    assert snap["serving.moe.experts_touched_total"] > 0
    assert snap["serving.kv.window_pages_per_slot_high_water"] \
        <= WINDOW // PAGE + 2
    assert snap["serving.kv.window_pages_released_total"] > 0
    assert set(snap["serving.kv.pages_in_use_by_kind"]) == \
        {"kind=full", "kind=window"}


# -- window=None is the call it always was ------------------------------------

def _qkv(rng, lq, lk, h, hkv, d):
    return [paddle.to_tensor(rng.standard_normal(s).astype("float32"))
            for s in ((1, lq, h, d), (1, lk, hkv, d), (1, lk, hkv, d))]


@pytest.mark.parametrize("length", [256, 100])     # the kernel; the XLA path
def test_flash_attention_without_a_window_is_bit_equal(length):
    q, k, v = _qkv(np.random.default_rng(0), length, length, 4, 2, 16)
    a = np.asarray(flash_attention(q, k, v, causal=True)._data)
    b = np.asarray(flash_attention(q, k, v, causal=True, window=None)._data)
    c = np.asarray(flash_attention(q, k, v, causal=True,
                                   window=length)._data)   # a band of all
    assert np.array_equal(a, b)
    assert np.abs(a - c).max() < 1e-6


@pytest.mark.parametrize("length,window", [(256, 64), (100, 30), (384, 200)])
def test_flash_attention_window_band(length, window):
    q, k, v = _qkv(np.random.default_rng(1), length, length, 4, 2, 16)
    got = np.asarray(flash_attention(q, k, v, causal=True,
                                     window=window)._data)
    qa, ka, va = (np.asarray(x._data)[0] for x in (q, k, v))
    ka, va = np.repeat(ka, 2, 1), np.repeat(va, 2, 1)
    s = np.einsum("qhd,khd->hqk", qa, ka) / 4.0
    i, j = np.arange(length)[:, None], np.arange(length)[None]
    s = np.where((j <= i) & (i - j < window), s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), va)
    assert np.abs(got[0] - want).max() < 1e-5


def test_flash_attention_window_has_no_backward():
    q, k, v = _qkv(np.random.default_rng(2), 128, 128, 2, 2, 16)
    from paddle_tpu.ops.flash_attention import _flash_core_window
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda a: _flash_core_window(
            a, jnp.swapaxes(k._data, 1, 2), jnp.swapaxes(v._data, 1, 2),
            0.25, 32).sum())(jnp.swapaxes(q._data, 1, 2))
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, k, v, causal=False, window=8)


@pytest.mark.parametrize("impl", ["kernel", "dense"])
def test_paged_decode_attention_without_a_window_is_bit_equal(impl):
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((9, 2, 2, 2, 4, 16)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    t = jnp.asarray([9, 6], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((2, 2, 16)), jnp.float32)
    args = (q, kn, kn, pool, None, tables, t, jnp.asarray(1))
    kw = dict(page_size=4, impl=impl, interpret=True)
    a = np.asarray(pa.paged_attention(*args, **kw))
    b = np.asarray(pa.paged_attention(*args, window=None, **kw))
    assert np.array_equal(a, b)
    # ... and a window that reaches every position reads the same pages
    # through the compact table (first page 0)
    c = np.asarray(pa.paged_attention(*args, window=16, **kw))
    assert np.abs(a - c).max() < 1e-6


@pytest.mark.parametrize("impl", ["kernel", "dense"])
def test_paged_decode_attention_window_reads_the_compact_table(impl):
    """Window 8 on pages of 4: at t = 14 the window starts on page 1, the
    compact table holds pages 1, 2, 3, and page 0 is never read — a pool
    whose page-0 entry is NaN decodes as if it were not there."""
    rng = np.random.default_rng(4)
    pool = rng.standard_normal((9, 1, 2, 2, 4, 16)).astype(np.float32)
    logical = [5, 6, 7, 8]                         # pages of positions 0..15
    t, window = 14, 8
    q = jnp.asarray(rng.standard_normal((1, 4, 16)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((1, 2, 16)), jnp.float32)
    keys = np.concatenate([pool[p, 0, 0] for p in logical], axis=1)
    vals = np.concatenate([pool[p, 0, 1] for p in logical], axis=1)
    keys[:, t], vals[:, t] = np.asarray(kn)[0], np.asarray(kn)[0]
    keep = (np.arange(16) <= t) & (np.arange(16) > t - window)
    s = np.einsum("hd,hld->hl", np.asarray(q)[0],
                  np.repeat(keys, 2, 0)) / 4.0
    s = np.where(keep[None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hl,hld->hd", p / p.sum(-1, keepdims=True),
                     np.repeat(vals, 2, 0))
    pool[logical[0]] = np.nan                      # below the window
    first = int(pa.window_first_page(t, window, 4))
    assert first == 1 and pa.window_table_pages(window, 4) == 4
    compact = jnp.asarray([logical[first:] + [0]], jnp.int32)
    got = np.asarray(pa.paged_attention(
        q, kn, kn, jnp.asarray(pool), None, compact,
        jnp.asarray([t], jnp.int32), jnp.asarray(0), page_size=4, impl=impl,
        interpret=True, window=window))
    assert np.abs(got[0] - want).max() < 1e-5


@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("window", [None, 16])
def test_grouped_decode_kernel_agrees_with_the_dense_tier(kv, window):
    """16 query heads to a KV head take the grouped kernel (the heads of a
    KV head as rows of one matmul, 8 pages a trip of the row's own loop);
    7 or 4 table columns are not a whole group, so the table is padded
    with the scratch page. Against the per-layer dense tier on the same
    pool (``tests/test_paged_row_walk.py``: the walk's edges at the serving
    cells' shapes)."""
    assert 16 >= pa._GROUPED_MIN_REP
    rng = np.random.default_rng(5)
    scales = None
    if kv == "int8":
        pool = jnp.asarray(rng.integers(-127, 128, (9, 2, 2, 2, 8, 32)),
                           jnp.int8)
        scales = jnp.asarray(rng.uniform(0.01, 0.03, (9, 2, 2, 2)),
                             jnp.float32)
    else:
        pool = jnp.asarray(rng.standard_normal((9, 2, 2, 2, 8, 32)), kv)
    cols = pa.window_table_pages(window, 8) if window else 7
    tables = jnp.asarray(rng.integers(1, 9, (3, cols)), jnp.int32)
    t = jnp.asarray([0, 23, 55], jnp.int32)        # row 0: batch padding
    q = jnp.asarray(rng.standard_normal((3, 32, 32)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((3, 2, 32)), jnp.float32)
    args = (q, kn, -kn, pool, scales, tables, t, jnp.asarray(1))
    kw = dict(page_size=8, interpret=True, window=window)
    got = np.asarray(pa.paged_attention(*args, impl="kernel", **kw))
    want = np.asarray(pa.paged_attention(*args, impl="dense", **kw))
    assert np.abs(got - want).max() < 1e-5
