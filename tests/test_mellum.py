"""Mellum 2 (``mellum``) at a tiny size on the CPU, against the benchmark's
plain reference (``perfbench/reference_mellum.py``, imported: one reference,
not two), and the window pages the serving engine keeps at prefix
boundaries.

The tiny configuration keeps every mechanism: hidden 64, 8 query heads on 2
KV heads with ``head_dim`` 16 != 64 / 8, 8 softmax-routed experts top-2 and
no shared one, window 8 on pages of 4, YaRN on the full layer with an
original context of 64 positions (prompts run past it), one period of the
published pattern (window x3, full). Weights are seeded random float32.

Tolerances, and why: model, engine and reference compute the same float32
sums in other orders (sorted grouped matmul against per-expert rows; online
softmax over pages or blocks against one softmax), so logits agree to a few
float32 ulps of values of order 1: ``LOGIT_TOL`` 2e-5 is a hundred times
what was seen (1.5e-7) and far under the gap between two tokens' logits.
Greedy tokens are compared exactly.
"""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import mellum
from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                           Cohere2MoeForCausalLM)
from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM
from paddle_tpu.ops import rotary
from paddle_tpu.serving.scheduler import GenerationRequest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench import reference_mellum as ref  # noqa: E402

LOGIT_TOL = 2e-5
MAX_LEN, PAGE, WINDOW, EVERY = 128, 4, 8, 16


@pytest.fixture(scope="module")
def model():
    paddle.seed(1)
    m = MellumForCausalLM(MellumConfig.tiny())
    m.eval()
    return m


def _ref_logits(m, ids, **over):
    cfg = dict(dataclasses.asdict(m.config), **over)
    return np.asarray(ref.logits(ref.params_of(m), jnp.asarray(ids), cfg))


def _is_ref_greedy(m, prompt, tokens):
    """Whether ``tokens`` is the reference's greedy continuation of
    ``prompt``: one teacher-forced pass over prompt + tokens."""
    ids = np.concatenate([prompt, tokens]).astype(np.int64)
    rows = _ref_logits(m, ids)[len(prompt) - 1:-1]
    return len(tokens) > 0 and rows.argmax(-1).tolist() == list(tokens)


def _engine(m, tier="off", **over):
    pf, sf = m.serving_callables(MAX_LEN)
    c = m.config
    kw = dict(num_layers=c.num_hidden_layers, num_heads=c.num_key_value_heads,
              head_dim=c.head_dim, max_len=MAX_LEN, max_batch=4,
              buckets=(1, 4), page_size=PAGE, layer_kinds=c.layer_kinds,
              window=c.sliding_window, paged_attention=tier,
              window_boundary_tokens=EVERY, window_boundary_pages=24)
    kw.update(over)
    return serving.Engine(pf, sf, serving.ServingConfig(**kw))


def _serve(eng, prompts, n):
    futs = [eng.submit(GenerationRequest(np.asarray(p, np.int32),
                                         max_new_tokens=n)) for p in prompts]
    eng.run()
    return [f.result().tokens for f in futs]


def _counter(name):
    return obs.snapshot().get(name, 0.0)


# -- the rotary -----------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """YaRN of the full layers: pairs 0-18 keep theta's frequency, 35-63
    turn 16 times slower, the ramp between falls linearly, and cos / sin are
    scaled by 0.1 ln 16 + 1 — the same from the program's rotary and the
    reference's own formulas."""
    inv, scale = rotary.frequencies(
        mellum.ROPE_PARAMETERS["full_attention"], 128)
    plain, one = rotary.frequencies(
        mellum.ROPE_PARAMETERS["sliding_attention"], 128)
    assert one == 1.0 and scale == pytest.approx(0.1 * math.log(16) + 1)
    ratio = inv / plain
    assert np.allclose(ratio[:19], 1.0, rtol=1e-6)
    assert np.allclose(ratio[35:], 1 / 16, rtol=1e-6)
    ramp = (np.arange(64) - 18) / 17
    want = np.clip(ramp, 0, 1) / 16 + 1 - np.clip(ramp, 0, 1)
    assert np.allclose(ratio, want, rtol=1e-5)
    theirs, their_scale = ref.frequencies(
        mellum.ROPE_PARAMETERS["full_attention"], 128)
    assert np.allclose(np.asarray(theirs), inv, rtol=1e-6)
    assert their_scale == scale


def test_rotation_turns_halves():
    x = np.zeros((1, 1, 4), np.float32)
    x[0, 0, 0] = 1.0                             # x1 = (1, 0), x2 = (0, 0)
    out = np.asarray(rotary.rotate_halves(
        jnp.asarray(x), jnp.asarray([1]), np.asarray([0.5, 0.0], np.float32),
        2.0))
    assert np.allclose(out[0, 0], [2 * math.cos(0.5), 0.0,
                                   2 * math.sin(0.5), 0.0], atol=1e-6)


# -- the model ------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 40, 100])     # 100: past YaRN's 64
def test_forward_agrees_with_the_reference(model, length):
    ids = np.random.default_rng(length).integers(0, 96, length)
    got = np.asarray(model(paddle.to_tensor(ids.astype("int32")))._data)
    assert np.abs(got - _ref_logits(model, ids)).max() < LOGIT_TOL


def test_no_yarn_is_another_model(model):
    """The reference's control without YaRN moves the logits of a prompt
    past the original context: the rotary of the full layer is in them."""
    ids = np.random.default_rng(3).integers(0, 96, 100)
    assert np.abs(_ref_logits(model, ids, control="no_yarn")
                  - _ref_logits(model, ids)).max() > 100 * LOGIT_TOL


@pytest.fixture(scope="module")
def wide_window():
    paddle.seed(2)
    m = MellumForCausalLM(MellumConfig.tiny(sliding_window=200))
    m.eval()
    return m


def test_forward_through_the_flash_band_agrees_with_the_reference(
        wide_window):
    """A run long enough for the padded flash path (512 rows and more),
    the window far shorter than the run."""
    ids = np.random.default_rng(0).integers(0, 96, 600)
    got = np.asarray(wide_window(paddle.to_tensor(ids.astype("int32")))._data)
    assert np.abs(got - _ref_logits(wide_window, ids)).max() < 5 * LOGIT_TOL


@pytest.mark.parametrize("start,tail", [(128, 512), (64, 40)])
def test_a_tail_over_a_prefix_is_the_full_forward(wide_window, start, tail):
    """A run at positions ``start ..`` over the prefix's K/V (a window
    layer's from where its band begins) gives the full forward's last rows:
    the flash kernel bottom-right aligned (128 + 512 keys tile), and the
    looped form (64 + 40 do not)."""
    m = wide_window
    ids = np.random.default_rng(start).integers(0, 96, start + tail)
    with paddle.no_grad():
        _, kvs, _ = m._run(paddle.to_tensor(ids[:start].astype("int32")))
        prefix = [(k[max(0, start - layer.window):], v[max(
            0, start - layer.window):]) if layer.window else (k, v)
            for (k, v), layer in zip(kvs, m.layers)]
        h, _, _ = m._run(paddle.to_tensor(ids[start:].astype("int32")),
                         prefix, start)
        got = np.asarray(m._logits(h)._data)
    want = _ref_logits(m, ids)[start:]
    assert np.abs(got - want).max() < 5 * LOGIT_TOL


# -- the engine: both decode tiers, both page kinds, kept boundaries ---------

@pytest.mark.parametrize("tier", ["off", "on"])
def test_engine_decodes_the_references_tokens(model, tier):
    """Prefill then paged decode past the window and past YaRN's original
    context, against the reference's full forward."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, n) for n in (70, 29, 17)]
    eng = _engine(model, tier)
    assert eng._paged_path == ("kernel" if tier == "on" else "dense")
    got = _serve(eng, prompts, 10)
    assert all(len(g) == 10 and _is_ref_greedy(model, p, g)
               for p, g in zip(prompts, got))
    assert 0 < eng._window_high_water <= WINDOW // PAGE + 2


@pytest.mark.parametrize("tier", ["off", "on"])
def test_a_follow_up_longer_than_the_window_prefills_its_tail_only(
        model, tier):
    """The document's boundary keeps the window before it: a second
    question of 20 tokens (five pages, past the 8-token window the first
    asker's own pages cover) maps the document from both pools, computes
    its 20 tokens only, and decodes what a full prefill of it decodes."""
    obs.enable()
    try:
        rng = np.random.default_rng(8)
        doc = rng.integers(0, 96, 32)              # a boundary at 16 and 32
        first = np.concatenate([doc, rng.integers(0, 96, 20)])
        second = np.concatenate([doc, rng.integers(0, 96, 20)])
        eng = _engine(model, tier)
        assert _is_ref_greedy(model, first, _serve(eng, [first], 6)[0])
        hits = _counter("serving.kv.window_prefix_hits_total")
        before = eng.prefill_token_stats()
        shared = _serve(eng, [second], 6)[0]
        req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
        assert (req, comp) == (52, 20)
        assert _counter("serving.kv.window_prefix_hits_total") == hits + 1
        assert _is_ref_greedy(model, second, shared)
        assert _serve(_engine(model, tier, prefix_sharing="off"),
                      [second], 6)[0] == shared
        # what is still claimed is the kept boundaries' alone, and a drained
        # engine holds no page
        kept = eng.window_boundaries.nbytes
        assert [kv.outstanding_pages for kv in eng.kvs] == [0, kept] \
            and kept > 0
        assert obs.snapshot()[
            "serving.kv.window_boundary_pages_high_water"] > 0
        eng.stop(drain=True)
        assert [kv.outstanding_pages for kv in eng.kvs] == [0, 0]
    finally:
        obs.disable()


def test_without_kept_boundaries_the_follow_up_prefills_in_full(model):
    """The same two asks with boundaries off: the window before the
    document's end is gone, so the second prefills all 52 tokens (a miss),
    and still decodes the reference's tokens."""
    obs.enable()
    try:
        rng = np.random.default_rng(8)
        doc = rng.integers(0, 96, 32)
        first = np.concatenate([doc, rng.integers(0, 96, 20)])
        second = np.concatenate([doc, rng.integers(0, 96, 20)])
        eng = _engine(model, window_boundary_tokens=0,
                      window_boundary_pages=0)
        assert eng.window_boundaries is None
        _serve(eng, [first], 4)
        misses = _counter("serving.kv.window_prefix_misses_total")
        before = eng.prefill_token_stats()
        assert _is_ref_greedy(model, second, _serve(eng, [second], 6)[0])
        req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
        assert req == comp == 52
        assert _counter("serving.kv.window_prefix_misses_total") \
            == misses + 1
    finally:
        obs.disable()


def test_kept_boundaries_stay_within_their_budget(model):
    """Four documents, each keeping its two boundaries (2 pages each), in a
    budget of 6 pages: the oldest go first, their claims go back, and what
    is kept is always the newest."""
    obs.enable()
    try:
        rng = np.random.default_rng(9)
        eng = _engine(model, window_boundary_pages=6)
        gone = _counter("serving.kv.window_boundary_evictions_total")
        docs = [rng.integers(0, 96, 32) for _ in range(4)]
        for doc in docs:
            _serve(eng, [np.concatenate([doc, rng.integers(0, 96, 4)])], 2)
            assert eng.window_boundaries.nbytes <= 6
        assert _counter("serving.kv.window_boundary_evictions_total") > gone
        assert eng.kvs[1].outstanding_pages == eng.window_boundaries.nbytes
        # the last document's deepest boundary is kept: its follow-up with a
        # long question shares it
        before = eng.prefill_token_stats()
        _serve(eng, [np.concatenate([docs[-1], rng.integers(0, 96, 20)])], 2)
        req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
        assert (req, comp) == (52, 20)
    finally:
        obs.disable()


def test_short_tails_share_as_before_without_boundaries():
    """Command A+'s 64-token tails (here: one page over a 24-token
    document, window 8): the first asker's own pages cover the sharer's
    window, so nothing is kept and the tail is computed alone — a hit."""
    obs.enable()
    try:
        paddle.seed(1)
        m = Cohere2MoeForCausalLM(Cohere2MoeConfig.tiny(experts_held=(2, 4)))
        m.eval()
        pf, sf = m.serving_callables(64)
        c = m.config
        eng = serving.Engine(pf, sf, serving.ServingConfig(
            num_layers=4, num_heads=2, head_dim=16, max_len=64, max_batch=4,
            buckets=(1, 4), page_size=PAGE, layer_kinds=c.layer_kinds,
            window=c.sliding_window, paged_attention="off"))
        assert eng.window_boundaries is None
        assert eng.kvs[1].config.num_pages == 4 * (WINDOW // PAGE + 2) + 1
        rng = np.random.default_rng(8)
        doc = rng.integers(0, 96, 24)
        _serve(eng, [np.concatenate([doc, rng.integers(0, 96, PAGE)])], 4)
        hits = _counter("serving.kv.window_prefix_hits_total")
        before = eng.prefill_token_stats()
        _serve(eng, [np.concatenate([doc, rng.integers(0, 96, PAGE)])], 4)
        req, comp = (a - b for a, b in zip(eng.prefill_token_stats(), before))
        assert (req, comp) == (28, PAGE)
        assert _counter("serving.kv.window_prefix_hits_total") == hits + 1
        assert [kv.outstanding_pages for kv in eng.kvs] == [0, 0]
    finally:
        obs.disable()


def test_window_boundaries_need_window_layers_and_a_budget():
    for over in (dict(layer_kinds=()), dict(window_boundary_pages=0),
                 dict(window_boundary_tokens=6)):
        kw = dict(num_layers=4, num_heads=2, head_dim=16, max_len=64,
                  max_batch=4, buckets=(4,), page_size=PAGE, window=WINDOW,
                  layer_kinds=("window",) * 3 + ("full",),
                  window_boundary_tokens=EVERY, window_boundary_pages=8)
        kw.update(over)
        with pytest.raises(ValueError, match="window_boundary_tokens"):
            serving.ServingConfig(**kw)
    cfg = serving.ServingConfig(
        num_layers=4, num_heads=2, head_dim=16, max_len=64, max_batch=4,
        buckets=(4,), page_size=PAGE, window=WINDOW,
        layer_kinds=("window",) * 3 + ("full",),
        window_boundary_tokens=EVERY, window_boundary_pages=8)
    # the window pool is the budget larger than every slot's most
    assert [c.num_pages for c in cfg.kv_configs()] == \
        [4 * 16 + 1, 4 * (WINDOW // PAGE + 2) + 1 + 8]


def test_random_sessions_share_exactly_what_a_full_prefill_computes(model):
    """Documents of 16-64 tokens asked again with questions of 1-20 tokens
    (past the window and not), four slots and a boundary budget of 10
    pages, so that boundaries are evicted, admission waits for the window
    pool and follow-ups fall back to full prefills: every answer equals
    what an engine that shares nothing decodes, no page is freed twice, and
    a drained engine holds none."""
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 96, n) for n in (16, 32, 48, 33, 64)]
    prompts = [np.concatenate([docs[rng.integers(len(docs))],
                               rng.integers(0, 96, int(rng.choice(
                                   [1, 4, 12, 20])))]) for _ in range(24)]
    new = [int(rng.integers(2, 6)) for _ in prompts]
    shared, alone = (_engine(model, buckets=(4,), window_boundary_pages=10,
                             **over)
                     for over in ({}, {"prefix_sharing": "off"}))
    got = []
    for eng in (shared, alone):
        futs = [eng.submit(GenerationRequest(np.asarray(p, np.int32),
                                             max_new_tokens=n))
                for p, n in zip(prompts, new)]
        eng.run()
        got.append([f.result().tokens for f in futs])
    assert got[0] == got[1]
    req, comp = shared.prefill_token_stats()
    assert comp < req                             # something was shared
    assert alone.window_boundaries.nbytes == 0    # nothing kept unshared
    assert shared.kvs[1].outstanding_pages == shared.window_boundaries.nbytes
    shared.stop(drain=True)
    assert [kv.outstanding_pages for kv in shared.kvs] == [0, 0]
    assert [kv.double_free_total for kv in shared.kvs] == [0, 0]
