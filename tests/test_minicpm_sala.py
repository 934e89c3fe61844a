"""MiniCPM-SALA (ISSUE 31) on the CPU at a tiny size — both mixers, a
selection that really drops blocks (blocks of 4 tokens, the best 3 kept past
16 tokens of context) — against ``perfbench/reference_minicpm_sala.py``, the
plain float32 forward that shares nothing with ``paddle_tpu``:

* the model's full forward, logits;
* the reference's controls are told from the sound forward: a bfloat16
  state, a bfloat16 selection score (a block flips), no forced block;
  ``state_distance`` tells the state's own rounding from the inputs' noise;
* a cut of the depth is a pipeline stage: layers 9-16 of a 32-layer model,
  run with the published indices and depth, equal the same layers inside
  the whole model's forward;
* through the compiled programs — pages, the state pool, the compressed
  keys, the chosen tables, both decode tiers: prefill, a tail from a state
  snapshot, and decode steps, LOGITS against the reference's full forward;
* through the engine: two slots decode while a third is admitted; a
  follow-up ask prefills only its tail and equals a full prefill; pages
  without a snapshot are not shared; a slot takes one state row and a
  failed admission returns it.

Tolerances: float32 on both sides, so ``TOL`` = 2e-5 is rounding in a
different order of summation (readings: 5e-8 forward, 1e-6 through the
pages); the controls move logits by 1e-4 and more.
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
from paddle_tpu.models.minicpm_sala import (_PUBLISHED_MIXERS,
                                            MiniCPMSalaConfig,
                                            MiniCPMSalaForCausalLM)
from paddle_tpu.ops import sparse_attention as sa
from paddle_tpu.serving import kv_cache as kvc

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench import reference_minicpm_sala as ref  # noqa: E402

TOL = 2e-5
MAX_LEN, BLOCK, V = 96, 8, 96


def ref_cfg(c, **over):
    d = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)
         if f.name != "sparse"}
    d["sparse_config"] = dataclasses.asdict(c.sparse)
    return dict(ref.reference_config(d, c.layers_run, c.num_hidden_layers,
                                     c.mixer_types), **over)


@pytest.fixture(scope="module")
def model():
    paddle.seed(31)
    m = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny())
    m.eval()
    return m


def _ids(seed, n):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def _ref_logits(m, ids, **over):
    cfg = ref_cfg(m.config, **over)
    return np.asarray(jax.jit(lambda p, i: ref.logits(p, i, cfg))(
        ref.params_of(m), jnp.asarray(ids)))


def _gaps(m, prompt, tokens):
    """The reference's largest logit minus its logit of each token the
    system chose, teacher-forced."""
    full = np.concatenate([prompt, tokens])
    rows = _ref_logits(m, full[:-1])[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(tokens)), tokens]


@pytest.mark.parametrize("length", [13, 45, 70])
def test_forward_agrees_with_the_reference(model, length):
    ids = _ids(length, length)
    got = np.asarray(model.forward(paddle.to_tensor(ids), block=BLOCK)._data)
    assert np.abs(got - _ref_logits(model, ids)).max() < TOL


@pytest.mark.parametrize("control", ["bf16_state", "no_forced",
                                     "fp8_weights"])
def test_the_references_controls_are_told_from_the_sound_forward(model,
                                                                 control):
    ids = _ids(70, 70)
    got = np.asarray(model.forward(paddle.to_tensor(ids), block=BLOCK)._data)
    assert np.abs(got - _ref_logits(model, ids, control=control)).max() \
        > 5 * TOL


def test_selection_agrees_with_the_reference_and_bf16_scores_flip_a_block():
    c = MiniCPMSalaConfig.tiny()
    sp, cfg = c.sparse, ref_cfg(c)
    rng = np.random.default_rng(5)
    t, hkv, rep, d = 240, 2, 2, 8
    k = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, hkv, rep, d)) * 3, jnp.float32)
    pos = jnp.arange(t)
    ent = sa.compress_keys(k, sp)
    sc = jnp.einsum("cgrd,fgd->cgrf", q, ent) / np.sqrt(d)
    blocks, chosen = sa.select_blocks(sc, pos, sp, t // sp.block_size)
    mine = np.zeros((t, hkv, t // sp.block_size), bool)
    for i, g in np.ndindex(t, hkv):
        mine[i, g, np.asarray(blocks)[i, g][np.asarray(chosen)[i, g]]] = True
    n_j = (t - sp.kernel_size) // sp.kernel_stride + 1
    kc = jnp.stack([k[sp.kernel_stride * j:sp.kernel_stride * j
                      + sp.kernel_size].mean(0) for j in range(n_j)])
    theirs, margin, away = ref.block_choice(q, kc, pos, cfg,
                                            t // sp.block_size)
    past = np.asarray(pos) + 1 > sp.dense_len
    assert (mine[past] == np.asarray(theirs)[past]).all()
    assert mine[past].sum(-1).max() == sp.topk      # blocks really dropped
    assert np.isfinite(np.asarray(margin)[past]).all()
    flipped, _, _ = ref.block_choice(
        q, kc, pos, dict(cfg, control="bf16_scores"), t // sp.block_size)
    moved = np.asarray(flipped)[past] != np.asarray(theirs)[past]
    assert moved.any()
    # a block that a bf16 score flips lies near the last chosen one's
    # score; a forced block lies nowhere near it
    assert np.asarray(away)[past][moved].max() < 0.05
    assert np.isinf(np.asarray(away)[past][:, :, 0]).all()


def test_state_distance_tells_the_states_own_rounding_from_the_inputs_noise():
    """Noise of one size on every head (the inputs') reads as no rounding;
    an error that grows as a head forgets more slowly (the state's own)
    does — and both show in the worst head's distance."""
    rng = np.random.default_rng(7)
    heads, d = 32, 64
    cfg = {"num_layers_published": 32}
    want = rng.standard_normal((2, heads, d, d)).astype(np.float32)
    unit = rng.standard_normal(want.shape).astype(np.float32)
    s = np.asarray(ref.slopes(heads, 10, 32))
    flat = want + 0.02 * unit
    by_head, worst, rounding = (np.asarray(x) for x in ref.state_distance(
        flat, want, cfg))
    assert by_head.shape == (2, heads) and 0.015 < worst < 0.025
    assert abs(rounding) < 0.006
    piled = flat + (0.002 / np.sqrt(2 * s))[None, :, None, None] * \
        rng.standard_normal(want.shape).astype(np.float32)
    _, worst, rounding = (np.asarray(x) for x in ref.state_distance(
        piled, want, cfg))
    assert rounding > 0.015 and worst > 0.03
    assert np.asarray(ref._bf16(jnp.float32(1.0 + 2.0 ** -9))) == 1.0


def test_layers_9_to_16_equal_the_same_layers_inside_the_whole_model():
    """The benchmark's cut: published layers 9-16 as one pipeline stage."""
    paddle.seed(7)
    whole = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny(
        num_hidden_layers=32, mixer_types=_PUBLISHED_MIXERS))
    sd = whole.state_dict()

    def stage(run):
        m = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny(
            num_hidden_layers=32, mixer_types=_PUBLISHED_MIXERS,
            layers_run=run))
        m.set_state_dict({
            (f"layers.{run.index(int(k.split('.')[1]))}."
             + k.split(".", 2)[2]) if k.startswith("layers.") else k: v
            for k, v in sd.items()
            if not k.startswith("layers.") or int(k.split(".")[1]) in run})
        return m

    first, cut, last = stage(tuple(range(9))), stage(tuple(range(9, 17))), \
        stage(tuple(range(17, 32)))
    assert cut.config.mixers_run == ("minicpm4",) + ("lightning-attn",) * 6 \
        + ("minicpm4",)
    ids = paddle.to_tensor(_ids(9, 40))
    h = first.hidden_states(ids, block=BLOCK)
    h = cut.hidden_states(hidden=h, block=BLOCK)
    h = last.hidden_states(hidden=h, block=BLOCK)
    want = whole.hidden_states(ids, block=BLOCK)
    assert np.abs(np.asarray(h._data) - np.asarray(want._data)).max() < TOL
    # and the whole model is the reference's: published indices in the
    # decay, depth 32 in the residual scale
    got = np.asarray(whole.forward(ids, block=BLOCK)._data)
    assert np.abs(got - _ref_logits(whole, np.asarray(ids._data))).max() < TOL
    # a stage that took its own depth for the published one would not be
    alone = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny(
        num_hidden_layers=8, mixer_types=cut.config.mixers_run))
    alone.set_state_dict(cut.state_dict())
    h9 = first.hidden_states(ids, block=BLOCK)
    assert np.abs(np.asarray(alone.hidden_states(hidden=h9)._data)
                  - np.asarray(cut.hidden_states(hidden=h9)._data)).max() \
        > 100 * TOL


# ---------------------------------------------------------------------------
# through the compiled programs
# ---------------------------------------------------------------------------

def _engine(m, tier="off", with_logits=False, **over):
    c = m.config
    prefill_fn, step_fn = m.serving_callables(MAX_LEN, block=BLOCK,
                                              with_logits=with_logits)
    kw = dict(num_layers=len(c.layers_run), num_heads=c.num_key_value_heads,
              head_dim=c.head_dim, max_len=MAX_LEN, max_batch=3,
              buckets=(1, 3), page_size=c.sparse.block_size,
              compute_dtype="float32", layer_kinds=c.layer_kinds,
              state_shape=c.state_shape,
              index_per_page=c.sparse.per_block, state_snapshot_tokens=BLOCK,
              paged_attention=tier)
    kw.update(over)
    return serving.Engine(prefill_fn, step_fn, serving.ServingConfig(**kw))


def _i32(x):
    return T(jnp.asarray(x, jnp.int32))


@pytest.mark.parametrize("tier", ["off", "on"])
def test_logits_through_pages_state_and_chosen_tables(model, tier):
    """Prefill two prompts, a third as a tail from the first one's state
    snapshot, then decode all three in one bucket: every program's logits
    against the reference's full forward of what the row has seen, every
    decode step's chosen blocks against the reference's, and the pages the
    step counted against the tables it was given."""
    eng = _engine(model, tier, with_logits=True)
    p, kv = eng.programs, eng.kv
    ps = kv.config.page_size
    doc = _ids(1, 40)                       # 5 snapshot boundaries
    prompts = [np.concatenate([doc, _ids(2, 5)]), _ids(3, 70),
               np.concatenate([doc, _ids(4, 7)])]
    seen, rows, pages = [], [], []
    got = [[], [], []]              # per row: (length seen, logits, blocks)
    c = model.config
    hkv, n_sparse = c.num_key_value_heads, c.layer_kinds.count("sparse")

    snaps = None
    for n, prompt in enumerate(prompts):
        start = 40 if n == 2 else 0
        ids = kv.alloc(kv.pages_for(len(prompt) + 8) - start // ps)
        if start:                           # map the first prompt's pages
            ids = pages[0][:start // ps] + ids
        row = eng.state.alloc()
        step = p.prefill(
            _i32(prompt[None, start:]), [_i32(kv.table_row(ids))],
            _i32(len(prompt)), start, _i32(row),
            (T(snaps[start // BLOCK - 1]),) if start else None)
        (tok,), lg = step.read()
        got[n].append((len(prompt), lg.view(np.float32), None))
        if n == 0:
            (snaps,) = (part._data for part in step.extra)
            assert snaps.shape[0] == len(prompt) // BLOCK
        seen.append(np.append(prompt, tok))
        rows.append(row)
        pages.append(ids)
    carry = p.no_carry
    for _ in range(6):
        t = [len(s) - 1 for s in seen]
        step = p.decode(
            _i32([[s[-1]] for s in seen]),
            [_i32(np.stack([kv.table_row(ids) for ids in pages]))],
            _i32(t), carry, _i32([-1] * 3), _i32(rows))
        toks, extras = step.read()
        counted, blocks, lg = model.split_step_extras(extras, 3)
        # every row is past dense_len: it holds t // ps + 1 pages a KV head
        # and sparse layer and attends the topk chosen, less its own page
        # when that holds no earlier token yet
        assert counted[0] == sum(x // ps + 1 for x in t) * hkv * n_sparse
        assert counted[1] == sum(c.sparse.topk - (x % ps == 0) for x in t) \
            * hkv * n_sparse
        for n in range(3):
            got[n].append((len(seen[n]), lg[n], blocks[n]))
            seen[n] = np.append(seen[n], toks[n])
    assert not p.pools_lost()
    cfg = ref_cfg(c)
    for n in range(3):                      # teacher-forced, once a row
        ids = seen[n][:-1]
        want = jax.jit(lambda p_, i: ref.answer_rows(
            p_, i, 1, i, cfg))(ref.params_of(model), jnp.asarray(ids))
        assert len(got[n]) == 7
        for length, logits, blocks in got[n]:
            assert np.abs(logits - np.asarray(want["logits"])[length - 1]
                          ).max() < TOL
            if blocks is not None:          # a decode step's choice
                mine = np.zeros(want["chosen"].shape[1:], bool)
                at = np.nonzero(blocks >= 0)
                mine[at[:2] + (blocks[at],)] = True
                assert (mine == np.asarray(want["chosen"])[length - 1]).all()


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


def test_a_follow_up_prefills_its_tail_from_the_snapshot(model):
    obs.enable()
    doc = _ids(21, 48)
    asks = [np.concatenate([doc, _ids(22 + i, 6)]) for i in range(2)]
    before = dict(obs.snapshot())
    shared = _engine(model)
    first, follow = (_serve(shared, [a], 8)[0] for a in asks)
    requested, computed = shared.prefill_token_stats()
    assert (requested, computed) == (2 * 54, 54 + 6)
    after = obs.snapshot()
    assert after.get("serving.state.snapshot_hits_total", 0) \
        - before.get("serving.state.snapshot_hits_total", 0) == 1
    assert after["serving.state.snapshot_bytes"] == shared.snapshots.nbytes
    alone = _engine(model, prefix_sharing="off")
    assert (_serve(alone, [asks[1]], 8)[0] == follow).all()
    assert alone.prefill_token_stats() == (54, 54)
    for ask, toks in zip(asks, (first, follow)):
        assert _gaps(model, ask, toks).max() < TOL
    # the state kept at the document's end is the reference's, and a
    # bfloat16 state is told from it
    kept = np.asarray(shared.snapshots.get(kvc.prefix_chain_digests(
        asks[0], shared.config.page_size, limit=48 // 4)[-1]))
    for control, told in (("", False), ("bf16_state", True)):
        want = np.asarray(jax.jit(lambda p_, i: ref.answer_rows(
            p_, i, 54, i[:1], ref_cfg(model.config, control=control), 48)
            ["states"])(ref.params_of(model), jnp.asarray(asks[0])))
        assert (np.abs(kept - want).max() > 1e-4) == told


def test_pages_without_a_snapshot_are_not_shared(model):
    obs.enable()
    doc = _ids(31, 48)
    asks = [np.concatenate([doc, _ids(32 + i, 6)]) for i in range(2)]
    eng = _engine(model, state_snapshot_bytes=0)     # nothing is kept
    before = dict(obs.snapshot()).get("serving.state.snapshot_misses_total", 0)
    outs = [_serve(eng, [a], 6)[0] for a in asks]
    assert eng.prefill_token_stats() == (108, 108)   # resident pages unused
    assert obs.snapshot()["serving.state.snapshot_misses_total"] - before == 1
    assert _gaps(model, asks[1], outs[1]).max() < TOL


def test_a_slot_takes_one_state_row_and_a_failed_admission_returns_it(model):
    """The state pool has a row a slot and a slot takes exactly one, so an
    admission never waits for a row; one that raises after its claims — the
    snapshot it counted on is gone — gives back its pages and its row."""
    eng = _engine(model)
    doc = _ids(41, 48)
    asks = [np.concatenate([doc, _ids(42 + i, 6)]) for i in range(2)]
    assert len(_serve(eng, asks[:1], 3)[0]) == 3
    assert eng.state.free_rows == eng.config.max_batch
    eng.snapshots.get_parts = lambda digest: None    # evicted meanwhile
    fut = eng.submit(serving.GenerationRequest(prompt=asks[1],
                                               max_new_tokens=3))
    eng.run()
    with pytest.raises(RuntimeError, match="snapshot vanished"):
        fut.result(timeout=30)
    assert eng.state.free_rows == eng.config.max_batch
    assert eng.kv.outstanding_pages == 0
    held = [eng.state.alloc() for _ in range(eng.config.max_batch)]
    with pytest.raises(RuntimeError, match="no free state row"):
        eng.state.alloc()
    eng.state.free(held.pop())
    with pytest.raises(ValueError, match="double free"):
        eng.state.free(0)


def test_snapshot_store_keeps_to_its_budget_least_recently_used_first_out():
    obs.enable()
    state = jnp.ones((2, 4), jnp.float32)            # 32 bytes
    store = kvc.SnapshotStore(budget_bytes=64)
    before = dict(obs.snapshot()).get(
        "serving.state.snapshot_evictions_total", 0)
    store.put_parts(b"a", (state,))
    store.put_parts(b"b", (state,))
    assert store.get(b"a") is state                  # a is now the newest
    store.put_parts(b"c", (state,))                  # b goes
    assert store.get(b"b") is None and store.get(b"a") is state
    assert store.nbytes == 64 and len(store) == 2
    assert store.deepest([b"x", b"a", b"b", b"c"], 4) == 4
    assert store.deepest([b"x", b"a", b"b", b"c"], 3) == 2
    assert store.deepest([b"x"], 1) == 0
    assert obs.snapshot()["serving.state.snapshot_evictions_total"] \
        - before == 1
    store.reset()
    assert store.nbytes == 0 and store.get(b"a") is None


def test_linear_layers_come_with_their_state_shape():
    with pytest.raises(ValueError, match="state_shape"):
        serving.ServingConfig(num_layers=2, num_heads=2, head_dim=8,
                              max_len=32, page_size=4,
                              layer_kinds=("sparse", "linear"))
