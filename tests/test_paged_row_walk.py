"""The grouped paged-decode kernel's walk of live rows and live pages
(ISSUE 35), under the Pallas interpreter against the per-layer dense tier:
one grid step a batch row, a loop inside the kernel over the row's own page
groups with double-buffered copies out of the pool where it lies, the next
row's first group started in a row's last trip.

The shapes are the two the serving cells run — Command A+'s 16 query heads
to each of 8 KV heads of 128, Qwen3-Next's 8 to each of 2 of 256 — on pages
of 64, so a group of 8 pages ends at position 512; the contexts sit on every
edge of the walk.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.ops import paged_attention as pa

PS, COLS, WINDOW = 64, 20, 640           # max_len 1280; a window of 10 pages
MAX_LEN = PS * COLS
GROUP = pa._GROUP_PAGES * PS             # positions a group of pages holds

# a bucket's rows (write positions): each edge, with a padding row (t = 0)
# between live rows and rows of very different lengths side by side
CONTEXTS = {
    "page_edges": [PS - 1, 0, PS, PS + 1, 1],
    "group_edges": [GROUP - 1, GROUP, 0, GROUP + 1],
    "long_beside_short": [MAX_LEN - 1, 1, 0, 2 * GROUP, 0],
    # window_first_page moves from 0 to 1 between 702 and 703
    "window_edges": [WINDOW - 1, WINDOW, 702, 0, 703],
}


def _case(rng, kv, window, h_kv, rep, d, ts):
    """A pool in which every row has pages of its own, the decode tables a
    layer of this kind is given (the compact window table for a window
    layer) and the step's q / k_new / v_new."""
    b = len(ts)
    pages = 1 + b * COLS
    shape = (pages, 2, 2, h_kv, PS, d)
    scales = None
    if kv == "int8":
        pool = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = jnp.asarray(rng.uniform(0.01, 0.03, shape[:4]), jnp.float32)
    else:
        pool = jnp.asarray(rng.standard_normal(shape, np.float32), kv)
    logical = 1 + np.arange(b * COLS, dtype=np.int32).reshape(b, COLS)
    for r, t in enumerate(ts):             # pages past t: the scratch page
        logical[r, -(-(t + 1) // PS):] = 0
    if window is None:
        tables = logical
    else:
        cols = pa.window_table_pages(window, PS)
        tables = np.zeros((b, cols), np.int32)
        for r, t in enumerate(ts):
            first = int(pa.window_first_page(t, window, PS))
            row = logical[r, first:first + cols]
            tables[r, :row.size] = row
    q = jnp.asarray(rng.standard_normal((b, h_kv * rep, d)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, h_kv, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, h_kv, d)), jnp.float32)
    return (q, kn, vn, pool, scales, jnp.asarray(tables),
            jnp.asarray(ts, jnp.int32), jnp.asarray(1))


@pytest.mark.parametrize("contexts", sorted(CONTEXTS))
@pytest.mark.parametrize("heads", [(8, 16, 128), (2, 8, 256)],
                         ids=["8x16x128", "2x8x256"])
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("kv", ["bfloat16", "int8", "float32"])
def test_row_walk_agrees_with_the_dense_tier(kv, window, heads, contexts):
    h_kv, rep, d = heads
    assert rep >= pa._GROUPED_MIN_REP
    args = _case(np.random.default_rng(35), kv, window, h_kv, rep, d,
                 CONTEXTS[contexts])
    assert pa._kernel_for(h_kv * rep, args[3], PS, "kernel", True) == "rows"
    kw = dict(page_size=PS, interpret=True, window=window)
    got = np.asarray(pa.paged_attention(*args, impl="kernel", **kw))
    want = np.asarray(pa.paged_attention(*args, impl="dense", **kw))
    assert np.isfinite(got).all()
    # int8 pages carry values up to 127 x 0.03: the same relative error
    assert np.abs(got - want).max() < (1e-3 if kv == "int8" else 1e-4)


def test_a_padding_row_reads_no_page():
    """``t = 0`` is how ``Engine._launch`` fills a bucket: such a row makes
    no trip, so with every page its table names turned to NaN it still
    decodes to the step's own V, and the live row between two such rows
    reads what it read."""
    rng = np.random.default_rng(36)
    q, kn, vn, pool, _, tables, t, layer = _case(
        rng, "float32", None, 2, 8, 128, [0, 70, 0])
    own = 1 + np.arange(3 * COLS, dtype=np.int32).reshape(3, COLS)
    tables = tables.at[0].set(own[0]).at[2].set(own[2])
    poisoned = pool.at[np.concatenate([own[0], own[2]])].set(jnp.nan)
    kw = dict(page_size=PS, interpret=True, impl="kernel")
    got = np.asarray(pa.paged_attention(q, kn, vn, poisoned, None, tables, t,
                                        layer, **kw))
    want = np.asarray(pa.paged_attention(q, kn, vn, pool, None, tables, t,
                                         layer, **kw))
    assert np.array_equal(got, want)
    for row in (0, 2):
        assert np.abs(got[row].reshape(2, 8, 128)
                      - np.asarray(vn[row])[:, None]).max() < 1e-6


def test_grouped_eligibility_counts_the_group_of_pages():
    # the row walk holds 2 x 8 pages of K and V: Command A+'s 4 MiB in bf16
    # and 8 MiB in float32 fit, 32 KV heads of 128 in float32 do not (they
    # fit the per-head kernel, which holds a page at a time)
    assert pa.kernel_eligible(64, 128, jnp.bfloat16, 8, 16)
    assert pa.kernel_eligible(64, 128, jnp.float32, 8, 16)
    assert pa.kernel_eligible(64, 256, jnp.bfloat16, 2, 8)
    assert pa.kernel_eligible(64, 128, jnp.float32, 32, 4)
    assert not pa.kernel_eligible(64, 128, jnp.float32, 32, 8)


# -- the gauge that says which kernel a decode program's layers took ---------

def _traced(engine, bucket):
    obs.set_gauge("serving.paged_attention_row_walk_layers", -1)
    engine.programs.warm(buckets=[bucket])
    return obs.snapshot()["serving.paged_attention_row_walk_layers"]


@pytest.fixture
def metrics():
    obs.enable()
    yield
    obs.disable()


def test_gauge_counts_command_a_plus_attention_layers(metrics, monkeypatch):
    from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                               Cohere2MoeForCausalLM)
    traced, body = [], pa._decode_kernel_grouped
    monkeypatch.setattr(
        pa, "_decode_kernel_grouped",
        lambda *a, **kw: traced.append(kw["window"]) or body(*a, **kw))
    pa._kernel_call.clear_cache()
    paddle.seed(5)
    cfg = Cohere2MoeConfig.tiny(num_attention_heads=32)     # 16 to 1
    model = Cohere2MoeForCausalLM(cfg)
    model.eval()
    eng = serving.Engine(*model.serving_callables(64), serving.ServingConfig(
        num_layers=cfg.num_hidden_layers, num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=64, max_batch=2, buckets=(2,),
        page_size=4, layer_kinds=cfg.layer_kinds, window=cfg.sliding_window,
        paged_attention="on"))
    # a period of four: three window layers and a full one, all on the walk
    assert _traced(eng, 2) == cfg.num_hidden_layers == 4
    # ... and the kernel's body is traced once a page kind, not once a layer:
    # on a TPU host each trace is seconds of every run's set-up
    assert sorted(traced, key=str) == [cfg.sliding_window, None]


def test_gauge_counts_qwen3_next_attention_layers(metrics):
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    paddle.seed(5)
    cfg = Qwen3NextConfig.tiny(num_attention_heads=16,      # 8 to 1
                               full_attention_interval=4, num_hidden_layers=8)
    model = Qwen3NextForCausalLM(cfg)
    model.eval()
    attention = sum(kind == "full" for kind in cfg.layer_kinds)
    eng = serving.Engine(*model.serving_callables(64, block=16),
                         serving.ServingConfig(
        num_layers=len(cfg.layers_run), num_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, max_len=64, max_batch=2, buckets=(2,),
        page_size=4, layer_kinds=cfg.layer_kinds,
        state_shape=cfg.state_shapes, state_snapshot_tokens=16,
        paged_attention="on"))
    # a period of four: three Gated DeltaNet layers to each attention layer
    assert _traced(eng, 2) == attention == 2


def test_gauge_reads_zero_for_llama_at_four_heads_to_one(metrics):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(5)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=64, hidden=64, layers=2, heads=8, kv_heads=2, inter=64,
        max_pos=64))
    model.eval()
    eng = serving.Engine(*model.serving_callables(64), serving.ServingConfig(
        num_layers=2, num_heads=2, head_dim=8, max_len=64, max_batch=2,
        buckets=(2,), page_size=8, paged_attention="on"))
    assert eng._paged_path == "kernel"
    assert _traced(eng, 2) == 0
