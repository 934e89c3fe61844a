"""The sparse decode kernel's walk of live rows and their chosen pages
(ISSUE 37), under the Pallas interpreter against the dense tier: one grid
step a batch row, a loop inside the kernel over each KV head's own list of
chosen pages with double-buffered copies out of the pool where it lies, the
next row's first group started in a row's last trip. And the gather of the
compressed keys, whose unused table columns read entries of their own.

The shapes are ``repo-agent-64k``'s: a bucket of 32 rows, 16 query heads to
each of 2 KV heads of 128, pages of 64, 64 chosen pages a head.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.ops import sparse_attention as sa

B, HKV, REP, D, PS, PAGES = 32, 2, 16, 128, 64, 200
LIVE = {1: [5], 7: [0, 3, 4, 9, 17, 20, 31], 32: list(range(B))}


def _case(rng, kv, live, cols, trailing=0, b=B):
    """A pool, the step's q / k_new / v_new and a table a (row, KV head):
    ``cols`` columns, the last ``trailing`` of a live row's naming no page
    and the column before them the page being written (part of it read); a
    row not in ``live`` is padding, every column naming the scratch page."""
    pool = jnp.asarray(rng.standard_normal((PAGES, 2, 2, HKV, PS, D),
                                           np.float32), kv)
    tables = np.zeros((b, HKV, cols), np.int32)
    lens = np.zeros((b, HKV, cols), np.int32)
    for r in live:
        for h in range(HKV):
            n = cols - trailing
            tables[r, h, :n] = rng.choice(np.arange(1, PAGES), n,
                                          replace=False)
            lens[r, h, :n] = PS
            lens[r, h, n - 1] = rng.integers(1, PS)
    q = jnp.asarray(rng.standard_normal((b, HKV * REP, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, HKV, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, HKV, D)), jnp.float32)
    return q, kn, vn, pool, tables, lens


def _both(q, kn, vn, pool, tables, lens, layer=1):
    return [np.asarray(sa.sparse_paged_attention(
        q, kn, vn, pool, jnp.asarray(tables), jnp.asarray(lens), layer,
        page_size=PS, impl=impl, interpret=True))
        for impl in ("kernel", "dense")]


def _plain(q, kn, vn, pool, tables, lens, r, h, layer=1):
    """Row ``r``'s query heads of KV head ``h`` over the positions its
    table lists and the step's own, in numpy."""
    pool = np.asarray(pool, np.float32)
    ks = [pool[p, layer, 0, h, :n] for p, n in zip(tables[r, h], lens[r, h])]
    vs = [pool[p, layer, 1, h, :n] for p, n in zip(tables[r, h], lens[r, h])]
    kk = np.concatenate(ks + [np.asarray(kn[r, h])[None]])
    vv = np.concatenate(vs + [np.asarray(vn[r, h])[None]])
    s = np.asarray(q[r, h * REP:(h + 1) * REP]) @ kk.T / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ vv


@pytest.mark.parametrize("columns", [(64, 0), (44, 5)],
                         ids=["K64", "K44_trailing_empty"])
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("kv", ["bfloat16", "float32"])
def test_walk_agrees_with_the_dense_tier(kv, live, columns):
    cols, trailing = columns
    args = _case(np.random.default_rng(37), kv, LIVE[live], cols, trailing)
    assert sa._takes_kernel(args[3], PS, "kernel", True)
    got, want = _both(*args)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < (2e-5 if kv == "float32" else 1e-4)
    for r in set(range(B)) - set(LIVE[live]):      # padding: the step's own V
        assert np.abs(got[r].reshape(HKV, REP, D)
                      - np.asarray(args[2][r])[:, None]).max() < 1e-6


def test_an_empty_page_at_the_front_of_a_list_does_not_end_it():
    """``t % 64 == 0``: the page being written holds no earlier token, and
    it sits among the forced blocks at the FRONT of a head's list. The
    pages after it are still read."""
    rng = np.random.default_rng(38)
    live = [1, 2]
    q, kn, vn, pool, tables, lens = _case(rng, "float32", live, 64, b=4)
    for r, at in zip(live, (0, 32)):
        lens[r, :, at] = 0
        tables[r, :, at] = 0                 # as _decode_layer hands it over
    got, want = _both(q, kn, vn, pool, tables, lens)
    assert np.abs(got - want).max() < 2e-5
    for r in live:
        for h in range(HKV):
            assert np.abs(got[r, h * REP:(h + 1) * REP] - _plain(
                q, kn, vn, pool, tables, lens, r, h)).max() < 2e-5


def test_each_kv_head_reads_its_own_list():
    """The two KV heads of a row choose different pages, of different
    counts: one head a whole list, the other three pages and part of a
    fourth, and in another row one head nothing but the step's own token."""
    rng = np.random.default_rng(39)
    live = [0, 2]
    q, kn, vn, pool, tables, lens = _case(rng, "float32", live, 64, b=3)
    assert not np.array_equal(tables[0, 0], tables[0, 1])
    lens[0, 1, 3], lens[0, 1, 4:], tables[0, 1, 4:] = 17, 0, 0
    lens[2, 0], tables[2, 0] = 0, 0
    got, want = _both(q, kn, vn, pool, tables, lens)
    assert np.abs(got - want).max() < 2e-5
    for r in live:
        for h in range(HKV):
            assert np.abs(got[r, h * REP:(h + 1) * REP] - _plain(
                q, kn, vn, pool, tables, lens, r, h)).max() < 2e-5
    assert np.abs(got[2, :REP] - np.asarray(vn[2, 0])).max() < 1e-6


def test_nothing_unlisted_is_copied():
    """Every page no live row's list names, the pages a padding row's table
    names among them, and the other layer and the other head's half of every
    page turned to NaN: the output is what it was."""
    rng = np.random.default_rng(40)
    live = [1, 3]
    q, kn, vn, pool, tables, lens = _case(rng, "float32", live, 64, 3, b=5)
    tables[0] = rng.integers(1, PAGES, (HKV, 64))    # a padding row's pages
    listed = np.zeros((PAGES, HKV), bool)
    listed[0] = True                     # the scratch page: empty columns
    for r in live:
        for h in range(HKV):
            listed[tables[r, h][lens[r, h] > 0], h] = True
    poisoned = np.array(pool)
    poisoned[:, 0] = np.nan                              # the other layer
    poisoned[:, 1] = np.where(listed[:, None, :, None, None],
                              poisoned[:, 1], np.nan)
    assert np.isnan(poisoned[tables[0, 0]]).any()
    got, _ = _both(q, kn, vn, jnp.asarray(poisoned), tables, lens)
    want, _ = _both(q, kn, vn, pool, tables, lens)
    assert np.isfinite(got).all()
    assert np.array_equal(got, want)


# -- the gather of the compressed keys ---------------------------------------

CFG = sa.SparseConfig(kernel_size=8, kernel_stride=4, block_size=8, topk=6,
                      init_blocks=1, window_size=16, dense_len=32)
G_ROWS, G_WIDTH, G_PAGES, G_PS, G_D = 5, 24, 200, 8, 16


def _layer_case(rng):
    """``_decode_layer``'s operands: rows past ``dense_len``, one at the
    first position of a page and a padding row, their pages drawn from the
    pool's upper half; every column a row does not use names the scratch
    page, as the engine's tables have it."""
    t = np.array([G_WIDTH * G_PS - 3, 0, 64, 35, 101], np.int32)
    tables = np.zeros((G_ROWS, G_WIDTH), np.int32)
    for r, pos in enumerate(t):
        if pos:
            n = pos // G_PS + 1
            tables[r, :n] = rng.choice(
                np.arange(G_ROWS * G_WIDTH, G_PAGES), n, replace=False)
    f32 = np.float32
    pool = rng.standard_normal((G_PAGES, 2, 2, HKV, G_PS, G_D), f32)
    index_pool = rng.standard_normal(
        (G_PAGES, 2, CFG.per_block * HKV, G_D), f32)
    q = rng.standard_normal((G_ROWS, HKV * 2, G_D), f32)
    kn = rng.standard_normal((G_ROWS, HKV, G_D), f32)
    vn = rng.standard_normal((G_ROWS, HKV, G_D), f32)
    return [jnp.asarray(a) for a in (q, kn, vn, pool, index_pool, tables, t)]


def _layer(args, index_pool=None):
    q, kn, vn, pool, ip, tables, t = args
    return [np.asarray(o) for o in sa._decode_layer(
        q, kn, vn, pool, ip if index_pool is None else index_pool, tables, t,
        1, CFG, G_PS, "kernel", True)]


def test_unused_columns_read_entries_of_their_own_and_choose_the_same(
        monkeypatch):
    """The blocks a step chooses, and everything else it returns, are what
    the parent's rule gave (an unused column reading the scratch page's
    entry) — also with NaN in every entry an unused column now reads."""
    args = _layer_case(np.random.default_rng(41))
    got = _layer(args)
    unused = G_ROWS * G_WIDTH            # _unused_entry names pages below it
    poisoned = _layer(args, args[4].at[:unused].set(jnp.nan))
    with monkeypatch.context() as m:
        m.setattr(sa, "_unused_entry",
                  lambda rows, width, pages: jnp.zeros((rows, width),
                                                       jnp.int32))
        want = _layer(args)
    assert (want[4] >= 0).any() and (want[4] == -1).any()
    for a, b, c in zip(got, poisoned, want):
        assert np.array_equal(a, c) and np.array_equal(b, c)


@pytest.mark.parametrize("shape", [(32, 1040, 33281), (5, 24, 200),
                                   (3, 16, 16)])
def test_unused_entries_lie_inside_the_pool_and_apart(shape):
    rows, width, pages = shape
    at = np.asarray(sa._unused_entry(rows, width, pages))
    assert at.shape == (rows, width) and at.dtype == np.int32
    assert at.min() >= 0 and at.max() < pages
    for row in at:                       # no two columns of a row one page
        assert np.unique(row).size == width


# -- the gauge that says the sparse layers took the walk ---------------------

def _traced(engine, bucket):
    obs.set_gauge("serving.sparse_attention_row_walk_layers", -1)
    engine.programs.warm(buckets=[bucket])
    return obs.snapshot()["serving.sparse_attention_row_walk_layers"]


@pytest.fixture
def metrics():
    obs.enable()
    yield
    obs.disable()


@pytest.mark.parametrize("tier", ["on", "off"])
def test_gauge_counts_minicpm_sala_sparse_layers(metrics, monkeypatch, tier):
    from paddle_tpu.models.minicpm_sala import (MiniCPMSalaConfig,
                                                MiniCPMSalaForCausalLM)
    traced, body = [], sa._sparse_decode_kernel
    monkeypatch.setattr(sa, "_sparse_decode_kernel",
                        lambda *a, **kw: traced.append(1) or body(*a, **kw))
    sa._sparse_kernel_call.clear_cache()
    paddle.seed(31)
    model = MiniCPMSalaForCausalLM(MiniCPMSalaConfig.tiny())
    model.eval()
    c = model.config
    sparse = c.layer_kinds.count("sparse")
    eng = serving.Engine(*model.serving_callables(96, block=8),
                         serving.ServingConfig(
        num_layers=len(c.layers_run), num_heads=c.num_key_value_heads,
        head_dim=c.head_dim, max_len=96, max_batch=2, buckets=(2,),
        page_size=c.sparse.block_size, compute_dtype="float32",
        layer_kinds=c.layer_kinds, state_shape=c.state_shape,
        index_per_page=c.sparse.per_block, state_snapshot_tokens=8,
        paged_attention=tier))
    assert sparse >= 2
    assert _traced(eng, 2) == (sparse if tier == "on" else 0)
    # ... and the kernel's body is traced once for all of them: on a TPU
    # host each trace is seconds of every run's set-up
    assert len(traced) == (tier == "on")


def test_gauge_reads_zero_for_llama(metrics):
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
