"""Block-sparse softmax attention whose blocks are chosen per query token
and KV head from mean-pooled keys (InfLLM-V2 as MiniCPM4 runs it, ISSUE 31),
on the serving engine's pages: a block IS a page.

The selection, for the query at position ``t`` with more than ``dense_len``
tokens of context (at or below it the query attends to everything):

* compressed keys ``K^c_j = mean(k[stride * j : stride * j + kernel])`` for
  every kernel that ends at or before ``t``. Stored and indexed by where
  the kernel ENDS: entry ``f = (stride * j + kernel - 1) // stride`` (``j +
  1`` for kernel 32 on stride 16), ``page // stride`` entries to a page, so
  the entries of a page depend on the tokens up to the page's end only —
  a shared prefix page shares its entries (:func:`compress_keys`);
* per query head ``p_h = softmax_j(q_h . K^c_j / sqrt(D))`` over the
  visible ``j``; per KV head ``a_j = sum`` of ``p_h`` over its query heads;
  per block ``m`` the largest ``a_j`` over the kernels that overlap it;
* the first ``init_blocks`` blocks and the ``window_size / block`` blocks
  that end at ``t``'s own are forced; the ``topk`` best blocks, forced ones
  included, are kept (:func:`select_blocks`). The selection has no weights.

Prefill (:func:`sparse_prefill_attention`) turns the chosen blocks into a
mask and runs masked attention over blocks of query rows — plain
``jax.numpy``; on a TPU's 128-row matrix unit a query token's 16 heads to a
KV head would fill an eighth of a matmul's rows if its 64 blocks were
gathered for it alone, so the rows of many tokens go through together and
the mask does the choosing. There is no kernel of this repo's in it.

Decode (:func:`sparse_decode_attention`) scores the row's compressed keys,
chooses, and hands a ``(B, H_kv, topk)`` table of PHYSICAL pages to a Pallas
kernel that copies those pages only — ``sparse_attention_decode`` in a
device trace. Its work follows what is live (ISSUE 37): one grid step a row
of the bucket, the pool handed over once where it lies in HBM, and inside a
loop over each KV head's own list of chosen pages, a group of pages a trip
through a two-slot VMEM buffer — the walk of
``paged_attention._decode_kernel_grouped``, whose list is a range of the
row's table where this one's is what the head chose; a padding row copies
nothing. The gather of the compressed keys before it still visits every
column of every row's table, but a column its row does not use reads an
entry of its own, not the scratch page's that every such column names
(:func:`_unused_entry`). Rows at or below ``dense_len`` go through
``paged_attention`` over their first ``dense_len / page`` pages instead,
under a ``lax.cond`` that costs nothing when no row is that short. The
compressed key a decoded token completes is computed from the pool's last
pages and written, with the token's K and V, after the last layer
(:func:`commit_index`).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (PagedDecodeCache, _NEG_INF, kernel_eligible,
                              paged_attention)

__all__ = ["SparseConfig", "HybridDecodeCache", "HybridPrefill", "compress_keys",
           "select_blocks", "sparse_prefill_attention",
           "sparse_decode_attention", "commit_index", "pages_counted",
           "sparse_paged_attention",
           "sparse_paged_attention_dense"]

_BIG = 1e30
# pages a trip of the decode kernel's walk copies for one (row, KV head): a
# row past dense_len has topk = 64 pages a head, two trips. Alone on a v5e at
# repo-agent-64k's shapes (7 live rows of 32) a layer's call took 0.131 /
# 0.085 / 0.066 / 0.059 ms at 4 / 8 / 16 / 32 pages a trip (PERF.md section
# 6, PR 37): a trip's fixed cost, not the first group's wait, is what counts
_GROUP_PAGES = 32


@dataclass(frozen=True)
class SparseConfig:
    """MiniCPM4's published ``sparse_config`` (arXiv:2506.07900)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.block_size % self.kernel_stride or \
                self.kernel_size % self.kernel_stride or \
                self.window_size % self.block_size or \
                self.dense_len % self.block_size:
            raise ValueError(f"sparse_config does not tile: {self}")

    @property
    def per_block(self) -> int:
        """Compressed keys stored with one block (page)."""
        return self.block_size // self.kernel_stride

    @property
    def entry_offset(self) -> int:
        """Entry ``f`` holds kernel ``j = f - entry_offset``."""
        return (self.kernel_size - 1) // self.kernel_stride

    def visible(self, f, pos):
        """Whether entry ``f``'s kernel exists and ends at or before
        ``pos`` (arrays broadcast)."""
        j = f - self.entry_offset
        return (j >= 0) & (self.kernel_stride * j + self.kernel_size - 1
                           <= pos)


def compress_keys(k, cfg: SparseConfig):
    """``k`` (Tk, H_kv, D), ``Tk`` a multiple of the block size -> entries
    ``(Tk / stride, H_kv, D)`` float32: entry ``f`` is the mean of kernel
    ``f - entry_offset``'s keys, zero where that kernel does not exist. An
    entry whose kernel runs past the true length is the mean over padding
    and is never visible."""
    tk, h, d = k.shape
    st, n = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    units = jnp.sum(k.astype(jnp.float32).reshape(tk // st, st, h, d), axis=1)
    # kernel j = units j .. j + n - 1, stored at f = j + entry_offset
    total = sum(jnp.pad(units, ((n - 1 - i, 0), (0, 0), (0, 0)))[
        :tk // st] for i in range(n))
    lead = cfg.entry_offset - (n - 1)        # 0 for kernel = 2 * stride
    total = jnp.pad(total, ((lead, 0), (0, 0), (0, 0)))[:tk // st]
    exists = (jnp.arange(tk // st) >= cfg.entry_offset)[:, None, None]
    return jnp.where(exists, total / cfg.kernel_size, 0.0)


def select_blocks(scores, pos, cfg: SparseConfig, num_blocks: int):
    """``scores`` (N, H_kv, rep, F) — ``q_h . K^c_f / sqrt(D)`` for the
    ``F = per_block * num_blocks`` entries — and the queries' positions
    ``pos`` (N,) -> ``(blocks (N, H_kv, K) int32, chosen (N, H_kv, K)
    bool)``, ``K = min(topk, num_blocks)``, best first; ``chosen`` is False
    where fewer than ``K`` blocks exist at that position."""
    f = scores.shape[-1]
    per = cfg.per_block
    seen = cfg.visible(jnp.arange(f)[None, :], pos[:, None])     # (N, F)
    s = jnp.where(seen[:, None, None, :], scores.astype(jnp.float32),
                  -jnp.inf)
    p = jnp.exp(s - jnp.maximum(jnp.max(s, -1, keepdims=True), -_BIG))
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    a = jnp.sum(p, axis=2)                                       # (N, Hkv, F)
    # block m overlaps kernels j with stride*j < block*(m+1) and
    # stride*j + kernel > block*m: entries lo(m) .. lo(m) + width - 1
    nk = cfg.kernel_size // cfg.kernel_stride
    lo0 = cfg.entry_offset + 1 - nk
    width = per + nk - 1
    front = max(0, -lo0)
    a = jnp.pad(a, ((0, 0), (0, 0), (front, width + per)))
    best = functools.reduce(jnp.maximum, [
        a[..., lo0 + front + d_:lo0 + front + d_ + per * num_blocks:per]
        for d_ in range(width)])                                 # (N, Hkv, M)
    m = jnp.arange(num_blocks)[None, :]
    own = (pos // cfg.block_size)[:, None]
    forced = (m < cfg.init_blocks) | \
        (m > own - cfg.window_size // cfg.block_size)
    best = jnp.where(forced[:, None, :], _BIG, best)
    best = jnp.where((m <= own)[:, None, :], best, -_BIG)
    vals, blocks = jax.lax.top_k(best, min(cfg.topk, num_blocks))
    return blocks.astype(jnp.int32), vals > -_BIG / 2


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

# a block of query rows is sized so that its float32 scores against every
# key stay about this many elements (0.5 GB)
_SCORE_ELEMENTS = 1 << 27


def sparse_prefill_attention(q, k, v, start: int, cfg: SparseConfig,
                             entries=None, sm_scale: Optional[float] = None):
    """``q`` (T, H, D) at positions ``start + i`` over ``k``/``v`` (Tk, H_kv,
    D) at positions ``0 .. Tk`` (``Tk >= start + T``; rows past it are
    padding) -> ``(T, H, D)`` in ``q``'s dtype. ``entries`` are
    :func:`compress_keys` of ``k`` (computed here if not given). Query rows
    go in blocks under a ``lax.map``; keys past the last query of a
    quarter of the rows are cut off (a static slice per quarter), so the
    work is about 5/8 of rows x keys."""
    t, h, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    bs = cfg.block_size
    scale = (1.0 / float(d) ** 0.5) if sm_scale is None else sm_scale
    pad_k = -k.shape[0] % bs
    k = jnp.pad(k, ((0, pad_k), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, pad_k), (0, 0), (0, 0)))
    if entries is None:
        entries = compress_keys(k, cfg)
    f32 = jnp.float32
    sparse_rows = start + t > cfg.dense_len   # static: any row past it

    def rows_block(qb, pos, kk, vv, ee):
        """``qb`` (C, H, D) at ``pos`` (C,) over the first ``kk.shape[0]``
        keys."""
        c = qb.shape[0]
        tk = kk.shape[0]
        qg = qb.reshape(c, hkv, rep, d)
        cols = jnp.arange(tk)
        keep = cols[None, :] <= pos[:, None]                     # (C, Tk)
        keep = jnp.broadcast_to(keep[:, None, :], (c, hkv, tk))
        if sparse_rows:
            sc = jnp.einsum("cgrd,fgd->cgrf", qg, ee.astype(qb.dtype),
                            preferred_element_type=f32) * scale
            blocks, chosen = select_blocks(sc, pos, cfg, tk // bs)
            hit = jnp.any((blocks[..., None] == jnp.arange(tk // bs))
                          & chosen[..., None], axis=2)           # (C,Hkv,M)
            dense = (pos + 1 <= cfg.dense_len)[:, None, None]
            keep &= jnp.repeat(hit | dense, bs, axis=2)
        s = jnp.einsum("cgrd,kgd->gcrk", qg, kk,
                       preferred_element_type=f32) * scale
        s = jnp.where(jnp.swapaxes(keep, 0, 1)[:, :, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
        o = jnp.einsum("gcrk,kgd->cgrd", p, vv, preferred_element_type=f32)
        return o.reshape(c, h, d).astype(qb.dtype)

    # a power of two of rows to a block, at most 256
    c = 1 << max(0, min(8, (_SCORE_ELEMENTS // (k.shape[0] * h)
                            ).bit_length() - 1))
    c = min(c, 1 << max(0, (t - 1).bit_length()))
    pad_q = -t % c
    qp = jnp.pad(q, ((0, pad_q), (0, 0), (0, 0)))
    pos = start + jnp.arange(t + pad_q)
    n_blocks = (t + pad_q) // c
    parts = 4 if n_blocks >= 8 else 1
    outs, at = [], 0
    for part in range(parts):
        upto = n_blocks * (part + 1) // parts
        rows = slice(at * c, upto * c)
        # keys this part's last row can see, in whole blocks
        tk = min(k.shape[0], -(-(start + upto * c) // bs) * bs)
        kk, vv, ee = k[:tk], v[:tk], entries[:tk // cfg.kernel_stride]
        out = jax.lax.map(
            lambda xs: rows_block(xs[0], xs[1], kk, vv, ee),
            (qp[rows].reshape(upto - at, c, h, d),
             pos[rows].reshape(upto - at, c)))
        outs.append(out.reshape(-1, h, d))
        at = upto
    return jnp.concatenate(outs)[:t] if parts > 1 else outs[0][:t]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

@dataclass
class HybridDecodeCache(PagedDecodeCache):
    """:class:`PagedDecodeCache` for a model that mixes sparse-attention
    layers (pages, and compressed keys beside them) with layers that keep a
    fixed state per slot (ISSUE 31). The page fields are the sparse layers'
    pool. Beside them, all Tensors:

    * ``index_pool`` — ``(num_pages, L_sparse, per_block * H_kv, D)``
      float32: the compressed keys stored with each page (row ``e * H_kv +
      h`` the page's ``e``-th entry of KV head ``h``; values rounded to the
      pages' dtype, held in float32 so that a page's rows are one whole
      (8, 128) tile and a gather moves no padding), indexed by the same
      tables
    * ``states`` — one part: ``((rows, L_lin, H, D, D) float32,)``, row 0 scratch
    * ``state_rows`` — ``(B,)`` int32: each batch row's row of the state
    * ``pending_index`` — per sparse layer decoded so far, the compressed
      key its token completes ``(B, H_kv, D)`` (written by
      :func:`commit_index` for the rows whose token ends a kernel)
    * ``chose`` — per sparse layer decoded so far, what its selection did:
      ``(pages (B, 2) int32, blocks (B, H_kv, K) int32)`` — the pages the
      row held and the pages it attended, each counted per KV head, and
      the logical blocks chosen (-1: none); :func:`pages_counted` sums the
      first over a step
    * ``sparse_walk_layers`` — of the sparse layers decoded so far, those
      whose call took the kernel that walks live rows' chosen pages and not
      the dense tier (``paged_attention.commit_pending`` files the count as
      the gauge ``serving.sparse_attention_row_walk_layers``)
    """

    index_pool: object = None
    states: tuple = ()
    state_rows: object = None
    sparse: Optional[SparseConfig] = None
    pending_index: tuple = ()
    chose: tuple = ()
    sparse_walk_layers: int = 0


@dataclass
class HybridPrefill:
    """What a prefill of such a model reads and leaves, in place of the dense
    stacked cache (Tensors): ``kv`` ``(L_sparse, 2, 1, H_kv, max_len, D)``
    — positions below ``start`` hold the shared prefix, the prefill writes
    ``[start, start + Lp)``; ``states`` ``((L_lin, H, D, D) float32,)``, the
    state before ``start`` going in and after the last token coming out.
    Coming out only: ``entries`` ``(L_sparse, H_kv, max_len / stride, D)``,
    the compressed keys of every position up to the prompt's end, and
    ``snapshots`` ``((n, L_lin, H, D, D),)``, the state after each whole
    ``block`` of the run (``n = Lp // block``). One part, in tuples."""

    kv: object
    states: tuple
    entries: Optional[object] = None
    snapshots: Optional[tuple] = None


def _sparse_decode_kernel(tables_ref, lens_ref, counts_ref, layer_ref, q_ref,
                          kn_ref, vn_ref, pool_ref, o_ref, buf, sem, slot_ref,
                          m_ref, l_ref, acc_ref, *, page_size: int,
                          num_kv_heads: int, group: int, sm_scale: float,
                          exact: bool):
    """One batch row a grid step; inside, a loop over the row's KV heads'
    own lists of chosen pages, ``group`` pages a trip, copied out of the
    pool where it lies in HBM (ISSUE 37: the walk of
    ``paged_attention._decode_kernel_grouped``, whose list here is a head's
    chosen pages and not a range of the row's table).

    ``lens[b * H_kv + h, c]`` is how many leading positions of table column
    ``c``'s page the row attends: the page size for a whole page, less for
    the page being written, 0 for a column that names no page — at the end
    of a head's list, or the page being written when ``t`` is its first
    position, among the forced ones at the list's front. ``counts[b * H_kv
    + h]`` is the head's last column with a position to read, plus one: the
    head makes ``ceil(count / group)`` trips and masks by ``lens`` inside a
    group, so a padding row (every count 0) copies and multiplies nothing.

    A row's trips run head after head as one sequence. A trip waits for its
    group's copies (one a page: a head's K and V of a page are two strided
    halves of one copy) in one half of a two-slot VMEM buffer, having
    started the next trip's into the other half; a row's last trip — or a
    row without trips — starts the NEXT row's first, so of a whole call only
    the first copy is waited for with nothing to do. Every row, a padding
    row too, then folds in position ``t`` and writes its output.

    Refs: q/out ``(1, H_kv, rep, D)`` float32 (q NOT scaled), kn/vn ``(1,
    H_kv, 1, D)``, the pool ``(P, L, 2, H_kv, ps, D)`` whole. Scratch: the
    page buffer ``(2, group, 2, ps, D)`` with a DMA semaphore a slot, the
    slot this row's first trip is in (SMEM: it outlives the grid step), m/l
    ``(H_kv, rep, 1)``, acc ``(H_kv, rep, D)``. Precision as
    ``_decode_kernel_grouped``."""
    b = pl.program_id(0)
    ps, hkv = page_size, num_kv_heads
    f32 = jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact \
        else jax.lax.Precision.DEFAULT
    layer = layer_ref[0]

    def walk(row):
        """Where each KV head's trips begin in row ``row``'s sequence, and
        how many trips the row makes."""
        begins, total = [], 0
        for h in range(hkv):
            begins.append(total)
            total = total + pl.cdiv(counts_ref[row * hkv + h], group)
        return begins, total

    def locate(begins, i):
        """Trip ``i`` of a row: ``(KV head, group of its list)``."""
        h, g = 0, i
        for j in range(1, hkv):
            past = i >= begins[j]
            h, g = jnp.where(past, j, h), jnp.where(past, i - begins[j], g)
        return h, g

    def copies(row, h, g, slot):
        """Group ``g`` of head ``h``'s list into ``slot``: a copy a page."""
        line, done = row * hkv + h, sem.at[slot]
        return [pltpu.make_async_copy(
            pool_ref.at[tables_ref[line, g * group + j], layer, :, h],
            buf.at[slot, j], done) for j in range(group)]

    def start(row, h, g, slot):
        for c in copies(row, h, g, slot):
            c.start()

    begins, n = walk(b)

    @pl.when(b == 0)                         # nobody started row 0's
    def _first_row():
        slot_ref[0] = 0

        @pl.when(n > 0)
        def _():
            start(0, *locate(begins, 0), 0)

    slot0 = slot_ref[0]                      # where this row's trip 0 is
    slot_ref[0] = (slot0 + n) % 2            # ... and the next row's

    def start_next_row():
        @pl.when(b + 1 < pl.num_programs(0))
        def _():
            begins1, n1 = walk(b + 1)

            @pl.when(n1 > 0)
            def _():
                start(b + 1, *locate(begins1, 0), (slot0 + n) % 2)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n == 0)
    def _no_trip():
        start_next_row()

    def trip(i, carry):
        slot = (slot0 + i) % 2
        h, g = locate(begins, i)

        @pl.when(i + 1 < n)
        def _():
            start(b, *locate(begins, i + 1), 1 - slot)

        @pl.when(i + 1 == n)
        def _():
            start_next_row()

        for c in copies(b, h, g, slot):
            c.wait()
        line = b * hkv + h
        col = jax.lax.broadcasted_iota(jnp.int32, (1, group * ps), 1)
        of_page = col // ps
        limit = jnp.zeros((1, group * ps), jnp.int32)
        for j in range(group):               # column's page j attends lens
            limit = jnp.where(of_page == j,
                              lens_ref[line, g * group + j] + j * ps, limit)
        live = col < limit
        # one load across the group's pages (every access to a ref costs
        # the trace as much as an operation: PERF.md section 6, PR 35)
        kv = buf[slot].astype(f32)                      # (group, 2, ps, D)
        logits = jax.lax.dot_general(
            q_ref[0, h], kv[:, 0].reshape(group * ps, -1),
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=f32) * sm_scale      # (rep, group * ps)
        logits = jnp.where(live, logits, _NEG_INF)
        m_prev = m_ref[h]                               # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p, kv[:, 1].reshape(group * ps, -1), precision=precision,
            preferred_element_type=f32)                 # (rep, D)
        m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n, trip, 0)

    # fold in position t and emit, every KV head at once
    logit_t = jnp.sum(q_ref[0] * kn_ref[0], axis=2,
                      keepdims=True) * sm_scale         # (H_kv, rep, 1)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logit_t)
    p_t = jnp.exp(logit_t - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_fin = alpha * l_ref[...] + p_t
    acc = alpha * acc_ref[...] + p_t * vn_ref[0]
    o_ref[0] = acc / jnp.maximum(l_fin, 1e-30)


# jitted so that a decode program's sparse layers share ONE trace of the
# kernel's body: the layer is an operand, not part of the trace (on a TPU
# host a kernel's trace is seconds of every run's set-up, cached or not:
# PERF.md section 6, PR 35)
@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _sparse_kernel_call(q, k_new, v_new, pool, tables, lens, layer,
                        page_size: int, interpret: bool):
    b, h, d = q.shape
    hkv = pool.shape[3]
    ps, group = page_size, _GROUP_PAGES
    cols = tables.shape[-1]
    tables = tables.reshape(b * hkv, cols).astype(jnp.int32)
    lens = lens.reshape(b * hkv, cols).astype(jnp.int32)
    if cols % group:             # whole groups: columns that name no page
        extra = ((0, 0), (0, -cols % group))
        tables, lens = jnp.pad(tables, extra), jnp.pad(lens, extra)
    # a head's last column with a position to read, plus one
    counts = jnp.max(jnp.where(lens > 0, jnp.arange(1, lens.shape[1] + 1), 0),
                     axis=1).astype(jnp.int32)
    qo = (hkv, h // hkv, d)                  # a KV head's query heads: rows
    f32 = jnp.float32

    def row_map(bi, tabs, ln, cn, lr):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(b,),
        in_specs=[pl.BlockSpec((1,) + qo, row_map),
                  pl.BlockSpec((1, hkv, 1, d), row_map),
                  pl.BlockSpec((1, hkv, 1, d), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1,) + qo, row_map),
        scratch_shapes=[
            pltpu.VMEM((2, group, 2, ps, d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),     # the slot of the row's trip 0
            pltpu.VMEM(qo[:2] + (1,), f32),  # running max
            pltpu.VMEM(qo[:2] + (1,), f32),  # running denominator
            pltpu.VMEM(qo, f32),             # weighted-V accumulator
        ])
    out = pl.pallas_call(
        functools.partial(
            _sparse_decode_kernel, page_size=ps, num_kv_heads=hkv,
            group=group, sm_scale=1.0 / float(d) ** 0.5,
            exact=pool.dtype == jnp.float32),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + qo, f32),
        # a row starts the next row's first copies: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sparse_attention_decode",
    )(tables, lens, counts, jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(f32).reshape((b,) + qo),
      k_new.astype(f32).reshape(b, hkv, 1, d),
      v_new.astype(f32).reshape(b, hkv, 1, d), pool)
    return out.reshape(b, h, d).astype(q.dtype)


def sparse_paged_attention_dense(q, k_new, v_new, pool, tables, lens, layer,
                                 page_size: int):
    """The reference math of :func:`sparse_paged_attention`: gather each
    (row, KV head)'s table of pages for the one layer, mask by ``lens``,
    fold in the current token."""
    p_, l_, _, hkv, ps, d = pool.shape
    b, _, cols = tables.shape
    rep = q.shape[1] // hkv
    f32 = jnp.float32
    idx = tables.astype(jnp.int32) * l_ + jnp.asarray(layer, jnp.int32)
    taken = jnp.take(pool.reshape(p_ * l_, 2, hkv, ps, d), idx, axis=0)
    # (B, Hkv, cols, 2, Hkv, ps, D): each head keeps its own head's rows
    own = jnp.arange(hkv)
    taken = taken[:, own, :, :, own].astype(f32)      # (Hkv, B, cols, 2, ps, D)
    taken = jnp.swapaxes(taken, 0, 1)
    kk = taken[:, :, :, 0].reshape(b, hkv, cols * ps, d)
    vv = taken[:, :, :, 1].reshape(b, hkv, cols * ps, d)
    live = (jnp.arange(ps)[None, None, None, :]
            < lens[..., None]).reshape(b, hkv, cols * ps)
    qg = q.astype(f32).reshape(b, hkv, rep, d)
    s = jnp.einsum("bgrd,bgkd->bgrk", qg, kk) / float(d) ** 0.5
    s = jnp.where(live[:, :, None, :], s, _NEG_INF)
    s_t = jnp.einsum("bgrd,bgd->bgr", qg, k_new.astype(f32)) / float(d) ** 0.5
    s = jnp.concatenate([s, s_t[..., None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bgkd->bgrd", p[..., :-1], vv) \
        + p[..., -1:] * v_new.astype(f32)[:, :, None, :]
    return o.reshape(b, -1, d).astype(q.dtype)


def sparse_paged_attention(q, k_new, v_new, pool, tables, lens, layer, *,
                           page_size: int, impl: str = "kernel",
                           interpret: bool = False):
    """Decode attention of one layer over the pages ``tables`` (B, H_kv,
    K) names per row AND KV head: ``lens`` (B, H_kv, K) leading positions
    of each are attended (0: the column names no page), then the current
    token's ``k_new`` / ``v_new`` (B, H_kv, D) unquantized. ``q`` (B, H,
    D); ``pool`` (P, L, 2, H_kv, page, D). Returns (B, H, D)."""
    if _takes_kernel(pool, page_size, impl, interpret):
        return _sparse_kernel_call(q, k_new, v_new, pool, tables, lens,
                                   layer, page_size, interpret)
    return sparse_paged_attention_dense(q, k_new, v_new, pool, tables, lens,
                                        layer, page_size)


def _takes_kernel(pool, page_size: int, impl: str, interpret: bool) -> bool:
    """Whether a layer's call takes the kernel (else the dense tier). What
    the kernel holds of the pool, two groups of one KV head's pages, is what
    a page of ``_GROUP_PAGES`` KV heads would be to ``paged_attention``."""
    return impl == "kernel" and (interpret or kernel_eligible(
        page_size, int(pool.shape[-1]), pool.dtype, _GROUP_PAGES))


def _unused_entry(rows: int, width: int, num_pages: int):
    """``(rows, width)`` int32: the page whose entry the gather reads for a
    table column its row does not use — inside the pool, and no two columns
    of a row the same page while a row's table is no wider than the pool."""
    at = jnp.arange(rows, dtype=jnp.int32)[:, None] * width \
        + jnp.arange(width, dtype=jnp.int32)[None, :]
    return at % num_pages


def _decode_layer(q, k_new, v_new, pool, index_pool, tables, t, layer,
                  cfg: SparseConfig, page_size: int, impl: str,
                  interpret: bool):
    """One sparse layer's decode step on arrays: ``(out (B, H, D), the
    compressed key position t completes (B, H_kv, D) float32, whether it
    completes one (B,), pages held and attended per KV head (B, 2), the
    blocks chosen (B, H_kv, K), -1 for none)``."""
    p_, l_, _, hkv, ps, d = pool.shape
    b, width = tables.shape
    rep = q.shape[1] // hkv
    per, st = cfg.per_block, cfg.kernel_stride
    f32 = jnp.float32
    t32 = t.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    # the compressed key whose kernel ends at t: its keys lie in the page
    # being written and the one before it
    spans = -(-cfg.kernel_size // ps) + 1
    page = t32 // ps
    back = page[:, None] - jnp.arange(spans - 1, -1, -1)[None, :]
    near = jnp.clip(back, 0, width - 1)                          # (B, spans)
    ids = jnp.take_along_axis(tables, near, axis=1) * l_ + layer
    last = jnp.take(pool.reshape(p_ * l_, 2, hkv, ps, d), ids,
                    axis=0)[:, :, 0].astype(f32)     # (B, spans, Hkv, ps, D)
    where = (near * ps)[:, :, None] + jnp.arange(ps)[None, None, :]
    inside = (where > (t32 - cfg.kernel_size)[:, None, None]) & \
        (where < t32[:, None, None]) & (back >= 0)[:, :, None]   # (B,spans,ps)
    fresh = (jnp.sum(jnp.where(inside[:, :, None, :, None], last, 0.0),
                     axis=(1, 3)) + k_new.astype(f32)) / cfg.kernel_size
    fresh = fresh.astype(pool.dtype).astype(f32)   # as the pool holds it
    f_new = t32 // st
    ends = ((t32 + 1) % st == 0) & cfg.visible(f_new, t32)
    # every entry of the row's pages: rows (e, h) of page w are entries
    # w * per + e, so the gathered rows ARE (B, F, Hkv, D). A column past
    # the row's own page names the scratch page in every row's table; the
    # gather reads an entry of the column's own there instead (ISSUE 37:
    # ~30,000 reads of one 4 KB entry a step took longer than as many reads
    # of distinct ones) — select_blocks discards what such a column scores
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    used = (col <= page[:, None]) & (t32 > 0)[:, None]
    own = _unused_entry(b, width, index_pool.shape[0])
    eidx = jnp.where(used, tables, own) * index_pool.shape[1] + layer
    ent = jnp.take(index_pool.reshape((-1,) + index_pool.shape[2:]), eidx,
                   axis=0).reshape(b, width * per, hkv, d)
    qg = q.reshape(b, hkv, rep, d)
    sc = jnp.einsum("bgrd,bfgd->bgrf", qg.astype(f32), ent,
                    preferred_element_type=f32) / float(d) ** 0.5
    # the entry this token completes is not in the pool yet: its score
    # goes in its place
    sc_new = jnp.einsum("bgrd,bgd->bgr", qg.astype(f32), fresh) \
        / float(d) ** 0.5
    put = (jnp.arange(width * per)[None, :] == f_new[:, None]) & \
        ends[:, None]
    sc = jnp.where(put[:, None, None, :], sc_new[..., None], sc)
    blocks, chosen = select_blocks(sc, t32, cfg, width)          # (B, Hkv, K)
    phys = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None, :], (b, hkv, width)), blocks, axis=2)
    lens = jnp.where(blocks == page[:, None, None], t32[:, None, None] % ps,
                     ps)
    lens = jnp.where(chosen, lens, 0)
    phys = jnp.where(lens > 0, phys, 0)
    out = sparse_paged_attention(q, k_new, v_new, pool, phys, lens, layer,
                                 page_size=ps, impl=impl, interpret=interpret)
    short = (t32 + 1 <= cfg.dense_len) & (t32 > 0)
    dense_pages = min(width, cfg.dense_len // ps)

    def with_short(_):
        full = paged_attention(q, k_new, v_new, pool, None,
                               tables[:, :dense_pages], t32, layer,
                               page_size=ps, impl=impl, interpret=interpret)
        return jnp.where(short[:, None, None], full, out)

    out = jax.lax.cond(jnp.any(short), with_short, lambda _: out, None)
    # what the row's KV heads held and what they attended: the chosen pages
    # with a position to read, or every page below t on the short path
    attended = jnp.where(short, hkv * (-(-t32 // ps)),
                         jnp.sum(lens > 0, axis=(1, 2)))
    pages = jnp.stack([jnp.where(t32 > 0, hkv * (page + 1), 0), attended],
                      axis=1).astype(jnp.int32)
    return out, jnp.where(ends[:, None, None], fresh, 0.0), \
        ends.astype(jnp.int32), pages, jnp.where(chosen, blocks, -1)


def sparse_decode_attention(q, k_new, v_new, cache: HybridDecodeCache):
    """One sparse layer's cached decode attention (Tensors; ``cache`` at
    that layer: ``cache.at_layer(i)``). Returns ``(out (B, H, D), cache')``
    with the token's K/V and the compressed key it completes pending until
    ``commit_pending`` / :func:`commit_index`."""
    from ..core.tensor import apply
    from ._helpers import ensure_tensor
    q, k_new, v_new = (ensure_tensor(x) for x in (q, k_new, v_new))
    layer_t = ensure_tensor(cache.layer).astype("int32")
    cfg, ps = cache.sparse, cache.page_size
    impl, interpret = cache.impl, cache.interpret

    def f(qa, kna, vna, pool, index_pool, tables, t, layer):
        return _decode_layer(qa, kna, vna, pool, index_pool, tables, t,
                             layer, cfg, ps, impl, interpret)

    out, fresh, ends, pages, blocks = apply(
        "sparse_attention_decode", f, q, k_new, v_new, cache.pool,
        cache.index_pool, cache.tables, cache.t, layer_t,
        differentiable=False, amp=False)
    walked = _takes_kernel(cache.pool, ps, impl, interpret)
    return out, replace(cache, pending=cache.pending + ((k_new, v_new),),
                        pending_index=cache.pending_index + ((fresh, ends),),
                        chose=cache.chose + ((pages, blocks),),
                        sparse_walk_layers=cache.sparse_walk_layers + walked)


def pages_counted(cache: HybridDecodeCache):
    """``(2,)`` int32 Tensor: over the step's rows and the sparse layers
    decoded so far, the pages held and the pages attended, each counted per
    KV head and layer — what ``serving.sparse.decode`` reports."""
    from ..core.tensor import apply
    return apply("sparse_pages_counted",
                 lambda *pages: sum(jnp.sum(p, axis=0) for p in pages),
                 *[p for p, _ in cache.chose], differentiable=False,
                 amp=False)


def commit_index(cache: HybridDecodeCache) -> HybridDecodeCache:
    """Write the compressed keys the step's tokens completed, every sparse
    layer's at once, into the entry of the page each kernel ends in — one
    row-sized update per batch row, as ``scatter_token_inplace`` makes
    them; a row whose token ends no kernel writes the scratch page."""
    from ..core.tensor import apply
    cfg, ps = cache.sparse, cache.page_size
    n = len(cache.pending_index)

    def f(index_pool, tables, t, *rest):
        fresh = jnp.stack(rest[:n], axis=1)              # (B, Ls, Hkv, D)
        ends = rest[n]
        t32 = t.astype(jnp.int32)
        pids = jnp.take_along_axis(tables.astype(jnp.int32),
                                   (t32 // ps)[:, None], axis=1)[:, 0]
        pids = jnp.where(ends > 0, pids, 0)
        hkv = fresh.shape[2]
        slot = (t32 % ps) // cfg.kernel_stride * hkv    # rows (e, h)
        rows = fresh.astype(index_pool.dtype)[:, None]  # (B, 1, Ls, Hkv, D)
        for b in range(rows.shape[0]):
            index_pool = jax.lax.dynamic_update_slice(
                index_pool, rows[b], (pids[b], 0, slot[b], 0))
        return index_pool

    index_pool = apply(
        "sparse_commit_index", f, cache.index_pool, cache.tables, cache.t,
        *[fr for fr, _ in cache.pending_index], cache.pending_index[0][1],
        differentiable=False, amp=False)
    return replace(cache, index_pool=index_pool, pending_index=())
