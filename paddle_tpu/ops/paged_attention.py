"""Paged-attention decode: a Pallas kernel that consumes the page pool +
page tables directly — the dense stacked cache never exists in the decode
program.

Why (ROADMAP 3a): the serving decode program used to reconstruct the full
dense ``(L, 2, B, H, max_len, D)`` cache inside the trace every step
(``serving/kv_cache.py::gather_pages``), so per-token attention bandwidth
scaled with ``max_len``, not with the live context. This module makes the
decode step's KV traffic O(live pages) reads + O(1) page writes:

* **Streaming kernels** (:func:`paged_attention`). The per-head kernel
  (:func:`_decode_kernel`; fewer than 8 query heads to a KV head) runs one
  program per batch row; the grid's innermost dimension walks the slot's
  page-table row, and the ``PrefetchScalarGridSpec`` index maps resolve
  each K/V block to ``pool[tables[b, s], layer, k/v]`` — Pallas
  double-buffers the page DMAs, and a repeated block index (the trailing
  scratch-page entries of a short slot) skips the re-fetch, so its HBM
  bytes follow the live page count while its grid steps follow ``rows x
  table width``. The grouped kernel (:func:`_decode_kernel_grouped`; 8
  heads to one and more) has no page axis and no page BlockSpec: the pool
  stays where it lies, the grid is the bucket's rows, and each row loops
  over its OWN live page groups with copies it starts itself — bytes AND
  steps follow the live rows' live pages (ISSUE 35). Online softmax (the
  ``ops/flash_attention.py`` pattern) runs in fp32 VMEM scratch carried
  across the pages; pages whose first position is ``>= t`` skip compute
  (``@pl.when``) or are never visited (grouped).
* **In-kernel dequant**: the int8 leg multiplies each streamed page by
  its per-(page, layer, K/V, head) absmax scale — the exact grid
  ``serving/kv_cache.py::quantize_pages`` wrote — so the quantized pool
  is never expanded outside VMEM. The bf16 leg upcasts in-register.
* **Current token exact**: the position-``t`` K/V is passed to the kernel
  unquantized and joins the softmax in fp32 — matching the dense path,
  where the step writes the fresh token into the gathered cache *before*
  attention and quantization happens only at write-back.
* **One in-place token write a step** (:func:`commit_pending` over
  :func:`scatter_token_inplace`): no layer's kernel reads another
  layer's position-``t`` write, so the layers only collect their
  ``(k_new, v_new)`` on the handle and the pool is written once, after
  the last layer, for all layers together — one row-sized update per
  batch row into the buffer the program was given (the engine donates
  it), O(1) pages per slot, no dense round-trip. The kernels read a pool
  that nothing in the program writes before them. The int8 leg
  re-quantizes the containing page of every layer under the kv_cache
  requantization contract (positions ``> t`` masked to zero; same math
  as ``scatter_token_page``, sourced from the pool instead of the dense
  cache).

* **A window bound** (ISSUE 27): a sliding-window layer's call takes
  ``window`` and the COMPACT window table — ``window // page_size + 2``
  columns, column ``j`` the logical page ``first + j`` with ``first`` the
  page of position ``t - window + 1`` — so the grid never visits a page
  below the window, and positions at or below ``t - window`` inside the
  first page are masked. ``window=None`` is the kernel as it was.
* **Many query heads to a KV head** (``rep >= 8``, ISSUE 27: 16 to 1)
  take :func:`_decode_kernel_grouped`: the heads of one KV head are the
  rows of one MXU matmul against eight pages at a time, where the per-head
  kernel makes ``rep`` VPU passes over each page (on a v5e at 32 rows x
  8000 tokens: 2.3 ms a layer against 22.8). **It walks live rows and live
  pages only** (ISSUE 35): one grid step a batch row; inside it a loop over
  the row's page groups — ``ceil((t - first * ps) / (8 * ps))`` trips,
  ``first`` the window's first page — each trip waiting for the eight
  page copies (``make_async_copy`` out of the pool in HBM, a page's K and V
  of every KV head in one copy) that the trip before started into the other
  half of a two-slot VMEM buffer; a row's last trip starts the next row's
  first group. A padding row (``t = 0``, how the engine fills a bucket)
  copies and multiplies nothing. Before, the grid was ``rows x table width
  / 8`` and every step visited 16 page operands whether the row was live
  and whether the pages lay below ``t``: with 25 of 64 rows live at ~4.5k
  of 10,752 positions five steps in six streamed nothing, 0.70 us each
  (PERF.md section 6, PR 35). The threshold is not a crossover: at 4 heads
  to 1 (16 rows, 7 layers in the page, a v5e) the grouped kernel was
  faster too — 0.37 against 0.75 ms at 300-600 tokens, 0.38 against 1.90
  at 1500-3000, int8 0.24 / 0.33 against 0.74 / 2.18 — at 4-30 times the
  rounding error (2e-3 bf16, 8e-3 int8, still under the 2e-2 held to). It
  stays at 8 so that a model with fewer heads to a KV head runs the
  program it ran before ISSUE 27; taking the per-head kernel out is a
  change to those models' numbers, to be made and measured on its own
  (ROADMAP A9).

Tiering (the flash-SDPA / step-capture contract): the kernel is the TPU
tier; off-TPU it runs under the Pallas interpreter when forced (tests)
while ``auto`` keeps CPU on the existing dense-gather debug tier, which
stays the parity reference (``ServingConfig.paged_attention``:
``auto|on|off``, :func:`decode_path`).
:func:`paged_attention_dense` is that reference restricted to one layer —
it gathers only the slot's pages for the layer being decoded, so even the
debug tier of a paged program never rebuilds the L-stacked cache.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["PagedDecodeCache", "PageKind", "window_first_page",
           "window_table_pages", "decode_path", "kernel_eligible",
           "paged_attention", "paged_attention_dense",
           "scatter_token_inplace", "paged_decode_attention",
           "commit_pending"]

_NEG_INF = -1e30  # matches ops/flash_attention.py's mask fill

def decode_path(mode: str = "auto") -> str:
    """``"kernel"`` or ``"dense"`` for a ``ServingConfig.paged_attention``
    value on the current device: ``auto`` — the kernel on a TPU, the
    dense-gather debug tier elsewhere (the same device split as flash
    SDPA); ``on`` — the kernel everywhere (Pallas interpreter off-TPU:
    slow, for parity tests); ``off`` — the dense tier everywhere."""
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"paged_attention mode must be auto|on|off, got {mode!r}")
    if mode == "auto":
        mode = "on" if jax.default_backend() == "tpu" else "off"
    return "kernel" if mode == "on" else "dense"


def kernel_interpret() -> bool:
    """Off-TPU the kernel runs under the Pallas interpreter (tests)."""
    return jax.default_backend() != "tpu"


# K and V page blocks, double-buffered, must fit the default 16 MiB
# scoped-VMEM window beside the kernel's fp32 temporaries
_VMEM_BLOCK_BUDGET = 12 * 2 ** 20

# with this many query heads to a KV head (a whole fp32 sublane tile of
# them) the heads of one KV head go through the MXU together, over this
# many pages at a time
_GROUPED_MIN_REP = 8
_GROUP_PAGES = 8


def kernel_eligible(page_size: int, head_dim: int, storage_dtype,
                    num_kv_heads: int = 1, rep: int = 1) -> bool:
    """What the compiled (non-interpret) kernel needs. Mosaic on a TPU v5e
    (libtpu 0.0.34) took every shape tried — page sizes 8, 16, 24, 32, 64
    and 128, head dims 64, 128 and 256, fp32, bf16 and int8 pages, 8 and 32
    KV heads, with and without GQA — so the stated bounds are the edge of
    what was tried: ``page_size`` in whole 8-row sublane groups and
    ``head_dim`` in whole 64-lane halves. The one hard limit is VMEM: what
    a kernel holds of the pool — a page's K and V of every KV head, in two
    buffers; ``_GROUP_PAGES`` such pages at ``rep`` query heads to a KV head
    from ``_GROUPED_MIN_REP`` up, where the kernel copies a group of pages
    at a time — must fit the scoped-VMEM budget. Anything else stays on the
    per-layer dense tier — correctness is never gated on tiling.
    ``chip_smoke.py`` re-checks the serve leg's shapes on every run."""
    block_bytes = (4 * num_kv_heads * page_size * head_dim
                   * jnp.dtype(storage_dtype).itemsize)
    if rep >= _GROUPED_MIN_REP:
        block_bytes *= _GROUP_PAGES
    return (page_size % 8 == 0 and head_dim % 64 == 0
            and block_bytes <= _VMEM_BLOCK_BUDGET)


@dataclass
class PageKind:
    """One pool of a :class:`PagedDecodeCache` that keeps pages by layer
    kind: the pool, its page table rows, its int8 scales and the window of
    its layers (``None``: full attention)."""

    pool: object
    tables: object
    scales: Optional[object] = None
    window: Optional[int] = None


def window_first_page(t, window: int, page_size: int):
    """The lowest logical page a window layer still reads at position
    ``t`` (int or int array): the page of position ``t - window + 1``."""
    return jnp.maximum(t - (window - 1), 0) // page_size


def window_table_pages(window: int, page_size: int) -> int:
    """Pages a window slot holds at most: the window's own, one more for
    the page its low edge cuts, one for the page being written."""
    return window // page_size + 2


@dataclass
class PagedDecodeCache:
    """The traced handle that threads the page pool through a decode step
    in place of the dense stacked cache.

    The serving engine builds one per compiled decode call and passes it
    as the ``step_fn``'s cache argument; models that understand it
    (``FusedMultiTransformer``, ``LlamaForCausalLM.serving_callables``)
    run their cached attention over the kernel and return the handle
    with every layer's new K/V pending; the engine commits them to the
    pool (:func:`commit_pending`). Fields are Tensors (traced inside the
    decode program):

    * ``pool``    — ``(num_pages, L, 2, H_kv, page_size, D)`` storage dtype
    * ``scales``  — ``(num_pages, L, 2, H_kv)`` fp32 (int8 leg only)
    * ``tables``  — ``(B, pages_per_slot)`` int32 page-table rows
    * ``t``       — ``(B,)`` int32 per-slot write position (the decode
      step attends positions ``<= t`` and writes K/V at ``t``)
    * ``layer``   — scalar int32 Tensor, set per layer by the model's
      layer loop/scan (:meth:`at_layer`); ``None`` on the engine-level
      handle
    * ``impl``    — ``"kernel"`` | ``"dense"`` (the per-layer debug tier)
    * ``interpret`` — run the kernel under the Pallas interpreter (CPU)
    * ``pending`` — the position-``t`` ``(k_new, v_new)`` pairs of the
      layers decoded so far, in layer order and not yet in the pool: each
      ``(B, H_kv, D)`` for one layer or ``(n, B, H_kv, D)`` for ``n``
      stacked ones (a scan over layers)
    * ``window``  — ``None``, or the sliding window of the pool's layers:
      a layer attends positions ``t - window < j <= t`` and ``tables`` is
      the COMPACT window table ``(B, window // page_size + 2)``, entry
      ``j`` mapping logical page ``first + j`` with ``first =
      max(0, t - window + 1) // page_size`` (:func:`window_first_page`) —
      pages below the window are not in the table, so they are never
      streamed
    * ``kinds``   — ``()`` for a model whose layers share one pool (the
      fields above are that pool's), else one :class:`PageKind` per pool
      (ISSUE 27: full-attention and window layers keep pages of their
      own) and ``layer_kinds[i] = (kind, layer within the kind's pool)``;
      :meth:`at_layer` then puts layer ``i``'s kind into ``pool`` /
      ``tables`` / ``scales`` / ``window``
    * ``row_walk_layers`` — of the layers decoded so far, those whose call
      took the row-walking grouped kernel (:func:`commit_pending` files the
      count as the gauge ``serving.paged_attention_row_walk_layers``)
    """

    pool: object
    tables: object
    t: object
    page_size: int
    scales: Optional[object] = None
    layer: Optional[object] = None
    impl: str = "kernel"
    interpret: bool = False
    pending: tuple = ()
    window: Optional[int] = None
    kinds: tuple = ()
    layer_kinds: tuple = ()
    row_walk_layers: int = 0

    def at_layer(self, layer) -> "PagedDecodeCache":
        if not self.kinds:
            return replace(self, layer=layer)
        k, local = self.layer_kinds[int(layer)]
        kind = self.kinds[k]
        return replace(self, layer=local, pool=kind.pool, tables=kind.tables,
                       scales=kind.scales, window=kind.window)

    @property
    def num_kv_heads(self) -> int:
        return int(self.pool.shape[3])

    @property
    def head_dim(self) -> int:
        return int(self.pool.shape[5])

    @property
    def pending_layers(self) -> int:
        return sum(1 if k.ndim == 3 else int(k.shape[0])
                   for k, _ in self.pending)


# ---------------------------------------------------------------------------
# the streaming kernel
# ---------------------------------------------------------------------------

def _decode_kernel(tables_ref, t_ref, layer_ref, q_ref, kn_ref, vn_ref,
                   k_ref, v_ref, *rest, page_size: int, num_pages: int,
                   num_kv_heads: int, rep: int, quantized: bool,
                   window: Optional[int] = None):
    """One batch row per program; grid dim 1 streams the slot's page-table
    row, and a ``fori_loop`` walks the KV heads of the streamed page. fp32
    online softmax carried in VMEM scratch across pages (TPU grids run
    sequentially, so scratch persists); the final page step folds in the
    CURRENT token's unquantized K/V at position ``t`` and writes the
    output block.

    Written for what Mosaic compiles: every per-head access indexes a
    MAJOR dimension (q/kn/vn/out carry a unit second-minor dim for that),
    every value is a 2-D ``(rows, lanes)`` tile, logits are VPU
    multiply + lane reductions (exact fp32 — a one-row MXU dot would run
    bf16 passes), and the running max/denominator live in ``(1, 1)``
    vector tiles, never scalars.

    Refs: q ``(1, H, 1, D)`` fp32, pre-scaled by ``1/sqrt(D)``; kn/vn
    ``(1, H_kv, 1, D)`` fp32; k/v ``(1, 1, 1, H_kv, ps, D)`` — the page
    the index map resolved via the prefetched table; int8 adds one
    ``(1, 1, 2, H_kv)`` scale ref (K row, V row; heads on lanes). Out
    ``(1, H, 1, D)`` fp32. Scratch: m/l ``(H, 1, 1)``, acc ``(H, 1, D)``.
    """
    rest = list(rest)
    sc_ref = rest.pop(0) if quantized else None
    o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(1)
    ps = page_size

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = t_ref[b]
    if window is None:
        page_start = s * ps
    else:
        page_start = (window_first_page(t, window, ps) + s) * ps

    @pl.when(page_start < t)                 # live page: stream it
    def _stream():
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
        live = pos < t
        if window is not None:
            live = jnp.logical_and(live, pos > t - window)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_kv_heads), 1)

        def head(h, carry):
            k_blk = k_ref[0, 0, 0, h].astype(jnp.float32)     # (ps, D)
            v_blk = v_ref[0, 0, 0, h].astype(jnp.float32)
            if quantized:
                # head h's absmax scales sit on lane h: select + lane-sum
                # lifts them into (1, 1) tiles. Applied to the logits and
                # the weighted-V row, not to the page (same product, a
                # D-th of the multiplies).
                pick = lane == h
                k_sc = jnp.sum(jnp.where(pick, sc_ref[0, 0, 0:1, :], 0.0),
                               axis=1, keepdims=True)
                v_sc = jnp.sum(jnp.where(pick, sc_ref[0, 0, 1:2, :], 0.0),
                               axis=1, keepdims=True)
            for r in range(rep):             # GQA: q heads sharing head h
                hq = h * rep + r
                logits = jnp.sum(k_blk * q_ref[0, hq], axis=1,
                                 keepdims=True)               # (ps, 1)
                if quantized:
                    logits = logits * k_sc
                logits = jnp.where(live, logits, _NEG_INF)
                m_prev = m_ref[hq]                            # (1, 1)
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, axis=0, keepdims=True))
                p = jnp.exp(logits - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[hq] = alpha * l_ref[hq] + jnp.sum(
                    p, axis=0, keepdims=True)
                pv = jnp.sum(p * v_blk, axis=0, keepdims=True)  # (1, D)
                if quantized:
                    pv = pv * v_sc
                acc_ref[hq] = alpha * acc_ref[hq] + pv
                m_ref[hq] = m_new
            return carry

        jax.lax.fori_loop(0, num_kv_heads, head, 0)

    @pl.when(s == num_pages - 1)             # fold in position t, emit
    def _finish():
        def head(h, carry):
            kn = kn_ref[0, h]                                 # (1, D)
            vn = vn_ref[0, h]
            for r in range(rep):
                hq = h * rep + r
                logit_t = jnp.sum(q_ref[0, hq] * kn, axis=1, keepdims=True)
                m_prev = m_ref[hq]
                m_new = jnp.maximum(m_prev, logit_t)
                p_t = jnp.exp(logit_t - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_fin = alpha * l_ref[hq] + p_t
                acc = alpha * acc_ref[hq] + p_t * vn
                o_ref[0, hq] = acc / jnp.maximum(l_fin, 1e-30)
            return carry

        jax.lax.fori_loop(0, num_kv_heads, head, 0)


def _decode_kernel_grouped(tables_ref, t_ref, layer_ref, q_ref, kn_ref,
                           vn_ref, pool_ref, *rest, page_size: int,
                           num_groups: int, num_kv_heads: int,
                           quantized: bool, group: int, sm_scale: float,
                           exact: bool, window: Optional[int] = None):
    """:func:`_decode_kernel` for many query heads to a KV head (ISSUE 27:
    16 to 1): the ``rep`` query heads that share KV head ``h`` are the rows
    of ONE ``(rep, D) x (D, group * ps)`` matmul against ``group`` pages,
    and of one ``(rep, group * ps) x (group * ps, D)`` for the weighted V,
    in place of ``rep`` VPU multiply-and-reduce passes over each page. Same
    online softmax, same fold of position ``t``.

    The walk (ISSUE 35): one grid step a batch row, the pool left in HBM,
    and a loop INSIDE the kernel over the row's own page groups —
    ``ceil((t - first * ps) / (group * ps))`` of them, ``first`` the
    window's first page (0 for a full layer) — so a padding row (``t = 0``)
    copies and multiplies nothing and a live row stops at its own ``t``,
    not at the table's width. A trip waits for its group's page copies
    (one a page: a page's K and V of every KV head lie side by side in the
    pool) in one half of a two-slot VMEM buffer, having started the next
    group's into the other half; a row's last trip — or a row without
    trips — starts the NEXT row's first group, so of a whole call only the
    first copy is waited for with nothing to do. Every row, a padding row
    too, then folds in position ``t`` and writes its output.

    Precision: a bf16 or int8 pool's values, and a bf16 model's queries,
    are exact in bf16, so ``q . k`` takes ONE bf16 pass of the MXU and is
    exact (``q`` comes unscaled, int8 scales and ``sm_scale`` multiply
    the logits); ``p . v`` rounds the probabilities to bf16 as flash
    kernels do. ``exact`` (a float32 pool) runs both at the highest
    precision instead.

    Refs: q/out ``(1, H_kv, rep, D)`` fp32 (q NOT pre-scaled); kn/vn
    ``(1, H_kv, 1, D)``; the pool ``(P, L, 2, H_kv, ps, D)`` whole, where
    it lies; (int8) the scales of the row's table, ``(1, S, 2, H_kv)``.
    Scratch: the page buffer ``(2, group, 2, H_kv, ps, D)`` with a DMA
    semaphore a slot, the slot this row's first group is in (SMEM: it
    outlives the grid step), m/l ``(H_kv, rep, 1)``, acc
    ``(H_kv, rep, D)``."""
    rest = list(rest)
    sc_ref = rest.pop(0) if quantized else None
    o_ref, buf, sem, slot_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    ps = page_size
    f32 = jnp.float32
    precision = jax.lax.Precision.HIGHEST if exact \
        else jax.lax.Precision.DEFAULT
    layer = layer_ref[0]

    def walk(row):
        """Row ``row``'s position, first page and count of page groups."""
        t = t_ref[row]
        first = 0 if window is None else window_first_page(t, window, ps)
        return t, first, jnp.minimum(pl.cdiv(t - first * ps, group * ps),
                                     num_groups)

    def copies(row, g, slot):
        """Group ``g`` of row ``row`` into ``slot``: a copy a page."""
        done = sem.at[slot]
        return [pltpu.make_async_copy(
            pool_ref.at[tables_ref[row, g * group + j], layer],
            buf.at[slot, j], done) for j in range(group)]

    def start(row, g, slot):
        for c in copies(row, g, slot):
            c.start()

    t, first, n = walk(b)

    @pl.when(b == 0)                         # nobody started row 0's
    def _first_row():
        slot_ref[0] = 0

        @pl.when(n > 0)
        def _():
            start(0, 0, 0)

    slot0 = slot_ref[0]                      # where this row's group 0 is
    slot_ref[0] = (slot0 + n) % 2            # ... and the next row's

    def start_next_row():
        @pl.when(b + 1 < pl.num_programs(0))
        def _():
            @pl.when(walk(b + 1)[2] > 0)
            def _():
                start(b + 1, 0, (slot0 + n) % 2)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n == 0)
    def _no_trip():
        start_next_row()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_kv_heads), 1)

    def trip(g, carry):
        slot = (slot0 + g) % 2

        @pl.when(g + 1 < n)
        def _():
            start(b, g + 1, 1 - slot)

        @pl.when(g + 1 == n)
        def _():
            start_next_row()

        for c in copies(b, g, slot):
            c.wait()
        begin = (first + g * group) * ps     # of this group's first page
        pos = begin + jax.lax.broadcasted_iota(jnp.int32, (1, group * ps), 1)
        live = pos < t
        if window is not None:
            live = jnp.logical_and(live, pos > t - window)

        def tile(kv, h):
            """Head h's rows of the group's pages, (group * ps, D) fp32:
            one load across the pages (every access to a ref costs the
            trace as much as an operation: PERF.md section 6, PR 35)."""
            return buf[slot, :, kv, h].astype(f32).reshape(group * ps, -1)

        def scale_row(h, row):
            """Head h's absmax scales of the group's pages, one a page,
            along the logits' columns: (1, group * ps)."""
            parts = [jnp.broadcast_to(jnp.sum(
                jnp.where(lane == h,
                          sc_ref[0, g * group + j, row:row + 1, :], 0.0),
                axis=1, keepdims=True), (1, ps)) for j in range(group)]
            return jnp.concatenate(parts, axis=1)

        for h in range(num_kv_heads):        # unrolled: the heads overlap
            logits = jax.lax.dot_general(
                q_ref[0, h], tile(0, h), (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=f32) * sm_scale        # (rep, G*ps)
            if quantized:
                logits = logits * scale_row(h, 0)
            logits = jnp.where(live, logits, _NEG_INF)
            m_prev = m_ref[h]                                 # (rep, 1)
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * scale_row(h, 1)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p, tile(1, h), precision=precision,
                preferred_element_type=f32)                   # (rep, D)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n, trip, 0)

    # fold in position t and emit, every KV head at once
    logit_t = jnp.sum(q_ref[0] * kn_ref[0], axis=2,
                      keepdims=True) * sm_scale               # (H_kv, rep, 1)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logit_t)
    p_t = jnp.exp(logit_t - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_fin = alpha * l_ref[...] + p_t
    acc = alpha * acc_ref[...] + p_t * vn_ref[0]
    o_ref[0] = acc / jnp.maximum(l_fin, 1e-30)


def _grouped_call(q, k_new, v_new, pool, scales, tables, t, layer,
                  page_size: int, interpret: bool, window: Optional[int]):
    """:func:`_kernel_call` at ``rep >= _GROUPED_MIN_REP``: one grid step a
    batch row, the pool handed over where it lies."""
    b, h, d = q.shape
    h_kv = pool.shape[3]
    ps, group = page_size, _GROUP_PAGES
    tables = tables.astype(jnp.int32)
    if tables.shape[1] % group:              # whole groups: pad with the
        tables = jnp.pad(                    # scratch page
            tables, ((0, 0), (0, -tables.shape[1] % group)))
    s = tables.shape[1]
    quantized = scales is not None
    qo = (h_kv, h // h_kv, d)                # a KV head's query heads: rows
    f32 = jnp.float32
    layer = jnp.asarray(layer, jnp.int32)
    kern = functools.partial(
        _decode_kernel_grouped, page_size=ps, num_groups=s // group,
        num_kv_heads=h_kv, quantized=quantized, group=group,
        sm_scale=1.0 / float(d) ** 0.5, exact=pool.dtype == jnp.float32,
        window=window)

    def row_map(bi, tabs, tt, lr):
        return (bi, 0, 0, 0)

    in_specs = [pl.BlockSpec((1,) + qo, row_map),
                pl.BlockSpec((1, h_kv, 1, d), row_map),
                pl.BlockSpec((1, h_kv, 1, d), row_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    inputs = [q.astype(f32).reshape((b,) + qo),
              k_new.astype(f32).reshape(b, h_kv, 1, d),
              v_new.astype(f32).reshape(b, h_kv, 1, d), pool]
    if quantized:
        # the scales of a row's table, gathered here: Mosaic takes no copy
        # of one page's (2, H_kv) out of the scales where they lie (a slice
        # of the minor dimension, under its 128-lane tile)
        n_p, n_l = scales.shape[:2]
        in_specs.append(pl.BlockSpec((1, s, 2, h_kv), row_map))
        inputs.append(jnp.take(scales.reshape(n_p * n_l, 2, h_kv),
                               tables * n_l + layer, axis=0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + qo, row_map),
        scratch_shapes=[
            pltpu.VMEM((2, group, 2, h_kv, ps, d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),     # the slot of the row's group 0
            pltpu.VMEM(qo[:2] + (1,), f32),  # running max
            pltpu.VMEM(qo[:2] + (1,), f32),  # running denominator
            pltpu.VMEM(qo, f32),             # weighted-V accumulator
        ])
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b,) + qo, f32),
        # a row starts the next row's first copy: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_decode",
    )(tables, t.astype(jnp.int32), layer.reshape(1), *inputs)
    return out.reshape(b, h, d).astype(q.dtype)


# jitted so that a decode program traces a kernel's body once for all its
# layers of one shape and window, not once a layer: on a TPU host a kernel's
# trace is seconds of every run's set-up, cached or not (PERF.md section 6,
# PR 35), and the layer is an operand, not part of the trace
@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret", "window"))
def _kernel_call(q, k_new, v_new, pool, scales, tables, t, layer,
                 page_size: int, interpret: bool,
                 window: Optional[int] = None):
    """q ``(B, H, D)``, k/v_new ``(B, H_kv, D)``, pool
    ``(P, L, 2, H_kv, ps, D)`` → out ``(B, H, D)`` in q.dtype. GQA via
    ``rep = H // H_kv`` inside the head loop (no repeat buffer); the
    page/layer/K-or-V selection stays in the index maps."""
    b, h, d = q.shape
    h_kv = pool.shape[3]
    rep = h // h_kv
    if rep >= _GROUPED_MIN_REP:
        return _grouped_call(q, k_new, v_new, pool, scales, tables, t, layer,
                             page_size, interpret, window)
    s = tables.shape[1]
    ps = page_size
    quantized = scales is not None
    kern = functools.partial(
        _decode_kernel, page_size=ps, num_pages=s, num_kv_heads=h_kv,
        rep=rep, quantized=quantized, window=window)

    def row_map(bi, si, tabs, tt, lr):
        return (bi, 0, 0, 0)

    def page_map(kv):
        def f(bi, si, tabs, tt, lr):
            return (tabs[bi, si], lr[0], kv, 0, 0, 0)
        return f

    def scale_map(bi, si, tabs, tt, lr):
        return (tabs[bi, si], lr[0], 0, 0)

    # q and out: one block per batch row, the per-head access on a MAJOR
    # dimension — (H, 1, D) a head. Every block's trailing two dims are the
    # array's own, which is what the Pallas TPU lowering accepts below the
    # (8, 128) tile
    in_specs = [
        pl.BlockSpec((1, h, 1, d), row_map),
        pl.BlockSpec((1, h_kv, 1, d), row_map),
        pl.BlockSpec((1, h_kv, 1, d), row_map),
        pl.BlockSpec((1, 1, 1, h_kv, ps, d), page_map(0)),
        pl.BlockSpec((1, 1, 1, h_kv, ps, d), page_map(1)),
    ]
    f32 = jnp.float32
    inputs = [(q.astype(f32) * (1.0 / float(d) ** 0.5)).reshape(b, h, 1, d),
              k_new.astype(f32).reshape(b, h_kv, 1, d),
              v_new.astype(f32).reshape(b, h_kv, 1, d), pool, pool]
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, 2, h_kv), scale_map))
        inputs.append(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, 1, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1, 1), f32),      # running max
            pltpu.VMEM((h, 1, 1), f32),      # running denominator
            pltpu.VMEM((h, 1, d), f32),      # weighted-V accumulator
        ],
    )
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_decode",
    )(tables.astype(jnp.int32), t.astype(jnp.int32), layer_arr, *inputs)
    return out.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# the per-layer dense tier (debug / parity reference / ineligible shapes)
# ---------------------------------------------------------------------------

def paged_attention_dense(q, k_new, v_new, pool, scales, tables, t, layer,
                          page_size: int, window: Optional[int] = None):
    """Reference math for one layer: gather the slot's pages FOR THE
    DECODED LAYER ONLY (a flat ``(page, layer)`` take — the L-stacked
    dense cache still never exists), insert the current token, span-mask
    to ``<= t`` (and above ``t - window``; ``tables`` is then the compact
    window table), softmax. The kernel is pinned against this."""
    p_, l_, _, h_kv, ps, d = pool.shape
    b, s = tables.shape
    m = s * ps
    rep = q.shape[1] // h_kv
    idx = tables.astype(jnp.int32) * l_ + jnp.asarray(layer, jnp.int32)
    taken = jnp.take(pool.reshape(p_ * l_, 2, h_kv, ps, d), idx, axis=0)
    taken = taken.astype(jnp.float32)
    if scales is not None:
        sc = jnp.take(scales.reshape(p_ * l_, 2, h_kv), idx, axis=0)
        taken = taken * sc[..., None, None]
    # (B, S, 2, H_kv, ps, D) -> k/v (B, H_kv, M, D)
    k = taken[:, :, 0].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, m, d)
    v = taken[:, :, 1].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, m, d)
    t32 = t.astype(jnp.int32)
    # column c of the gathered rows is position base + c
    base = 0 if window is None else \
        (window_first_page(t32, window, ps) * ps)[:, None]
    pos = base + jnp.arange(m, dtype=jnp.int32)[None, :]         # (B, M)
    onehot = (pos == t32[:, None])[:, None, :, None]
    k = jnp.where(onehot, k_new.astype(jnp.float32)[:, :, None, :], k)
    v = jnp.where(onehot, v_new.astype(jnp.float32)[:, :, None, :], v)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bhd,bhld->bhl", qf, k) / float(d) ** 0.5
    span = pos <= t32[:, None]
    if window is not None:
        span = jnp.logical_and(span, pos > t32[:, None] - window)
    logits = jnp.where(span[:, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhl,bhld->bhd", p, v).astype(q.dtype)


def paged_attention(q, k_new, v_new, pool, scales, tables, t, layer, *,
                    page_size: int, impl: str = "kernel",
                    interpret: bool = False, window: Optional[int] = None):
    """Decode attention for one layer over the page pool. Dispatches the
    streaming kernel or the per-layer dense tier; the compiled TPU kernel
    additionally requires :func:`kernel_eligible` tiling (interpret mode
    has no tiling constraints)."""
    if _kernel_for(q.shape[1], pool, page_size, impl, interpret):
        return _kernel_call(q, k_new, v_new, pool, scales, tables, t,
                            layer, page_size, interpret, window)
    return paged_attention_dense(q, k_new, v_new, pool, scales, tables, t,
                                 layer, page_size, window)


def _kernel_for(heads: int, pool, page_size: int, impl: str,
                interpret: bool) -> Optional[str]:
    """Which kernel a layer's call with ``heads`` query heads takes:
    ``"rows"`` (the grouped kernel's walk of live rows and live pages),
    ``"heads"`` (:func:`_decode_kernel`) or None (the dense tier)."""
    h_kv, d = int(pool.shape[3]), int(pool.shape[-1])
    rep = heads // h_kv
    if impl != "kernel" or not (interpret or kernel_eligible(
            page_size, d, pool.dtype, h_kv, rep)):
        return None
    return "rows" if rep >= _GROUPED_MIN_REP else "heads"


# ---------------------------------------------------------------------------
# the in-place token write
# ---------------------------------------------------------------------------

def scatter_token_inplace(pool, scales, tables, t, k_new, v_new,
                          page_size: int, window: Optional[int] = None):
    """Write position ``t``'s K/V of EVERY layer into the containing pool
    page — no dense round-trip. ``k_new``/``v_new`` are ``(L, B, H_kv, D)``.
    Returns ``(pool', scales')``.

    One ``dynamic_update_slice`` per batch row, all layers in it (the
    layer dimension is whole in the update): XLA writes each in place in
    the pool's own layout. A scatter, per layer or one for all, is not
    used: on the TPU it wants the pool in a layout of its own (page rows
    above the heads), which the Pallas kernel cannot read, so the compiler
    copied the whole pool into that layout and back around every one.

    bf16/native: a single row per (layer, K/V, head). int8: the kv_cache
    requantization contract — the containing pages are gathered,
    dequantized under their old scales, the token inserted, positions
    ``> t`` zeroed, and the pages re-quantized — the exact math of
    ``scatter_token_page``, sourced from the pool. Rows that share a page
    (padded rows, all on the scratch page) are written in row order.
    With a ``window``, ``tables`` is the compact window table."""
    ps = page_size
    t32 = t.astype(jnp.int32)
    col = t32 // ps
    if window is not None:
        col = col - window_first_page(t32, window, ps)
    pids = jnp.take_along_axis(tables.astype(jnp.int32),
                               col[:, None], axis=1)[:, 0]          # (B,)
    off = t32 % ps
    # (B, L, 2, H_kv, D): a row's update is one block of the pool
    kv_new = jnp.swapaxes(jnp.stack([k_new, v_new], axis=2), 0, 1)
    if scales is None:
        rows = kv_new.astype(pool.dtype)[:, None, :, :, :, None, :]
        for b in range(rows.shape[0]):
            pool = jax.lax.dynamic_update_slice(
                pool, rows[b], (pids[b], 0, 0, 0, off[b], 0))
        return pool, None
    from ..serving.kv_cache import quantize_pages
    page = jnp.take(pool, pids, axis=0).astype(jnp.float32)  # (B,L,2,H,ps,D)
    old_sc = jnp.take(scales, pids, axis=0)                  # (B, L, 2, H)
    page = page * old_sc[..., None, None]
    sel = jax.nn.one_hot(off, ps, dtype=jnp.bool_)[:, None, None, None, :,
                                                   None]
    page = jnp.where(sel, kv_new.astype(jnp.float32)[..., None, :], page)
    pos = (t32 // ps * ps)[:, None] + jnp.arange(ps, dtype=jnp.int32)[None]
    valid = pos <= t32[:, None]                              # (B, ps)
    page = jnp.where(valid[:, None, None, None, :, None], page, 0.0)
    q8, sc = quantize_pages(page)                # (B,L,2,H,ps,D)/(B,L,2,H)
    q8 = q8.astype(pool.dtype)
    for b in range(q8.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, q8[b][None], (pids[b], 0, 0, 0, 0, 0))
        scales = jax.lax.dynamic_update_slice(
            scales, sc[b][None], (pids[b], 0, 0, 0))
    return pool, scales


# ---------------------------------------------------------------------------
# Tensor-level surface (the op models call)
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_new, v_new, cache: PagedDecodeCache):
    """One layer's cached decode attention over the paged pool.

    ``q`` ``(B, H, D)``, ``k_new``/``v_new`` ``(B, H_kv, D)`` Tensors (the
    CURRENT token's projections, attended unquantized at position ``t``);
    ``cache`` must carry a ``layer``, and the layers of a step come in
    order. Returns ``(out (B, H, D) Tensor, cache')``: the pool is read,
    not written — ``cache'`` holds this layer's ``(k_new, v_new)`` pending
    until :func:`commit_pending` writes every layer's at once."""
    from ..core.tensor import apply
    from ._helpers import ensure_tensor
    if cache.layer is None:
        raise ValueError("paged_decode_attention: cache.layer is unset — "
                         "derive a per-layer view with cache.at_layer(i)")
    if isinstance(cache.layer, int) and not cache.kinds \
            and cache.layer != cache.pending_layers:
        raise ValueError(
            f"paged_decode_attention: layer {cache.layer} after "
            f"{cache.pending_layers} pending — a step decodes every layer "
            "once, in order")
    q, k_new, v_new = (ensure_tensor(x) for x in (q, k_new, v_new))
    layer_t = ensure_tensor(cache.layer).astype("int32")
    quantized = cache.scales is not None
    ps, impl, interpret = cache.page_size, cache.impl, cache.interpret
    window = cache.window

    def f(qa, kna, vna, pool, tables, t, layer, *maybe_scales):
        sc = maybe_scales[0] if quantized else None
        return paged_attention(qa, kna, vna, pool, sc, tables, t, layer,
                               page_size=ps, impl=impl, interpret=interpret,
                               window=window)

    args = [q, k_new, v_new, cache.pool, cache.tables, cache.t,
            layer_t] + ([cache.scales] if quantized else [])
    out = apply("paged_attention_decode", f, *args, differentiable=False,
                amp=False)
    walked = _kernel_for(int(q.shape[1]), cache.pool, ps, impl,
                         interpret) == "rows"
    return out, replace(cache, pending=cache.pending + ((k_new, v_new),),
                        row_walk_layers=cache.row_walk_layers + walked)


def commit_pending(cache: PagedDecodeCache) -> PagedDecodeCache:
    """The decode step's one pool write: position ``t``'s K/V of every
    layer, collected on the handle by :func:`paged_decode_attention`, into
    the containing pages (:func:`scatter_token_inplace`) — one write per
    pool, so a handle with pages by layer kind makes one per kind. Called
    where the handle's owner takes the pool back (the serving engine,
    after ``step_fn``), so no model has to remember it."""
    from .. import observability as _obs
    from ..core.tensor import apply
    # set as the decode program is traced: which kernel its layers took
    _obs.set_gauge("serving.paged_attention_row_walk_layers",
                   cache.row_walk_layers)
    # ... and its sparse layers (a sparse_attention.HybridDecodeCache's count)
    _obs.set_gauge("serving.sparse_attention_row_walk_layers",
                   getattr(cache, "sparse_walk_layers", 0))
    n = cache.pending_layers
    total = sum(int(k.pool.shape[1]) for k in cache.kinds) \
        if cache.kinds else int(cache.pool.shape[1])
    if n != total:
        raise ValueError(
            f"commit_pending: {n} layers pending for pools of "
            f"{total} — a step decodes every layer once")
    ps = cache.page_size

    def write(pool, tables, scales, window, pending):
        quantized = scales is not None
        pairs = len(pending)

        def f(pool, tables, t, *rest):
            stacked = [a if a.ndim == 4 else a[None]
                       for a in rest[:2 * pairs]]
            ks, vs = stacked[0::2], stacked[1::2]
            sc = rest[2 * pairs] if quantized else None
            pool2, sc2 = scatter_token_inplace(
                pool, sc, tables, t, jnp.concatenate(ks),
                jnp.concatenate(vs), page_size=ps, window=window)
            return (pool2, sc2) if quantized else pool2

        args = [pool, tables, cache.t] + [x for pair in pending for x in pair] \
            + ([scales] if quantized else [])
        outs = apply("paged_commit_tokens", f, *args, differentiable=False,
                     amp=False)
        return outs if quantized else (outs, None)

    if not cache.kinds:
        pool2, sc2 = write(cache.pool, cache.tables, cache.scales,
                           cache.window, cache.pending)
        return replace(cache, pool=pool2, scales=sc2, pending=())
    if any(k.ndim != 3 for k, _ in cache.pending):
        raise ValueError("commit_pending: pages by layer kind take one "
                         "pending pair per layer, not stacked ones")
    kinds = []
    for ki, kind in enumerate(cache.kinds):
        mine = tuple(pair for pair, (k, _) in
                     zip(cache.pending, cache.layer_kinds) if k == ki)
        pool2, sc2 = write(kind.pool, kind.tables, kind.scales, kind.window,
                           mine)
        kinds.append(replace(kind, pool=pool2, scales=sc2))
    return replace(cache, kinds=tuple(kinds), pending=())
