"""Slot-paged KV cache for the serving engine: a fixed pool of pages plus
per-slot page tables, with an optional int8 leg.

Layout (the vLLM/PagedAttention shape adapted to the stacked-cache decode
path this repo already compiles — ``FusedMultiTransformer._scan_decode``
consumes a dense ``(L, 2, B, H, max_len, D)`` cache):

* ``pool``   — ``(num_pages, L, 2, H, page_size, D)``. One page holds
  ``page_size`` consecutive token positions of ONE sequence across ALL
  layers (K and V). Page 0 is a reserved scratch page: padded batch rows
  and unallocated page-table entries point at it, so gathers and
  scatters never need a validity branch.
* ``scales`` — ``(num_pages, L, 2, H)`` fp32, int8 leg only. Symmetric
  per-(page, layer, k/v, head) absmax scales following the q8 layout rule
  (``optimizer._q8_quantize`` / ``ops/q8_adam_pallas.py``):
  ``scale = absmax / 127``, zero absmax quantized with scale 1.
* page table — ``(B, pages_per_slot)`` int32 per batch, row ``b`` maps
  slot ``b``'s logical positions ``[i*page_size, (i+1)*page_size)`` to a
  pool page; unused entries are 0 (scratch).

The decode program gathers a slot's pages into the dense stacked layout
(dequantizing on the int8 leg), runs the EXISTING compiled decode step
unchanged, then writes back only the page containing the one position the
step touched. Both halves are pure jnp functions traced into the same
program as the decode itself — paging costs no extra dispatches.

int8 requantization contract: writing position ``t`` re-quantizes the
whole containing page (positions ``> t`` are masked to zero first, so a
freshly allocated page never inherits stale pool bytes). While a page is
filling, its scale can only grow; entries quantized under an earlier,
smaller scale are re-gridded at most ``page_size`` times, each bounded by
half a quantization step — the dense-vs-int8 logits-tolerance test in
``tests/test_serving.py`` pins the accumulated effect.

Host-side accounting (:class:`PagedKVCache`) is a refcounted free list
over page ids with page 0 reserved, plus a prompt-prefix hash index
(ISSUE 17): pages holding fully-prompt content are published under a
page-aligned chain hash, a later admission whose prompt walks the same
chain maps those pages read-only into its table (``acquire_prefix``
bumps refcounts), and ``free()`` decrements instead of releasing a page
other slots still reference. Copy-on-write holds by construction: the
decode step's in-place token write targets the page containing position
``t >= prompt_len``, which is never a published (fully-prompt) page, so
shared pages are only ever read. Published pages whose refcount drops to
zero are retained on an idle LRU (still indexed, still reclaimable by
``alloc`` under pressure) so a later identical prompt reuses them even
with no concurrent sharer. Admission policy (whether a request may claim
pages at all) lives in ``serving.scheduler``.

Pages by layer kind (ISSUE 27): an engine whose model mixes full-attention
and sliding-window layers holds one :class:`PagedKVCache` per kind
(``KVCacheConfig.kind`` / ``window``; ``num_layers`` counts that kind's
layers). A window pool's slot keeps no page below its window, so its page
ids start at a logical page of their own: ``table_row(ids, first=...)``,
``publish(tokens, ids, first=...)`` and ``acquire_prefix(tokens,
first=..., count=...)`` say which.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..ops.paged_attention import PagedDecodeCache  # noqa: F401  (re-export:
# the paged-attention decode tier threads the pool through the step as this
# handle instead of gathering the dense cache — see ops/paged_attention.py)

__all__ = ["KVCacheConfig", "PagedKVCache", "PagedDecodeCache",
           "gather_pages", "scatter_token_page", "scatter_prefill_pages",
           "quantize_pages", "prefix_chain_digests", "StatePool",
           "IndexPool", "SnapshotStore", "StatePart"]

_Q8_MAX = 127.0  # symmetric absmax grid, same rule as the q8 optimizer state


@dataclass
class KVCacheConfig:
    """Shape + dtype contract shared by the host pool and the traced ops."""

    num_layers: int
    num_heads: int
    head_dim: int
    max_len: int
    page_size: int = 64
    num_pages: Optional[int] = None   # default set by PagedKVCache
    compute_dtype: str = "float32"    # dtype the decode step consumes
    kv_dtype: str = "native"          # "native" | "bf16" | "int8"
    min_shared_pages: int = 1         # shortest prefix chain worth sharing
    # pages by layer kind (ISSUE 27): an engine whose model mixes
    # full-attention and sliding-window layers keeps one PagedKVCache per
    # kind. ``kind`` labels this pool's gauges ("" = the only pool);
    # ``window`` is the sliding window of its layers — such a slot holds
    # at most ``window_pages`` pages at once, whatever its length
    kind: str = ""
    window: Optional[int] = None

    def __post_init__(self):
        if self.max_len % self.page_size != 0:
            raise ValueError(
                f"max_len ({self.max_len}) must be a multiple of page_size "
                f"({self.page_size})")
        if self.min_shared_pages < 1:
            raise ValueError("min_shared_pages must be >= 1")

    @property
    def pages_per_slot(self) -> int:
        return self.max_len // self.page_size

    @property
    def window_pages(self) -> Optional[int]:
        """Most pages one slot holds at once in a window pool (``None``
        for full attention: a slot holds its whole length)."""
        if self.window is None:
            return None
        from ..ops.paged_attention import window_table_pages
        return min(self.pages_per_slot,
                   window_table_pages(self.window, self.page_size))

    def window_first_page(self, t: int) -> int:
        """Lowest logical page a window layer reads at position ``t``
        (0 for full attention)."""
        if self.window is None:
            return 0
        return max(0, t - (self.window - 1)) // self.page_size

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def storage_dtype(self):
        if self.kv_dtype == "int8":
            return jnp.int8
        if self.kv_dtype == "bf16":
            return jnp.bfloat16
        return jnp.dtype(self.compute_dtype)

    def page_shape(self) -> Tuple[int, ...]:
        return (self.num_layers, 2, self.num_heads, self.page_size,
                self.head_dim)


# ---------------------------------------------------------------------------
# pure jnp halves — traced into the decode/prefill programs
# ---------------------------------------------------------------------------

def quantize_pages(pages: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """absmax-int8 quantize ``(..., L, 2, H, ps, D)`` pages → (int8 pages,
    fp32 scales over ``(..., L, 2, H)``). Same grid rule as the q8
    optimizer layout: ``scale = absmax/127``, zero absmax → scale 1."""
    x = pages.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=(-2, -1))
    scale = absmax / _Q8_MAX
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale[..., None, None]), -_Q8_MAX, _Q8_MAX)
    return q.astype(jnp.int8), scale


def gather_pages(pool: jnp.ndarray, scales: Optional[jnp.ndarray],
                 tables: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """Pages → dense stacked cache ``(L, 2, B, H, max_len, D)``.

    ``tables`` is ``(B, pages_per_slot)`` int32. Rows gathered through
    scratch entries carry garbage at positions the attention span mask
    (``masked_multihead_attention``: span ``<= t``) never admits.

    Casts are conditional: the int8 leg dequantizes the gathered rows
    directly into ``compute_dtype`` (one multiply, no fp32 detour when
    compute is bf16), and the storage legs convert only when storage
    dtype differs from compute dtype — on the bf16/bf16 and native legs
    the gather emits the storage bytes untouched."""
    compute_dtype = jnp.dtype(compute_dtype)
    taken = jnp.take(pool, tables, axis=0)          # (B, S, L, 2, H, ps, D)
    if scales is not None:
        sc = jnp.take(scales, tables, axis=0)       # (B, S, L, 2, H)
        taken = taken.astype(compute_dtype) * \
            sc[..., None, None].astype(compute_dtype)
    b, s, l, two, h, ps, d = taken.shape
    dense = taken.transpose(2, 3, 0, 4, 1, 5, 6)    # (L, 2, B, H, S, ps, D)
    dense = dense.reshape(l, two, b, h, s * ps, d)
    if dense.dtype != compute_dtype:
        dense = dense.astype(compute_dtype)
    return dense


def scatter_token_page(dense: jnp.ndarray, pool: jnp.ndarray,
                       scales: Optional[jnp.ndarray], tables: jnp.ndarray,
                       t: jnp.ndarray, page_size: int):
    """Write back the one page per slot containing position ``t``.

    ``dense`` is the post-step stacked cache (the decode wrote K/V for the
    current token at per-slot position ``t``); everything outside the
    containing page is unchanged by a single decode step, so only that
    page returns to the pool. Positions ``> t`` inside the page are masked
    to zero: a fresh page never inherits stale pool bytes, and the int8
    scale is computed over written positions only. Returns
    ``(pool', scales')``."""
    ps = page_size
    l, two, b, h, m, d = dense.shape
    t = t.astype(jnp.int32).reshape(-1)

    def grab(dense_b, tb):                          # dense_b (L, 2, H, M, D)
        start = (tb // ps) * ps
        page = jax.lax.dynamic_slice(
            dense_b, (0, 0, 0, start, 0), (l, two, h, ps, d))
        valid = (start + jnp.arange(ps, dtype=jnp.int32)) <= tb
        return jnp.where(valid[None, None, None, :, None], page, 0)

    pages = jax.vmap(grab, in_axes=(2, 0), out_axes=0)(dense, t)
    pids = jnp.take_along_axis(tables, (t // ps)[:, None], axis=1)[:, 0]
    if scales is not None:
        q, s = quantize_pages(pages)
        return pool.at[pids].set(q), scales.at[pids].set(s)
    return pool.at[pids].set(pages.astype(pool.dtype)), None


def scatter_prefill_pages(dense: jnp.ndarray, pool: jnp.ndarray,
                          scales: Optional[jnp.ndarray],
                          page_ids: jnp.ndarray, true_len: jnp.ndarray,
                          page_size: int, start: int = 0):
    """Store a freshly prefilled single-slot dense cache into the pool.

    ``dense`` is ``(L, 2, 1, H, Lp, D)`` with positions ``[start,
    true_len)`` holding freshly computed K/V (right padding beyond
    ``true_len`` is masked to zero — padded prompt positions never reach
    the pool). ``start`` is a static, page-aligned offset: only pages
    covering positions ``>= start`` are written, so a prefix-shared
    admission scatters ONLY its unshared tail and the shared pages it
    mapped read-only are never touched (ISSUE 17). ``page_ids`` is
    ``((Lp - start) // page_size,)`` — the tail pages only; entries past
    the prompt's last page are 0 and harmlessly overwrite the scratch
    page. Returns ``(pool', scales')``."""
    ps = page_size
    if start % ps != 0:
        raise ValueError(f"start ({start}) must be page-aligned ({ps})")
    l, two, _, h, lp, d = dense.shape
    n = (lp - start) // ps
    x = dense[:, :, 0, :, start:, :]                 # (L, 2, H, Lp-start, D)
    x = x.reshape(l, two, h, n, ps, d).transpose(3, 0, 1, 2, 4, 5)
    pos = start + jnp.arange(lp - start, dtype=jnp.int32).reshape(n, ps)
    valid = pos < true_len.astype(jnp.int32).reshape(())
    x = jnp.where(valid[:, None, None, None, :, None], x, 0)
    if scales is not None:
        q, s = quantize_pages(x)
        return pool.at[page_ids].set(q), scales.at[page_ids].set(s)
    return pool.at[page_ids].set(x.astype(pool.dtype)), None


# ---------------------------------------------------------------------------
# prefix chain hashing (host side, pure)
# ---------------------------------------------------------------------------

def prefix_chain_digests(tokens, page_size: int,
                         limit: Optional[int] = None) -> List[bytes]:
    """Page-aligned chain hashes of a prompt: ``h_i = blake2b(h_{i-1} ||
    tokens[i*ps:(i+1)*ps])`` over FULL pages only. A prefix match between
    two prompts is a chain of leading digest equalities, so the index can
    be a flat ``digest -> page`` dict and a lookup is a walk that stops at
    the first miss. Shared by :class:`PagedKVCache` and the router's
    prefix-affine placement (``serving/router.py``)."""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32).reshape(-1))
    n = toks.size // page_size
    if limit is not None:
        n = min(n, limit)
    out: List[bytes] = []
    h = b""
    for i in range(n):
        h = hashlib.blake2b(
            h + toks[i * page_size:(i + 1) * page_size].tobytes(),
            digest_size=16).digest()
        out.append(h)
    return out


# ---------------------------------------------------------------------------
# host-side pool accounting
# ---------------------------------------------------------------------------

class PagedKVCache:
    """The preallocated page pool plus refcounted accounting and the
    prompt-prefix hash index.

    Holds the pool/scales as raw jnp arrays. The engine hands them to
    each compiled program as donated inputs and rebinds what comes back:
    one buffer, written in place; a call that consumed it and raised is
    answered with :meth:`reset_pool`. Thread-safe: every accounting surface (free list,
    refcount table ``_ref``, prefix index ``_index``, idle LRU) is guarded
    by the single instance lock ``_lock``.

    Page lifecycle::

        alloc()            rc=1, private
        publish()          page enters the prefix index (content frozen)
        acquire_prefix()   rc+=1 per sharer (read-only mapping)
        free()             rc-=1; at rc==0 a published page parks on the
                           idle LRU (still indexed, reclaimable), an
                           unpublished page returns to the free list
        alloc() pressure   idle pages are evicted LRU-first (index entries
                           removed) when the free list alone can't cover

    ``free()`` raises loudly (and counts ``serving.kv.double_free_total``)
    on any free that would corrupt the accounting: freeing scratch,
    freeing an id already on the free list, or freeing a page whose
    refcount is already 0 — i.e. releasing more claims than were ever
    handed out, which with sharing enabled means some other slot's table
    still references the page."""

    def __init__(self, config: KVCacheConfig):
        if config.num_pages is None:
            raise ValueError("KVCacheConfig.num_pages must be set (the "
                             "engine sizes it from max_batch)")
        if config.num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is scratch)")
        self.config = config
        self.scales: Optional[jnp.ndarray] = None
        self._alloc_pool()
        self._lock = threading.Lock()
        # page 0 is scratch: never allocated, target of padded rows.
        # _free_set mirrors _free for O(1) double-free detection — free()
        # runs on the step thread's critical path at every eviction.
        self._free: List[int] = list(range(config.num_pages - 1, 0, -1))
        self._free_set = set(self._free)
        # refcounts for claimed pages (entries exist only while rc > 0)
        self._ref: Dict[int, int] = {}
        # prefix index: chain digest -> page id, and its reverse
        self._index: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}
        # published pages with rc == 0, LRU order (reclaimed under pressure)
        self._idle: "OrderedDict[int, None]" = OrderedDict()
        # stats
        self._high_water = 0
        self._double_free_total = 0
        self._prefix_queries = 0
        self._prefix_query_hits = 0
        self._prefix_pages_shared_total = 0

    def _alloc_pool(self) -> None:
        config = self.config
        shape = (config.num_pages,) + config.page_shape()
        self.pool = jnp.zeros(shape, config.storage_dtype)
        if config.quantized:
            self.scales = jnp.ones(
                (config.num_pages, config.num_layers, 2, config.num_heads),
                jnp.float32)

    def reset_pool(self) -> None:
        """A fresh zeroed pool and an empty prefix index, for the engine
        whose program call consumed the pool and raised. No resident page
        holds content any more, so nothing stays advertised: idle cached
        pages return to the free list, and claimed ones follow as their
        holders release them (the engine replays every running slot)."""
        self._alloc_pool()
        with self._lock:
            self._index.clear()
            self._page_hash.clear()
            for pid in self._idle:
                self._free.append(pid)
                self._free_set.add(pid)
            self._idle.clear()
            _obs.set_gauge("serving.kv.prefix_index_pages", 0.0)

    # -- accounting ---------------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Allocatable pages: the free list plus idle (published, rc==0)
        pages that ``alloc`` may reclaim under pressure."""
        with self._lock:
            return len(self._free) + len(self._idle)

    @property
    def outstanding_pages(self) -> int:
        """Pages currently claimed by slots (rc > 0; scratch and idle
        cached pages excluded). The drain and chaos invariants pin this to
        0 after shutdown: a nonzero value with no active slots is a page
        leak."""
        with self._lock:
            return len(self._ref)

    @property
    def idle_pages(self) -> int:
        """Published pages retained with rc == 0 (prefix cache residue)."""
        with self._lock:
            return len(self._idle)

    @property
    def double_free_total(self) -> int:
        with self._lock:
            return self._double_free_total

    def refcounts(self) -> Dict[int, int]:
        """Snapshot of nonzero refcounts (chaos suites pin this empty)."""
        with self._lock:
            return dict(self._ref)

    def pages_for(self, positions: int) -> int:
        """Pages needed to cover logical positions ``[0, positions)``."""
        ps = self.config.page_size
        return min(self.config.pages_per_slot, -(-positions // ps))

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` private pages (rc=1 each), or None if the pool
        cannot cover them (the caller must not admit — partial claims
        never escape). Takes from the free list first, then reclaims idle
        prefix-cache pages LRU-first, dropping their index entries."""
        with self._lock:
            if n > len(self._free) + len(self._idle):
                return None
            ids: List[int] = []
            for _ in range(n):
                if self._free:
                    pid = self._free.pop()
                    self._free_set.discard(pid)
                else:
                    pid, _ = self._idle.popitem(last=False)
                    self._unpublish_locked(pid)
                self._ref[pid] = 1
                ids.append(pid)
            self._note_usage_locked()
        return ids

    def free(self, ids: Sequence[int]) -> None:
        """Release one claim on each page. A shared page (rc > 1) is
        decremented, not released; at rc == 0 a published page parks on
        the idle LRU and an unpublished page returns to the free list.
        Raises ValueError on double free (see class docstring)."""
        with self._lock:
            for pid in ids:
                rc = self._ref.get(pid, 0)
                if pid == 0 or pid in self._free_set or pid in self._idle \
                        or rc <= 0:
                    self._double_free_total += 1
                    _obs.inc("serving.kv.double_free_total")
                    raise ValueError(
                        f"double free / scratch free: page {pid} (rc={rc})")
                if rc > 1:
                    self._ref[pid] = rc - 1
                    continue
                del self._ref[pid]
                if pid in self._page_hash:
                    self._idle[pid] = None      # retained: still indexed
                else:
                    self._free.append(pid)
                    self._free_set.add(pid)
            self._note_usage_locked()

    # -- prefix sharing ------------------------------------------------------
    def acquire_prefix(self, tokens, first: int = 0,
                       count: Optional[int] = None,
                       quiet: bool = False) -> List[int]:
        """Map the longest resident prefix chain of ``tokens`` read-only:
        walk the page-aligned chain digests through the index, bump each
        hit page's refcount, and return the page ids in chain order (empty
        on no useful match). At most ``(len(tokens) - 1) // page_size``
        pages are shareable — the unshared tail always keeps at least one
        prompt token, so the admission still has a position to prefill and
        emit the first output token from. Matches shorter than
        ``config.min_shared_pages`` are rejected without bumping.

        With ``count`` (a window pool following the full pool's match,
        ISSUE 27) the answer is all or nothing: logical pages ``[first,
        count)`` of the chain, every one resident, or ``[]`` — a window
        layer's tail prefill reads no page below ``first``, so none is
        asked for. ``quiet``: a claim that is no sharer's admission (the
        engine keeping a boundary's pages), left out of the sharing
        stats."""
        ps = self.config.page_size
        toks = np.asarray(tokens).reshape(-1)
        cap = max(0, (toks.size - 1) // ps)
        if count is not None:
            cap = min(cap, count)
        digests = prefix_chain_digests(toks, ps, limit=cap)
        with self._lock:
            self._prefix_queries += not quiet
            got: List[int] = []
            for h in digests[first:]:
                pid = self._index.get(h)
                if pid is None:
                    break
                got.append(pid)
            if count is not None:
                if len(got) < count - first:
                    return []
            elif len(got) < self.config.min_shared_pages:
                return []
            for pid in got:
                if pid in self._idle:
                    del self._idle[pid]         # revive from the idle LRU
                self._ref[pid] = self._ref.get(pid, 0) + 1
            if not quiet:
                self._prefix_query_hits += 1
                self._prefix_pages_shared_total += len(got)
                _obs.inc("serving.kv.prefix_pages_shared_total",
                         float(len(got)))
            self._note_usage_locked()
        return got

    def sole_claims(self, ids: Sequence[int]) -> int:
        """How many of ``ids`` carry exactly one claim."""
        with self._lock:
            return sum(1 for pid in ids if self._ref.get(pid) == 1)

    def peek_prefix_pages(self, tokens) -> int:
        """Length of the resident prefix chain for ``tokens`` WITHOUT
        bumping refcounts — the scheduler's admission cost model uses this
        to charge only the unshared tail. Subject to the same shareable
        cap and ``min_shared_pages`` threshold as :meth:`acquire_prefix`."""
        ps = self.config.page_size
        toks = np.asarray(tokens).reshape(-1)
        cap = max(0, (toks.size - 1) // ps)
        digests = prefix_chain_digests(toks, ps, limit=cap)
        with self._lock:
            depth = 0
            for h in digests:
                if h not in self._index:
                    break
                depth += 1
        return depth if depth >= self.config.min_shared_pages else 0

    def publish(self, tokens, page_ids: Sequence[int],
                first: int = 0) -> int:
        """Register a freshly prefilled slot's fully-prompt pages in the
        prefix index. Only pages ``k < len(tokens) // page_size`` are
        publishable (the page holding the prompt tail also receives decoded
        tokens and is NOT content-frozen). First publisher of a digest
        wins; duplicate content on another page is left unindexed.
        ``page_ids[0]`` is logical page ``first`` (a window slot holds no
        page below its window). Returns the number of pages newly
        indexed."""
        ps = self.config.page_size
        toks = np.asarray(tokens).reshape(-1)
        digests = prefix_chain_digests(toks, ps)
        added = 0
        with self._lock:
            for h, pid in zip(digests[first:], page_ids):
                if h in self._index or pid in self._page_hash:
                    continue
                if self._ref.get(pid, 0) <= 0:
                    continue                    # never index an unclaimed page
                self._index[h] = pid
                self._page_hash[pid] = h
                added += 1
            if added:
                _obs.set_gauge("serving.kv.prefix_index_pages",
                               float(len(self._index)))
        return added

    def prefix_summary(self) -> frozenset:
        """The advertised prefix index: the set of resident chain digests.
        The router's prefix-affine placement walks a prompt's chain
        through each replica's summary to find where the pages live."""
        with self._lock:
            return frozenset(self._index)

    def prefix_stats(self) -> Dict[str, float]:
        """Point-in-time sharing stats for /metrics, /debug/cost and the
        flight-recorder dump tail."""
        with self._lock:
            claims = sum(self._ref.values())
            shared_extra = claims - len(self._ref)
            return {
                "pages_in_use": float(len(self._ref)),
                "pages_idle": float(len(self._idle)),
                "pages_high_water": float(self._high_water),
                "pages_shared_ratio":
                    shared_extra / claims if claims else 0.0,
                "prefix_index_pages": float(len(self._index)),
                "prefix_queries": float(self._prefix_queries),
                "prefix_query_hits": float(self._prefix_query_hits),
                "prefix_hit_rate":
                    self._prefix_query_hits / self._prefix_queries
                    if self._prefix_queries else 0.0,
                "prefix_pages_shared_total":
                    float(self._prefix_pages_shared_total),
                "double_free_total": float(self._double_free_total),
            }

    # -- internals ----------------------------------------------------------
    def _unpublish_locked(self, pid: int) -> None:
        h = self._page_hash.pop(pid, None)
        if h is not None and self._index.get(h) == pid:
            del self._index[h]

    def _note_usage_locked(self) -> None:
        in_use = len(self._ref)
        if in_use > self._high_water:
            self._high_water = in_use
        if self.config.kind:
            # one of several pools: its own series, and the unlabelled
            # gauges stay a single-pool engine's
            _obs.set_gauge("serving.kv.pages_in_use_by_kind", float(in_use),
                           kind=self.config.kind)
            return
        claims = sum(self._ref.values())
        shared_extra = claims - in_use
        _obs.set_gauge("serving.kv.pages_in_use", float(in_use))
        _obs.set_gauge("serving.kv.pages_high_water", float(self._high_water))
        _obs.set_gauge("serving.kv.pages_shared_ratio",
                       shared_extra / claims if claims else 0.0)

    def table_row(self, page_ids: Sequence[int], first: int = 0,
                  width: Optional[int] = None) -> np.ndarray:
        """A slot's page-table row: allocated ids then scratch padding.
        ``page_ids[0]`` is logical page ``first`` and lands in column
        ``first`` of a row of ``width`` columns (default: every logical
        page); a window layer's compact row has ``first=0`` and
        ``width=config.window_pages``."""
        row = np.zeros(self.config.pages_per_slot if width is None
                       else width, np.int32)
        row[first:first + len(page_ids)] = np.asarray(page_ids, np.int32)
        return row


# ---------------------------------------------------------------------------
# the third kind of cache (ISSUE 31, 33): a fixed state per slot in one or
# more parts, the compressed keys a sparse model stores with its pages, and
# snapshots of the state at prefix boundaries
# ---------------------------------------------------------------------------

class StatePart:
    """One donated array of a :class:`StatePool`: ``(rows + 1, layers,
    *shape)`` float32, row 0 the scratch row."""

    def __init__(self, shape: Sequence[int]):
        self.shape = tuple(int(n) for n in shape)
        self.array = None                   # the pool's ``reset`` makes it

    def reset(self) -> None:
        self.array = jnp.zeros(self.shape, jnp.float32)

    @property
    def row_bytes(self) -> int:
        return 4 * int(np.prod(self.shape[1:]))


class StatePool:
    """One float32 state per slot for the layers that keep a fixed state
    and no pages (linear attention), in one or more PARTS of different
    shapes (ISSUE 33: a delta-rule state and a convolution's tail): part
    ``i`` is ``(rows + 1, layers, *shapes[i])``, row 0 the scratch row padded
    batch rows name (float32: the kernels that update a row in place take
    nothing else). Every part is donated to every program and adopted back
    like a page pool; a slot's row is the same in all of them, claimed and
    released together. The host side is a free list of rows. ``shape`` is
    the first part's. Thread-safe."""

    def __init__(self, rows: int, layers: int,
                 shapes: Sequence[Sequence[int]]):
        self.parts = [StatePart((rows + 1, layers) + tuple(s))
                      for s in shapes]
        self.shape = self.parts[0].shape
        self.rows, self.layers = rows, layers
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        """Fresh zeroed states; every row free (the engine replays every
        running slot after a call consumed the pools and raised)."""
        for part in self.parts:
            part.reset()
        with self._lock:
            self._free: List[int] = list(range(self.rows, 0, -1))

    @property
    def free_rows(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def row_bytes(self) -> int:
        return sum(part.row_bytes for part in self.parts)

    def alloc(self) -> int:
        """A free row. There is one per slot and a slot takes exactly one,
        so an admission that found a free slot finds a row."""
        with self._lock:
            if not self._free:
                raise RuntimeError(
                    f"no free state row of {self.rows}: more slots hold one "
                    f"than the engine has slots")
            return self._free.pop()

    def free(self, row: int) -> None:
        with self._lock:
            if row <= 0 or row in self._free:
                raise ValueError(f"double free / scratch free: state row "
                                 f"{row}")
            self._free.append(row)


class IndexPool:
    """The compressed keys of a sparse-attention model, stored WITH the
    pages: ``array`` is ``(num_pages, layers, per_page * H, D)`` float32
    (row ``e * H + h``: a page's ``e``-th entry of KV head ``h``; the
    values are rounded to the pages' storage dtype, the container is
    float32 so that on a TPU a page's rows fill whole (8, 128) tiles and
    the decode step's gather moves no padding), indexed by the same page
    ids and tables, so a shared prefix page shares its entries. No
    accounting of its own."""

    def __init__(self, config: KVCacheConfig, per_page: int):
        self.per_page = per_page
        self.shape = (config.num_pages, config.num_layers,
                      per_page * config.num_heads, config.head_dim)
        self.dtype = jnp.float32
        self.reset()

    def reset(self) -> None:
        self.array = jnp.zeros(self.shape, self.dtype)


class SnapshotStore:
    """What is kept at page-aligned prefix boundaries, keyed by the prefix
    chain digest of the boundary's last page (``prefix_chain_digests``): a
    later prompt that shares the pages up to a boundary starts its prefill
    from what is kept there. An entry is a tuple of parts: kept, counted and
    evicted together under the one digest. A budget, least recently used
    first out.

    Two things are kept this way. A state per slot (the default): a part is
    an array, the budget is bytes, ``serving.state.snapshot_evictions_total``
    and the gauge ``serving.state.snapshot_bytes`` are fed here. A window
    pool's pages at a boundary (``serving.kv.window_boundary_*``): a part is
    one pool's page ids, held by a claim of the store's own, ``size`` counts
    pages and ``on_evict`` gives the claims back. Hits and misses are the
    engine's, which knows what a lookup was for. Thread-safe."""

    def __init__(self, budget_bytes: int, size=None, on_evict=None,
                 evictions: str = "serving.state.snapshot_evictions_total",
                 gauge: str = "serving.state.snapshot_bytes"):
        self.budget = int(budget_bytes)
        self._size = size or self._nbytes
        self._on_evict = on_evict
        self._evictions, self._gauge = evictions, gauge
        self._lock = threading.Lock()
        self._kept: "OrderedDict[bytes, tuple]" = OrderedDict()
        self._bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._kept)

    @property
    def nbytes(self) -> int:
        """What is kept, in the budget's unit."""
        with self._lock:
            return self._bytes

    def values(self) -> List[tuple]:
        with self._lock:
            return list(self._kept.values())

    def __contains__(self, digest: bytes) -> bool:
        with self._lock:
            return digest in self._kept

    def deepest(self, digests: Sequence[bytes], limit: int) -> int:
        """The largest ``n <= limit`` with an entry under ``digests[n - 1]``
        (0: none)."""
        with self._lock:
            for n in range(min(limit, len(digests)), 0, -1):
                if digests[n - 1] in self._kept:
                    return n
        return 0

    def get_parts(self, digest: bytes) -> Optional[tuple]:
        """What is kept under ``digest``, one part a tuple entry (``None``:
        nothing), now the most recently used."""
        with self._lock:
            parts = self._kept.get(digest)
            if parts is not None:
                self._kept.move_to_end(digest)
            return parts

    def get(self, digest: bytes):
        """The first part of what is kept under ``digest``: a one-part
        state whole."""
        parts = self.get_parts(digest)
        return None if parts is None else parts[0]

    def make_room(self, size: int) -> None:
        """Evict, least recently used first, until ``size`` more fits."""
        with self._lock:
            gone = self._evict_locked(size)
        self._evicted(gone)

    def put_parts(self, digest: bytes, parts: Sequence) -> bool:
        """File an entry, one part a tuple entry, under one digest. Returns
        whether it is kept: ``False`` when the digest is kept already (that
        entry is now the most recently used) or the entry alone outgrows
        the budget — what was handed over is then the caller's."""
        parts = tuple(parts)
        size = self._size(parts)
        with self._lock:
            if digest in self._kept:
                self._kept.move_to_end(digest)
                return False
            if size > self.budget:
                return False
            gone = self._evict_locked(size)
            self._kept[digest] = parts
            self._bytes += size
            total = self._bytes
        self._evicted(gone)
        _obs.set_gauge(self._gauge, float(total))
        return True

    def _evict_locked(self, size: int) -> List[tuple]:
        gone = []
        while self._kept and self._bytes + size > self.budget:
            _, old = self._kept.popitem(last=False)
            self._bytes -= self._size(old)
            gone.append(old)
        return gone

    def _evicted(self, gone: List[tuple]) -> None:
        if not gone:
            return
        _obs.inc(self._evictions, float(len(gone)))
        _obs.set_gauge(self._gauge, float(self.nbytes))
        if self._on_evict is not None:
            for parts in gone:
                self._on_evict(parts)

    @staticmethod
    def _nbytes(parts: tuple) -> int:
        return sum(int(a.size) * a.dtype.itemsize for a in parts)

    def reset(self) -> None:
        """Drop everything (``on_evict`` sees each entry go)."""
        with self._lock:
            gone = list(self._kept.values())
            self._kept.clear()
            self._bytes = 0
        _obs.set_gauge(self._gauge, 0.0)
        if self._on_evict is not None:
            for parts in gone:
                self._on_evict(parts)
