"""The serving engine's compiled programs, and the one place that knows how
a call to one of them is laid out.

**Which programs exist.** One decode program (compiled once per batch
bucket), the full prefill (once per prompt length) and one tail prefill
per page-aligned ``start`` offset of a prefix-shared admission (built on
first use). The decode tier is decided here from what the code can observe:
``ServingConfig.paged_attention`` ``auto`` takes the Pallas kernel on a TPU
and the dense gather -> step -> scatter tier elsewhere (``on`` / ``off``
force one; ``off`` is the parity tests' reference), and a shape Mosaic
cannot tile demotes the WHOLE engine to the dense tier, so the tier's label
tells the truth. Whether a prefix can be shared is the prefill callable's
arity (:attr:`Programs.tail_capable`).

**The layout.** A call is built from named parts — ``head`` (decode: the
rows' input tokens ``(B, 1)``; prefill: the ids ``(1, L)``), per pool its
``(tables, pool, scales)`` (``scales`` on the int8 leg only), ``mid``
(decode: positions ``t (B,)``; prefill: the prompt's true length) and, for
a decode step, ``carry`` and ``sel`` — by :meth:`Programs._flatten`, and
read back by :meth:`Programs._adopt`: the first output (the tokens, and
flat behind them whatever int32 counts the model returned as a third
value), then every pool's ``pool, scales``, then a decode step's own
tokens in ``carry``'s shape. Nobody else indexes either. ``carry`` is the
tokens the step before left ON THE DEVICE (one shape for every bucket) and
``sel`` says, per row, which of them the row continues (-1: ``head``'s).

**A model with a fixed state per slot** (ISSUE 31, 33:
``config.state_shape``) adds donated arrays behind the pools — every part of
the state pool (``kv_cache.StatePool.parts``) and, before them, the
compressed keys a model with sparse pages stores with them
(``kv_cache.IndexPool``) — and their own bodies: the decode step hands the
model the page-pool view with those on it and each row's state row (a
``StateDecodeCache``; a ``HybridDecodeCache`` with sparse pages), a prefill
the gathered prefix and the state at ``start`` (``StatePrefill`` /
``HybridPrefill``), scatters what it filled, puts the final state in the
slot's row of every part and returns what it kept of each at snapshot
boundaries (:attr:`Step.extra`, one array a part). They ride in ``tail``:
donated first, then a call's own small arguments. What a state IS — the
rule inside a layer — only the model knows.

**Adoption.** Every program takes the pools (and their scales) donated,
writes them in place and gives them back; the call deletes the arrays it
was given. **The pool a program returns is always adopted; only its tokens
may be abandoned** — sound because a decode step writes position ``t`` of
its rows' own pages (never a published prefix page; padded rows write the
scratch page), and a retried or replayed row rewrites the same positions.
A call that consumed the pools and raised leaves nothing to adopt:
:meth:`Programs.pools_lost` says so, and what to do about it is the
engine's.
"""

from __future__ import annotations

import inspect
import logging
import threading
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor as _T, apply as _apply
from ..core.tracing import no_grad
from ..jit import to_static
from ..ops import paged_attention as _pa
from . import kv_cache as _kv

__all__ = ["Programs", "Step"]

_log = logging.getLogger(__name__)


def _prefill_accepts_start(fn: Callable) -> bool:
    """Whether a prefill callable takes the ISSUE 17 start offset —
    ``prefill_fn(ids, cache, start)`` — and can therefore prefill only the
    unshared tail of a prefix-shared admission. 2-arg callables (the PR 7
    contract) keep working unchanged: sharing just stays off for them."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = list(sig.parameters.values())
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return True
    pos = [p for p in params
           if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    return len(pos) >= 3


@dataclass(eq=False)
class Step:
    """What one program call left on the device, unread: ``tokens`` holds
    the call's ``rows`` tokens and, flat behind them, what the model
    counted; ``carry`` is a decode step's tokens as the step after it takes
    them (``None`` for a prefill, whose ``rows`` is 1)."""

    tokens: _T
    rows: int
    carry: Optional[_T] = None
    extra: tuple = ()               # a prefill's state snapshots, per part

    def read(self) -> Tuple[np.ndarray, np.ndarray]:
        """The call's ONE host sync: ``(tokens (rows,), counts (flat))``."""
        flat = np.asarray(self.tokens._data).reshape(-1)
        return flat[:self.rows], flat[self.rows:]


class Programs:
    """The compiled programs of one engine over its page pools ``kvs`` (one
    per layer kind); ``layer_pool[i]`` = (pool, layer within it) of model
    layer ``i``. Calls run on the engine's single step thread."""

    def __init__(self, prefill_fn: Callable, step_fn: Callable, config,
                 kvs: Sequence[_kv.PagedKVCache],
                 layer_pool: Sequence[Tuple[int, int]],
                 index=None, state=None):
        self.kvs = list(kvs)
        # donated arrays behind the pools, each held as ``.array``: the
        # compressed-key pool of a model with sparse pages, then every part
        # of the state pool; () for a model of pages
        self.index = index
        self.state_parts = list(state.parts) if state is not None else []
        self.extras = ([index] if index is not None else []) \
            + self.state_parts
        cfg = self.kvs[0].config
        self._quantized = cfg.quantized
        self.tail_capable = _prefill_accepts_start(prefill_fn)
        # "kernel" hands step_fn a PagedDecodeCache view (the dense stacked
        # cache never exists in the program); "dense" keeps the gather ->
        # step -> scatter tier the toy and test callables consume
        self.path = _pa.decode_path(config.paged_attention)
        self._interpret = _pa.kernel_interpret()
        if self.path == "kernel" and not self._interpret and \
                not _pa.kernel_eligible(cfg.page_size, cfg.head_dim,
                                        cfg.storage_dtype, cfg.num_heads):
            _log.warning(
                "paged-attention kernel ineligible for page_size=%d "
                "head_dim=%d kv_heads=%d kv storage %s (see "
                "ops.paged_attention.kernel_eligible) — serving on the "
                "dense decode tier", cfg.page_size, cfg.head_dim,
                cfg.num_heads, cfg.storage_dtype)
            self.path = "dense"
        # the donated positions, from the layout itself: the pools and
        # their scales — never what follows them (a decode program's
        # carried tokens are not its to consume)
        marks = self._flatten("head", [
            ("tables", "pool", "pool" if self._quantized else None)
        ] * len(self.kvs), "mid", ("pool",) * len(self.extras))
        self._donate = tuple(i for i, m in enumerate(marks) if m == "pool")
        self._name = config.name or "engine"
        self._carry_rows = config.buckets[-1]
        # what a decode step is handed for ``carry`` when no row of it
        # continues a step still unread (never donated: one array for good)
        self.no_carry = _T(jnp.zeros((self._carry_rows,), jnp.int32))
        self._build(prefill_fn, step_fn, config.num_layers,
                    tuple(layer_pool))
        self._tail_programs: Dict[int, Callable] = {}
        self._program_lock = threading.Lock()

    # ------------------------------------------------------------------
    # the layout
    # ------------------------------------------------------------------
    @staticmethod
    def _flatten(head, parts, mid, tail=()) -> tuple:
        """A call's arguments from its named parts, in the order XLA sees:
        ``head``, the first pool's tables, ``mid``, the first pool (and its
        scales), each later pool's tables, pool and scales, then ``tail``
        (a decode step's ``carry, sel``)."""
        flat = [x for part in parts for x in part if x is not None]
        return (head, flat[0], mid, *flat[1:], *tail)

    def _unflatten(self, args, n_tail: int = 0):
        """:meth:`_flatten` undone, inside a program:
        ``(head, [(tables, pool, scales)], mid, tail)``."""
        head, tables, mid, *rest = args
        flat = [tables, *rest[:len(rest) - n_tail]]
        per = 2 + int(self._quantized)
        parts = [tuple(flat[k * per:(k + 1) * per])
                 + (None,) * (3 - per) for k in range(len(self.kvs))]
        return head, parts, mid, rest[len(rest) - n_tail:]

    @staticmethod
    def _returns(first, pools, carry=()) -> tuple:
        """A program's outputs from their named parts: ``first``, every
        pool's ``(pool, scales)``, then a decode step's ``carry``."""
        return (first, *(x for p in pools for x in p if x is not None),
                *carry)

    def _adopt(self, outs) -> Tuple[_T, tuple]:
        """Read a call's outputs into ``(first, what follows the pools)``
        and adopt the pools on the way: the call consumed the ones it was
        given, so these are the engine's whatever becomes of the tokens."""
        first, *rest = outs
        per = 1 + int(self._quantized)
        for k, kv in enumerate(self.kvs):
            kv.pool = rest[k * per]._data
            if self._quantized:
                kv.scales = rest[k * per + 1]._data
        return first, tuple(rest[len(self.kvs) * per:])

    def _call(self, prog, head, tables, mid, tail=()):
        parts = [(tb, _T(kv.pool),
                  _T(kv.scales) if self._quantized else None)
                 for kv, tb in zip(self.kvs, tables)]
        if not self.extras:
            return self._adopt(prog(*self._flatten(head, parts, mid, tail)))
        held = tuple(_T(x.array) for x in self.extras)
        first, rest = self._adopt(prog(*self._flatten(
            head, parts, mid, held + tuple(tail))))
        for x, new in zip(self.extras, rest):
            x.array = new._data
        return first, rest[len(held):]

    # ------------------------------------------------------------------
    # what the engine calls
    # ------------------------------------------------------------------
    def decode(self, tok, tables, t, carry, sel, state_rows=None) -> Step:
        """Launch one decode step of ``tok.shape[0]`` rows: ``tables`` one
        ``(B, width)`` table per pool, ``carry`` the step before's
        :attr:`Step.carry` (or :attr:`no_carry`); ``state_rows`` ``(B,)``
        each row's row of the state pool, for a model that keeps one.
        Returns unread."""
        tail = (carry, sel) if not self.state_parts \
            else (carry, sel, state_rows)
        first, (carried,) = self._call(self.decode_program, tok, tables, t,
                                       tail)
        return Step(first, int(tok.shape[0]), carried)

    def prefill(self, ids, rows, true_len, start: int = 0, state_row=None,
                start_state=None) -> Step:
        """Prefill one slot whose table row in each pool is ``rows``: the
        full program, or for ``start > 0`` the tail program of that offset
        (``ids`` then holds positions ``start`` onwards only). A model that
        keeps a state leaves it in ``state_row`` of every part of the state
        pool and starts from ``start_state`` (the snapshot at ``start``, one
        Tensor a part; zeros for a full prefill); what it kept at the
        boundaries it passed is the step's ``extra``."""
        prog = self._tail_program(start) if start else self.prefill_program
        if not self.state_parts:
            return Step(self._call(prog, ids, rows, true_len)[0], 1)
        if start_state is None:
            start_state = self.zero_states
        first, snaps = self._call(prog, ids, rows, true_len,
                                  (state_row, *start_state))
        return Step(first, 1, extra=tuple(snaps))

    def pools_lost(self) -> bool:
        """Whether a call that raised had already consumed the pools: the
        donated arrays are deleted and nothing came back."""
        return any(kv.pool.is_deleted() or (
            self._quantized and kv.scales.is_deleted()) for kv in self.kvs) \
            or any(x.array.is_deleted() for x in self.extras)

    def table_width(self, kv, decode: bool) -> int:
        """Columns of a pool's page-table rows: every logical page, but
        for a window pool under the decode kernel, which takes the compact
        window table."""
        if decode and self.path == "kernel" and kv.config.window:
            return kv.config.window_pages
        return kv.config.pages_per_slot

    def decode_row(self, kv, ids: List[int], first: int) -> np.ndarray:
        """A slot's row of pool ``kv``'s decode table: ``ids[0]`` is
        logical page ``first``, which the compact window table puts in
        column 0."""
        width = self.table_width(kv, True)
        compact = width != kv.config.pages_per_slot
        return kv.table_row(ids, first=0 if compact else first, width=width)

    def warm(self, buckets: Sequence[int] = (),
             prompt_lens: Sequence[int] = (),
             tails: Sequence[Tuple[int, int]] = ()) -> None:
        """Compile (or load) a decode program per bucket, a full prefill
        per prompt length and a tail prefill per ``(start, tail length)``,
        against all-scratch tables: the calls read and write the scratch
        page only."""
        def zeros(*shape):
            return _T(jnp.zeros(shape, jnp.int32))

        hybrid = bool(self.state_parts)
        for b in buckets:
            self.decode(zeros(b, 1), [zeros(b, self.table_width(kv, True))
                                      for kv in self.kvs], zeros(b),
                        self.no_carry, _T(jnp.full((b,), -1, jnp.int32)),
                        zeros(b) if hybrid else None)
        for start, n in [(0, lp) for lp in prompt_lens] + list(tails):
            start, n = int(start), int(n)
            self.prefill(zeros(1, n), [zeros(self.table_width(kv, False))
                                       for kv in self.kvs],
                         _T(jnp.asarray(start + n, jnp.int32)), start,
                         zeros() if hybrid else None)

    # ------------------------------------------------------------------
    # the programs
    # ------------------------------------------------------------------
    def _program(self, op: str, body: Callable, site: str, label: str):
        def program(*args):
            return _apply(op, body, *args, differentiable=False, amp=False)

        prog = to_static(program, donate_argnums=self._donate)
        # ISSUE 16: the cost registry files one record per warmed batch
        # bucket under serving.decode (bucket inferred from the compiled
        # tok spec) and one per prefill length under serving.prefill
        prog.cost_site = site
        prog.cost_label = f"{self._name}.{label}"
        return prog

    def _tail_program(self, start: int) -> Callable:
        """The tail-prefill program of a static ``start`` offset, built on
        first use (admission runs on the one step thread; the lock keeps a
        warm-up from a caller's thread harmless)."""
        with self._program_lock:
            prog = self._tail_programs.get(start)
            if prog is None:
                prog = self._tail_programs[start] = self._program(
                    "serving_prefill", self._tail_body(start),
                    "serving.prefill", f"prefill_tail{start}")
        return prog

    def _build(self, prefill_fn, step_fn, L: int, layer_pool) -> None:
        kvs, quantized = self.kvs, self._quantized
        cfg = kvs[0].config
        ps, nk = cfg.page_size, len(kvs)
        compute_dtype = jnp.dtype(cfg.compute_dtype)
        carry_rows = self._carry_rows
        unflatten, returns = self._unflatten, self._returns

        def assemble(parts):
            """Per-pool dense caches (L_k, 2, B, H, M, D) -> the model's
            (L, ...) in layer order; one pool's is the model's already."""
            if nk == 1:
                return parts[0]
            return jnp.stack([parts[k][i] for k, i in layer_pool])

        def layers_of(dense, k):
            if nk == 1:
                return dense
            return dense[jnp.asarray(
                [i for i, (kk, _) in enumerate(layer_pool) if kk == k])]

        def first_out(ret):
            """(token output, cache): a model that counts as it goes (an
            expert layer's rows per expert) returns a third value, an int32
            array read back WITH the tokens — one flat vector, the tokens
            first."""
            nxt = ret[0]._data.astype(jnp.int32)
            if len(ret) > 2:
                nxt = jnp.concatenate([nxt.reshape(-1), ret[2]._data.astype(
                    jnp.int32).reshape(-1)])
            return nxt, ret[1]

        def pick_tok(tok_a, carry_a, sel_a):
            return jnp.where(sel_a[:, None] >= 0,
                             carry_a[jnp.maximum(sel_a, 0)][:, None], tok_a)

        def carry_of(nxt, rows):
            return (jnp.zeros((carry_rows,), jnp.int32)
                    .at[:rows].set(nxt.reshape(-1)[:rows]),)

        def decode_dense(*args):
            tok_a, kinds, t_a, (carry_a, sel_a) = unflatten(args, 2)
            dense = assemble([_kv.gather_pages(pl_, sc, tb, compute_dtype)
                              for tb, pl_, sc in kinds])
            with no_grad():
                nxt, new_dense = first_out(step_fn(
                    _T(pick_tok(tok_a, carry_a, sel_a)), _T(dense), _T(t_a)))
            new_dense = new_dense._data.astype(compute_dtype)
            return returns(nxt, [
                _kv.scatter_token_page(layers_of(new_dense, k), pl_, sc, tb,
                                       t_a, ps)
                for k, (tb, pl_, sc) in enumerate(kinds)],
                carry_of(nxt, tok_a.shape[0]))

        def decode_kernel(*args):
            # the cache argument is the page-pool VIEW: every layer's
            # attention streams live pages through the Pallas kernel and
            # leaves position t's K/V pending on the view; the commit
            # below is the program's one write per pool, in place — made
            # here so that no model forgets it
            tok_a, kinds, t_a, (carry_a, sel_a) = unflatten(args, 2)
            tb, pl_, sc = kinds[0]
            view = _pa.PagedDecodeCache(
                pool=_T(pl_), tables=_T(tb), t=_T(t_a),
                page_size=ps, scales=_T(sc) if quantized else None,
                impl="kernel", interpret=self._interpret,
                window=kvs[0].config.window)
            if nk > 1:
                view = replace(view, layer_kinds=layer_pool, kinds=tuple(
                    _pa.PageKind(pool=_T(pl_), tables=_T(tb),
                                 scales=_T(sc) if quantized else None,
                                 window=kv.config.window)
                    for kv, (tb, pl_, sc) in zip(kvs, kinds)))
            with no_grad():
                nxt, view2 = first_out(step_fn(
                    _T(pick_tok(tok_a, carry_a, sel_a)), view, _T(t_a)))
                view2 = _pa.commit_pending(view2)
            return returns(nxt, [
                (k.pool._data, k.scales._data if quantized else None)
                for k in view2.kinds or (view2,)],
                carry_of(nxt, tok_a.shape[0]))

        def prefill_body(*args):
            ids_a, kinds, len_a, _ = unflatten(args)
            zero = jnp.zeros((L, 2, 1, cfg.num_heads, cfg.max_len,
                              cfg.head_dim), compute_dtype)
            with no_grad():
                nxt, dense = first_out(prefill_fn(_T(ids_a), _T(zero)))
            dense = dense._data.astype(compute_dtype)
            return returns(nxt, [
                _kv.scatter_prefill_pages(layers_of(dense, k), pl_, sc, row,
                                          len_a, ps)
                for k, (row, pl_, sc) in enumerate(kinds)])

        # ISSUE 17: the dense cache enters a tail prefill populated with
        # the shared prefix (gathered from the mapped pages), the 3-arg
        # prefill callable computes K/V for positions [start, prompt_len)
        # only, and the scatter writes ONLY tail pages — the shared pages
        # are never store targets (COW by construction)
        def tail_body(start: int):
            def body(*args):
                ids_a, kinds, len_a, _ = unflatten(args)
                dense = assemble([
                    _kv.gather_pages(pl_, sc, row[None, :], compute_dtype)
                    for row, pl_, sc in kinds])
                with no_grad():
                    nxt, dense2 = first_out(
                        prefill_fn(_T(ids_a), _T(dense), start))
                dense2 = dense2._data.astype(compute_dtype)
                return returns(nxt, [
                    _kv.scatter_prefill_pages(
                        layers_of(dense2, k), pl_, sc, row[start // ps:],
                        len_a, ps, start=start)
                    for k, (row, pl_, sc) in enumerate(kinds)])
            return body

        if self.state_parts:
            decode_body, prefill_body, tail_body = self._state_bodies(
                prefill_fn, step_fn, carry_of, pick_tok, first_out)
        else:
            decode_body = decode_kernel if self.path == "kernel" \
                else decode_dense
        self._tail_body = tail_body
        self.decode_program = self._program(
            "serving_decode_step", decode_body, "serving.decode", "decode")
        self.prefill_program = self._program(
            "serving_prefill", prefill_body, "serving.prefill", "prefill")

    def _state_bodies(self, prefill_fn, step_fn, carry_of, pick_tok,
                      first_out):
        """The decode, prefill and tail bodies of a model with one page pool
        (not quantized) and a state per slot (ISSUE 31, 33): ``tail =
        (held..., a call's own)``, ``held`` every part of the state pool
        and, before them, the compressed keys of a model whose pages are
        sparse. One set of bodies: the model's caches carry the state as
        ``states``, one Tensor a part, with or without an index pool."""
        kv = self.kvs[0]
        cfg = kv.config
        ps = cfg.page_size
        compute_dtype = jnp.dtype(cfg.compute_dtype)
        sparse = self.index is not None
        n_parts = len(self.state_parts)
        n_held = n_parts + int(sparse)
        if sparse:
            # only a model with sparse pages needs (and imports) this
            from ..ops import sparse_attention as _sa
            decode_cache, prefill_cache = _sa.HybridDecodeCache, \
                _sa.HybridPrefill
            per = self.index.per_page
            stride = ps // per
        else:
            from ..ops.linear_attention import (
                StateDecodeCache as decode_cache,
                StatePrefill as prefill_cache)
        self.zero_states = tuple(_T(jnp.zeros(p.shape[1:], jnp.float32))
                                 for p in self.state_parts)
        unflatten, returns = self._unflatten, self._returns
        impl = "kernel" if self.path == "kernel" else "dense"

        def tensors(arrays):
            return tuple(_T(a) for a in arrays)

        def decode_body(*args):
            tok_a, kinds, t_a, tail = unflatten(args, n_held + 3)
            held, (carry_a, sel_a, rows_a) = tail[:n_held], tail[n_held:]
            tb, pl_, _ = kinds[0]
            view = decode_cache(
                pool=_T(pl_), tables=_T(tb), t=_T(t_a), page_size=ps,
                impl=impl, interpret=self._interpret,
                states=tensors(held[int(sparse):]), state_rows=_T(rows_a),
                **({"index_pool": _T(held[0])} if sparse else {}))
            with no_grad():
                nxt, view2 = first_out(step_fn(
                    _T(pick_tok(tok_a, carry_a, sel_a)), view, _T(t_a)))
                # the step's writes, after its last layer: the token's K/V
                # (and the compressed keys it completed); the state rows
                # were written by their layers, in place
                view2 = _pa.commit_pending(view2)
                held2 = tuple(a._data for a in view2.states)
                if sparse:
                    view2 = _sa.commit_index(view2)
                    held2 = (view2.index_pool._data,) + held2
            return returns(nxt, [(view2.pool._data, None)],
                           held2 + carry_of(nxt, tok_a.shape[0]))

        def prefill_at(start: int):
            def body(*args):
                ids_a, kinds, len_a, tail = unflatten(
                    args, n_held + 1 + n_parts)
                held, row_a, from_as = tail[:n_held], tail[n_held], \
                    tail[n_held + 1:]
                row, pl_, _ = kinds[0]
                if start:
                    dense = _kv.gather_pages(pl_, None, row[None, :],
                                             compute_dtype)
                else:
                    dense = jnp.zeros((cfg.num_layers, 2, 1, cfg.num_heads,
                                       cfg.max_len, cfg.head_dim),
                                      compute_dtype)
                with no_grad():
                    nxt, out = first_out(prefill_fn(
                        _T(ids_a), prefill_cache(kv=_T(dense),
                                                 states=tensors(from_as)),
                        start))
                pages = row[start // ps:]
                pool2, _ = _kv.scatter_prefill_pages(
                    out.kv._data.astype(compute_dtype), pl_, None, pages,
                    len_a, ps, start=start)
                held2 = []
                if sparse:
                    # (L, H, M / stride, D) -> the tail pages' entries
                    ent = out.entries._data[:, :, start // stride:]
                    l_, h_, _, d_ = ent.shape
                    ent = ent.reshape(l_, h_, -1, per, d_).transpose(
                        2, 0, 3, 1, 4).reshape(-1, l_, per * h_, d_)
                    held2.append(held[0].at[pages].set(
                        ent.astype(held[0].dtype)))
                for part, new in zip(held[int(sparse):], out.states):
                    held2.append(jax.lax.dynamic_update_slice(
                        part, new._data.astype(part.dtype)[None],
                        (row_a.reshape(()),) + (0,) * (part.ndim - 1)))
                return returns(nxt, [(pool2, None)],
                               (*held2, *(x._data for x in out.snapshots)))
            return body

        return decode_body, prefill_at(0), prefill_at
