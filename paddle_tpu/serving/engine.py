"""The serving step loop: continuous batching over ONE compiled decode
program per batch bucket.

Shape of the engine (the Orca/vLLM iteration-level-scheduling design over
this repo's compiled-decode machinery):

* The model enters as two pure Tensor callables — the exact functions
  ``benchmarks/bench_generation.py`` already compiles:

  - ``prefill_fn(ids (1, Lp), cache (L, 2, 1, H, max_len, D))
    -> (first_token (1, 1) int, filled cache)``
  - ``step_fn(tok (B, 1) int, cache (L, 2, B, H, max_len, D), t (B,) int)
    -> (next_tok (B, 1) int, new cache)``

  The engine never imports a model class: anything that decodes through
  the stacked-cache layout (FusedMultiTransformer's serving path) plugs
  in unchanged.

* The compiled programs live in ``serving/programs.py``, which alone
  knows how a call to one is laid out: ONE decode program per batch
  bucket (kernel tier: attention reads the page pool through a
  ``PagedDecodeCache`` view; dense tier: gather the active slots' pages,
  run the step, scatter back the page each slot wrote), the full prefill,
  one tail prefill per shared-prefix length. Tier and sharing follow the
  platform and the prefill callable's arity under ``ServingConfig``'s
  ``auto``; no environment switch selects either. The engine hands
  ``programs.decode`` / ``prefill`` named parts and gets a ``Step`` back,
  unread: one compiled call and one host sync per step, for ``B`` tokens.

* Batch rows are assigned to active slots PER STEP (per-slot state is
  host-side: a page-table row, a position, a last token — or, for a row
  that continues a step not yet read, where on the device that token is),
  so the batch
  dimension is always compact. It is padded up to a BUCKET size
  (default {1, 4, 16}); padded rows point at the scratch page and are
  masked by construction, so admission/eviction changes which program
  runs only when the bucket changes — and every bucket can be compiled
  up front (:meth:`Engine.warmup`), so admission never recompiles
  mid-flight.

* Admission happens at step boundaries via prefill-into-slot: the
  scheduler pops what fits (slots + pages for the request's WHOLE
  lifetime — no mid-flight preemption), the single-slot prefill program
  fills the prompt's pages and emits the first token. Prefill compiles
  per distinct prompt LENGTH (prompt padding would change the model's
  attention; serve bucketed prompt lengths if that matters).

The page pool is ONE buffer (ISSUE 26): every serving program takes it
donated, writes it in place and gives it back, and ``programs`` adopts
what comes back. **The pool a program returns is always adopted; only its
tokens may be abandoned** (why that is sound: ``serving/programs.py``);
``slot.t`` does not advance when tokens are abandoned. A call that
consumed the pool and raised leaves nothing to adopt
(``programs.pools_lost()``): fresh pool, empty prefix index, every
running slot replayed.

One decode step is in flight ahead of the host's read (ISSUE 28). At a
boundary the loop launches step n+1 and only then reads step n's tokens,
so emitting, freeing, admitting and building run under a program, not
between programs. A row that continues takes its input token from step
n's output ON THE DEVICE (the program's ``carry`` / ``sel`` arguments);
position and page tables are functions of host state known before the
read (``slot.t + slot.ahead``). Who is in step n+1 is decided from what
the host knows: a row whose step-n token is its last by
``max_new_tokens`` or ``max_len`` is left out. **What the host cannot
know ahead is discarded, not waited for**: a row whose request ended at
step n by ``eos_token_id``, a raising stream callback, cancellation or a
fault is already in step n+1, and that row of n+1 is dropped on read —
never streamed, never counted in ``serving.tokens_total``, ``slot.t``
never advanced (``serving.decode_discarded_rows_total``). Sound for the
reason above: the stray write landed at the next position of pages that
were the slot's own (or the scratch page), never a published prefix page,
and whichever later program reuses those pages takes the pool from the
same donation chain, so the device orders it after the stray write. The
same holds for a window page that ``_advance_window`` releases for step
n+1 while step n, still running, reads it. Nothing selects this: step n+1
is launched whenever a slot passes the gate; an admission's prefill
queues behind the step in flight and its synchronous first-token read
drains the pipe for that boundary; when nobody is left to launch for, the
read leaves nothing in flight, so ``run()`` returning, ``stop()`` and the
idle loop see an engine with no program outstanding. One ``step()`` still
emits at most one token per running row.

Pages by layer kind (ISSUE 27): a model that mixes full-attention and
sliding-window layers (``ServingConfig.layer_kinds`` / ``window``) gets one
``PagedKVCache`` per kind — the same class, its own pool ``(pages, layers
of that kind, 2, H, page_size, D)``, free list, refcounts and prefix index
— and every program takes and gives back one pool per kind. A
full-attention slot holds its whole length from admission. A window slot
holds only what its layers still read: before each decode step it claims
the page the step writes and releases the pages below ``t - window``
(:meth:`Engine._advance_window`), at most ``window / page_size + 2`` at
once, and the decode kernel is handed the compact table of just those.
Admission counts both kinds; a prefix is shared when the full pool holds
its pages AND the window pool still holds the last window before the tail.
The first asker holds only the window a sharer of its WHOLE prompt reads;
for a sharer whose own tail is longer than that, a window layer's state at
a page-aligned boundary is the window of K/V before it, and it is kept like
a state (``ServingConfig.window_boundary_tokens``): a prefill writes those
window pages every so many tokens and hands them, by reference — one claim
each — to a ``kv_cache.SnapshotStore`` under the prefix chain digest of the
boundary's last page, a page budget, least recently used out first; a later
prompt maps the prefix up to the deepest boundary so kept when the window
before its full match is gone (``serving.kv.window_prefix_hits_total`` /
``_misses_total``).
A model whose layers are all of one kind gets the one pool and the programs
it always had. A model may return a third value from ``step_fn`` /
``prefill_fn``: an int32 array of what it counted on the device (an expert
layer's rows per held expert), which rides behind the tokens in the step's
one read-back (``serving.moe.*``).

A fixed state per slot (ISSUE 31, 33): a model whose layers mix attention
over pages with linear attention (``layer_kinds`` ``"linear"`` beside
``"sparse"`` or ``"full"``, ``ServingConfig.state_shape``) gets, beside the
one page pool of its attention layers, a ``kv_cache.StatePool`` — one row a
slot of ``(linear layers, *shape)`` in each of the state's parts (one
float32 array, or several of different shapes: a delta-rule state and a
convolution's tail), claimed at admission, released with the
slot — and, only if its pages are ``"sparse"``, a ``kv_cache.IndexPool`` of
the compressed keys stored with each page; all are donated to and returned
by every program like a pool. **A prefix is shared only up to a boundary
whose state was kept**: a
prefill leaves the state after every ``state_snapshot_tokens`` tokens in a
``kv_cache.SnapshotStore`` (a byte budget, least recently used out first)
under the prefix chain digest of the boundary's last page; a later prompt
maps the pages up to the deepest such boundary and prefills the rest from
the state kept there (every part of it, under the one digest). Pages past
it are not shared — a wrong state is not a slower answer. With decode-ahead
nothing of a state crosses to the host; the third value a model with sparse
pages returns from ``step_fn`` is two counts — the pages its rows held and
the pages its selections attended, summed on the device — which ride behind
the tokens and become the ``serving.sparse.decode`` instant (the engine
knows nothing of the rule that chose them, nor of the rule inside a state's
layer); any other model's third value is an expert layer's rows, as above.
The state pool has a row a slot and a slot takes exactly one, so admission
never waits for a row.

Failure semantics (``resilience`` seams):

* ``serving.admit`` fires once per admission attempt, before prefill.
  One retry; a second fault fails THAT request (future gets the error),
  its pages are freed, nothing else is touched.
* ``serving.step`` fires once per (step, included slot), in admission
  order — call index N deterministically targets one slot. A faulted
  slot sits out the current step; the first fault retries it at the next
  step, a second fault fails it. Its batchmates run the very same step
  unaffected: a faulted slot fails ALONE.
* ``serving.watchdog`` fires once per batched-decode launch ATTEMPT,
  inside the armed watchdog window: a ``delay`` fault there simulates a
  hung device step, an ``error`` a whole-batch device fault. A device
  fault is retried once (the seam raises before the call, so the pool is
  as it was); a second fault — or a watchdog trip
  (``PADDLE_TPU_SERVING_WATCHDOG_S``), or a lost pool — abandons the
  tokens of EVERY step in flight, the one being launched and the one not
  yet read (their pools stay adopted), and recovers their slots through
  **bounded prefill replay** from ``slot.tokens``, which hold only what
  was emitted: each slot's prompt + tokens-so-far are requeued at
  the queue head and re-prefilled into a fresh slot (at most
  ``max_replays`` times, then the request fails), so one bad step no
  longer takes every batchmate down with it.
* ``serving.drain`` fires at ``stop(drain=True)`` entry; an injected
  error degrades the graceful drain to an immediate stop. Either way
  every submitted Future resolves and every page returns to the pool.

Overload protection: per-request ``deadline_s``/``ttft_budget_s`` and
the scheduler's queue-wait shedding (see ``serving/scheduler.py``) keep
queue time bounded; an admitted request's deadline becomes the ambient
``resilience.deadline_scope`` around its prefill and around every decode
step it joins, so nested retry policies inherit the same budget.

Metrics: ``serving.requests_total{status}``, ``serving.tokens_total``,
``serving.steps_total`` (steps read), ``serving.decode_ahead_steps_total``
(those launched before the step ahead of them was read) and
``serving.decode_discarded_rows_total`` (rows computed for a request that
had ended — ISSUE 28),
``serving.paged_attention_steps_total{path=kernel|dense}`` (which decode
tier ran — ISSUE 13), ``serving.prefills_total``,
``serving.step_retries_total``, ``serving.pool_resets_total``,
``serving.rejected_total{reason}``,
``serving.watchdog_trips_total{kind}``, ``serving.replays_total``,
``serving.queue_depth``, ``serving.active_slots``,
``serving.batch_utilization``, ``serving.moe.rows_total`` /
``experts_touched_total`` / ``rows_by_expert_total{layer,expert}``,
``serving.kv.pages_in_use_by_kind{kind}``,
``serving.kv.window_pages_per_slot_high_water``,
``serving.kv.window_pages_released_total``,
``serving.kv.window_prefix_hits_total`` / ``_misses_total``,
``serving.kv.window_boundary_evictions_total``,
``serving.kv.window_boundary_pages`` and ``_pages_high_water``,
``serving.state.snapshot_hits_total`` / ``_misses_total`` (admissions that
found resident prefix pages and did / did not find a state to start from) /
``_evictions_total``, ``serving.state.snapshot_bytes``,
``serving.state.row_bytes`` (what one slot's state holds, all parts), and
``serving.ttft_seconds`` /
``serving.tpot_seconds`` / ``serving.queue_wait_seconds`` histograms
(SLO-shaped buckets — see ``TTFT_BUCKETS``/``TPOT_BUCKETS`` below).

Tracing (ISSUE 12): each request carries a trace root from ``submit()``
(``observability.trace`` — spans for submit/prefill, instants for
queue/fault/replay/completion, all linked across the caller and step
threads); the step loop's own phases (ISSUE 25, mode ``on`` only:
``serving.cancel``/``admit``/``publish``/``idle`` and
``serving.decode.build``/``launch``/``wait``/``emit``/``release``, the
table in ``observability/trace.py``) ride the engine's track — since
ISSUE 28 ``serving.decode`` is the span of the step READ at a boundary
(``batch`` its rows, ``ahead`` whether it was launched behind an unread
step), its ``build``/``launch`` are the NEXT step's and its
``wait``/``emit``/``release`` its own;
unrecoverable batched steps dump the flight
recorder (``serving_recover``); the step loop heartbeats ``/healthz``;
``PADDLE_TPU_OBS_HTTP_PORT`` opts into the scrape endpoint.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..core.tensor import Tensor as _T
from ..observability import cost as _cost
from ..observability import http as _obs_http
from ..observability import trace as _trace
from ..resilience import deadline_scope, faults as _faults, jitter_sleep
from ..resilience.watchdog import StepWatchdog, WatchdogTimeout
from . import kv_cache as _kv
from .programs import Programs, Step
from .scheduler import (GenerationRequest, GenerationResult, Scheduler,
                        _Pending)

__all__ = ["ServingConfig", "Engine", "EngineStopped", "DrainTimeout",
           "TTFT_BUCKETS", "TPOT_BUCKETS"]

_log = logging.getLogger(__name__)

# extra seconds past the drain budget the loop thread is given to come
# back from its in-flight compiled call before stop() proceeds without it
_JOIN_GRACE_S = 1.0

# join bound for a stop() WITHOUT a drain budget (timeout=None): the loop
# thread normally exits within one step, but one wedged inside a hung
# compiled call (the watchdog's zombie case) must not turn stop() into
# the very unbounded hang it promises to avoid — past this, the zombie
# is abandoned exactly as in the budgeted case. PADDLE_TPU_STOP_JOIN_S
# overrides for programs whose single step legitimately runs longer.
_STOP_JOIN_S = 30.0

# SLO-shaped latency boundaries (ISSUE 12). The generic 10us..10s decade
# grid clipped exactly the bands a serving SLO routes on: sub-10ms decode
# steps all fell into two buckets, and TTFT targets (100ms/250ms/500ms)
# sat between boundaries. Registered at import so every later observe
# joins these families.
TTFT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0, 2.5, 5.0,
    10.0, 30.0,
)
TPOT_BUCKETS = (
    0.0005, 0.001, 0.002, 0.004, 0.006, 0.008, 0.01, 0.015, 0.025,
    0.05, 0.1, 0.25, 1.0,
)
_obs.histogram("serving.ttft_seconds",
               "submit -> first token (once per request)",
               buckets=TTFT_BUCKETS)
_obs.histogram("serving.tpot_seconds",
               "inter-token time after the first", buckets=TPOT_BUCKETS)

# engine step-loop liveness beacon ttl (/healthz goes 503 past this)
_HEARTBEAT_TTL_S = 60.0


class EngineStopped(RuntimeError):
    """The engine is draining or stopped: ``submit`` rejects new work, and
    queued-but-never-admitted requests resolve with this on a terminal
    ``stop(drain=True, on_timeout="fail")``."""


class DrainTimeout(EngineStopped):
    """An in-flight request was still decoding when the drain budget
    expired and ``on_timeout="fail"`` evicted it."""


def _env_seconds(name: str) -> Optional[float]:
    """Float seconds from the env, with 0/empty/absent meaning off."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    val = float(raw)
    return val if val > 0 else None


@dataclass
class ServingConfig:
    """Engine sizing + policy. Model-shape fields must match the cache
    layout the step/prefill callables consume."""

    num_layers: int
    num_heads: int
    head_dim: int
    max_len: int
    # replica identity (ISSUE 15): names this engine's liveness beacon
    # ``serving.engine.<name>`` so a multi-replica process reports one
    # per-replica /healthz component (the router's rotation signal);
    # empty keeps the single-engine beacon name ``serving.engine``
    name: str = ""
    max_batch: int = 16
    buckets: Tuple[int, ...] = (1, 4, 16)
    max_queue: int = 64
    page_size: int = 64
    num_pages: Optional[int] = None      # default: full coverage + scratch
    kv_dtype: str = "native"             # native | bf16 | int8 pages
    compute_dtype: str = "float32"
    policy: str = "fifo"
    prefill_token_budget: Optional[int] = None
    # -- serving-under-fire knobs (ISSUE 8) --
    # bounded prefill replay: how many times an unrecoverable step fault /
    # watchdog trip may requeue a slot before its Future fails
    max_replays: int = 1
    # step watchdog budget in seconds; None -> $PADDLE_TPU_SERVING_WATCHDOG_S
    # (0/absent = disabled). Pass 0 to force off regardless of env.
    watchdog_s: Optional[float] = None
    # hard cap on queue wait; None -> $PADDLE_TPU_SERVING_MAX_QUEUE_WAIT
    # (0/absent = unbounded). Pass 0 to force off regardless of env.
    max_queue_wait_s: Optional[float] = None
    # paged-attention decode tier (ISSUE 13): auto = Pallas kernel on TPU /
    # dense-gather debug tier on CPU; on = kernel everywhere (Pallas
    # interpreter off-TPU — parity tests); off = the dense tier
    # everywhere, the parity tests' reference.
    paged_attention: str = "auto"
    # prefix-cache page sharing (ISSUE 17) needs a prefill callable that
    # accepts a start offset (``prefill_fn(ids, cache, start)``): auto =
    # share when it does and prefill in full otherwise, on = require it
    # (Engine raises at build if 2-arg), off = never share (the reference).
    prefix_sharing: str = "auto"
    # shortest resident prefix chain worth mapping, in pages
    min_shared_pages: int = 1
    # pages by layer kind (ISSUE 27): a model that mixes full-attention
    # and sliding-window layers names each layer's kind here ("full" |
    # "window", one per layer) and its ``window``; the engine then keeps
    # one page pool per kind — a window slot holds at most
    # window/page_size + 2 pages, whatever its length. Empty: every layer
    # is "full" and there is the one pool. ``num_pages_window`` sizes the
    # window pool (default: every slot's most + the scratch page).
    layer_kinds: Tuple[str, ...] = ()
    window: Optional[int] = None
    num_pages_window: Optional[int] = None
    # a fixed state per slot (ISSUE 31, 33): ``layer_kinds`` may also name
    # "sparse" (full-attention pages whose blocks the model chooses from
    # compressed keys stored with them, ``index_per_page`` to a page) and
    # "linear" (no pages: a float32 state a slot — ``state_shape`` is its
    # shape or, for a state in parts, a tuple of shapes; kept as the
    # latter). One mechanism does not imply the other.
    # ``state_snapshot_tokens``: a prefill keeps the state after every so
    # many tokens (whole pages), and a prefix is shared up to such a
    # boundary only; ``state_snapshot_bytes`` bounds what is kept.
    state_shape: Tuple = ()
    index_per_page: int = 0
    state_snapshot_tokens: int = 4096
    state_snapshot_bytes: int = 1 << 30
    # a window layer's state at a page-aligned boundary is the window of
    # K/V before it: ``window_boundary_tokens`` (whole pages; 0: off) has a
    # prefill keep those window-pool pages every so many tokens, for a
    # later prompt that shares the prefix up to the boundary and whose tail
    # is longer than what the first asker's own pages cover;
    # ``window_boundary_pages`` bounds what is kept, in window-pool pages,
    # and the window pool is that much larger by default
    window_boundary_tokens: int = 0
    window_boundary_pages: int = 0

    def __post_init__(self):
        self.layer_kinds = tuple(self.layer_kinds)
        shapes = tuple(self.state_shape)
        if shapes and not isinstance(shapes[0], (tuple, list)):
            shapes = (shapes,)              # one part
        self.state_shape = tuple(tuple(int(n) for n in s) for s in shapes)
        if self.layer_kinds:
            if len(self.layer_kinds) != self.num_layers or \
                    set(self.layer_kinds) - {"full", "window", "sparse",
                                             "linear"}:
                raise ValueError(
                    f"layer_kinds must name \"full\", \"window\", "
                    f"\"sparse\" or \"linear\" for each "
                    f"of the {self.num_layers} layers, got {self.layer_kinds}")
            if "window" in self.layer_kinds and not self.window:
                raise ValueError("layer_kinds has window layers: set window")
        # a check per mechanism: sparse pages, a state per slot
        if "linear" in self.layer_kinds and not self.state_shape:
            raise ValueError("linear layers need state_shape")
        if "sparse" in self.layer_kinds and not self.index_per_page:
            raise ValueError("sparse layers need index_per_page")
        if "linear" in self.layer_kinds:
            if len(set(self.layer_kinds) - {"linear"}) != 1:
                raise ValueError(
                    "a model with a state per slot keeps one kind of pages "
                    f"beside it today, got {self.layer_kinds}")
            if self.state_snapshot_tokens % self.page_size:
                raise ValueError("state_snapshot_tokens must be whole pages")
            if self.kv_dtype == "int8":
                raise ValueError("a model with a state per slot keeps its "
                                 "pages unquantized today")
        self.buckets = tuple(sorted(set(int(b) for b in self.buckets)))
        if not self.buckets or self.buckets[-1] < self.max_batch:
            raise ValueError(
                f"buckets {self.buckets} must cover max_batch "
                f"{self.max_batch}")
        if self.kv_dtype not in ("native", "bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be native|bf16|int8, got {self.kv_dtype!r}")
        if self.max_replays < 0:
            raise ValueError(f"max_replays must be >= 0, got "
                             f"{self.max_replays}")
        if self.watchdog_s is None:
            self.watchdog_s = _env_seconds("PADDLE_TPU_SERVING_WATCHDOG_S")
        elif self.watchdog_s <= 0:
            self.watchdog_s = None
        if self.max_queue_wait_s is None:
            self.max_queue_wait_s = _env_seconds(
                "PADDLE_TPU_SERVING_MAX_QUEUE_WAIT")
        elif self.max_queue_wait_s <= 0:
            self.max_queue_wait_s = None
        for name in ("paged_attention", "prefix_sharing"):
            value = getattr(self, name).strip().lower()
            if value not in ("auto", "on", "off"):
                raise ValueError(
                    f"{name} must be auto|on|off, got {value!r}")
            setattr(self, name, value)
        if self.min_shared_pages < 1:
            raise ValueError(f"min_shared_pages must be >= 1, got "
                             f"{self.min_shared_pages}")
        if self.window_boundary_tokens and (
                "window" not in self.layer_kinds
                or self.window_boundary_tokens % self.page_size
                or self.window_boundary_pages < 1):
            raise ValueError(
                "window_boundary_tokens needs window layers, whole pages "
                "and a window_boundary_pages budget")

    def kv_config(self, kind: str = "", num_layers: Optional[int] = None
                  ) -> _kv.KVCacheConfig:
        window = self.window if kind == "window" else None
        cfg = _kv.KVCacheConfig(
            num_layers=self.num_layers if num_layers is None else num_layers,
            num_heads=self.num_heads,
            head_dim=self.head_dim, max_len=self.max_len,
            page_size=self.page_size,
            num_pages=self.num_pages_window if window else self.num_pages,
            compute_dtype=self.compute_dtype, kv_dtype=self.kv_dtype,
            min_shared_pages=self.min_shared_pages, kind=kind, window=window)
        if cfg.num_pages is None:
            # every slot fully resident + the scratch page; requests with
            # short prompt+max_new claim fewer pages, freeing pool for a
            # deeper queue when num_pages is set below this default
            cfg.num_pages = self.max_batch * (
                cfg.window_pages or cfg.pages_per_slot) + 1
            if window and self.window_boundary_tokens:
                cfg.num_pages += self.window_boundary_pages
        return cfg

    def kv_configs(self) -> List[_kv.KVCacheConfig]:
        """One pool's config per layer kind that keeps pages, "full" first:
        the one pool of every layer that every engine had, unless the model
        names another kind."""
        if not set(self.layer_kinds) - {"full"}:
            return [self.kv_config()]
        return [self.kv_config(kind, self.layer_kinds.count(kind))
                for kind in ("full", "window", "sparse")
                if kind in self.layer_kinds]


@dataclass(eq=False)                     # identity semantics: slots hold an
class _Slot:                             # ndarray-bearing request, and
    """Host-side state of one in-flight request (the device holds only
    pool pages; batch row assignment happens per step). ``list.remove``
    in ``_release`` must match THIS slot, not a field-equal one."""

    pending: _Pending
    # per pool (one per layer kind): the page ids this slot holds one
    # refcount on each — the leading ones may be mapped read-only from the
    # prefix index (ISSUE 17) — and the logical page the first of them is
    # (0 but for a window pool, which keeps no page below its window)
    pages: List[List[int]]
    first_page: List[int]
    rows: List[np.ndarray]              # per pool: the decode table row
    t: int                              # next cache write position
    last_tok: int
    tokens: List[int] = field(default_factory=list)
    # decode steps launched for this slot whose tokens the host has not
    # read (ISSUE 28): the next one writes position ``t + ahead``, and
    # ``tokens``, ``t`` and ``last_tok`` know nothing of them yet
    ahead: int = 0
    faults: int = 0
    first_token_time: float = 0.0
    last_token_time: float = 0.0
    state_row: int = 0                  # its row of the state pool (0: none)

    @property
    def request(self) -> GenerationRequest:
        return self.pending.request


@dataclass(eq=False)
class _Flight:
    """One decode step the device has been given and the host has not read
    (ISSUE 28): its rows in batch order, and the call's device arrays —
    the inputs ``built`` for it, kept so that freeing them has a phase of
    its own, and the ``step`` it returned: its tokens, unread, and the
    tokens it carries to the step after it."""

    included: List[_Slot]
    bucket: int
    ahead: int                          # launched behind an unread step
    built: Optional[tuple]
    step: Optional[Step]


class Engine:
    """Continuous-batching decode engine over a paged KV pool.

    ``step()`` is single-consumer (call it from one thread: your own loop,
    :meth:`run`, or the :meth:`start` background thread); ``submit`` and
    ``cancel`` are safe from any thread.
    """

    def __init__(self, prefill_fn: Callable, step_fn: Callable,
                 config: ServingConfig):
        self.config = config
        # one pool per layer kind (ISSUE 27), the full-attention one first;
        # ``kv`` is the first: the only one unless the model has window
        # layers. ``_layer_pool[i]`` = (pool, layer within it) of layer i.
        self.kvs = [_kv.PagedKVCache(c) for c in config.kv_configs()]
        self.kv = self.kvs[0]
        names = [kv.config.kind for kv in self.kvs]
        count = [0] * len(self.kvs)
        self._layer_pool: List[Tuple[int, int]] = []
        n_linear = config.layer_kinds.count("linear")
        for kind in (config.layer_kinds if len(self.kvs) > 1 or names[0]
                     else ("",) * config.num_layers):
            if kind == "linear":            # no pool: a state row's layer
                self._layer_pool.append((-1, 0))
                continue
            k = names.index(kind)
            self._layer_pool.append((k, count[k]))
            count[k] += 1
        # ISSUE 31, 33, each by its own mechanism: the state pool and the
        # states kept at prefix boundaries for a model with "linear" layers,
        # the compressed keys beside the pages for one with "sparse" pages
        # (None: neither)
        self.state = self.index = self.snapshots = None
        if n_linear:
            self.state = _kv.StatePool(config.max_batch, n_linear,
                                       config.state_shape)
            self.snapshots = _kv.SnapshotStore(config.state_snapshot_bytes)
            _obs.set_gauge("serving.state.row_bytes",
                           float(self.state.row_bytes))
        if "sparse" in config.layer_kinds:
            self.index = _kv.IndexPool(self.kv.config, config.index_per_page)
        # pages a window pool's admitted slots may come to hold at once:
        # admission keeps it within the pool, so a decode step's page
        # claim never fails (guarded by _slot_lock)
        self._window_committed = [0] * len(self.kvs)
        self._window_high_water = 0
        # the window pools' pages kept at prefix boundaries: per entry one
        # list of page ids a window pool, each held by a claim of the
        # store's own (None: not kept)
        self.window_boundaries = None
        self._boundary_high_water = 0
        if config.window_boundary_tokens:
            self.window_boundaries = _kv.SnapshotStore(
                config.window_boundary_pages,
                size=lambda parts: sum(len(ids) for ids in parts),
                on_evict=self._give_back_boundary,
                evictions="serving.kv.window_boundary_evictions_total",
                gauge="serving.kv.window_boundary_pages")
        # an expert layer's row counts since they were last published
        self._expert_rows: Optional[np.ndarray] = None
        self._expert_touches = 0
        self._expert_rows_at = 0.0
        # ISSUE 16: the HBM ledger tracks the pools' bytes (weakly — a
        # dropped engine drops its pools from the ledger)
        for kv in self.kvs:
            _cost.register_kv_cache(kv)
        # the compiled programs and how they are called; the tier they run
        self.programs = Programs(
            prefill_fn, step_fn, config, self.kvs, self._layer_pool,
            index=self.index, state=self.state)
        self._paged_path = self.programs.path
        # ISSUE 17: prefix-cache page sharing — on only when the prefill
        # callable can start from a page-aligned offset (3-arg form)
        capable = self.programs.tail_capable
        if config.prefix_sharing == "on" and not capable:
            raise ValueError(
                "prefix_sharing=on requires a tail-capable prefill "
                "callable (prefill_fn(ids, cache, start)); this one takes "
                "2 args — pass auto/off, or extend the callable")
        self._share_prefix = config.prefix_sharing != "off" and capable
        # prefill tokens requested vs actually computed (the sharing win;
        # guarded by _slot_lock — written on the step thread, read by the
        # bench/router threads)
        self._prefill_tokens_requested = 0
        self._prefill_tokens_computed = 0
        self.scheduler = Scheduler(
            max_queue=config.max_queue, policy=config.policy,
            prefill_token_budget=config.prefill_token_budget,
            max_queue_wait_s=config.max_queue_wait_s,
            prefill_cost=self._prefill_cost if self._share_prefix else None)
        self._slots: List[_Slot] = []    # admission order == batch row order
        # serializes slot admission/eviction and the in-transit counter:
        # normally the step loop is the single consumer, but a budgeted
        # stop() that gave up on a wedged loop thread resolves stragglers
        # from the CALLER's thread while the wedged call may return
        # concurrently — _release must decide a slot's winner exactly
        # once, and the drain-owed probe must read a consistent
        # slots/in-transit snapshot (ISSUE 14: shared-state-race)
        self._slot_lock = threading.Lock()
        # requests in transit between queue and slot at this step boundary
        # (popped by _admit but prefill not yet finished) or between slot
        # and queue (crash-recovery eviction before its requeue lands):
        # the drain-owed probe polls from another thread and must not
        # mistake either window for "nothing left to finish". Guarded by
        # _slot_lock on every side.
        self._in_transit = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._draining = threading.Event()
        # how the ACTIVE drain resolves stragglers ("fail"|"requeue"):
        # written by stop() under _slot_lock before the straggler sweep,
        # read by a late-returning _admit_one that landed after the sweep
        # (ISSUE 15: the wedged-mid-admission window)
        self._drain_on_timeout = "fail"
        self._thread: Optional[threading.Thread] = None
        self._watchdog: Optional[StepWatchdog] = (
            StepWatchdog(config.watchdog_s) if config.watchdog_s else None)
        # per-replica beacon name (ISSUE 15): one /healthz component per
        # engine, so the router can take ONE wedged replica out of
        # rotation instead of reading a process-global staleness bit
        self._beacon = (f"serving.engine.{config.name}" if config.name
                        else "serving.engine")
        # ISSUE 12: one trace track for the engine's own batched steps
        # (requests carry their own), and the opt-in scrape endpoint
        self._engine_trace = None
        self._obs_http = _obs_http.maybe_serve_from_env()
        # the decode step launched and not yet read (ISSUE 28; written
        # under _slot_lock: stop() may step from its own thread)
        self._flight: Optional[_Flight] = None

    # ------------------------------------------------------------------
    # compiled programs (serving/programs.py owns them)
    # ------------------------------------------------------------------
    def warmup(self, prompt_lens: Sequence[int] = (),
               tails: Sequence[Tuple[int, int]] = ()) -> "Engine":
        """Compile every batch bucket (and optional prefill lengths, and
        ``(shared prefix, tail)`` length pairs of prefix-shared
        admissions) up front, against the scratch page only — admission
        then never recompiles mid-flight. Idempotent; call before serving
        traffic."""
        with _trace.span("serving.warmup", replica=self.config.name
                         or "engine", programs=len(self.config.buckets)
                         + len(prompt_lens) + len(tails)):
            self.programs.warm(self.config.buckets, prompt_lens, tails)
        return self

    # ROADMAP C11's debt: perfbench's ``_warm_tails`` reaches in with the
    # one-pool call ``_tail_program(start)(ids, row, true_len, pool,
    # *_scales_args())``. The pool it hands over is the engine's own, which
    # the programs take themselves. Both go when it calls warmup(tails=).
    def _tail_program(self, start: int) -> Callable:
        return lambda ids, row, true_len, *_pool: self.programs.prefill(
            ids, [row], true_len, start)

    def _scales_args(self) -> tuple:
        return (_T(self.kv.scales),) if self.kv.config.quantized else ()

    def _restore_lost_pool(self, exc: BaseException) -> bool:
        """After a serving program raised: if the call had already
        consumed the pools (the donated arrays are deleted and nothing came
        back), every resident page is gone with them. Start from fresh
        pools and an empty prefix index, and send EVERY running slot
        through bounded replay — their re-prefill rewrites their pages.
        A call that raised before it ran (a trace failure, an injected
        fault) leaves the pools alone, and so does this (returns False)."""
        if not self.programs.pools_lost():
            return False
        _log.warning("serving: a program call consumed the page pool and "
                     "raised (%s) — fresh pool, prefix index dropped, %d "
                     "running slot(s) replayed", type(exc).__name__,
                     len(self._slots))
        _obs.inc("serving.pool_resets_total")
        for kv in self.kvs:
            kv.reset_pool()
        if self.window_boundaries is not None:
            self.window_boundaries.reset()
        if self.index is not None:
            self.index.reset()
        if self.state is not None:          # the states went with the pools
            self.state.reset()
            self.snapshots.reset()
            for slot in self._slots:        # reset() freed every row
                slot.state_row = 0
        self._recover_slots(list(self._slots), exc)
        return True

    # ------------------------------------------------------------------
    # request surface
    # ------------------------------------------------------------------
    def _pages_needed(self, request: GenerationRequest, kv=None) -> int:
        """Pages of pool ``kv`` (default: the first) the request holds at
        most: its whole length, or a window pool's bounded share."""
        kv = kv or self.kv
        last = min(self.config.max_len,
                   int(request.prompt.size) + request.max_new_tokens)
        need = kv.pages_for(last)
        return min(need, kv.config.window_pages or need)

    def _prefill_cost(self, request: GenerationRequest) -> int:
        """The scheduler's admission cost for a request: prompt tokens the
        prefill will actually COMPUTE — the full prompt minus whatever
        prefix chain is resident right now (ISSUE 17). A peek, not a
        claim: the admission itself re-resolves (and refcounts) the chain
        under the kv lock."""
        full = int(request.prompt.size)
        shared = self._shareable_pages(request.prompt)[1] \
            * self.config.page_size
        return max(1, full - shared)

    def _shareable_pages(self, prompt) -> Tuple[int, int]:
        """``(resident, shareable)`` leading pages of ``prompt``: those the
        prefix index holds, and those an admission may map — all of them,
        or for a model with a state per slot only up to the deepest
        boundary whose state the snapshot store still has."""
        resident = self.kv.peek_prefix_pages(prompt)
        if self.snapshots is None or not resident:
            return resident, resident
        digests = _kv.prefix_chain_digests(
            prompt, self.config.page_size, limit=resident)
        return resident, self.snapshots.deepest(digests, resident)

    def prefix_summary(self) -> frozenset:
        """The kv pool's advertised prefix index (chain digests) — the
        router's prefix-affine placement signal (ISSUE 17)."""
        return self.kv.prefix_summary()

    @property
    def prefix_sharing_enabled(self) -> bool:
        return self._share_prefix

    def prefill_token_stats(self) -> Tuple[int, int]:
        """(requested, computed) prompt tokens across all admissions so
        far — the bench's prefix-sharing win of record."""
        with self._slot_lock:
            return (self._prefill_tokens_requested,
                    self._prefill_tokens_computed)

    def submit(self, request: GenerationRequest):
        """Enqueue; returns a Future resolving to GenerationResult.
        Raises QueueFull / DeadlineExceeded (shed on arrival) /
        EngineStopped (draining) / ValueError (request can never fit)
        here, on the caller's thread.

        With tracing enabled the request gets its own trace root here
        (one Perfetto track per request): the context rides the pending
        through the scheduler queue to the engine step thread, so the
        span tree follows the request across threads."""
        ctx = _trace.new_trace(f"request-{request.request_id}",
                               rid=request.request_id) \
            if _trace.enabled() else None
        with _trace.span("serving.submit", parent=ctx,
                         rid=request.request_id):
            return self._submit(request, ctx)

    def _submit(self, request: GenerationRequest, ctx):
        if self._draining.is_set():
            _obs.inc("serving.requests_total", status="rejected")
            _obs.inc("serving.rejected_total", reason="shed")
            raise EngineStopped("engine is draining/stopped: not admitting")
        if int(request.prompt.size) + request.max_new_tokens \
                > self.config.max_len:
            raise ValueError(
                f"prompt ({request.prompt.size}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds max_len "
                f"{self.config.max_len}")
        if any(self._pages_needed(request, kv) > kv.config.num_pages - 1
               for kv in self.kvs):
            raise ValueError("request needs more pages than the pool holds")
        fut = self.scheduler.submit(request, submit_time=time.monotonic(),
                                    trace_ctx=ctx)
        if self._draining.is_set():
            # raced a concurrent stop(drain=True) past the check above: the
            # drain's queue resolution may already have run, in which case
            # our fresh pending would sit in a queue nobody will ever pop —
            # withdraw it and reject here; if the drain DID resolve it
            # first, the Future already carries EngineStopped
            if self.scheduler.withdraw(request.request_id) is not None:
                _obs.inc("serving.requests_total", status="rejected")
                _obs.inc("serving.rejected_total", reason="shed")
                raise EngineStopped(
                    "engine is draining/stopped: not admitting")
            return fut
        self._wake.set()
        return fut

    def cancel(self, request_id: int) -> bool:
        ok = self.scheduler.cancel(request_id)
        self._wake.set()
        return ok

    @property
    def active_requests(self) -> int:
        return len(self._slots)

    @property
    def queue_depth(self) -> int:
        return self.scheduler.queue_depth

    @property
    def name(self) -> str:
        """Replica name ("" for a single-engine process)."""
        return self.config.name

    @property
    def beacon(self) -> str:
        """This engine's /healthz component name (ISSUE 15)."""
        return self._beacon

    @property
    def draining(self) -> bool:
        """True once ``stop(drain=...)`` latched new admissions off: the
        router's marks-out-of-rotation-before-the-drain signal."""
        return self._draining.is_set()

    # ------------------------------------------------------------------
    # the step loop
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One step boundary: evict cancellations, admit what fits, launch
        ONE batched decode step and read the one launched a boundary
        earlier (ISSUE 28) — at most one token per running row. Returns
        False when there was nothing to do (the idle step — no program
        runs, no device touch)."""
        _trace.heartbeat(self._beacon, ttl_s=_HEARTBEAT_TTL_S)
        if self._engine_trace is None and _trace.enabled():
            self._engine_trace = _trace.new_trace("serving-engine")
        # the phases of one boundary, on the engine's track (ISSUE 25):
        # with the profiler running they name what the host was doing in
        # each device idle gap
        track = self._engine_trace
        with _trace.phase("serving.cancel", parent=track):
            progressed = self._process_cancellations()
        with _trace.phase("serving.admit", parent=track):
            # draining latches out NEW admissions only: slots evicted by
            # crash-recovery mid-drain still re-admit, or the drain would
            # misreport an in-flight (recoverable) request as
            # never-admitted
            progressed |= self._admit(
                replay_only=self._draining.is_set())
            included = self._fault_gate()       # no slots: nothing gated
        progressed |= self._decode(included)
        with _trace.phase("serving.publish", parent=track):
            self._publish_gauges(len(included),
                                 self._bucket_for(len(included))
                                 if included else 0)
        return progressed

    def run(self) -> None:
        """Drive step() until queue and slots drain and no step is left in
        flight (bench/offline mode). Like :meth:`start`, clears the
        draining latch first, so run() after ``stop(drain=True,
        on_timeout="requeue")`` resumes the requeued work instead of
        refusing to admit it forever."""
        self._stop.clear()
        self._draining.clear()
        while self.scheduler.queue_depth or self._slots \
                or self._flight is not None:
            self.step()

    def start(self) -> "Engine":
        """Serve from a background thread until stop(). Re-entrant after
        a stop: clears the draining latch, so requests requeued by
        ``stop(drain=True, on_timeout="requeue")`` resume decoding."""
        if self._thread is not None:
            return self
        self._stop.clear()
        self._draining.clear()

        def loop():
            while not self._stop.is_set():
                if not self.step():
                    with _trace.phase("serving.idle",
                                      parent=self._engine_trace):
                        self._wake.wait(0.01)
                    self._wake.clear()
            self._decode([])        # stopped: read what is in flight

        self._thread = threading.Thread(
            target=loop, name="paddle-tpu-serving", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = False, timeout: Optional[float] = None,
             on_timeout: str = "fail") -> None:
        """Stop serving.

        ``drain=False`` (default) pauses the loop where it stands:
        in-flight slots and the queue are left intact, ``start()``
        resumes them — the PR 7 semantics, unchanged.

        ``drain=True`` is the online-shutdown contract: stop admitting
        (``submit`` raises :class:`EngineStopped`, queued requests stay
        queued), keep stepping until every in-flight sequence finishes or
        ``timeout`` seconds pass, then resolve the stragglers —
        ``on_timeout="fail"`` fails still-active slots with
        :class:`DrainTimeout` and never-admitted queued requests with
        :class:`EngineStopped` (no Future is left stranded);
        ``on_timeout="requeue"`` puts active stragglers back at the queue
        head via the bounded-replay path (prompt + tokens so far) and
        leaves the queue intact, so a later ``start()`` resumes exactly
        where the drain stopped. Idempotent, callable from any thread
        EXCEPT the engine step thread itself — a stream callback calling
        ``stop()`` would be asking the loop to drain itself (raises
        ``RuntimeError``; use :meth:`cancel`, or stop from another
        thread). Signal handlers are fine: flag-set + a join bounded by
        the drain budget +1 s grace — or by ``PADDLE_TPU_STOP_JOIN_S``
        (default 30 s) when no budget was given, so a wedged loop thread
        never makes stop() itself hang — if the loop thread is wedged
        inside a compiled call past that, stop() logs it, resolves the
        stragglers anyway, and abandons the zombie step's late return;
        a second concurrent call finds nothing left to resolve."""
        if on_timeout not in ("fail", "requeue"):
            raise ValueError(f"on_timeout must be fail|requeue, "
                             f"got {on_timeout!r}")
        if self._thread is not None \
                and threading.current_thread() is self._thread:
            raise RuntimeError(
                "Engine.stop() called from the engine step thread (a "
                "stream callback): the loop cannot drain itself — use "
                "cancel(), or call stop() from another thread")
        deadline = (time.monotonic() + timeout) if timeout is not None \
            else None
        graceful = False
        if drain:
            self._draining.set()
            self._wake.set()
            try:
                _faults.fault_point("serving.drain")
                graceful = True
            except Exception:
                # injected drain fault: degrade to an immediate stop — the
                # no-stranded-futures invariant outranks graceful finish
                graceful = False
        if graceful:
            # work still owed = active slots + crash-recovery requeues
            # awaiting re-admission + requests in transit between the two
            # (popped-but-prefilling, evicted-but-not-yet-requeued) — NOT
            # new never-admitted requests
            def owed() -> bool:
                # consistent snapshot of the step thread's slot state; the
                # scheduler probe stays OUTSIDE _slot_lock (it takes the
                # scheduler's own lock — no nesting, no new lock order)
                with self._slot_lock:
                    busy = bool(self._slots) or self._in_transit > 0 \
                        or self._flight is not None
                return busy or self.scheduler.queued_replays() > 0
            if self._thread is not None:
                # the loop thread keeps stepping (new admissions are
                # latched off); poll until the last owed sequence evicts
                # or the budget ends
                while owed() and not self._stop.is_set():
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        break
                    jitter_sleep(0.002)
            else:
                # offline/manually-driven engine: drive the steps inline
                while owed() and \
                        (deadline is None or time.monotonic() < deadline):
                    self.step()
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            # bounded by the caller's budget — or by _STOP_JOIN_S when no
            # budget was given: a loop thread wedged inside a hung
            # compiled call (the watchdog's zombie case) must not turn
            # stop() into a second unbounded hang either way
            if deadline is None:
                join_s = _env_seconds("PADDLE_TPU_STOP_JOIN_S") \
                    or _STOP_JOIN_S
            else:
                join_s = max(0.0, deadline - time.monotonic()) \
                    + _JOIN_GRACE_S
            t.join(timeout=join_s)
            if t.is_alive():
                _log.warning(
                    "serving stop(): loop thread still wedged in a "
                    "compiled call past the drain budget — resolving "
                    "stragglers without it; its late return is abandoned "
                    "(slots already released; restart the process to "
                    "reclaim the thread)")
        if t is None or not t.is_alive():
            # nobody steps any more: read the step in flight, so that a
            # stopped engine has no program outstanding (a wedged loop
            # thread keeps its own: what it reads late is discarded)
            self._decode([])
        self._thread = None
        if self._watchdog is not None:
            self._watchdog.stop()
        if drain:
            with self._slot_lock:
                self._drain_on_timeout = on_timeout
            # a wedged loop thread may be MID-ADMISSION (pending popped
            # from the queue, prefill in flight): give that short window
            # one bounded grace to land, or the pending would be in
            # neither the queue nor the slots when the sweep runs. If it
            # still lands later, _admit_one's late-admission guard
            # resolves it per _drain_on_timeout — no Future is stranded
            # either way.
            grace = time.monotonic() + _JOIN_GRACE_S
            while time.monotonic() < grace:
                with self._slot_lock:
                    if self._in_transit == 0:
                        break
                jitter_sleep(0.002)
            self._resolve_stragglers(on_timeout)
            if self.window_boundaries is not None:
                # a drained engine holds no page: the kept boundaries too
                self.window_boundaries.reset()
        # a cleanly stopped engine is not a liveness failure; and with
        # PADDLE_TPU_TRACE=on + a TRACE_DIR, leave the operator a
        # Perfetto-loadable trace of the run
        _trace.heartbeat_clear(self._beacon)
        _trace.maybe_export_chrome("serving")

    def _resolve_stragglers(self, on_timeout: str) -> None:
        """Terminal accounting for a drain: no Future may stay stranded
        (``fail``) or every straggler is requeued resumable (``requeue``).
        Runs after the loop thread has joined — single-threaded."""
        requeue: List[_Pending] = []
        for slot in list(self._slots):
            pend = slot.pending
            if on_timeout == "requeue":
                # drain eviction is not a fault: it does not spend the
                # replay budget — a restarted engine re-prefills
                # prompt + tokens-so-far and continues bit-identically.
                # A late-returning wedged step may have won the _release
                # race and settled the Future: requeuing it then would
                # re-decode settled work and set_result would raise
                if not self._release(slot):
                    continue
                pend.replay_tokens = list(slot.tokens)
                requeue.append(pend)
            else:
                self._finish_error(slot, DrainTimeout(
                    f"request {slot.request.request_id} evicted at drain "
                    f"timeout after {len(slot.tokens)} tokens"))
        if requeue:
            self.scheduler.requeue(requeue)
        if on_timeout == "fail":
            for pend in self.scheduler.drain_queue():
                # settle the Future BEFORE the telemetry calls: this
                # method's contract is "no Future may stay stranded", so
                # a counter/trace hook raising must not leave this pend —
                # or the untouched rest of the drained queue — unresolved
                # (found by the resource-discipline lint)
                if pend.replays or pend.replay_tokens:
                    # NOT overload shed: this request was admitted and
                    # decoding when crash-recovery requeued it, and the
                    # drain budget ran out before its re-admission
                    pend.future.set_exception(DrainTimeout(
                        f"request {pend.request.request_id} evicted at "
                        f"drain timeout awaiting replay re-admission "
                        f"after {len(pend.replay_tokens)} tokens"))
                    _obs.inc("serving.requests_total", status="failed")
                    _trace.instant("serving.fault", parent=pend.trace_ctx,
                                   rid=pend.request.request_id,
                                   error="DrainTimeout")
                    continue
                pend.future.set_exception(EngineStopped(
                    f"request {pend.request.request_id} never admitted: "
                    f"engine stopped"))
                _obs.inc("serving.requests_total", status="shed")
                _obs.inc("serving.rejected_total", reason="shed")
                _trace.instant("serving.shed", parent=pend.trace_ctx,
                               rid=pend.request.request_id,
                               reason="engine_stopped")

    # -- step phases ----------------------------------------------------
    def _process_cancellations(self) -> bool:
        cancelled = self.scheduler.take_cancelled_active()
        if not cancelled:
            return False
        hit = False
        for slot in [s for s in self._slots
                     if s.request.request_id in cancelled]:
            self._finish(slot, "cancelled")
            hit = True
        return hit

    def _admit(self, replay_only: bool = False) -> bool:
        free_slots = self.config.max_batch - len(self._slots)
        if free_slots <= 0:
            return False
        # ``claimed`` reserves pages WITHIN this boundary's admission
        # batch: free_pages alone would let every queued request pass the
        # check against the same pages, over-committing the pool and then
        # letting a small request slip past a requeued large one —
        # breaking the scheduler's strict-FIFO contract
        claimed = [0] * len(self.kvs)
        kept_budget = self.config.window_boundary_pages \
            if self.window_boundaries is not None else 0

        def can_fit(req: GenerationRequest) -> bool:
            # both kinds of page (ISSUE 27). A full-attention pool hands a
            # slot its whole length at admission: free pages decide. A
            # window slot claims and releases pages as it decodes, so what
            # decides is the most every admitted slot may come to hold:
            # kept within the pool, a step's claim cannot fail
            need = [self._pages_needed(req, kv) for kv in self.kvs]
            for k, kv in enumerate(self.kvs):
                # (the kept boundaries' budget is theirs, not the slots')
                room = kv.free_pages if not kv.config.window else \
                    kv.config.num_pages - 1 - self._window_committed[k] \
                    - kept_budget
                if claimed[k] + need[k] > room:
                    return False
            for k, n in enumerate(need):
                claimed[k] += n
            return True

        # pop-in-progress guard: next_admissions removes replays from the
        # queue BEFORE they are counted here, and the drain-owed probe
        # must never observe that window as "nothing left to finish" —
        # hold one unit of in-transit across the pop, then swap it for
        # the real count under the same lock
        with self._slot_lock:
            self._in_transit += 1
        try:
            pending = self.scheduler.next_admissions(
                free_slots, can_fit, replay_only=replay_only)
        except BaseException:
            with self._slot_lock:
                self._in_transit -= 1
            raise
        admitted = False
        with self._slot_lock:
            self._in_transit += len(pending) - 1
        try:
            for i, p in enumerate(pending):
                status = self._admit_one(p)
                with self._slot_lock:
                    self._in_transit -= 1
                admitted |= status == "ok"
                if status == "noroom":
                    # pool raced out from under the reservation (defensive
                    # — single consumer makes this unreachable today): put
                    # THIS request and everything behind it back in order
                    self.scheduler.requeue(pending[i:])
                    break
        except BaseException as exc:
            # ISSUE 18: _admit_one raising (it returns ok/failed/noroom on
            # every scheduling outcome, so this is a bug surfacing) used
            # to strand the whole popped batch — futures never resolved,
            # requests gone from the queue. Put the untouched tail back in
            # order and fail THIS request (unless _admit_one already
            # resolved it before raising), then let the error surface.
            self.scheduler.requeue(pending[i + 1:])
            if not p.future.done():
                p.future.set_exception(exc)
            raise
        finally:
            with self._slot_lock:
                self._in_transit = 0
        return admitted

    def _deadline_ctx(self, pendings: Sequence[_Pending]):
        """The ambient deadline for work done on behalf of ``pendings``:
        the tightest (submit_time + deadline_s) among them, as a
        ``resilience.deadline_scope`` (or a no-op when none carries one).
        Nested retry policies then clamp to the same monotonic instant."""
        until = [p.submit_time + p.request.deadline_s for p in pendings
                 if p.submit_time and p.request.deadline_s is not None]
        return deadline_scope(until=min(until)) if until else nullcontext()

    def _admit_one(self, pending: _Pending) -> str:
        """Admit one popped request: ``"ok"`` | ``"failed"`` (future got
        the error, nothing to requeue) | ``"noroom"`` (untouched — the
        caller must requeue it and everything behind it). A replayed
        request (``pending.replay_tokens``) re-prefills prompt + the
        tokens already generated, so the continuation is bit-identical to
        a never-faulted run."""
        req = pending.request
        prompt = req.prompt
        if pending.replay_tokens:
            prompt = np.concatenate([
                prompt, np.asarray(pending.replay_tokens, np.int32)])
        # ISSUE 17: map whatever prefix chain is resident read-only (a
        # replayed slot re-acquires its shared prefix here too, or
        # re-prefills in full if the chain was evicted), then claim
        # private pages for the rest of the request's lifetime
        claim = (self._share_prefix and self._claim_pages(req, prompt, True)) \
            or self._claim_pages(req, prompt, False)
        if claim is None:
            return "noroom"
        pages, first_page, shared, boundaries = claim
        start = shared * self.config.page_size
        rows = [kv.table_row(ids, first=lo)
                for kv, ids, lo in zip(self.kvs, pages, first_page)]
        for k, got in (boundaries[1] if boundaries else {}).items():
            rows[k][list(got)] = list(got.values())
        state_row, start_state = 0, None
        try:
            if self.state is not None:
                state_row = self.state.alloc()
                start_state = self._start_state(prompt, shared)
            with _trace.span("serving.prefill", parent=pending.trace_ctx,
                             rid=req.request_id, prompt=int(prompt.size),
                             shared_pages=shared,
                             replay=len(pending.replay_tokens)), \
                    self._deadline_ctx([pending]):
                for attempt in (0, 1):
                    try:
                        _faults.fault_point("serving.admit")
                        break
                    except Exception as exc:
                        if attempt:
                            raise exc
                        _obs.inc("serving.admit_retries_total")
                        _trace.instant("serving.fault",
                                       parent=pending.trace_ctx,
                                       rid=req.request_id,
                                       site="serving.admit", retried=True,
                                       error=type(exc).__name__)
                # a tail program for a mapped prefix, else the full one;
                # either consumes the pools and adopts what comes back
                first = self.programs.prefill(
                    _T(jnp.asarray(prompt[None, start:], jnp.int32)),
                    [_T(jnp.asarray(row)) for row in rows],
                    _T(jnp.asarray(prompt.size, jnp.int32)), start,
                    *(() if self.state is None else (
                        _T(jnp.asarray(state_row, jnp.int32)), start_state)))
                # ISSUE 18: the pool swap, first-token host read and
                # prefix publish belong to the guarded region too — the
                # host sync raising here (wedged device, watchdog replay)
                # used to leak the slot's pages AND strand the future; now
                # it is just another "failed" admission. Inside the span
                # (ISSUE 25): it ends when the first token exists, so its
                # duration is a prefill, not an enqueue
                (first_tok,), counts = first.read()
                first_tok = int(first_tok)
            now = time.monotonic()
            self._note_expert_rows(counts, "serving.moe.prefill", 1)
            _obs.inc("serving.prefills_total")
            _obs.inc("serving.prefill_tokens_requested_total",
                     float(prompt.size))
            _obs.inc("serving.prefill_tokens_computed_total",
                     float(prompt.size - start))
            if self._share_prefix:
                # publish this slot's fully-prompt pages (content now
                # frozen: decode writes land at t >= prompt_len, past
                # every published page). Over the ORIGINAL prompt only —
                # a replay's appended tokens are generated content, not a
                # shareable prompt.
                for kv, row in zip(self.kvs, rows):
                    kv.publish(req.prompt, row.tolist())
                self._keep_snapshots(req.prompt, start, first.extra)
                if boundaries:
                    self._keep_window_boundaries(req.prompt, boundaries)
                    boundaries = None
            # what only the tail prefill read: a window pool's pages below
            # the first decode step's window go now, so a slot holds at most
            # the window's pages + 2 however long its tail was
            for k, kv in enumerate(self.kvs):
                if kv.config.window:
                    pages[k], first_page[k] = self._release_below_window(
                        kv, pages[k], first_page[k], int(prompt.size))
        except Exception as exc:
            if state_row:
                self.state.free(state_row)
            if boundaries:
                for k, got in boundaries[1].items():
                    self.kvs[k].free(list(got.values()))
            self._free_pages(pages)             # refcount-aware: shared
            # pages are decremented, private ones actually released
            _obs.inc("serving.requests_total", status="failed")
            _trace.instant("serving.fault", parent=pending.trace_ctx,
                           rid=req.request_id, site="serving.admit",
                           error=type(exc).__name__)
            pending.future.set_exception(exc)
            # the pages this prefill wrote are free again, so a pool it
            # returned stays adopted as it is; one it consumed and never
            # returned takes every running slot's pages with it
            self._restore_lost_pool(exc)
            return "failed"
        slot = _Slot(pending=pending, pages=pages, first_page=first_page,
                     # a full pool's row stands for the slot's whole life;
                     # a window pool's is made before each decode step
                     rows=[None if kv.config.window else
                           self.programs.decode_row(kv, ids, 0)
                           for kv, ids in zip(self.kvs, pages)],
                     t=int(prompt.size), last_tok=first_tok,
                     tokens=list(pending.replay_tokens),
                     first_token_time=now, last_token_time=now,
                     state_row=state_row)
        # under the eviction lock: the append must be visible as one
        # event to a concurrent budgeted stop() sweeping stragglers from
        # the caller's thread (ISSUE 14: shared-state-race)
        with self._slot_lock:
            self._slots.append(slot)
            for k, kv in enumerate(self.kvs):
                if kv.config.window:
                    self._window_committed[k] += self._pages_needed(req, kv)
            self._prefill_tokens_requested += int(prompt.size)
            self._prefill_tokens_computed += int(prompt.size) - start
            late_dead = self._stop.is_set() and self._draining.is_set()
            mode = self._drain_on_timeout
        if late_dead:
            # ISSUE 15: this admission was in flight on a wedged loop
            # thread when a budgeted drain gave up and swept stragglers —
            # nobody will ever step this slot, so resolve it NOW per the
            # drain's mode (concurrent sweep is fine: _release decides
            # each slot's winner exactly once). No token was emitted yet,
            # so a requeue re-prefills bit-identically on restart.
            if mode == "requeue":
                if self._release(slot):
                    pending.replay_tokens = list(slot.tokens)
                    self.scheduler.requeue([pending])
            else:
                self._finish_error(slot, DrainTimeout(
                    f"request {req.request_id} admitted after the drain "
                    f"resolved its stragglers — evicted with "
                    f"{len(slot.tokens)} tokens"))
            return "ok"
        self._emit_token(slot, first_tok, now, first=True)
        return "ok"

    def _claim_pages(self, req: GenerationRequest, prompt: np.ndarray,
                     share: bool):
        """Claim the request's pages in every pool: ``(pages, first_page,
        shared, boundaries)`` — per pool the page ids and the logical page
        the first of them is, how many leading pages of the prompt were
        mapped read-only from the prefix index, and the window pools' pages
        this prefill writes only to keep at prefix boundaries
        (:meth:`_boundary_pages`) — or ``None`` when a pool cannot cover it
        (nothing stays claimed).

        The first pool decides how far the prefix is shared. A
        full-attention pool maps those pages and claims the rest of the
        request's lifetime. A window pool maps only what the tail prefill
        reads — the pages of the ``window`` positions before the tail —
        all of them or the prefix is not shared that far
        (:meth:`_map_window_prefix`), and claims the pages a later sharer
        of this whole prompt would read in turn and up to the prompt's
        last; its decode steps claim and release as they go
        (:meth:`_advance_window`). A logical page in between that nobody
        will read has no page: its id is 0, the scratch page."""
        ps = self.config.page_size
        size = int(prompt.size)
        mapped = [[] for _ in self.kvs]
        n = 0
        if share and self.snapshots is not None:
            # a state per slot (ISSUE 31): map the pages up to the deepest
            # boundary whose state is kept, all of them or none
            resident, n = self._shareable_pages(prompt)
            mapped[0] = self.kv.acquire_prefix(prompt, count=n) if n else []
            n = len(mapped[0])
            if resident:
                _obs.inc("serving.state.snapshot_hits_total" if n else
                         "serving.state.snapshot_misses_total")
        elif share:
            mapped[0] = self.kv.acquire_prefix(prompt)
            n = self._map_window_prefix(prompt, mapped)
            if not n:                  # a full prefill: nothing stays mapped
                self._free_pages(mapped)
                mapped = [[] for _ in self.kvs]
        pages: List[List[int]] = []
        first_page: List[int] = []
        boundaries = None
        try:
            for k, kv in enumerate(self.kvs):
                if not kv.config.window:
                    new = kv.alloc(self._pages_needed(req, kv) - n)
                    lo, gap = 0, 0
                else:
                    keep = kv.config.window_first_page((size - 1) // ps * ps)
                    lo = kv.config.window_first_page(n * ps) if mapped[k] \
                        else max(keep, n)
                    gap = max(0, keep - n) if mapped[k] else 0
                    new = kv.alloc(kv.pages_for(size) - max(keep, n))
                if new is None:
                    raise MemoryError
                pages.append(mapped[k] + [0] * gap + new)
                first_page.append(lo)
            boundaries = self._boundary_pages(req.prompt, n, pages,
                                              first_page)
        except BaseException as exc:
            # a pool REFUSING is the None return; a pool (or the sizing
            # arithmetic) RAISING must not strand what was claimed so far
            self._free_pages(pages + mapped[len(pages):])
            if isinstance(exc, MemoryError):
                return None
            raise
        return pages, first_page, n, boundaries

    def _map_window_prefix(self, prompt: np.ndarray,
                           mapped: List[List[int]]) -> int:
        """After the full pool mapped ``mapped[0]``: map every window
        pool's pages of the ``window`` positions before the shared prefix's
        end — at the full pool's match, or else at the deepest boundary
        below it whose pages are kept (:attr:`window_boundaries`), handing
        back the full pool's pages past it. Returns the pages shared, 0 if
        no window pool had them (``serving.kv.window_prefix_hits_total`` /
        ``_misses_total``, counted when the full pool matched)."""
        n = len(mapped[0])
        if not n or len(self.kvs) == 1:
            return n
        ps = self.config.page_size
        tries = [n]
        digests = []
        if self.window_boundaries is not None:
            digests = _kv.prefix_chain_digests(prompt, ps, limit=n)
            kept = self.window_boundaries.deepest(digests, n)
            if 0 < kept < n:
                tries.append(kept)
        for m in tries:
            got = []
            for kv in self.kvs[1:]:
                lo = kv.config.window_first_page(m * ps)
                ids = kv.acquire_prefix(prompt, first=lo, count=m)
                if not ids:
                    break
                got.append(ids)
            if len(got) == len(self.kvs) - 1:
                self.kv.free(mapped[0][m:])
                mapped[0] = mapped[0][:m]
                mapped[1:] = got
                if digests:                     # a use: the newest now
                    self.window_boundaries.get_parts(digests[m - 1])
                _obs.inc("serving.kv.window_prefix_hits_total")
                return m
            for kv, ids in zip(self.kvs[1:], got):
                kv.free(ids)
        _obs.inc("serving.kv.window_prefix_misses_total")
        return 0

    def _boundary_pages(self, prompt: np.ndarray, n: int,
                        pages: List[List[int]], first_page: List[int]):
        """The boundaries a prefill from page ``n`` of ``prompt`` keeps for
        later sharers, every ``window_boundary_tokens`` up to the last page
        a sharer could map, none kept already, the deepest ones that the
        budget holds — and per window pool the pages it claims for them
        beyond the request's own (``{logical page: id}``): the prefill
        writes them, :meth:`_keep_window_boundaries` hands them over.
        ``None``: nothing to keep."""
        store = self.window_boundaries
        if store is None or not self._share_prefix:
            return None
        ps = self.config.page_size
        every = self.config.window_boundary_tokens // ps
        last = (int(prompt.size) - 1) // ps
        digests = _kv.prefix_chain_digests(prompt, ps, limit=last)
        wins = [k for k, kv in enumerate(self.kvs) if kv.config.window]
        span = {b: [self.kvs[k].config.window_first_page(b * ps)
                    for k in wins]
                for b in range((n // every + 1) * every, last + 1, every)
                if digests[b - 1] not in store}
        bounds, size = [], 0
        for b in sorted(span, reverse=True):    # the deepest first
            cost = sum(b - lo for lo in span[b])
            if size + cost > store.budget:
                break
            bounds.insert(0, b)
            size += cost
        if not bounds:
            return None
        store.make_room(size)
        extra = {}
        for j, k in enumerate(wins):
            kv, have = self.kvs[k], first_page[k]
            own = {have + i for i, p in enumerate(pages[k]) if p}
            want = sorted({lp for b in bounds
                           for lp in range(span[b][j], b)} - own)
            ids = kv.alloc(len(want))
            if ids is None:                      # kept within the pool by
                for kk, got in extra.items():    # the budget: defensive
                    self.kvs[kk].free(list(got.values()))
                return None
            extra[k] = dict(zip(want, ids))
        return bounds, extra

    def _keep_window_boundaries(self, prompt: np.ndarray, boundaries) -> None:
        """After the prefill wrote them and its pages were published: file
        each boundary's window pages under the chain digest of its last
        page, one claim each of the store's own, and give back the
        request's claims on the pages it took only for them."""
        bounds, extra = boundaries
        ps = self.config.page_size
        digests = _kv.prefix_chain_digests(prompt, ps, limit=bounds[-1])
        wins = [kv for kv in self.kvs if kv.config.window]
        with _trace.span("serving.kv.window_keep",
                         pages=sum(len(e) for e in extra.values())):
            for b in bounds:
                got = []
                try:
                    for kv in wins:
                        got.append(kv.acquire_prefix(
                            prompt, first=kv.config.window_first_page(b * ps),
                            count=b, quiet=True))
                    filed = all(got) and self.window_boundaries.put_parts(
                        digests[b - 1], tuple(got))
                except BaseException:
                    for kv, ids in zip(wins, got):
                        kv.free(ids)
                    raise
                if not filed:
                    for kv, ids in zip(wins, got):
                        kv.free(ids)
            for k, got in extra.items():
                self.kvs[k].free(list(got.values()))
        self._note_boundary_pages()

    def _give_back_boundary(self, parts) -> None:
        """Release the store's claims on one boundary's window pages."""
        for kv, ids in zip([kv for kv in self.kvs if kv.config.window],
                           parts):
            kv.free(ids)

    def _note_boundary_pages(self) -> None:
        """Feed ``serving.kv.window_boundary_pages_high_water``: the window
        pages held only for later sharers — kept at a boundary, read by no
        live slot (one claim: the store's)."""
        store = self.window_boundaries
        if store is None:
            return
        wins = [kv for kv in self.kvs if kv.config.window]
        held = sum(kv.sole_claims(ids) for parts in store.values()
                   for kv, ids in zip(wins, parts))
        with self._slot_lock:               # stop() may step from its thread
            risen = held > self._boundary_high_water
            if risen:
                self._boundary_high_water = held
        if risen:
            _obs.set_gauge("serving.kv.window_boundary_pages_high_water",
                           float(held))

    def _start_state(self, prompt: np.ndarray, shared: int):
        """The state an admission that mapped ``shared`` prefix pages
        starts its prefill from: the snapshot kept at that boundary
        (``None``: from zero)."""
        if not shared:
            return None
        with _trace.span("serving.state.restore", pages=shared,
                         bytes=self.state.row_bytes):
            digest = _kv.prefix_chain_digests(
                prompt, self.config.page_size, limit=shared)[-1]
            kept = self.snapshots.get_parts(digest)
        if kept is None:                    # evicted since the page claim:
            raise RuntimeError(             # the one step thread rules it out
                "state snapshot vanished between the page claim and the "
                "prefill")
        return tuple(_T(a) for a in kept)

    def _keep_snapshots(self, prompt: np.ndarray, start: int, kept) -> None:
        """File the states a prefill from ``start`` kept (``kept``: per part
        of the state ``(n, ...)``), one every ``state_snapshot_tokens``,
        under the chain digests of the ORIGINAL prompt's pages at those
        boundaries (a replay's appended tokens are generated content, not a
        shareable prompt). The parts of one boundary go under one digest."""
        n = int(kept[0].shape[0]) if kept else 0
        if not n:
            return
        ps, every = self.config.page_size, self.config.state_snapshot_tokens
        digests = _kv.prefix_chain_digests(prompt, ps)
        with _trace.span("serving.state.snapshot", states=n,
                         bytes=n * self.state.row_bytes):
            for i in range(n):
                pages = (start + (i + 1) * every) // ps
                if pages <= len(digests):
                    self.snapshots.put_parts(
                        digests[pages - 1],
                        tuple(part._data[i] for part in kept))

    def _free_pages(self, pages: List[List[int]]) -> None:
        """Release one claim on every page of a per-pool list of ids (0 is
        a logical page that never had one)."""
        for kv, ids in zip(self.kvs, pages):
            kv.free([p for p in ids if p])

    def _advance_window(self, slot: _Slot, t: int) -> None:
        """Before a decode step of ``slot`` at position ``t``: in every
        window pool, claim the page the step writes if the slot has not
        got it yet, then release the pages that fell out of every window
        layer's reach (ISSUE 27). Claim first: the most a slot holds is
        the window's pages + 2, which admission kept room for. The step
        before may still be running and reading a page released here: see
        the module docstring on what is in flight."""
        ps = self.config.page_size
        for k, kv in enumerate(self.kvs):
            if not kv.config.window:
                continue
            ids = slot.pages[k]
            changed = grown = slot.rows[k] is None
            while slot.first_page[k] + len(ids) <= t // ps:
                new = kv.alloc(1)
                slot.pages[k] = ids = ids + (new or [])   # the slot's now
                if new is None:
                    raise RuntimeError(
                        f"window pool {kv.config.kind!r} has no page for "
                        f"request {slot.request.request_id} at position "
                        f"{t}: admission over-committed it")
                changed = grown = True
            if grown:                       # the most is just after a claim
                held = sum(1 for p in ids if p)
                with self._slot_lock:       # stop() may step from its thread
                    risen = held > self._window_high_water
                    if risen:
                        self._window_high_water = held
                if risen:
                    _obs.set_gauge(
                        "serving.kv.window_pages_per_slot_high_water",
                        float(held))
            ids, first = self._release_below_window(
                kv, ids, slot.first_page[k], t)
            if first != slot.first_page[k]:
                slot.pages[k], slot.first_page[k] = ids, first
                changed = True
            if changed:
                slot.rows[k] = self.programs.decode_row(
                    kv, ids, slot.first_page[k])

    @staticmethod
    def _release_below_window(kv, ids: List[int], first: int, t: int):
        """Release what of a window pool's ``ids`` (logical page ``first``
        on) no window layer reads from position ``t`` on -> ``(ids left,
        their first logical page)``."""
        drop = kv.config.window_first_page(t) - first
        if drop <= 0:
            return ids, first
        gone = [p for p in ids[:drop] if p]
        kv.free(gone)
        _obs.inc("serving.kv.window_pages_released_total", float(len(gone)))
        return ids[drop:], first + drop

    def _note_expert_rows(self, counts: np.ndarray, event: str,
                          batch: int) -> None:
        """Book what an expert layer counted on the device and the step's
        one read-back brought along (ISSUE 27): ``counts`` is flat
        ``(layers, experts held)``, the (token, expert) pairs each held
        expert computed. Empty for a model without one. On the step's path
        this is one vector add; the ``serving.moe.*`` counters are fed from
        the sum in :meth:`_publish_expert_rows`."""
        if not counts.size:
            return
        with self._slot_lock:               # stop() may step from its thread
            if self._expert_rows is None:
                self._expert_rows = np.zeros(counts.shape, np.int64)
            self._expert_rows += counts
            self._expert_touches += int(np.count_nonzero(counts))
        if _trace.mode() == "on":           # the instant exists there only
            _trace.phase_instant(
                event, parent=self._engine_trace, rows=int(counts.sum()),
                experts_touched=int(np.count_nonzero(counts)),
                experts_held=int(counts.size), batch=batch)

    def _publish_expert_rows(self, now: float, every_s: float) -> None:
        """Feed ``serving.moe.rows_total``, ``.experts_touched_total`` and
        ``.rows_by_expert_total{layer,expert}`` from what the steps since
        the last call summed — at most every ``every_s`` seconds, so up to
        64 labelled increments are not a cost of each decode step."""
        with self._slot_lock:               # stop() may step from its thread
            if not self._expert_touches or \
                    now - self._expert_rows_at < every_s:
                return
            self._expert_rows_at = now
            rows, touched = self._expert_rows.copy(), self._expert_touches
            self._expert_rows[:] = 0
            self._expert_touches = 0
        _obs.inc("serving.moe.rows_total", float(rows.sum()))
        _obs.inc("serving.moe.experts_touched_total", float(touched))
        per_layer = rows.reshape(self.config.num_layers, -1)
        for layer, expert in zip(*np.nonzero(per_layer)):
            _obs.inc("serving.moe.rows_by_expert_total",
                     float(per_layer[layer, expert]), layer=int(layer),
                     expert=int(expert))

    def _fault_gate(self) -> List[_Slot]:
        """The per-slot ``serving.step`` seam, in admission order. A
        faulted slot sits this step out; everyone else proceeds. A slot
        whose token in flight is its last — the host can count
        ``max_new_tokens`` and ``max_len`` ahead of the read — is not in
        the next step and passes no seam, as if it had finished already."""
        included: List[_Slot] = []
        for slot in list(self._slots):
            if len(slot.tokens) + slot.ahead >= \
                    slot.request.max_new_tokens or \
                    slot.t + slot.ahead >= self.config.max_len:
                continue
            try:
                _faults.fault_point("serving.step")
            except Exception as exc:
                slot.faults += 1
                if slot.faults > 1:
                    self._finish_error(slot, exc)
                else:
                    _obs.inc("serving.step_retries_total")
                    _trace.instant("serving.fault",
                                   parent=slot.pending.trace_ctx,
                                   rid=slot.request.request_id,
                                   site="serving.step", retried=True,
                                   error=type(exc).__name__)
                continue
            included.append(slot)
        return included

    def _bucket_for(self, n: int) -> int:
        for b in self.config.buckets:
            if b >= n:
                return b
        raise AssertionError(f"no bucket for batch {n}")  # __post_init__

    def _decode(self, included: List[_Slot]) -> bool:
        """One boundary of the decode pipe (ISSUE 28): launch the next
        step for ``included``, THEN read the step launched a boundary
        earlier — so the device runs the one while the host emits the
        other, admits, and builds the one after. With nothing in flight
        the launch stands alone (the pipe's first boundary: its phases
        ride the engine's track); with nobody to launch for, the read
        does, and leaves nothing in flight. ``serving.decode`` is the
        span of the step READ here. False: there was neither."""
        with self._slot_lock:
            prev = self._flight
        if prev is None:
            if included:
                self._launch(included, None)
            return bool(included)
        with _trace.span("serving.decode", parent=self._engine_trace,
                         batch=len(prev.included), ahead=prev.ahead):
            if included:
                if not self._launch(included, prev):
                    return True     # prev was abandoned with the launch
            else:
                with self._slot_lock:
                    self._flight = None
            self._land(prev)
        return True

    def _launch(self, included: List[_Slot], prev: Optional[_Flight]
                ) -> bool:
        """Build and launch one decode step; it becomes the step in flight.
        A row that is in ``prev``, still unread, takes its input token
        from ``prev``'s output on the device; every other row takes the
        host's ``last_tok``. Positions and tables are functions of host
        state known before ``prev``'s tokens are. False: the launch failed
        for good (a second device fault, a watchdog trip, a lost pool) and
        every slot concerned was recovered, ``prev`` abandoned with it."""
        # inside the span of the step read at this boundary, or bare on the
        # engine's track when there is none
        parent = self._engine_trace if prev is None else None
        with _trace.phase("serving.decode.build", parent=parent):
            bucket = self._bucket_for(len(included))
            tok = np.zeros((bucket, 1), np.int32)
            sel = np.full((bucket,), -1, np.int32)
            t = np.zeros((bucket,), np.int32)
            # one table per pool; padded rows -> scratch
            tables = [np.zeros((bucket, self.programs.table_width(kv, True)),
                               np.int32) for kv in self.kvs]
            row_of = {id(s): i for i, s in enumerate(prev.included)} \
                if prev is not None else {}
            state_rows = np.zeros((bucket,), np.int32)
            for i, slot in enumerate(included):
                if slot.ahead:              # its token is on the device
                    sel[i] = row_of[id(slot)]
                else:
                    tok[i, 0] = slot.last_tok
                t[i] = slot.t + slot.ahead
                state_rows[i] = slot.state_row
                self._advance_window(slot, int(t[i]))
                for k, table in enumerate(tables):
                    table[i] = slot.rows[k]
            built = (_T(jnp.asarray(tok)),
                     [_T(jnp.asarray(tb)) for tb in tables],
                     _T(jnp.asarray(t)),
                     prev.step.carry if prev is not None
                     else self.programs.no_carry, _T(jnp.asarray(sel)))
            if self.state is not None:
                built += (_T(jnp.asarray(state_rows)),)
        with self._deadline_ctx([s.pending for s in included]):
            for attempt in (0, 1):
                gen = self._watchdog.arm() if self._watchdog else None
                try:
                    # the device-step seam: delay = hung step (trips the
                    # watchdog), error = whole-batch device fault
                    with _trace.phase("serving.decode.launch",
                                      parent=parent):
                        _faults.fault_point("serving.watchdog")
                        step = self.programs.decode(*built)
                except Exception as exc:
                    if gen is not None:
                        self._watchdog.disarm(gen)
                    # a whole-batch device fault. A call that raised
                    # before it ran (an injected fault, a trace failure)
                    # left the pool as it was: retry the identical step
                    # once, then recover the slots through bounded replay.
                    # One that consumed the pool and raised took every
                    # resident page with it: fresh pool, every running
                    # slot replayed — there is nothing to retry against
                    if self._restore_lost_pool(exc):
                        return False
                    if attempt:
                        self._recover_slots(included, exc)
                        return False
                    _obs.inc("serving.step_retries_total")
                    continue
                verdict = self._watchdog.disarm(gen) if gen is not None \
                    else None
                if verdict is not None:
                    # tripped step: its pool is already adopted (the call
                    # consumed the old one), only its tokens are
                    # abandoned, and those of the step before it. Sound
                    # because a step writes its own position of the
                    # included slots' own pages and nothing else (never a
                    # shared prefix page; padded rows write the scratch
                    # page), slot.t did not advance, and the replay's
                    # re-prefill rewrites those pages anyway
                    self._recover_slots(included, WatchdogTimeout(
                        f"decode step classified {verdict} by the "
                        f"watchdog (budget "
                        f"{self._watchdog.timeout_s:.3f}s)"))
                    return False
                break
        for slot in included:
            slot.ahead += 1
        with self._slot_lock:
            self._flight = _Flight(included, bucket, int(prev is not None),
                                   built, step)
        return True

    def _land(self, flight: _Flight) -> None:
        """Read a launched step's tokens and emit them. A row whose slot
        has gone since the launch — it ended at the step before by
        ``eos_token_id`` or a raising callback, was cancelled, or a
        budgeted stop() resolved it while the loop was wedged — is
        discarded: never streamed, never counted, and what its step wrote
        is position ``t`` of pages that were the slot's own."""
        with _trace.phase("serving.decode.wait"):
            # the ONE host sync: the tokens, and behind them whatever the
            # model counted on the device (an expert layer's rows)
            next_np, counts = flight.step.read()
        now = time.monotonic()
        # what the model counted, by mechanism: a model with sparse pages
        # counts pages held and pages attended (per KV head and sparse
        # layer), any other an expert layer's rows
        if self.index is None:
            self._note_expert_rows(counts, "serving.moe.decode",
                                   len(flight.included))
        elif counts.size and _trace.mode() == "on":
            _trace.phase_instant(
                "serving.sparse.decode", parent=self._engine_trace,
                rows=len(flight.included),
                pages_resident=int(counts[0]), pages_read=int(counts[1]))
        if self.state is not None and _trace.mode() == "on":
            _trace.phase_instant(
                "serving.linear.decode", parent=self._engine_trace,
                rows=len(flight.included), layers=self.state.layers)
        _obs.inc("serving.steps_total")
        if flight.ahead:
            _obs.inc("serving.decode_ahead_steps_total")
        # which decode tier actually ran (ISSUE 13): the bench's
        # all-dense-on-TPU suspect rule reads this split
        _obs.inc("serving.paged_attention_steps_total",
                 path=self._paged_path)
        with _trace.phase("serving.decode.emit"):
            with self._slot_lock:
                live = {id(s) for s in self._slots}
            discarded = 0
            for i, slot in enumerate(flight.included):
                if id(slot) not in live:
                    discarded += 1
                    continue
                slot.ahead -= 1
                slot.t += 1
                self._emit_token(slot, int(next_np[i]), now)
            if discarded:
                _obs.inc("serving.decode_discarded_rows_total",
                         float(discarded))
        with _trace.phase("serving.decode.release"):
            # the step's device arrays (its inputs, the token output) die
            # here, not with the object: on the chip freeing them takes
            # about a millisecond, and it should carry a name
            flight.built = flight.step = None

    def _abandon_flight(self) -> List[_Slot]:
        """Drop the step in flight unread: its tokens are abandoned (the
        pool it returned stays adopted). Returns its slots, which the
        caller recovers: their ``tokens`` hold only what was emitted."""
        with self._slot_lock:
            flight, self._flight = self._flight, None
        if flight is None:
            return []
        for slot in flight.included:
            slot.ahead = 0
        return flight.included

    def _emit_token(self, slot: _Slot, token: int, now: float,
                    first: bool = False) -> None:
        req = slot.request
        slot.tokens.append(token)
        slot.last_tok = token
        _obs.inc("serving.tokens_total")
        if first:
            # a replay's re-prefill also lands here; TTFT is observed
            # only for the request's true first token
            sub = slot.pending.submit_time
            if sub and not slot.pending.ttft_done:
                _obs.observe("serving.ttft_seconds", now - sub)
            slot.pending.ttft_done = True
        else:
            _obs.observe("serving.tpot_seconds", now - slot.last_token_time)
        slot.last_token_time = now
        if req.stream is not None:
            try:
                req.stream(req.request_id, token)
            except Exception as exc:
                # the documented contract: a raising callback is the
                # REQUEST's failure, never its batchmates' — without this
                # catch it would unwind the whole step loop (and silently
                # kill the start() thread), stranding every in-flight
                # future with its pages leaked
                self._finish_error(slot, exc)
                return
        if req.eos_token_id is not None and token == req.eos_token_id:
            self._finish(slot, "eos")
        elif len(slot.tokens) >= req.max_new_tokens:
            self._finish(slot, "length")
        elif slot.t >= self.config.max_len:
            self._finish(slot, "length")   # cache exhausted (validated
            # at submit, reachable only with adversarial max_len configs)

    def _release(self, slot: _Slot) -> bool:
        """Evict ``slot`` and return its pages. Returns False when the
        slot was already released — the one way that happens is a wedged
        step returning AFTER a budgeted stop() resolved the stragglers
        without it; the late return must not double-free pages or
        re-resolve a settled Future."""
        with self._slot_lock:
            if slot not in self._slots:
                return False
            self._slots.remove(slot)
            for k, kv in enumerate(self.kvs):
                if kv.config.window:
                    self._window_committed[k] -= self._pages_needed(
                        slot.request, kv)
        self._free_pages(slot.pages)
        if slot.state_row:
            self.state.free(slot.state_row)
        self._note_boundary_pages()
        return True

    def _finish(self, slot: _Slot, reason: str) -> None:
        if not self._release(slot):
            return
        _obs.inc("serving.requests_total", status=(
            "completed" if reason in ("eos", "length") else reason))
        _trace.instant("serving.complete", parent=slot.pending.trace_ctx,
                       rid=slot.request.request_id, reason=reason,
                       tokens=len(slot.tokens))
        n = len(slot.tokens)
        tpot = ((slot.last_token_time - slot.first_token_time) / (n - 1)
                if n > 1 else None)
        slot.pending.future.set_result(GenerationResult(
            slot.request.request_id, slot.tokens, reason,
            ttft_s=(slot.first_token_time - slot.pending.submit_time
                    if slot.pending.submit_time else None),
            tpot_s=tpot))

    def _finish_error(self, slot: _Slot, exc: BaseException) -> None:
        if not self._release(slot):
            return
        _obs.inc("serving.requests_total", status="failed")
        # the chaos-suite invariant: a faulted request's trace always
        # carries the fault event, whatever path resolved it
        _trace.instant("serving.fault", parent=slot.pending.trace_ctx,
                       rid=slot.request.request_id,
                       error=type(exc).__name__)
        slot.pending.future.set_exception(exc)

    def _recover_slots(self, included: List[_Slot],
                       exc: BaseException) -> None:
        """Crash-recovery for an unrecoverable batched step (device fault
        after the retry, or a watchdog trip): every included slot is
        evicted with its pages reclaimed, and — replay budget permitting —
        requeued AT THE QUEUE HEAD with bounded prefill replay (prompt +
        tokens generated so far), so the continuation is bit-identical and
        batchmates no longer share one slot's fate. Past ``max_replays``
        the slot's Future gets ``exc``."""
        requeue: List[_Pending] = []
        # the tokens of a step still in flight are abandoned with this
        # one's (ISSUE 28), so its slots are recovered too — in admission
        # order, as they are requeued
        flown = self._abandon_flight()
        included = [s for s in list(self._slots)
                    if s in included or s in flown]
        # post-mortem first: the flight ring's tail already carries the
        # fault/trip events that got us here — snapshot it to disk before
        # recovery mutates anything (ISSUE 12: crash-recovery dump site)
        _trace.record("serving.recover", error=type(exc).__name__,
                      slots=len(included))
        _trace.flight_dump("serving_recover", error=type(exc).__name__,
                           slots=len(included))
        # cover the eviction->requeue gap for the drain-owed probe: these
        # slots leave _slots before their requeue lands in the queue
        with self._slot_lock:
            self._in_transit += len(included)
        try:
            for slot in list(included):
                pend = slot.pending
                if pend.replays >= self.config.max_replays:
                    self._finish_error(slot, exc)
                    continue
                if not self._release(slot):
                    # already resolved by a budgeted stop() that gave up
                    # on this wedged step: requeuing would re-decode a
                    # settled Future and set_result would raise
                    continue
                pend.replays += 1
                pend.replay_tokens = list(slot.tokens)
                _obs.inc("serving.replays_total")
                _trace.instant("serving.replay", parent=pend.trace_ctx,
                               rid=pend.request.request_id,
                               replays=pend.replays,
                               error=type(exc).__name__)
                requeue.append(pend)
            if requeue:
                self.scheduler.requeue(requeue)
                self._wake.set()
        finally:
            with self._slot_lock:
                self._in_transit -= len(included)

    def _publish_gauges(self, active: int, bucket: int) -> None:
        # at once when the last slot has gone: nothing may follow
        self._publish_expert_rows(time.monotonic(),
                                  0.25 if self._slots else 0.0)
        _obs.set_gauge("serving.active_slots", len(self._slots))
        _obs.set_gauge("serving.batch_utilization",
                       active / bucket if bucket else 0.0)
