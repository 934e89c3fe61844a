"""End-to-end tracing + crash flight recorder (ISSUE 12).

Three layers, one event envelope (``{"ts", "kind", "name", "attrs"}`` —
span events additionally carry ``trace``/``span``/``parent`` ids):

* **Spans** — ``with trace.span("serving.prefill", parent=ctx, rid=7):``
  opens one node of a span tree. Context propagates thread-locally (a
  nested ``span()`` on the same thread becomes a child automatically) and
  across threads explicitly: ``new_trace(label)`` mints a
  :class:`SpanContext` root that travels with the work item (the serving
  scheduler carries one per request, so a request's trace follows it from
  ``submit()`` on the caller thread through the engine step thread), and
  any ``span(..., parent=ctx)`` attaches to it. ``instant(...)`` records a
  point event into the same tree. Span begin/end pairing is structural —
  spans exist ONLY as context managers (enforced by the
  ``span-discipline`` lint rule), so every start has exactly one end on
  every exit path, including exceptions and simulated kills.
* **The trace buffer** — with ``PADDLE_TPU_TRACE=on`` every span/instant
  (plus per-op dispatch events via ``core.tensor._op_trace_hook``) lands
  in an in-process buffer; :func:`export_chrome` converts it to a Chrome
  trace-event JSON that loads in ``chrome://tracing`` / Perfetto (one
  track per trace, spans nested by time containment).
* **The profiler mirror** (ISSUE 25) — in mode ``on`` every span is also
  entered and left as a ``jax.profiler.TraceAnnotation`` of the same name
  (attributes that are numbers or short strings ride along as the event's
  stats). While a ``jax.profiler`` session runs, the program's spans
  therefore land in the host plane of the same ``.xplane.pb`` as the
  device's ops (plane ``/host:CPU``, one line per OS thread, the event
  named by the span), in the profiler's own timebase: a device idle gap
  can be set against what the host thread was doing in it. With no
  session running an annotation is a no-op in native code.
  ``jax.profiler`` is imported when the first span opens in mode ``on``,
  never at import (observability is a foundation layer).
* **Phase spans** — :func:`phase` / :func:`phase_instant` are the
  detail tier: recorded in mode ``on`` only and never into the flight
  ring (as the per-op events are not), so the per-step phases of the
  serving loop and the compiled call cannot churn the post-mortem's tail
  and mode ``flight`` keeps what it kept before them.
  :func:`phase_done` is the same tier for work that is only known once it
  is over (ISSUE 36): it writes the begin and the end of a span that ended
  now and began ``seconds`` ago, in one call, with no profiler mirror.
* **The flight recorder** — an ALWAYS-ON lock-free ring of the last N
  events (``PADDLE_TPU_FLIGHT_EVENTS``, default 512): lifecycle instants,
  injected/real fault events, watchdog trips, NaN skips, restores. On an
  abort path (``TrainAborted``, a watchdog trip, engine crash-recovery,
  an unhandled supervisor exit) :func:`flight_dump` snapshots the ring to
  a JSON file under ``PADDLE_TPU_TRACE_DIR`` — the post-mortem is on disk
  before the process is gone.

Overhead contract (the ``_op_metrics_hook`` discipline): with tracing off
(the default) ``span()`` is one global read returning a shared no-op
context manager, the per-op dispatch seam stays at its is-None probe, and
only explicit ``instant``/``record`` calls (request/step-rate lifecycle
sites, never per-op) pay one dict build + one ring slot write for the
always-on recorder. ``bench.py`` pins the captured-step p50 delta of
``off`` vs ``flight`` vs ``on`` in its ``trace_overhead`` block.

The spans the program opens (ISSUE 25 fixed the names; ``*`` = phase
span, mode ``on`` only)::

    serving.submit            Engine.submit (caller thread; request track)
    serving.prefill           _admit_one: page claim done -> first token read
    serving.decode            the decode step READ at this boundary (engine
                              track; ISSUE 28): ``batch`` = its rows,
                              ``ahead`` = 1 if its program was launched
                              before the step ahead of it was read. One per
                              step read; its build/launch are the NEXT
                              step's (absent when nobody continues), and the
                              pipe's first build/launch ride the track bare
      serving.decode.build  * numpy batch + the host->device puts of the
                              step launched here
      serving.decode.launch * that step's program call until it returns
      serving.decode.wait   * the one host read of the tokens of the step
                              launched a boundary EARLIER (it ran meanwhile)
      serving.decode.emit   * its per-slot bookkeeping, stream callbacks,
                              finish; rows of requests that ended meanwhile
                              are discarded here
      serving.decode.release * its device arrays freed (inputs, tokens)
    serving.cancel          * Engine.step: evict cancelled slots
    serving.admit           * Engine.step: scheduler pop, page reservation,
                              every prefill of this boundary, the fault gate
    serving.publish         * Engine.step: gauges
    serving.idle            * Engine.start's loop: waiting for work
    serving.http.token      * (instant) front door: one per streamed token,
                              ``lag_ms`` = engine hand-over -> frame flushed
    serving.moe.decode      * (instant) after a decode step's read-back, for
                              a model with an expert layer (ISSUE 27):
                              ``rows`` = (token, expert) pairs the experts
                              held here computed, all layers;
                              ``experts_touched`` = held experts with a row,
                              summed over layers; ``experts_held`` = experts
                              held x expert layers (ISSUE 33: what
                              ``experts_touched`` is a share of); ``batch`` =
                              live rows
    serving.moe.prefill     * (instant) the same after a prefill's read-back
    serving.sparse.decode   * (instant) after a decode step's read-back, for
                              a model that chooses the pages it attends
                              (ISSUE 31): ``rows`` = live rows,
                              ``pages_resident`` = pages those rows hold,
                              ``pages_read`` = pages they attend (the
                              selection's chosen pages with a position to
                              read), both summed over rows, KV heads and
                              sparse layers ON THE DEVICE by the decode
                              program and read back behind its tokens
    serving.linear.decode   * (instant) a decode step's state updates, for
                              a model that keeps a state per slot (lightning
                              or Gated DeltaNet layers): ``rows`` live rows x
                              ``layers`` such layers, one state (every part
                              of it) read and written each
    serving.state.snapshot    (span) an admission files the states its
                              prefill kept at snapshot boundaries
                              (``states`` of them, ``bytes`` in all: every
                              part of a state goes under the one digest)
    serving.state.restore     (span) an admission that mapped ``pages``
                              prefix pages fetches the state kept at that
                              boundary (``bytes``: all its parts), to start
                              its tail prefill from
    serving.kv.window_keep    (span) an admission hands the window pages its
                              prefill wrote at prefix boundaries to the
                              engine's store of them (``pages``: those it
                              claimed for that alone), one claim each
    serving.warmup            Engine.warmup's body (``replica``,
                              ``programs`` = buckets + lengths + tails);
                              holds one jit.call a program it compiles
    jit.call                * StaticFunction: one whole compiled call
      jit.dispatch          * the jitted function alone (``program`` = the
                              function's ``cost_label``, else its name);
                              ``jit.call``'s self time is hooks + registry
                              walk + key + rebind
        jit.trace           * (ISSUE 36; these three are written when they
        jit.lower           *  END, by ``phase_done``, from jax's own
        jit.compile         *  monitoring events: ``compile_events.py``) the
                              trace to a jaxpr, the lowering to an MLIR
                              module, and the backend compile — an XLA
                              compile or a load from the persistent cache,
                              whichever the call paid. Whose program: the
                              ``program`` of the ``jit.dispatch`` begin that
                              ``parent`` names; ``fun`` is jax's name for
                              what was traced; on ``jit.compile`` also
                              ``cache_hit`` (1 loaded, 0 compiled and
                              written, absent: the cache was not asked) and
                              ``load_s``. Only events of a millisecond and
                              more. A jitted function traced inside a trace
                              (a kernel body, a ``jax.numpy`` helper) is a
                              child by TIME, a sibling by ``parent``: sum a
                              phase as the union of its intervals. They
                              appear wherever jax compiles: under
                              ``serving.warmup``, under a ``serving.prefill``
                              that met a new tail in mid-traffic, under
                              ``train.captured_step``
    train.step / train.captured_step   the supervisor's / CapturedStep's

Beside them set-up counts, tracing on or off (ISSUE 36; ``observability``
registry, fed by the same listener once ``observability.enable()`` has run):
``jit.compile_seconds_total{phase="trace"|"lower"|"compile"}`` (nested events
counted once, so the three sum to wall time),
``jit.persistent_cache_hits_total`` and ``jit.persistent_cache_misses_total``
(both on the scrape at 0 from ``enable()``). ``jit.compiles_total`` stays
what it was: fresh ``StaticFunction`` builds, one per cache entry, not one
per XLA compile.

Beside them the decode pipe counts (ISSUE 28; ``observability`` registry):
``serving.decode_ahead_steps_total`` against ``serving.steps_total`` is the
share of steps launched ahead, without a trace;
``serving.decode_discarded_rows_total`` the rows computed for a request that
had already ended (known only after the read, so not a span attribute).

Beside them the decode program says which paged-attention kernel its layers
took (ISSUE 35; ``observability`` registry): gauge
``serving.paged_attention_row_walk_layers``, set as a decode program is
traced (``ops/paged_attention.py::commit_pending``) — the attention layers
of that program whose call took the grouped kernel that walks live rows and
live pages only (8 query heads to a KV head and more: 4 for one period of
Command A+, 1 per period of Qwen3-Next, 0 for Mistral at 4 to 1 and for
MiniCPM-SALA, whose sparse layers run their own kernel). Beside it, set at
the same point (ISSUE 37): gauge ``serving.sparse_attention_row_walk_layers``
— the sparse layers of that program whose call took the kernel that walks a
live row's chosen pages only and not the dense tier (2 for the MiniCPM-SALA
stage the benchmark runs, 0 for a model without sparse pages).

Beside them the state snapshots count (ISSUE 31; ``observability``
registry): counters ``serving.state.snapshot_hits_total`` /
``serving.state.snapshot_misses_total`` (admissions whose prompt's first
pages were resident and that did / did not find a state snapshot to start
from; a first ask counts as neither) and
``serving.state.snapshot_evictions_total``; gauges
``serving.state.snapshot_bytes`` and ``serving.state.row_bytes`` (ISSUE 33:
what one slot's state holds over all its parts and layers, set when the
engine is built).

Beside them the expert layer and the pages by layer kind count (ISSUE 27;
``observability`` registry, not events): counters ``serving.moe.rows_total``,
``serving.moe.experts_touched_total``,
``serving.moe.rows_by_expert_total{layer,expert}`` (``expert`` numbered
within the held ones; the three are fed from the engine's running sum at
most four times a second and when the last slot goes, not every step) and ``serving.kv.window_pages_released_total``; gauges
``serving.kv.pages_in_use_by_kind{kind}`` and
``serving.kv.window_pages_per_slot_high_water`` (the most window-kind pages
one slot has held at once: window / page + 2 when pages leave with the
window). The window pages kept at prefix boundaries count there too:
counters ``serving.kv.window_prefix_hits_total`` /
``serving.kv.window_prefix_misses_total`` (admissions whose prompt's first
pages the full-attention pool held, and whose window pools did / did not
hold the window before the shared prefix's end — the first asker's own
pages, or those kept at a boundary) and
``serving.kv.window_boundary_evictions_total``; gauges
``serving.kv.window_boundary_pages`` (what the store keeps) and
``serving.kv.window_boundary_pages_high_water`` (the most window pages held
at once only for later sharers: kept, and read by no live slot). The model's ``jax.named_scope``s ``attn_window``, ``attn_full``,
``moe_route``, ``moe_experts`` and ``moe_shared`` name its ops in the HLO's
metadata and in the profiler's own viewer; an ``.xplane.pb`` read through
``jax.profiler.ProfileData`` carries an op's instruction name only.

Clocks: an event's ``ts`` is ``time.perf_counter()``. The benchmark cuts
its window with ``time.monotonic()`` and compares the two directly; they
are the same clock (``CLOCK_MONOTONIC``) on Linux by CPython's
implementation only, not by the language's contract. The profiler's
timebase is its own: compare a span with a device op through the
mirrored annotation, never through ``ts``.

Health beacons ride along (``heartbeat(name)`` from the engine/supervisor
step loops and the watchdog poll threads); ``observability.http`` serves
them at ``/healthz`` next to ``/metrics`` and ``/debug/flight``.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

__all__ = [
    "SpanContext", "FlightRecorder",
    "span", "phase", "phase_done", "instant", "phase_instant", "record",
    "new_trace", "current",
    "mode", "enabled", "set_mode", "tracing",
    "events", "clear", "dropped", "make_event", "span_problems",
    "export_chrome", "trace_dir",
    "flight_recorder", "flight_dump",
    "heartbeat", "heartbeat_clear", "health", "beacon_detail",
]

_log = logging.getLogger(__name__)

_VALID_MODES = ("off", "on", "flight")

# soft cap on the "on"-mode buffer: tracing a runaway loop must degrade to
# dropped-event accounting, not an OOM
_BUFFER_CAP = 500_000
# cap on remembered track labels (export metadata only): a long-running
# engine mints one trace per request, and the label map must not become
# the leak the buffer cap exists to prevent
_TRACKS_CAP = 50_000

_DEFAULT_FLIGHT_EVENTS = 512
_DEFAULT_HEARTBEAT_TTL_S = 60.0


def _env_mode() -> str:
    raw = os.environ.get("PADDLE_TPU_TRACE", "").strip().lower()
    if raw in ("", "0", "false", "no", "off", "disable", "disabled"):
        return "off"
    if raw == "flight":
        return "flight"
    if raw in ("1", "true", "yes", "on"):
        return "on"
    # an unrecognized value must NOT silently enable the most expensive
    # tier (a typo of "flight" would otherwise install the per-op hook
    # and start buffering up to 500k events on a production hot path)
    _log.warning("PADDLE_TPU_TRACE=%r is not off|on|flight — tracing "
                 "stays OFF", raw)
    return "off"


_MODE = _env_mode()

_IDS = itertools.count(1)      # span + trace ids, one process-global space
_TLS = threading.local()


class SpanContext(NamedTuple):
    """Immutable handle for explicit cross-thread handoff: ``trace`` names
    the tree (one Chrome track), ``span`` the parent node (0 = root)."""

    trace: int
    span: int


class _TraceState:
    """The "on"-mode event buffer + track labels. Mutation is CPython-
    atomic (list.append / dict store), so the hot path takes no lock."""

    __slots__ = ("buffer", "tracks", "dropped")

    def __init__(self):
        self.buffer: List[Dict[str, Any]] = []
        self.tracks: Dict[int, str] = {}
        self.dropped = 0


_STATE = _TraceState()

# attribute values that ride along into the profiler's trace: numbers, and
# strings no longer than this (a prompt or a traceback is not a stat)
_MIRROR_STR_LIMIT = 64
_ANNOTATION = None     # jax.profiler.TraceAnnotation, bound on first use


def _annotation(name: str, attrs: Dict[str, Any]):
    """The span's twin in the profiler's trace (mode ``on`` only). The
    deferred import keeps ``import paddle_tpu`` off ``jax.profiler``."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **{
        k: v for k, v in attrs.items()
        if isinstance(v, (int, float))
        or (isinstance(v, str) and len(v) <= _MIRROR_STR_LIMIT)})


def mode() -> str:
    return _MODE


def enabled() -> bool:
    """True when spans are being recorded (``on`` or ``flight``)."""
    return _MODE != "off"


def set_mode(m: str) -> None:
    """Switch tracing mode at runtime (``PADDLE_TPU_TRACE`` seeds the
    initial value at import). ``on`` also installs the per-op dispatch
    hook; ``off``/``flight`` keep the dispatch seam at its is-None
    probe."""
    global _MODE
    if m not in _VALID_MODES:
        raise ValueError(f"trace mode must be one of {_VALID_MODES}, "
                         f"got {m!r}")
    _MODE = m
    _sync_op_hook()


class tracing:
    """``with tracing("on"): ...`` — scoped mode switch for tests."""

    def __init__(self, m: str = "on"):
        self._mode = m
        self._prev = ""

    def __enter__(self):
        self._prev = _MODE
        set_mode(self._mode)
        return self

    def __exit__(self, *exc):
        set_mode(self._prev)


def make_event(kind: str, name: str, ts: Optional[float] = None,
               attrs: Optional[Dict[str, Any]] = None,
               **fields: Any) -> Dict[str, Any]:
    """The one event envelope every sink shares (the Chrome exporter, the
    flight recorder, and the JSONL step-telemetry stream): ``ts`` (a
    ``perf_counter`` instant), ``kind``, ``name``, ``attrs`` — plus
    optional span-tree fields (``trace``/``span``/``parent``)."""
    ev: Dict[str, Any] = {
        "ts": time.perf_counter() if ts is None else float(ts),
        "kind": kind, "name": name, "attrs": dict(attrs or {})}
    if fields:
        ev.update(fields)
    return ev


def _emit(ev: Dict[str, Any], ring: bool = True) -> None:
    if _MODE == "on":
        buf = _STATE.buffer
        if len(buf) < _BUFFER_CAP:
            buf.append(ev)
        else:
            _STATE.dropped += 1
    if ring:
        _FLIGHT.record(ev)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _set_track(tid: int, label: str) -> None:
    """Remember a track label for the Chrome export. Labels only matter in
    "on" mode (the exporter reads them) and the map is capped — in
    "flight" mode a long-running engine mints one trace per request, and
    an unbounded label dict would be exactly the leak the buffer cap
    exists to prevent."""
    if _MODE == "on" and len(_STATE.tracks) < _TRACKS_CAP:
        _STATE.tracks.setdefault(tid, label)


def _stack() -> List[SpanContext]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current() -> Optional[SpanContext]:
    """The innermost open span on THIS thread (for implicit parenting)."""
    st = getattr(_TLS, "stack", None)
    return st[-1] if st else None


class _NoopSpan:
    """Shared disabled-mode span: one global read, nothing else."""

    __slots__ = ()
    ctx: Optional[SpanContext] = None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


class _Span:
    """One live span. Only :func:`span` constructs these, and only as a
    context manager — begin/end pairing is structural, which is what lets
    the chaos suites assert every trace is balanced."""

    __slots__ = ("_name", "_attrs", "_parent", "_ring", "_mirror", "ctx")

    def __init__(self, name: str, parent: Optional[SpanContext],
                 attrs: Dict[str, Any], ring: bool = True):
        self._name = name
        self._attrs = attrs
        self._parent = parent
        self._ring = ring
        self._mirror = None
        self.ctx: Optional[SpanContext] = None

    def __enter__(self) -> "_Span":
        stack = _stack()
        if self._parent is not None:
            tr, par = self._parent.trace, self._parent.span
        elif stack:
            top = stack[-1]
            tr, par = top.trace, top.span
        else:
            tr, par = next(_IDS), 0
            _set_track(tr, self._name)
        sid = next(_IDS)
        self.ctx = SpanContext(tr, sid)
        stack.append(self.ctx)
        _emit({"ts": time.perf_counter(), "kind": "B", "name": self._name,
               "attrs": self._attrs, "trace": tr, "span": sid,
               "parent": par, "thread": threading.get_ident()}, self._ring)
        if _MODE == "on":
            # the same span on the profiler's clock (entered last, left
            # first: the annotation lies inside the B/E pair)
            self._mirror = _annotation(self._name, self._attrs)
            self._mirror.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] == self.ctx:
            stack.pop()
        elif self.ctx in stack:          # defensive: interleaved exit
            stack.remove(self.ctx)
        attrs = {"error": exc_type.__name__} if exc_type is not None else {}
        _emit({"ts": time.perf_counter(), "kind": "E", "name": self._name,
               "attrs": attrs, "trace": self.ctx.trace,
               "span": self.ctx.span}, self._ring)
        return False


def span(name: str, parent: Optional[SpanContext] = None, **attrs):
    """Open one span of the trace tree (context manager — the ONLY way to
    create a span). ``parent`` is an explicit :class:`SpanContext` for
    cross-thread handoff; omitted, the innermost open span on this thread
    (or a fresh root) parents it. Near-free when tracing is off."""
    if _MODE == "off":
        return _NOOP
    return _Span(name, parent, attrs)


def phase(name: str, parent: Optional[SpanContext] = None, **attrs):
    """A span of the detail tier (context manager, like :func:`span`): one
    phase of a step that runs many times a second — the serving loop's
    build/launch/wait/emit, a compiled call's dispatch. Recorded in mode
    ``on`` only, into the buffer and the profiler's trace, never into the
    flight ring: in ``off`` AND ``flight`` it is one global read returning
    the shared no-op, so the ring's 512 slots keep the lifecycle tail."""
    if _MODE != "on":
        return _NOOP
    return _Span(name, parent, attrs, ring=False)


def phase_done(name: str, seconds: float, **attrs) -> None:
    """A span of the detail tier that has ALREADY run: it ended now and
    began ``seconds`` ago. For work whose only record is a duration handed
    over at its end — jax reports a trace, a lowering and a compile that
    way. Mode ``on`` only, buffer only (the phase tier's rule), a child of
    the innermost open span of this thread (track 0 when none is open).
    Begin and end are written in this one call, so pairing stays
    structural; the begin's ``ts`` lies before events already in the
    buffer (its children by time containment). No profiler mirror: an
    annotation cannot be backdated."""
    if _MODE != "on":
        return
    end = time.perf_counter()
    buf = _STATE.buffer
    if len(buf) + 2 > _BUFFER_CAP:      # both events or neither
        _STATE.dropped += 2
        return
    tr, par = current() or (0, 0)
    sid = next(_IDS)
    buf.append({"ts": end - seconds, "kind": "B", "name": name,
                "attrs": attrs, "trace": tr, "span": sid, "parent": par,
                "thread": threading.get_ident()})
    buf.append({"ts": end, "kind": "E", "name": name, "attrs": {},
                "trace": tr, "span": sid})


def new_trace(label: str, **attrs) -> Optional[SpanContext]:
    """Mint a root context for a logical unit of work (one Chrome track):
    the cross-thread handle a serving request or training run carries.
    Returns None when tracing is off — every consumer treats the context
    as optional."""
    if _MODE == "off":
        return None
    tid = next(_IDS)
    _set_track(tid, label)
    _emit(make_event("ev", label, attrs=attrs, trace=tid, span=0, parent=0))
    return SpanContext(tid, 0)


def _instant_event(name: str, parent: Optional[SpanContext],
                   attrs: Dict[str, Any]) -> Dict[str, Any]:
    if parent is not None:
        tr, par = parent.trace, parent.span
    else:
        cur = current()
        tr, par = (cur.trace, cur.span) if cur is not None else (0, 0)
    return make_event("i", name, attrs=attrs, trace=tr, parent=par)


def instant(name: str, parent: Optional[SpanContext] = None,
            **attrs) -> None:
    """A point event. Attached to ``parent`` (or the current span) in the
    trace tree when tracing is on; ALWAYS appended to the flight ring —
    instants are the coarse lifecycle/fault record the post-mortem needs,
    and they fire at request/step rate, never per op."""
    _emit(_instant_event(name, parent, attrs))


def phase_instant(name: str, parent: Optional[SpanContext] = None,
                  **attrs) -> None:
    """A point event of the detail tier (see :func:`phase`): per-token
    rate, so mode ``on`` only and buffer only — never the flight ring."""
    if _MODE == "on":
        _emit(_instant_event(name, parent, attrs), ring=False)


def record(name: str, **attrs) -> None:
    """An un-parented lifecycle event (always in the flight ring; in the
    trace buffer too when tracing is on). The seam the fault injector and
    the watchdog use."""
    _emit(make_event("ev", name, attrs=attrs, trace=0, parent=0))


def events() -> List[Dict[str, Any]]:
    """Copy of the "on"-mode trace buffer."""
    return list(_STATE.buffer)


def dropped() -> int:
    return _STATE.dropped


def clear() -> None:
    """Reset the trace buffer + track names (test isolation seam; the
    flight ring has its own ``flight_recorder().clear()``)."""
    _STATE.buffer = []
    _STATE.tracks = {}
    _STATE.dropped = 0


def span_problems(evs: Optional[List[Dict[str, Any]]] = None) -> List[str]:
    """Structural validation the chaos suites assert on: every span begin
    has exactly one end (same id), no end without a begin, and every
    non-root parent id exists as a span in the same trace. Returns a list
    of human-readable problems ([] = the trace is a well-formed forest)."""
    evs = events() if evs is None else evs
    begins: Dict[int, Dict[str, Any]] = {}
    ended: Dict[int, int] = {}
    problems: List[str] = []
    for e in evs:
        if e["kind"] == "B":
            if e["span"] in begins:
                problems.append(f"span {e['span']} ({e['name']}) began twice")
            begins[e["span"]] = e
        elif e["kind"] == "E":
            if e["span"] not in begins:
                problems.append(f"span {e['span']} ({e['name']}) ended "
                                f"without a begin")
            ended[e["span"]] = ended.get(e["span"], 0) + 1
    for sid, b in begins.items():
        n = ended.get(sid, 0)
        if n != 1:
            problems.append(f"span {sid} ({b['name']}) has {n} ends")
        par = b.get("parent", 0)
        if par and par not in begins:
            # parent may be a new_trace root (span id 0 handled above) or
            # another span; a dangling nonzero parent is a broken handoff
            problems.append(f"span {sid} ({b['name']}) parent {par} is not "
                            f"a span in the buffer")
        elif par and begins[par].get("trace") != b.get("trace"):
            problems.append(f"span {sid} ({b['name']}) crosses traces "
                            f"{begins[par].get('trace')} -> {b.get('trace')}")
    return problems


# ---------------------------------------------------------------------------
# per-op dispatch hook ("on" mode only)
# ---------------------------------------------------------------------------

def _op_event_hook(op_name: str, t0: float, t1: float) -> None:
    """Installed into ``core.tensor._op_trace_hook`` while mode == "on":
    one complete event per eager dispatch, buffer-only (per-op noise must
    never churn the flight ring's post-mortem tail)."""
    cur = current()
    ev = {"ts": t0, "kind": "O", "name": op_name, "attrs": {},
          "dur": t1 - t0, "trace": cur.trace if cur is not None else 0}
    buf = _STATE.buffer
    if len(buf) < _BUFFER_CAP:
        buf.append(ev)
    else:
        _STATE.dropped += 1


def _sync_op_hook() -> None:
    """Install/remove the dispatch hook to match the mode. Deferred core
    import (observability is a foundation layer; ``paddle_tpu/__init__``
    re-syncs once the core is importable, covering an env-set mode)."""
    try:
        from ..core import tensor as _tensor_mod
    except ImportError:
        return  # why: early in package import the core does not exist yet;
        #        the package root calls _sync_op_hook() again at the end
    _tensor_mod._op_trace_hook = _op_event_hook if _MODE == "on" else None


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def trace_dir() -> str:
    """Where exports and flight dumps land: ``PADDLE_TPU_TRACE_DIR``, or a
    stable per-tmpdir default."""
    d = os.environ.get("PADDLE_TPU_TRACE_DIR", "").strip()
    return d or os.path.join(tempfile.gettempdir(), "paddle_tpu_obs")


def export_chrome(path: Optional[str] = None,
                  evs: Optional[List[Dict[str, Any]]] = None):
    """Convert the trace buffer to the Chrome trace-event format
    (``chrome://tracing`` / Perfetto-loadable). Spans become complete
    ("X") events on one track per trace id (nesting falls out of time
    containment), instants "i" events, per-op events "X" on their trace's
    track; a span left open by a crash exports as a bare "B" (Perfetto
    renders it to the end of the trace). Returns the document dict, or
    writes it to ``path`` and returns the path."""
    evs = events() if evs is None else list(evs)
    pid = os.getpid()
    base = min((e["ts"] for e in evs), default=0.0)

    def us(ts: float) -> float:
        return (ts - base) * 1e6

    out: List[Dict[str, Any]] = []
    open_b: Dict[int, Dict[str, Any]] = {}
    for e in evs:
        kind = e["kind"]
        tid = e.get("trace", 0)
        if kind == "B":
            open_b[e["span"]] = e
        elif kind == "E":
            b = open_b.pop(e.get("span", 0), None)
            if b is None:
                continue
            args = dict(b.get("attrs") or {})
            args.update(e.get("attrs") or {})
            args["span"] = b["span"]
            if b.get("parent"):
                args["parent"] = b["parent"]
            out.append({"name": b["name"], "cat": "paddle_tpu", "ph": "X",
                        "ts": us(b["ts"]), "dur": max(0.0, us(e["ts"]) -
                                                      us(b["ts"])),
                        "pid": pid, "tid": b.get("trace", 0), "args": args})
        elif kind == "O":
            out.append({"name": e["name"], "cat": "paddle_tpu.op",
                        "ph": "X", "ts": us(e["ts"]),
                        "dur": max(0.0, e.get("dur", 0.0) * 1e6),
                        "pid": pid, "tid": tid, "args": {}})
        else:   # "i" instants + "ev" lifecycle/step events
            out.append({"name": e["name"], "cat": "paddle_tpu",
                        "ph": "i", "s": "t" if tid else "g",
                        "ts": us(e["ts"]), "pid": pid, "tid": tid,
                        "args": dict(e.get("attrs") or {})})
    for b in open_b.values():   # crash-open spans: begin-only is loadable
        out.append({"name": b["name"], "cat": "paddle_tpu", "ph": "B",
                    "ts": us(b["ts"]), "pid": pid,
                    "tid": b.get("trace", 0),
                    "args": dict(b.get("attrs") or {})})
    out.append({"name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": f"paddle_tpu[{pid}]"}})
    for tid, label in list(_STATE.tracks.items()):
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": label}})
    doc = {"traceEvents": out, "displayTimeUnit": "ms"}
    if path is None:
        return doc
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, default=str)
    return path


def maybe_export_chrome(label: str) -> Optional[str]:
    """Operator-facing auto-export: when tracing is fully on AND the
    operator pointed ``PADDLE_TPU_TRACE_DIR`` somewhere, drop a Chrome
    trace there (the engine/supervisor call this at shutdown). Never
    raises; returns the path or None."""
    if _MODE != "on" or not os.environ.get("PADDLE_TPU_TRACE_DIR",
                                           "").strip():
        return None
    path = os.path.join(trace_dir(), f"trace-{label}-{os.getpid()}.json")
    try:
        return export_chrome(path)
    except OSError as e:
        _log.error("trace: chrome export to %s failed: %s", path, e)
        return None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Lock-free ring of the last N envelope events.

    Writers pay one C-level counter bump (``itertools.count``) and one
    list-slot store — no lock, safe from any thread including the
    watchdog's. ``snapshot()`` reorders by sequence number; a dump taken
    while writers race may miss the very newest slot, which is the right
    trade for a recorder that must never stall the path it observes.
    """

    __slots__ = ("capacity", "_slots", "_seq")

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            raw = os.environ.get("PADDLE_TPU_FLIGHT_EVENTS", "").strip()
            try:
                capacity = int(raw) if raw else _DEFAULT_FLIGHT_EVENTS
            except ValueError:
                capacity = _DEFAULT_FLIGHT_EVENTS
        self.capacity = max(8, int(capacity))
        self._slots: List[Optional[Any]] = [None] * self.capacity
        self._seq = itertools.count()

    def record(self, ev: Dict[str, Any]) -> None:
        i = next(self._seq)
        self._slots[i % self.capacity] = (i, ev)

    def snapshot(self) -> List[Dict[str, Any]]:
        entries = [s for s in list(self._slots) if s is not None]
        entries.sort(key=lambda p: p[0])
        return [ev for _, ev in entries]

    def clear(self) -> None:
        self._slots = [None] * self.capacity

    def dump(self, reason: str, path: Optional[str] = None,
             **info: Any) -> Optional[str]:
        """Write the ring's last-N snapshot to a JSON file (atomic
        replace; one file per (pid, reason) so repeated aborts keep the
        LATEST post-mortem). Never raises — a failing dump must not turn
        an abort into a second crash. Returns the path or None."""
        evs = self.snapshot()
        doc = {"schema": 1, "reason": reason, "pid": os.getpid(),
               "dumped_at": time.time(),
               "dumped_perf_ts": time.perf_counter(),
               "info": dict(info), "events": evs}
        try:
            # ISSUE 16: the post-mortem names the programs that were live
            # AND what they should have cost (records + HBM ledger);
            # flight_snapshot itself never raises, the guard covers import
            from . import cost as _cost
            doc["cost"] = _cost.flight_snapshot()
        except Exception:
            doc["cost"] = None
        if path is None:
            slug = "".join(c if c.isalnum() or c in "-_" else "_"
                           for c in reason)
            path = os.path.join(trace_dir(),
                                f"flight-{os.getpid()}-{slug}.json")
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, default=str)
            os.replace(tmp, path)
        except OSError as e:
            _log.error("flight recorder: dump to %s failed: %s", path, e)
            return None
        from . import inc as _inc   # deferred: trace is imported by the
        _inc("trace.flight_dumps_total", reason=reason)  # package __init__
        _log.warning("flight recorder: %d events -> %s (reason=%s)",
                     len(evs), path, reason)
        return path


_FLIGHT = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _FLIGHT


def flight_dump(reason: str, **info: Any) -> Optional[str]:
    """Dump the process-global flight ring (see
    :meth:`FlightRecorder.dump`)."""
    return _FLIGHT.dump(reason, **info)


# ---------------------------------------------------------------------------
# health beacons (the /healthz surface)
# ---------------------------------------------------------------------------

class _Heartbeats:
    __slots__ = ("beats",)

    def __init__(self):
        self.beats: Dict[str, Dict[str, Any]] = {}


_HEALTH = _Heartbeats()


def heartbeat(name: str, ttl_s: float = _DEFAULT_HEARTBEAT_TTL_S,
              ok: bool = True) -> None:
    """Liveness beacon: the engine/supervisor step loops (and the watchdog
    poll threads) ping one per iteration; ``/healthz`` reports a component
    unhealthy once its beacon goes stale past ``ttl_s`` (a loop thread
    wedged inside a compiled call stops beating — exactly the failure an
    external prober needs to see) or it last reported ``ok=False``."""
    _HEALTH.beats[name] = {"at": time.monotonic(), "ttl_s": float(ttl_s),
                           "ok": bool(ok)}


def heartbeat_clear(name: str) -> None:
    """Retire a beacon (clean shutdown is not a liveness failure)."""
    _HEALTH.beats.pop(name, None)


def health() -> Dict[str, Any]:
    """The /healthz document: per-component age vs ttl; overall ``ok``
    only when every registered beacon is fresh and ok.

    Each component carries the full per-beacon detail (ISSUE 15 — the
    router and the front door route on it, a multi-replica process
    registers one ``serving.engine.<replica>`` beacon per engine):
    ``age_s`` since the last beat, the beacon's ``ttl_s``, an explicit
    ``stale`` bit (age past ttl — a loop thread wedged in a compiled call
    stops beating), and ``ok`` (fresh AND the last beat reported
    healthy) — not just one process-global staleness bit."""
    now = time.monotonic()
    comps: Dict[str, Any] = {}
    healthy = True
    # copy first: heartbeat() inserts new keys lock-free from other
    # threads (an engine's first beat racing a scrape), and iterating the
    # live dict would raise mid-/healthz
    for name, b in sorted(dict(_HEALTH.beats).items()):
        comps[name] = c = _beacon_component(b, now)
        healthy = healthy and c["ok"]
    # ISSUE 16: HBM ledger detail rides along 503-INDEPENDENTLY — low
    # headroom warns (once, in the cost module) but never flips the
    # routing status; the component's ok is always True by contract
    try:
        from . import cost as _cost
        hbm = _cost.healthz_component()
        if hbm is not None:
            comps["hbm"] = hbm
    except Exception:
        # why silent: the hbm component is advisory detail — a ledger
        # walk failing mid-scrape must not turn /healthz into a 500,
        # and the failure is already counted by the cost module
        _log.debug("healthz: hbm component unavailable", exc_info=True)
    return {"status": "ok" if healthy else "unhealthy",
            "components": comps, "pid": os.getpid()}


def _beacon_component(b: Dict[str, Any], now: float) -> Dict[str, Any]:
    """One beacon's component document — the single definition of the
    stale/ok semantics both :func:`health` and :func:`beacon_detail`
    report (they must never drift: the router's rotation signal IS the
    /healthz document)."""
    age = now - b["at"]
    stale = age > b["ttl_s"]
    return {"age_s": round(age, 3), "ttl_s": b["ttl_s"], "stale": stale,
            "ok": b["ok"] and not stale}


def beacon_detail(name: str) -> Optional[Dict[str, Any]]:
    """One beacon's /healthz component (or None when it never beat):
    the router's per-replica liveness probe — a replica whose engine
    beacon is ``stale`` leaves the rotation without an HTTP scrape."""
    b = dict(_HEALTH.beats).get(name)
    if b is None:
        return None
    return _beacon_component(b, time.monotonic())


_sync_op_hook()
