"""jax's own trace / lowering / compile events as spans and counters of the
program (ISSUE 36).

jax reports each phase of making an executable through ``jax.monitoring``,
at the phase's END and on the thread that asked: a duration event for the
trace to a jaxpr, one for the lowering to an MLIR module, one for the backend
compile (an XLA compile or a load from the persistent cache, whichever the
call paid), plus plain events for the cache's hits and misses. One listener,
registered by the first ``observability.enable()`` (jax 0.9.0 has no public
unregister, so it stays and looks at ``obs.enabled()``), turns them into

* spans ``jit.trace`` / ``jit.lower`` / ``jit.compile`` (mode ``on``; events
  of a millisecond and more — a decode program's trace holds hundreds of
  shorter ones), written with :func:`trace.phase_done` under whatever span
  is open: ``jit.dispatch`` of the ``StaticFunction`` that asked, so whose
  program a span is reads off its ``parent``: that begin's ``program``. A
  jitted function traced inside another trace (a Pallas kernel body under
  ``_kernel_call``, a jitted ``jax.numpy`` helper) fires its own event: a
  child by time containment and a sibling by ``parent``, told apart by
  ``fun``, jax's name for what was traced. Read a phase's seconds as the
  UNION of its intervals;
* counters, tracing on or off: ``jit.compile_seconds_total{phase}`` (each
  event adds what no earlier event of its phase and thread covered, so the
  three phases sum to wall time), ``jit.persistent_cache_hits_total`` and
  ``jit.persistent_cache_misses_total``.
"""

from __future__ import annotations

import threading
import time

from . import _REGISTRY, enabled, trace

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

# shorter events feed the counters only
MIN_SPAN_S = 1e-3
# intervals remembered per thread and phase; the oldest half goes beyond it
# (only an event that encloses more than this many others could count twice)
_COVERED_CAP = 4096

_SECONDS = _REGISTRY.counter(
    "jit.compile_seconds_total",
    "wall seconds in jax's trace / lower / compile (XLA compile or "
    "persistent-cache load) events, nested events counted once",
    labelnames=("phase",))
_HITS = _REGISTRY.counter(
    "jit.persistent_cache_hits_total",
    "executables loaded from jax's persistent compilation cache")
_MISSES = _REGISTRY.counter(
    "jit.persistent_cache_misses_total",
    "executables compiled and written to jax's persistent compilation cache")

_TLS = threading.local()
_INSTALLED = False


def _uncovered(phase: str, seconds: float) -> float:
    """The seconds of ``[now - seconds, now]`` that no earlier event of this
    phase on this thread covered. Events arrive in the order they END, so an
    enclosing event comes after what it encloses: it takes back what those
    added."""
    end = time.perf_counter()
    start = end - seconds
    done = _TLS.__dict__.setdefault(phase, [])
    while done and done[-1][1] > start:
        s0, e0 = done.pop()
        seconds -= e0 - max(s0, start)
        start = min(start, s0)
    if len(done) >= _COVERED_CAP:
        del done[:_COVERED_CAP // 2]
    done.append((start, end))
    return max(seconds, 0.0)


def _on_duration(event: str, seconds: float, **kw) -> None:
    if not enabled():
        return
    if event == _CACHE_LOAD:
        _TLS.__dict__.setdefault("cache", {})["load_s"] = seconds
        return
    phase = _PHASES.get(event)
    if phase is None:
        return
    _SECONDS.inc(_uncovered(phase, seconds), phase=phase)
    # what the cache said inside this compile, on this thread
    attrs = _TLS.__dict__.pop("cache", {}) if phase == "compile" else {}
    if seconds < MIN_SPAN_S or trace.mode() != "on":
        return
    trace.phase_done(f"jit.{phase}", seconds,
                     fun=str(kw.get("fun_name", "")), **attrs)


def _on_event(event: str, **_kw) -> None:
    if not enabled():
        return
    if event == _CACHE_HIT:
        _HITS.inc()
        _TLS.cache = {"cache_hit": 1}
    elif event == _CACHE_MISS:
        _MISSES.inc()
        _TLS.cache = {"cache_hit": 0}


def install() -> None:
    """Register the listener (once a process; the caller, ``enable()``,
    holds the package's lock) and put the two cache counters on the scrape
    at 0: a warm run then READS 0 misses, where a program without the
    listener reads nothing."""
    global _INSTALLED
    if not _INSTALLED:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _INSTALLED = True
    _HITS.inc(0.0)
    _MISSES.inc(0.0)
