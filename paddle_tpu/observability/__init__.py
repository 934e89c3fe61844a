"""``paddle_tpu.observability`` — framework-wide metrics & telemetry.

Answers "what is the runtime doing right now" without a profiler session:
op-dispatch rates and latency, jit trace/compile/cache-hit counts, PS RPC
retries and failovers, pipeline step time and bubble fraction, elastic
store health, dataloader queue depth and wait time.

Reference parity: the monitor/stat surface (paddle/fluid/platform/
monitor.h StatRegistry, ``paddle.utils.monitor``); exporters follow the
Prometheus data model instead of the reference's bespoke dump because the
north-star deployment (ROADMAP) scrapes.

Usage::

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs

    obs.enable()                      # installs the dispatch hook
    ... train ...
    snap = obs.snapshot()             # {"dispatch.ops_total": 1234, ...}
    print(obs.prometheus_text())      # scrape document
    obs.reset(); obs.disable()

Naming convention (enforced by habit, asserted in tests for the built-ins):
``<subsystem>.<noun>_<unit>`` with counters suffixed ``_total``, histograms
suffixed ``_seconds`` (SI base units), gauges plain nouns — e.g.
``dispatch.ops_total``, ``ps.rpc_retries_total``,
``dataloader.wait_seconds``, ``pipeline.bubble_fraction``.

Since ISSUE 12 the package also owns the TRACING surface: ``trace``
(span trees, the Chrome trace-event exporter, and the always-on crash
flight recorder — see :mod:`paddle_tpu.observability.trace`) and ``http``
(the ``/metrics`` + ``/healthz`` + ``/debug`` scrape endpoint behind
``PADDLE_TPU_OBS_HTTP_PORT`` — :mod:`paddle_tpu.observability.http`).

Zero-overhead contract: when disabled (the default), the op-dispatch seam
carries NO observability work — ``core.tensor._op_metrics_hook`` is
``None`` and ``apply()`` only performs the same is-None probe it already
performed for the profiler. Module-level helpers (``inc``/``observe``/
``set_gauge``/``scoped_timer``) short-circuit on one global bool, cheap
enough for per-call (not per-op) seams like jit cache lookups and RPC
issue paths.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence

from .registry import (Counter, Gauge, Histogram, LogThrottle, Registry,
                       ScopedTimer, DEFAULT_LATENCY_BUCKETS)
from . import trace  # noqa: F401  (ISSUE 12: spans + flight recorder;
#                      imported BEFORE export, which shares its envelope)
from .export import (StepTelemetryWriter, parse_prometheus_text,
                     prometheus_text as _prom_text, read_jsonl)

__all__ = [
    "Counter", "Gauge", "Histogram", "LogThrottle", "Registry",
    "StepTelemetryWriter",
    "DEFAULT_LATENCY_BUCKETS",
    "enable", "disable", "enabled", "default_registry",
    "counter", "gauge", "histogram",
    "inc", "set_gauge", "observe", "scoped_timer",
    "snapshot", "reset", "prometheus_text", "parse_prometheus_text",
    "read_jsonl",
    "trace", "cost",
]

_REGISTRY = Registry()
_ENABLED = False
_LOCK = threading.Lock()

# ISSUE 16: the program cost registry (submodule `cost`) shares this
# registry's metric families. It is imported lazily inside
# enable()/disable()/reset() — a module-scope `from . import cost` here
# would be a load-bearing import cycle (cost reads _REGISTRY back out of
# this package at ITS import time), and nothing needs the submodule
# before the first enable().


def default_registry() -> Registry:
    return _REGISTRY


def enabled() -> bool:
    return _ENABLED


# -- built-in dispatch instrumentation ---------------------------------------
# Families are pre-created so the hot hook never takes the registry lock.
_DISPATCH_OPS = _REGISTRY.counter(
    "dispatch.ops_total", "ops dispatched through core.tensor.apply")
_DISPATCH_BY_OP = _REGISTRY.counter(
    "dispatch.ops_by_name_total", "per-op dispatch counts", labelnames=("op",))
_DISPATCH_LATENCY = _REGISTRY.histogram(
    "dispatch.latency_seconds", "host-side latency of one eager dispatch")

# eager compiled-op cache (core/dispatch_cache.py): hit/miss/compile/evict
# plus bypasses labeled by reason (capture, symbolic_input, closure_array,
# static_unhashable, untraceable)
_CACHE_HITS = _REGISTRY.counter(
    "dispatch.cache_hits_total", "eager-cache dispatches served compiled")
_CACHE_MISSES = _REGISTRY.counter(
    "dispatch.cache_misses_total", "eager-cache probes that found no entry")
_CACHE_COMPILES = _REGISTRY.counter(
    "dispatch.cache_compiles_total", "signatures compiled into the cache")
_CACHE_EVICTIONS = _REGISTRY.counter(
    "dispatch.cache_evictions_total", "LRU evictions from the eager cache")
_CACHE_BYPASS = _REGISTRY.counter(
    "dispatch.cache_bypass_total", "dispatches that bypassed the eager cache",
    labelnames=("reason",))


# what to_static counts, said where a scrape shows it: one per cache entry.
# An entry's jax.jit compiles again for every new input shape (the serving
# engine's batch buckets and prompt lengths); those are counted from jax's
# own events (compile_events: jit.compile_seconds_total, the jit.* spans)
_REGISTRY.counter(
    "jit.compiles_total",
    "fresh StaticFunction builds whose first call succeeded: one per cache "
    "entry, NOT one per XLA compile")


def _dispatch_hook(op_name: str, t0: float, t1: float) -> None:
    """Installed into ``core.tensor._op_metrics_hook`` while enabled."""
    _DISPATCH_OPS.inc()
    _DISPATCH_BY_OP.inc(op=op_name)
    _DISPATCH_LATENCY.observe(t1 - t0)


def _cache_hook(kind: str, reason) -> None:
    """Installed into ``core.dispatch_cache._obs_hook`` while enabled."""
    if kind == "hit":
        _CACHE_HITS.inc()
    elif kind == "miss":
        _CACHE_MISSES.inc()
    elif kind == "compile":
        _CACHE_COMPILES.inc()
    elif kind == "evict":
        _CACHE_EVICTIONS.inc()
    else:
        _CACHE_BYPASS.inc(reason=reason or "other")


def enable() -> None:
    """Turn metrics collection on and install the dispatch hooks."""
    global _ENABLED
    with _LOCK:
        _ENABLED = True
        from ..core import tensor as _tensor_mod
        from ..core import dispatch_cache as _dcache_mod
        _tensor_mod._op_metrics_hook = _dispatch_hook
        _dcache_mod._obs_hook = _cache_hook
        # jax's trace / lower / compile events as spans and counters: one
        # listener for the life of the process, gated by enabled()
        from . import compile_events
        compile_events.install()
    # compile-time cost capture rides the same switch (its own is-None
    # hooks in to_static/dispatch_cache; no-op under PADDLE_TPU_COST=off)
    from . import cost
    cost.install()


def disable() -> None:
    """Stop collecting; collected values remain readable."""
    global _ENABLED
    with _LOCK:
        _ENABLED = False
        from ..core import tensor as _tensor_mod
        from ..core import dispatch_cache as _dcache_mod
        _tensor_mod._op_metrics_hook = None
        _dcache_mod._obs_hook = None
    from . import cost
    cost.uninstall()


# -- family accessors (get-or-create on the default registry) ----------------
def counter(name: str, help: str = "",
            labelnames: Sequence[str] = ()) -> Counter:
    return _REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames: Sequence[str] = ()) -> Gauge:
    return _REGISTRY.gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames: Sequence[str] = (),
              buckets: Optional[Sequence[float]] = None) -> Histogram:
    return _REGISTRY.histogram(name, help, labelnames, buckets)


# -- cheap instrumentation helpers (no-ops while disabled) -------------------
def _check_labels(labels) -> None:
    # ``value`` is positional-only on the helpers: obs.inc("m", value=5)
    # would otherwise land here as a bogus {value="5"} label on an
    # increment of 1 — silently the wrong metric. (``name`` stays legal
    # as a label: the metric name cannot be passed by keyword at all, so
    # name=... is always an intentional label, e.g. the profiler bridge's
    # record_event_seconds{name=...}.)
    if "value" in labels:
        # every production call site passes a literal label set, so this
        # TypeError is unreachable at runtime from the serving/training
        # entry roots — it exists to fail developer mistakes loudly in
        # tier-1, not as part of any typed failure surface
        raise TypeError(  # graft-lint: disable=exception-contract
            "'value' is positional-only — obs.inc(name, amount, **labels); "
            "pass the amount positionally, not as a label")


def inc(name: str, value: float = 1.0, /, **labels) -> None:
    if not _ENABLED:
        return
    _check_labels(labels)
    _REGISTRY.counter(name, labelnames=tuple(sorted(labels))).inc(value, **labels)


def set_gauge(name: str, value: float, /, **labels) -> None:
    if not _ENABLED:
        return
    _check_labels(labels)
    _REGISTRY.gauge(name, labelnames=tuple(sorted(labels))).set(value, **labels)


def observe(name: str, value: float, /, **labels) -> None:
    if not _ENABLED:
        return
    _check_labels(labels)
    _REGISTRY.histogram(name, labelnames=tuple(sorted(labels))).observe(value,
                                                               **labels)


def scoped_timer(name: str, /, **labels) -> ScopedTimer:
    """``with obs.scoped_timer("train.step_seconds", phase="fwd"): ...``
    — observes a latency sample when enabled, free when disabled. Label
    sets are fixed per family: time an EXISTING built-in metric only with
    its declared labels (e.g. ``ps.rpc_seconds`` is unlabeled)."""
    if not _ENABLED:
        return ScopedTimer(None, {})
    return ScopedTimer(_REGISTRY.histogram(name, labelnames=tuple(sorted(labels))),
                       labels)


# -- read-out ----------------------------------------------------------------
def snapshot() -> Dict[str, Any]:
    """Plain-data view of every collected series (works while disabled)."""
    return _REGISTRY.snapshot()


def reset() -> None:
    """Zero every series (metric families survive) and drop cost
    records; test isolation seam."""
    _REGISTRY.reset()
    from . import cost
    cost.clear()


def prometheus_text(registry: Optional[Registry] = None) -> str:
    return _prom_text(registry if registry is not None else _REGISTRY)
