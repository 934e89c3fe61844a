"""to_static: trace → functionalize → jax.jit with state donation."""

from __future__ import annotations

import functools
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import (Tensor, TraceBreakError, _state_registry,
                           _is_tracer)
from .. import flags as _flags
from .. import observability as _obs
from ..observability import trace as _trace
from ..core.tracing import (TraceState, pop_trace_state, push_trace_state,
                            trace_state)

__all__ = ["StaticFunction", "to_static", "not_to_static", "ignore_module",
           "register_pretrace_hook", "TraceBreakError", "LoweringError"]

_ENABLED = True

_FALLBACK = object()  # cache sentinel: this signature graph-breaks to eager
_SEGMENTED = object()  # cache sentinel: run via lazy compiled segments

# serializes trace/invoke/rebind across threads (ISSUE 15: in-process
# multi-replica serving runs one step thread per engine): the global state
# registry is threaded through every compiled call, so interleaved calls
# would capture each other's tracers. RLock — a dead-state rebuild or a
# nested fallback re-enters on the same thread.
_INVOKE_LOCK = threading.RLock()

# ISSUE 16: compile-time cost capture. observability.cost installs a
# callable here while enabled (the _op_metrics_hook is-None contract: the
# build path pays one probe when off, and analysis — a second AOT
# compile — runs only for fresh builds while the hook is live).
# Signature: hook("build", sf, jitted=, state_specs=, arg_specs=, key=)
# on a fresh successful build; hook("retire", sf, key=) when a dead-state
# entry is dropped before its retrace.
_cost_hook: Optional[Callable] = None


def _lower_spec(a):
    """ShapeDtypeStruct for lowering outside the live call. Single-device
    shardings mean "uncommitted" here — passing them into lower() would
    conflict with in-step mesh constraints, which the real call
    (uncommitted arrays) never does."""
    sh = getattr(a, "sharding", None)
    if not isinstance(sh, jax.sharding.NamedSharding):
        sh = None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)


class LoweringError(RuntimeError):
    """The program traced, and then lowering it for the device failed —
    a Pallas kernel the compiler refused, a primitive with no lowering
    rule. Never a graph break: demoting such a step to the eager tier
    would re-run it op by op, where the CPU fallback could pick the op up
    and the run would end 0 having left the chip. The compiler's own error
    is the ``__cause__``."""


def _is_trace_failure(e: BaseException) -> bool:
    """Graph breaks are TRACE failures only (tensor-dependent Python
    control flow, tracer leaks, ops without abstract eval) — the reference
    SOT's fallback contract. Failures after the trace completed — lowering
    (:class:`LoweringError`), XLA execution errors, device OOM, asserts
    that only fire under jit — must NOT memoize a permanent eager
    fallback: they re-raise so the user sees them."""
    return isinstance(e, (jax.errors.JAXTypeError,
                          jax.errors.NonConcreteBooleanIndexError,
                          NotImplementedError, TraceBreakError))

# Objects with lazily-derived state (e.g. optimizer AMP masters) register here;
# before any (re)trace we give them a chance to reconcile derived state with
# concrete values — inside the trace the data is symbolic and it's too late.
_pretrace_refs: List = []


def register_pretrace_hook(obj) -> None:
    with _INVOKE_LOCK:
        _pretrace_refs.append(weakref.ref(obj))


def _run_pretrace_hooks_locked() -> None:
    """Caller holds ``_INVOKE_LOCK`` (the ``_call_locked`` path)."""
    alive = []
    for r in _pretrace_refs:
        o = r()
        if o is not None:
            alive.append(r)
            o._refresh_derived_state()
    _pretrace_refs[:] = alive


def _set_enabled(flag: bool) -> None:
    global _ENABLED
    _ENABLED = bool(flag)


def _is_tensor(x) -> bool:
    return isinstance(x, Tensor)


class StaticFunction:
    """Callable wrapping ``fn`` with whole-step XLA compilation.

    Functionalization contract:
    * every live registered state tensor (params, buffers, optimizer
      accumulators, RNG keys) becomes a jit input AND a jit output — outputs
      for un-mutated state are aliases of the donated inputs, so donation is
      always safe (every state tensor is rebound to a live buffer after the
      call; nothing is left pointing at a deleted donated array);
    * additional mutated locations discovered while tracing (``.grad`` slots,
      non-registered tensors) ride along as extra outputs via the holder spec.
    * plain arguments are read-only inputs, except those named by
      ``donate_argnums``: their arrays are donated like the state (XLA may
      alias them to outputs and write in place) and are deleted by the
      call — the caller adopts what the program returns in their place.
    * cache entries hold only WEAK references to state tensors; the cache key
      is the tuple of registry ids, so a discarded model's entry can never be
      hit again and its parameter arrays are free to be collected.
    """

    def __init__(self, fn: Callable, input_spec=None, build_strategy=None,
                 backend=None, full_graph=True, donate_states: bool = True,
                 iters_per_call: int = 1, donate_argnums=()):
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._name = getattr(fn, "__name__", "program")
        self._donate = donate_states
        # positional arguments the CALLER gives up beside the state (the
        # serving engine's page pool): every array under them is donated,
        # so the program may write it in place, and is deleted by the call.
        # The caller takes the buffer back from the outputs. Asked for per
        # argument; without it a plain argument is never consumed.
        self._donate_argnums = tuple(sorted({int(i) for i in donate_argnums}))
        if self._donate_argnums and (int(iters_per_call) > 1
                                     or not full_graph):
            # a scanned argument is sliced per step; an eager re-run after
            # a graph break would read the deleted array
            raise ValueError("donate_argnums needs full_graph=True and "
                             "iters_per_call=1")
        # full_graph=False is the reference SOT contract: a trace failure
        # (tensor-dependent Python control flow) switches the signature to
        # PARTIAL-GRAPH capture — the lazy segment executor (core/lazy.py)
        # compiles the op runs around each break and re-runs Python as the
        # control-flow interpreter, like upstream SOT's
        # subgraph-with-guards. Our default stays strict (full_graph=True)
        # because the silent perf change is usually a bug the user wants
        # to see.
        self._full_graph = bool(full_graph)
        self._warned_fallback = False
        if not self._full_graph:
            # fallback may re-run the fn eagerly after a compiled attempt
            # failed mid-flight; donation would have deleted the state
            # buffers that eager rerun reads — the compatibility mode
            # trades donation for a safe graph-break
            self._donate = False
        # iters_per_call > 1: lax.scan ``fn`` over the leading axis of every
        # tensor argument inside ONE compiled call (state is the scan carry).
        # This is the standard TPU scan-over-steps trainer pattern — it
        # amortizes per-dispatch overhead (which on a remote-attached chip is
        # ~20ms/call for a model-sized buffer set) across K steps. The fn is
        # still written per-step; the caller passes K-stacked inputs.
        self._iters = int(iters_per_call)
        self._cache: Dict[Any, Tuple] = {}
        self.concrete_program = None  # parity attribute
        self._last_lowered = None  # (jitted, arg shape/sharding specs)
        # ISSUE 16: cost-record identity. Owners that know what this
        # program IS (step_capture, the serving engine) set site/label so
        # the cost registry files its records under the right name;
        # unset means a generic "jit" program. cost_analytic_flops is the
        # flops_counter-style fallback used when XLA has no cost model.
        self.cost_site: Optional[str] = None
        self.cost_label: Optional[str] = None
        self.cost_analytic_flops: Optional[float] = None
        # (cache key, arg aval signature) pairs already captured: one
        # cache entry's jax.jit respecializes per input shape (the
        # serving engine's batch buckets), so "fresh build" alone would
        # miss every executable after the first
        self._cost_captured: set = set()

    @property
    def program_cache(self):
        return self._cache

    def compiled_text(self) -> str:
        """XLA-compiled HLO of the most recent call (requires the
        FLAGS_to_static_capture_lowered debug flag). Test/debug surface for
        asserting on the compiled program, e.g. that ZeRO sharding lowered
        to reduce-scatter rather than a full all-reduce."""
        if self._last_lowered is None:
            raise RuntimeError(
                "no lowered call captured; set "
                "paddle.set_flags({'FLAGS_to_static_capture_lowered': True}) "
                "and invoke the function first")
        jitted, state_specs, arg_specs = self._last_lowered
        return jitted.lower(state_specs, arg_specs).compile().as_text()

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = functools.partial(self.__call__, instance)
        functools.update_wrapper(bound, self._fn)
        return bound

    def __call__(self, *args, **kwargs):
        if not _ENABLED or trace_state() is not None:
            # nested to_static or globally disabled -> run eagerly/inline
            if self._iters > 1:
                return self._run_iters_eager(args, kwargs)
            return self._fn(*args, **kwargs)
        # one compiled call at a time, PROCESS-WIDE (ISSUE 15): every
        # StaticFunction threads the same global state registry (params,
        # RNG key) through trace + post-call rebinding — two threads (e.g.
        # two serving replicas in one process) interleaving here leak each
        # other's tracers into the registry. Reentrant, so a rebuild
        # recursion or a nested eager fallback on the SAME thread is fine;
        # uncontended for every single-threaded caller.
        # jit.call (ISSUE 25, mode "on" only): the whole compiled call.
        # Its child jit.dispatch is the jitted function alone, so its self
        # time is what this layer adds around every program: pre-trace
        # hooks, the registry walk, the cache key, the rebind of every
        # donated state tensor.
        with _INVOKE_LOCK, _trace.phase("jit.call"):
            return self._call_locked(*args, **kwargs)

    def _call_locked(self, *args, **kwargs):
        # runs on every call (not just cache misses): a state_dict load after
        # compilation must be reconciled into derived state (fp32 masters)
        # BEFORE the compiled step reads it — masters are carried state, so a
        # data refresh needs no retrace
        _run_pretrace_hooks_locked()

        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=_is_tensor)
        arg_arrays: List[Any] = []
        proto: List[Any] = []  # per-leaf: Tensor template | None (raw array) | _STATIC
        statics: List[Any] = []
        for leaf in leaves:
            if isinstance(leaf, Tensor):
                arg_arrays.append(leaf._data)
                proto.append(leaf)
            elif isinstance(leaf, (jax.Array, np.ndarray)) and not isinstance(leaf, np.bool_):
                arg_arrays.append(jnp.asarray(leaf))
                proto.append(None)
            else:
                statics.append(leaf)
                proto.append(_STATIC)

        if self._iters > 1:
            for arr in arg_arrays:
                if arr.ndim == 0 or arr.shape[0] != self._iters:
                    raise ValueError(
                        f"iters_per_call={self._iters}: every tensor argument "
                        f"must be stacked with leading dim {self._iters}, got "
                        f"shape {tuple(arr.shape)}")

        state_items = _state_registry.alive_items()  # [(regid, tensor)]
        try:
            static_key = tuple(statics)
            hash(static_key)
        except TypeError:
            static_key = tuple(repr(s) for s in statics)
        key = (treedef, static_key, tuple(rid for rid, _ in state_items))
        entry = self._cache.get(key)
        if entry is None:
            # hooks may touch the registry; recompute the key before building
            state_items = _state_registry.alive_items()
            key = (treedef, static_key, tuple(rid for rid, _ in state_items))
            entry = self._cache.get(key)
        if entry is _SEGMENTED:
            return self._run_segmented(args, kwargs, key)
        if entry is _FALLBACK:
            # memoized graph break (full_graph=False): skip re-tracing
            if self._iters > 1:
                return self._run_iters_eager(args, kwargs)
            return self._fn(*args, **kwargs)
        fresh_build = entry is None
        if fresh_build:
            _obs.inc("jit.cache_misses_total")
            entry = self._build(treedef, proto, statics,
                                [t for _, t in state_items],
                                self._given_up(args, proto))
            self._cache[key] = entry
        jitted, state_refs, holder = entry

        state_tensors = [r() for r in state_refs]
        if any(t is None for t in state_tensors):
            # a state tensor died between building and calling (rare): rebuild
            cost_hook = _cost_hook
            if cost_hook is not None:
                cost_hook("retire", self, key=key)
            self._cost_captured = {c for c in self._cost_captured
                                   if c[0] != key}
            del self._cache[key]
            return self.__call__(*args, **kwargs)
        if not fresh_build:
            # counted AFTER the dead-state check: a stale entry that forces
            # the rebuild recursion above is one logical call, not a hit
            # plus a miss
            _obs.inc("jit.cache_hits_total")

        # cost capture needs the argument specs from BEFORE the call —
        # donation deletes the very buffers the specs describe. Keyed on
        # (cache key, arg aval signature), not fresh_build: one entry's
        # jax.jit compiles a NEW executable per input shape (serving
        # batch buckets), and each deserves its own cost record.
        cost_hook = _cost_hook
        cost_specs = cost_key = None
        if cost_hook is not None:
            sig = tuple((tuple(a.shape), str(a.dtype)) for a in arg_arrays)
            if (key, sig) not in self._cost_captured:
                cost_key = (key, sig)
                cost_specs = ([_lower_spec(t._data) for t in state_tensors],
                              [_lower_spec(a) for a in arg_arrays])
        try:
            result = self._invoke(jitted, holder, state_tensors, arg_arrays,
                                  leaves, key)
            if fresh_build:
                # counted on SUCCESS, not at _build: a first call that
                # graph-breaks discards the executable without XLA ever
                # compiling it, and must not read as a compile
                _obs.inc("jit.compiles_total")
            if cost_specs is not None:
                self._cost_captured.add(cost_key)
                cost_hook("build", self, jitted=jitted,
                          state_specs=cost_specs[0],
                          arg_specs=cost_specs[1], key=key,
                          sig=cost_key[1])
            return result
        except Exception as e:
            if self._full_graph or not _is_trace_failure(e):
                # full-graph mode, or a genuine runtime failure (XLA execution
                # error, assert under jit): surface it — only trace failures
                # are graph breaks
                raise
            # SOT-style graph break (upstream python/paddle/jit/sot/):
            # tracing failed (tensor-dependent Python control flow,
            # unsupported op). Partial-graph capture: re-run through the
            # lazy segment executor — compiled segments around the break,
            # Python as the control-flow interpreter (core/lazy.py). Falls
            # back to plain eager only if segmenting itself fails.
            _obs.inc("jit.graph_breaks_total")
            if self._iters > 1:
                self._cache[key] = _FALLBACK
                self._warn_break(e, "eager execution (iters_per_call)")
                return self._run_iters_eager(args, kwargs)
            self._cache[key] = _SEGMENTED
            self._warn_break(e, "compiled-segment execution")
            return self._run_segmented(args, kwargs, key)

    def _warn_break(self, e, how: str) -> None:
        if not self._warned_fallback:
            import warnings
            warnings.warn(
                f"to_static(full_graph=False): tracing "
                f"{getattr(self._fn, '__name__', '?')} failed "
                f"({type(e).__name__}: {e}); falling back to {how}")
            self._warned_fallback = True

    def _run_segmented(self, args, kwargs, key):
        """Graph-break mode: execute through the lazy segment recorder —
        device work runs as cached compiled segments split at concrete
        reads; Python runs every call and owns the control flow."""
        from ..core import lazy as _lazy
        try:
            with _lazy.segment_mode():
                return self._fn(*args, **kwargs)
        except Exception as e:
            # segment_mode.__exit__ flushed whatever had been recorded, so
            # state mutations up to the failure are applied exactly once —
            # re-running the fn here would double-apply them, so we never
            # do. A LAZY-MACHINERY failure (an op touching the placeholder
            # in a way the recorder can't stage) downgrades FUTURE calls to
            # plain eager; genuine user errors keep the segmented path.
            if ("LazyValue" in str(e) or isinstance(e, NotImplementedError)
                    or isinstance(e, jax.errors.UnexpectedTracerError)):
                self._cache[key] = _FALLBACK
                import warnings
                warnings.warn(
                    f"to_static(full_graph=False): segmented execution of "
                    f"{getattr(self._fn, '__name__', '?')} cannot stage this "
                    f"function ({type(e).__name__}: {e}); later calls run "
                    "plain eager")
            raise

    def _invoke(self, jitted, holder, state_tensors, arg_arrays, leaves,
                key):
        state_arrays = [t._data for t in state_tensors]
        if _flags.flag("to_static_capture_lowered"):
            self._last_lowered = (jitted,
                                  [_lower_spec(a) for a in state_arrays],
                                  [_lower_spec(a) for a in arg_arrays])
        given = getattr(jitted, "given", ())
        if self._donate or given:
            # donated buffers must be unique: two state tensors aliasing one
            # jax.Array (or a state array that is also a plain argument) make
            # XLA reject the executable call on TPU. Copy the duplicates so
            # every donated slot owns its buffer — the given-up plain
            # arguments first, then the state.
            seen = {id(a) for i, a in enumerate(arg_arrays)
                    if i not in given}
            _own_buffers(arg_arrays, given, seen)
            if self._donate:
                _own_buffers(state_arrays, range(len(state_arrays)), seen)
        holder["traced"] = False
        try:
            # ``program`` names the jit.trace / jit.lower / jit.compile
            # written under this span (observability/compile_events.py). On
            # every call, not only a fresh build: one entry's jax.jit
            # compiles anew for each input shape
            with _trace.phase("jit.dispatch",
                              program=self.cost_label or self._name):
                out_arrays, new_state, mut_vals = jitted(state_arrays,
                                                         arg_arrays)
        except Exception as e:
            if holder["traced"] and _is_trace_failure(e):
                # pure_fn ran to its end, so this came out of lowering
                raise LoweringError(
                    f"{getattr(self._fn, '__name__', 'program')} traced but "
                    f"did not lower ({type(e).__name__}: {e})") from e
            raise
        for t, arr in zip(state_tensors, new_state):
            t._data = arr
        self._rebind(holder, mut_vals, leaves)
        return _wrap_outputs(out_arrays)

    def _run_iters_eager(self, args, kwargs):
        """Eager-mode equivalent of the scan: slice the K-stacked tensor args
        and run fn per step, stacking the outputs — so a debug run with
        to_static disabled keeps the compiled run's semantics."""
        def _is_sliceable(x):
            return (isinstance(x, Tensor) or
                    (isinstance(x, (jax.Array, np.ndarray))
                     and getattr(x, "ndim", 0) > 0))

        def slice_leaf(i):
            # slice the same leaves the compiled path scans over: Tensors AND
            # raw arrays (both land in arg_arrays there)
            return lambda x: x[i] if _is_sliceable(x) else x

        def stack_leaf(*xs):
            if isinstance(xs[0], Tensor):
                return Tensor(jnp.stack([x._data for x in xs]),
                              stop_gradient=True)
            if isinstance(xs[0], (jax.Array, np.ndarray)):
                return jnp.stack([jnp.asarray(x) for x in xs])
            return xs[0]

        outs = []
        for i in range(self._iters):
            a_i, k_i = jax.tree_util.tree_map(
                slice_leaf(i), (args, kwargs), is_leaf=_is_tensor)
            outs.append(self._fn(*a_i, **k_i))
        return jax.tree_util.tree_map(stack_leaf, *outs, is_leaf=_is_tensor)

    # -------------------------------------------------------------------------
    def _given_up(self, args, proto) -> Tuple[int, ...]:
        """Where the arrays under the ``donate_argnums`` arguments sit among
        the call's flat array arguments (leaves in flattening order, the
        static ones skipped). Fixed per cache entry, like ``proto``."""
        if not self._donate_argnums:
            return ()
        bounds = [0]
        for a in args:
            bounds.append(bounds[-1] + len(jax.tree_util.tree_flatten(
                a, is_leaf=_is_tensor)[0]))
        leaves = {j for i in self._donate_argnums if i < len(args)
                  for j in range(bounds[i], bounds[i + 1])}
        arrays = [j for j, p in enumerate(proto) if p is not _STATIC]
        return tuple(k for k, j in enumerate(arrays) if j in leaves)

    def _build(self, treedef, proto, statics, state_tensors, given=()):
        _obs.inc("jit.traces_total")
        if self._iters > 1:
            return self._build_scan(treedef, proto, statics, state_tensors)
        holder: Dict[str, Any] = {"spec": None}
        fn = self._fn
        state_refs = [weakref.ref(t) for t in state_tensors]
        state_ids = {id(t) for t in state_tensors}

        def pure_fn(state_arrays, arg_arrays):
            tensors = [r() for r in state_refs]
            saved_state = [t._data for t in tensors]
            for t, arr in zip(tensors, state_arrays):
                t._data = arr
            ts = TraceState()
            push_trace_state(ts)
            try:
                arg_pos = {}  # id(inner arg Tensor) -> leaf position
                args2, kwargs2 = _rebuild_args(proto, statics, arg_arrays,
                                               treedef, arg_pos)
                out = fn(*args2, **kwargs2)
                out_arrays = jax.tree_util.tree_map(
                    lambda x: x._data if isinstance(x, Tensor) else x, out,
                    is_leaf=_is_tensor)
                # all state is carried through (un-mutated entries become
                # input->output aliases under donation)
                new_state = [t._data for t in tensors]
                # extra mutated locations not covered by the state carry
                spec = []
                mut_vals = []
                for kind, ref in ts.mutations:
                    tt = ref()
                    if tt is None:
                        continue
                    if kind == "data":
                        if id(tt) in state_ids:
                            continue  # carried via new_state
                        val = tt._data
                    else:
                        g = tt._grad
                        val = None if g is None else g._data
                    if val is not None and not _is_tracer(val):
                        val = jnp.asarray(val)
                    if id(tt) in arg_pos:
                        # mutation of a traced ARG tensor: rebind onto the
                        # caller's tensor for that leaf position at call time
                        # (paddle parity: x.grad lands on the passed-in x)
                        spec.append((f"arg_{kind}", arg_pos[id(tt)]))
                    else:
                        spec.append((kind, ref))
                    mut_vals.append(val)
                holder["spec"] = spec
                holder["traced"] = True
                return out_arrays, new_state, mut_vals
            finally:
                pop_trace_state()
                ts.restore()
                for t, arr in zip(tensors, saved_state):
                    t._data = arr

        donate = (0,) if self._donate else ()
        if given:
            jitted = _GivenUp(pure_fn, given, donate)
        else:
            jitted = jax.jit(pure_fn, donate_argnums=donate)
        return jitted, state_refs, holder

    def _build_scan(self, treedef, proto, statics, state_tensors):
        """iters_per_call mode: scan the per-step fn over K-stacked args.

        Constraint: every per-step mutation must either be registered state
        (rides the scan carry) or resolve to None by step end (grads after
        ``clear_grad``) — anything else cannot escape the scan body.
        """
        holder: Dict[str, Any] = {"spec": None}
        fn = self._fn
        state_refs = [weakref.ref(t) for t in state_tensors]
        state_ids = {id(t) for t in state_tensors}

        def pure_fn(state_arrays, arg_arrays):
            tensors = [r() for r in state_refs]
            saved_state = [t._data for t in tensors]

            def body(carry, xs):
                for t, arr in zip(tensors, carry):
                    t._data = arr
                ts = TraceState()
                push_trace_state(ts)
                try:
                    args2, kwargs2 = _rebuild_args(proto, statics, xs, treedef)
                    out = fn(*args2, **kwargs2)
                    out_arrays = jax.tree_util.tree_map(
                        lambda x: x._data if isinstance(x, Tensor) else x, out,
                        is_leaf=_is_tensor)
                    spec = []
                    for kind, ref in ts.mutations:
                        tt = ref()
                        if tt is None:
                            continue
                        if kind == "data":
                            if id(tt) in state_ids:
                                continue
                            if _is_tracer(tt._data):
                                raise RuntimeError(
                                    "iters_per_call: the step mutates a "
                                    f"non-state tensor ({tt.name or 'unnamed'})"
                                    "; register it as state or drop "
                                    "iters_per_call")
                            continue  # concrete host-side write: ignore
                        g = tt._grad
                        if g is not None and _is_tracer(g._data):
                            raise RuntimeError(
                                "iters_per_call: gradients must be cleared "
                                "within the step (call opt.clear_grad()) so "
                                "no per-step value escapes the scan")
                        spec.append(("grad", ref))
                    holder["spec"] = spec
                    new_state = [t._data for t in tensors]
                    return new_state, out_arrays
                finally:
                    pop_trace_state()
                    ts.restore()
                    for t, arr in zip(tensors, saved_state):
                        t._data = arr

            final_state, outs = jax.lax.scan(body, list(state_arrays),
                                             list(arg_arrays),
                                             length=self._iters)
            mut_vals = [None] * len(holder["spec"] or [])
            holder["traced"] = True
            return outs, final_state, mut_vals

        donate = (0,) if self._donate else ()
        jitted = jax.jit(pure_fn, donate_argnums=donate)
        return jitted, state_refs, holder

    @staticmethod
    def _rebind(holder, mut_vals, leaves=None) -> None:
        spec = holder["spec"] or []
        for (kind, ref), val in zip(spec, mut_vals):
            if kind.startswith("arg_"):
                tt = leaves[ref] if leaves is not None else None
                kind = kind[4:]
            else:
                tt = ref()
            if tt is None or not isinstance(tt, Tensor):
                continue
            if kind == "data":
                if val is not None:
                    tt._data = val
            else:
                if val is None:
                    tt._grad = None
                elif tt._grad is None:
                    tt._grad = Tensor(val, stop_gradient=True)
                else:
                    tt._grad._data = val


def _own_buffers(arrays: List[Any], positions, seen: set) -> None:
    """Replace, in place, every array at ``positions`` whose buffer was
    already ``seen`` by a copy, and note the others as seen."""
    for i in positions:
        if id(arrays[i]) in seen:
            arrays[i] = jnp.copy(arrays[i])
        else:
            seen.add(id(arrays[i]))


class _GivenUp:
    """The jitted program of an entry whose flat array arguments at
    ``given`` the caller gives up. jax donates whole top-level arguments,
    so those arrays travel as a third one, donated beside the state; this
    keeps the plain entry's ``(state_arrays, arg_arrays)`` surface for the
    call, ``compiled_text()`` and the cost hook's ``lower``."""

    def __init__(self, fn, given: Tuple[int, ...], donate: Tuple[int, ...]):
        self.given = given

        def pure_fn(state_arrays, kept, gone):
            arrays = list(kept)
            for i, a in zip(given, gone):    # ascending: final positions
                arrays.insert(i, a)
            return fn(state_arrays, arrays)

        self._jitted = jax.jit(pure_fn, donate_argnums=donate + (2,))

    def _split(self, arrays):
        return ([a for i, a in enumerate(arrays) if i not in self.given],
                [arrays[i] for i in self.given])

    def __call__(self, state_arrays, arg_arrays):
        return self._jitted(state_arrays, *self._split(arg_arrays))

    def lower(self, state_specs, arg_specs):
        return self._jitted.lower(state_specs, *self._split(arg_specs))


class _StaticMarker:
    __slots__ = ()


_STATIC = _StaticMarker()


def _rebuild_args(proto, statics, arrays, treedef, arg_pos=None):
    """Reconstruct the traced call's (args, kwargs) from the flat pieces:
    per-leaf proto (Tensor template | None | _STATIC), static values, and the
    traced arrays. Shared by the single-step and scan build paths."""
    it_arr = iter(arrays)
    it_static = iter(statics)
    leaves = []
    for pos, p in enumerate(proto):
        if p is _STATIC:
            leaves.append(next(it_static))
        elif p is None:
            leaves.append(next(it_arr))
        else:
            t = Tensor(next(it_arr), stop_gradient=p.stop_gradient,
                       name=p.name)
            if arg_pos is not None:
                arg_pos[id(t)] = pos
            leaves.append(t)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _wrap_outputs(out):
    return jax.tree_util.tree_map(
        lambda x: Tensor(x, stop_gradient=True)
        if isinstance(x, jax.Array) else x, out)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              **kwargs):
    """``paddle.jit.to_static`` parity decorator."""

    sf_kwargs = {k: kwargs[k]
                 for k in ("iters_per_call", "donate_states", "full_graph",
                           "donate_argnums")
                 if k in kwargs}

    def decorate(fn):
        # Layers: wrap forward, return the layer (paddle semantics)
        from ..nn.layer import Layer
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec, build_strategy,
                                        backend, **sf_kwargs)
            return fn
        return StaticFunction(fn, input_spec, build_strategy, backend,
                              **sf_kwargs)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn=None):
    """Marker parity: functions excluded from capture simply run inline."""
    if fn is None:
        return lambda f: f
    return fn


def ignore_module(modules) -> None:
    """Parity no-op: our tracing never descends into foreign modules'
    internals anyway (jax handles them natively or they fail loudly)."""
