"""Whole-step static capture with buffer donation (ISSUE 11).

The survey's CINN→XLA thesis is that Paddle-on-TPU wins by compiling whole
PROGRAMS, not ops: the eager fast path (PR 2) amortizes per-op dispatch
behind a signature-keyed compiled-op cache, but a train step still pays one
host dispatch per op plus inter-op materialization. This module captures
the ENTIRE train step — forward, backward, optimizer update (q8/Adam
including the fused Pallas path), with the LR schedule riding as carried
state — into ONE ``jax.jit`` program with ``donate_argnums`` on every
registered state tensor (parameters, optimizer moments/masters, the RNG
key), built on the ``to_static`` functionalization (PR 10's ``TrainState``
already enumerates every piece of carried state, which is what makes the
donation safe: each state tensor is rebound to a live output buffer after
every call).

:class:`CapturedStep` (surfaced as ``paddle_tpu.jit.capture_step``) is the
per-run handle; ``hapi.Model.fit`` and ``resilience.TrainingSupervisor``
route over it behind ``PADDLE_TPU_STEP_CAPTURE``:

* ``auto`` (default) — capture when safe, bypass cleanly (and visibly)
  when a functionalization seam is already live (``to_static`` trace, lazy
  segment recording, static-graph capture — the PR 2 "capture" bypass
  accounting counts the per-op side of this), when an input payload is
  symbolic, when a fault schedule targets the in-trace ``dispatch.*``
  seams (injected per-op faults must keep firing per op, not once at
  trace), or when the step cannot trace (memoized per signature).
* ``off`` — the eager tier, unchanged dispatch; the debug escape hatch.

Re-traces are keyed on the PR 2 structural signature (code objects +
hashable closure state of the step/update closures) + the runtime
flags-epoch + input avals, so a shape change, a mutated closure scalar, or
a ``set_flags`` write can never serve a stale executable. Counters:
``train.capture_hits_total`` / ``train.capture_retraces_total`` /
``train.capture_bypasses_total{reason}`` and the
``train.capture_donated_bytes`` gauge.

NaN-gating (the supervisor contract "a non-finite loss withholds the
update"): when ``update_fn`` is folded in with ``nan_gate=True``, the
update's state writes are selected per-tensor with
``where(isfinite(loss), new, old)`` INSIDE the program — a skipped batch
leaves parameters, moments, step count and RNG key bitwise untouched,
exactly like the eager skip path, without a host round-trip.

Numerics contract (measured, honest): a captured step is bitwise
DETERMINISTIC — same program, same inputs, same bits — so restart/resume
within the captured tier is bit-identical (the PR 10 guarantee). A
captured step is NOT bitwise-equal to the eager tier: XLA contracts
``a*x + b*y`` chains to FMA inside a fused whole-step kernel, which per-op
dispatch cannot (micro-repro: ``jit(lambda: b1*m + (1-b1)*g)`` differs
from the op-by-op value by 1 ulp; ``--xla_allow_excess_precision=false``
does not restore equality). Eager↔captured parity is therefore pinned at
ulp-scale tolerance in tests, and a checkpoint must be resumed under the
same tier it was written from for bitwise continuation.

Host-written state (the stale-constant trap): a per-step ``update_fn``
that computes a state value in PYTHON and writes it (the classic case:
``scheduler.step()`` inside the update — ``_sync_lr_tensor`` writes
``opt_lr`` from a host float) would bake the trace-time value into the
executable and silently serve it forever. Capture detects any registered
state tensor whose post-step payload is concrete (not a tracer) DURING
tracing and raises :class:`HostStateWriteError` before anything executes
— loud and uniform, never stale. The fix is to keep ``scheduler.step()``
outside the captured step: the LR VALUE rides the program as carried
state (``opt._lr_t``), so the host-side schedule advance between steps is
picked up by the next call with no retrace.
"""

from __future__ import annotations

import os
import threading
import types
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from .. import observability as _obs
from ..observability import trace as _trace
from ..resilience import faults as _faults
from . import dispatch_cache as _dcache
from . import lazy as _lazy
from . import tensor as _tensor_mod
from . import tracing as _tracing
from .tensor import Tensor, _is_tracer, _state_registry

__all__ = ["CapturedStep", "HostStateWriteError", "capture_step", "mode",
           "capture_info", "stats_clear"]


class HostStateWriteError(RuntimeError):
    """The captured step writes a registered state tensor from a
    host-computed (concrete) value. Replaying the compiled program would
    serve the trace-time constant forever — e.g. ``scheduler.step()``
    inside the captured update freezes the LR. Move the host-side write
    outside the captured step (the LR schedule's VALUE already rides as
    carried state), or run with ``PADDLE_TPU_STEP_CAPTURE=off``."""


_VALID_MODES = ("auto", "off")


def mode() -> str:
    """Resolve ``PADDLE_TPU_STEP_CAPTURE`` (default ``auto``)."""
    m = os.environ.get("PADDLE_TPU_STEP_CAPTURE", "auto").strip().lower()
    if m in _VALID_MODES:
        return m
    if m in ("0", "false", "no", "disable", "disabled"):
        return "off"
    return "auto"


# process-global counters (always maintained — the observability mirror
# no-ops while metrics are disabled, like the PR 2 dispatch-cache stats)
_LOCK = threading.Lock()
_STATS: Dict[str, Any] = {"hits": 0, "retraces": 0, "bypasses": {},
                          "donated_bytes": 0}


def _count(kind: str, reason: Optional[str] = None) -> None:
    with _LOCK:
        if kind == "bypass":
            b = _STATS["bypasses"]
            b[reason] = b.get(reason, 0) + 1
        else:
            _STATS[kind] += 1
    if kind == "bypass":
        _obs.inc("train.capture_bypasses_total", reason=reason or "other")
    elif kind == "hits":
        _obs.inc("train.capture_hits_total")
    else:
        _obs.inc("train.capture_retraces_total")


def capture_info() -> Dict[str, Any]:
    with _LOCK:
        return {"hits": _STATS["hits"], "retraces": _STATS["retraces"],
                "bypasses": dict(_STATS["bypasses"]),
                "donated_bytes": _STATS["donated_bytes"]}


def stats_clear() -> None:
    with _LOCK:
        _STATS.update(hits=0, retraces=0, bypasses={}, donated_bytes=0)


# ---------------------------------------------------------------------------
# structural signature (the PR 2 fingerprint, made total)
# ---------------------------------------------------------------------------

class _IdKey:
    """Identity wrapper for closure values the PR 2 fingerprint refuses
    (arrays, tensors, layers, optimizers): hashable, equal only to itself,
    and holding a strong ref so the id can never be reused while the key
    lives. Identity keying is stable for per-run closures — a NEW closure
    over a NEW model simply keys a new program."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _IdKey) and other.obj is self.obj


def _lenient_fp(v, depth: int = 0):
    """Value fingerprint: content-keyed where the PR 2 rules allow (python
    scalars, tuples, dicts — a mutated closure scalar retraces rather than
    serving a stale program), identity-keyed where they bypass."""
    try:
        return _dcache._fp_value(v, depth)
    except (_dcache._Bypass, TypeError):
        return _IdKey(v)


def _structural_sig(fn) -> Any:
    """The step/update closure's structural signature: code object +
    per-cell closure fingerprints + defaults (the PR 2 ``_fp_fn`` walk,
    with the lenient per-value fallback above)."""
    if fn is None:
        return None
    if not isinstance(fn, types.FunctionType):
        return _IdKey(fn)
    parts = [fn.__code__]
    for cell in fn.__closure__ or ():
        try:
            parts.append(_lenient_fp(cell.cell_contents))
        except ValueError:  # empty cell
            parts.append(("E",))
    if fn.__defaults__:
        parts.append(tuple(_lenient_fp(d) for d in fn.__defaults__))
    if fn.__kwdefaults__:
        parts.append(tuple(sorted(
            (k, _lenient_fp(d)) for k, d in fn.__kwdefaults__.items())))
    return tuple(parts)


def _loss_array(out):
    """The loss payload out of whatever the step closure returned (the
    supervisor's ``_loss_value`` coercion, minus the host read)."""
    if isinstance(out, (tuple, list)):
        if not out:
            raise ValueError("captured step returned an empty loss sequence")
        out = out[0]
    if out is None:
        raise ValueError("captured step must return the step's loss")
    return out._data if isinstance(out, Tensor) else out


def _is_sym(a) -> bool:
    return _is_tracer(a) or type(a).__name__ == "LazyValue"


# ---------------------------------------------------------------------------
# the captured step
# ---------------------------------------------------------------------------

class CapturedStep:
    """One train step as one compiled, donated-buffer XLA program.

    ``step_fn(*args) -> loss`` (or ``(loss, extras...)``) runs forward +
    backward. ``update_fn`` (optional) is folded INTO the program —
    callers that fold it must keep it pure tensor math over carried state
    (the optimizer update qualifies; per-step host Python like
    ``scheduler.step()`` does not and raises
    :class:`HostStateWriteError`). ``nan_gate=True`` makes the folded
    update conditional on ``isfinite(loss)`` in-program (the supervisor's
    skip-batch contract). ``iters_per_call`` scans the step over K-stacked
    args inside one program (the bench's scan-over-steps pattern;
    incompatible with ``nan_gate``).

    Bypasses run the step eagerly with identical semantics (update applied
    iff the gate passes), so callers never branch on the tier.
    """

    _MAX_PROGRAMS = 8  # distinct (signature, flags-epoch, avals) programs

    def __init__(self, step_fn: Callable, *,
                 update_fn: Optional[Callable[[], None]] = None,
                 clear_fn: Optional[Callable[[], None]] = None,
                 nan_gate: bool = False, iters_per_call: int = 1,
                 donate: bool = True, mode: Optional[str] = None,
                 label: str = "train"):
        if nan_gate and update_fn is None:
            raise ValueError("nan_gate requires update_fn (the gate decides "
                             "whether the folded update applies)")
        if nan_gate and iters_per_call > 1:
            raise ValueError("nan_gate is a per-step host contract; it "
                             "cannot ride a scanned multi-step program")
        self._step_fn = step_fn
        self._update_fn = update_fn
        self._clear_fn = clear_fn
        self._nan_gate = bool(nan_gate)
        self._iters = int(iters_per_call)
        self._donate = bool(donate)
        self._mode = globals()["mode"]() if mode is None else mode
        self._label = label
        self._programs: "OrderedDict[Any, Any]" = OrderedDict()
        self._dead: set = set()  # keys whose trace failed: eager forever
        self._warned = False
        self.stats = {"hits": 0, "retraces": 0, "bypasses": {}}
        self.donated_bytes = 0

    @property
    def applies_update(self) -> bool:
        """True when the optimizer update is folded into this step (the
        caller must NOT apply it again)."""
        return self._update_fn is not None

    # -- accounting ----------------------------------------------------------
    def _note(self, kind: str, reason: Optional[str] = None) -> None:
        if kind == "bypass":
            b = self.stats["bypasses"]
            b[reason] = b.get(reason, 0) + 1
        else:
            self.stats[kind] += 1
        _count(kind if kind != "bypass" else "bypass", reason)

    # -- the call ------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        reason = self._bypass_reason(args, kwargs)
        if reason is not None:
            self._note("bypass", reason)
            return self._eager_step(args, kwargs)
        key = self._key(args, kwargs)
        if key is None:
            self._note("bypass", "symbolic_input")
            return self._eager_step(args, kwargs)
        if key in self._dead:
            self._note("bypass", "untraceable")
            return self._eager_step(args, kwargs)
        sf = self._programs.get(key)
        fresh = sf is None
        if fresh:
            from ..jit.to_static import StaticFunction
            sf = StaticFunction(self._program_fn, donate_states=self._donate,
                                iters_per_call=self._iters)
            # ISSUE 16: file this program's cost record under the training
            # step, not a generic "jit" entry
            sf.cost_site = "train.step"
            sf.cost_label = self._label
            self._programs[key] = sf
            if len(self._programs) > self._MAX_PROGRAMS:
                # the popped StaticFunction's weakref finalizer retires its
                # cost records with it
                self._programs.popitem(last=False)
            self._set_donated_bytes()
        else:
            self._programs.move_to_end(key)
        try:
            # span-discipline: this __call__ is a fast_path_roots entry, so
            # even the disabled-mode span probe stays behind the explicit
            # enabled() guard (the _op_metrics_hook discipline)
            if _trace.enabled():
                with _trace.span("train.captured_step", label=self._label,
                                 fresh=fresh):
                    out = sf(*args, **kwargs)
            else:
                out = sf(*args, **kwargs)
        except HostStateWriteError:
            raise  # deliberate, loud: never demote to a silently-stale tier
        except Exception as e:
            from ..jit.to_static import _is_trace_failure
            if not _is_trace_failure(e):
                raise  # lowering failure (a refused kernel arrives as
                #        LoweringError) or runtime failure (XLA error,
                #        device fault): surface — the supervisor's
                #        restore-last-good owns recovery
            # the step cannot trace (tensor-dependent python control flow,
            # host read mid-step): memoize and stay eager for this signature
            # — trace-time tensor state was restored by the functionalizer,
            # so the eager re-run below is the step's one real execution
            self._programs.pop(key, None)
            self._dead.add(key)
            self._note("bypass", "untraceable")
            if not self._warned:
                self._warned = True
                warnings.warn(
                    f"step capture ({self._label}): the step cannot be "
                    f"captured ({type(e).__name__}: {e}); this signature "
                    f"runs on the eager tier")
            return self._eager_step(args, kwargs)
        self._note("retraces" if fresh else "hits")
        return out

    # -- bypass policy -------------------------------------------------------
    def _bypass_reason(self, args, kwargs) -> Optional[str]:
        if self._mode == "off":
            return "off"
        if (_tracing.trace_state() is not None or _lazy.active()
                or _tensor_mod._op_graph_hook is not None):
            # a functionalization seam is already live: capture-inside-
            # capture would fight over the same mutation log (the per-op
            # half of this is the PR 2 "capture" bypass accounting)
            return "capture_seam"
        sched = _faults._SCHEDULE
        if sched is not None and any(
                s.startswith("dispatch.") for s in sched.sites()):
            # injected per-op faults must keep firing per op; inside a
            # compiled program the dispatch seams run only at trace time
            return "fault_injection"
        return None

    def _key(self, args, kwargs):
        # the structural signature is rebuilt per call (the PR 2 contract:
        # closure CONTENT keys the program — a mutated python scalar in the
        # step's closure must retire the executable, never serve the baked
        # constant); identity-keyed leaves make the walk cheap, and it runs
        # once per STEP, not per op
        fn_sig = (_structural_sig(self._step_fn),
                  _structural_sig(self._update_fn),
                  self._nan_gate, self._iters)
        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        sigs = []
        for leaf in leaves:
            if isinstance(leaf, Tensor):
                a = leaf._data
                if _is_sym(a):
                    return None
                sigs.append(("t", a.shape, str(a.dtype)))
            elif isinstance(leaf, (jax.Array, np.ndarray)):
                if _is_sym(leaf):
                    return None
                sigs.append(("a", leaf.shape, str(leaf.dtype)))
            else:
                try:
                    hash(leaf)
                    sigs.append(("s", leaf))
                except TypeError:
                    sigs.append(("s", repr(leaf)))
        return (fn_sig, _flags.epoch(), treedef, tuple(sigs))

    # -- program body (runs under the to_static functionalization) -----------
    def _program_fn(self, *args, **kwargs):
        states = _state_registry.alive()
        entry = {id(t): t._data for t in states}
        out = self._step_fn(*args, **kwargs)
        if self._update_fn is not None:
            if self._nan_gate:
                # gate on the SAME value the supervisor (and the eager
                # bypass below) reads — the first element of the loss —
                # not an all() over a vector loss: the tiers must agree
                # on whether the update applied, and the supervisor's
                # skip accounting keys on that exact scalar
                finite = jnp.isfinite(jnp.ravel(jnp.asarray(
                    _loss_array(out), jnp.float32))[0])
                pre = [(t, t._data) for t in states]
                self._update_fn()
                for t, old in pre:
                    new = t._data
                    if new is not old and not _is_tracer(new):
                        continue  # reported by the walk below
                    if new is not old:
                        # withheld update == bitwise-untouched state: the
                        # eager skip path's exact contract, in-program
                        t._set_data(jnp.where(finite, new, old))
            else:
                self._update_fn()
        bad = [t.name or "unnamed" for t in states
               if t._data is not entry.get(id(t), t._data)
               and not _is_tracer(t._data)]
        if bad:
            raise HostStateWriteError(
                f"captured step writes state from host-computed values "
                f"({', '.join(sorted(bad))}); replaying the program would "
                f"serve the trace-time constant forever — keep per-step "
                f"host writes (e.g. scheduler.step()) outside the captured "
                f"step, or set PADDLE_TPU_STEP_CAPTURE=off")
        return out

    # -- eager tier ----------------------------------------------------------
    def _eager_step(self, args, kwargs):
        if self._iters > 1:
            return self._eager_iters(args, kwargs)
        out = self._step_fn(*args, **kwargs)
        if self._update_fn is not None:
            if self._nan_gate:
                lossf = float(np.asarray(_loss_array(out)).ravel()[0])
                if np.isfinite(lossf):
                    self._update_fn()
                elif self._clear_fn is not None:
                    self._clear_fn()
            else:
                self._update_fn()
        return out

    def _eager_iters(self, args, kwargs):
        """Slice the K-stacked args and run the step per iteration (the
        ``StaticFunction._run_iters_eager`` semantics, so a bypassed scan
        keeps the compiled run's meaning)."""
        def is_leaf(x):
            return isinstance(x, Tensor)

        def slice_at(i):
            def f(x):
                if isinstance(x, Tensor):
                    return x[i]
                if isinstance(x, (jax.Array, np.ndarray)) \
                        and getattr(x, "ndim", 0) > 0:
                    return x[i]
                return x
            return f

        outs = []
        for i in range(self._iters):
            a_i, k_i = jax.tree_util.tree_map(
                slice_at(i), (args, kwargs), is_leaf=is_leaf)
            outs.append(self._eager_step_once(a_i, k_i))

        def stack(*xs):
            if isinstance(xs[0], Tensor):
                return Tensor(jnp.stack([x._data for x in xs]),
                              stop_gradient=True)
            if isinstance(xs[0], (jax.Array, np.ndarray)):
                return jnp.stack([jnp.asarray(x) for x in xs])
            return xs[0]

        return jax.tree_util.tree_map(stack, *outs, is_leaf=is_leaf)

    def _eager_step_once(self, args, kwargs):
        out = self._step_fn(*args, **kwargs)
        if self._update_fn is not None:
            self._update_fn()
        return out

    # -- observability -------------------------------------------------------
    def compiled_text(self) -> str:
        """XLA-compiled HLO of the most recently used program — the
        ``StaticFunction.compiled_text`` debug surface (set
        ``FLAGS_to_static_capture_lowered`` before the call): what
        ``chip_smoke.py`` reads to prove the Pallas kernels are IN the
        step the chip ran."""
        if not self._programs:
            raise RuntimeError("no captured program: the step has not run, "
                               "or every call bypassed capture")
        return next(reversed(self._programs.values())).compiled_text()

    def _set_donated_bytes(self) -> None:
        if not self._donate:
            return
        total = 0
        for t in _state_registry.alive():
            a = t._data
            shape = getattr(a, "shape", None)
            if shape is None or _is_sym(a):
                continue
            n = 1
            for s in shape:
                n *= int(s)
            total += n * jnp.dtype(a.dtype).itemsize
        self.donated_bytes = total
        with _LOCK:
            _STATS["donated_bytes"] = total
        _obs.set_gauge("train.capture_donated_bytes", float(total))


def capture_step(step_fn: Callable, *,
                 update_fn: Optional[Callable[[], None]] = None,
                 clear_fn: Optional[Callable[[], None]] = None,
                 nan_gate: bool = False, iters_per_call: int = 1,
                 donate: bool = True) -> CapturedStep:
    """Capture a train step as ONE donated-buffer XLA program.

    ``paddle_tpu.jit.capture_step`` — see :class:`CapturedStep`. Honors
    ``PADDLE_TPU_STEP_CAPTURE`` (``off`` keeps every call on the eager
    debug tier with identical semantics)."""
    return CapturedStep(step_fn, update_fn=update_fn, clear_fn=clear_fn,
                        nan_gate=nan_gate, iters_per_call=iters_per_call,
                        donate=donate)
