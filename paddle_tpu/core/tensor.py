"""Tensor: an imperative, autograd-capable wrapper over ``jax.Array``.

Parity surface: ``paddle.Tensor`` (upstream: paddle/phi/api/include/tensor.h,
pybind eager tensor in paddle/fluid/pybind/eager.cc, method surface in
python/paddle/tensor/). TPU-native design: the payload is always a jax array
(or a jax tracer while ``to_static`` is tracing); every op goes through one
dispatch function, ``apply``, which is the analogue of the reference's
generated ``*_ad_func`` + Phi API path — it handles AMP autocast, autograd
tape recording (via ``jax.vjp``), trace-state read logging, and NaN checks.
"""

from __future__ import annotations

import itertools
import numbers
import time as _time
import weakref
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from .. import device as _device
from ..resilience.faults import fault_point as _fault_point
from . import dtype as _dtype
from . import dispatch_cache as _dcache
from . import fallback as _fallback
from . import lazy as _lazy
from . import tracing as _tracing
from .autograd import GradNode, backward as _backward

from jax.core import Tracer as _Tracer

__all__ = ["Tensor", "Parameter", "to_tensor", "apply",
           "register_tensor_method", "TraceBreakError"]


class TraceBreakError(RuntimeError):
    """A concrete host-side read (``.numpy()``, ``float()``, ``bool()``) hit a
    traced value. Under ``to_static(full_graph=False)`` this is a graph break
    (eager fallback / segment boundary); under full_graph=True it surfaces."""


def _is_tracer(x) -> bool:
    return isinstance(x, _Tracer)


class RemovableHandle:
    # itertools.count is a single C-level atomic step: hook registration from
    # dataloader worker threads can't mint duplicate ids the way the old
    # unlocked ``_next_id += 1`` read-modify-write could
    _id_counter = itertools.count()

    def __init__(self, hooks: dict):
        self._hooks = hooks
        self.hook_id = next(RemovableHandle._id_counter)

    def remove(self) -> None:
        self._hooks.pop(self.hook_id, None)


class Tensor:
    # __dict__ is included deliberately: paddle code (and users) attach
    # ad-hoc attributes to tensors (is_distributed, placements, ...)
    __slots__ = (
        "_data", "stop_gradient", "_grad", "_grad_node", "_grad_index",
        "name", "persistable", "trainable", "_hooks", "__weakref__", "__dict__",
    )

    # let binary dunders win over numpy array ops
    __array_priority__ = 100

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        self._data = data
        if type(data).__name__ == "LazyValue":  # cheap check, hot path
            data.owners.add(self)
        self.stop_gradient = stop_gradient
        self._grad: Optional["Tensor"] = None
        self._grad_node: Optional[GradNode] = None
        self._grad_index: int = 0
        self.name = name
        self.persistable = False
        self.trainable = True
        self._hooks: dict = {}
        self._version = 0  # bumped on _set_data; lets derived state (AMP
        #                    masters) detect external writes (state_dict load)

    # --- payload mutation (the single write seam; trace-visible) ------------
    def _set_data(self, value) -> None:
        ts = _tracing.trace_state()
        if ts is not None:
            ts.record_mutation("data", self)
        if type(value).__name__ == "LazyValue":
            value.owners.add(self)
        self._data = value
        self._version += 1

    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, value: Optional["Tensor"]) -> None:
        ts = _tracing.trace_state()
        if ts is not None:
            ts.record_mutation("grad", self)
        self._grad = value

    # --- metadata -----------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    def shape_tuple(self) -> Tuple[int, ...]:
        """``shape`` without the per-access list build: the payload's shape
        tuple as-is. Hot-path consumers (dispatch-cache key extraction)
        use this so metadata reads don't allocate."""
        return self._data.shape

    @property
    def ndim(self) -> int:
        return self._data.ndim

    ndimension = ndim

    @property
    def dtype(self):
        return jnp.dtype(self._data.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self):
        d = getattr(self._data, "devices", None)
        if d is None or _is_tracer(self._data):
            return _device.current_place()
        dev = next(iter(self._data.devices()))
        return _device.Place(dev.platform, dev.id)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def numel(self) -> int:
        return self.size

    def element_size(self) -> int:
        return self.dtype.itemsize

    @property
    def itemsize(self) -> int:
        return self.dtype.itemsize

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    def is_sparse(self) -> bool:
        # method, not property: the reference API (and this repo's sparse
        # classes) spell it t.is_sparse()
        return False

    def data_ptr(self) -> int:
        """Opaque buffer identity (reference: device pointer). PJRT exposes
        the device address only on some backends; fall back to the buffer
        object's identity — stable for aliasing checks, not arithmetic."""
        try:
            return int(self._data.unsafe_buffer_pointer())
        except Exception:
            return id(self._data)

    def dim(self) -> int:
        return self.ndim

    # --- host interop -------------------------------------------------------
    def numpy(self) -> np.ndarray:
        if _is_tracer(self._data):
            raise TraceBreakError(
                "Tensor.numpy() is not available while tracing "
                "inside paddle.jit.to_static")
        if type(self._data).__name__ == "LazyValue":
            # concrete read of a pending value: segment boundary — flush the
            # recorded graph (the SOT graph-break point)
            if self._data.array is None:
                _lazy.flush()
            if type(self._data).__name__ == "LazyValue":
                if self._data.array is None:
                    # the flush failed (or this value's segment flushed while
                    # it had no live owner): surface a clear error instead of
                    # silently degrading to a 0-d object array of None
                    raise RuntimeError(
                        "lazy tensor was never materialized: its recorded "
                        "segment failed to flush or flushed without a live "
                        "owner; re-run the producing op eagerly")
                self._data = self._data.array
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        return self.numpy().item(*args)

    def tolist(self):
        return self.numpy().tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.item())

    def __index__(self):
        return int(self.item())

    def __len__(self):
        if not self._data.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    def __repr__(self):
        sg = self.stop_gradient
        if _is_tracer(self._data):
            body = f"<traced {self._data.aval}>"
        else:
            body = np.array2string(np.asarray(self._data), separator=", ")
        return (f"Tensor(shape={self.shape}, dtype={_dtype.dtype_name(self.dtype)}, "
                f"place={self.place}, stop_gradient={sg},\n       {body})")

    # --- autograd -----------------------------------------------------------
    def backward(self, grad_tensor: Optional["Tensor"] = None, retain_graph: bool = False):
        _backward([self], [grad_tensor] if grad_tensor is not None else None,
                  retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self.grad = None

    clear_gradient = clear_grad

    def zero_grad(self) -> None:
        self.grad = None

    def register_hook(self, hook: Callable) -> RemovableHandle:
        h = RemovableHandle(self._hooks)
        self._hooks[h.hook_id] = hook
        return h

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self._grad_index = 0
        self.stop_gradient = True
        return self

    @property
    def requires_grad(self) -> bool:
        return not self.stop_gradient

    @requires_grad.setter
    def requires_grad(self, v: bool) -> None:
        self.stop_gradient = not v

    # --- device movement ----------------------------------------------------
    def to(self, *args, **kwargs) -> "Tensor":
        device = kwargs.pop("device", None)
        dtype = kwargs.pop("dtype", None)
        blocking = kwargs.pop("blocking", None)  # noqa: F841  (async is native)
        for a in args:
            if isinstance(a, (str, _device.Place)):
                device = a
            else:
                dtype = a
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            place = device if isinstance(device, _device.Place) else _parse_place(device)
            if out is self:
                out = Tensor(self._data, stop_gradient=self.stop_gradient, name=self.name)
                out._grad_node, out._grad_index = self._grad_node, self._grad_index
            if not _is_tracer(out._data):
                out._data = _device.device_put(out._data, place)
        return out

    def cpu(self) -> "Tensor":
        return self.to(device="cpu")

    def cuda(self, device_id=None) -> "Tensor":
        return self.to(device="tpu")

    def tpu(self) -> "Tensor":
        return self.to(device="tpu")

    def pin_memory(self) -> "Tensor":
        return self

    def clone(self) -> "Tensor":
        return apply("clone", jnp.copy, self)

    # --- misc parity --------------------------------------------------------
    def copy_(self, other: "Tensor") -> "Tensor":
        src = other._data if isinstance(other, Tensor) else jnp.asarray(other)
        self._set_data(jnp.broadcast_to(src, self._data.shape).astype(self._data.dtype))
        return self

    def set_value(self, value) -> None:
        self.copy_(value if isinstance(value, Tensor) else to_tensor(value))

    def get_tensor(self):  # LoDTensor parity shim
        return self

    def value(self):
        return self

    def _rebind(self, out: "Tensor") -> "Tensor":
        """Adopt another tensor's payload + grad linkage (in-place op seam)."""
        self._set_data(out._data)
        self._grad_node = out._grad_node
        self._grad_index = out._grad_index
        self.stop_gradient = out.stop_gradient
        return self


class Parameter(Tensor):
    """Trainable tensor (parity: paddle Parameter / EagerParamBase)."""

    __slots__ = ("optimize_attr", "regularizer", "is_distributed", "need_clip")

    _param_counter = 0

    def __init__(self, data, name: Optional[str] = None, trainable: bool = True):
        if name is None:
            name = f"param_{Parameter._param_counter}"
            Parameter._param_counter += 1
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True
        _state_registry.register(self)

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


class _StateRegistry:
    """All live parameters / optimizer accumulators / RNG states.

    ``to_static`` consults this to decide which concrete tensors may legally
    become jit inputs (anything else that is read gets baked as a constant).
    """

    def __init__(self):
        self._items = weakref.WeakValueDictionary()
        self._next = 0

    def register(self, t: Tensor) -> None:
        self._items[self._next] = t
        self._next += 1

    def alive(self):
        return [t for _, t in sorted(self._items.items())]

    def alive_items(self):
        """[(registration id, tensor)] — ids are never reused, so they make a
        stable cache key distinguishing same-length registries over time."""
        return sorted(self._items.items())


_state_registry = _StateRegistry()


def register_state_tensor(t: Tensor) -> None:
    _state_registry.register(t)


def _parse_place(device) -> _device.Place:
    if isinstance(device, _device.Place):
        return device
    dev = str(device).lower()
    if dev in ("gpu", "cuda", "xpu", "tpu"):
        return _device.TPUPlace() if _device.is_compiled_with_tpu() else _device.CPUPlace()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        return _device.Place("tpu" if kind in ("gpu", "cuda", "tpu", "xpu") else kind, int(idx))
    return _device.Place(dev, 0)


# ---------------------------------------------------------------------------
# op dispatch
# ---------------------------------------------------------------------------

def _autocast_targets(op_name: str, arrays):
    """Per-input cast target dtypes for the active autocast state (or None).

    Returns None when no casting applies. The actual cast happens INSIDE the
    vjp'd function so the cast itself is differentiated — cotangents then
    arrive in each producer's original dtype.
    """
    st = _tracing.amp_state()
    if st is None or not st.enable:
        return None
    low = st.dtype
    fp32 = jnp.float32

    if st.level == "O2":
        target = fp32 if op_name in st.black_set else low
    elif op_name in st.white_set:
        target = low
    elif op_name in st.black_set:
        target = fp32
    else:
        return None
    out = [target if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != target
           else None for a in arrays]
    return out if any(t is not None for t in out) else None


# Set by paddle_tpu.profiler while a Profiler window is recording; called as
# hook(op_name, t0, t1) after each dispatch. None ⇒ zero overhead.
_op_profile_hook: Optional[Callable[[str, float, float], None]] = None

# Set by paddle_tpu.observability while metrics are enabled; same signature
# and same zero-overhead contract as the profiler hook (the disabled path
# pays only the is-None probes below).
_op_metrics_hook: Optional[Callable[[str, float, float], None]] = None

# Set by paddle_tpu.observability.trace while PADDLE_TPU_TRACE=on; same
# signature and zero-overhead contract — per-op events land in the trace
# buffer so a Chrome export shows where each eager step's time went.
_op_trace_hook: Optional[Callable[[str, float, float], None]] = None

# Set by paddle_tpu.static while static-graph mode is capturing; called as
# hook(op_name, pure_fn, tensor_inputs, out_tensors) after each dispatch so
# the Program can record a replayable op node. None ⇒ zero overhead.
_op_graph_hook: Optional[Callable] = None


def _lazy_apply(op_name, f, tensor_inputs, arrays, needs_grad):
    """Segment-mode dispatch (full_graph=False partial-graph capture): the
    op is RECORDED, outputs are LazyValue placeholders, and the tape node
    carries only pure_fn — backward re-dispatches through apply() so the
    gradient ops land in the (compiled) segment too."""
    out_lazies, multi = _lazy.record(op_name, f, arrays)
    out_tensors = []
    if needs_grad:
        node = GradNode(op_name, None, tensor_inputs, len(out_lazies),
                        tuple((lv.aval.shape, lv.aval.dtype)
                              for lv in out_lazies),
                        pure_fn=f, multi_out=multi)
        for i, lv in enumerate(out_lazies):
            t = Tensor(lv, stop_gradient=False)
            t._grad_node = node
            t._grad_index = i
            out_tensors.append(t)
    else:
        for lv in out_lazies:
            out_tensors.append(Tensor(lv, stop_gradient=True))
    if multi:
        return tuple(out_tensors)
    return out_tensors[0]


def apply(op_name: str, fn: Callable, *tensor_inputs: Tensor,
          differentiable: bool = True, amp: bool = True, **static_kwargs) -> Any:
    """Dispatch one op: the TPU analogue of ad_func → Phi API → kernel.

    ``fn`` is a pure jax function over arrays. Tensor inputs are unwrapped,
    autocast applied, and — when grad is enabled and some input requires grad
    — the op is linearized with ``jax.vjp`` and a ``GradNode`` recorded.
    """
    prof_hook = _op_profile_hook
    metrics_hook = _op_metrics_hook
    trace_hook = _op_trace_hook
    if prof_hook is not None or metrics_hook is not None \
            or trace_hook is not None:
        _t0 = _time.perf_counter()
        try:
            return _apply_impl(op_name, fn, *tensor_inputs,
                               differentiable=differentiable, amp=amp,
                               **static_kwargs)
        finally:
            _t1 = _time.perf_counter()
            if prof_hook is not None:
                prof_hook(op_name, _t0, _t1)
            if metrics_hook is not None:
                metrics_hook(op_name, _t0, _t1)
            if trace_hook is not None:
                trace_hook(op_name, _t0, _t1)
    return _apply_impl(op_name, fn, *tensor_inputs,
                       differentiable=differentiable, amp=amp, **static_kwargs)


def _build_pure_fn(fn: Callable, cast_targets, static_kwargs) -> Callable:
    """The traced/differentiated form of one op: autocast applied INSIDE so
    the cast itself is differentiated, static kwargs baked, list outputs
    normalized to tuples. Shared by the uncached, cached, and lazy paths."""
    def f(*xs):
        if cast_targets is not None:
            xs = [x.astype(d) if d is not None else x
                  for x, d in zip(xs, cast_targets)]
        r = fn(*xs, **static_kwargs) if static_kwargs else fn(*xs)
        return tuple(r) if isinstance(r, list) else r
    return f


def _input_sig(t: Tensor):
    """(shape, dtype, weak_type) of one input — the aval when the payload
    carries one (jax arrays), ``shape_tuple()`` otherwise (numpy payloads)."""
    a = t._data
    av = getattr(a, "aval", None)
    if av is not None:
        return (av.shape, av.dtype, av.weak_type)
    return (t.shape_tuple(), np.dtype(a.dtype), False)


def _make_out_tensors(op_name, tensor_inputs, out_arrays, multi, needs_grad,
                      vjp_fn, pure_fn):
    out_tensors = []
    if needs_grad:
        node = GradNode(op_name, vjp_fn, tensor_inputs, len(out_arrays),
                        tuple((oa.shape, oa.dtype) for oa in out_arrays),
                        pure_fn=pure_fn, multi_out=multi)
        for i, oa in enumerate(out_arrays):
            t = Tensor(oa, stop_gradient=False)
            t._grad_node = node
            t._grad_index = i
            out_tensors.append(t)
    else:
        for oa in out_arrays:
            out_tensors.append(Tensor(oa, stop_gradient=True))
    return out_tensors


_UNCACHED = object()  # _apply_cached verdict: run the uncached path


def _concrete_dispatch(ts, arrays) -> bool:
    """True when every input is a concrete array and no functionalization
    seam is live — the only state in which re-executing on another device
    is meaningful (symbolic values cannot be ``device_put``)."""
    if ts is not None:
        return False
    for a in arrays:
        if _is_tracer(a) or type(a).__name__ == "LazyValue":
            return False
    return True


def _dispatch_execute(op_name: str, f: Callable, arrays, needs_grad: bool,
                      ts):
    """Run one op's pure fn (with ``jax.vjp`` when grad is needed), with
    backend fallback: a primitive with no TPU lowering degrades to a CPU
    re-execution instead of crashing the program (core/fallback.py — the
    KernelFactory-fallback analogue). Returns ``(outs, vjp_fn)``.

    ``dispatch.lower`` / ``dispatch.execute`` are resilience fault sites:
    CPU-only CI installs a FaultSchedule raising e.g. NotImplementedError
    here to drive the full degrade-warn-count-cache sequence
    deterministically (tests/test_fallback.py).
    """
    if (_fallback.should_fallback(op_name)
            and _concrete_dispatch(ts, arrays)):
        # registry/denylist short-circuit: the doomed TPU compile is
        # skipped entirely — this is what makes the SECOND call cheap
        return _fallback.run_cpu(op_name, f, arrays, needs_grad)
    try:
        _fault_point("dispatch.lower")
        if needs_grad:
            outs, vjp_fn = jax.vjp(f, *arrays)
        else:
            outs, vjp_fn = f(*arrays), None
        _fault_point("dispatch.execute")
    except Exception as e:
        if not (_fallback.enabled()
                and _fallback.is_lowering_failure(e, op_name)
                and _concrete_dispatch(ts, arrays)):
            raise
        return _fallback.run_cpu(op_name, f, arrays, needs_grad, exc=e)
    return outs, vjp_fn


def _apply_cached(op_name, fn, tensor_inputs, differentiable, amp,
                  static_kwargs):
    """Fast path: dispatch through the signature-keyed compiled-op cache.

    Returns ``_UNCACHED`` whenever the op must see the plain path: any
    tracing/capture seam is live (to_static functionalization, lazy segment
    recording, static-graph capture), an input payload is symbolic, or the
    signature cannot be keyed safely. The caller falls through with NO state
    changed, so the bypass is semantically invisible.
    """
    if (_tracing.trace_state() is not None or _op_graph_hook is not None
            or _lazy.active()):
        _dcache.note_bypass("capture")
        return _UNCACHED
    arrays = []
    for t in tensor_inputs:
        a = t._data
        if _is_tracer(a) or type(a).__name__ == "LazyValue":
            _dcache.note_bypass("symbolic_input")
            return _UNCACHED
        arrays.append(a)

    needs_grad = (differentiable and _tracing.grad_enabled()
                  and any(not t.stop_gradient for t in tensor_inputs))
    st = _tracing.amp_state() if amp else None
    amp_key = st.cache_key if (st is not None and st.enable) else None
    nan_check = _flags.flag("check_nan_inf")
    # backend joins the signature key: an op that fell back to CPU keys
    # separately, so a TPU-compiled callable is never served for it — the
    # fallen-back signature compiles its own CPU executable below
    backend = _fallback.backend_token(op_name)
    fb_cpu = bool(backend)

    in_sigs = tuple(_input_sig(t) for t in tensor_inputs)
    key, reason = _dcache.make_key(op_name, fn, in_sigs, static_kwargs,
                                   amp_key, needs_grad, nan_check,
                                   _flags._EPOCH, backend=backend)
    if key is None:
        _dcache.note_bypass(reason)
        return _UNCACHED

    entry = _dcache.lookup(key)
    if entry is None:
        return _UNCACHED  # cold signature: stay on the uncached path
    fresh = entry is _dcache.NEEDS_COMPILE
    if fresh:
        # signature is warm: resolve autocast targets ONCE, build the
        # compiled pair, and serve this call from it
        cast_targets = _autocast_targets(op_name, arrays) if amp else None
        entry = _dcache.CachedOp(
            _build_pure_fn(fn, cast_targets, static_kwargs), nan_check)

    # fallen-back op: inputs move to host CPU first, so the jitted entry
    # compiles for (and executes on) the CPU backend — committed inputs
    # decide the jit placement — and the key's backend token keeps this
    # executable separate from any TPU-compiled one
    run_arrays = _fallback.to_cpu(arrays) if fb_cpu else arrays
    try:
        outs, finite = entry.fwd(*run_arrays)
        multi = isinstance(outs, tuple)
        out_arrays = outs if multi else (outs,)
        if fresh and needs_grad:
            # snapshot the linearization at dispatch time, like jax.vjp did
            entry.warm_bwd(run_arrays, out_arrays, multi)
    except (jax.errors.JAXTypeError, NotImplementedError):
        if fresh:
            # the fn is legal eagerly but not under jit (it branches on
            # concrete values / lacks an abstract eval): poison the
            # signature so it is never re-traced, and run the plain path —
            # a genuine op error re-raises identically from there
            _dcache.mark_uncacheable(key)
        return _UNCACHED
    except Exception:
        # anything else (transient runtime fault, input-dependent error)
        # must not poison outright: fall through, eager decides. Counted,
        # and poisoned after a few consecutive failures so a persistent
        # non-trace failure can't levy a doomed re-trace per call forever.
        if fresh:
            _dcache.note_compile_failure(key)
        return _UNCACHED
    if fresh:
        _dcache.store(key, entry)
        # ISSUE 16: compile-time cost capture — once per fresh signature,
        # with the run arrays still in scope for spec building; is-None
        # when observability.cost is not installed
        cost_hook = _dcache._cost_hook
        if cost_hook is not None:
            cost_hook("store", key, entry=entry, op=op_name,
                      arrays=run_arrays)
    if finite is not None and not bool(finite):
        raise FloatingPointError(f"op {op_name} produced nan/inf")

    vjp_fn = entry.make_vjp(tuple(run_arrays)) if needs_grad else None
    if fb_cpu:
        _fallback.note_fallback(op_name)  # warn-once for denylist-seeded ops
        _fallback.count_cpu_dispatch(op_name)
        if vjp_fn is not None:
            vjp_fn = _fallback.wrap_vjp(vjp_fn)
        out_arrays = _fallback.from_cpu(out_arrays)
    out_tensors = _make_out_tensors(op_name, tensor_inputs, out_arrays, multi,
                                    needs_grad, vjp_fn, entry.fn)
    if multi:
        return tuple(out_tensors)
    return out_tensors[0]


def _apply_impl(op_name: str, fn: Callable, *tensor_inputs: Tensor,
                differentiable: bool = True, amp: bool = True,
                **static_kwargs) -> Any:
    if _dcache._ENABLED:
        out = _apply_cached(op_name, fn, tensor_inputs, differentiable, amp,
                            static_kwargs)
        if out is not _UNCACHED:
            return out

    ts = _tracing.trace_state()
    arrays = []
    for t in tensor_inputs:
        a = t._data
        if ts is not None and not _is_tracer(a):
            ts.record_read(t)
        arrays.append(a)

    cast_targets = _autocast_targets(op_name, arrays) if amp else None

    needs_grad = (differentiable and _tracing.grad_enabled()
                  and any(not t.stop_gradient for t in tensor_inputs))

    f = _build_pure_fn(fn, cast_targets, static_kwargs)

    if _lazy.active():
        return _lazy_apply(op_name, f, tensor_inputs, arrays, needs_grad)

    outs, vjp_fn = _dispatch_execute(op_name, f, arrays, needs_grad, ts)

    multi = isinstance(outs, tuple)
    out_arrays = outs if multi else (outs,)

    if _flags.flag("check_nan_inf"):
        for oa in out_arrays:
            if not _is_tracer(oa) and jnp.issubdtype(oa.dtype, jnp.inexact):
                if not bool(jnp.all(jnp.isfinite(oa))):
                    raise FloatingPointError(f"op {op_name} produced nan/inf")

    out_tensors = _make_out_tensors(op_name, tensor_inputs, out_arrays, multi,
                                    needs_grad, vjp_fn, f)

    if _op_graph_hook is not None:
        _op_graph_hook(op_name, f, tensor_inputs, tuple(out_tensors))

    if multi:
        return tuple(out_tensors)
    return out_tensors[0]


def register_tensor_method(name: str, fn: Callable) -> None:
    """Install a method on Tensor (ops modules use this to build the ~400
    method surface without circular imports)."""
    setattr(Tensor, name, fn)


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------

def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """``paddle.to_tensor`` parity."""
    dtype = _dtype.convert_dtype(dtype)
    if isinstance(data, Tensor):
        arr = data._data
        if dtype is not None and arr.dtype != dtype:
            arr = arr.astype(dtype)
        t = Tensor(arr, stop_gradient=stop_gradient)
        return t
    if isinstance(data, (jnp.ndarray, jax.Array)) and not isinstance(data, np.ndarray):
        arr = data
    else:
        np_arr = np.asarray(data)
        if np_arr.dtype == np.float64 and dtype is None:
            np_arr = np_arr.astype(np.float32)
        elif np_arr.dtype == np.int32 and dtype is None and isinstance(data, (int, numbers.Integral)):
            np_arr = np_arr.astype(np.int64)
        arr = np_arr
    if dtype is not None and arr.dtype != dtype:
        if _is_tracer(arr):
            arr = jnp.asarray(arr, dtype=dtype)
        elif isinstance(arr, np.ndarray):
            arr = arr.astype(dtype)
        else:
            # committed jax.Array (device_put upstream or passed in by the
            # caller): cast on device, preserving its placement
            arr = jnp.asarray(arr, dtype=dtype)
    if not _is_tracer(arr):
        if place is not None:
            # explicit placement commits the array to that device
            arr = _device.device_put(arr, _parse_place(place))
        else:
            cur = _device.current_place()
            default_platform = "cpu" if not _device.is_compiled_with_tpu() else "tpu"
            if cur.device_type != default_platform or cur.device_id != 0:
                arr = _device.device_put(arr, cur)
            else:
                # UNCOMMITTED on the default device: lets eager ops mix with
                # mesh-committed (sharded) arrays without transfer errors
                arr = jnp.asarray(arr)
    return Tensor(arr, stop_gradient=stop_gradient)


# Tensor.is_floating_point()/is_integer()/is_complex() methods (upstream
# exposes these both as paddle.* functions and as Tensor methods)
register_tensor_method("is_floating_point",
                       lambda self: _dtype.is_floating_point(self.dtype))
register_tensor_method("is_integer",
                       lambda self: _dtype.is_integer(self.dtype))
register_tensor_method("is_complex",
                       lambda self: _dtype.is_complex(self.dtype))
