"""Backend-fallback dispatch: graceful CPU degradation for missing lowerings.

Parity surface: upstream KernelFactory backend fallback
(paddle/phi/core/kernel_factory.cc ``SelectKernelOrThrowError``): when an op
has no kernel registered for the requested place, the factory selects the
CPU kernel and inserts H2D/D2H transfers instead of aborting the program.
TPU-native design: the "kernel registration probe" is the XLA lowering
itself. A primitive with no TPU implementation surfaces
``NotImplementedError`` (missing lowering rule at trace time) or a jaxlib
``XlaRuntimeError`` marked UNIMPLEMENTED/unsupported (compile/first
execution). This module classifies those failures, re-executes the op's
pure fn on the host CPU devices, transfers the results back to the default
device, and records the op in a process-level registry so every later
dispatch of that op skips the doomed TPU compile entirely.

Control surface:

* ``PADDLE_TPU_FALLBACK=auto`` (default) — degrade per-op: one-time
  warning (:class:`BackendFallbackWarning`), ``dispatch.fallbacks_total{op}``
  counter, ``dispatch.fallback_ops`` gauge, registry short-circuit.
* ``PADDLE_TPU_FALLBACK=off`` — today's hard-fail surface, for debugging:
  you want the crash, not the degradation.

``DEFAULT_DENYLIST`` pre-seeds the known-bad families on current libtpu
(``linalg.eig``, complex ``sgn``, ``fft.hfft2``) so a real-chip run never
pays their doomed compile even once. The denylist only engages when an
accelerator is actually present — on a CPU-only host there is nothing to
degrade FROM, and tier-1 semantics stay byte-identical.

Composition contracts:

* dispatch cache (PR 2): the backend token joins the signature key
  (``core/dispatch_cache.py::make_key``), so a TPU-compiled callable is
  never served for an op that has since fallen back; the fallen-back
  signature compiles its own CPU executable and hits the cache normally.
* resilience (PR 5): ``core/tensor.py::_dispatch_execute`` wraps the
  execution in ``fault_point("dispatch.lower")`` /
  ``fault_point("dispatch.execute")`` seams, so CPU-only CI can inject a
  lowering failure and drive the full degrade-warn-count-cache sequence
  deterministically.

This module (together with ``paddle_tpu/device.py``) is the only place
allowed to touch ``jax.devices``/``jax.device_put`` directly — enforced by
the ``device-access`` lint rule (tools/lint/rules/device_access.py).
"""

from __future__ import annotations

import os
import sys
import threading
import warnings
from typing import Any, Callable, Optional, Tuple

import jax

from .. import device as _device
from .. import observability as _obs

__all__ = [
    "BackendFallbackWarning", "DEFAULT_DENYLIST", "KERNEL_OPS",
    "XlaRuntimeError",
    "enabled", "configure", "reset", "fallback_ops", "should_fallback",
    "backend_token", "is_lowering_failure", "note_fallback", "run_cpu",
    "to_cpu", "from_cpu", "wrap_vjp",
]

# public alias of jaxlib's XlaRuntimeError (same class object) — using the
# supported surface instead of jax._src keeps the classifier working (and
# the whole fallback layer live) across jaxlib-internal relayouts
XlaRuntimeError = jax.errors.JaxRuntimeError


class BackendFallbackWarning(RuntimeWarning):
    """Emitted exactly once per op the first time it degrades to CPU."""


# Known-bad families on current libtpu (ROADMAP item 2 / VERDICT Missing
# #1): eig has no TPU lowering at all, complex sgn hits an UNIMPLEMENTED
# elementwise lowering, hfft2's C2R path is rejected by the TPU fft rule.
DEFAULT_DENYLIST = frozenset({"eig", "sgn", "hfft2"})

# Ops whose pure fn is a Pallas kernel. A kernel the compiler refuses is a
# defect to surface, never a reason to leave the chip: these never degrade.
# paged_commit_tokens is the decode kernel's companion, the in-place write
# into the page pool: degraded, it would carry the whole pool to the host.
KERNEL_OPS = frozenset({"flash_attention", "flash_attention_dropout",
                        "flash_attn_unpadded", "paged_attention_decode",
                        "paged_commit_tokens"})


def _env_mode() -> str:
    v = os.environ.get("PADDLE_TPU_FALLBACK", "auto").strip().lower()
    return "off" if v in ("off", "0", "false", "no") else "auto"


_MODE: str = _env_mode()
_LOCK = threading.Lock()
_REGISTRY: set = set()   # ops that have fallen back (process-level)
_WARNED: set = set()     # ops whose one-time warning has fired
_DENYLIST: frozenset = DEFAULT_DENYLIST

# Families pre-created so the series carry help text in the Prometheus
# exposition; the helpers below still no-op while observability is
# disabled (the standard zero-overhead contract).
_obs.counter("dispatch.fallbacks_total",
             "dispatches executed on the CPU fallback path",
             labelnames=("op",))
_obs.gauge("dispatch.fallback_ops",
           "ops currently registered on the CPU fallback path")


# ---------------------------------------------------------------------------
# mode / registry surface
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """True when fallback may engage (``PADDLE_TPU_FALLBACK`` != off)."""
    return _MODE == "auto"


def configure(mode: Optional[str] = None,
              denylist: Optional[frozenset] = None) -> None:
    """Runtime override of the env-derived settings (tests, debugging)."""
    global _MODE, _DENYLIST
    with _LOCK:
        if mode is not None:
            if mode not in ("auto", "off"):
                raise ValueError(f"PADDLE_TPU_FALLBACK mode must be "
                                 f"'auto' or 'off', got {mode!r}")
            _MODE = mode
        if denylist is not None:
            _DENYLIST = frozenset(denylist)


def reset() -> None:
    """Drop all fallback state and re-read the env knob (test isolation)."""
    global _MODE, _DENYLIST
    with _LOCK:
        _REGISTRY.clear()
        _WARNED.clear()
        _MODE = _env_mode()
        _DENYLIST = DEFAULT_DENYLIST
        _obs.set_gauge("dispatch.fallback_ops", 0.0)


def fallback_ops() -> frozenset:
    """Snapshot of the ops currently registered on the fallback path."""
    with _LOCK:
        return frozenset(_REGISTRY)


def should_fallback(op_name: str) -> bool:
    """True when this op must skip the TPU compile and run on CPU: it
    already fell back once (registry), or it is denylisted and an
    accelerator is present (on a CPU-only host there is nothing to
    degrade from, so the denylist stays inert and tier-1 is unchanged)."""
    if _MODE != "auto":
        return False
    if op_name in _REGISTRY:
        return True
    return op_name in _DENYLIST and _device.is_compiled_with_tpu()


def backend_token(op_name: str) -> str:
    """The backend component of the dispatch-cache signature key: ``"cpu"``
    for an op on the fallback path, ``""`` for normal placement. Keying on
    this retires any TPU-compiled entry the moment its op falls back."""
    return "cpu" if should_fallback(op_name) else ""


# ---------------------------------------------------------------------------
# failure classification
# ---------------------------------------------------------------------------

# Substrings (lower-cased) marking an XlaRuntimeError as a missing/broken
# lowering rather than a transient runtime fault. RESOURCE_EXHAUSTED (OOM)
# and connection-ish failures are deliberately NOT fallback-eligible:
# silently re-running an OOM'd batch on host CPU would hide a capacity
# problem behind a 100x slowdown.
_MSG_MARKERS = ("unimplemented", "not implemented", "unsupported",
                "not supported", "no registered lowering", "could not lower",
                "unable to lower")
_MSG_EXCLUDE = ("resource_exhausted", "out of memory")


def is_lowering_failure(exc: BaseException, op_name: str = "") -> bool:
    """Classify one dispatch failure: may this op degrade to CPU?"""
    if op_name in KERNEL_OPS:
        return False
    if isinstance(exc, NotImplementedError):
        return True
    if isinstance(exc, XlaRuntimeError):
        msg = str(exc).lower()
        if any(m in msg for m in _MSG_EXCLUDE):
            return False
        return any(m in msg for m in _MSG_MARKERS)
    return False


_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _user_stacklevel() -> int:
    """Stacklevel of the nearest frame outside paddle_tpu, so the one-time
    fallback warning names the USER call site regardless of which dispatch
    path (cached/uncached, varying wrapper depth) reached us."""
    f, level = sys._getframe(1), 1
    while f is not None and f.f_code.co_filename.startswith(_PKG_DIR):
        f, level = f.f_back, level + 1
    return level


def note_fallback(op_name: str, exc: Optional[BaseException] = None) -> None:
    """Register ``op_name`` on the fallback path; warn exactly once per op
    per process and publish the ``dispatch.fallback_ops`` gauge."""
    with _LOCK:
        new = op_name not in _REGISTRY
        if new:
            _REGISTRY.add(op_name)
            # gauge published under the lock: a later registration's
            # set_gauge can't be overwritten by an earlier (smaller) one
            _obs.set_gauge("dispatch.fallback_ops", float(len(_REGISTRY)))
        warn = op_name not in _WARNED
        if warn:
            _WARNED.add(op_name)
    if warn:
        cause = (f"{type(exc).__name__}: {exc}" if exc is not None
                 else "denylisted for this backend")
        warnings.warn(
            f"op '{op_name}' has no working TPU lowering ({cause}); "
            f"falling back to CPU for this op from now on. Set "
            f"PADDLE_TPU_FALLBACK=off to restore the hard failure.",
            BackendFallbackWarning, stacklevel=_user_stacklevel())


# ---------------------------------------------------------------------------
# CPU re-execution + transfers
# ---------------------------------------------------------------------------

def _cpu_device():
    return _device.CPUPlace().jax_device()


def _put(a, dev):
    """One transfer, skipping what must not (or need not) move: ``None``
    and float0 cotangents pass through, and an array already resident on
    ``dev`` keeps its (un)committed placement instead of being re-committed
    — on a CPU-only host the fallback path is then placement-neutral."""
    if a is None or getattr(a, "dtype", None) == jax.dtypes.float0:
        return a
    devs = getattr(a, "devices", None)
    if devs is not None:
        try:
            if a.devices() == {dev}:
                return a
        except Exception:
            pass  # multi-device/sharded array: let device_put decide
    return jax.device_put(a, dev)


def to_cpu(arrays: Tuple[Any, ...]) -> Tuple[Any, ...]:
    """Transfer op inputs to the host CPU device (D2H leg)."""
    cpu = _cpu_device()
    return tuple(_put(a, cpu) for a in arrays)


def from_cpu(arrays: Tuple[Any, ...]) -> Tuple[Any, ...]:
    """Transfer op results back to the default device (H2D leg)."""
    tgt = _device.default_jax_device()
    return tuple(_put(a, tgt) for a in arrays)


def wrap_vjp(cpu_vjp: Callable) -> Callable:
    """Wrap a CPU-resident vjp for the tape: cotangents arrive wherever the
    consumer produced them (usually the accelerator), move to CPU for the
    pull-back, and the input grads move back to the default device so the
    rest of the backward pass stays on the accelerator."""
    def vjp_fn(cts):
        if isinstance(cts, tuple):
            cts = to_cpu(cts)
        else:
            cts = to_cpu((cts,))[0]
        return from_cpu(tuple(cpu_vjp(cts)))
    return vjp_fn


def count_cpu_dispatch(op_name: str) -> None:
    """Count one dispatch served by the CPU fallback path (both the eager
    re-execution and the cached-CPU-callable route report here)."""
    _obs.inc("dispatch.fallbacks_total", op=op_name)


def run_cpu(op_name: str, f: Callable, arrays: Tuple[Any, ...],
            needs_grad: bool, exc: Optional[BaseException] = None):
    """Execute one op's pure fn on host CPU and transfer results back.

    Returns ``(outs, vjp_fn)`` with the ``jax.vjp`` contract
    (``vjp_fn`` is None when ``needs_grad`` is false). If the CPU backend
    is unreachable (``JAX_PLATFORMS`` pinned accelerator-only) the original
    failure — when there was one — is re-raised instead of masked.

    The registry/warning/counter commit only AFTER the CPU execution
    succeeds: an op whose fn fails on CPU too must keep its real error
    surface, not get pinned to a fallback path that can never serve it.
    """
    try:
        cpu_arrays = to_cpu(arrays)
    except RuntimeError:
        if exc is not None:
            raise exc
        raise
    if needs_grad:
        outs, cpu_vjp = jax.vjp(f, *cpu_arrays)
        vjp_fn = wrap_vjp(cpu_vjp)
    else:
        outs, vjp_fn = f(*cpu_arrays), None
    note_fallback(op_name, exc)
    count_cpu_dispatch(op_name)
    if isinstance(outs, tuple):
        outs = from_cpu(outs)
    else:
        outs = from_cpu((outs,))[0]
    return outs, vjp_fn
