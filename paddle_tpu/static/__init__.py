"""Static graph façade: define-once, run-many programs.

Parity surface: ``paddle.static`` (reference: python/paddle/static/ — Program
/ Executor / data / program_guard / save & load_inference_model; the C++
strata behind it are ProgramDesc + StandaloneExecutor in
paddle/fluid/framework/, see SURVEY.md §2.2).

TPU-native design — *record/replay over the eager dispatch seam*, not a
ProgramDesc interpreter:

- While static mode captures, every op dispatched through
  ``core.tensor.apply`` is appended to the current ``Program`` as a node
  ``(op_name, pure_jax_fn, input_tensors, output_tensors)``. The pure fn is
  exactly the kernel closure XLA will compile — the Program IS a jaxpr-able
  op list (the PIR analogue), with Parameters appearing as captured leaves.
- ``Executor.run`` replays the op list with feeds substituted, wrapped in
  ``paddle.jit.to_static`` so the whole program compiles to ONE XLA
  executable (the StandaloneExecutor's instruction-stream role collapses
  into XLA's scheduler). ``optimizer.minimize(loss)`` captured in the
  program makes ``run`` a full compiled train step: replay → backward →
  update (the generated backward ops of the reference's append_backward).
- Feeds with new shapes simply re-trace (static shapes per executable —
  XLA's compilation model).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import tensor as _tensor_mod
from ..core.tensor import Tensor

__all__ = [
    "Program", "Executor", "data", "program_guard", "default_main_program",
    "default_startup_program", "enable_static", "disable_static",
    "in_static_mode", "save_inference_model", "load_inference_model",
    "InputSpec", "global_scope", "CompiledProgram",
]

from ..jit.save_load import InputSpec  # re-export (paddle.static.InputSpec)


class Program:
    """A recorded op graph. ``clone(for_test=True)`` returns a view without
    the minimize step (parity: Program.clone)."""

    def __init__(self):
        self._records: List[Tuple[str, Any, Tuple[Tensor, ...],
                                  Tuple[Tensor, ...]]] = []
        self._feeds: Dict[str, Tensor] = {}
        self._minimize: Optional[Tuple[Any, Tensor]] = None  # (optimizer, loss)
        self._exec_cache: Dict[Any, Any] = {}
        self.random_seed = 0

    def record(self, op_name, fn, inputs, outputs):
        self._records.append((op_name, fn, tuple(inputs), tuple(outputs)))
        self._exec_cache.clear()

    def clone(self, for_test: bool = False) -> "Program":
        p = Program()
        p._records = list(self._records)
        p._feeds = dict(self._feeds)
        p._minimize = None if for_test else self._minimize
        return p

    def list_vars(self):
        seen, out = set(), []
        for _, _, ins, outs in self._records:
            for t in (*ins, *outs):
                if id(t) not in seen:
                    seen.add(id(t))
                    out.append(t)
        return out

    def global_block(self):
        return self  # parity shim: block-level APIs resolve on the program

    def __repr__(self):
        return (f"Program({len(self._records)} ops, "
                f"feeds={list(self._feeds)}, "
                f"minimize={'yes' if self._minimize else 'no'})")


_default_main = Program()
_default_startup = Program()
_static_mode = False


def default_main_program() -> Program:
    return _default_main


def default_startup_program() -> Program:
    return _default_startup


def _current_program() -> Program:
    return _default_main


def _record_hook(op_name, fn, tensor_inputs, out_tensors):
    _current_program().record(op_name, fn, tensor_inputs, out_tensors)


def enable_static() -> None:
    """Enter static capture mode: ops now record into the default main
    program (fresh). paddle.enable_static() parity."""
    global _static_mode, _default_main, _default_startup
    _static_mode = True
    _default_main = Program()
    _default_startup = Program()
    _tensor_mod._op_graph_hook = _record_hook


def disable_static() -> None:
    global _static_mode
    _static_mode = False
    _tensor_mod._op_graph_hook = None


def in_static_mode() -> bool:
    return _static_mode


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    global _default_main, _default_startup
    old_main, old_startup = _default_main, _default_startup
    _default_main = main_program
    if startup_program is not None:
        _default_startup = startup_program
    try:
        yield
    finally:
        _default_main, _default_startup = old_main, old_startup


def data(name: str, shape: Sequence[Optional[int]], dtype: str = "float32",
         lod_level: int = 0) -> Tensor:
    """Declare a feed placeholder. The placeholder carries a concrete
    zero array (dim None/-1 → 1) purely to drive capture; Executor.run
    re-traces per actual feed shape."""
    import jax.numpy as jnp
    from ..core.dtype import convert_dtype

    declared = tuple(None if (s is None or int(s) < 0) else int(s)
                     for s in shape)
    concrete = tuple(1 if s is None else s for s in declared)
    t = Tensor(jnp.zeros(concrete, dtype=convert_dtype(dtype)),
               stop_gradient=True)
    t.name = name
    t._declared_shape = declared  # None dims stay symbolic for export
    _current_program()._feeds[name] = t
    return t


class _Scope:
    def find_var(self, name):
        for prog in (_default_main, _default_startup):
            for t in prog.list_vars():
                if getattr(t, "name", None) == name:
                    return t
        return None


_scope = _Scope()


def global_scope() -> _Scope:
    return _scope


class CompiledProgram:
    """Parity shim: compilation happens in Executor.run (whole-program jit);
    CompiledProgram(prog) just forwards."""

    def __init__(self, program: Program, build_strategy=None):
        self.program = program


class Executor:
    """Runs a Program: one whole-program XLA executable per feed signature."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list: Optional[List] = None, return_numpy: bool = True):
        if isinstance(program, CompiledProgram):
            program = program.program
        if isinstance(program, _LoadedProgram):
            return program._run(feed or {}, return_numpy)
        program = program if program is not None else _default_main
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        if not program._records:  # startup program: params already init'ed
            return []

        # Re-execution happens eagerly through `apply` (so minimize's
        # backward works), wrapped in to_static for whole-program XLA
        # compilation; static capture must be suspended during replay.
        feed_names = tuple(sorted(feed))
        key = (feed_names, tuple(id(f) for f in fetch_list))
        runner = program._exec_cache.get(key)
        if runner is None:
            runner = self._build_runner(program, feed_names, fetch_list)
            program._exec_cache[key] = runner
        feed_tensors = [self._to_tensor(feed[n]) for n in feed_names]
        outs = runner(*feed_tensors)
        if return_numpy:
            return [np.asarray(o.numpy()) for o in outs]
        return list(outs)

    @staticmethod
    def _to_tensor(x) -> Tensor:
        if isinstance(x, Tensor):
            return x
        import jax.numpy as jnp
        return Tensor(jnp.asarray(x))

    def _build_runner(self, program: Program, feed_names, fetch_list):
        from ..jit.to_static import to_static
        from ..core.tensor import apply

        def _replay(*feed_tensors):
            hook = _tensor_mod._op_graph_hook
            _tensor_mod._op_graph_hook = None  # no capture while replaying
            try:
                env: Dict[int, Tensor] = {}
                for name, ft in zip(feed_names, feed_tensors):
                    ph = program._feeds.get(name)
                    if ph is not None:
                        env[id(ph)] = ft
                for op_name, fn, ins, outs in program._records:
                    new_ins = [env.get(id(t), t) for t in ins]
                    res = apply(op_name, fn, *new_ins, amp=False)
                    res_t = res if isinstance(res, tuple) else (res,)
                    for o, r in zip(outs, res_t):
                        env[id(o)] = r
                if program._minimize is not None:
                    opt, loss = program._minimize
                    new_loss = env.get(id(loss), loss)
                    new_loss.backward()
                    opt.step()
                    opt.clear_grad()
                return tuple(env.get(id(f), f) for f in fetch_list)
            finally:
                _tensor_mod._op_graph_hook = hook

        return to_static(_replay)


# ---------------------------------------------------------------------------
# Inference model save/load (reference: paddle.static.save_inference_model →
# serialized program + params; here: StableHLO via jax.export, params
# embedded as XLA constants)
# ---------------------------------------------------------------------------

def save_inference_model(path_prefix: str, feed_vars, fetch_vars, executor=None,
                         program: Optional[Program] = None, **configs) -> None:
    import os
    import pickle
    import jax
    import jax.numpy as jnp
    from ..core.tensor import apply

    program = program or _default_main
    feed_vars = list(feed_vars) if isinstance(feed_vars, (list, tuple)) else [feed_vars]
    fetch_vars = list(fetch_vars) if isinstance(fetch_vars, (list, tuple)) else [fetch_vars]
    inference = program.clone(for_test=True)

    def fwd(*feed_arrays):
        env: Dict[int, Any] = {id(v): Tensor(a)
                               for v, a in zip(feed_vars, feed_arrays)}
        hook = _tensor_mod._op_graph_hook
        _tensor_mod._op_graph_hook = None
        try:
            from ..core.tracing import no_grad
            with no_grad():
                for op_name, fn, ins, outs in inference._records:
                    new_ins = [env.get(id(t), t) for t in ins]
                    res = apply(op_name, fn, *new_ins, amp=False)
                    res_t = res if isinstance(res, tuple) else (res,)
                    for o, r in zip(outs, res_t):
                        env[id(o)] = r
        finally:
            _tensor_mod._op_graph_hook = hook
        return tuple(env.get(id(f), f)._data for f in fetch_vars)

    from jax import export as jax_export

    def _specs(polymorphic: bool):
        specs = []
        for i, v in enumerate(feed_vars):
            decl = getattr(v, "_declared_shape", None)
            if polymorphic and decl and any(s is None for s in decl):
                dims = ", ".join(f"d{i}_{j}" if s is None else str(s)
                                 for j, s in enumerate(decl))
                shape = jax_export.symbolic_shape(dims)
                specs.append(jax.ShapeDtypeStruct(shape, v._data.dtype))
            else:
                specs.append(jax.ShapeDtypeStruct(tuple(v._data.shape),
                                                  v._data.dtype))
        return specs

    try:
        # shape-polymorphic export: None dims in static.data stay dynamic so
        # the serialized artifact serves any batch size
        exp = jax_export.export(jax.jit(fwd))(*_specs(True))
    except Exception:
        # program not shape-polymorphic (e.g. hard reshape) — pin shapes
        exp = jax_export.export(jax.jit(fwd))(*_specs(False))

    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    # safe container (magic + JSON + raw StableHLO) — NOT pickle: a pickle
    # would execute arbitrary code at load and silently masquerade as the
    # reference's protobuf ProgramDesc format (framework/artifact.py).
    from ..framework.artifact import write_artifact
    write_artifact(path_prefix + ".pdmodel", {
        "format": "paddle_tpu.static_inference.v2",
        "feed_names": [getattr(v, "name", f"feed_{i}")
                       for i, v in enumerate(feed_vars)],
        "fetch_names": [getattr(v, "name", f"fetch_{i}")
                        for i, v in enumerate(fetch_vars)],
        "feed_specs": [(list(v._data.shape), str(v._data.dtype))
                       for v in feed_vars],
    }, blobs={"stablehlo": exp.serialize()})


class _LoadedProgram:
    def __init__(self, payload):
        from jax import export as jax_export
        self._exported = jax_export.deserialize(payload["stablehlo"])
        self.feed_names: List[str] = payload["feed_names"]
        self.fetch_names: List[str] = payload["fetch_names"]
        self.feed_specs = payload.get("feed_specs", [])

    def _run(self, feed: Dict[str, Any], return_numpy: bool = True):
        import jax.numpy as jnp
        args = [jnp.asarray(feed[n].numpy() if isinstance(feed[n], Tensor)
                            else feed[n]) for n in self.feed_names]
        outs = self._exported.call(*args)
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return [Tensor(o) for o in outs]


def load_inference_model(path_prefix: str, executor=None):
    """Returns [program, feed_target_names, fetch_targets] (reference
    contract); ``program`` is runnable via Executor.run(program, feed=...)."""
    from ..framework.artifact import read_model_payload
    payload = read_model_payload(path_prefix + ".pdmodel")
    prog = _LoadedProgram(payload)
    return [prog, prog.feed_names, prog.fetch_names]


from . import nn  # noqa: F401,E402  (control flow: while_loop/cond/case/switch_case)


# ---------------------------------------------------------------------------
# Utility surface: gradients / guards / py_func / create_parameter / metrics
# (reference: python/paddle/static/ + python/paddle/base/backward.py)
# ---------------------------------------------------------------------------

def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """Sum-of-targets gradients w.r.t. inputs (parity: paddle.static.gradients).

    The record/replay design keeps eager tensors behind the program, so this
    is the autograd engine's ``grad`` over the captured tape.
    """
    from ..core.autograd import grad as _grad

    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if target_gradients is not None and not isinstance(target_gradients,
                                                       (list, tuple)):
        target_gradients = [target_gradients]
    hook = _tensor_mod._op_graph_hook
    _tensor_mod._op_graph_hook = None  # the grad pass is not program ops
    try:
        return list(_grad(list(targets), list(inputs),
                          grad_outputs=target_gradients, allow_unused=True))
    finally:
        _tensor_mod._op_graph_hook = hook


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """Parity: records (param, grad-slot) pairs; grads materialize when the
    Executor replays the minimize step."""
    params = parameter_list
    if params is None:
        params = [t for t in _current_program().list_vars()
                  if not t.stop_gradient]
    return [(p, getattr(p, "grad", None)) for p in params]


@contextlib.contextmanager
def scope_guard(scope):
    """Variable scopes collapse onto live tensors here; the guard simply
    swaps the lookup table used by global_scope()."""
    global _scope
    old, _scope = _scope, scope
    try:
        yield
    finally:
        _scope = old


@contextlib.contextmanager
def name_scope(prefix: str = None):
    from ..utils import unique_name
    with unique_name.guard(f"{prefix}/" if prefix else None):
        yield


@contextlib.contextmanager
def device_guard(device: str = None):
    """Pin ops to 'cpu'/'gpu'(=tpu) within the block (best-effort: XLA owns
    placement inside a compiled program; eager factories honor it)."""
    from .. import device as _device_mod
    if device is None:
        yield
        return
    old = _device_mod.get_device()
    try:
        _device_mod.set_device("cpu" if device == "cpu" else "tpu"
                               if _device_mod.is_compiled_with_tpu() else "cpu")
        yield
    finally:
        _device_mod.set_device(old)


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-callback op (reference: paddle.static.py_func over
    PyFuncRegistry): runs ``func`` on host numpy values. Under jit this
    lowers to ``jax.pure_callback`` (XLA host callout)."""
    import jax
    from ..core.tensor import apply as _apply

    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    shapes = [jax.ShapeDtypeStruct(tuple(o._data.shape), o._data.dtype)
              for o in outs]

    def kernel(*arrays):
        def host(*np_arrays):
            r = func(*np_arrays)
            r = r if isinstance(r, (list, tuple)) else [r]
            return tuple(np.asarray(v) for v in r)
        if any(isinstance(a, jax.core.Tracer) for a in arrays):
            # under jit: lower to an XLA host callout
            res = jax.pure_callback(host, tuple(shapes), *arrays)
        else:  # eager: run on the host directly, no callout needed
            import jax.numpy as jnp
            res = tuple(jnp.asarray(v) for v in host(*(np.asarray(a)
                                                       for a in arrays)))
        return tuple(res) if len(outs) > 1 else res[0]

    result = _apply("py_func", kernel, *[Executor._to_tensor(t) for t in xs],
                    differentiable=False)
    res_t = result if isinstance(result, tuple) else (result,)
    for o, r in zip(outs, res_t):
        o._rebind(r)
    return out


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from ..core.tensor import Parameter
    from ..nn import initializer as I

    init = default_initializer
    if init is None and attr is not None:
        init = getattr(attr, "initializer", None)
    if init is None:
        init = I.Constant(0.0) if is_bias else I.XavierUniform()
    from ..core.dtype import convert_dtype
    p = Parameter(init(tuple(int(s) for s in shape), convert_dtype(dtype)),
                  name=name)
    return p


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    import jax.numpy as jnp
    from ..core.dtype import convert_dtype
    t = Tensor(jnp.full(tuple(int(s) for s in shape), value,
                        convert_dtype(dtype)), stop_gradient=True)
    t.name = name
    t.persistable = persistable
    return t


def accuracy(input, label, k=1, correct=None, total=None):
    """Top-k accuracy as a tensor (parity: paddle.static.accuracy)."""
    import jax.numpy as jnp
    from ..core.tensor import apply as _apply

    def f(pred, lab):
        topk = jnp.argsort(pred, axis=-1)[..., ::-1][..., :k]
        lab2 = lab.reshape(lab.shape[0], -1)[:, :1]
        hit = jnp.any(topk == lab2, axis=-1)
        return jnp.mean(hit.astype(jnp.float32))

    return _apply("accuracy", f, Executor._to_tensor(input),
                  Executor._to_tensor(label), differentiable=False)


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1):
    """Batch AUC via thresholded confusion counts (parity shape: returns
    (auc_out, batch_auc_out, state...) reduced to the auc tensor here)."""
    import jax.numpy as jnp
    from ..core.tensor import apply as _apply

    def f(pred, lab):
        score = pred[:, 1] if pred.ndim == 2 and pred.shape[1] == 2 else pred.reshape(-1)
        lab2 = lab.reshape(-1).astype(jnp.float32)
        thresholds = jnp.linspace(0.0, 1.0, num_thresholds)
        pos = (score[None, :] >= thresholds[:, None]).astype(jnp.float32)
        tp = jnp.sum(pos * lab2[None, :], axis=1)
        fp = jnp.sum(pos * (1 - lab2)[None, :], axis=1)
        tpr = tp / jnp.clip(jnp.sum(lab2), 1e-6, None)
        fpr = fp / jnp.clip(jnp.sum(1 - lab2), 1e-6, None)
        return -jnp.trapezoid(tpr, fpr)

    return _apply("auc", f, Executor._to_tensor(input),
                  Executor._to_tensor(label), differentiable=False)


__all__ += ["gradients", "append_backward", "scope_guard", "name_scope",
            "device_guard", "py_func", "create_parameter",
            "create_global_var", "accuracy", "auc"]


# ---------------------------------------------------------------------------
# Static-mode module aliases + small utilities (reference: python/paddle/
# static/__init__.py exports)
# ---------------------------------------------------------------------------

from .. import amp  # noqa: E402,F401  (static.amp == the amp package)
from ..incubate import asp as sparsity  # noqa: E402,F401


class ExponentialMovingAverage:
    """EMA of parameter values with apply/restore (reference:
    paddle.static.ExponentialMovingAverage)."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        import jax.numpy as jnp
        self.decay = float(decay)
        self._ema: dict = {}
        self._backup: dict = {}
        self._jnp = jnp

    def update(self, parameters=None):
        params = parameters or [
            t for t in _default_main.list_vars() if not t.stop_gradient]
        for p in params:
            cur = self._ema.get(id(p))
            new = (p._data.astype("float32") if cur is None
                   else self.decay * cur + (1 - self.decay) *
                   p._data.astype("float32"))
            self._ema[id(p)] = new
        self._params = params

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def cm():
            for p in getattr(self, "_params", []):
                self._backup[id(p)] = p._data
                p._set_data(self._ema[id(p)].astype(p._data.dtype))
            try:
                yield
            finally:
                if need_restore:
                    self.restore()

        return cm()

    def restore(self, executor=None):
        for p in getattr(self, "_params", []):
            bk = self._backup.pop(id(p), None)
            if bk is not None:
                p._set_data(bk)


import contextlib as _ctx  # noqa: E402


@_ctx.contextmanager
def ipu_shard_guard(index=-1, stage=-1):
    """IPU-only sharding annotation upstream; no-op on TPU (mesh shardings
    come from pjit specs)."""
    yield


def setitem(x, index, value):
    """Functional __setitem__ (reference: paddle.static.setitem)."""
    x[index] = value
    return x


def Print(input, first_n=-1, message=None, summarize=20, print_tensor_name=True,
          print_tensor_type=True, print_tensor_shape=True,
          print_tensor_layout=True, print_tensor_lod=True,
          print_phase="both"):
    """Debug print op (reference: paddle.static.Print). Eagerly prints and
    returns the input so program capture keeps flowing."""
    msg = f"{message or ''} {input.name if print_tensor_name else ''}".strip()
    try:
        print(f"[static.Print] {msg} shape={input.shape} "
              f"values={np.asarray(input._data).reshape(-1)[:summarize]}")
    except Exception:
        print(f"[static.Print] {msg} <unavailable while tracing>")
    return input


class WeightNormParamAttr:
    """Parity container (reference: paddle.static.WeightNormParamAttr):
    weight-norm reparameterization is applied via nn.utils.weight_norm in
    this build; the attr carries the config through."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate=1.0, regularizer=None, trainable=True,
                 do_model_average=False, need_clip=True):
        self.dim = dim
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


__all__ += ["sparsity", "ExponentialMovingAverage", "ipu_shard_guard",
            "setitem", "Print", "WeightNormParamAttr", "amp"]
