"""Optimizers.

Parity surface: python/paddle/optimizer/ (SGD/Momentum/Adam/AdamW/Lamb/... ,
grad clip, regularization, multi-tensor paths). TPU-native: updates are pure
jnp expressions over the param/accumulator payloads via ``_set_data`` — under
``to_static`` they fuse into the whole-step XLA program (the analogue of the
reference's fused_adam multi-tensor CUDA kernel, which XLA gets for free).
"""

from __future__ import annotations

import math
import weakref
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from ..core.tensor import Tensor, register_state_tensor
from ..core.tracing import no_grad
from . import lr as lr_mod
from .lr import LRScheduler

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax", "Adagrad",
    "Adadelta", "RMSProp", "Lamb", "LBFGS", "lr",
]

lr = lr_mod


_Q8_BLOCK = 2048  # block size for int8 moment quantization
# a parameter's int8 state, by accumulator name: (moments, scales) twice
_Q8_STATE = ("moment1", "moment1_scale", "moment2_sqrt", "moment2_sqrt_scale")


def _on_one_device() -> bool:
    """No multi-device hybrid mesh is active (``fleet.init``): the only
    state in which a parameter can be handed to a Mosaic kernel whole."""
    from ..distributed.topology import multi_device_mesh
    return multi_device_mesh() is None


def _q8_quantize(x32, block: int = _Q8_BLOCK):
    """Per-block absmax int8 quantization of an fp32 array: returns
    (q int8 (nb, block), scale fp32 (nb,)). The bitsandbytes-style 8-bit
    optimizer-state layout (1 byte/element + 4/block bytes of scale)."""
    flat = x32.reshape(-1)
    n = flat.shape[0]
    nb = -(-n // block)
    flat = jnp.pad(flat, (0, nb * block - n))
    blocks = flat.reshape(nb, block)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _q8_dequantize(q, scale, shape):
    n = 1
    for s in shape:
        n *= int(s)
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return flat[:n].reshape(shape)


def _q8_shapes(shape):
    """(moments' shape, scales' shape) of a parameter's int8 state. A
    parameter of ndim >= 2 whose last dimension C holds whole blocks keeps
    its own layout with the leading dimensions merged, (R, C) and
    (R, C // 2048): the view in which the fused kernel walks it, free on
    the chip where flat rows of one block — (nb, 2048) and (nb,), the form
    of everything else — cost a copy of the parameter each way. Block ``b``
    of the flattened parameter is block ``b`` of either form read row by
    row, so a reshape between the two is exact."""
    n = int(np.prod(shape)) if shape else 1
    if len(shape) >= 2 and n and shape[-1] % _Q8_BLOCK == 0:
        rows, cols = n // int(shape[-1]), int(shape[-1])
        return (rows, cols), (rows, cols // _Q8_BLOCK)
    nb = -(-n // _Q8_BLOCK)
    return (nb, _Q8_BLOCK), (nb,)


def _stochastic_round_bf16(x32, key):
    """Stochastically round f32 -> bf16 (add uniform low bits, truncate).
    Unbiased: E[round(x)] = x. Master-weight-free bf16 training depends on
    it — round-to-nearest silently drops updates below ~2^-8 relative, so a
    bf16 weight would stop learning once lr*update falls under its ulp.
    (Reference keeps fp32 masters instead: python/paddle/amp/ O2 +
    optimizer multi_precision; this is the TPU-native low-memory option.)"""
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    rnd = jax.random.bits(key, bits.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    rounded = (bits + rnd) & jnp.uint32(0xFFFF0000)
    out = jax.lax.bitcast_convert_type(rounded, jnp.float32)
    # adding mantissa bits to inf/nan patterns would corrupt them
    out = jnp.where(jnp.isfinite(x32), out, x32)
    return out.astype(jnp.bfloat16)


class _ClipBase:
    def __call__(self, params_grads):
        raise NotImplementedError


class ClipGradByValue(_ClipBase):
    def __init__(self, max, min=None):
        self.max = float(max)
        self.min = float(min) if min is not None else -self.max

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            if g is None or not getattr(p, "need_clip", True):
                out.append((p, g))
            else:
                out.append((p, jnp.clip(g, self.min, self.max)))
        return out


class ClipGradByNorm(_ClipBase):
    def __init__(self, clip_norm):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        out = []
        for p, g in params_grads:
            if g is None or not getattr(p, "need_clip", True):
                out.append((p, g))
                continue
            n = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
            scale = jnp.minimum(self.clip_norm / jnp.maximum(n, 1e-12), 1.0)
            out.append((p, (g * scale).astype(g.dtype)))
        return out


class ClipGradByGlobalNorm(_ClipBase):
    def __init__(self, clip_norm, group_name="default_group", auto_skip_clip=False):
        self.clip_norm = float(clip_norm)

    def __call__(self, params_grads):
        sq = [jnp.sum(g.astype(jnp.float32) ** 2) for p, g in params_grads
              if g is not None and getattr(p, "need_clip", True)]
        if not sq:
            return params_grads
        total = jnp.sqrt(sum(sq))
        scale = self.clip_norm / jnp.maximum(total, self.clip_norm)
        out = []
        for p, g in params_grads:
            if g is None or not getattr(p, "need_clip", True):
                out.append((p, g))
            else:
                out.append((p, (g * scale).astype(g.dtype)))
        return out


def _normalize_param_groups(parameters):
    """Accept a flat parameter list or paddle-style list of group dicts
    ({'params', 'learning_rate' (scale), 'weight_decay', 'grad_clip'})."""
    if parameters is None:
        return None
    plist = list(parameters)
    if plist and isinstance(plist[0], dict):
        return [{
            "params": list(g["params"]),
            "learning_rate": g.get("learning_rate", 1.0),
            "weight_decay": g.get("weight_decay", None),
            "grad_clip": g.get("grad_clip", None),
        } for g in plist]
    return [{"params": plist, "learning_rate": 1.0, "weight_decay": None,
             "grad_clip": None}]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._learning_rate = learning_rate
        self._groups = _normalize_param_groups(parameters)
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._group_wd = None  # active group's weight-decay override
        self._multi_precision = multi_precision
        # None = default (fp32 masters for low-precision params, the
        # reference multi_precision behavior); False = master-weight-free:
        # low-precision params update in their own dtype (with stochastic
        # rounding for bf16) — halves optimizer memory for bf16 training
        self._use_master_weights: Optional[bool] = None
        self._stochastic_rounding = True
        self._accumulators: Dict[str, Dict[int, Tensor]] = {}
        self._master_weights: Dict[int, Tensor] = {}
        # the global step is carried STATE (an int32 scalar tensor), not a
        # Python int: under to_static the bias-correction term must advance
        # every compiled step, so it has to live in the functionalized state
        self._step_t = Tensor(jnp.zeros((), jnp.int32), stop_gradient=True,
                              name="opt_step")
        self._step_t.persistable = True
        register_state_tensor(self._step_t)
        # scheduler LR is also carried state: a compiled step must READ the
        # current LR at runtime, not bake the trace-time float into the
        # executable (scheduler.step() between compiled steps would otherwise
        # be silently ignored)
        self._lr_t: Optional[Tensor] = None
        if isinstance(learning_rate, LRScheduler):
            self._lr_t = Tensor(jnp.asarray(learning_rate.last_lr, jnp.float32),
                                stop_gradient=True, name="opt_lr")
            self._lr_t.persistable = True
            register_state_tensor(self._lr_t)
            if not hasattr(learning_rate, "_bound_opts"):
                learning_rate._bound_opts = []
            learning_rate._bound_opts.append(weakref.ref(self))
        self._master_versions: Dict[int, int] = {}
        # never-reused instance id: anchors the recorded-segment signature of
        # the staged (full_graph=False) optimizer update
        Optimizer._uid_counter += 1
        self._opt_uid = Optimizer._uid_counter
        from ..jit.to_static import register_pretrace_hook
        register_pretrace_hook(self)

    _uid_counter = 0

    # --- lr -----------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value: float) -> None:
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def _lr_value(self):
        """LR as seen by the update math: a traced scalar for schedulers (so
        compiled steps pick up scheduler.step() without recompiling), a plain
        float otherwise."""
        if self._lr_t is not None:
            return self._lr_t._data
        return float(self._learning_rate)

    def _sync_lr_tensor(self) -> None:
        if self._lr_t is None:
            return
        from ..core.tracing import trace_state
        if trace_state() is not None:
            # scheduler.step() inside a captured/traced step: the host-
            # computed LR would constant-fold into the compiled program and
            # silently serve the trace-time value forever (inside a trace
            # even jnp.asarray of a python float is a constant-derived
            # tracer, so the step-capture concrete-write walk cannot see
            # it). Fail loud and uniform instead — the LR VALUE already
            # rides the program as carried state; the schedule's position
            # advance belongs between steps, on the host.
            from ..core.step_capture import HostStateWriteError
            raise HostStateWriteError(
                "scheduler.step() ran inside a captured/traced train step: "
                "the new LR would bake into the compiled program as a "
                "constant. Call scheduler.step() outside the captured step "
                "(its value reaches the program via the carried opt_lr "
                "state), or set PADDLE_TPU_STEP_CAPTURE=off")
        self._lr_t._set_data(
            jnp.asarray(self._learning_rate.last_lr, jnp.float32))

    @property
    def _param_groups(self):
        """Flat parameter list (all groups)."""
        if self._groups is None:
            raise ValueError("optimizer constructed without parameters; pass "
                             "parameters=model.parameters()")
        return [p for g in self._groups for p in g["params"]]

    # --- accumulators ---------------------------------------------------------
    def _acc(self, name: str, p: Tensor, init=None, dtype=None) -> Tensor:
        store = self._accumulators.setdefault(name, {})
        t = store.get(id(p))
        if t is None:
            data = jnp.zeros_like(p._data, dtype=dtype) if init is None else init
            t = Tensor(data, stop_gradient=True, name=f"{p.name}_{name}")
            t.persistable = True
            register_state_tensor(t)
            store[id(p)] = t
        return t

    def _decayed_grad(self, p: Tensor, g):
        """Coupled (L2) weight decay + per-param regularizer."""
        wd = self._group_wd if self._group_wd is not None else self._weight_decay
        reg = getattr(p, "regularizer", None)
        if reg is not None:
            g = g + reg.coeff * p._data if getattr(reg, "_l2", True) \
                else g + reg.coeff * jnp.sign(p._data)
        elif wd is not None and not isinstance(self, AdamW):
            coeff = wd.coeff if hasattr(wd, "coeff") else float(wd)
            g = g + coeff * p._data
        return g

    # sparse (SelectedRows) gradient support: optimizers that can apply a
    # row-wise update override this; None means "densify and take the dense
    # path" (reading grad._data densifies transparently)
    def _update_param_sparse(self, p, sr, lr_eff) -> bool:
        return False

    def _sparse_eligible(self, p, group) -> bool:
        from ..core.selected_rows import SelectedRowsTensor
        g = p.grad
        if not (isinstance(g, SelectedRowsTensor) and g.is_selected_rows()):
            return False
        if type(self)._update_param_sparse is Optimizer._update_param_sparse:
            return False
        # clipping and coupled decay/regularizers read the full gradient —
        # those configurations densify (upstream sparse grads have the same
        # restriction: ClipGradByGlobalNorm densifies SelectedRows)
        if ((group or {}).get("grad_clip") or self._grad_clip) is not None:
            return False
        if (group or {}).get("weight_decay") is not None or \
                self._weight_decay is not None or \
                getattr(p, "regularizer", None) is not None:
            return False
        return True

    def _collect_params_grads(self, group=None):
        params = group["params"] if group is not None else self._param_groups
        pg = [(p, p.grad._data) for p in params
              if p.grad is not None and p.trainable
              and not self._sparse_eligible(p, group)]
        clip = (group or {}).get("grad_clip") or self._grad_clip
        if clip is not None:
            pg = clip(pg)
        return pg

    def _step_sparse_params(self, group, group_lr) -> None:
        for p in group["params"]:
            if p.grad is None or not p.trainable or \
                    not self._sparse_eligible(p, group):
                continue
            lr_eff = group_lr * p.optimize_attr.get("learning_rate", 1.0) \
                if hasattr(p, "optimize_attr") else group_lr
            self._update_param_sparse(p, p.grad.selected_rows, lr_eff)

    # --- the step -------------------------------------------------------------
    @property
    def _step_count(self) -> int:
        from ..core.tensor import _is_tracer
        d = self._step_t._data
        return int(d) if not _is_tracer(d) else -1

    def _create_accumulators(self, p: Tensor) -> None:
        """Create this optimizer's per-param state for ``p`` (overridden)."""

    def _materialize_state(self) -> None:
        """Eagerly create all lazy per-param state (accumulators, AMP master
        weights). Without this, the first ``to_static`` train step registers
        new state tensors mid-trace and the SECOND call must rebuild+recompile
        the whole program — a hidden multi-second stall per model."""
        if self._groups is None:
            return
        for p in self._param_groups:
            if not getattr(p, "trainable", True):
                continue
            self._ensure_master(p)
            self._create_accumulators(p)

    def _refresh_derived_state(self) -> None:
        """Pre-trace hook: fold externally re-set param payloads (state_dict
        load after optimizer construction) into their fp32 masters."""
        if self._groups is None:
            return
        for p in self._param_groups:
            m = self._master_weights.get(id(p))
            if m is None:
                continue
            ver = getattr(p, "_version", 0)
            if self._master_versions.get(id(p)) != ver:
                m._set_data(p._data.astype(jnp.float32))
                self._master_versions[id(p)] = ver

    def _note_param_written(self, p: Tensor) -> None:
        """Record that ``p`` was just written FROM its master (so the new
        version does not look like an external write)."""
        if id(p) in self._master_weights:
            self._master_versions[id(p)] = getattr(p, "_version", 0)

    def _on_params_cast(self) -> None:
        """amp.decorate just cast the params to a low dtype: create any
        missing masters (from the cast values)."""
        self._materialize_state()

    @no_grad()
    def step(self) -> None:
        from ..core import lazy as _lazy
        from ..core.tracing import trace_state
        if _lazy.active():
            # segment mode (full_graph=False partial capture): stage the
            # whole update as ONE recorded meta-op so it compiles into the
            # current segment — a full_graph=False train step then runs as
            # [fwd(+bwd) segment] -> host read -> [bwd+update segment] with
            # no eager tail (upstream SOT compiles the update into its
            # subgraphs: python/paddle/jit/sot/)
            if self._try_record_step():
                return
            # ineligible configuration (sparse grads, custom step): the raw
            # jnp update math below cannot record — materialize first
            _lazy.flush_if_active()
        if trace_state() is None:
            # eager step after an external weight load: reconcile masters
            self._refresh_derived_state()
        self._step_impl()

    def _step_impl(self) -> None:
        """The update math proper (pure jnp over the state payloads; also
        traced by the recorded optimizer-step segment)."""
        self._q8_serial_tokens = []  # per-trace ordering chain (q8 path)
        self._step_t._set_data(self._step_t._data + 1)
        base_lr = self._lr_value()
        for group in self._groups:
            self._group_wd = group.get("weight_decay")
            group_lr = base_lr * float(group.get("learning_rate", 1.0))
            self._step_sparse_params(group, group_lr)
            for p, g in self._collect_params_grads(group):
                g = self._decayed_grad(p, g)
                lr_eff = group_lr * p.optimize_attr.get("learning_rate", 1.0) \
                    if hasattr(p, "optimize_attr") else group_lr
                self._update_param(p, g, lr_eff)
        self._group_wd = None

    # --- staged update for the lazy segment executor -------------------------
    def _lazy_step_tensors(self) -> List[Tensor]:
        """Every state tensor the update math READS or WRITES, in a fixed
        order. All of them ride the recorded segment as explicit inputs (and
        outputs) — a state tensor missing from this list would be baked into
        the compiled segment as a trace-time constant and silently go stale
        on replay."""
        from ..core.random import default_generator
        out = [self._step_t, default_generator._key]
        if self._lr_t is not None:
            out.append(self._lr_t)
        params = self._param_groups
        out.extend(params)
        fs = getattr(self, "_fused", None)
        if fs is not None and getattr(self, "_use_multi_tensor", False):
            out += [fs["m"], fs["v"], fs["master"]]
            for k in ("wd_mask", "lr_scale"):
                if fs[k] is not None:
                    out.append(fs[k])
            for key in sorted(fs["live_cache"]):
                out.append(fs["live_cache"][key])
        else:
            for name in sorted(self._accumulators):
                store = self._accumulators[name]
                for p in params:
                    t = store.get(id(p))
                    if t is not None:
                        out.append(t)
            for p in params:
                m = self._master_weights.get(id(p))
                if m is not None:
                    out.append(m)
        return out

    def _lazy_step_sig(self):
        """Hashable signature covering every Python-level constant the traced
        update bakes in: two steps with equal signatures (and equal input
        avals) may legally share one compiled segment."""
        def _reg_sig(p):
            r = getattr(p, "regularizer", None)
            return None if r is None else (float(r.coeff),
                                           bool(getattr(r, "_l2", True)))
        groups_sig = tuple(
            (float(g.get("learning_rate", 1.0)), repr(g.get("weight_decay")),
             repr(g.get("grad_clip")), len(g["params"]))
            for g in self._groups)
        params_sig = tuple(
            (p.grad is not None, bool(getattr(p, "trainable", True)),
             bool(getattr(p, "need_clip", True)),
             float(p.optimize_attr.get("learning_rate", 1.0))
             if hasattr(p, "optimize_attr") else 1.0,
             _reg_sig(p))
            for p in self._param_groups)
        return ("optimizer_step", self._opt_uid,
                None if self._lr_t is not None else float(self._learning_rate),
                repr(self._weight_decay), repr(self._grad_clip),
                bool(self._stochastic_rounding), groups_sig, params_sig)

    def _try_record_step(self) -> bool:
        """Segment mode: record the whole optimizer update as one meta-op.

        The recorded fn temporarily binds the traced values into the live
        state tensors, re-runs ``_step_impl`` (plain jnp math traces fine),
        and returns each state tensor's new payload; the segment executor
        compiles it into the current segment and rebinds the real arrays on
        flush. Returns False for configurations the staged path cannot
        express (sparse SelectedRows grads, subclass custom ``step``)."""
        from ..core import lazy as _lazy
        from ..core.selected_rows import SelectedRowsTensor
        if self._groups is None or type(self).step is not Optimizer.step:
            return False
        params = self._param_groups
        if not params:
            return False
        for p in params:
            if isinstance(p.grad, SelectedRowsTensor):
                return False  # row-sparse update path stays eager
        self._refresh_derived_state()
        fs = getattr(self, "_fused", None)
        if fs is not None and getattr(self, "_use_multi_tensor", False):
            # pre-build the liveness mask OUTSIDE the trace (built inside it
            # would register a model-sized constant as fresh state mid-trace)
            live = tuple(p.grad is not None and p.trainable
                         for p in fs["params"])
            if not all(live):
                self._fused_live_mask(live)
        else:
            # per-param accumulators/masters must pre-exist: created inside
            # the trace they would capture tracers as persistent state
            Optimizer._materialize_state(self)
        state = self._lazy_step_tensors()
        # snapshot the grad TENSORS, not just their payloads: the replay
        # trace runs at flush time, which can be after clear_grad() — the fn
        # must see the record-time grad structure, not a later-cleared one
        grad_pairs = [(p, p.grad) for p in params
                      if p.grad is not None and p.trainable]
        grads = [g for _, g in grad_pairs]
        tensors = state + grads
        arrays = [t._data for t in tensors]

        def optimizer_step_fn(*flat):
            # called by the segment trace (eval_shape at record, replay at
            # flush): binds the traced values into the live tensors, re-runs
            # the update math, and restores the real payloads no matter what
            saved = [t._data for t in tensors]
            saved_grads = [p._grad for p, _ in grad_pairs]
            try:
                for t, v in zip(tensors, flat):
                    t._data = v
                for p, g in grad_pairs:
                    p._grad = g
                with no_grad(), _lazy.suspended():
                    self._step_impl()
                return tuple(t._data for t in state)
            finally:
                for t, s in zip(tensors, saved):
                    t._data = s
                for (p, _), g0 in zip(grad_pairs, saved_grads):
                    p._grad = g0

        try:
            outs, _ = _lazy.record("optimizer_step", optimizer_step_fn,
                                   arrays, fn_sig=self._lazy_step_sig())
        except Exception as e:
            # unstageable update math: take the eager path — but say so
            # once, because the silent cost is ~8x step throughput
            if not getattr(self, "_warned_unstaged", False):
                self._warned_unstaged = True
                import warnings
                warnings.warn(
                    f"optimizer update could not be staged as a compiled "
                    f"segment ({type(e).__name__}: {e}); falling back to "
                    f"the eager per-op update for this optimizer")
            return False
        for t, lv in zip(state, outs):
            t._set_data(lv)
        # the writes above bump versions; re-sync so the derived-state
        # refresh doesn't mistake them for external loads
        for p in params:
            self._note_param_written(p)
        if fs is not None and getattr(self, "_use_multi_tensor", False):
            self._fused_sync_versions()
        return True

    def _update_param(self, p: Tensor, g, lr_eff: float) -> None:
        raise NotImplementedError

    def clear_grad(self, set_to_zero: bool = False) -> None:
        for p in self._param_groups:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from .. import static as _static
        if _static.in_static_mode():
            # static capture: record the train-step tail (backward + update)
            # on the program; Executor.run replays it inside the compiled step
            prog = _static.default_main_program()
            prog._minimize = (self, loss)
            prog._exec_cache.clear()  # runners built pre-minimize lack the update
            return None, None
        loss.backward()
        self.step()
        return None, None

    # --- state ---------------------------------------------------------------
    def state_dict(self):
        state = {"step": self._step_t}
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        for p in self._param_groups:
            for name, store in self._accumulators.items():
                t = store.get(id(p))
                if t is not None:
                    state[f"{p.name}_{name}"] = t
            if id(p) in self._master_weights:
                state.setdefault("master_weights", {})[p.name] = \
                    self._master_weights[id(p)]
        return state

    def set_state_dict(self, state):
        step = state.get("step", 0)
        if isinstance(step, Tensor):
            step = int(step._data)
        self._step_t._set_data(jnp.asarray(step, jnp.int32))
        if isinstance(self._learning_rate, LRScheduler) and "LR_Scheduler" in state:
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
            self._sync_lr_tensor()  # the carried LR state must follow
        # accumulators are created lazily on first step(); when resuming a
        # fresh optimizer they must be materialized here from the checkpoint
        # keys (f"{param.name}_{acc_name}")
        for p in self._param_groups:
            prefix = f"{p.name}_"
            for key, src in state.items():
                if key in ("step", "LR_Scheduler", "master_weights"):
                    continue
                if key.startswith(prefix):
                    acc_name = key[len(prefix):]
                    arr = src._data if isinstance(src, Tensor) else jnp.asarray(src)
                    self._acc(acc_name, p)._set_data(arr)
        mw = state.get("master_weights", {})
        for p in self._param_groups:
            if p.name in mw:
                src = mw[p.name]
                arr = src._data if isinstance(src, Tensor) else jnp.asarray(src)
                m = self._ensure_master(p)
                if m is not None:
                    m._set_data(arr)
                else:
                    self._master_weights[id(p)] = Tensor(
                        jnp.asarray(arr, jnp.float32), stop_gradient=True,
                        name=f"{p.name}_master")
                # the checkpoint master is now authoritative: mark it in sync
                # with the param so the pre-trace refresh doesn't overwrite it
                # with bf16-rounded param values
                self._master_versions[id(p)] = getattr(p, "_version", 0)

    set_dict = set_state_dict

    def _narrow_write(self, new32, dtype):
        """fp32 update -> storage dtype: THE write-narrowing policy, shared
        by the per-param, fused-flat and sparse-row paths. bf16 rounds
        stochastically when enabled (sub-ulp updates apply in expectation);
        everything else is a plain cast (fp32: no-op)."""
        if dtype == jnp.bfloat16 and self._stochastic_rounding:
            from ..core.random import default_generator
            return _stochastic_round_bf16(new32, default_generator.split_key())
        return new32.astype(dtype)

    def _param_write_back(self, p: Tensor, new_p32) -> None:
        """Write an fp32 update into a master-weight-free param."""
        p._set_data(self._narrow_write(new_p32, p._data.dtype))

    def _ensure_master(self, p: Tensor):
        """fp32 master weight for low-precision params (AMP O2)."""
        if self._use_master_weights is False:
            return None
        if p._data.dtype in (jnp.bfloat16, jnp.float16):
            m = self._master_weights.get(id(p))
            if m is None:
                m = Tensor(p._data.astype(jnp.float32), stop_gradient=True,
                           name=f"{p.name}_master")
                m.persistable = True
                register_state_tensor(m)
                self._master_weights[id(p)] = m
                self._master_versions[id(p)] = getattr(p, "_version", 0)
            return m
        return None


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        if self._groups is not None:
            self._materialize_state()

    def _update_param(self, p, g, lr_eff):
        master = self._ensure_master(p)
        if master is not None:
            new_m = master._data - lr_eff * g.astype(jnp.float32)
            master._set_data(new_m)
            p._set_data(new_m.astype(p._data.dtype))
            self._note_param_written(p)
        else:
            self._param_write_back(
                p, p._data.astype(jnp.float32) - lr_eff * g.astype(jnp.float32))

    def _update_param_sparse(self, p, sr, lr_eff) -> bool:
        """Row-wise SGD (upstream sgd kernel's SelectedRows overload):
        touch only the looked-up rows — exact (SGD has no cross-row
        state), so sparse SGD == dense SGD numerically."""
        sr = sr.merged()
        rows = sr.rows
        delta = (-lr_eff * sr.values.astype(jnp.float32))
        master = self._ensure_master(p)
        if master is not None:
            new_m = master._data.at[rows].add(delta, mode="drop")
            master._set_data(new_m)
            p._set_data(p._data.at[rows].set(
                new_m[rows].astype(p._data.dtype), mode="drop"))
            self._note_param_written(p)
        else:
            p._set_data(p._data.at[rows].add(delta.astype(p._data.dtype),
                                             mode="drop"))
        return True


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("velocity", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        v = self._acc("velocity", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        new_v = self._momentum * v._data + g32
        v._set_data(new_v)
        if self._nesterov:
            upd = g32 + self._momentum * new_v
        else:
            upd = new_v
        master = self._ensure_master(p)
        if master is not None:
            new_m = master._data - lr_eff * upd
            master._set_data(new_m)
            p._set_data(new_m.astype(p._data.dtype))
            self._note_param_written(p)
        else:
            self._param_write_back(
                p, p._data.astype(jnp.float32) - lr_eff * upd)


class Adam(Optimizer):
    """``paddle.optimizer.Adam`` with two TPU-native memory knobs beyond the
    reference surface (upstream python/paddle/optimizer/adam.py keeps fp32
    m/v + fp32 masters unconditionally):

    * ``moment_dtype``: dtype of the m/v accumulators — "float32" default;
      "bfloat16" halves optimizer state; "int8" stores per-block
      absmax-quantized moments (1 byte/param + 4/2048 scale overhead, the
      bitsandbytes 8-bit layout; unfused path only). Update math always
      runs in fp32. int8 caveat: the per-block absmax REDUCTION pins the
      fp32 update transient in HBM (a cast can fuse away, a reduction
      cannot), so for one giant scan-stacked tensor its peak memory
      exceeds bf16's — int8 wins on models made of many medium tensors;
      at the single-chip scan-stacked memory limit prefer "bfloat16".
    * ``use_master_weights``: None keeps the reference behavior (fp32
      masters for bf16/fp16 params); False trains master-weight-free — bf16
      params update in-place with stochastic rounding
      (``stochastic_rounding=False`` to disable).

    bf16 m/v + master-free bf16 params cut per-param optimizer bytes from
    16 (bf16 p + f32 master/m/v) to 6 (bf16 p/m/v) — the difference between
    816M and ~1.9B params fitting a 16GB chip.
    """

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, use_multi_tensor=False,
                 moment_dtype="float32", use_master_weights=None,
                 stochastic_rounding=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._use_multi_tensor = use_multi_tensor
        self._lazy_mode = bool(lazy_mode)
        self._moment_q8 = str(moment_dtype) == "int8"
        if self._moment_q8 and use_multi_tensor:
            raise ValueError(
                "moment_dtype='int8' is supported on the per-param path "
                "only; drop use_multi_tensor (XLA fuses the per-param "
                "updates under to_static anyway)")
        self._moment_dtype = jnp.dtype("float32") if self._moment_q8 \
            else jnp.dtype(moment_dtype)
        self._use_master_weights = use_master_weights
        self._stochastic_rounding = bool(stochastic_rounding)
        self._fused = None  # flat-buffer state, built by _materialize_state
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        if self._moment_q8:
            moments, scales = _q8_shapes(p._data.shape)
            # "moment2_sqrt": the second moment is stored in SQRT space
            # (see _adam_q8_update) — the key name versions the format so
            # a legacy linear-v checkpoint cannot silently bind to it
            for name in ("moment1", "moment2_sqrt"):
                self._acc(name, p, init=jnp.zeros(moments, jnp.int8))
                self._acc(name + "_scale", p,
                          init=jnp.ones(scales, jnp.float32))
            return
        self._acc("moment1", p, dtype=self._moment_dtype)
        self._acc("moment2", p, dtype=self._moment_dtype)

    # --- fused (multi-tensor) path -------------------------------------------
    # One flat f32 buffer each for moment1/moment2/master instead of 3 arrays
    # per parameter. This is the analogue of the reference's multi_tensor
    # fused_adam kernel (paddle/phi/kernels/fusion/ fused_adam), and on this
    # runtime it also slashes per-call buffer-handling overhead (~0.2 ms per
    # buffer per step through PJRT on hundreds of state arrays).
    def _materialize_state(self) -> None:
        if self._groups is None:
            return
        # fuse/unfuse is decided ONCE here, from construction-stable facts
        # only — per-step fallback would desync the flat m/v buffers from
        # freshly-created per-param accumulators. Per-step variation
        # (grad is None, trainable toggles) is handled INSIDE the fused
        # update via a segment mask, never by switching paths.
        fusable = (self._use_multi_tensor and len(self._groups) == 1
                   and self._groups[0].get("grad_clip") is None
                   and self._groups[0].get("weight_decay") is None
                   and self._weight_decay is None
                   and not isinstance(self._grad_clip, ClipGradByNorm)
                   and all(getattr(p, "regularizer", None) is None
                           and (not hasattr(p, "optimize_attr") or
                                p.optimize_attr.get("learning_rate", 1.0) == 1.0)
                           for p in self._param_groups))
        if not fusable:
            self._use_multi_tensor = False
            super()._materialize_state()
            return
        # ALL params ride in the flat layout (a frozen param may be unfrozen
        # later); liveness is applied per step via the segment mask
        params = list(self._param_groups)
        total = 0
        offsets = []
        for p in params:
            n = int(np.prod(p._data.shape)) if p._data.shape else 1
            offsets.append((total, n))
            total += n
        # master-weight-free + all-bf16 params: the flat buffer (the
        # authoritative storage) itself lives in bf16 and updates with
        # stochastic rounding; mixed/fp32 params keep the fp32 flat buffer
        flat_dtype = jnp.bfloat16 if (
            self._use_master_weights is False and params
            and all(p._data.dtype == jnp.bfloat16 for p in params)) \
            else jnp.float32
        master = jnp.concatenate(
            [p._data.reshape(-1).astype(flat_dtype) for p in params]) \
            if params else jnp.zeros((0,), flat_dtype)
        fused = self._fused
        if fused is not None and fused["total"] == total:
            # re-materialize (e.g. after amp.decorate cast): refresh master
            fused["master"]._set_data(master.astype(fused["master"]._data.dtype))
            fused["params"] = params
            self._fused_sync_versions()
            return
        self._fused = {
            "params": params, "offsets": offsets, "total": total,
            "flat_dtype": flat_dtype,
            "m": self._reg_flat("moment1",
                                jnp.zeros((total,), self._moment_dtype)),
            "v": self._reg_flat("moment2",
                                jnp.zeros((total,), self._moment_dtype)),
            "master": self._reg_flat("master", master),
            "wd_mask": None,  # scalar 1.0 unless apply_decay_param_fun set
            "lr_scale": None,
            "live_cache": {},  # liveness tuple -> segment mask state tensor
        }
        self._fused_rebuild_masks()
        if not all(getattr(p, "trainable", True) for p in params):
            # prebuild the expected liveness mask eagerly (outside any trace):
            # created mid-trace it would embed as a model-sized constant
            self._fused_live_mask(tuple(p.trainable for p in params))
        self._fused_sync_versions()

    def _reg_flat(self, name: str, data) -> Tensor:
        t = Tensor(data, stop_gradient=True, name=f"fused_{name}")
        t.persistable = True
        register_state_tensor(t)
        return t

    def _fused_rebuild_masks(self) -> None:
        """Segment-constant wd/lr vectors; registered as state (not trace
        constants — a model-sized f32 constant would bloat the executable)."""
        fs = self._fused
        if fs is None:
            return
        decay_fn = getattr(self, "_apply_decay_param_fun", None)
        lr_ratio = getattr(self, "_lr_ratio", None)
        if decay_fn is not None:
            fs["wd_mask"] = self._reg_flat("wd_mask", self._segment_vector(
                [0.0 if not decay_fn(p.name) else 1.0
                 for p in fs["params"]]))
        if lr_ratio is not None:
            fs["lr_scale"] = self._reg_flat("lr_scale", self._segment_vector(
                [float(lr_ratio(p)) for p in fs["params"]]))

    def _fused_sync_versions(self) -> None:
        fs = self._fused
        fs["versions"] = [getattr(p, "_version", 0) for p in fs["params"]]

    def _fused_refresh_stale(self) -> None:
        """Pre-trace: fold externally re-set param values (e.g. a state_dict
        load AFTER optimizer construction) back into the flat master."""
        fs = self._fused
        if fs is None:
            return
        stale = [i for i, (p, ver) in enumerate(zip(fs["params"], fs["versions"]))
                 if getattr(p, "_version", 0) != ver]
        if not stale:
            return
        master = fs["master"]._data
        for i in stale:
            p = fs["params"][i]
            off, n = fs["offsets"][i]
            master = master.at[off:off + n].set(
                p._data.reshape(-1).astype(master.dtype))
        fs["master"]._set_data(master)
        self._fused_sync_versions()

    def _refresh_derived_state(self) -> None:
        if self._use_multi_tensor:
            self._fused_refresh_stale()
        else:
            super()._refresh_derived_state()

    def _on_params_cast(self) -> None:
        if self._fused is not None:
            fs = self._fused
            if self._use_master_weights is False and fs["params"] and all(
                    p._data.dtype == jnp.bfloat16 for p in fs["params"]):
                # master-weight-free: after the O2 cast the flat buffer IS
                # the bf16 storage (no fp32 shadow kept)
                fs["flat_dtype"] = jnp.bfloat16
                fs["master"]._set_data(fs["master"]._data.astype(jnp.bfloat16))
            # the flat master already holds the PRE-cast values (built at
            # construction); treat the cast as an internal write, don't clobber
            self._fused_sync_versions()
        else:
            super()._on_params_cast()

    # chunk width for >int32-range flat buffers; a class attribute so tests
    # can shrink it and exercise the chunked path on small totals
    _SEGVEC_CHUNK = np.iinfo(np.int32).max

    def _segment_vector(self, per_segment_values):
        """Flat (total,) f32 vector that is constant within each param's
        segment. Built as tiny-literal boundaries + one gather — NOT a dense
        literal (materialized mid-trace that embeds a model-sized constant
        into the program: the remote-compile 413 failure mode) and NOT an
        O(n_params) where-chain. Totals past int32 range are built in
        chunks with the segment boundaries shifted host-side into each
        chunk's window — lax.iota(int64) silently canonicalizes to int32
        when x64 is off, so a single big iota would wrap and corrupt the
        segment masks at 7B scale."""
        fs = self._fused
        bounds = np.asarray([off for off, _ in fs["offsets"]][1:], np.int64)
        vals = jnp.asarray(np.asarray(per_segment_values, np.float32))
        total = fs["total"]
        chunk = int(self._SEGVEC_CHUNK)
        if total <= chunk:
            idx = jax.lax.iota(jnp.int32, total)
            seg = jnp.searchsorted(jnp.asarray(bounds, jnp.int32), idx,
                                   side="right")
            return vals[seg]
        parts = []
        start = 0
        while start < total:
            n = min(chunk, total - start)
            # bounds before the window clip to 0 (counted for every local
            # idx), bounds past it clip to n (never counted) — searchsorted
            # over the shifted bounds yields the GLOBAL segment id
            local = np.clip(bounds - start, 0, n).astype(np.int32)
            idx = jax.lax.iota(jnp.int32, n)
            seg = jnp.searchsorted(jnp.asarray(local), idx, side="right")
            parts.append(vals[seg])
            start += n
        return jnp.concatenate(parts)

    def _fused_live_mask(self, live):
        """0/1 f32 segment mask for the given per-param liveness tuple,
        registered as carried state (cached per distinct pattern)."""
        fs = self._fused
        m = fs["live_cache"].get(live)
        if m is None:
            m = self._reg_flat("live_mask", self._segment_vector(
                [1.0 if ok else 0.0 for ok in live]))
            fs["live_cache"][live] = m
        return m._data

    def _fused_step(self) -> None:
        fs = self._fused
        base_lr = self._lr_value()
        base_lr = base_lr * float(self._groups[0].get("learning_rate", 1.0))
        # liveness matches the unfused skip rule (_collect_params_grads):
        # a param with no grad / trainable=False keeps its m, v, master and
        # payload EXACTLY unchanged this step
        live = tuple(p.grad is not None and p.trainable for p in fs["params"])
        mask = None if all(live) else self._fused_live_mask(live)
        g_flat = jnp.concatenate([
            (p.grad._data.reshape(-1) if ok
             else jnp.zeros((n,), p._data.dtype)).astype(jnp.float32)
            for ok, (p, (off, n)) in
            zip(live, zip(fs["params"], fs["offsets"]))])
        clip = self._grad_clip
        if isinstance(clip, ClipGradByGlobalNorm):
            # dead segments carry zero grads, so they don't affect the norm —
            # identical to the unfused per-present-grad computation
            norm = jnp.sqrt(jnp.sum(g_flat * g_flat))
            g_flat = g_flat * (clip.clip_norm / jnp.maximum(norm, clip.clip_norm))
        elif isinstance(clip, ClipGradByValue):
            g_flat = jnp.clip(g_flat, clip.min, clip.max)
        b1, b2 = self._beta1, self._beta2
        t = self._step_t._data.astype(jnp.float32)
        # fp32 update math over possibly-narrow storage (casts fuse into the
        # elementwise chain; a bf16 state buffer never widens in HBM)
        m32 = fs["m"]._data.astype(jnp.float32)
        v32 = fs["v"]._data.astype(jnp.float32)
        new_m = b1 * m32 + (1 - b1) * g_flat
        new_v = b2 * v32 + (1 - b2) * g_flat * g_flat
        if mask is not None:
            new_m = mask * new_m + (1.0 - mask) * m32
            new_v = mask * new_v + (1.0 - mask) * v32
        fs["m"]._set_data(new_m.astype(self._moment_dtype))
        fs["v"]._set_data(new_v.astype(self._moment_dtype))
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        lr_vec = base_lr if fs["lr_scale"] is None \
            else base_lr * fs["lr_scale"]._data
        wd = getattr(self, "_wd_coeff", 0.0)
        base = fs["master"]._data.astype(jnp.float32)
        upd = base
        if wd:
            decay = lr_vec * wd if fs["wd_mask"] is None \
                else lr_vec * wd * fs["wd_mask"]._data
            upd = upd * (1.0 - decay)
        upd = upd - lr_vec * mhat / (jnp.sqrt(vhat) + self._epsilon)
        new_p = upd if mask is None else mask * upd + (1.0 - mask) * base
        new_flat = self._narrow_write(new_p, fs["flat_dtype"])
        fs["master"]._set_data(new_flat)
        for ok, (p, (off, n)) in zip(live, zip(fs["params"], fs["offsets"])):
            if ok:
                p._set_data(new_flat[off:off + n].reshape(p._data.shape)
                            .astype(p._data.dtype))
        self._fused_sync_versions()

    def _step_impl(self) -> None:
        if not self._use_multi_tensor or self._fused is None:
            # which way this step's int8 updates went (`_adam_q8_update`),
            # counted as the step runs or is traced: parameters the fused
            # kernel walked where they lie, and the elements of those whose
            # operands were reshaped into flat (nb, 2048) rows
            self._q8_routed = {"in_layout_params": 0, "relaid_elements": 0}
            super()._step_impl()
            if self._moment_q8:
                from .. import observability as _obs
                for name, value in self._q8_routed.items():
                    _obs.set_gauge("train.q8." + name, value)
            return
        self._step_t._set_data(self._step_t._data + 1)
        self._fused_step()

    def state_dict(self):
        if self._fused is None:
            return super().state_dict()
        # expose per-param views of the flat buffers (checkpoint compatibility
        # with the unfused layout)
        state = {"step": self._step_t}
        if isinstance(self._learning_rate, LRScheduler):
            state["LR_Scheduler"] = self._learning_rate.state_dict()
        fs = self._fused
        for p, (off, n) in zip(fs["params"], fs["offsets"]):
            shape = p._data.shape
            for key, flat in (("moment1", fs["m"]), ("moment2", fs["v"])):
                state[f"{p.name}_{key}"] = Tensor(
                    flat._data[off:off + n].reshape(shape), stop_gradient=True)
            if p._data.dtype in (jnp.bfloat16, jnp.float16):
                state.setdefault("master_weights", {})[p.name] = Tensor(
                    fs["master"]._data[off:off + n].reshape(shape),
                    stop_gradient=True)
        return state

    def _convert_legacy_q8_v(self) -> None:
        """A round-3 int8 checkpoint stores moment2 as LINEAR-v int8; the
        current format is sqrt-space under the versioned key moment2_sqrt.
        Binding the old arrays directly would square-shrink v (~1000x too
        large updates); convert linear -> sqrt per block on load instead."""
        store = self._accumulators.pop("moment2", None)
        sstore = self._accumulators.pop("moment2_scale", None)
        if not store:
            return
        import warnings
        warnings.warn("converting legacy int8 moment2 (linear v) checkpoint "
                      "state to the sqrt-space layout (moment2_sqrt)")
        for pid, t in store.items():
            if t._data.dtype != jnp.int8 or sstore is None:
                continue
            sc = sstore.get(pid)
            if sc is None:
                continue
            v = jnp.maximum(t._data.astype(jnp.float32) * sc._data[:, None],
                            0.0)
            q, nsc = _q8_quantize(jnp.sqrt(v).reshape(-1))
            self._accumulators.setdefault("moment2_sqrt", {})[pid] = t
            t._set_data(q)
            self._accumulators.setdefault("moment2_sqrt_scale", {})[pid] = sc
            sc._set_data(nsc)

    def _q8_state_into_view(self) -> None:
        """int8 state as a checkpoint gave it -> the shapes this optimizer
        keeps (`_q8_shapes`). A checkpoint from before PR 32 holds every
        parameter's moments flat, (nb, 2048) with (nb,) scales; the blocks
        and their order are the same, so the reshape is exact."""
        for p in self._param_groups:
            for name, shape in zip(_Q8_STATE, _q8_shapes(p._data.shape) * 2):
                t = self._accumulators.get(name, {}).get(id(p))
                if t is not None and t._data.shape != shape:
                    t._set_data(t._data.reshape(shape))

    def set_state_dict(self, state):
        if self._fused is None:
            super().set_state_dict(state)
            if self._moment_q8:
                self._convert_legacy_q8_v()
                self._q8_state_into_view()
            return
        step = state.get("step", 0)
        if isinstance(step, Tensor):
            step = int(step._data)
        self._step_t._set_data(jnp.asarray(step, jnp.int32))
        if isinstance(self._learning_rate, LRScheduler) and "LR_Scheduler" in state:
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
            self._sync_lr_tensor()  # the carried LR state must follow
        fs = self._fused
        mw = state.get("master_weights", {})
        for key, flat in (("moment1", fs["m"]), ("moment2", fs["v"])):
            buf = np.array(flat._data)
            for p, (off, n) in zip(fs["params"], fs["offsets"]):
                src = state.get(f"{p.name}_{key}")
                if src is not None:
                    arr = src._data if isinstance(src, Tensor) else src
                    buf[off:off + n] = np.asarray(arr, np.float32).reshape(-1)
            flat._set_data(jnp.asarray(buf))
        buf = np.array(fs["master"]._data)
        for p, (off, n) in zip(fs["params"], fs["offsets"]):
            src = mw.get(p.name)
            if src is not None:
                arr = src._data if isinstance(src, Tensor) else src
                buf[off:off + n] = np.asarray(arr, np.float32).reshape(-1)
        fs["master"]._set_data(jnp.asarray(buf))
        # loaded flat master is authoritative: don't let the pre-trace refresh
        # fold bf16-rounded param values back over it
        self._fused_sync_versions()

    set_dict = set_state_dict

    # fp32 transient budget per chunk of the int8 update (elements); a class
    # attribute so tests can shrink it and exercise multi-chunk paths on
    # small params. 2M measured best at the 2.07B single-chip ceiling: the
    # XLA memory scheduler needs the headroom (4M chunks miss fitting by
    # ~45MB there), and per-chunk traffic is already bandwidth-amortized.
    _Q8_CHUNK_ELEMS = 2 * 1024 * 1024

    # Software-pipelining knobs for the chunked int8 update (round 5).
    # The serialized tail is LATENCY-bound, not bandwidth-bound: at 2.07B
    # params the ~0.19s/step tail is ~7x over the ~25ms HBM floor of its
    # ~10 B/param traffic, because every chunk's read->compute->write chain
    # conservatively orders after the previous chunk's writes (dynamic
    # slice offsets defeat XLA's alias analysis). Two semantics-preserving
    # levers recover the bubbles:
    #  - _Q8_UNROLL chunks per fori_loop iteration, with ALL reads hoisted
    #    before ANY write — the chunks' pipelines overlap inside one
    #    iteration (regions are disjoint by construction);
    #  - _Q8_PARAM_WINDOW params in flight: the ordering barrier threads
    #    the token from the param WINDOW back, so a bounded number of
    #    per-param pipelines overlap while the summed fp32 transients stay
    #    O(WINDOW * chunk) — full serialization (window 1) was the round-4
    #    fix for unordered updates blowing the HBM headroom.
    # Both default to 1: the 2.07B on-chip sweep measured unroll-2 and
    # window-2 WITHIN NOISE of baseline (TPUs execute fusions
    # sequentially — there is no cross-fusion overlap for the HLO
    # scheduler to unlock) while doubling transient HBM against a
    # ~46MB-tight headroom. The knobs remain for re-measurement
    # (`bench_llama.py --q8-unroll/--q8-window`); the real fix is the
    # fused Pallas kernel (ops/q8_adam_pallas.py), which TPU runs route
    # to automatically.
    _Q8_UNROLL = 1
    _Q8_PARAM_WINDOW = 1

    def _adam_q8_update(self, p, g, lr_eff, decoupled_wd=0.0):
        """Fully-chunked int8-moment Adam step.

        The whole-tensor formulation pinned fp32 transients of the one
        giant scan-stacked parameter in HBM (casts fuse into elementwise
        chains, but the per-block absmax REDUCTION forces the fp32 update
        to materialize) — measured to OOM a 2.07B single-chip run by
        ~0.5-0.9GB. Here the dequantize -> moment update -> requantize ->
        param write pipeline runs chunk-by-chunk IN PLACE: a fori_loop
        carries the full m/v/scale/param buffers (XLA aliases the carry, so
        dynamic-slice reads + dynamic-update-slice writes touch the
        original storage) and each iteration's fp32 live set is
        O(_Q8_CHUNK_ELEMS), independent of parameter size. No whole-array
        pad/stack copies: an earlier lax.map-over-padded-groups draft added
        ~3 full-tensor copies, which pushed a 2.07B step to the HBM ceiling
        and collapsed throughput ~10x (measured: fwd+bwd 0.165s/step, the
        copying optimizer tail +1.5s). A ragged tail (params not a multiple
        of chunk x block) is processed as one separate static-shape chunk."""
        m, ms, v, vs = (self._acc(name, p) for name in _Q8_STATE)
        shape = p._data.shape
        n = int(np.prod(shape)) if shape else 1
        nb = int(ms._data.size)
        b1, b2 = self._beta1, self._beta2
        t = self._step_t._data.astype(jnp.float32)
        c1 = 1.0 - b1 ** t
        c2 = 1.0 - b2 ** t
        if (n % _Q8_BLOCK == 0 and n >= _Q8_BLOCK
                and _flags.flag("q8_pallas_update")
                and jax.default_backend() == "tpu"
                and _on_one_device()):
            # TPU: the whole update is ONE Pallas kernel (pipelined DMA
            # over (G, 2048) tiles, fp32 intermediates in VMEM, in-place
            # via aliasing). No cross-param ordering barrier needed — the
            # HBM fp32 transients that forced serialization don't exist
            # on this path. Ragged params fall through to the chunked
            # XLA loop below (they are small; their cost is noise), and
            # so does everything under a multi-device mesh: Mosaic
            # kernels cannot be partitioned automatically, XLA's loop can.
            return self._adam_q8_update_pallas(
                p, g, lr_eff, decoupled_wd, m, ms, v, vs, c1, c2)
        self._q8_routed["relaid_elements"] += n
        gb = max(1, min(nb, int(self._Q8_CHUNK_ELEMS) // _Q8_BLOCK))
        full_blocks = n // _Q8_BLOCK          # blocks with no ragged tail
        loops = full_blocks // gb             # uniform in-loop chunks
        master = self._ensure_master(p)
        base = (master._data if master is not None else p._data).reshape(-1)
        gview = g.reshape(-1)
        # SERIALIZE updates across parameters: without an explicit ordering
        # XLA overlaps every param's chunk pipeline, and the summed fp32
        # transients of several giant scan-stacked params blow the HBM
        # headroom the chunking just bought. optimization_barrier threads a
        # token from the previous param's result into this one's input.
        toks = getattr(self, "_q8_serial_tokens", None)
        if toks is None:
            toks = self._q8_serial_tokens = []
        if len(toks) >= self._Q8_PARAM_WINDOW:
            # order after the param WINDOW back: params in between stay in
            # flight concurrently with this one (bounded transient memory)
            gview, _ = jax.lax.optimization_barrier(
                (gview, toks[-self._Q8_PARAM_WINDOW]))
        use_sr = (master is None and p._data.dtype == jnp.bfloat16
                  and self._stochastic_rounding)
        if use_sr:
            from ..core.random import default_generator
            key = default_generator.split_key()

        def chunk_update(mq, msq, vq, vsq, gg, bb, kidx):
            """(k, B) int8 moments + (k*B,) grad/base chunk -> updated.

            The SECOND moment is stored in SQRT SPACE: linear absmax int8
            of raw v zeroes every entry below absmax/127 — Adam divides by
            sqrt(v), so a zeroed v turns into a lr*m/eps update and the
            run EXPLODES (reproduced: 60-step MLP diverges to 1e18; this
            is why bitsandbytes uses nonlinear quantization maps for v).
            Quantizing sqrt(v) squares the representable dynamic range
            (absmax ratio 1e-4 in v is 1e-2 in sqrt space -> survives) and
            is free: the update needs sqrt(v) anyway."""
            g32 = gg.astype(jnp.float32)
            m32 = (mq.astype(jnp.float32) * msq[:, None]).reshape(-1)
            sv = (vq.astype(jnp.float32) * vsq[:, None]).reshape(-1)
            v32 = sv * sv
            nm = b1 * m32 + (1 - b1) * g32
            nv = b2 * v32 + (1 - b2) * g32 * g32
            # ONE quantization rule shared with the whole-tensor path —
            # nm/nv are exact block multiples, so _q8_quantize pads nothing
            qm, msc = _q8_quantize(nm)
            qv, vsc = _q8_quantize(jnp.sqrt(nv))
            upd = bb.astype(jnp.float32)
            if decoupled_wd:
                upd = upd * (1.0 - lr_eff * decoupled_wd)
            upd = upd - lr_eff * (nm / c1) / (jnp.sqrt(nv / c2) +
                                              self._epsilon)
            if use_sr:
                new_b = _stochastic_round_bf16(
                    upd, jax.random.fold_in(key, kidx))
            else:
                new_b = upd.astype(base.dtype)
            return qm, msc, qv, vsc, new_b

        def unrolled_body(u):
            """fori_loop body processing ``u`` chunks per iteration.

            All reads come off the carry BEFORE any write enters the
            dataflow graph: the u chunk updates are then independent and
            XLA overlaps their read->compute->write pipelines. Reading the
            carry-in is correct because the chunks' regions are disjoint —
            chunk j's region is untouched by chunk j' != j's writes."""
            def body(i, carry):
                mb, msb, vb, vsb, bb = carry
                outs = []
                for j in range(u):
                    blk = (i * u + j) * gb
                    off = blk * _Q8_BLOCK
                    s2 = lambda a, blk=blk: \
                        jax.lax.dynamic_slice_in_dim(a, blk, gb, 0)
                    s1 = lambda a, off=off: \
                        jax.lax.dynamic_slice_in_dim(a, off,
                                                     gb * _Q8_BLOCK, 0)
                    outs.append(chunk_update(
                        s2(mb), s2(msb), s2(vb), s2(vsb),
                        s1(gview), s1(bb), i * u + j))
                u2 = jax.lax.dynamic_update_slice_in_dim
                for j, (qm, msc, qv, vsc, new_b) in enumerate(outs):
                    blk = (i * u + j) * gb
                    off = blk * _Q8_BLOCK
                    mb = u2(mb, qm, blk, 0)
                    msb = u2(msb, msc, blk, 0)
                    vb = u2(vb, qv, blk, 0)
                    vsb = u2(vsb, vsc, blk, 0)
                    bb = u2(bb, new_b, off, 0)
                return (mb, msb, vb, vsb, bb)
            return body

        U = max(1, int(self._Q8_UNROLL))
        loops_u, peel = divmod(loops, U)
        # the loop walks flat rows of one block whatever view the state is
        # kept in (`_q8_shapes`: the same blocks in the same order)
        carry = (m._data.reshape(nb, _Q8_BLOCK), ms._data.reshape(nb),
                 v._data.reshape(nb, _Q8_BLOCK), vs._data.reshape(nb), base)
        if loops_u > 0:
            carry = jax.lax.fori_loop(0, loops_u, unrolled_body(U), carry)
        if peel:
            # leftover full chunks run in a SECOND fori_loop, not inlined:
            # a chunk executed outside a compiled loop body fuses
            # differently (FMA grouping) and drifts 1 ulp from its in-loop
            # twin, breaking the chunk-shape-invariance bit-equality the
            # q8 tests pin. unrolled_body(1)'s body indexes chunks
            # globally, so iterating the global range works directly.
            carry = jax.lax.fori_loop(loops_u * U, loops,
                                      unrolled_body(1), carry)
        mb, msb, vb, vsb, newb = carry

        # ragged tail: remaining blocks (incl. the partial last block) as one
        # static-shape chunk — only the SMALL tail slices get padded
        tail_blocks = nb - loops * gb
        if tail_blocks > 0:
            blk = loops * gb
            off = blk * _Q8_BLOCK
            tail_n = n - off
            pad = tail_blocks * _Q8_BLOCK - tail_n
            gg = jnp.pad(jax.lax.dynamic_slice_in_dim(gview, off, tail_n, 0),
                         (0, pad))
            bb_t = jnp.pad(jax.lax.dynamic_slice_in_dim(newb, off, tail_n, 0),
                           (0, pad))
            qm, msc, qv, vsc, new_b = chunk_update(
                mb[blk:], msb[blk:], vb[blk:], vsb[blk:], gg, bb_t,
                jnp.uint32(loops))
            mb = mb.at[blk:].set(qm)
            msb = msb.at[blk:].set(msc)
            vb = vb.at[blk:].set(qv)
            vsb = vsb.at[blk:].set(vsc)
            newb = jax.lax.dynamic_update_slice_in_dim(
                newb, new_b[:tail_n], off, 0)

        m._set_data(mb.reshape(m._data.shape))
        ms._set_data(msb.reshape(ms._data.shape))
        v._set_data(vb.reshape(v._data.shape))
        vs._set_data(vsb.reshape(vs._data.shape))
        toks.append(msb[0])  # later params' updates order after us (window)
        new_flat = newb.reshape(shape)
        if master is not None:
            master._set_data(new_flat)
            p._set_data(new_flat.astype(p._data.dtype))
            self._note_param_written(p)
        else:
            p._set_data(new_flat)

    def _adam_q8_update_pallas(self, p, g, lr_eff, decoupled_wd,
                               m, ms, v, vs, c1, c2):
        """Fused single-kernel int8 update (see ops/q8_adam_pallas.py).

        Parameter and gradient enter the kernel in the view the moments
        are kept in (`_q8_shapes`): the parameter's own layout with the
        leading dimensions merged where its rows hold whole blocks — no
        copy on the chip — and flat (nb, 2048) rows otherwise, which cost
        a relayout of the parameter each way."""
        from ..ops.q8_adam_pallas import q8_adam_update

        view = m._data.shape
        if ms._data.ndim == 2:
            self._q8_routed["in_layout_params"] += 1
        else:
            self._q8_routed["relaid_elements"] += m._data.size
        scale_view = (view[0], view[1] // _Q8_BLOCK)
        master = self._ensure_master(p)
        base = (master._data if master is not None else p._data) \
            .reshape(view)
        gview = g.reshape(view)
        use_sr = (master is None and p._data.dtype == jnp.bfloat16
                  and self._stochastic_rounding)
        if use_sr:
            from ..core.random import default_generator
            key = default_generator.split_key()
            # the kernel's on-core PRNG takes an int32 seed; folding the
            # (raw uint32[2]) threefry key halves keeps per-step/per-param
            # streams distinct
            kd = jnp.asarray(key, jnp.uint32).reshape(-1)
            seed = (kd[0] ^ kd[-1]).astype(jnp.int32).reshape(1)
        else:
            seed = jnp.zeros((1,), jnp.int32)
        wd = float(decoupled_wd) if decoupled_wd else 0.0
        scalars = jnp.stack([
            jnp.asarray(lr_eff, jnp.float32).reshape(()),
            jnp.float32(wd), c1.astype(jnp.float32),
            c2.astype(jnp.float32), jnp.float32(self._epsilon),
            jnp.float32(self._beta1), jnp.float32(self._beta2)])
        mq, msc, vq, vsc, newb = q8_adam_update(
            m._data, ms._data.reshape(scale_view), v._data,
            vs._data.reshape(scale_view), base, gview, scalars, seed,
            use_sr=use_sr, has_wd=bool(wd))
        m._set_data(mq)
        ms._set_data(msc.reshape(ms._data.shape))
        v._set_data(vq)
        vs._set_data(vsc.reshape(vs._data.shape))
        new_flat = newb.reshape(p._data.shape)
        if master is not None:
            master._set_data(new_flat)
            p._set_data(new_flat.astype(p._data.dtype))
            self._note_param_written(p)
        else:
            p._set_data(new_flat)

    def _adam_core(self, p, g, lr_eff, decoupled_wd=0.0):
        if self._moment_q8:
            return self._adam_q8_update(p, g, lr_eff, decoupled_wd)
        m = self._acc("moment1", p, dtype=self._moment_dtype)
        v = self._acc("moment2", p, dtype=self._moment_dtype)
        g32 = g.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_t._data.astype(jnp.float32)
        # update math in fp32 regardless of storage dtype (XLA fuses the
        # widen/narrow casts into the elementwise chain — no fp32 copy of
        # the state ever materializes in HBM)
        new_m = b1 * m._data.astype(jnp.float32) + (1 - b1) * g32
        new_v = b2 * v._data.astype(jnp.float32) + (1 - b2) * g32 * g32
        m._set_data(new_m.astype(self._moment_dtype))
        v._set_data(new_v.astype(self._moment_dtype))
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        master = self._ensure_master(p)
        base = master._data if master is not None else p._data.astype(jnp.float32)
        if decoupled_wd:
            base = base * (1.0 - lr_eff * decoupled_wd)
        new_p = base - lr_eff * mhat / (jnp.sqrt(vhat) + self._epsilon)
        if master is not None:
            master._set_data(new_p)
            p._set_data(new_p.astype(p._data.dtype))
            self._note_param_written(p)
        else:
            self._param_write_back(p, new_p)

    def _update_param(self, p, g, lr_eff):
        self._adam_core(p, g, lr_eff)

    def _sparse_eligible(self, p, group) -> bool:
        # Adam's moments decay every step for every row; skipping untouched
        # rows is the explicit ``lazy_mode`` approximation (upstream adam
        # kernel's lazy_mode flag) — without it, densify
        return (self._lazy_mode
                and not self._moment_q8  # block quant is whole-tensor
                and getattr(self, "_lr_ratio", None) is None
                and super()._sparse_eligible(p, group))

    def _update_param_sparse(self, p, sr, lr_eff) -> bool:
        """lazy_mode row update: moments and weights advance only for the
        touched rows (upstream adam_dense_param_sparse_grad kernel)."""
        m = self._acc("moment1", p, dtype=self._moment_dtype)
        v = self._acc("moment2", p, dtype=self._moment_dtype)
        sr = sr.merged()
        rows = sr.rows
        g32 = sr.values.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_t._data.astype(jnp.float32)
        m_rows = m._data[rows].astype(jnp.float32)
        v_rows = v._data[rows].astype(jnp.float32)
        new_m = b1 * m_rows + (1 - b1) * g32
        new_v = b2 * v_rows + (1 - b2) * g32 * g32
        m._set_data(m._data.at[rows].set(new_m.astype(self._moment_dtype),
                                         mode="drop"))
        v._set_data(v._data.at[rows].set(new_v.astype(self._moment_dtype),
                                         mode="drop"))
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        master = self._ensure_master(p)
        base = master._data if master is not None \
            else p._data.astype(jnp.float32)
        base_rows = base[rows]
        wd = getattr(self, "_wd_coeff", 0.0)
        decay_fn = getattr(self, "_apply_decay_param_fun", None)
        if wd and (decay_fn is None or decay_fn(p.name)):
            # decoupled decay on the touched rows only (lazy semantics)
            base_rows = base_rows * (1.0 - lr_eff * wd)
        new_rows = base_rows - lr_eff * mhat / (jnp.sqrt(vhat) + self._epsilon)
        if master is not None:
            new_master = master._data.at[rows].set(new_rows, mode="drop")
            master._set_data(new_master)
            p._set_data(p._data.at[rows].set(
                new_rows.astype(p._data.dtype), mode="drop"))
            self._note_param_written(p)
        else:
            p._set_data(p._data.at[rows].set(
                self._narrow_write(new_rows, p._data.dtype), mode="drop"))
        return True


class AdamW(Adam):
    """Decoupled weight decay (upstream: python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, use_multi_tensor=False,
                 moment_dtype="float32", use_master_weights=None,
                 stochastic_rounding=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         use_multi_tensor=use_multi_tensor,
                         moment_dtype=moment_dtype,
                         use_master_weights=use_master_weights,
                         stochastic_rounding=stochastic_rounding, name=name)
        self._wd_coeff = weight_decay.coeff if hasattr(weight_decay, "coeff") \
            else float(weight_decay or 0.0)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio
        if self._fused is not None and (apply_decay_param_fun is not None
                                        or lr_ratio is not None):
            self._fused_rebuild_masks()

    def _update_param(self, p, g, lr_eff):
        wd = self._wd_coeff
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            wd = 0.0
        if self._lr_ratio is not None:
            lr_eff = lr_eff * self._lr_ratio(p)
        self._adam_core(p, g, lr_eff, decoupled_wd=wd)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("moment", p, dtype=jnp.float32)
        self._acc("inf_norm", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        m = self._acc("moment", p, dtype=jnp.float32)
        u = self._acc("inf_norm", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        new_m = self._beta1 * m._data + (1 - self._beta1) * g32
        new_u = jnp.maximum(self._beta2 * u._data, jnp.abs(g32))
        m._set_data(new_m)
        u._set_data(new_u)
        t = self._step_t._data.astype(jnp.float32)
        p._set_data((p._data.astype(jnp.float32) -
                     lr_eff / (1 - self._beta1 ** t) * new_m / (new_u + self._epsilon)
                     ).astype(p._data.dtype))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("moment", p,
                  init=jnp.full_like(p._data, self._init_acc, dtype=jnp.float32))

    def _update_param(self, p, g, lr_eff):
        acc = self._acc("moment", p,
                        init=jnp.full_like(p._data, self._init_acc, dtype=jnp.float32))
        g32 = g.astype(jnp.float32)
        new_acc = acc._data + g32 * g32
        acc._set_data(new_acc)
        p._set_data((p._data.astype(jnp.float32) -
                     lr_eff * g32 / (jnp.sqrt(new_acc) + self._epsilon)
                     ).astype(p._data.dtype))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = epsilon, rho
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("avg_squared_grad", p, dtype=jnp.float32)
        self._acc("avg_squared_update", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        avg_sq = self._acc("avg_squared_grad", p, dtype=jnp.float32)
        avg_upd = self._acc("avg_squared_update", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        new_sq = self._rho * avg_sq._data + (1 - self._rho) * g32 * g32
        upd = jnp.sqrt(avg_upd._data + self._epsilon) / \
            jnp.sqrt(new_sq + self._epsilon) * g32
        new_upd = self._rho * avg_upd._data + (1 - self._rho) * upd * upd
        avg_sq._set_data(new_sq)
        avg_upd._set_data(new_upd)
        p._set_data((p._data.astype(jnp.float32) - lr_eff * upd).astype(p._data.dtype))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("mean_square", p, dtype=jnp.float32)
        self._acc("momentum", p, dtype=jnp.float32)
        if self._centered:
            self._acc("mean_grad", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        ms = self._acc("mean_square", p, dtype=jnp.float32)
        mom = self._acc("momentum", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        new_ms = self._rho * ms._data + (1 - self._rho) * g32 * g32
        ms._set_data(new_ms)
        denom = new_ms
        if self._centered:
            mg = self._acc("mean_grad", p, dtype=jnp.float32)
            new_mg = self._rho * mg._data + (1 - self._rho) * g32
            mg._set_data(new_mg)
            denom = new_ms - new_mg * new_mg
        upd = self._momentum * mom._data + lr_eff * g32 / \
            jnp.sqrt(denom + self._epsilon)
        mom._set_data(upd)
        p._set_data((p._data.astype(jnp.float32) - upd).astype(p._data.dtype))


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        t = self._step_t._data.astype(jnp.float32)
        new_m = b1 * m._data + (1 - b1) * g32
        new_v = b2 * v._data + (1 - b2) * g32 * g32
        m._set_data(new_m)
        v._set_data(new_v)
        mhat = new_m / (1 - b1 ** t)
        vhat = new_v / (1 - b2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon)
        wd = 0.0 if (self._exclude_fn is not None and self._exclude_fn(p)) \
            else self._lamb_wd
        p32 = p._data.astype(jnp.float32)
        upd = r + wd * p32
        w_norm = jnp.linalg.norm(p32)
        u_norm = jnp.linalg.norm(upd)
        trust = jnp.where(jnp.logical_and(w_norm > 0, u_norm > 0),
                          w_norm / u_norm, 1.0)
        p._set_data((p32 - lr_eff * trust * upd).astype(p._data.dtype))


class LBFGS(Optimizer):
    """Minimal L-BFGS (paddle.optimizer.LBFGS parity shim; full-batch only)."""

    def __init__(self, learning_rate=1.0, max_iter=20, history_size=100,
                 parameters=None, **kw):
        super().__init__(learning_rate, parameters, None, None)
        self._max_iter = max_iter

    def step(self, closure=None):
        if closure is None:
            # fall back to plain gradient descent on current grads
            for p, g in self._collect_params_grads():
                p._set_data(p._data - self.get_lr() * g)
            return None
        loss = None
        for _ in range(self._max_iter):
            self.clear_grad()
            loss = closure()
            for p, g in self._collect_params_grads():
                p._set_data(p._data - self.get_lr() * g)
        return loss


class L1Decay:
    _l2 = False

    def __init__(self, coeff=0.0):
        self.coeff = coeff


class L2Decay:
    _l2 = True

    def __init__(self, coeff=0.0):
        self.coeff = coeff


class Rprop(Optimizer):
    """Resilient backpropagation (reference: paddle.optimizer.Rprop):
    sign-based per-element step sizes grown/shrunk by ``etas``."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision)
        self._lr_range = (float(learning_rate_range[0]),
                          float(learning_rate_range[1]))
        self._etas = (float(etas[0]), float(etas[1]))
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("prev_grad", p, dtype=jnp.float32)
        self._acc("step_size", p,
                  init=jnp.full_like(p._data, float(self._learning_rate)
                                     if not isinstance(self._learning_rate,
                                                       LRScheduler)
                                     else self._learning_rate.last_lr,
                                     dtype=jnp.float32))

    def _update_param(self, p, g, lr_eff):
        prev = self._acc("prev_grad", p, dtype=jnp.float32)
        size = self._acc("step_size", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        sign = jnp.sign(g32 * prev._data)
        eta_minus, eta_plus = self._etas
        factor = jnp.where(sign > 0, eta_plus,
                           jnp.where(sign < 0, eta_minus, 1.0))
        new_size = jnp.clip(size._data * factor, self._lr_range[0],
                            self._lr_range[1])
        # on sign change the gradient is zeroed (no step, no state carry)
        g_eff = jnp.where(sign < 0, 0.0, g32)
        size._set_data(new_size)
        prev._set_data(g_eff)
        p._set_data((p._data.astype(jnp.float32) -
                     jnp.sign(g_eff) * new_size).astype(p._data.dtype))


class ASGD(Optimizer):
    """Averaged SGD (reference: paddle.optimizer.ASGD): steps along the
    moving sum of the last ``batch_num`` gradients."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._batch_num = max(1, int(batch_num))
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("d", p, dtype=jnp.float32)
        if self._batch_num > 1:
            self._acc("grad_hist", p,
                      init=jnp.zeros((self._batch_num,) + tuple(p._data.shape),
                                     jnp.float32))

    def _update_param(self, p, g, lr_eff):
        d = self._acc("d", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        if self._batch_num > 1:
            # accumulator exists since _create_accumulators; no init arg (it
            # would eagerly allocate a batch_num-sized dead buffer per step)
            hist = self._accumulators["grad_hist"][id(p)]
            slot = (self._step_t._data - 1) % self._batch_num
            old = jax.lax.dynamic_index_in_dim(hist._data, slot, 0,
                                               keepdims=False)
            new_d = d._data - old + g32
            hist._set_data(jax.lax.dynamic_update_index_in_dim(
                hist._data, g32, slot, 0))
        else:
            new_d = g32
        d._set_data(new_d)
        # reference formula divides by n = min(t, batch_num): until the
        # window fills, average over the gradients actually seen
        n = jnp.minimum(self._step_t._data.astype(jnp.float32),
                        jnp.float32(self._batch_num))
        p._set_data((p._data.astype(jnp.float32) -
                     lr_eff * new_d / n).astype(p._data.dtype))


class NAdam(Optimizer):
    """Adam with Nesterov momentum (reference: paddle.optimizer.NAdam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2 = beta1, beta2
        self._epsilon, self._psi = epsilon, momentum_decay
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)
        self._acc("mu_product", p, init=jnp.ones((), jnp.float32))

    def _update_param(self, p, g, lr_eff):
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        mu_prod = self._acc("mu_product", p, init=jnp.ones((), jnp.float32))
        g32 = g.astype(jnp.float32)
        t = self._step_t._data.astype(jnp.float32)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_next = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        new_mu_prod = mu_prod._data * mu_t
        new_m = self._beta1 * m._data + (1 - self._beta1) * g32
        new_v = self._beta2 * v._data + (1 - self._beta2) * g32 * g32
        m._set_data(new_m)
        v._set_data(new_v)
        mu_prod._set_data(new_mu_prod)
        m_hat = (mu_next * new_m / (1 - new_mu_prod * mu_next) +
                 (1 - mu_t) * g32 / (1 - new_mu_prod))
        v_hat = new_v / (1 - self._beta2 ** t)
        p._set_data((p._data.astype(jnp.float32) -
                     lr_eff * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
                     ).astype(p._data.dtype))


class RAdam(Optimizer):
    """Rectified Adam (reference: paddle.optimizer.RAdam): variance
    rectification switches between adaptive and plain-momentum updates."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        if self._groups is not None:
            self._materialize_state()

    def _create_accumulators(self, p):
        self._acc("moment1", p, dtype=jnp.float32)
        self._acc("moment2", p, dtype=jnp.float32)

    def _update_param(self, p, g, lr_eff):
        m = self._acc("moment1", p, dtype=jnp.float32)
        v = self._acc("moment2", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        t = self._step_t._data.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        new_m = b1 * m._data + (1 - b1) * g32
        new_v = b2 * v._data + (1 - b2) * g32 * g32
        m._set_data(new_m)
        v._set_data(new_v)
        m_hat = new_m / (1 - b1 ** t)
        bc2 = 1 - b2 ** t
        rho_inf = 2.0 / (1 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * b2 ** t / bc2
        # rectified path (rho_t > 5): variance estimate is tractable
        r_num = (rho_t - 4.0) * (rho_t - 2.0) * rho_inf
        r_den = (rho_inf - 4.0) * (rho_inf - 2.0) * jnp.clip(rho_t, 1e-6, None)
        r_t = jnp.sqrt(jnp.clip(r_num / r_den, 0.0, None))
        adaptive = (lr_eff * r_t * m_hat * jnp.sqrt(bc2) /
                    (jnp.sqrt(new_v) + self._epsilon))
        plain = lr_eff * m_hat
        upd = jnp.where(rho_t > 5.0, adaptive, plain)
        p._set_data((p._data.astype(jnp.float32) - upd).astype(p._data.dtype))


__all__ += ["Rprop", "ASGD", "NAdam", "RAdam"]
