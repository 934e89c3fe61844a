"""Mixture-of-Experts with expert parallelism.

Parity surface: paddle.incubate.distributed.models.moe (``MoELayer``,
``GShardGate``, ``SwitchGate``, ``NaiveGate``; fused dispatch CUDA ops
number_count/assign_pos/limit_by_capacity — upstream
python/paddle/incubate/distributed/models/moe/ + paddle/fluid/operators moe
ops).

:class:`DroplessMoE` (ISSUE 27) is the serving-side expert layer: sigmoid
or softmax scores (ISSUE 33), top-k renormalised, no capacity and no dropped
token — (token, expert) pairs sorted by expert and one grouped matmul
(``jax.lax.ragged_dot``) over the experts this chip holds — plus shared
experts, averaged or added under a sigmoid gate.

TPU-native design (SURVEY.md §2.5 item 10): token dispatch is the dense
GShard einsum formulation — (tokens, experts, capacity) one-hot dispatch and
combine tensors; no scatter kernels, XLA fuses the einsums onto the MXU. With
an expert-parallel axis active, the (E, C, M) dispatched tensor gets a
sharding constraint on E and XLA emits the all-to-all (the reference's
Global_Scatter/Gather brpc+NCCL ops collapse into GSPMD)."""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, apply
from ..nn import functional as F
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..distributed.topology import get_hybrid_communicate_group

__all__ = ["MoELayer", "NaiveGate", "GShardGate", "SwitchGate",
           "DroplessMoE", "dropless_moe"]


class NaiveGate(Layer):
    """Top-k softmax gate."""

    def __init__(self, d_model: int, num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.5):
        super().__init__()
        from ..nn.common import Linear
        self.gate_proj = Linear(d_model, num_experts, bias_attr=False)
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.l_aux: Optional[Tensor] = None

    def capacity(self, num_tokens: int) -> int:
        c = int(math.ceil(self.top_k * self.capacity_factor * num_tokens
                          / self.num_experts))
        return max(c, 4)

    def forward(self, x: Tensor):
        """x: (S, M) -> (dispatch (S,E,C), combine (S,E,C), aux loss)."""
        logits = self.gate_proj(x)
        s = x.shape[0]
        cap = self.capacity(s)
        e, k = self.num_experts, self.top_k

        def route(lg):
            probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)  # (S,E)
            topv, topi = jax.lax.top_k(probs, k)  # (S,k)
            # position of each routed token within its expert queue
            onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # (S,k,E)
            # priority: first choice before second choice (gshard)
            flat = onehot.transpose(1, 0, 2).reshape(k * lg.shape[0], e)
            pos_in_expert = jnp.cumsum(flat, axis=0) - flat  # (k*S, E)
            pos = jnp.sum(flat * pos_in_expert, axis=-1).reshape(k, lg.shape[0])
            pos = pos.transpose(1, 0)  # (S,k)
            keep = pos < cap
            gates = topv * keep  # drop overflow
            denom = jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
            gates = gates / denom
            cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                        dtype=jnp.float32)  # (S,k,C)
            dispatch = jnp.einsum("ske,skc,sk->sec", onehot, cap_onehot,
                                  keep.astype(jnp.float32))
            combine = jnp.einsum("ske,skc,sk->sec", onehot, cap_onehot, gates)
            # gshard aux loss: mean_prob * token_fraction per expert
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(onehot[:, 0, :], axis=0)
            aux = jnp.sum(me * ce) * e
            return dispatch, combine, aux

        dispatch, combine, aux = apply("moe_gate", route, logits)
        self.l_aux = aux
        return dispatch, combine, aux


class GShardGate(NaiveGate):
    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=1.5,
                 group=None, **kw):
        super().__init__(d_model, num_experts, top_k=top_k,
                         capacity_factor=capacity_factor)


class SwitchGate(NaiveGate):
    def __init__(self, d_model, num_experts, top_k=1, capacity_factor=1.25,
                 group=None, **kw):
        super().__init__(d_model, num_experts, top_k=top_k,
                         capacity_factor=capacity_factor)


def _ep_mesh():
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None, None
    for axis in ("mp", "sharding", "dp"):
        try:
            if int(hcg.mesh.shape[axis]) > 1:
                return hcg.mesh, axis
        except KeyError:
            continue
    return None, None


class MoELayer(Layer):
    """Parity: paddle.incubate.distributed.models.moe.MoELayer.

    ``experts`` is a list/LayerList of expert modules (each maps (C, M) ->
    (C, M')). The dispatched tensor (E, C, M) carries an expert-axis sharding
    constraint when an expert-parallel mesh axis is active.
    """

    def __init__(self, d_model: int, experts, gate=None, moe_group=None,
                 mp_group=None, recompute_interval: int = 0, top_k: int = 2,
                 **kwargs):
        super().__init__()
        self.d_model = d_model
        self.experts = experts if isinstance(experts, LayerList) \
            else LayerList(list(experts))
        num_experts = len(self.experts)
        if gate is None or isinstance(gate, dict):
            cfg = gate or {}
            gtype = cfg.get("type", "gshard")
            cls = {"gshard": GShardGate, "switch": SwitchGate,
                   "naive": NaiveGate}[gtype]
            self.gate = cls(d_model, num_experts,
                            top_k=cfg.get("top_k", top_k),
                            capacity_factor=cfg.get("capacity_factor", 1.5))
        else:
            self.gate = gate
        self.l_aux: Optional[Tensor] = None

    def forward(self, x: Tensor) -> Tensor:
        orig_shape = x.shape
        from ..ops.manipulation import reshape
        flat = reshape(x, [-1, self.d_model])  # (S, M)
        dispatch, combine, aux = self.gate(flat)
        self.l_aux = aux

        # (S, E, C) x (S, M) -> (E, C, M)
        expert_in = apply("moe_dispatch",
                          lambda d, t: jnp.einsum("sec,sm->ecm", d, t),
                          dispatch, flat)
        mesh, axis = _ep_mesh()
        if mesh is not None and len(self.experts) % int(mesh.shape[axis]) == 0:
            expert_in = apply(
                "moe_ep_constraint",
                lambda a: jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, P(axis, None, None))), expert_in)

        outs = []
        for i, expert in enumerate(self.experts):
            outs.append(expert(expert_in[i]))
        from ..ops.manipulation import stack
        expert_out = stack(outs, axis=0)  # (E, C, M')

        out = apply("moe_combine",
                    lambda c, eo: jnp.einsum("sec,ecm->sm", c, eo),
                    combine, expert_out)
        new_shape = orig_shape[:-1] + [out.shape[-1]]
        return reshape(out, new_shape)


# ---------------------------------------------------------------------------
# the dropless expert layer (ISSUE 27)
# ---------------------------------------------------------------------------

# tokens a grouped matmul sorts at once: the sorted (token, expert) pairs of a
# 12k-token prompt would not fit beside a chip's share of the weights
_CHUNK_TOKENS = 1024


_SCORES = {"sigmoid": jax.nn.sigmoid,
           "softmax": functools.partial(jax.nn.softmax, axis=-1)}
_SHARED = ("average", "gated sum")


def _dropless_chunk(h, valid, wr, wg, wu, wd, *, top_k: int, first: int,
                    score: str = "sigmoid"):
    """One chunk of tokens through the routed experts held here.

    ``h`` (T, M); ``valid`` (T,) bool — a padding row routes nowhere;
    ``wr`` (M, E) the router over ALL experts; ``wg``/``wu`` (n, M, F) and
    ``wd`` (n, F, M) the n experts held here, experts ``first .. first+n``.
    Returns ``(routed (T, M) float32, rows per held expert (n,) int32)``.

    Scores, top-k and the weights are float32 on the input as given (a
    near-tie between the k-th and the next score must not turn on a bf16
    rounding of the score itself). Every (token, expert) pair is sorted by
    expert, pairs whose expert is not held here last: the grouped matmul
    walks the held experts' rows only, and a token's weights are
    renormalised over all k chosen, wherever they live. (Tried on the
    chip, PR 27: a decode batch's held experts as one batched matmul over
    every row — XLA streams it no better, 3.1 ms a layer against 1.95.)"""
    t, n = h.shape[0], wg.shape[0]
    f32 = jnp.float32
    with jax.named_scope("moe_route"):
        scores = _SCORES[score](jnp.dot(
            h.astype(f32), wr.astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        top_v, top_i = jax.lax.top_k(scores, top_k)
        weight = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        local = top_i - first
        held = (local >= 0) & (local < n) & valid[:, None]
        key = jnp.where(held, local, n).reshape(-1)       # absent: last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        token = order // top_k
    with jax.named_scope("moe_experts"):
        x = jnp.take(h, token, axis=0)
        gate = jax.lax.ragged_dot(x, wg, sizes, preferred_element_type=f32)
        up = jax.lax.ragged_dot(x, wu, sizes, preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        y = jax.lax.ragged_dot(act, wd, sizes, preferred_element_type=f32)
        # rows past the held experts' belong to no group: whatever the
        # grouped matmul left there is not a result
        y = jnp.where(jnp.take(held.reshape(-1), order)[:, None],
                      y * jnp.take(weight.reshape(-1), order)[:, None], 0.0)
        routed = jnp.take(y, jnp.argsort(order), axis=0).reshape(
            t, top_k, -1).sum(axis=1)
    return routed, sizes


def dropless_moe(h, valid, wr, wg, wu, wd, *shared, top_k: int, first: int,
                 chunk: int, score: str = "sigmoid",
                 shared_mode: str = "average"):
    """The whole layer on arrays: routed experts held here (chunked over
    tokens: the sorted pairs of a 12k-token prompt would not fit beside
    the weights), their scores ``score`` (``sigmoid`` | ``softmax``) of the
    router's output over ALL experts, plus the ``shared`` experts, if any —
    one ``[M, s*F]`` / ``[M, s*F]`` / ``[s*F, M]`` triple. ``shared_mode``
    ``average`` scales it by ``1/s``, the same sum as s experts averaged;
    ``gated sum`` takes a fourth array ``[M, 1]`` and adds the triple's
    output scaled by ``sigmoid(h . w)``, float32 a token. Returns ``(out (T,
    M) in h's dtype, rows per held expert (n,) int32)``."""
    t = h.shape[0]
    part = functools.partial(_dropless_chunk, wr=wr, wg=wg, wu=wu, wd=wd,
                             top_k=top_k, first=first, score=score)
    if t <= chunk:
        routed, sizes = part(h, valid)
    else:
        pad = -t % chunk
        hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, chunk, h.shape[1])
        vp = jnp.pad(valid, (0, pad)).reshape(-1, chunk)
        routed, sizes = jax.lax.map(lambda a: part(a[0], a[1]), (hp, vp))
        routed = routed.reshape(-1, h.shape[1])[:t]
        sizes = sizes.sum(axis=0)
    if shared:
        sg, su, sd = shared[:3]
        with jax.named_scope("moe_shared"):
            f32 = jnp.float32
            mid = jax.nn.silu(jnp.dot(h, sg, preferred_element_type=f32)) \
                * jnp.dot(h, su, preferred_element_type=f32)
            out = jnp.dot(mid.astype(h.dtype), sd, preferred_element_type=f32)
            if shared_mode == "average":
                scale = 1.0 / (sg.shape[1] // wg.shape[2])
            else:
                scale = jax.nn.sigmoid(jnp.dot(
                    h.astype(f32), shared[3].astype(f32),
                    precision=jax.lax.Precision.HIGHEST))
            routed = routed + out * scale
    return routed.astype(h.dtype), sizes


class DroplessMoE(Layer):
    """Top-k mixture of SwiGLU experts without capacity, routed by the
    ``score`` (``sigmoid`` | ``softmax``, float32) of the router's output:
    every (token, expert) pair is computed, none dropped.

    The layer is told which experts it holds (``experts_held = (first,
    count)`` of ``num_experts``): it routes over all of them, renormalises
    each token's weights over all ``top_k`` chosen, and adds only what its
    own experts give — one chip's part of an expert-parallel layer, which
    on one chip runs without its exchange. ``num_shared`` shared experts
    of width ``d_ff_shared`` (default ``d_ff``) run for every token:
    ``shared="average"`` averages them, ``"gated sum"`` adds them scaled by
    ``sigmoid(x . shared_score)``.

    ``forward(x (T, M), valid=None) -> (out (T, M), rows (count,) int32)``:
    ``rows`` counts the pairs each held expert computed (the serving
    engine's ``serving.moe.*`` counters); ``valid`` (T,) bool marks rows
    that are tokens, not batch padding."""

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 top_k: int, experts_held=None, num_shared: int = 0,
                 dtype=None, chunk_tokens: int = _CHUNK_TOKENS,
                 score: str = "sigmoid", shared: str = "average",
                 d_ff_shared: Optional[int] = None):
        super().__init__(dtype=dtype)
        from ..nn.initializer import Normal
        first, count = experts_held or (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"experts_held {(first, count)} lies outside "
                             f"the {num_experts} experts")
        if score not in _SCORES or shared not in _SHARED:
            raise ValueError(f"score must be one of {sorted(_SCORES)} and "
                             f"shared of {_SHARED}, got {score!r}, {shared!r}")
        self.top_k, self.first, self.num_shared = top_k, first, num_shared
        self.chunk_tokens = chunk_tokens
        self.score, self.shared = score, shared

        def w(*shape):
            return self.create_parameter(shape, dtype=dtype,
                                         default_initializer=Normal(std=0.02))

        self.router = w(d_model, num_experts)
        self.w_gate, self.w_up = w(count, d_model, d_ff), w(count, d_model, d_ff)
        self.w_down = w(count, d_ff, d_model)
        self.shared_gate = self.shared_up = self.shared_down = None
        self.shared_score = None
        if num_shared:
            wide = num_shared * (d_ff_shared or d_ff)
            self.shared_gate = w(d_model, wide)
            self.shared_up = w(d_model, wide)
            self.shared_down = w(wide, d_model)
            if shared == "gated sum":
                self.shared_score = w(d_model, 1)

    def forward(self, x: Tensor, valid: Optional[Tensor] = None):
        fn = functools.partial(dropless_moe, top_k=self.top_k,
                               first=self.first, chunk=self.chunk_tokens,
                               score=self.score, shared_mode=self.shared)
        if valid is None:
            valid = Tensor(jnp.ones((x.shape[0],), bool))
        shared = tuple(w for w in (self.shared_gate, self.shared_up,
                                   self.shared_down, self.shared_score)
                       if w is not None)
        return apply("dropless_moe", fn, x, valid, self.router, self.w_gate,
                     self.w_up, self.w_down, *shared, differentiable=False,
                     amp=False)
