"""Qwen3-Next (``qwen3_next``): Gated DeltaNet layers — a delta-rule state and
a convolution tail per sequence, a decay and a write strength per token —
beside gated full attention, every layer over a softmax-routed mixture of
many small experts with one gated shared expert (ISSUE 33).

``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred). Published
layer ``i`` is full attention iff ``(i + 1) % full_attention_interval == 0``,
else Gated DeltaNet; every layer is::

    x = x + mixer(N1(x));   x = x + moe(N2(x))

then ``N(x) @ lm_head`` (untied).

Gated attention: ``[q | gate] = h Wq`` (H x 2 x D, split per head), ``k, v =
h Wk, h Wv`` (H_kv x D); ``q, k = Nq(q), Nk(k)`` over the head; rotary (halves
rotated, theta ``rope_theta``) on the first ``partial_rotary_factor * D``
dimensions; causal softmax of ``q k^T / sqrt(D)``; ``(attn * sigmoid(gate))
Wo``.

Gated DeltaNet: ``[q | k | v | z] = h Wqkvz`` (Hk x Dk, Hk x Dk, Hv x Dv, Hv x
Dv — the order of the columns is this file's), ``[b | a] = h Wba``; ``[q | k |
v] = silu(conv(...))``, a depthwise causal convolution of
``linear_conv_kernel_dim`` taps without bias; ``beta = sigmoid(b)``, ``g =
-exp(A_log) softplus(a + dt_bias)``; ``q, k`` L2-normalised per head, ``q /
sqrt(Dk)``; key head ``h // (Hv / Hk)`` serves value head ``h``; the
recurrence of ``ops/linear_attention.py``; ``rmsnorm(o; w_o) * silu(z)`` per
head, then ``W_out``.

Experts: ``incubate.moe.DroplessMoE`` with ``score="softmax"`` and
``shared="gated sum"``.

Not built: the multi-token-prediction module.

The model may be built as a chip's SHARE: ``layers_run`` names the published
layer indices it holds (a pipeline stage), ``experts_held = (first, count)``
the routed experts (it routes over all ``num_experts``), ``vocab_held`` the
rows of the embedding and the columns of the head (ids index the slice).
Parameters are created in ``config.dtype``. Serving only: everything runs
under ``no_grad``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor, apply
from ..core.tracing import no_grad
from ..incubate.moe import DroplessMoE, dropless_moe
from ..nn.initializer import Constant, Normal, Uniform
from ..ops import rotary
from ..ops.linear_attention import (StateDecodeCache, StatePrefill,
                                    chunked_gated_delta_rule,
                                    conv_tail_decode, gated_delta_decode)
from ..ops.paged_attention import paged_decode_attention

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM"]

_NEG_INF = -1e30
_Q_ROWS = 256       # query rows of a prefill whose scores exist at once
_LANES = 128


@dataclass
class Qwen3NextConfig:
    # the catalog's keys, as published
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48          # the PUBLISHED depth
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_value_head_dim: int = 128
    linear_num_value_heads: int = 32
    num_experts: int = 512               # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    max_position_embeddings: int = 262144
    # this chip's share (all of it if None)
    layers_run: Optional[Tuple[int, ...]] = None
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.layers_run is None:
            self.layers_run = tuple(range(self.num_hidden_layers))
        self.layers_run = tuple(int(i) for i in self.layers_run)
        self.experts_held = tuple(self.experts_held or (0, self.num_experts))
        self.vocab_held = tuple(self.vocab_held or (0, self.vocab_size))
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("value heads must be a multiple of key heads")

    def is_full(self, index: int) -> bool:
        return (index + 1) % self.full_attention_interval == 0

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The serving engine's names for the layers run."""
        return tuple("full" if self.is_full(i) else "linear"
                     for i in self.layers_run)

    @property
    def conv_channels(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def state_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        """A slot's state per Gated DeltaNet layer, in parts: the delta
        state ``(Hv, Dk, Dv)`` and the convolution's tail, its last ``K - 1``
        inputs with the channels laid out in rows of 128 (whole tiles on a
        TPU; one row where they are no multiple of 128)."""
        c = self.conv_channels
        lanes = _LANES if c % _LANES == 0 else c
        return ((self.linear_num_value_heads, self.linear_key_head_dim,
                 self.linear_value_head_dim),
                (self.linear_conv_kernel_dim - 1, c // lanes, lanes))

    @staticmethod
    def tiny(**over) -> "Qwen3NextConfig":
        """Both mixers and the expert layer at a size the CPU runs: two
        periods of (delta, delta, full), 8 experts chosen 3 at a time."""
        cfg = dict(vocab_size=96, hidden_size=32, num_hidden_layers=6,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   partial_rotary_factor=0.5, full_attention_interval=3,
                   linear_key_head_dim=8, linear_num_key_heads=2,
                   linear_value_head_dim=8, linear_num_value_heads=4,
                   num_experts=8, num_experts_per_tok=3,
                   moe_intermediate_size=16,
                   shared_expert_intermediate_size=16,
                   max_position_embeddings=512)
        cfg.update(over)
        return Qwen3NextConfig(**cfg)


# ---------------------------------------------------------------------------
# the layer equations on arrays
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis, float32 inside."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _causal_attention(q, k, v, start: int):
    """``q`` (Tq, H, D) at positions ``start ..`` over ``k``/``v`` (Tk, H_kv,
    D) at positions ``0 ..``: causal, ``_Q_ROWS`` query rows at a time."""
    tq, h, d = q.shape
    tk, h_kv, _ = k.shape
    rep = h // h_kv
    rows = min(_Q_ROWS, tq)
    pad = -tq % rows
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, rows, h_kv, rep, d)
    kg, vg = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)     # (Hkv, Tk, D)
    cols = jnp.arange(tk)

    def block(args):
        qq, lo = args
        s = jnp.einsum("qgrd,gkd->gqrk", qq, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        at = start + lo + jnp.arange(rows)
        s = jnp.where((cols[None, :] <= at[:, None])[None, :, None, :], s,
                      _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("gqrk,gkd->qgrd", p, vg,
                          preferred_element_type=jnp.float32)

    with jax.named_scope("attn_full"):
        out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * rows))
    return out.reshape(-1, h, d)[:tq].astype(q.dtype)


_FULL = ("input_norm", "q_proj", "k_proj", "v_proj", "q_norm", "k_norm",
         "o_proj")
_DELTA = ("input_norm", "in_qkvz", "in_ba", "conv", "A_log", "dt_bias",
          "o_norm", "out_proj")
_MOE = ("post_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down", "shared_score")


class _Layer(nn.Layer):
    def __init__(self, config: Qwen3NextConfig, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.index = index                   # the PUBLISHED layer index
        self.full = c.is_full(index)
        e = c.hidden_size

        def lin(n_in, n_out):
            return self.create_parameter((n_in, n_out), dtype=c.dtype,
                                         default_initializer=Normal(std=0.02))

        def const(n, value):
            return self.create_parameter(
                (n,), dtype=c.dtype, default_initializer=Constant(value))

        self.input_norm = const(e, 0.0)
        if self.full:
            h, hkv, d = c.num_attention_heads, c.num_key_value_heads, \
                c.head_dim
            self.q_proj = lin(e, h * 2 * d)          # [q | gate] a head
            self.k_proj = lin(e, hkv * d)
            self.v_proj = lin(e, hkv * d)
            self.q_norm = const(d, 0.0)
            self.k_norm = const(d, 0.0)
            self.o_proj = lin(h * d, e)
        else:
            hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
            self.in_qkvz = lin(e, c.conv_channels + hv * dv)
            self.in_ba = lin(e, 2 * hv)
            # the family's own initial values: a convolution drawn uniformly
            # within 1 / sqrt(taps), A ~ U(0, 16), dt_bias 1 (float32: the
            # decay is computed there)
            bound = 1.0 / math.sqrt(c.linear_conv_kernel_dim)
            self.conv = self.create_parameter(
                (c.conv_channels, c.linear_conv_kernel_dim), dtype=c.dtype,
                default_initializer=Uniform(-bound, bound))
            self.A_log = self.create_parameter(
                (hv,), dtype="float32",
                default_initializer=Uniform(0.0, 16.0))
            self.A_log._set_data(jnp.log(jnp.maximum(self.A_log._data, 1e-4)))
            self.dt_bias = self.create_parameter(
                (hv,), dtype="float32", default_initializer=Constant(1.0))
            self.o_norm = const(dv, 1.0)
            self.out_proj = lin(hv * dv, e)
        self.post_norm = const(e, 0.0)
        self.moe = DroplessMoE(
            e, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            experts_held=c.experts_held, num_shared=1, dtype=c.dtype,
            score="softmax", shared="gated sum",
            d_ff_shared=c.shared_expert_intermediate_size)

    def tensors(self) -> List[Tensor]:
        own = [getattr(self, n) for n in (_FULL if self.full else _DELTA)]
        return own + [self.post_norm] + [getattr(self.moe, n)
                                         for n in _MOE[1:]]

    def weights(self, flat) -> dict:
        return dict(zip((_FULL if self.full else _DELTA) + _MOE, flat))


class Qwen3NextForCausalLM(nn.Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__(dtype=config.dtype)
        self.config = c = config
        self.embed_tokens = self.create_parameter(
            (c.vocab_held[1], c.hidden_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        self.layers = nn.LayerList([_Layer(c, i) for i in c.layers_run])
        self.norm = self.create_parameter(
            (c.hidden_size,), dtype=c.dtype,
            default_initializer=Constant(0.0))
        self.lm_head = self.create_parameter(
            (c.hidden_size, c.vocab_held[1]), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        # a prefill's layers of one kind are one traced function called once
        # a layer: tracing eight unrolled layers was most of what warming a
        # prompt length cost (ISSUE 33: 25 programs a run)
        self._full_layer = jax.jit(self._full_prefill, static_argnums=(3,))
        self._delta_layer = jax.jit(self._delta_prefill, static_argnums=(4,))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # -- arrays in, arrays out ------------------------------------------
    def _moe(self, x, w, valid):
        """``x + moe(N2(x))`` and the rows each held expert computed."""
        c = self.config
        h = _norm(x, w["post_norm"], c.rms_norm_eps)
        out, rows = dropless_moe(
            h, valid, w["router"], w["w_gate"], w["w_up"], w["w_down"],
            w["shared_gate"], w["shared_up"], w["shared_down"],
            w["shared_score"], top_k=c.num_experts_per_tok,
            first=c.experts_held[0], chunk=1024, score="softmax",
            shared_mode="gated sum")
        return x + out, rows

    def _attn_qkv(self, h, w, pos):
        """Normalised rows ``h`` (N, E) at ``pos`` (N,) -> q (N, H, D), gate
        (N, H * D), k, v (N, H_kv, D)."""
        c = self.config
        n, d = h.shape[0], c.head_dim
        inv, _ = rotary.frequencies({"rope_theta": c.rope_theta},
                                    int(d * c.partial_rotary_factor))
        qg = jnp.dot(h, w["q_proj"]).reshape(n, c.num_attention_heads, 2 * d)
        q, gate = qg[..., :d], qg[..., d:].reshape(n, -1)
        k = jnp.dot(h, w["k_proj"]).reshape(n, c.num_key_value_heads, d)
        v = jnp.dot(h, w["v_proj"]).reshape(n, c.num_key_value_heads, d)
        q = rotary.rotate_halves(_norm(q, w["q_norm"], c.rms_norm_eps), pos,
                                 inv)
        k = rotary.rotate_halves(_norm(k, w["k_norm"], c.rms_norm_eps), pos,
                                 inv)
        return q, gate, k, v

    def _attn_out(self, x, attn, gate, w):
        n = x.shape[0]
        o = (attn.reshape(n, -1).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        return x + jnp.dot(o, w["o_proj"]).astype(x.dtype)

    def _delta_in(self, h, w):
        """Normalised rows ``h`` (N, E) -> the convolution's inputs (N, C)
        float32, z (N, Hv, Dv), beta and g (N, Hv) float32."""
        c = self.config
        n, hv = h.shape[0], c.linear_num_value_heads
        mixed = jnp.dot(h, w["in_qkvz"])
        ch = c.conv_channels
        z = mixed[:, ch:].reshape(n, hv, c.linear_value_head_dim)
        ba = jnp.dot(h, w["in_ba"]).astype(jnp.float32)
        g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, hv:] + w["dt_bias"].astype(jnp.float32))
        return mixed[:, :ch].astype(jnp.float32), z, \
            jax.nn.sigmoid(ba[:, :hv]), g

    def _delta_qkv(self, conv):
        """The convolution's output (N, C) float32 -> q, k (N, Hv, Dk)
        normalised, scaled and repeated per value head, v (N, Hv, Dv)."""
        c = self.config
        n = conv.shape[0]
        hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
        hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
        q = _l2(conv[:, :hk * dk].reshape(n, hk, dk)) / math.sqrt(dk)
        k = _l2(conv[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
        v = conv[:, 2 * hk * dk:].reshape(n, hv, dv)
        return jnp.repeat(q, hv // hk, axis=1), \
            jnp.repeat(k, hv // hk, axis=1), v

    def _delta_out(self, x, o, z, w):
        """``x + (rmsnorm(o; w_o) * silu(z)) W_out`` for ``o`` (N, Hv, Dv)
        float32."""
        c = self.config
        y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + c.rms_norm_eps) \
            * w["o_norm"].astype(jnp.float32)
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        return x + jnp.dot(y.reshape(x.shape[0], -1),
                           w["out_proj"]).astype(x.dtype)

    def _full_prefill(self, x, w, prefix_kv, start: int):
        """A gated-attention layer over rows ``x`` (T, E) at positions
        ``start ..`` after ``prefix_kv`` (2, H_kv, M, D) whose first
        ``start`` positions are the prefix -> ``(x', kv with the run
        written, rows per held expert)``."""
        c = self.config
        t = x.shape[0]
        h = _norm(x, w["input_norm"], c.rms_norm_eps)
        q, gate, k, v = self._attn_qkv(h, w, start + jnp.arange(t))
        new = jnp.swapaxes(jnp.stack([k, v]), 1, 2)           # (2, Hkv, T, D)
        kv = jax.lax.dynamic_update_slice(
            prefix_kv, new.astype(prefix_kv.dtype), (0, 0, start, 0))
        kk = jnp.swapaxes(kv[0, :, :start + t], 0, 1).astype(x.dtype)
        vv = jnp.swapaxes(kv[1, :, :start + t], 0, 1).astype(x.dtype)
        x = self._attn_out(x, _causal_attention(q, kk, vv, start), gate, w)
        x, rows = self._moe(x, w, jnp.ones((t,), bool))
        return x, kv, rows

    def _delta_prefill(self, x, w, state, tail, block: int):
        """A Gated DeltaNet layer over rows ``x`` (T, E) from ``state`` (Hv,
        Dk, Dv) and ``tail`` (K - 1, C) -> ``(x', state and tail after the
        last row, those after each whole block (n, ...), rows per held
        expert)``."""
        c = self.config
        t = x.shape[0]
        taps = c.linear_conv_kernel_dim
        cw = w["conv"].astype(jnp.float32)                    # (C, K)

        def run(carry, xb):
            S, tl = carry
            n = xb.shape[0]
            h = _norm(xb, w["input_norm"], c.rms_norm_eps)
            u, z, beta, g = self._delta_in(h, w)
            seen = jnp.concatenate([tl, u])                   # (K - 1 + n, C)
            conv = jax.nn.silu(sum(seen[j:j + n] * cw[:, j]
                                   for j in range(taps)))
            q, k, v = self._delta_qkv(conv)
            o, S = chunked_gated_delta_rule(q, k, v, g, beta, S)
            xb = self._delta_out(xb, o, z, w)
            xb, rows = self._moe(xb, w, jnp.ones((n,), bool))
            return (S, seen[n:]), xb, rows

        full = t // block
        carry = (state.astype(jnp.float32), tail.astype(jnp.float32))
        outs, rows = [], jnp.zeros((c.experts_held[1],), jnp.int32)
        snaps = tuple(jnp.zeros((0,) + a.shape, jnp.float32) for a in carry)
        if full:
            def body(cr, xb):
                cr, xb, r = run(cr, xb)
                return cr, (xb, cr, r)
            carry, (head, snaps, r) = jax.lax.scan(
                body, carry, x[:full * block].reshape(full, block, -1))
            outs.append(head.reshape(full * block, -1))
            rows = rows + r.sum(axis=0)
        if full * block < t:
            carry, rest, r = run(carry, x[full * block:])
            outs.append(rest)
            rows = rows + r
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0], carry, \
            snaps, rows

    def _run_arrays(self, ids, kv, states, flat, start: int, block: int):
        """The layers run over one sequence ``ids`` (T,) at positions
        ``start ..``: ``kv`` (L_full, 2, H_kv, M, D), ``states`` the state
        parts ``((L_lin, Hv, Dk, Dv), (L_lin, K - 1, R, lanes))`` before
        ``start``. Returns ``(h (T, E), kv', states', snapshots per part (n,
        L_lin, ...), rows (L, held))``."""
        c = self.config
        x = jnp.take(flat[0], ids.astype(jnp.int32), axis=0)
        at = 1
        tail_shape = c.state_shapes[1]
        kvs, new, snaps, rows = [], [], [], []
        for layer in self.layers:
            n = len(layer.tensors())
            w = layer.weights(flat[at:at + n])
            at += n
            if layer.full:
                x, kv_l, r = self._full_layer(x, w, kv[len(kvs)], start)
                kvs.append(kv_l)
            else:
                i = len(new)
                x, (S, tl), (sn_s, sn_t), r = self._delta_layer(
                    x, w, states[0][i],
                    states[1][i].reshape(tail_shape[0], -1), block)
                new.append((S, tl.reshape(tail_shape)))
                snaps.append((sn_s, sn_t.reshape((-1,) + tail_shape)))
            rows.append(r)

        def stacked(parts, axis, like):
            return tuple(jnp.stack(p, axis=axis) for p in zip(*parts)) \
                if parts else like
        return x, jnp.stack(kvs) if kvs else kv, \
            stacked(new, 0, tuple(states)), \
            stacked(snaps, 1, tuple(jnp.zeros((0,) + s.shape, jnp.float32)
                                    for s in states)), \
            jnp.stack(rows)

    def _flat(self) -> List[Tensor]:
        """Every weight in ``_run_arrays``' order; then the final norm and
        the head."""
        return [self.embed_tokens] + [t for layer in self.layers
                                      for t in layer.tensors()] \
            + [self.norm, self.lm_head]

    def _logits_arrays(self, h, norm, head):
        return jnp.dot(_norm(h, norm, self.config.rms_norm_eps), head,
                       preferred_element_type=jnp.float32)

    def _caches(self, max_len: int):
        """Empty ``(kv, states)`` arrays for one sequence of ``max_len``."""
        c = self.config
        kinds = c.layer_kinds
        kv = jnp.zeros((kinds.count("full"), 2, c.num_key_value_heads,
                        max_len, c.head_dim), self.embed_tokens._data.dtype)
        return kv, tuple(jnp.zeros((kinds.count("linear"),) + s, jnp.float32)
                         for s in c.state_shapes)

    # -- whole-sequence forward -----------------------------------------
    def forward(self, input_ids: Tensor, block: int = 2048) -> Tensor:
        """``input_ids`` (1, T) or (T,) -> logits (1, T, V) / (T, V) float32
        over the vocabulary held here."""
        batched = len(input_ids.shape) == 2
        ids = input_ids[0] if batched else input_ids
        kv, states = self._caches(int(ids.shape[0]))

        def f(a, *flat):
            h = self._run_arrays(a, kv, states, flat[:-2], 0, block)[0]
            return self._logits_arrays(h, flat[-2], flat[-1])
        with no_grad():
            # one program for the whole run: eagerly, every layer's scans
            # and maps would each compile on their own
            lg = apply("qwen3_next_logits", jax.jit(f), ids, *self._flat(),
                       differentiable=False, amp=False)
        return lg[None] if batched else lg

    def generate(self, input_ids: Tensor, max_new_tokens: int = 32) -> Tensor:
        """Greedy decode of one sequence by re-running the prefix (the
        plain loop: the serving engine is the cached path)."""
        from ..ops.manipulation import concat
        from ..ops.reduce import argmax
        ids = input_ids[0] if len(input_ids.shape) == 2 else input_ids
        for _ in range(max_new_tokens):
            nxt = argmax(self.forward(ids)[-1:], axis=-1).astype(ids.dtype)
            ids = concat([ids, nxt], axis=0)
        return ids[None]

    # -- the serving engine's contract ------------------------------------
    def serving_callables(self, max_len: int, block: int = 2048,
                          with_logits: bool = False):
        """``(prefill_fn, step_fn)`` for an engine built with
        ``ServingConfig(num_layers=len(layers_run), num_heads=
        num_key_value_heads, head_dim=head_dim, layer_kinds=
        config.layer_kinds, state_shape=config.state_shapes,
        state_snapshot_tokens=block)``.

        * ``prefill_fn(ids (1, Lp), cache: StatePrefill, start=0)`` runs
          positions ``[start, start + Lp)``: ``start`` is a multiple of
          ``block`` (a state snapshot's boundary), ``cache.kv`` holds the
          prefix below it and ``cache.states`` the state and the tail at it.
          Returns the first token and the cache filled as
          :class:`StatePrefill` says.
        * ``step_fn(tok (B, 1), cache: StateDecodeCache, t (B,))`` decodes
          one token a row: an attention layer streams its pages through the
          paged decode kernel, a Gated DeltaNet layer shifts the row's tail
          and updates its state in the pools. A row with ``t == 0`` is batch
          padding: it routes to no expert and names the scratch row.

        Both return a third value, the int32 ``(L, experts held)`` count of
        (token, expert) pairs computed, which the engine reads back with the
        tokens. ``with_logits`` (a check's way to logits through the
        compiled programs; not for an ``Engine``'s step loop) appends the
        float32 logits bit-cast to int32: :meth:`split_extras` takes the
        two apart."""
        c = self.config
        if max_len > c.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings "
                             f"{c.max_position_embeddings}")
        layers = list(self.layers)
        roles, n_f, n_l = [], 0, 0
        for layer in layers:
            roles.append(n_f if layer.full else n_l)
            n_f, n_l = n_f + layer.full, n_l + (not layer.full)
        tail_shape = c.state_shapes[1]

        def counted(rows, lg):
            if not with_logits:
                return rows
            return jnp.concatenate([
                rows.reshape(-1),
                jax.lax.bitcast_convert_type(lg, jnp.int32).reshape(-1)])

        def prefill_fn(ids, cache: StatePrefill, start=0):
            def f(ids_a, kv_a, s_a, t_a, *flat):
                h, kv2, st2, snaps, rows = self._run_arrays(
                    ids_a[0], kv_a[:, :, 0], (s_a, t_a), flat[:-2], start,
                    block)
                lg = self._logits_arrays(h[-1:], flat[-2], flat[-1])
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (nxt.reshape(1, 1), kv2[:, :, None], counted(rows, lg),
                        *st2, *snaps)
            nxt, kv2, rows, s2, t2, sn_s, sn_t = apply(
                "qwen3_next_prefill", f, ids, cache.kv, *cache.states,
                *self._flat(), differentiable=False, amp=False)
            return nxt, StatePrefill(kv=kv2, states=(s2, t2),
                                     snapshots=(sn_s, sn_t)), rows

        def full_pre(layer):
            def f(x, t, *flat):
                w = layer.weights(flat)
                h = _norm(x, w["input_norm"], c.rms_norm_eps)
                return self._attn_qkv(h, w, t)
            return f

        def full_post(layer):
            def f(x, attn, gate, valid, *flat):
                w = layer.weights(flat)
                return self._moe(self._attn_out(x, attn, gate, w), w, valid)
            return f

        def delta(layer, role, impl, interpret):
            def f(x, valid, state, tail, rows_, *flat):
                w = layer.weights(flat)
                h = _norm(x, w["input_norm"], c.rms_norm_eps)
                u, z, beta, g = self._delta_in(h, w)
                cw = w["conv"].astype(jnp.float32).T.reshape(
                    (c.linear_conv_kernel_dim,) + tail_shape[1:])
                conv, tail = conv_tail_decode(
                    u.reshape((-1,) + tail_shape[1:]), cw, tail, rows_, role,
                    impl=impl, interpret=interpret)
                q, k, v = self._delta_qkv(conv.reshape(x.shape[0], -1))
                o, state = gated_delta_decode(
                    q, k, v, g, beta, state, rows_, role, impl=impl,
                    interpret=interpret)
                x, rows = self._moe(self._delta_out(x, o, z, w), w, valid)
                return x, rows, state, tail
            return f

        def step_fn(tok, cache, t):
            if not isinstance(cache, StateDecodeCache):
                raise TypeError(
                    "Qwen3NextForCausalLM decodes over a StateDecodeCache "
                    "(pages and the state pools): build the engine with "
                    "this config's layer_kinds and state_shapes")
            x = apply("qwen3_next_embed", lambda i, e: jnp.take(
                e, i.reshape(-1).astype(jnp.int32), axis=0),
                tok, self.embed_tokens, differentiable=False, amp=False)
            valid = t > 0
            rows = []
            for layer, role in zip(layers, roles):
                ws = layer.tensors()
                if layer.full:
                    q, gate, k, v = apply(
                        "qwen3_next_qkv", full_pre(layer), x, t, *ws,
                        differentiable=False, amp=False)
                    attn, cache = paged_decode_attention(
                        q, k, v, cache.at_layer(role))
                    x, r = apply("qwen3_next_attn_out", full_post(layer), x,
                                 attn, gate, valid, *ws,
                                 differentiable=False, amp=False)
                else:
                    x, r, state, tail = apply(
                        "qwen3_next_delta",
                        delta(layer, role, cache.impl, cache.interpret), x,
                        valid, *cache.states, cache.state_rows, *ws,
                        differentiable=False, amp=False)
                    cache = replace(cache, states=(state, tail))
                rows.append(r)

            def head(a, n_, hd, *rs):
                lg = self._logits_arrays(a, n_, hd)
                return jnp.argmax(lg, axis=-1).astype(jnp.int32).reshape(
                    -1, 1), counted(jnp.stack(rs), lg)
            nxt, rows = apply("qwen3_next_head", head, x, self.norm,
                              self.lm_head, *rows, differentiable=False,
                              amp=False)
            return nxt, cache, rows

        return prefill_fn, step_fn

    def split_extras(self, flat, batch: int):
        """What a ``with_logits`` call of ``batch`` rows left behind its
        tokens (``Step.read()[1]``) -> ``(rows (L, held) int32, logits
        (batch, vocab held) float32)``."""
        c = self.config
        flat = np.asarray(flat, np.int32)
        n = len(c.layers_run) * c.experts_held[1]
        return flat[:n].reshape(len(c.layers_run), -1), \
            flat[n:].view(np.float32).reshape(batch, c.vocab_held[1])
