"""Mellum 2 (``mellum``): a pre-norm decoder whose layers keep a 1024-token
sliding window three times in four and attend to every position the fourth
time, with YaRN-stretched rotary there, over softmax-routed experts.

One layer, for input ``x`` (T, E)::

    h = N1(x);  q, k, v = h Wq, h Wk, h Wv      # H query heads on H_kv KV
                                                # heads of head_dim, no bias
    sliding layer:  q, k = rotate(q, k; default frequencies)
                    keys with 0 <= i - j < sliding_window
    full layer:     q, k = rotate(q, k; YaRN frequencies, cos/sin scaled)
                    keys with j <= i
    x = x + softmax(q k^T / sqrt(D)) v Wo
    h = N2(x);  p = softmax(h Wr) (float32);  top-k, renormalised
    x = x + sum_k p_k (silu(h Wg_k) * h Wu_k) Wd_k     # no shared expert

with ``N(x) = x rsqrt(mean(x^2) + eps) w``; after the last layer ``N`` and
the untied head. Both rotary forms turn halves (``ops/rotary.py``: one
function per ``rope_parameters`` entry, by layer type). The experts are
:class:`~paddle_tpu.incubate.moe.DroplessMoE` with softmax scores, every
expert held. Parameters are created in ``config.dtype``.

Serving only: ``forward`` / ``generate`` / ``serving_callables`` run under
``no_grad``; the window band of ``flash_attention`` has no backward.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor, apply
from ..core.tracing import no_grad
from ..incubate.moe import DroplessMoE
from ..nn.initializer import Constant, Normal
from ..ops import rotary
from ..ops.flash_attention import _flash_core, _flash_core_window
from ..ops.manipulation import concat, reshape, stack
from ..ops.paged_attention import PagedDecodeCache, paged_decode_attention

__all__ = ["MellumConfig", "MellumForCausalLM"]

_FLASH_ROWS = 512      # a run this long takes the flash kernel, padded
_SCORES = 1 << 24      # float32 scores a block of the looped attention holds
_NEG_INF = -1e30

# the published rotary: YaRN on full layers, plain on sliding ones
ROPE_PARAMETERS = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    sliding_window: int = 1024
    layer_types: Optional[Tuple[str, ...]] = None
    rope_parameters: Optional[Dict] = None
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 131072
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if (i + 1) % 4 == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        if self.rope_parameters is None:
            self.rope_parameters = ROPE_PARAMETERS
        if not self.norm_topk_prob:
            raise NotImplementedError("norm_topk_prob false: the expert "
                                      "layer renormalises the top-k")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The serving engine's names for ``layer_types``."""
        return tuple("window" if t == "sliding_attention" else "full"
                     for t in self.layer_types)

    @staticmethod
    def tiny(**over) -> "MellumConfig":
        """Every mechanism at a size the CPU runs: a window shorter than a
        prompt, YaRN whose ramp lies inside the head (pairs 0-2 of 8) and an
        ``original_max_position_embeddings`` shorter than a prompt, one
        period of the published pattern."""
        cfg = dict(vocab_size=96, hidden_size=64, moe_intermediate_size=32,
                   num_hidden_layers=4, num_attention_heads=8,
                   num_key_value_heads=2, head_dim=16, sliding_window=8,
                   num_experts=8, num_experts_per_tok=2,
                   max_position_embeddings=1024, rope_parameters={
                       "full_attention": {
                           "rope_type": "yarn", "rope_theta": 1000,
                           "factor": 4, "original_max_position_embeddings": 64,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
                       "sliding_attention": {"rope_type": "default",
                                             "rope_theta": 1000}})
        cfg.update(over)
        return MellumConfig(**cfg)


def _rms_norm(x: Tensor, weight: Tensor, eps: float) -> Tensor:
    def f(a, w):
        a32 = a.astype(jnp.float32)
        return (a32 * jax.lax.rsqrt(jnp.mean(jnp.square(a32), -1,
                                             keepdims=True) + eps)
                * w.astype(jnp.float32)).astype(a.dtype)
    return apply("mellum_rms_norm", f, x, weight)


def _looped_attention(q, k, v, start: int, first: int,
                      window: Optional[int]):
    """Attention of ``q`` (Tq, H, D), rows at positions ``start + i``, over
    ``k``/``v`` (Tk, H_kv, D) at positions ``first ..``: causal, within
    ``window`` if given. Blocks of query rows go through one ``lax.map`` —
    a loop, not a program unrolled a block — with the query heads of one KV
    head as rows of one matmul, so no block holds more than ``_SCORES``
    scores. For runs the flash kernel cannot tile."""
    tq, h, d = q.shape
    tk, h_kv, _ = k.shape
    rep = h // h_kv
    block = max(1, min(tq, _SCORES // max(1, tk * h)))
    nb = -(-tq // block)
    qg = jnp.pad(q, ((0, nb * block - tq), (0, 0), (0, 0))).reshape(
        nb, block, h_kv, rep, d)
    kg, vg = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)    # (Hkv,Tk,D)
    cols = first + jnp.arange(tk)

    def one(args):
        qb, at = args
        rows = start + at + jnp.arange(block)
        s = jnp.einsum("qgrd,gkd->gqrk", qb, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep &= rows[:, None] - cols[None, :] < window
        s = jnp.where(keep[None, :, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("gqrk,gkd->qgrd", p, vg,
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(one, (qg, jnp.arange(nb) * block))
    return out.reshape(nb * block, h, d)[:tq].astype(q.dtype)


def _flash_by_kv_head(q, k, v, window: Optional[int], pad: int):
    """Causal attention (within ``window`` if given) of ``q`` (Tq, H, D) as
    the last rows of ``k``/``v`` (Tk, H_kv, D) — bottom-right aligned —
    through the flash kernel, one KV head at a time in a ``lax.map``: the
    kernel takes a KV head repeated for each of its query heads, and every
    head's repeat alive at once is most of a 100k-token prefill's
    workspace. ``pad`` rows pad both runs at their end (no real row reaches
    a padding key); they are cut off again."""
    tq, h, d = q.shape
    h_kv = k.shape[1]
    rep = h // h_kv

    def heads(a):                               # (T, n, D) -> (n, T + pad, D)
        return jnp.swapaxes(jnp.pad(a, ((0, pad), (0, 0), (0, 0))), 0, 1)
    qg = heads(q).reshape(h_kv, rep, tq + pad, d)
    scale = 1.0 / math.sqrt(d)

    def one(args):
        qa, ka, va = args                       # (rep, Tq, D), (Tk, D) x 2
        kr = jnp.broadcast_to(ka, (rep,) + ka.shape)[None]
        vr = jnp.broadcast_to(va, (rep,) + va.shape)[None]
        if window is None:
            return _flash_core(qa[None], kr, vr, True, scale)[0]
        return _flash_core_window(qa[None], kr, vr, scale, int(window))[0]

    out = jax.lax.map(one, (qg, heads(k), heads(v)))   # (Hkv, rep, Tq, D)
    return jnp.moveaxis(out.reshape(h, tq + pad, d), 1, 0)[:tq]


class MellumDecoderLayer(nn.Layer):
    def __init__(self, config: MellumConfig, kind: str):
        super().__init__(dtype=config.dtype)
        c = config
        self.window = c.sliding_window if kind == "sliding_attention" else None
        self.rope = rotary.frequencies(c.rope_parameters[kind], c.head_dim)
        init = Normal(std=0.02)

        def lin(n_in, n_out):
            return self.create_parameter((n_in, n_out), dtype=c.dtype,
                                         default_initializer=init)

        def ones():
            return self.create_parameter((c.hidden_size,), dtype=c.dtype,
                                         default_initializer=Constant(1.0))

        self.input_norm, self.post_norm = ones(), ones()
        self.q_proj = lin(c.hidden_size, c.num_attention_heads * c.head_dim)
        self.k_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.v_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.o_proj = lin(c.num_attention_heads * c.head_dim, c.hidden_size)
        self.moe = DroplessMoE(c.hidden_size, c.moe_intermediate_size,
                               c.num_experts, c.num_experts_per_tok,
                               dtype=c.dtype, score="softmax")


class MellumForCausalLM(nn.Layer):
    def __init__(self, config: MellumConfig):
        super().__init__(dtype=config.dtype)
        self.config = c = config
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        self.layers = nn.LayerList(
            [MellumDecoderLayer(c, kind) for kind in c.layer_types])
        self.norm = self.create_parameter(
            (c.hidden_size,), dtype=c.dtype,
            default_initializer=Constant(1.0))
        self.lm_head = self.create_parameter(
            (c.hidden_size, c.vocab_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # -- pieces ---------------------------------------------------------
    def _embed(self, ids: Tensor) -> Tensor:
        return apply("mellum_embed",
                     lambda i, e: jnp.take(e, i.astype(jnp.int32), axis=0),
                     ids, self.embed_tokens)

    def _logits(self, h: Tensor) -> Tensor:
        h = _rms_norm(h, self.norm, self.config.rms_norm_eps)
        return apply("mellum_logits", lambda a, w: jnp.dot(
            a, w, preferred_element_type=jnp.float32), h, self.lm_head)

    def _qkv(self, layer, h: Tensor, pos: Tensor):
        """``h`` (T, E), ``pos`` (T,) -> q (T, H, D), k, v (T, H_kv, D),
        q and k rotated by the layer's own rotary."""
        c = self.config
        inv, scale = layer.rope

        def f(a, wq, wk, wv, p):
            t = a.shape[0]
            q = jnp.dot(a, wq).reshape(t, c.num_attention_heads, c.head_dim)
            k = jnp.dot(a, wk).reshape(t, c.num_key_value_heads, c.head_dim)
            v = jnp.dot(a, wv).reshape(t, c.num_key_value_heads, c.head_dim)
            return (rotary.rotate_halves(q, p, inv, scale),
                    rotary.rotate_halves(k, p, inv, scale), v)
        return apply("mellum_qkv", f, h, layer.q_proj, layer.k_proj,
                     layer.v_proj, pos)

    def _sequence_attention(self, layer, q, k, v, start: int, first: int):
        """Attention of a run of tokens at positions ``start ..`` over
        ``k``/``v`` at positions ``first ..`` (a prefix, or the part of it a
        window reaches, then the run itself). A run of ``_FLASH_ROWS`` rows
        and more takes the flash kernel, padded at its end (bottom-right
        aligned: the run's rows are the last keys' positions; a padding row
        is cut off again, no real row reaches a padding key); anything the
        kernel cannot tile takes the looped form."""
        tq = int(q.shape[0])
        window = layer.window
        cut = 0 if window is None else max(0, start - window - first)
        tk = int(k.shape[0]) - cut
        pad = -tq % _FLASH_ROWS
        scope = "attn_window" if window is not None else "attn_full"
        if tq >= _FLASH_ROWS and (tk + pad) % 128 == 0:
            def f(qa, ka, va):
                with jax.named_scope(scope):
                    return _flash_by_kv_head(qa, ka[cut:], va[cut:], window,
                                             pad)
            return apply("mellum_flash_attention", f, q, k, v)

        def f(qa, ka, va):
            with jax.named_scope(scope):
                return _looped_attention(qa, ka[cut:], va[cut:], start,
                                         first + cut, window)
        return apply("mellum_looped_attention", f, q, k, v)

    def _attn_out(self, layer, x: Tensor, attn: Tensor) -> Tensor:
        t = int(x.shape[0])
        return apply("mellum_attn_out", lambda a, at, wo: a + jnp.dot(
            at.reshape(t, -1), wo).astype(a.dtype), x, attn, layer.o_proj)

    def _experts(self, layer, x: Tensor, valid: Optional[Tensor] = None):
        """``x + moe(N2(x))`` and the expert layer's row counts."""
        h = _rms_norm(x, layer.post_norm, self.config.rms_norm_eps)
        out, rows = layer.moe(h, valid)
        return apply("mellum_add", lambda a, m: a + m, x, out), rows

    # -- whole-sequence forward -----------------------------------------
    def _run(self, ids: Tensor, prefix=None, start: int = 0):
        """One sequence ``ids`` (T,) at positions ``start ..`` after the
        per-layer ``prefix`` K/V ``[(k, v)]``, each ending at position
        ``start`` (a window layer's may begin where its band begins).
        Returns ``(h (T, E), [(k, v)] of the run, rows (L, experts))``."""
        c = self.config
        t = int(ids.shape[0])
        pos = Tensor(start + jnp.arange(t, dtype=jnp.int32))
        x = self._embed(ids)
        kvs, rows = [], []
        for i, layer in enumerate(self.layers):
            h = _rms_norm(x, layer.input_norm, c.rms_norm_eps)
            q, k, v = self._qkv(layer, h, pos)
            kvs.append((k, v))
            first = start
            if prefix is not None:
                first = start - int(prefix[i][0].shape[0])
                k = concat([prefix[i][0], k], axis=0)
                v = concat([prefix[i][1], v], axis=0)
            x = self._attn_out(layer, x, self._sequence_attention(
                layer, q, k, v, start, first))
            x, r = self._experts(layer, x)
            rows.append(r)
        return x, kvs, stack(rows, axis=0)

    def forward(self, input_ids: Tensor) -> Tensor:
        """``input_ids`` (1, T) or (T,) -> logits (1, T, V) / (T, V)
        float32."""
        batched = len(input_ids.shape) == 2
        ids = input_ids[0] if batched else input_ids
        with no_grad():
            h, _, _ = self._run(ids)
            lg = self._logits(h)
        return lg[None] if batched else lg

    def generate(self, input_ids: Tensor, max_new_tokens: int = 32) -> Tensor:
        """Greedy decode of one sequence by re-running the prefix (the
        plain loop: the serving engine is the cached path)."""
        from ..ops.reduce import argmax
        ids = input_ids[0] if len(input_ids.shape) == 2 else input_ids
        for _ in range(max_new_tokens):
            nxt = argmax(self.forward(ids)[-1:], axis=-1).astype(ids.dtype)
            ids = concat([ids, nxt], axis=0)
        return ids[None]

    # -- the serving engine's contract ------------------------------------
    def serving_callables(self, max_len: int):
        """``(prefill_fn, step_fn)`` over the serving engine's cache
        contract, for an engine built with ``ServingConfig(num_layers=L,
        num_heads=num_key_value_heads, head_dim=head_dim,
        layer_kinds=config.layer_kinds, window=sliding_window)``: the engine
        then keeps pages by layer kind.

        * ``prefill_fn(ids (1, Lp), cache (L, 2, 1, H_kv, max_len, D),
          start=0)`` writes K/V at ``[start, start + Lp)``; with ``start``
          the leading positions are a shared prefix resident in ``cache``
          (a window layer's only where its band reaches, which is all it
          reads).
        * ``step_fn(tok (B, 1), cache, t (B,))`` decodes one token a row:
          over a ``PagedDecodeCache`` every layer streams its own kind's
          pages through the paged decode kernel; over the dense stacked
          cache it is the span-masked debug tier. A row with ``t == 0`` is
          batch padding: it routes to no expert.

        Both return a third value beside the engine's two: the int32
        ``(L, num_experts)`` count of (token, expert) pairs computed,
        which the engine reads back with the tokens."""
        c = self.config
        if max_len > c.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings "
                             f"{c.max_position_embeddings}")
        layers = list(self.layers)
        nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        rep = nh // nkv
        from ..ops.reduce import argmax

        def dense_attn(i, window):
            """One layer's cached decode attention on the dense stacked
            cache (L, 2, B, H_kv, M, D): write K/V at t, span <= t and
            within the window."""
            def f(qa, ka, va, ca, ta):
                t32 = ta.astype(jnp.int32)
                m = ca.shape[4]
                pos = jnp.arange(m, dtype=jnp.int32)[None, :]
                sel = (pos == t32[:, None])[:, None, :, None]
                kc = jnp.where(sel, ka[:, :, None, :].astype(ca.dtype),
                               ca[i, 0])
                vc = jnp.where(sel, va[:, :, None, :].astype(ca.dtype),
                               ca[i, 1])
                ca = ca.at[i, 0].set(kc).at[i, 1].set(vc)
                qg = qa.astype(jnp.float32).reshape(-1, nkv, rep, hd)
                logits = jnp.einsum("bgrd,bgld->bgrl", qg,
                                    kc.astype(jnp.float32)) / math.sqrt(hd)
                span = pos <= t32[:, None]
                if window is not None:
                    span &= pos > t32[:, None] - window
                logits = jnp.where(span[:, None, None, :], logits, _NEG_INF)
                p = jax.nn.softmax(logits, axis=-1)
                out = jnp.einsum("bgrl,bgld->bgrd", p, vc.astype(jnp.float32))
                return out.reshape(-1, nh, hd).astype(qa.dtype), ca
            return f

        def step_fn(tok, cache, t):
            paged = isinstance(cache, PagedDecodeCache)
            b = int(tok.shape[0])
            x = self._embed(reshape(tok, [b]))
            valid = t > 0
            rows = []
            for i, layer in enumerate(layers):
                h = _rms_norm(x, layer.input_norm, c.rms_norm_eps)
                q, k, v = self._qkv(layer, h, t)
                if paged:
                    attn, cache = paged_decode_attention(
                        q, k, v, cache.at_layer(i))
                else:
                    attn, cache = apply(f"mellum_cached_attn_l{i}",
                                        dense_attn(i, layer.window),
                                        q, k, v, cache, t)
                x, r = self._experts(layer, self._attn_out(layer, x, attn),
                                     valid)
                rows.append(r)
            nxt = argmax(self._logits(x), axis=-1)
            return reshape(nxt, [b, 1]).astype("int32"), cache, \
                stack(rows, axis=0)

        def prefill_fn(ids, cache, start=0):
            lp = int(ids.shape[1])
            prefix = None
            if start:
                # a window layer's prefix from where its band begins: the
                # positions below it are not read (and a window pool does
                # not hold them)
                cuts = [0 if layer.window is None
                        else max(0, start - layer.window) for layer in layers]

                def take_prefix(ca):
                    # (L, 2, 1, Hkv, M, D) -> 2L arrays (start - cut, Hkv, D)
                    dt = self.embed_tokens._data.dtype
                    return tuple(
                        jnp.swapaxes(ca[i, kv, 0, :, cuts[i]:start, :], 0, 1
                                     ).astype(dt)
                        for i in range(len(layers)) for kv in (0, 1))
                flat = apply("mellum_take_prefix", take_prefix, cache)
                prefix = [(flat[2 * i], flat[2 * i + 1])
                          for i in range(len(layers))]
            h, kvs, rows = self._run(ids[0], prefix, start)
            nxt = argmax(self._logits(h[-1:]), axis=-1)

            def pack(ca, *flat_kv):
                for i in range(len(layers)):
                    for kv in (0, 1):               # (Lp, Hkv, D) -> (Hkv, ..)
                        ca = ca.at[i, kv, 0, :, start:start + lp, :].set(
                            jnp.swapaxes(flat_kv[2 * i + kv], 0, 1).astype(
                                ca.dtype))
                return ca
            cache = apply("mellum_pack_prefill", pack, cache,
                          *[a for pair in kvs for a in pair])
            return reshape(nxt, [1, 1]).astype("int32"), cache, rows

        return prefill_fn, step_fn
