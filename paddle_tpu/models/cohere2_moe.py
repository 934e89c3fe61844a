"""Command A+ (``cohere2_moe``): a parallel-block decoder with sigmoid-routed
experts, averaged shared experts, and sliding-window layers between
full-attention ones (ISSUE 27).

One layer, for input ``x`` (T, E)::

    h    = LayerNorm(x) * g              # mean-subtracting, weight only
    q, k, v = h Wq, h Wk, h Wv           # H query heads on H_kv KV heads,
                                         # head_dim given, not E / H
    sliding layer:  q, k = rope(q), rope(k)   # interleaved pairs (2i, 2i+1)
                    mask 0 <= i - j < sliding_window
    full layer:     no positional encoding;  mask j <= i
    attn = softmax(q k^T / sqrt(D) + mask) v Wo
    y    = x + attn + routed(h) + shared(h)   # ONE norm feeds both branches

``routed`` + ``shared`` is :class:`~paddle_tpu.incubate.moe.DroplessMoE`.
After the last layer a LayerNorm, then ``logits = logit_scale * h E^T`` over
the tied embedding.

The model is built as ONE CHIP'S SHARE of an expert-parallel deployment:
``experts_held = (first, count)`` names the routed experts whose weights
this chip holds (routing still runs over all ``num_experts``), and
``vocab_held = (first, count)`` the rows of the tied embedding it holds
(token ids and logits are indices into that slice). Both default to
everything. Parameters are created in ``config.dtype`` — at published
widths a float32 build would not fit the chip it is cast for.

Serving only: ``forward`` / ``generate`` / ``serving_callables`` run under
``no_grad``; the window band of ``flash_attention`` has no backward.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor, apply
from ..core.tracing import no_grad
from ..incubate.moe import DroplessMoE
from ..nn.initializer import Constant, Normal
from ..ops.flash_attention import flash_attention
from ..ops.manipulation import reshape
from ..ops.paged_attention import PagedDecodeCache, paged_decode_attention

__all__ = ["Cohere2MoeConfig", "Cohere2MoeForCausalLM"]

_FLASH_ROWS = 512      # a prefill this long runs the flash kernel, padded
_NEG_INF = -1e30


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096        # the width of ONE expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    layer_switch: int = 4                # every 4th layer is full attention
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    # this chip's share (see the module docstring)
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if (i + 1) % self.layer_switch == 0
                else "sliding_attention"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        if self.vocab_held is None:
            self.vocab_held = (0, self.vocab_size)
        self.experts_held = tuple(self.experts_held)
        self.vocab_held = tuple(self.vocab_held)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The serving engine's names for ``layer_types``."""
        return tuple("window" if t == "sliding_attention" else "full"
                     for t in self.layer_types)

    @staticmethod
    def tiny(**over) -> "Cohere2MoeConfig":
        """Every mechanism at a size the CPU runs: ``head_dim`` is not
        ``hidden / heads``, the window is shorter than a prompt, and a
        period of the published pattern is four layers."""
        cfg = dict(vocab_size=96, hidden_size=64, intermediate_size=32,
                   num_hidden_layers=4, num_attention_heads=8,
                   num_key_value_heads=2, head_dim=16, sliding_window=8,
                   num_experts=8, num_experts_per_tok=2,
                   num_shared_experts=2, max_position_embeddings=256)
        cfg.update(over)
        return Cohere2MoeConfig(**cfg)


def _layer_norm(x: Tensor, weight: Tensor, eps: float) -> Tensor:
    def f(a, w):
        a32 = a.astype(jnp.float32)
        mu = jnp.mean(a32, -1, keepdims=True)
        var = jnp.mean(jnp.square(a32 - mu), -1, keepdims=True)
        return ((a32 - mu) * jax.lax.rsqrt(var + eps)
                * w.astype(jnp.float32)).astype(a.dtype)
    return apply("cohere_layer_norm", f, x, weight)


def _rope(x, pos, theta: float):
    """Interleaved rotary (``rope_gptj``): pairs (2i, 2i+1) of the last
    axis turn by ``pos * theta**(-2i/D)``. ``x`` (..., T, H, D) arrays,
    ``pos`` broadcastable to (..., T)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None, None] * inv       # (..., T,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _band_attention(q, k, v, start: int, window: Optional[int]):
    """Plain attention of ``q`` (Tq, H, D), rows at positions ``start +
    i``, over ``k``/``v`` (Tk, H_kv, D) at positions ``0 .. Tk``: causal,
    within ``window`` if given. The query heads of one KV head ride as
    extra rows of one matmul, and query rows go in blocks, so no (H, Tq,
    Tk) tensor exists. For tails and short prompts; long prefills run the
    flash kernel."""
    tq, h, d = q.shape
    tk, h_kv, _ = k.shape
    rep = h // h_kv
    lo = 0
    if window is not None:                 # keys no row reaches: cut off
        lo = max(0, start - window + 1)
        k, v, tk = k[lo:], v[lo:], tk - lo
    block = max(1, min(tq, (1 << 24) // max(1, tk * rep)))
    qg = jnp.swapaxes(q.reshape(tq, h_kv, rep, d), 0, 1)     # (Hkv,Tq,rep,D)
    kg, vg = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)    # (Hkv,Tk,D)
    cols = lo + jnp.arange(tk)
    outs = []
    for at in range(0, tq, block):
        qb = qg[:, at:at + block]
        rows = start + at + jnp.arange(qb.shape[1])
        s = jnp.einsum("gqrd,gkd->gqrk", qb, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep &= rows[:, None] - cols[None, :] < window
        s = jnp.where(keep[None, :, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("gqrk,gkd->gqrd", p, vg,
                               preferred_element_type=jnp.float32))
    out = jnp.concatenate(outs, axis=1)                       # (Hkv,Tq,rep,D)
    return jnp.swapaxes(out, 0, 1).reshape(tq, h, d).astype(q.dtype)


class Cohere2MoeDecoderLayer(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig, kind: str):
        super().__init__(dtype=config.dtype)
        c = config
        self.window = c.sliding_window if kind == "sliding_attention" else None
        init = Normal(std=0.02)

        def lin(n_in, n_out):
            return self.create_parameter((n_in, n_out), dtype=c.dtype,
                                         default_initializer=init)

        self.norm = self.create_parameter(
            (c.hidden_size,), dtype=c.dtype,
            default_initializer=Constant(1.0))
        self.q_proj = lin(c.hidden_size, c.num_attention_heads * c.head_dim)
        self.k_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.v_proj = lin(c.hidden_size, c.num_key_value_heads * c.head_dim)
        self.o_proj = lin(c.num_attention_heads * c.head_dim, c.hidden_size)
        self.moe = DroplessMoE(
            c.hidden_size, c.intermediate_size, c.num_experts,
            c.num_experts_per_tok, experts_held=c.experts_held,
            num_shared=c.num_shared_experts, dtype=c.dtype)


class Cohere2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Cohere2MoeConfig):
        super().__init__(dtype=config.dtype)
        self.config = c = config
        self.embed_tokens = self.create_parameter(
            (c.vocab_held[1], c.hidden_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        self.layers = nn.LayerList(
            [Cohere2MoeDecoderLayer(c, kind) for kind in c.layer_types])
        self.norm = self.create_parameter(
            (c.hidden_size,), dtype=c.dtype,
            default_initializer=Constant(1.0))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # -- pieces ---------------------------------------------------------
    def _embed(self, ids: Tensor) -> Tensor:
        return apply("cohere_embed",
                     lambda i, e: jnp.take(e, i.astype(jnp.int32), axis=0),
                     ids, self.embed_tokens)

    def _logits(self, h: Tensor) -> Tensor:
        c = self.config
        h = _layer_norm(h, self.norm, c.layer_norm_eps)
        return apply("cohere_logits", lambda a, e: jnp.dot(
            a, e.T, preferred_element_type=jnp.float32) * c.logit_scale,
            h, self.embed_tokens)

    def _qkv(self, layer, h: Tensor, pos: Tensor):
        """``h`` (T, E), ``pos`` (T,) -> q (T, H, D), k, v (T, H_kv, D),
        rotated on a sliding layer."""
        c = self.config

        def f(a, wq, wk, wv, p):
            t = a.shape[0]
            q = jnp.dot(a, wq).reshape(t, c.num_attention_heads, c.head_dim)
            k = jnp.dot(a, wk).reshape(t, c.num_key_value_heads, c.head_dim)
            v = jnp.dot(a, wv).reshape(t, c.num_key_value_heads, c.head_dim)
            if layer.window is not None:
                q, k = _rope(q, p, c.rope_theta), _rope(k, p, c.rope_theta)
            return q, k, v
        return apply("cohere_qkv", f, h, layer.q_proj, layer.k_proj,
                     layer.v_proj, pos)

    def _sequence_attention(self, layer, q, k, v, start: int):
        """Attention of a run of tokens at positions ``start ..`` over
        ``k``/``v`` at positions ``0 ..`` (a prefix + the run itself)."""
        tq = int(q.shape[0])
        scope = "attn_window" if layer.window is not None else "attn_full"
        if start == 0 and tq >= _FLASH_ROWS:
            # the flash kernel wants whole blocks: pad the run at its end —
            # causal rows never reach a key after them, and the padding
            # rows are cut off again
            pad = -tq % _FLASH_ROWS

            def padded(a):
                return jnp.pad(a, ((0, pad), (0, 0), (0, 0)))[None]
            with jax.named_scope(scope):
                out = flash_attention(
                    apply("cohere_pad", padded, q),
                    apply("cohere_pad", padded, k),
                    apply("cohere_pad", padded, v),
                    causal=True, window=layer.window)
            return out[0, :tq]

        def f(qa, ka, va):
            with jax.named_scope(scope):
                return _band_attention(qa, ka, va, start, layer.window)
        return apply("cohere_band_attention", f, q, k, v)

    def _block_out(self, layer, x: Tensor, h: Tensor, attn: Tensor,
                   valid: Optional[Tensor] = None):
        """``x + attn Wo + moe(h)`` and the expert layer's row counts."""
        moe, rows = layer.moe(h, valid)
        t = int(x.shape[0])
        out = apply("cohere_block_out", lambda a, at, wo, m: a + jnp.dot(
            at.reshape(t, -1), wo).astype(a.dtype) + m,
            x, attn, layer.o_proj, moe)
        return out, rows

    # -- whole-sequence forward -----------------------------------------
    def _run(self, ids: Tensor, prefix=None, start: int = 0):
        """One sequence ``ids`` (T,) at positions ``start ..`` after the
        per-layer ``prefix`` K/V ``[(k, v)]`` of positions ``0 .. start``.
        Returns ``(h (T, E), [(k, v)] of the run, rows (L, held))``."""
        c = self.config
        t = int(ids.shape[0])
        pos = Tensor(start + jnp.arange(t, dtype=jnp.int32))
        x = self._embed(ids)
        kvs, rows = [], []
        for i, layer in enumerate(self.layers):
            h = _layer_norm(x, layer.norm, c.layer_norm_eps)
            q, k, v = self._qkv(layer, h, pos)
            kvs.append((k, v))
            if prefix is not None:
                from ..ops.manipulation import concat
                k = concat([prefix[i][0], k], axis=0)
                v = concat([prefix[i][1], v], axis=0)
            attn = self._sequence_attention(layer, q, k, v, start)
            x, r = self._block_out(layer, x, h, attn)
            rows.append(r)
        from ..ops.manipulation import stack
        return x, kvs, stack(rows, axis=0)

    def forward(self, input_ids: Tensor) -> Tensor:
        """``input_ids`` (1, T) or (T,) -> logits (1, T, V) / (T, V) float32
        over the vocabulary rows held here."""
        batched = len(input_ids.shape) == 2
        ids = input_ids[0] if batched else input_ids
        with no_grad():
            h, _, _ = self._run(ids)
            lg = self._logits(h)
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
    def serving_callables(self, max_len: int):
        """``(prefill_fn, step_fn)`` over the serving engine's cache
        contract, as ``LlamaForCausalLM.serving_callables`` gives it, for
        an engine built with ``ServingConfig(num_layers=L,
        num_heads=num_key_value_heads, head_dim=head_dim,
        layer_kinds=config.layer_kinds, window=sliding_window)``: the
        engine then keeps pages by layer kind.

        * ``prefill_fn(ids (1, Lp), cache (L, 2, 1, H_kv, max_len, D),
          start=0)`` writes K/V at ``[start, start + Lp)``; with ``start``
          the leading positions are a shared prefix resident in ``cache``
          (a window layer's only where its band reaches).
        * ``step_fn(tok (B, 1), cache, t (B,))`` decodes one token a row:
          over a ``PagedDecodeCache`` every layer streams its own kind's
          pages through the paged decode kernel; over the dense stacked
          cache it is the span-masked debug tier. A row with ``t == 0`` is
          batch padding: it routes to no expert.

        Both return a third value beside the engine's two: the int32
        ``(L, experts held)`` count of (token, expert) pairs computed,
        which the engine reads back with the tokens."""
        c = self.config
        if max_len > c.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings "
                             f"{c.max_position_embeddings}")
        layers = list(self.layers)
        nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        rep = nh // nkv
        from ..ops.manipulation import stack
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
                h = _layer_norm(x, layer.norm, c.layer_norm_eps)
                q, k, v = self._qkv(layer, h, t)
                if paged:
                    attn, cache = paged_decode_attention(
                        q, k, v, cache.at_layer(i))
                else:
                    attn, cache = apply(f"cohere_cached_attn_l{i}",
                                        dense_attn(i, layer.window),
                                        q, k, v, cache, t)
                x, r = self._block_out(layer, x, h, attn, valid)
                rows.append(r)
            nxt = argmax(self._logits(x), axis=-1)
            return reshape(nxt, [b, 1]).astype("int32"), cache, \
                stack(rows, axis=0)

        def prefill_fn(ids, cache, start=0):
            lp = int(ids.shape[1])
            prefix = None
            if start:
                def take_prefix(ca):
                    # (L, 2, 1, Hkv, M, D) -> 2L arrays (start, Hkv, D)
                    pre = jnp.swapaxes(ca[:, :, 0, :, :start, :], 2, 3)
                    return tuple(pre[i, kv].astype(self.embed_tokens._data.dtype)
                                 for i in range(len(layers))
                                 for kv in (0, 1))
                flat = apply("cohere_take_prefix", take_prefix, cache)
                prefix = [(flat[2 * i], flat[2 * i + 1])
                          for i in range(len(layers))]
            h, kvs, rows = self._run(ids[0], prefix, start)
            nxt = argmax(self._logits(h[-1:]), axis=-1)

            def pack(ca, *flat_kv):
                for i in range(len(layers)):
                    kt = jnp.swapaxes(flat_kv[2 * i], 0, 1)      # (Hkv,Lp,D)
                    vt = jnp.swapaxes(flat_kv[2 * i + 1], 0, 1)
                    ca = ca.at[i, 0, 0, :, start:start + lp, :].set(
                        kt.astype(ca.dtype))
                    ca = ca.at[i, 1, 0, :, start:start + lp, :].set(
                        vt.astype(ca.dtype))
                return ca
            cache = apply("cohere_pack_prefill", pack, cache,
                          *[a for pair in kvs for a in pair])
            return reshape(nxt, [1, 1]).astype("int32"), cache, rows

        return prefill_fn, step_fn
