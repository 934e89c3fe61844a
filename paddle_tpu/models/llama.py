"""Llama-2 family (flagship LLM; BASELINE config #4).

Parity surface: PaddleNLP ``llm/`` LlamaForCausalLM under Fleet hybrid
parallel. TPU-native design decisions:

* attention runs through ``F.scaled_dot_product_attention`` (XLA-fused; the
  Pallas flash-attention kernel slots in through the same seam for long
  sequences),
* GQA via kv-head broadcast,
* rotary embeddings precomputed once per (max_len, head_dim) and gathered,
* tensor-parallel variants of q/k/v/o and MLP projections come from
  ``distributed.fleet.mp_layers`` when a hybrid mesh is active — the layer
  chooses plain Linear on a 1-device mesh so the same model code serves both.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core.tensor import Tensor, _is_tracer, apply, to_tensor
from ..core.tracing import grad_enabled, no_grad
from .. import nn
from ..nn import functional as F
from ..ops.creation import zeros
from ..ops.manipulation import concat, reshape, transpose


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # activation checkpointing, one checkpoint per decoder layer. What a
    # layer keeps for its backward depends on the branch:
    # * with ``scan_layers`` (jax.checkpoint on the scan body, ISSUE 30):
    #   the layer's input and ``SCAN_SAVED_NAMES`` — q and k after rotary, v
    #   (at their own head counts), flash's output and log-sum-exp, the
    #   post-attention residual, and the gate_proj / up_proj outputs. In
    #   bf16 that is 2·(4·hidden + 2·kv_width + 2·intermediate) + 4·heads
    #   bytes a token a layer (94 KB at Mistral-7B's widths: 386 MB a layer
    #   per 4096 tokens, where the layer's input alone is 33.5 MB). Computed
    #   again in the backward: the two norms, rotary's inputs, GQA's repeat
    #   of k and v, silu(gate)·up — elementwise work, no matmul and no
    #   flash forward (closest to upstream's recompute_granularity=
    #   "core_attn"; "full" keeps the input only, as this branch did before).
    # * without it (fleet.utils.recompute around each layer): the layer's
    #   input only; the whole forward runs again, ~1/3 more FLOPs.
    recompute: bool = False
    # scan-over-layers: stack identical decoder-layer params and lax.scan a
    # single layer body over them. The compiled program stops growing with
    # depth (a 32-layer model compiles as fast as a 2-layer one) and
    # composes with ``recompute`` as above — the standard TPU big-model
    # trainer structure. NOTE: state_dict keys use
    # the stacked layout (model.scan_*) — not interchangeable with the
    # per-layer layout; cached generation requires scan_layers=False
    scan_layers: bool = False

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab=128, hidden=64, layers=2, heads=4, kv_heads=2, inter=128,
             max_pos=128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                           num_hidden_layers=layers, num_attention_heads=heads,
                           num_key_value_heads=kv_heads, intermediate_size=inter,
                           max_position_embeddings=max_pos)


def _rope_cache(max_len: int, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv)  # (L, D/2)
    return np.cos(freqs), np.sin(freqs)


# What the scanned layer's checkpoint keeps for its backward, besides the
# scan's carry (``LlamaConfig.recompute``): named where each is made —
# ``flash_out`` / ``flash_lse`` in ``ops/flash_attention.py``'s forward rules.
SCAN_SAVED_NAMES = ("attn_q", "attn_k", "attn_v", "flash_out", "flash_lse",
                    "attn_residual", "mlp_gate", "mlp_up")


def _keep(x: Tensor, name: str) -> Tensor:
    """Name ``x`` for the scanned layer's recompute policy: under it a named
    value is kept for the backward, not computed a second time. Only
    ``jax.checkpoint`` reads a name, and the scan body is the one place it
    wraps these layers — traced, with the tape off (jax differentiates the
    whole scan). Anywhere else ``x`` passes through untouched, so every
    other program's lowered text stays what it was."""
    if grad_enabled() or not _is_tracer(x._data):
        return x
    return apply("checkpoint_name", lambda a: checkpoint_name(a, name), x,
                 amp=False)


def apply_rotary(x: Tensor, cos: Tensor, sin: Tensor, position_offset: int = 0):
    """x: (B, L, H, D). cos/sin: (max_len, D/2)."""
    L = x.shape[1]

    def f(a, c, s):
        c = c[position_offset:position_offset + L][None, :, None, :]
        s = s[position_offset:position_offset + L][None, :, None, :]
        x1, x2 = jnp.split(a, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    return apply("rope", f, x, cos, sin)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, nh, nkv = config.hidden_size, config.num_attention_heads, \
            config.num_key_value_heads
        self.head_dim = h // nh
        self.num_heads = nh
        self.num_kv_heads = nkv
        LinearCls = _maybe_parallel_linear()
        self.q_proj = LinearCls(h, nh * self.head_dim, bias_attr=False)
        self.k_proj = LinearCls(h, nkv * self.head_dim, bias_attr=False)
        self.v_proj = LinearCls(h, nkv * self.head_dim, bias_attr=False)
        self.o_proj = _maybe_parallel_linear(row=True)(
            nh * self.head_dim, h, bias_attr=False)

    def forward(self, x, cos, sin, attn_mask=None, cache=None):
        b, l = x.shape[0], x.shape[1]
        q = reshape(self.q_proj(x), [b, l, -1, self.head_dim])
        k = reshape(self.k_proj(x), [b, l, -1, self.head_dim])
        v = reshape(self.v_proj(x), [b, l, -1, self.head_dim])
        offset = 0 if cache is None else cache[0].shape[1]
        # the 8-head k and v: flash repeats them to 32 heads from these
        q = _keep(apply_rotary(q, cos, sin, offset), "attn_q")
        k = _keep(apply_rotary(k, cos, sin, offset), "attn_k")
        v = _keep(v, "attn_v")
        if cache is not None:
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            new_cache = (k, v)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
            training=self.training)
        out = self.o_proj(reshape(out, [b, l, -1]))
        if cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    """SwiGLU."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        LinearCls = _maybe_parallel_linear()
        self.gate_proj = LinearCls(config.hidden_size, config.intermediate_size,
                                   bias_attr=False)
        self.up_proj = LinearCls(config.hidden_size, config.intermediate_size,
                                 bias_attr=False)
        self.down_proj = _maybe_parallel_linear(row=True)(
            config.intermediate_size, config.hidden_size, bias_attr=False)

    def forward(self, x):
        act = F.silu(_keep(self.gate_proj(x), "mlp_gate"))
        return self.down_proj(act * _keep(self.up_proj(x), "mlp_up"))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cos, sin, attn_mask=None, cache=None):
        res = x
        h = self.self_attn(self.input_layernorm(x), cos, sin, attn_mask, cache)
        if cache is not None:
            h, new_cache = h
        x = _keep(res + h, "attn_residual")
        x = x + self.mlp(self.post_attention_layernorm(x))
        if cache is not None:
            return x, new_cache
        return x


def _scan_policy(carry):
    """The scanned layer's recompute policy: keep ``SCAN_SAVED_NAMES`` and
    compute everything else again. Each value the policy keeps is counted
    as the backward is traced, into the two gauges that say it engaged:
    how many named values a layer keeps, and their bytes with the carry's."""
    from .. import observability as _obs
    names = jax.checkpoint_policies.save_only_these_names(*SCAN_SAVED_NAMES)
    carry_bytes = carry.size * carry.dtype.itemsize
    kept = {}                                   # name -> bytes

    def policy(prim, *avals, **params):
        keep = names(prim, *avals, **params)
        if keep:
            kept[params["name"]] = avals[0].size * avals[0].dtype.itemsize
            _obs.set_gauge("train.scan.saved_values", len(kept))
            _obs.set_gauge("train.scan.saved_bytes_per_layer",
                           carry_bytes + sum(kept.values()))
        return keep

    return policy


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config.max_position_embeddings,
                               config.hidden_size // config.num_attention_heads,
                               config.rope_theta)
        self.register_buffer("rope_cos", to_tensor(cos), persistable=False)
        self.register_buffer("rope_sin", to_tensor(sin), persistable=False)
        if config.scan_layers:
            self._build_scan_stack()

    def _build_scan_stack(self):
        """Stack per-layer params into (L, ...) Parameters; layer 0 stays as
        the trace template, the other layer objects are released."""
        from ..core.tensor import Parameter as _Parameter

        layers = list(self.layers)
        self._scan_names = sorted(layers[0].state_dict().keys())
        self._scan_params = {}
        for name in self._scan_names:
            stacked = jnp.stack(
                [l.state_dict()[name]._data for l in layers], axis=0)
            p = _Parameter(stacked, name=f"llama_scan_{name.replace('.', '_')}")
            self._scan_params[name] = p
            setattr(self, f"scan_{name.replace('.', '_')}", p)
        # keep only the template, OUTSIDE the registered sublayer tree: its
        # params are trace placeholders and must not surface in
        # parameters()/state_dict (an optimizer would build dead state for
        # them). Plain-attribute storage keeps the object alive without
        # registration.
        from ..nn.container import LayerList as _LayerList
        object.__setattr__(self, "_scan_template", layers[0])
        self.layers = _LayerList([])
        for q in layers[0].parameters():
            q.trainable = False
            q.stop_gradient = True

    def _scan_body(self, cos, sin, carry):
        """One decoder layer as ``lax.scan``'s body over the stacked
        parameters (raw arrays in, raw arrays out). With
        ``config.recompute`` it is one ``jax.checkpoint`` per layer that
        keeps ``SCAN_SAVED_NAMES`` and the carry, and computes the rest —
        norms, rotary, SwiGLU's product — again in the backward."""
        template = self._scan_template
        names = self._scan_names

        def body(carry, sl):
            with no_grad():
                sd = template.state_dict()
                saved = {n: sd[n]._data for n in names}
                for n, v in zip(names, sl):
                    sd[n]._data = v
                try:
                    out = template(Tensor(carry), Tensor(cos),
                                   Tensor(sin))._data
                finally:
                    for n in names:
                        sd[n]._data = saved[n]
            return out, None

        if self.config.recompute:
            body = jax.checkpoint(body, policy=_scan_policy(carry))
        return body

    def _scan_forward(self, x):
        flat = [self._scan_params[n] for n in self._scan_names]

        def fn(cos, sin, h, *stacked):
            out, _ = jax.lax.scan(self._scan_body(cos, sin, h), h,
                                  list(stacked))
            return out

        return apply("llama_scan_layers", fn, self.rope_cos, self.rope_sin,
                     x, *flat, amp=False)

    def forward(self, input_ids, attn_mask=None, caches=None):
        x = self.embed_tokens(input_ids)
        if caches is None:
            if self.config.scan_layers:
                if attn_mask is not None:
                    raise NotImplementedError(
                        "scan_layers supports the causal training path only")
                return self.norm(self._scan_forward(x))
            if self.config.recompute:
                from ..distributed.fleet.utils import recompute as _rc
                for layer in self.layers:
                    x = _rc(layer, x, self.rope_cos, self.rope_sin, attn_mask)
            else:
                for layer in self.layers:
                    x = layer(x, self.rope_cos, self.rope_sin, attn_mask)
            return self.norm(x)
        if self.config.scan_layers:
            raise NotImplementedError(
                "scan_layers is a training-path structure; rebuild the "
                "model with scan_layers=False and load the converted "
                "weights (models.llama.scan_to_layered_state_dict) for "
                "cached generation")
        new_caches = []
        for layer, c in zip(self.layers, caches):
            x, nc = layer(x, self.rope_cos, self.rope_sin, attn_mask, cache=c)
            new_caches.append(nc)
        return self.norm(x), new_caches


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        logits = self._logits(h)
        if labels is not None:
            loss = F.cross_entropy(
                reshape(logits[:, :-1, :], [-1, self.config.vocab_size]),
                reshape(labels[:, 1:], [-1]))
            return loss, logits
        return logits

    def _logits(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        return F.linear(h, transpose(self.model.embed_tokens.weight, [1, 0]))

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 do_sample: bool = False, eos_token_id=None):
        """Autoregressive decode with a KV cache via the shared generation
        loop (reference surface: PaddleNLP GenerationMixin.generate)."""
        import jax.numpy as jnp

        from .generation import kv_cache_generate

        cfg = self.config
        b = input_ids.shape[0]
        kvh = cfg.num_key_value_heads
        hd = cfg.hidden_size // cfg.num_attention_heads
        empty = jnp.zeros((b, 0, kvh, hd),
                          self.model.embed_tokens.weight._data.dtype)
        caches = [(Tensor(empty), Tensor(empty))
                  for _ in range(cfg.num_hidden_layers)]
        return kv_cache_generate(
            lambda x, c: self.model(x, caches=c), self._logits, input_ids,
            caches, max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, do_sample=do_sample,
            eos_token_id=eos_token_id)

    def serving_callables(self, max_len: int):
        """``(prefill_fn, step_fn)`` over the serving engine's cache
        contract — the bridge that lets Llama decode through
        ``paddle_tpu.serving.Engine`` (continuous batching, paged KV)
        instead of the per-request concat-cache ``generate`` loop.

        * ``prefill_fn(ids (1, Lp), cache (L, 2, 1, H_kv, max_len, D))``
          runs the normal full-sequence forward (flash SDPA) and packs the
          per-layer K/V into the stacked layout at positions ``[0, Lp)``.
        * ``step_fn(tok (B, 1), cache, t (B,))`` decodes one token per
          slot. ``cache`` is EITHER the dense stacked cache (the debug
          tier: write K/V at ``t``, span-masked attention) OR a
          ``PagedDecodeCache`` view — then every layer's attention
          streams its live pages through the paged-attention Pallas
          kernel and leaves position ``t``'s K/V pending on the returned
          view, which the engine commits to the pool in one write
          (``ServingConfig.paged_attention``; ISSUE 13, 26). GQA stays a
          kv-head broadcast on both tiers; RoPE gathers per-row rows at
          each slot's own position.

        Greedy (argmax) next-token, matching the engine's parity-oracle
        contract. Wire up with ``ServingConfig(num_layers=L,
        num_heads=num_key_value_heads, head_dim=D, max_len=max_len)`` —
        the pool stores KV heads. The per-layer Python loop unrolls L
        layers into the compiled step (llama layers are unshared objects;
        the FusedMultiTransformer scan path covers the stacked-weight
        case)."""
        import jax

        from ..ops.paged_attention import (PagedDecodeCache,
                                           paged_decode_attention)

        cfg = self.config
        if cfg.scan_layers:
            raise NotImplementedError(
                "serving_callables needs the per-layer layout; rebuild "
                "with scan_layers=False (scan_to_layered_state_dict "
                "converts the checkpoint)")
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len {max_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}")
        model = self.model
        layers = list(model.layers)
        nh = cfg.num_attention_heads
        nkv = cfg.num_key_value_heads
        hd = cfg.hidden_size // nh
        rep = nh // nkv
        inv_scale = 1.0 / math.sqrt(hd)

        def _rope_rows(x, cos, sin, t):
            """Rotary at PER-ROW positions: x (B, H, D), t (B,)."""
            c = jnp.take(cos, t.astype(jnp.int32), axis=0)[:, None, :]
            s = jnp.take(sin, t.astype(jnp.int32), axis=0)[:, None, :]
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                                   axis=-1)

        def _dense_attn(i):
            """One layer's cached decode attention on the dense stacked
            cache (L, 2, B, H_kv, M, D): write K/V at t, span <= t."""
            def f(qa, ka, va, ca, ta):
                t32 = ta.astype(jnp.int32)
                m = ca.shape[4]
                kc, vc = ca[i, 0], ca[i, 1]          # (B, H_kv, M, D)
                sel = jax.nn.one_hot(t32, m, dtype=jnp.bool_)[
                    :, None, :, None]
                kc = jnp.where(sel, ka[:, :, None, :].astype(kc.dtype), kc)
                vc = jnp.where(sel, va[:, :, None, :].astype(vc.dtype), vc)
                ca = ca.at[i, 0].set(kc)
                ca = ca.at[i, 1].set(vc)
                kr = jnp.repeat(kc, rep, axis=1) if rep > 1 else kc
                vr = jnp.repeat(vc, rep, axis=1) if rep > 1 else vc
                logits = jnp.einsum("bhd,bhld->bhl", qa.astype(jnp.float32),
                                    kr.astype(jnp.float32)) * inv_scale
                span = jnp.arange(m, dtype=jnp.int32)[None, :] <= \
                    t32[:, None]
                logits = jnp.where(span[:, None, :], logits, -1e30)
                p = jax.nn.softmax(logits, axis=-1)
                out = jnp.einsum("bhl,bhld->bhd", p,
                                 vr.astype(jnp.float32))
                return out.astype(qa.dtype), ca
            return f

        def step_fn(tok, cache, t):
            paged = isinstance(cache, PagedDecodeCache)
            b = int(tok.shape[0])
            x = model.embed_tokens(tok)              # (B, 1, E)
            for i, layer in enumerate(layers):
                res = x
                h = layer.input_layernorm(x)
                att = layer.self_attn
                q = reshape(att.q_proj(h), [b, nh, hd])
                k = reshape(att.k_proj(h), [b, nkv, hd])
                v = reshape(att.v_proj(h), [b, nkv, hd])
                q = apply("llama_rope_rows", _rope_rows, q,
                          model.rope_cos, model.rope_sin, t)
                k = apply("llama_rope_rows", _rope_rows, k,
                          model.rope_cos, model.rope_sin, t)
                if paged:
                    out, cache = paged_decode_attention(
                        q, k, v, cache.at_layer(i))
                else:
                    out, cache = apply(f"llama_cached_attn_l{i}",
                                       _dense_attn(i), q, k, v, cache, t)
                x = res + att.o_proj(reshape(out, [b, 1, nh * hd]))
                x = x + layer.mlp(layer.post_attention_layernorm(x))
            h = model.norm(x)
            from ..ops.reduce import argmax
            nxt = argmax(self._logits(h), axis=-1)   # (B, 1) greedy
            return nxt.astype("int32"), cache

        def prefill_fn(ids, cache, start=0):
            """3-arg form (ISSUE 17): with ``start > 0`` the leading
            ``start`` cache positions are a shared prefix already resident
            in ``cache`` — slice them into per-layer concat caches and run
            the incremental forward over the TAIL only. Correct by the
            same machinery the generate loop uses: RoPE applies at
            ``offset = start`` and SDPA's causal mask is bottom-right
            aligned (tail query i attends keys ``<= start + i`` on both
            the XLA and flash paths), so the tail K/V and next-token
            logits match a full prefill bit-for-bit given identical prefix
            K/V bytes."""
            lp = int(ids.shape[1])               # tail length when start>0
            dt = model.embed_tokens.weight._data.dtype
            if start:
                def take_prefix(ca):
                    # (L, 2, 1, Hkv, M, D) -> 2L arrays (1, start, Hkv, D)
                    pre = jnp.swapaxes(ca[:, :, :, :, :start, :], 3, 4)
                    return tuple(pre[i, kv].astype(dt)
                                 for i in range(len(layers))
                                 for kv in (0, 1))
                flat_pre = apply("llama_take_prefix", take_prefix, cache)
                caches_in = [(flat_pre[2 * i], flat_pre[2 * i + 1])
                             for i in range(len(layers))]
            else:
                empty = jnp.zeros((1, 0, nkv, hd), dt)
                caches_in = [(Tensor(empty), Tensor(empty))
                             for _ in range(len(layers))]
            h, new_caches = model(ids, caches=caches_in)
            from ..ops.reduce import argmax
            nxt = argmax(self._logits(h[:, -1:]), axis=-1)

            def pack(ca, *kvs):
                for i in range(len(layers)):
                    # new_caches concat prefix+tail; store the tail at its
                    # own positions — shared-prefix pages are not written
                    kt = jnp.swapaxes(kvs[2 * i][:, start:], 1, 2)
                    vt = jnp.swapaxes(kvs[2 * i + 1][:, start:], 1, 2)
                    ca = ca.at[i, 0, :, :, start:start + lp, :].set(
                        kt.astype(ca.dtype))
                    ca = ca.at[i, 1, :, :, start:start + lp, :].set(
                        vt.astype(ca.dtype))
                return ca

            flat = [kv for pair in new_caches for kv in pair]
            cache = apply("llama_pack_prefill", pack, cache, *flat)
            return nxt.astype("int32"), cache

        return prefill_fn, step_fn

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len: int) -> float:
        """Approximate training FLOPs/token (6N + attention terms)."""
        n = self.num_params()
        c = self.config
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        return 6.0 * n + attn


def scan_to_layered_state_dict(sd):
    """Convert a ``scan_layers=True`` state_dict (stacked ``model.scan_*``
    keys, leaves (L, ...)) to the per-layer layout
    (``model.layers.{i}.{name}``) — the bridge that lets a scan-trained
    checkpoint load into a ``scan_layers=False`` model for cached
    generation (the one layout restriction LlamaModel documents)."""
    out = {}
    for k, v in sd.items():
        if ".scan_" not in k and not k.startswith("scan_"):
            out[k] = v
            continue
        prefix, flat = (k.split(".scan_", 1) if ".scan_" in k
                        else ("", k[len("scan_"):]))
        dotted = _unflatten_scan_name(flat)
        arr = v._data if hasattr(v, "_data") else v
        layer_prefix = f"{prefix}.layers" if prefix else "layers"
        for i in range(arr.shape[0]):
            out[f"{layer_prefix}.{i}.{dotted}"] = \
                Tensor(arr[i], stop_gradient=True)
    return out


def _scan_name_map():
    """{flattened: dotted} for every decoder-layer state key, derived from
    the layer structure itself (no hardcoded attribute list — a layer
    variant or added param is covered automatically)."""
    global _SCAN_NAME_MAP
    try:
        return _SCAN_NAME_MAP
    except NameError:
        pass
    # building the template draws initializer samples — snapshot/restore
    # the generator so a seeded program gets identical randomness whether
    # or not it converted a checkpoint first
    from ..core.random import default_generator
    state = default_generator.get_state()
    try:
        template = LlamaDecoderLayer(LlamaConfig.tiny())
    finally:
        default_generator.set_state(state)
    _SCAN_NAME_MAP = {k.replace(".", "_"): k
                      for k in template.state_dict().keys()}
    return _SCAN_NAME_MAP


def _unflatten_scan_name(flat: str) -> str:
    """scan key names flatten '.' to '_' (q_proj.weight → q_proj_weight);
    rebuild the dotted path from the decoder layer's own key set."""
    dotted = _scan_name_map().get(flat)
    if dotted is None:
        raise ValueError(
            f"unrecognized scan-stacked key {flat!r}: not a "
            "LlamaDecoderLayer state entry (custom layers need their own "
            "layout converter)")
    return dotted


def layered_to_scan_state_dict(sd, num_layers: int):
    """Inverse of :func:`scan_to_layered_state_dict`: stack
    ``model.layers.{i}.{name}`` keys into ``model.scan_{name}``."""
    import re

    out = {}
    groups = {}
    for k, v in sd.items():
        m = re.match(r"(?:(.*)\.)?layers\.(\d+)\.(.+)$", k)
        if m is None:
            out[k] = v
            continue
        prefix, i, name = m.group(1) or "", int(m.group(2)), m.group(3)
        groups.setdefault((prefix, name), {})[i] = \
            v._data if hasattr(v, "_data") else v
    for (prefix, name), per_layer in groups.items():
        if len(per_layer) != num_layers:
            raise ValueError(
                f"layer group {name!r} has {len(per_layer)} of "
                f"{num_layers} layers")
        stacked = jnp.stack([per_layer[i] for i in range(num_layers)], 0)
        scan_key = f"scan_{name.replace('.', '_')}"
        out[f"{prefix}.{scan_key}" if prefix else scan_key] = \
            Tensor(stacked, stop_gradient=True)
    return out


def _maybe_parallel_linear(row: bool = False):
    """Return ColumnParallelLinear/RowParallelLinear when a hybrid mesh with
    mp_degree > 1 is active, else nn.Linear (same ctor signature subset)."""
    try:
        from ..distributed import fleet
        hcg = fleet.get_hybrid_communicate_group()
        if hcg is not None and hcg.get_model_parallel_world_size() > 1:
            from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                                       RowParallelLinear)
            return RowParallelLinear if row else ColumnParallelLinear
    except Exception:
        pass  # no hybrid communicate group initialized (single-process
        #       run): plain nn.Linear is the correct degenerate layer
    return nn.Linear
