"""MiniCPM-SALA (``minicpm_sala``): a dense decoder whose layers mix two
attention mechanisms by ``mixer_types`` (ISSUE 31) — ``minicpm4`` layers,
softmax attention made sparse by InfLLM-V2 block selection, and
``lightning-attn`` layers, linear attention with a fixed per-head decay that
keep a ``D x D`` state per head and no keys at all.

For the residual stream ``x`` (T, E), ``xh = RMSNorm(x)`` (eps
``rms_norm_eps``) and ``a = scale_depth / sqrt(num_hidden_layers)`` with the
PUBLISHED depth, every layer is::

    x = x + a * mixer(RMSNorm(x))
    x = x + a * down(silu(gate RMSNorm(x)) * up RMSNorm(x))

``minicpm4`` mixer: ``q = xh Wq`` (H x D), ``k, v = xh Wk, xh Wv`` (H_kv x
D); RMSNorm per head on q and k (``qk_norm``); no positions
(``attn_use_rope`` false); scale ``1 / sqrt(D)``; the blocks a query
attends are chosen as ``ops/sparse_attention.py`` says (everything at or
below ``dense_len``); ``out = (attn * sigmoid(xh Wg)) Wo``
(``attn_use_output_gate``).

``lightning-attn`` mixer: ``q, k, v = xh Wq, xh Wk, xh Wv`` (H x D each,
``lightning_nkv`` = H); RMSNorm per head on q and k; rotary (theta
``rope_theta``, halves rotated as in ``models/llama.py``) on q and k;
``S_t = lam_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(D)`` with ``lam_h
= exp(-s_h)`` from ``ops.linear_attention.lightning_slopes`` at the layer's
PUBLISHED index; ``out = (RMSNorm(o) * sigmoid(xh Wz)) Wo``, the output norm
over all ``H * D`` features.

Embeddings are multiplied by ``scale_emb``; the head (untied) reads
``RMSNorm(x) / (hidden_size / dim_model_base)``.

The model may be built as a CUT of the published depth: ``layers_run``
names the published layer indices it holds (a pipeline stage); the decay
and the residual scale still follow the published indices and depth, so the
layers compute what they compute inside the whole model
(``tests/test_minicpm_sala.py`` holds them to it). Parameters are created in
``config.dtype``. Serving only: everything runs under ``no_grad``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor, apply
from ..core.tracing import no_grad
from ..nn.initializer import Constant, Normal
from ..ops import rotary
from ..ops.linear_attention import (chunked_linear_attention,
                                    lightning_slopes, linear_state_decode)
from ..ops.sparse_attention import (HybridDecodeCache, HybridPrefill,
                                    SparseConfig, compress_keys,
                                    pages_counted, sparse_decode_attention,
                                    sparse_prefill_attention)

__all__ = ["MiniCPMSalaConfig", "MiniCPMSalaForCausalLM"]

SPARSE, LINEAR = "minicpm4", "lightning-attn"
_PUBLISHED_MIXERS = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LINEAR
    for i in range(32))


@dataclass
class MiniCPMSalaConfig:
    # the catalog's keys, as published
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32          # the PUBLISHED depth
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    # what the catalog's config does not carry (configs/*.json: assumed)
    sparse: SparseConfig = field(default_factory=SparseConfig)
    # this chip's share: the published layer indices it holds (all if None)
    layers_run: Optional[Tuple[int, ...]] = None
    dtype: str = "float32"

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_hidden_layers or \
                set(self.mixer_types) - {SPARSE, LINEAR}:
            raise ValueError(
                f"mixer_types must name {SPARSE!r} or {LINEAR!r} for each of "
                f"the {self.num_hidden_layers} published layers")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("lightning_nkv != lightning_nh is not built")
        if isinstance(self.sparse, dict):
            self.sparse = SparseConfig(**self.sparse)
        if self.layers_run is None:
            self.layers_run = tuple(range(self.num_hidden_layers))
        self.layers_run = tuple(int(i) for i in self.layers_run)

    @property
    def mixers_run(self) -> Tuple[str, ...]:
        return tuple(self.mixer_types[i] for i in self.layers_run)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The serving engine's names for the layers run."""
        return tuple("sparse" if m == SPARSE else "linear"
                     for m in self.mixers_run)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.num_hidden_layers)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        return (self.lightning_nh, self.lightning_head_dim,
                self.lightning_head_dim)

    @staticmethod
    def tiny(**over) -> "MiniCPMSalaConfig":
        """Both mixers at a size the CPU runs, with a selection that really
        drops blocks: blocks of 4 tokens (kernel 2 on stride 1), the best 3
        of them kept past 16 tokens of context, one initial block and a
        window of one block forced."""
        cfg = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                   num_hidden_layers=4, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=8, lightning_nh=4,
                   lightning_nkv=4, lightning_head_dim=8,
                   max_position_embeddings=512, dim_model_base=16,
                   mixer_types=(SPARSE, LINEAR, LINEAR, SPARSE),
                   sparse=SparseConfig(kernel_size=2, kernel_stride=1,
                                       block_size=4, topk=3, init_blocks=1,
                                       window_size=4, dense_len=16))
        cfg.update(over)
        return MiniCPMSalaConfig(**cfg)


# ---------------------------------------------------------------------------
# the layer equations on arrays
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _by_blocks(fn, block: int, *arrays):
    """``fn`` over blocks of ``block`` leading rows of ``arrays`` (all (T,
    ...)): the whole blocks under one ``lax.map``, the rest in one call. It
    bounds what ``fn`` holds at once (an FFN's (T, F) intermediate)."""
    t = arrays[0].shape[0]
    full = t // block
    if full <= 1:
        return fn(*arrays)
    head = jax.lax.map(lambda xs: fn(*xs), tuple(
        a[:full * block].reshape((full, block) + a.shape[1:])
        for a in arrays))
    head = head.reshape((full * block,) + head.shape[2:])
    if full * block == t:
        return head
    return jnp.concatenate([head, fn(*(a[full * block:] for a in arrays))])


class _Layer(nn.Layer):
    def __init__(self, config: MiniCPMSalaConfig, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.index = index                   # the PUBLISHED layer index
        self.mixer = c.mixer_types[index]
        init = Normal(std=0.02)
        e = c.hidden_size

        def lin(n_in, n_out):
            return self.create_parameter((n_in, n_out), dtype=c.dtype,
                                         default_initializer=init)

        def ones(n):
            return self.create_parameter((n,), dtype=c.dtype,
                                         default_initializer=Constant(1.0))

        if self.mixer == SPARSE:
            h, hkv, d = c.num_attention_heads, c.num_key_value_heads, \
                c.head_dim
        else:
            h = hkv = c.lightning_nh
            d = c.lightning_head_dim
        self.input_norm = ones(e)
        self.q_proj = lin(e, h * d)
        self.k_proj = lin(e, hkv * d)
        self.v_proj = lin(e, hkv * d)
        self.gate_proj = lin(e, h * d)       # the mixer's output gate
        self.o_proj = lin(h * d, e)
        self.q_norm = ones(d)
        self.k_norm = ones(d)
        if self.mixer == LINEAR:
            self.o_norm = ones(h * d)
        self.post_norm = ones(e)
        self.mlp_gate = lin(e, c.intermediate_size)
        self.mlp_up = lin(e, c.intermediate_size)
        self.mlp_down = lin(c.intermediate_size, e)

    NAMES = ("input_norm", "q_proj", "k_proj", "v_proj", "gate_proj",
             "o_proj", "q_norm", "k_norm", "post_norm", "mlp_gate", "mlp_up",
             "mlp_down", "o_norm")

    def tensors(self) -> List[Tensor]:
        return [getattr(self, n) for n in self.NAMES if hasattr(self, n)]


class MiniCPMSalaForCausalLM(nn.Layer):
    def __init__(self, config: MiniCPMSalaConfig):
        super().__init__(dtype=config.dtype)
        self.config = c = config
        self.embed_tokens = self.create_parameter(
            (c.vocab_size, c.hidden_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        self.layers = nn.LayerList([_Layer(c, i) for i in c.layers_run])
        self.norm = self.create_parameter(
            (c.hidden_size,), dtype=c.dtype,
            default_initializer=Constant(1.0))
        self.lm_head = self.create_parameter(
            (c.hidden_size, c.vocab_size), dtype=c.dtype,
            default_initializer=Normal(std=0.02))
        self._slopes = {layer.index: lightning_slopes(
            c.lightning_nh, layer.index, c.num_hidden_layers)
            for layer in self.layers if layer.mixer == LINEAR}

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # -- arrays in, arrays out ------------------------------------------
    def _weights(self, layer, flat) -> dict:
        names = [n for n in layer.NAMES if hasattr(layer, n)]
        return dict(zip(names, flat))

    def _ffn(self, x, w, block: int):
        c = self.config
        a = c.residual_scale

        def f(xb):
            h = _rms(xb, w["post_norm"], c.rms_norm_eps)
            mid = jax.nn.silu(jnp.dot(h, w["mlp_gate"])) * \
                jnp.dot(h, w["mlp_up"])
            return xb + (a * jnp.dot(mid, w["mlp_down"])).astype(xb.dtype)
        return _by_blocks(f, block, x)

    def _qkv(self, h, w, heads: int, kv_heads: int, d: int, pos):
        """Projections of normalised rows ``h`` (N, E), per-head norms,
        rotary where ``pos`` is given."""
        c = self.config
        n = h.shape[0]
        q = jnp.dot(h, w["q_proj"]).reshape(n, heads, d)
        k = jnp.dot(h, w["k_proj"]).reshape(n, kv_heads, d)
        v = jnp.dot(h, w["v_proj"]).reshape(n, kv_heads, d)
        if c.qk_norm:
            q = _rms(q, w["q_norm"], c.rms_norm_eps)
            k = _rms(k, w["k_norm"], c.rms_norm_eps)
        if pos is not None:
            inv, _ = rotary.frequencies({"rope_theta": c.rope_theta},
                                        q.shape[-1])
            q, k = (rotary.rotate_halves(q, pos, inv),
                    rotary.rotate_halves(k, pos, inv))
        return q, k, v

    def _mixer_out(self, x, h, o, w):
        """``x + a * (o * sigmoid(h Wg)) Wo`` for mixer output ``o`` (N,
        H, D) (already normalised on a lightning layer)."""
        n = x.shape[0]
        gate = jax.nn.sigmoid(jnp.dot(h, w["gate_proj"]).astype(jnp.float32))
        o = (o.reshape(n, -1).astype(jnp.float32) * gate).astype(x.dtype)
        return x + (self.config.residual_scale
                    * jnp.dot(o, w["o_proj"])).astype(x.dtype)

    def _sparse_prefill(self, x, w, prefix_kv, start: int, block: int):
        """A ``minicpm4`` layer over rows ``x`` (T, E) at positions ``start
        ..`` after ``prefix_kv`` (2, H_kv, M, D) whose first ``start``
        positions are the prefix. Returns ``(x', kv (2, H_kv, M, D) with the
        run written, entries (H_kv, M / stride, D))``."""
        c = self.config
        sp = c.sparse
        t = x.shape[0]
        heads, hkv, d = c.num_attention_heads, c.num_key_value_heads, \
            c.head_dim

        def project(xb):
            h = _rms(xb, w["input_norm"], c.rms_norm_eps)
            q, k, v = self._qkv(h, w, heads, hkv, d, None)
            return jnp.concatenate([q, k, v], axis=1)

        qkv = _by_blocks(project, block, x)
        q, k, v = qkv[:, :heads], qkv[:, heads:heads + hkv], \
            qkv[:, heads + hkv:]
        new = jnp.swapaxes(jnp.stack([k, v]), 1, 2)      # (2, Hkv, T, D)
        kv = jax.lax.dynamic_update_slice(
            prefix_kv, new.astype(prefix_kv.dtype), (0, 0, start, 0))
        # keys up to the run's end, in whole blocks
        upto = -(-(start + t) // sp.block_size) * sp.block_size
        kk = jnp.swapaxes(kv[0, :, :upto], 0, 1).astype(x.dtype)
        vv = jnp.swapaxes(kv[1, :, :upto], 0, 1).astype(x.dtype)
        live = (jnp.arange(upto) < start + t)[:, None, None]
        kk, vv = jnp.where(live, kk, 0), jnp.where(live, vv, 0)
        ent = compress_keys(kk, sp).astype(prefix_kv.dtype)
        attn = sparse_prefill_attention(q, kk, vv, start, sp, entries=ent)
        entries = jnp.zeros((prefix_kv.shape[2] // sp.kernel_stride, hkv, d),
                            ent.dtype).at[:ent.shape[0]].set(ent)

        def finish(xb, ab):
            h = _rms(xb, w["input_norm"], c.rms_norm_eps)
            return self._mixer_out(xb, h, ab, w)

        x = _by_blocks(finish, block, x, attn)
        return self._ffn(x, w, block), kv, jnp.swapaxes(entries, 0, 1)

    def _linear_prefill(self, x, w, slopes, state, start: int, block: int):
        """A ``lightning-attn`` layer over rows ``x`` (T, E) at positions
        ``start ..`` from ``state`` (H, D, D). Returns ``(x', state after
        the last row, states after each whole block (n, H, D, D))``."""
        c = self.config
        t = x.shape[0]
        heads, d = c.lightning_nh, c.lightning_head_dim
        scale = 1.0 / math.sqrt(d)

        def run(S, xb, pos):
            h = _rms(xb, w["input_norm"], c.rms_norm_eps)
            q, k, v = self._qkv(h, w, heads, heads, d,
                                pos if c.lightning_use_rope else None)
            o, S = chunked_linear_attention(q, k, v, slopes, S, scale)
            o = o.reshape(xb.shape[0], heads * d)
            if c.use_output_norm:
                o = _rms(o, w["o_norm"], c.rms_norm_eps)
            xb = self._mixer_out(xb, h, o, w)
            return self._ffn(xb, w, block), S

        full = t // block
        pos = start + jnp.arange(t)
        state = state.astype(jnp.float32)
        outs, snaps = [], jnp.zeros((0,) + state.shape, jnp.float32)
        if full:
            def body(S, xs):
                xb, S = run(S, *xs)
                return S, (xb, S)
            state, (head, snaps) = jax.lax.scan(body, state, (
                x[:full * block].reshape(full, block, -1),
                pos[:full * block].reshape(full, block)))
            outs.append(head.reshape(full * block, -1))
        if full * block < t:
            tail, state = run(state, x[full * block:], pos[full * block:])
            outs.append(tail)
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0], state, \
            snaps

    def _run_arrays(self, ids, kv, state, flat, start: int, block: int,
                    hidden=None):
        """The layers run over one sequence ``ids`` (T,) at positions
        ``start ..`` (or over the residual stream ``hidden`` (T, E) an
        earlier stage left): ``kv`` (L_sparse, 2, H_kv, M, D), ``state``
        (L_lin, H, D, D). Returns ``(h (T, E), kv', entries, state',
        snapshots)``."""
        c = self.config
        x = hidden if hidden is not None else (jnp.take(
            flat[0], ids.astype(jnp.int32), axis=0).astype(
            jnp.float32) * c.scale_emb).astype(flat[0].dtype)
        at = 1
        kvs, ents, states, snaps = [], [], [], []
        for layer in self.layers:
            n = len(layer.tensors())
            w = self._weights(layer, flat[at:at + n])
            at += n
            if layer.mixer == SPARSE:
                x, kv_l, ent = self._sparse_prefill(
                    x, w, kv[len(kvs)], start, block)
                kvs.append(kv_l)
                ents.append(ent)
            else:
                x, st, sn = self._linear_prefill(
                    x, w, self._slopes[layer.index], state[len(states)],
                    start, block)
                states.append(st)
                snaps.append(sn)
        kv = jnp.stack(kvs) if kvs else kv
        ents = jnp.stack(ents) if ents else jnp.zeros((0,), x.dtype)
        state = jnp.stack(states) if states else state
        snaps = jnp.stack(snaps, axis=1) if snaps else \
            jnp.zeros((0,) + state.shape, jnp.float32)
        return x, kv, ents, state, snaps

    def _flat(self) -> List[Tensor]:
        """Every weight the layers run read, in ``_run_arrays``' order;
        then the final norm and the head."""
        return [self.embed_tokens] + [t for layer in self.layers
                                      for t in layer.tensors()] \
            + [self.norm, self.lm_head]

    def _logits_arrays(self, h, norm, head):
        c = self.config
        h = _rms(h, norm, c.rms_norm_eps).astype(jnp.float32) \
            / (c.hidden_size / c.dim_model_base)
        return jnp.dot(h.astype(head.dtype), head,
                       preferred_element_type=jnp.float32)

    def _caches(self, max_len: int):
        """Empty ``(kv, state)`` arrays for one sequence of ``max_len``."""
        c = self.config
        kinds = c.layer_kinds
        dt = self.embed_tokens._data.dtype
        kv = jnp.zeros((kinds.count("sparse"), 2, c.num_key_value_heads,
                        max_len, c.head_dim), dt)
        state = jnp.zeros((kinds.count("linear"),) + c.state_shape,
                          jnp.float32)
        return kv, state

    # -- whole-sequence forward -----------------------------------------
    def hidden_states(self, input_ids: Optional[Tensor] = None,
                      hidden: Optional[Tensor] = None,
                      block: int = 4096) -> Tensor:
        """The residual stream (T, E) after the layers run here, from
        ``input_ids`` (T,) or from the ``hidden`` (T, E) the stage before
        this one left (a cut of the depth is a pipeline stage)."""
        first = input_ids if hidden is None else hidden
        t = int(first.shape[0])
        bs = self.config.sparse.block_size
        kv, state = self._caches(-(-t // bs) * bs)

        def f(a, *flat):
            return self._run_arrays(
                a, kv, state, flat, 0, block,
                hidden=None if hidden is None else a)[0]
        with no_grad():
            # one program for the whole run: eagerly, every layer's scans
            # and maps would each compile on their own
            return apply("minicpm_sala_hidden", jax.jit(f), first,
                         *self._flat()[:-2], differentiable=False, amp=False)

    def forward(self, input_ids: Tensor, block: int = 4096) -> Tensor:
        """``input_ids`` (1, T) or (T,) -> logits (1, T, V) / (T, V)
        float32."""
        batched = len(input_ids.shape) == 2
        ids = input_ids[0] if batched else input_ids
        with no_grad():
            lg = apply("minicpm_sala_logits", self._logits_arrays,
                       self.hidden_states(ids, block=block), self.norm,
                       self.lm_head, differentiable=False, amp=False)
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
    def serving_callables(self, max_len: int, block: int = 4096,
                          with_logits: bool = False):
        """``(prefill_fn, step_fn)`` for an engine built with
        ``ServingConfig(num_layers=len(layers_run), num_heads=
        num_key_value_heads, head_dim=head_dim, layer_kinds=
        config.layer_kinds, state_shape=config.state_shape,
        index_per_page=page_size // kernel_stride,
        state_snapshot_tokens=block)``; the page size is the selection's
        block size.

        * ``prefill_fn(ids (1, Lp), cache: HybridPrefill, start=0)`` runs
          positions ``[start, start + Lp)``: ``start`` is a multiple of
          ``block`` (a state snapshot's boundary), ``cache.kv`` holds the
          prefix below it and ``cache.states`` the state at it. Returns the
          first token and the cache filled as :class:`HybridPrefill` says.
        * ``step_fn(tok (B, 1), cache: HybridDecodeCache, t (B,))`` decodes
          one token a row: a sparse layer scores, chooses and streams its
          chosen pages; a lightning layer updates the row's state in the
          pool. A row with ``t == 0`` is batch padding.

          Its third value is what the step's selections counted, ``(2,)``
          int32: pages held and pages attended, summed over rows, KV heads
          and sparse layers (``sparse_attention.pages_counted``); it rides
          behind the tokens in the step's one read-back and the engine
          stamps ``serving.sparse.decode`` from it.

        ``with_logits`` (a check's way to logits and chosen blocks through
        the compiled programs; not for an ``Engine``'s step loop, which
        reads two counts): ``prefill_fn`` returns a third value, the
        float32 logits behind its token bit-cast to int32, and ``step_fn``
        appends to its counts the blocks every sparse layer chose and the
        logits — ``Programs`` reads both back with the tokens, and
        :meth:`split_step_extras` takes a decode step's apart."""
        c = self.config
        sp = c.sparse
        if max_len > c.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings "
                             f"{c.max_position_embeddings}")
        if block % sp.block_size:
            raise ValueError(f"block {block} is not whole pages of "
                             f"{sp.block_size}")
        layers = list(self.layers)
        roles, n_s, n_l = [], 0, 0
        for layer in layers:
            if layer.mixer == SPARSE:
                roles.append(n_s)
                n_s += 1
            else:
                roles.append(n_l)
                n_l += 1

        def prefill_fn(ids, cache: HybridPrefill, start=0):
            def f(ids_a, kv_a, state_a, *flat):
                h, kv2, ents, state2, snaps = self._run_arrays(
                    ids_a[0], kv_a[:, :, 0], state_a, flat[:-2], start,
                    block)
                lg = self._logits_arrays(h[-1:], flat[-2], flat[-1])
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return nxt.reshape(1, 1), kv2[:, :, None], ents, state2, \
                    snaps, jax.lax.bitcast_convert_type(lg, jnp.int32)
            nxt, kv2, ents, state2, snaps, lg = apply(
                "minicpm_sala_prefill", f, ids, cache.kv, cache.states[0],
                *self._flat(), differentiable=False, amp=False)
            out = HybridPrefill(kv=kv2, states=(state2,), entries=ents,
                                snapshots=(snaps,))
            return (nxt, out, lg) if with_logits else (nxt, out)

        def pre(layer, heads, kv_heads, d, rope):
            def f(x, t, *flat):
                w = self._weights(layer, flat)
                h = _rms(x, w["input_norm"], c.rms_norm_eps)
                q, k, v = self._qkv(h, w, heads, kv_heads, d,
                                    t if rope else None)
                return h, q, k, v
            return f

        def post(layer):
            def f(x, h, o, *flat):
                w = self._weights(layer, flat)
                if layer.mixer == LINEAR and c.use_output_norm:
                    o = _rms(o.reshape(x.shape[0], -1), w["o_norm"],
                             c.rms_norm_eps)
                x = self._mixer_out(x, h, o, w)
                return self._ffn(x, w, x.shape[0])
            return f

        def step_fn(tok, cache, t):
            if not isinstance(cache, HybridDecodeCache):
                raise TypeError(
                    "MiniCPMSalaForCausalLM decodes over a HybridDecodeCache "
                    "(pages, compressed keys and the state pool): build the "
                    "engine with this config's layer_kinds and state_shape")
            cache = replace(cache, sparse=sp)
            b = int(tok.shape[0])
            x = apply("minicpm_sala_embed", lambda i, e: (jnp.take(
                e, i.reshape(-1).astype(jnp.int32), axis=0).astype(
                jnp.float32) * c.scale_emb).astype(e.dtype),
                tok, self.embed_tokens, differentiable=False, amp=False)
            for layer, role in zip(layers, roles):
                ws = layer.tensors()
                sparse = layer.mixer == SPARSE
                h, q, k, v = apply("minicpm_sala_qkv", pre(layer, *(
                    (c.num_attention_heads, c.num_key_value_heads,
                     c.head_dim, False) if sparse else
                    (c.lightning_nh, c.lightning_nh, c.lightning_head_dim,
                     c.lightning_use_rope))), x, t, *ws,
                    differentiable=False, amp=False)
                if sparse:
                    o, cache = sparse_decode_attention(
                        q, k, v, cache.at_layer(role))
                else:
                    slopes = self._slopes[layer.index]
                    impl, interpret = cache.impl, cache.interpret

                    def upd(qa, ka, va, pool, rows, role=role, slopes=slopes):
                        return linear_state_decode(
                            qa, ka, va, slopes, pool, rows, role,
                            1.0 / math.sqrt(c.lightning_head_dim),
                            impl=impl, interpret=interpret)
                    o, state = apply(
                        "linear_state_decode", upd, q, k, v, cache.states[0],
                        cache.state_rows, differentiable=False, amp=False)
                    cache = replace(cache, states=(state,))
                x = apply("minicpm_sala_out", post(layer), x, h, o, *ws,
                          differentiable=False, amp=False)
            def head(a, n_, hd):
                lg = self._logits_arrays(a, n_, hd)
                return jnp.argmax(lg, axis=-1).astype(jnp.int32).reshape(
                    -1, 1), jax.lax.bitcast_convert_type(lg, jnp.int32)
            nxt, lg = apply("minicpm_sala_head", head, x, self.norm,
                            self.lm_head, differentiable=False, amp=False)
            counted = pages_counted(cache)
            if with_logits:
                counted = apply(
                    "minicpm_sala_check", lambda n_, lg_, *blocks:
                    jnp.concatenate([n_, jnp.stack(blocks, 1).reshape(-1),
                                     lg_.reshape(-1)]),
                    counted, lg, *[b_ for _, b_ in cache.chose],
                    differentiable=False, amp=False)
            return nxt, cache, counted

        return prefill_fn, step_fn

    def split_step_extras(self, flat, batch: int):
        """What a ``with_logits`` decode step of ``batch`` rows left behind
        its tokens (``Step.read()[1]``) -> ``(pages (2,), blocks (batch,
        sparse layers, H_kv, K) int32 — -1 where none was chosen —, logits
        (batch, vocab) float32)``."""
        c = self.config
        hkv = c.num_key_value_heads
        n_s = c.layer_kinds.count("sparse")
        flat = np.asarray(flat, np.int32)
        logits = flat[flat.size - batch * c.vocab_size:]
        blocks = flat[2:flat.size - logits.size]
        return flat[:2], blocks.reshape(batch, n_s, hkv, -1), \
            logits.view(np.float32).reshape(batch, c.vocab_size)
