"""``paddle.incubate.nn.functional`` — fused-op surface.

Parity: python/paddle/incubate/nn/functional/ (fused_rms_norm,
fused_layer_norm, fused_rotary_position_embedding, swiglu, fused_dropout_add,
fused_linear*, memory-efficient attention). The reference backs these with
hand-written CUDA kernels (paddle/phi/kernels/fusion/); on TPU the same
fusion happens in XLA — each function below is the algebra, written so the
compiler fuses it into the surrounding matmuls — with flash attention
(Pallas) behind the attention entries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, apply
from ..nn import functional as F
from ..ops._helpers import ensure_tensor
from ..ops.linalg import _precision


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                   quant_min_bound=0, name=None):
    """RMSNorm with optional pre-norm bias/residual add. Returns
    (out, residual_out) when ``residual`` is given, else out."""
    x = ensure_tensor(x)
    extras, has = [], {}
    for key, t in (("bias", bias), ("residual", residual),
                   ("w", norm_weight), ("b", norm_bias)):
        if t is not None:
            has[key] = len(extras)
            extras.append(ensure_tensor(t))

    def f(a, *rest):
        h = a
        if "bias" in has:
            h = h + rest[has["bias"]]
        if "residual" in has:
            h = h + rest[has["residual"]]
        res_out = h
        bna = begin_norm_axis % h.ndim
        axes = tuple(range(bna, h.ndim))
        ms = jnp.mean(jnp.square(h.astype(jnp.float32)), axis=axes,
                      keepdims=True)
        out = (h.astype(jnp.float32) * jax.lax.rsqrt(ms + epsilon))
        if "w" in has:
            out = out * rest[has["w"]].astype(jnp.float32)
        if "b" in has:
            out = out + rest[has["b"]].astype(jnp.float32)
        out = out.astype(a.dtype)
        return (out, res_out) if "residual" in has else out

    out = apply("fused_rms_norm", f, x, *extras)
    return out


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None,
                     quant_scale=-1, name=None):
    x = ensure_tensor(x)
    extras, has = [], {}
    for key, t in (("bias", bias), ("residual", residual),
                   ("w", norm_weight), ("b", norm_bias)):
        if t is not None:
            has[key] = len(extras)
            extras.append(ensure_tensor(t))

    def f(a, *rest):
        h = a
        if "bias" in has:
            h = h + rest[has["bias"]]
        if "residual" in has:
            h = h + rest[has["residual"]]
        res_out = h
        h32 = h.astype(jnp.float32)
        bna = begin_norm_axis % h.ndim
        axes = tuple(range(bna, h.ndim))
        mean = jnp.mean(h32, axis=axes, keepdims=True)
        var = jnp.var(h32, axis=axes, keepdims=True)
        out = (h32 - mean) * jax.lax.rsqrt(var + epsilon)
        if "w" in has:
            out = out * rest[has["w"]].astype(jnp.float32)
        if "b" in has:
            out = out + rest[has["b"]].astype(jnp.float32)
        out = out.astype(a.dtype)
        return (out, res_out) if "residual" in has else out

    return apply("fused_layer_norm", f, x, *extras)


def _apply_rope(a, cos, sin, neox):
    """a: (B, S, H, D); cos/sin: (S, D) or broadcastable."""
    if neox:  # rotate halves: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)
        d = a.shape[-1] // 2
        x1, x2 = a[..., :d], a[..., d:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
    else:  # GPT-J interleaved pairs
        x1 = a[..., 0::2]
        x2 = a[..., 1::2]
        rot = jnp.stack([-x2, x1], axis=-1).reshape(a.shape)
    return a * cos + rot * sin


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0,
                                    name=None):
    """Apply RoPE to q (and k, v when given). ``sin``/``cos``: (1, S, 1, D)
    or (S, D); generated from ``rotary_emb_base`` when omitted."""
    q = ensure_tensor(q)
    if time_major:  # (S, B, H, D) -> batch-major, swap back at the end
        from ..ops.manipulation import transpose
        perm = [1, 0, 2, 3]
        outs = fused_rotary_position_embedding(
            transpose(q, perm),
            transpose(k, perm) if k is not None else None,
            transpose(v, perm) if v is not None else None,
            sin=sin, cos=cos, position_ids=position_ids,
            use_neox_rotary_style=use_neox_rotary_style, time_major=False,
            rotary_emb_base=rotary_emb_base)
        return tuple(transpose(t, perm) if t is not None else None
                     for t in outs)
    b, s, h, d = (int(v_) for v_ in q._data.shape)
    if cos is None or sin is None:
        import numpy as np
        inv = 1.0 / (rotary_emb_base ** (np.arange(0, d, 2,
                                                   dtype=np.float32) / d))
        t = np.arange(s, dtype=np.float32)
        freqs = np.outer(t, inv)                       # (S, D/2)
        if use_neox_rotary_style:
            emb = np.concatenate([freqs, freqs], axis=-1)
        else:
            emb = np.repeat(freqs, 2, axis=-1)
        cos = Tensor(jnp.asarray(np.cos(emb)[None, :, None, :]))
        sin = Tensor(jnp.asarray(np.sin(emb)[None, :, None, :]))
    cos, sin = ensure_tensor(cos), ensure_tensor(sin)

    tensors = [t for t in (q, k, v) if t is not None]
    n = len(tensors)

    def f(cc, ss, *qkv):
        if cc.ndim == 2:  # documented (S, D) form -> (1, S, 1, D)
            cc, ss = cc[None, :, None, :], ss[None, :, None, :]
        if position_ids is not None:
            pid = jnp.asarray(position_ids._data
                              if hasattr(position_ids, "_data")
                              else position_ids)
            # drop only the broadcast axes (0: batch, 2: heads) — squeezing
            # everything would also collapse a length-1 sequence (decode step)
            cc2 = cc.reshape(cc.shape[1], cc.shape[3])
            ss2 = ss.reshape(ss.shape[1], ss.shape[3])
            cc = cc2[pid][:, :, None, :]
            ss = ss2[pid][:, :, None, :]
        outs = tuple(_apply_rope(t, cc.astype(t.dtype), ss.astype(t.dtype),
                                 use_neox_rotary_style) for t in qkv)
        return outs if len(outs) > 1 else outs[0]

    out = apply("fused_rope", f, cos, sin, *tensors)
    outs = list(out) if isinstance(out, tuple) else [out]
    result = []
    for t in (q, k, v):
        result.append(outs.pop(0) if t is not None else None)
    return tuple(result)


def swiglu(x, y=None, name=None):
    """silu(x) * y; when y is None, x is split in half on the last axis."""
    x = ensure_tensor(x)
    if y is None:
        return apply("swiglu",
                     lambda a: jax.nn.silu(a[..., :a.shape[-1] // 2]) *
                     a[..., a.shape[-1] // 2:], x)
    return apply("swiglu", lambda a, b: jax.nn.silu(a) * b, x,
                 ensure_tensor(y))


def fused_dropout_add(x, y, p=0.5, training=True,
                      mode="upscale_in_train", name=None):
    """dropout(x) + y in one fused region."""
    dropped = F.dropout(x, p=p, training=training, mode=mode)
    return dropped + y


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True, name=None):
    h = x if bias is None else x + bias
    h = F.dropout(h, p=dropout_rate, training=training)
    h = h + residual
    return F.layer_norm(h, h.shape[-1:], weight=ln_scale, bias=ln_bias,
                        epsilon=ln_epsilon)


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def f(a, w, *b):
        ww = w.T if transpose_weight else w
        out = jnp.matmul(a, ww, precision=_precision())
        return out + b[0] if b else out

    if bias is not None:
        return apply("fused_linear", f, x, weight, ensure_tensor(bias))
    return apply("fused_linear", f, x, weight)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """matmul + bias + activation, fused by XLA into one kernel."""
    x, y = ensure_tensor(x), ensure_tensor(y)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda v: v, "": lambda v: v}[activation]

    def f(a, w, *b):
        if trans_x:
            a = jnp.swapaxes(a, -1, -2)
        if trans_y:
            w = jnp.swapaxes(w, -1, -2)
        out = jnp.matmul(a, w, precision=_precision())
        if b:
            out = out + b[0]
        return act(out)

    if bias is not None:
        return apply("fused_linear_activation", f, x, y, ensure_tensor(bias))
    return apply("fused_linear_activation", f, x, y)


def memory_efficient_attention(query, key, value, attn_bias=None, p=0.0,
                               scale=None, training=True, name=None):
    """Memory-efficient attention (reference: cutlass-backed kernel); here
    the SDPA layer, which routes to the Pallas flash kernel when eligible.
    SDPA applies 1/sqrt(d) internally; a custom ``scale`` is folded into the
    query so the net scaling equals ``scale``."""
    if scale is not None:
        d = int(query.shape[-1])
        query = query * (float(scale) * (d ** 0.5))
    return F.scaled_dot_product_attention(
        query, key, value, attn_mask=attn_bias,
        dropout_p=p if training else 0.0, is_causal=False)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0, name=None):
    """Varlen attention: per-sequence lengths become an additive mask over
    the padded batch (static shapes — the TPU-friendly varlen form).

    query/key/value: (B, H, S, D); seq_lens/kv_seq_lens: (B,) or (B, 1).
    """
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    seq_lens, kv_seq_lens = ensure_tensor(seq_lens), ensure_tensor(kv_seq_lens)
    extras = [ensure_tensor(mask)] if mask is not None else []

    def f(q, k, v, sl, kvl, *mk):
        b, h, sq, d = q.shape
        sk = k.shape[2]
        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sc
        kvalid = jnp.arange(sk)[None, :] < kvl.reshape(-1, 1)
        logits = jnp.where(kvalid[:, None, None, :], logits, -1e30)
        if causal:
            cm = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
            logits = jnp.where(cm[None, None], logits, -1e30)
        if mk:
            logits = logits + mk[0]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)
        qvalid = jnp.arange(sq)[None, :] < sl.reshape(-1, 1)
        return out * qvalid[:, None, :, None].astype(q.dtype)

    return apply("varlen_mea", f, query, key, value, seq_lens, kv_seq_lens,
                 *extras)


def softmax_mask_fuse(x, mask, name=None):
    """softmax(x + mask) fused (reference: incubate.softmax_mask_fuse)."""
    x, mask = ensure_tensor(x), ensure_tensor(mask)
    return apply("softmax_mask_fuse",
                 lambda a, m: jax.nn.softmax(
                     a.astype(jnp.float32) + m.astype(jnp.float32),
                     axis=-1).astype(a.dtype), x, mask)


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None,
                     name=None):
    a = ensure_tensor(seq_lens_encoder)
    b = ensure_tensor(seq_lens_decoder)
    return apply("blha_get_max_len",
                 lambda x_, y_: (jnp.max(x_), jnp.max(y_)), a, b)


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None,
                               ln_scale=None, ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.0,
                               attn_dropout_rate=0.0, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=None,
                               name=None):
    """The reference's fused attention op (paddle/phi/kernels/fusion/
    fused_attention): pre/post LN + qkv matmul + SDPA + out proj + residual,
    one region for XLA to fuse. qkv_weight: (3, H, h, h/H) as upstream."""
    x = ensure_tensor(x)
    qkv_w = ensure_tensor(qkv_weight)  # (3, num_heads, head_dim, embed_dim)
    lin_w = ensure_tensor(linear_weight)
    h = int(x.shape[-1])
    nh = int(qkv_w.shape[1])
    hd = int(qkv_w.shape[2])

    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [h], weight=pre_ln_scale, bias=pre_ln_bias,
                         epsilon=pre_ln_epsilon)
    b, s = int(x.shape[0]), int(x.shape[1])
    from ..ops.manipulation import reshape
    qkv = apply("fused_qkv",
                lambda a, w: jnp.einsum("bsh,tndh->tbsnd", a,
                                        w, precision=_precision()),
                x, qkv_w)
    if qkv_bias is not None:
        qkv = qkv + ensure_tensor(qkv_bias).reshape([3, 1, 1, nh, hd])
    q, k, v = qkv[0], qkv[1], qkv[2]
    cache_out = None
    if cache_kv is not None:
        # cache layout (reference): (2, B, num_heads, cache_len, head_dim)
        cache_kv = ensure_tensor(cache_kv)
        from ..ops.manipulation import concat, transpose
        k_hist = transpose(cache_kv[0], [0, 2, 1, 3])  # -> (B, L, H, D)
        v_hist = transpose(cache_kv[1], [0, 2, 1, 3])
        k = concat([k_hist, k], axis=1)
        v = concat([v_hist, v], axis=1)
        from ..ops.manipulation import stack as _stack
        cache_out = _stack([transpose(k, [0, 2, 1, 3]),
                            transpose(v, [0, 2, 1, 3])], axis=0)
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0,
        training=training)
    out = reshape(out, [b, s, h])
    out = apply("fused_out_proj",
                lambda a, w: jnp.matmul(a, w, precision=_precision()),
                out, lin_w)
    if linear_bias is not None:
        out = out + ensure_tensor(linear_bias)
    out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = out + residual
    if not pre_layer_norm:
        out = F.layer_norm(out, [h], weight=ln_scale, bias=ln_bias,
                           epsilon=ln_epsilon)
    if cache_out is not None:
        return out, cache_out
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode="upscale_in_train",
                      ring_id=-1, add_residual=True, name=None):
    """The reference's fused FFN: LN + linear + act + dropout + linear +
    residual (+ LN)."""
    x = ensure_tensor(x)
    h = int(x.shape[-1])
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [h], weight=ln1_scale, bias=ln1_bias,
                         epsilon=ln1_epsilon)
    out = fused_linear(x, linear1_weight, bias=linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, p=dropout1_rate, training=training, mode=mode)
    out = fused_linear(out, linear2_weight, bias=linear2_bias)
    out = F.dropout(out, p=dropout2_rate, training=training, mode=mode)
    if add_residual:
        out = out + residual
    if not pre_layer_norm:
        out = F.layer_norm(out, [h], weight=ln2_scale, bias=ln2_bias,
                           epsilon=ln2_epsilon)
    return out


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """matmul + bias epilogue (the cublasLt-fused op upstream)."""
    x, y = ensure_tensor(x), ensure_tensor(y)

    def f(a, w, *b):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            w = jnp.swapaxes(w, -1, -2)
        out = jnp.matmul(a, w, precision=_precision())
        return out + b[0] if b else out

    if bias is not None:
        return apply("fused_matmul_bias", f, x, y, ensure_tensor(bias))
    return apply("fused_matmul_bias", f, x, y)


def softmax_mask_fuse_upper_triangle(x, name=None):
    """Causal-masked softmax fused (reference:
    incubate.softmax_mask_fuse_upper_triangle): softmax over the last dim
    with strictly-upper-triangle positions masked to -inf. XLA fuses the
    mask + softmax into one kernel."""
    import jax.numpy as jnp

    from ..core.tensor import apply
    from ..ops._helpers import ensure_tensor

    x = ensure_tensor(x)

    def f(a):
        q, k = a.shape[-2], a.shape[-1]
        mask = jnp.tril(jnp.ones((q, k), bool), k=k - q)
        logits = jnp.where(mask, a.astype(jnp.float32), -1e30)
        import jax
        return jax.nn.softmax(logits, axis=-1).astype(a.dtype)

    return apply("softmax_mask_fuse_upper_triangle", f, x)


def identity_loss(x, reduction="none", name=None):
    """Pass-through loss head (reference: paddle.incubate.identity_loss —
    marks a tensor as the loss for IPU-style pipelines; here it reduces per
    ``reduction`` and is differentiable)."""
    import jax.numpy as jnp

    from ..core.tensor import apply
    from ..ops._helpers import ensure_tensor

    x = ensure_tensor(x)
    red = {0: "sum", 1: "mean", 2: "none"}.get(reduction, reduction)

    def f(a):
        if red == "sum":
            return jnp.sum(a)
        if red == "mean":
            return jnp.mean(a)
        return a

    return apply("identity_loss", f, x)


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type="gelu", name=None):
    """Functional expert-choice MoE (upstream
    paddle.incubate.nn.functional.fused_ec_moe — the op behind the
    FusedEcMoe layer): weights (E, H, I)/(E, 1, I)/(E, I, H)/(E, 1, H),
    gate LOGITS (B, S, E). Same einsum-over-experts lowering as the layer;
    see incubate/nn.py FusedEcMoe for the capacity policy."""
    from .nn import _ec_moe_apply
    if act_type not in ("gelu", "relu"):
        raise ValueError("act_type must be gelu or relu")
    return _ec_moe_apply(ensure_tensor(x), ensure_tensor(gate),
                         ensure_tensor(bmm0_weight), ensure_tensor(bmm0_bias),
                         ensure_tensor(bmm1_weight), ensure_tensor(bmm1_bias),
                         act_type)


def _paged_mmha(x, cache):
    """Fused-qkv decode attention over a :class:`PagedDecodeCache` view.

    ``x`` is the (B, 3*H*D) fused qkv of ONE new token (fused layout ⇒
    q heads == kv heads). Splits q/k/v, runs the paged kernel for the
    view's layer and returns ``(out (B, H*D), cache')``, position ``t``'s
    K/V pending on ``cache'`` for the step's one pool write
    (``ops.paged_attention.commit_pending``)."""
    from ..ops.manipulation import reshape
    from ..ops.paged_attention import paged_decode_attention
    nh, hd = cache.num_kv_heads, cache.head_dim
    b = int(x.shape[0])
    qkv = reshape(x, [b, 3, nh, hd])
    q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]    # (B, H, D)
    out, new_cache = paged_decode_attention(q, k_new, v_new, cache)
    return reshape(out, [b, nh * hd]), new_cache


def masked_multihead_attention(x, bias=None, src_mask=None,
                               sequence_lengths=None, rotary_tensor=None,
                               beam_cache_offset=None, cache_kv=None,
                               out_shift=None, out_smooth=None, seq_len=1,
                               rotary_emb_dims=0, use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0, name=None):
    """Single-token decode attention over a pre-allocated KV cache
    (reference: paddle.incubate.nn.functional.masked_multihead_attention —
    the generation-loop kernel behind FusedMultiTransformer decode).

    ``x``: (B, 3*H*D) fused qkv for ONE new token; ``cache_kv``:
    (2, B, H, max_len, D) pre-allocated; ``sequence_lengths`` (B,) gives
    each row's current length t — k/v write at position t and attention
    spans positions <= t (static shapes: the span mask is built from
    ``sequence_lengths``, no dynamic slicing). Returns (out (B, H*D),
    updated cache). The int8/quant knobs (out_shift/out_smooth/out_scale)
    and beam offsets are inference-server features the XLA path does not
    need — accepted for signature parity, non-default values raise."""
    for unsupported, label in ((rotary_tensor, "rotary_tensor"),
                               (beam_cache_offset, "beam_cache_offset"),
                               (out_shift, "out_shift"),
                               (out_smooth, "out_smooth")):
        if unsupported is not None:
            raise NotImplementedError(
                f"masked_multihead_attention: {label} is not supported on "
                "the XLA path (quant/beam serving knobs)")
    x = ensure_tensor(x)
    if cache_kv is None:
        raise ValueError("masked_multihead_attention requires cache_kv")
    from ..ops.paged_attention import PagedDecodeCache
    if isinstance(cache_kv, PagedDecodeCache):
        # paged-attention decode tier (ISSUE 13): the cache is a page-pool
        # view, not the dense (2, B, H, max_len, D) buffer — attention
        # streams the slot's live pages through the Pallas kernel and the
        # token's K/V rides the returned view as pending. ``sequence_lengths``
        # already rides inside the view (``t``); an additive src_mask has
        # no kernel leg (the span mask is the decode contract).
        if src_mask is not None:
            raise NotImplementedError(
                "masked_multihead_attention: src_mask is not supported on "
                "the paged-attention path (span masking to <= t is built "
                "in; run the dense tier for additive masks)")
        if bias is not None:
            x = x + ensure_tensor(bias)
        return _paged_mmha(x, cache_kv)
    cache = ensure_tensor(cache_kv)
    two, b, nh, max_len, hd = (int(s) for s in cache.shape)
    if bias is not None:
        x = x + ensure_tensor(bias)
    if sequence_lengths is None:
        from ..ops.creation import zeros
        sequence_lengths = zeros([b], dtype="int32")
    seq_lens = ensure_tensor(sequence_lengths)
    mask_t = ensure_tensor(src_mask) if src_mask is not None else None

    from ..core.tensor import _is_tracer
    sl_data = seq_lens._data
    # bounds check in NUMPY: jnp ops on a concrete array still stage to
    # tracers when an outer trace (e.g. the scan-decode body) is active,
    # and a staged bool cannot branch
    import numpy as _np
    if not _is_tracer(sl_data) and bool(_np.any(_np.asarray(sl_data)
                                                >= max_len)):
        raise ValueError(
            f"masked_multihead_attention: sequence length >= cache max_len "
            f"{max_len} — the write would be silently dropped")

    def f(xa, ca, sl, *maybe_mask):
        qkv = xa.reshape(b, 3, nh, hd)
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # (B, H, D)
        t = jnp.broadcast_to(sl.astype(jnp.int32).reshape(-1), (b,))  # (B,)
        onehot = jax.nn.one_hot(t, max_len, dtype=jnp.bool_)  # (B, L)
        sel = onehot[:, None, :, None]                      # (B, 1, L, 1)
        k_cache, v_cache = ca[0], ca[1]                     # (B, H, L, D)
        # OVERWRITE slot t (not accumulate): cache reuse / step retry must
        # replace, never sum with stale contents
        k_cache = jnp.where(sel, k_new[:, :, None, :], k_cache)
        v_cache = jnp.where(sel, v_new[:, :, None, :], v_cache)
        logits = jnp.einsum("bhd,bhld->bhl", q, k_cache) / (hd ** 0.5)
        span = jnp.arange(max_len)[None, :] <= t[:, None]   # (B, L)
        logits = jnp.where(span[:, None, :], logits, -1e30)
        if maybe_mask:
            # upstream src_mask: (B, 1|nh, 1, Lm) additive, Lm = t+1 —
            # keep the head axis and zero-pad to max_len (positions past t
            # are already -1e30 via the span mask)
            m = maybe_mask[0].reshape(b, -1, maybe_mask[0].shape[-1])
            lm = m.shape[-1]
            if lm < max_len:
                m = jnp.pad(m, ((0, 0), (0, 0), (0, max_len - lm)))
            logits = logits + m[:, :, :max_len]
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhl,bhld->bhd", p, v_cache)
        return out.reshape(b, nh * hd), jnp.stack([k_cache, v_cache])

    args = [x, cache, seq_lens] + ([mask_t] if mask_t is not None else [])
    out, new_cache = apply("masked_multihead_attention", f, *args)
    return out, new_cache
