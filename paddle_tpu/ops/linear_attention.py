"""Linear attention over a fixed state per slot: lightning attention with a
fixed per-head decay (ISSUE 31) and the gated delta rule (ISSUE 33), one
family — what walks the state pool is shared (:func:`_walk_pool`), the
update rule differs.

Lightning: the recurrence

    S_t = lam_h * S_{t-1} + k_t^T v_t          (S: D x D, float32)
    o_t = scale * q_t S_t

for ``H`` heads, as the serving engine needs it:

* :func:`lightning_slopes` — the decay's exponent ``s_h`` (``lam_h =
  exp(-s_h)``): Lightning Attention's per-head slopes (arXiv:2401.04658)
  as MiniMax-Text-01 builds them, ``2 ** (-8 (h + 1) / H)`` scaled by
  ``1 - l / (L - 1) + 1e-5`` for layer ``l`` of ``L``.
* :func:`chunked_linear_attention` — a prefill: the sequence in chunks,
  quadratic inside a chunk, the state carried between chunks, starting
  from any ``state`` (a tail after a shared prefix starts from the
  prefix's). Plain ``jax.numpy`` under a ``lax.scan``: XLA fuses it, there
  is no kernel of this repo's in it.
* :func:`linear_state_decode` — the one-token update of a batch of rows
  whose states live in a POOL ``(rows, L_lin, H, D, D)``: row ``rows[b]``
  of layer ``layer`` is read, updated and written IN PLACE (the pool comes
  donated). On a TPU a Pallas kernel, ``linear_state_decode`` in a device
  trace: per (head group, row) one block of the pool in, one out — the
  bytes a state costs a step, ``2 * H * D * D * 4`` a row and layer, and
  nothing else. Elsewhere the same arithmetic as a gather and a scatter.

The gated delta rule (Gated DeltaNet): per value head a state ``S`` (Dk x
Dv, float32), a decay ``g_t <= 0`` and a write strength ``beta_t`` per token,

    S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T;  o_t = S^T q_t

* :func:`gated_delta_decode` — the one-token update over the same kind of
  pool, ``gated_delta_decode`` in a device trace (its dense form:
  :func:`gated_delta_dense`).
* :func:`conv_tail_decode` — the depthwise causal convolution in front of
  it, one token a row over a second per-slot pool: the last ``K - 1`` inputs
  of every channel (``gated_delta_decode_conv`` in a device trace).
* :func:`chunked_gated_delta_rule` — a prefill from any start state: within
  a chunk the corrections of all its tokens are one unit-lower-triangular
  solve (row by row, forward substitution), the state is carried between
  chunks. Plain ``jax.numpy`` under a ``lax.scan``.
* :class:`StateDecodeCache` / :class:`StatePrefill` — what the serving
  engine hands a model that keeps plain pages and a state per slot.

Every product that touches the state runs in float32 at the highest
matmul precision: a state kept or updated in bfloat16 drifts by 2**-9 a
token, which the reference's tolerance is there to catch.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import PagedDecodeCache

__all__ = ["lightning_slopes", "chunked_linear_attention",
           "linear_decode_dense", "linear_state_decode",
           "gated_delta_dense", "gated_delta_decode", "conv_tail_decode",
           "chunked_gated_delta_rule", "StateDecodeCache", "StatePrefill"]

_HI = jax.lax.Precision.HIGHEST
_HEAD_GROUP = 8     # heads a grid step of the decode kernel updates


def lightning_slopes(num_heads: int, layer_index: int,
                     num_layers: int) -> np.ndarray:
    """``s_h`` (H,) float32 for published layer ``layer_index`` of
    ``num_layers``; the decay is ``exp(-s_h)`` a token."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    base = 2.0 ** (-8.0 * h / num_heads)
    scale = 1.0 - layer_index / max(num_layers - 1, 1) + 1e-5
    return (base * scale).astype(np.float32)


def chunked_linear_attention(q, k, v, slopes, state, scale: float,
                             chunk: int = 256):
    """``q, k, v`` (T, H, D), ``slopes`` (H,), ``state`` (H, D, D) float32
    (the state BEFORE the first token) -> ``(o (T, H, D) float32, state
    after the last token)``. ``T`` need not be a multiple of ``chunk``: the
    padding carries no key, no value and no decay."""
    t, h, d = q.shape
    f32 = jnp.float32
    c = min(chunk, max(t, 1))
    pad = -t % c
    n = (t + pad) // c

    def chunks(a):
        return jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(n, c, h, d)

    # log-decay of each token: -s_h, and 0 for padding
    real = (jnp.arange(t + pad) < t).astype(f32).reshape(n, c)
    s = jnp.asarray(slopes, f32)
    lower = jnp.tril(jnp.ones((c, c), bool))

    def body(S, xs):
        qc, kc, vc, rc = xs
        g = jnp.cumsum(rc)[:, None] * (-s)[None, :]          # (c, H): G_i
        a = jnp.einsum("ihd,jhd->hij", qc, kc,
                       preferred_element_type=f32)
        decay = jnp.exp(jnp.where(lower[None], (g.T[:, :, None]
                                                - g.T[:, None, :]), -jnp.inf))
        a = a * decay                                         # (H, c, c)
        intra = jnp.einsum("hij,jhd->ihd", a, vc.astype(f32), precision=_HI)
        inter = jnp.einsum("ihd,hde->ihe",
                           qc.astype(f32) * jnp.exp(g)[:, :, None], S,
                           precision=_HI)
        last = g[-1]                                          # (H,)
        kd = kc.astype(f32) * jnp.exp(last[None, :] - g)[:, :, None]
        S = jnp.exp(last)[:, None, None] * S + jnp.einsum(
            "jhd,jhe->hde", kd, vc.astype(f32), precision=_HI)
        return S, (intra + inter) * scale

    state, out = jax.lax.scan(
        body, state.astype(f32), (chunks(q), chunks(k), chunks(v), real))
    return out.reshape(n * c, h, d)[:t], state


def linear_decode_dense(q, k, v, slopes, states, scale: float):
    """One token a row on given states: ``q, k, v`` (B, H, D), ``states``
    (B, H, D, D) -> ``(o (B, H, D) float32, states')``."""
    f32 = jnp.float32
    lam = jnp.exp(-jnp.asarray(slopes, f32))[None, :, None, None]
    new = lam * states.astype(f32) + \
        k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    out = jnp.sum(q.astype(f32)[..., :, None] * new, axis=-2) * scale
    return out, new


def _decode_kernel(rows_ref, layer_ref, qt_ref, kt_ref, v_ref, lam_ref,
                   s_ref, o_ref, s_out_ref, *, group: int, scale: float):
    """One (head group, batch row): refs ``qt/kt (1, 1, D, group)`` (a
    head's vector is a COLUMN, so that it broadcasts along the state's
    rows), ``v/o (1, 1, group, D)``, ``lam (1, 1, group)``, the state block
    ``(1, 1, group, D, D)`` in and out. All float32 on the VPU: exact."""
    del rows_ref, layer_ref                  # used by the index maps only
    for h in range(group):
        kc = kt_ref[0, 0][:, h:h + 1]                         # (D, 1)
        qc = qt_ref[0, 0][:, h:h + 1]
        vr = v_ref[0, 0, h:h + 1, :]                          # (1, D)
        new = lam_ref[0][:, h:h + 1] * s_ref[0, 0, h] + kc * vr
        s_out_ref[0, 0, h] = new
        o_ref[0, 0, h:h + 1, :] = jnp.sum(
            qc * new, axis=0, keepdims=True) * scale


def _walk_pool(kernel, name: str, ins, pool, rows, layer, b: int, ng: int,
               g: int, d_out: int, interpret: bool):
    """What every one-token update of the state pool shares: a grid of
    (head group, batch row); ``ins`` the per-step operands as ``(array,
    BlockSpec)`` (index maps take ``(gi, bi, rows, layer)``); the block
    ``(rows[bi], layer, gi)`` of ``pool`` in, the same block out IN PLACE,
    and an output ``(1, 1, g, d_out)`` a step. ``kernel(rows_ref, layer_ref,
    *in_refs, s_ref, o_ref, s_out_ref)`` is the update rule."""
    f32 = jnp.float32
    block = (1, 1, g) + tuple(pool.shape[3:])

    def state_map(gi, bi, rows_, layer_):
        return (rows_[bi], layer_[0], gi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # rows innermost: consecutive padded rows name the same (scratch)
        # block, which the pipeline then neither fetches nor writes again
        grid=(ng, b),
        in_specs=[spec for _, spec in ins] + [pl.BlockSpec(block, state_map)],
        out_specs=[pl.BlockSpec((1, 1, g, d_out),
                                lambda gi, bi, r, l: (bi, gi, 0, 0)),
                   pl.BlockSpec(block, state_map)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, ng, g, d_out), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is the last operand (after the two prefetched scalars),
        # and it is output 1: the rows not named keep what they held
        input_output_aliases={2 + len(ins): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *[a for a, _ in ins], pool)


def _row_map(gi, bi, rows_, layer_):
    return (bi, gi, 0, 0)


def _head_groups(h: int):
    g = _HEAD_GROUP if h % _HEAD_GROUP == 0 else h
    return g, h // g


def _decode_kernel_call(q, k, v, slopes, pool, rows, layer, scale: float,
                        interpret: bool):
    b, h, d = q.shape
    g, ng = _head_groups(h)
    f32 = jnp.float32

    def columns(a):                          # (B, H, D) -> (B, ng, D, g)
        return jnp.swapaxes(a.astype(f32).reshape(b, ng, g, d), 2, 3)

    lam = jnp.exp(-jnp.asarray(slopes, f32)).reshape(ng, 1, g)
    out, pool = _walk_pool(
        functools.partial(_decode_kernel, group=g, scale=scale),
        "linear_state_decode",
        [(columns(q), pl.BlockSpec((1, 1, d, g), _row_map)),
         (columns(k), pl.BlockSpec((1, 1, d, g), _row_map)),
         (v.astype(f32).reshape(b, ng, g, d),
          pl.BlockSpec((1, 1, g, d), _row_map)),
         (lam, pl.BlockSpec((1, 1, g), lambda gi, bi, r, l: (gi, 0, 0)))],
        pool, rows, layer, b, ng, g, d, interpret)
    return out.reshape(b, h, d), pool


def linear_state_decode(q, k, v, slopes, pool, rows, layer: int,
                        scale: float, impl: str = "kernel",
                        interpret: bool = False):
    """One token a row over the state POOL ``(rows, L_lin, H, D, D)``
    float32: row ``rows[b]`` of layer ``layer`` is updated in place.
    ``q, k, v`` (B, H, D). Returns ``(o (B, H, D) float32, pool')``. Padded
    batch rows name row 0, the scratch row."""
    if impl == "kernel" and (interpret or (
            pool.dtype == jnp.float32 and q.shape[-1] % 128 == 0)):
        return _decode_kernel_call(q, k, v, slopes, pool, rows, layer,
                                   scale, interpret)
    rows = rows.astype(jnp.int32)
    out, new = linear_decode_dense(q, k, v, slopes, pool[rows, layer], scale)
    return out, pool.at[rows, layer].set(new.astype(pool.dtype))


# ---------------------------------------------------------------------------
# the gated delta rule (ISSUE 33)
# ---------------------------------------------------------------------------

@dataclass
class StateDecodeCache(PagedDecodeCache):
    """:class:`PagedDecodeCache` for a model that keeps, beside plain pages,
    a fixed state per slot in one or more parts (ISSUE 33): ``states`` —
    per part a Tensor ``(rows, L_state, *shape)``, row 0 scratch — and
    ``state_rows`` ``(B,)`` int32, each batch row's row of them. The page
    fields are the attention layers' pool(s); a layer that keeps a state
    updates its rows in place and puts the part back with
    ``dataclasses.replace``."""

    states: tuple = ()
    state_rows: object = None


@dataclass
class StatePrefill:
    """What a prefill of such a model reads and leaves, in place of the
    dense stacked cache (Tensors): ``kv`` ``(L_pages, 2, 1, H_kv, max_len,
    D)`` — positions below ``start`` hold the shared prefix, the prefill
    writes ``[start, start + Lp)``; ``states`` per part ``(L_state,
    *shape)``, the state before ``start`` going in and after the last token
    coming out. Coming out only: ``snapshots`` per part ``(n, L_state,
    *shape)``, the state after each whole ``block`` of the run."""

    kv: object
    states: tuple
    snapshots: Optional[tuple] = None


def gated_delta_dense(q, k, v, g, beta, states):
    """One token a row on given states: ``q, k`` (B, H, Dk) (already
    normalised and scaled, one per VALUE head), ``v`` (B, H, Dv), ``g,
    beta`` (B, H), ``states`` (B, H, Dk, Dv) -> ``(o (B, H, Dv) float32,
    states')``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = jnp.exp(g.astype(f32))[..., None, None] * states.astype(f32)
    r = v - jnp.sum(k[..., :, None] * s, axis=-2)
    new = s + k[..., :, None] * (beta.astype(f32)[..., None] * r)[..., None, :]
    return jnp.sum(q[..., :, None] * new, axis=-2), new


def _delta_kernel(rows_ref, layer_ref, qt_ref, kt_ref, v_ref, lam_ref,
                  beta_ref, s_ref, o_ref, s_out_ref, *, group: int):
    """One (head group, batch row), as :func:`_decode_kernel`; ``lam`` and
    ``beta`` are this ROW's, ``(1, 1, 1, group)``. All float32 on the VPU."""
    del rows_ref, layer_ref                  # used by the index maps only
    for h in range(group):
        kc = kt_ref[0, 0][:, h:h + 1]                         # (Dk, 1)
        qc = qt_ref[0, 0][:, h:h + 1]
        vr = v_ref[0, 0, h:h + 1, :]                          # (1, Dv)
        s = lam_ref[0, 0][:, h:h + 1] * s_ref[0, 0, h]
        r = vr - jnp.sum(kc * s, axis=0, keepdims=True)
        new = s + kc * (beta_ref[0, 0][:, h:h + 1] * r)
        s_out_ref[0, 0, h] = new
        o_ref[0, 0, h:h + 1, :] = jnp.sum(qc * new, axis=0, keepdims=True)


def gated_delta_decode(q, k, v, g, beta, pool, rows, layer: int,
                       impl: str = "kernel", interpret: bool = False):
    """One token a row over the state POOL ``(rows, L_state, H, Dk, Dv)``
    float32: row ``rows[b]`` of layer ``layer`` is updated in place. ``q,
    k`` (B, H, Dk), ``v`` (B, H, Dv), ``g, beta`` (B, H). Returns ``(o (B,
    H, Dv) float32, pool')``. Padded batch rows name row 0, the scratch
    row."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    if impl == "kernel" and (interpret or (
            pool.dtype == jnp.float32 and dk % 128 == 0 and dv % 128 == 0)):
        grp, ng = _head_groups(h)

        def columns(a):                      # (B, H, Dk) -> (B, ng, Dk, g)
            return jnp.swapaxes(a.astype(f32).reshape(b, ng, grp, dk), 2, 3)

        def per_row(a):                      # (B, H) -> (B, ng, 1, g)
            return a.astype(f32).reshape(b, ng, 1, grp)

        out, pool = _walk_pool(
            functools.partial(_delta_kernel, group=grp), "gated_delta_decode",
            [(columns(q), pl.BlockSpec((1, 1, dk, grp), _row_map)),
             (columns(k), pl.BlockSpec((1, 1, dk, grp), _row_map)),
             (v.astype(f32).reshape(b, ng, grp, dv),
              pl.BlockSpec((1, 1, grp, dv), _row_map)),
             (per_row(jnp.exp(g.astype(f32))),
              pl.BlockSpec((1, 1, 1, grp), _row_map)),
             (per_row(beta), pl.BlockSpec((1, 1, 1, grp), _row_map))],
            pool, rows, layer, b, ng, grp, dv, interpret)
        return out.reshape(b, h, dv), pool
    rows = rows.astype(jnp.int32)
    out, new = gated_delta_dense(q, k, v, g, beta, pool[rows, layer])
    return out, pool.at[rows, layer].set(new.astype(pool.dtype))


def _conv_kernel(rows_ref, layer_ref, x_ref, w_ref, t_ref, o_ref, t_out_ref,
                 *, taps: int):
    """One batch row: ``x (1, R, C)`` the token's input, ``w (K, R, C)``, the
    tail block ``(1, 1, K - 1, R, C)`` in and out — the oldest input first."""
    del rows_ref, layer_ref
    x = x_ref[0]
    acc = w_ref[taps - 1] * x
    for j in range(taps - 1):
        old = t_ref[0, 0, j]
        acc = acc + w_ref[j] * old
        if j:
            t_out_ref[0, 0, j - 1] = old
    t_out_ref[0, 0, taps - 2] = x
    o_ref[0] = acc * jax.nn.sigmoid(acc)


def conv_tail_decode(x, w, pool, rows, layer: int, impl: str = "kernel",
                     interpret: bool = False):
    """``silu`` of the depthwise causal convolution at one token a row, over
    the tail POOL ``(rows, L_state, K - 1, R, C)`` float32 — the channels
    laid out ``R x C`` so that a row's block is whole tiles —: ``x`` (B, R,
    C) this token's inputs, ``w`` (K, R, C), tap ``K - 1`` the current
    token's. Row ``rows[b]`` of layer ``layer`` is shifted in place.
    Returns ``(y (B, R, C) float32, pool')``."""
    b, r, c = x.shape
    taps = w.shape[0]
    f32 = jnp.float32
    x, w = x.astype(f32), w.astype(f32)
    if impl == "kernel" and taps > 1 and (interpret or (
            pool.dtype == jnp.float32 and c % 128 == 0 and r % 8 == 0)):
        def tail_map(bi, rows_, layer_):
            return (rows_[bi], layer_[0], 0, 0, 0)
        block = (1, 1, taps - 1, r, c)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, r, c), lambda bi, r_, l_: (bi, 0, 0)),
                      pl.BlockSpec((taps, r, c), lambda bi, r_, l_: (0, 0, 0)),
                      pl.BlockSpec(block, tail_map)],
            out_specs=[pl.BlockSpec((1, r, c), lambda bi, r_, l_: (bi, 0, 0)),
                       pl.BlockSpec(block, tail_map)])
        return tuple(pl.pallas_call(
            functools.partial(_conv_kernel, taps=taps), grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((b, r, c), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret, name="gated_delta_decode_conv",
        )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
          x, w, pool))
    rows = rows.astype(jnp.int32)
    seen = jnp.concatenate([pool[rows, layer].astype(f32), x[:, None]], 1)
    return jax.nn.silu(jnp.einsum("bkrc,krc->brc", seen, w)), \
        pool.at[rows, layer].set(seen[:, 1:].astype(pool.dtype))


def chunked_gated_delta_rule(q, k, v, g, beta, state, chunk: int = 64):
    """``q, k`` (T, H, Dk) (normalised and scaled, one per value head), ``v``
    (T, H, Dv), ``g, beta`` (T, H), ``state`` (H, Dk, Dv) float32 (the state
    BEFORE the first token) -> ``(o (T, H, Dv) float32, state after the last
    token)``. ``T`` need not be a multiple of ``chunk``: the padding carries
    no decay and no write strength.

    Within a chunk, with ``G_i`` the decay summed up to token ``i`` and ``A =
    strictly-lower(beta_i k_i . k_j exp(G_i - G_j))``, the corrected values
    of all its tokens solve ``(I + A) U = beta (V - exp(G) K S)``; ``T = (I +
    A)^-1`` comes from forward substitution, row by row."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    c = min(chunk, max(t, 1))
    pad = -t % c
    n = (t + pad) // c

    def chunks(a):                           # (T, H, ...) -> (n, H, c, ...)
        a = jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a.reshape((n, c) + a.shape[1:]), 2, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)              # (n, H, c, D)
    gc = jnp.cumsum(chunks(g), axis=-1)                       # (n, H, c): G_i
    bc = chunks(beta)
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))                      # (n, H, c, c)
    kb = kc * bc[..., None]
    a = -jnp.einsum("nhik,nhjk->nhij", kb, kc, precision=_HI) * decay \
        * jnp.tril(jnp.ones((c, c), f32), -1)

    def substitute(i, m):
        # row i of (I + A)^-1 - I from the rows above it (its entries at
        # and past column i are still zero)
        row = jax.lax.dynamic_slice_in_dim(m, i, 1, axis=-2)
        row = row + jnp.einsum("nhxj,nhjk->nhxk", row, m, precision=_HI)
        return jax.lax.dynamic_update_slice_in_dim(m, row, i, axis=-2)

    inv = jax.lax.fori_loop(1, c, substitute, a) + jnp.eye(c, dtype=f32)
    u = jnp.einsum("nhij,nhjv->nhiv", inv, vc * bc[..., None], precision=_HI)
    w = jnp.einsum("nhij,nhjk->nhik", inv, kb * jnp.exp(gc)[..., None],
                   precision=_HI)
    qk = jnp.einsum("nhik,nhjk->nhij", qc, kc, precision=_HI) * decay

    def body(S, xs):
        q_, k_, u_, w_, qk_, g_ = xs
        new = u_ - jnp.einsum("hik,hkv->hiv", w_, S, precision=_HI)
        out = jnp.einsum("hik,hkv->hiv", q_ * jnp.exp(g_)[..., None], S,
                         precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_, new, precision=_HI)
        last = g_[:, -1]
        S = jnp.exp(last)[:, None, None] * S + jnp.einsum(
            "hjk,hjv->hkv", k_ * jnp.exp(last[:, None] - g_)[..., None], new,
            precision=_HI)
        return S, out

    state, out = jax.lax.scan(body, state.astype(f32),
                              (qc, kc, u, w, qk, gc))
    return jnp.moveaxis(out, 1, 2).reshape(n * c, h, dv)[:t], state
