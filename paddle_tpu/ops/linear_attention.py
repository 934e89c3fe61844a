"""Lightning (linear) attention with a fixed per-head decay (ISSUE 31):
the recurrence

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

Every product that touches the state runs in float32 at the highest
matmul precision: a state kept or updated in bfloat16 drifts by 2**-9 a
token, which the reference's tolerance is there to catch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lightning_slopes", "chunked_linear_attention",
           "linear_decode_dense", "linear_state_decode"]

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


def _decode_kernel_call(q, k, v, slopes, pool, rows, layer, scale: float,
                        interpret: bool):
    b, h, d = q.shape
    g = _HEAD_GROUP if h % _HEAD_GROUP == 0 else h
    ng = h // g
    f32 = jnp.float32

    def columns(a):                          # (B, H, D) -> (B, ng, D, g)
        return jnp.swapaxes(a.astype(f32).reshape(b, ng, g, d), 2, 3)

    lam = jnp.exp(-jnp.asarray(slopes, f32)).reshape(ng, 1, g)

    def row_map(gi, bi, rows_, layer_):
        return (bi, gi, 0, 0)

    def state_map(gi, bi, rows_, layer_):
        return (rows_[bi], layer_[0], gi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # rows innermost: consecutive padded rows name the same (scratch)
        # block, which the pipeline then neither fetches nor writes again
        grid=(ng, b),
        in_specs=[pl.BlockSpec((1, 1, d, g), row_map),
                  pl.BlockSpec((1, 1, d, g), row_map),
                  pl.BlockSpec((1, 1, g, d), row_map),
                  pl.BlockSpec((1, 1, g), lambda gi, bi, r, l: (gi, 0, 0)),
                  pl.BlockSpec((1, 1, g, d, d), state_map)],
        out_specs=[pl.BlockSpec((1, 1, g, d), row_map),
                   pl.BlockSpec((1, 1, g, d, d), state_map)])
    out, pool = pl.pallas_call(
        functools.partial(_decode_kernel, group=g, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, ng, g, d), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the two prefetched scalars) is the pool, and it
        # is output 1: the rows not named keep what they held
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="linear_state_decode",
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      columns(q), columns(k), v.astype(f32).reshape(b, ng, g, d), lam, pool)
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
