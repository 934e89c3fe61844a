"""Fused int8-state AdamW update as ONE Pallas kernel per parameter.

Why this exists (round 5): the chunked XLA formulation of the int8 update
(`optimizer._adam_q8_update`) runs ~1000 dynamic-slice fusions back-to-back
per giant scan-stacked parameter — TPUs execute fusions sequentially, so
the serialized tail cost (~0.19 s/step at 2.07B params, ~8x over the HBM
floor of its ~10 B/param traffic) cannot be recovered by unrolling or
cross-param windows at the HLO level. The Pallas kernel streams the whole
parameter once: the grid walks (G, 2048)-block tiles with double-buffered
DMA, all fp32 intermediates live in VMEM (zero HBM transients — the very
thing the chunking existed to bound), and the five state buffers update
in place via input_output_aliases.

Reference parity surface: the bitsandbytes-style 8-bit optimizer layout
(1 byte/element + 4 bytes/block scale) recorded in SURVEY §2.1 "fused
kernels" (upstream: paddle/phi/kernels/gpu/fused_adam_kernel.cu and the
multi_tensor_adam family); the sqrt-space second moment is this repo's
round-4 finding (linear int8 of v explodes training).

Layout contract (matches `optimizer._q8_quantize`; since PR 32 the operands
keep the PARAMETER's layout instead of a flat one):
  base     : param dtype (R, C)      a 2-D view of the param/master whose
                                     rows hold whole quantization blocks:
                                     C = k * 2048
  grad     : any float   (R, C)
  m_q, v_q : int8        (R, C)      v_q stores quantized sqrt(v)
  m_s, v_s : fp32        (R, k)      per-block absmax/127 scales
Block b of the flattened parameter is lanes [c*2048, (c+1)*2048) of row r
with b = r*k + c, so (R, C) / (R, k) hold the same blocks in the same order
as the flat (nb, 2048) / (nb,) form and a reshape between the two is exact.
The optimizer hands in a parameter of ndim >= 2 whose last dimension is a
multiple of 2048 as `x.reshape(prod(shape[:-1]), shape[-1])` — a merge of
leading dimensions, free in the chip's tiled layout (tiles cover the last
two dimensions), where `x.reshape(nb, 2048)` changes the minor dimension
and costs a parameter-sized copy each for base in, grad in and base out.
Everything else with n % 2048 == 0 comes as (nb, 2048) with k = 1: the
same kernel, the block geometry read off the operand. Ragged parameters
take the chunked XLA path (they are small, so their cost is noise).

The grid walks (row group, column block): each step sees one (G, 2048)
tile of the four big operands — the tile the flat form always had — and
the whole (G, k) rows of the two scale arrays, which stay resident while
the column axis advances (a (G, 1) block of an (R, k) array is not a legal
TPU block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 2048      # quantization block (elements) — fixed by the q8 layout
# blocks per grid step: the rows of a (G, 2048) tile. 256 (0.5M elements)
# put the non-stochastic-rounding kernel at 16.70M of scoped VMEM against
# the 16.00M default on a TPU v5e (libtpu 0.0.34: "exceeded scoped vmem
# limit by 720.0K"); 128 halves it
_TILE_BLOCKS = 128


def _kernel(sc_ref, seed_ref, mq_ref, ms_ref, vq_ref, vs_ref, base_ref,
            g_ref, mq_o, ms_o, vq_o, vs_o, base_o, *, use_sr, has_wd,
            out_dtype):
    lr, wd, c1, c2, eps, b1, b2 = (sc_ref[i] for i in range(7))
    # this step's column of the resident (G, k) scale rows, picked and put
    # back with a lane mask (a dynamic lane index is no Mosaic load/store)
    j = pl.program_id(1)
    mine = jax.lax.broadcasted_iota(jnp.int32, ms_ref.shape, 1) == j
    column = lambda ref: jnp.sum(jnp.where(mine, ref[:], 0.0), axis=1,
                                 keepdims=True)
    g32 = g_ref[:].astype(jnp.float32)
    m32 = mq_ref[:].astype(jnp.float32) * column(ms_ref)
    sv = vq_ref[:].astype(jnp.float32) * column(vs_ref)
    v32 = sv * sv
    nm = b1 * m32 + (1.0 - b1) * g32
    nv = b2 * v32 + (1.0 - b2) * g32 * g32

    # requantize m (linear) and v (sqrt space) — same rule as _q8_quantize
    msc = jnp.max(jnp.abs(nm), axis=1, keepdims=True) / 127.0
    msc = jnp.where(msc == 0.0, 1.0, msc)
    mq_o[:] = jnp.clip(jnp.round(nm / msc), -127, 127).astype(jnp.int8)
    ms_o[:] = jnp.where(mine, msc, ms_o[:])
    sq = jnp.sqrt(nv)
    vsc = jnp.max(jnp.abs(sq), axis=1, keepdims=True) / 127.0
    vsc = jnp.where(vsc == 0.0, 1.0, vsc)
    vq_o[:] = jnp.clip(jnp.round(sq / vsc), -127, 127).astype(jnp.int8)
    vs_o[:] = jnp.where(mine, vsc, vs_o[:])

    upd = base_ref[:].astype(jnp.float32)
    if has_wd:
        upd = upd * (1.0 - lr * wd)
    upd = upd - lr * (nm / c1) / (jnp.sqrt(nv / c2) + eps)
    if use_sr:
        # stochastic f32->bf16 rounding, per-tile seeded (unbiased: adds
        # uniform low mantissa bits then truncates — optimizer.
        # _stochastic_round_bf16's rule with the on-core PRNG)
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0) * pl.num_programs(1)
                        + j)
        bits = jax.lax.bitcast_convert_type(upd, jnp.uint32)
        rnd = pltpu.prng_random_bits(upd.shape).astype(jnp.uint32) \
            & jnp.uint32(0xFFFF)
        rounded = (bits + rnd) & jnp.uint32(0xFFFF0000)
        out = jax.lax.bitcast_convert_type(rounded, jnp.float32)
        out = jnp.where(jnp.isfinite(upd), out, upd)
        base_o[:] = out.astype(jnp.bfloat16)
    else:
        base_o[:] = upd.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("use_sr", "has_wd",
                                             "interpret"))
def q8_adam_update(m_q, m_s, v_q, v_s, base, grad, scalars, seed, *,
                   use_sr: bool, has_wd: bool, interpret: bool = False):
    """One-kernel in-place int8 AdamW step.

    scalars: (7,) fp32 — lr_eff, weight_decay, c1 (=1-b1^t), c2 (=1-b2^t),
    epsilon, beta1, beta2. seed: (1,) int32 (ignored unless use_sr).
    Returns (m_q', m_s', v_q', v_s', base') aliased onto the inputs."""
    rows, k = m_s.shape
    g = min(_TILE_BLOCKS, rows)
    grid = (pl.cdiv(rows, g), k)
    tile = pl.BlockSpec((g, _BLOCK), lambda i, j: (i, j))
    scale_rows = pl.BlockSpec((g, k), lambda i, j: (i, 0))
    const = lambda i, j: (0,)
    out_dtype = base.dtype
    kern = functools.partial(_kernel, use_sr=use_sr, has_wd=has_wd,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((7,), const, memory_space=pltpu.SMEM),
            pl.BlockSpec((1,), const, memory_space=pltpu.SMEM),
            tile, scale_rows, tile, scale_rows, tile, tile,
        ],
        out_specs=[tile, scale_rows, tile, scale_rows, tile],
        out_shape=[
            jax.ShapeDtypeStruct(m_q.shape, jnp.int8),
            jax.ShapeDtypeStruct(m_s.shape, jnp.float32),
            jax.ShapeDtypeStruct(v_q.shape, jnp.int8),
            jax.ShapeDtypeStruct(v_s.shape, jnp.float32),
            jax.ShapeDtypeStruct(base.shape, out_dtype),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        interpret=interpret, name="q8_adam_update",
    )(scalars, seed, m_q, m_s, v_q, v_s, base, grad)
