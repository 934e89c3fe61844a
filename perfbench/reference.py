"""The plain reference: Mistral-7B-v0.3's forward pass in ``jax.numpy``, float32.

No kernels, no cache, no batching; one sequence at a time. Pre-norm decoder
of RMSNorm, rotary embeddings (rotate-half convention, ``rope_theta`` from
the config), grouped-query causal attention, SwiGLU, untied head — v0.3 has
no sliding window. Departures from a textbook forward, both for memory only:
attention is computed over blocks of query rows, and the model's bf16 weights
are upcast one layer at a time, so one layer is held in float32 at once.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul is otherwise done in bf16 passes. The callers jit these
functions whole — nothing here runs eagerly on the chip.

Weights come in a neutral layout the runners fill from the model under test
(matrices stored ``[in, out]``)::

    {"embed": [V, H], "norm": [H], "head": [H, V],
     "layers": [{"ln1", "q", "k", "v", "o", "ln2", "gate", "up", "down"}, ...]}

``layers`` may instead be one such dict of arrays stacked along a leading
layer axis (the scan-over-layers layout of the training model).

Tolerances, and why
-------------------
* ``SERVE_LOGIT_TOL`` — serving compares logits, not tokens: with random
  weights the two largest of 32768 logits lie some hundredths apart (logit
  sigma about 0.5), so a rounding may flip the argmax though nothing is
  wrong. The test is that the reference's logit *of the token the engine
  chose* is within the tolerance of the reference's largest logit at that
  position, teacher-forced over prompt + answer. On the chip, over six seeds,
  the bf16 engine chose the reference's own argmax at 45 to 48 of 48
  positions and the largest gap was 0.0087 (PERF.md): the tolerance only has
  to admit a near-tie. 0.04 is under a tenth of a logit sigma and 4.6 times
  the largest gap seen. A token chosen from a wrong cache row, position or
  page, or from a cache held in fewer bits than the configuration states,
  lands a sizeable part of a sigma away.
* ``TRAIN_LOSS_TOL`` — the system's bf16 AMP loss against the float32 loss
  on the same sequence and weights, relative. Measured on the chip over six
  seeds: 7e-7 to 1.1e-5 of a loss of 10.5 (PERF.md). 2e-4 is twenty times
  the largest, and still two thousandths of a nat: a dropped layer, mask or
  rotary, or a loss reduced in bf16, moves a random model's loss by more
  (one wrong logit row in 4095 moves the mean by 1e-4 of it already).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

SERVE_LOGIT_TOL = 0.04
TRAIN_LOSS_TOL = 2e-4
_Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _rotary(x, theta):
    """x: [T, heads, D]; rotate-half pairs (i, i + D/2)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, q: [T, Hq, D], k/v: [T, Hkv, D]."""
    t, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    cols = jnp.arange(t)
    out = []
    for lo in range(0, t, _Q_BLOCK):
        qb = q[lo:lo + _Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        rows = lo + jnp.arange(qb.shape[0])
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, 0)


def _layer(x, p, cfg):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    t = h.shape[0]
    q = (h @ _f32(p["q"])).reshape(t, -1, hd)
    k = (h @ _f32(p["k"])).reshape(t, -1, hd)
    v = (h @ _f32(p["v"])).reshape(t, -1, hd)
    a = _attention(_rotary(q, theta), _rotary(k, theta), v)
    x = x + a.reshape(t, -1) @ _f32(p["o"])
    h = _rms_norm(x, p["ln2"], eps)
    return x + (jax.nn.silu(h @ _f32(p["gate"])) * (h @ _f32(p["up"]))) \
        @ _f32(p["down"])


def _layers(params):
    layers = params["layers"]
    if isinstance(layers, dict):                 # stacked along axis 0
        n = next(iter(layers.values())).shape[0]
        return [{k: v[i] for k, v in layers.items()} for i in range(n)]
    return layers


def logits(params: Dict, ids, cfg: Dict):
    """ids: [T] int -> float32 logits [T, V]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        for p in _layers(params):
            x = _layer(x, p, cfg)
        x = _rms_norm(x, params["norm"], cfg["rms_norm_eps"])
        return x @ _f32(params["head"])


def loss(params: Dict, ids, cfg: Dict):
    """Mean next-token cross-entropy of one sequence ``ids`` [T]."""
    lg = logits(params, ids, cfg)[:-1]
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], 1))


def chosen_logit_gaps(params: Dict, ids, prompt_len, answer, cfg: Dict):
    """Teacher-forced over prompt + answer: at each answer position, the
    reference's largest logit minus its logit of the token the system chose
    (0 where they agree). ``ids`` [T] holds prompt + answer[:-1], padded to
    any fixed length: attention is causal, so what follows a position never
    reaches it, and one compiled program serves prompts of several lengths
    (``prompt_len`` is a traced scalar). answer: [A] -> float32 [A]."""
    lg = logits(params, ids, cfg)
    rows = jax.lax.dynamic_slice_in_dim(lg, prompt_len - 1, answer.shape[0])
    chosen = jnp.take_along_axis(rows, answer[:, None], 1)[:, 0]
    return jnp.max(rows, -1) - chosen
