"""The plain reference of ``Mellum2-12B-A2.5B`` (``mellum``): the layer
equations in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.

No kernels, no pages, no cache, no batching; one sequence at a time, and
nothing shared with ``paddle_tpu``. ``N(x) = x * rsqrt(mean(x^2) + eps) * w``.
Layer ``i`` of kind ``layer_types[i]``, input ``x`` [T, E]::

    h = N1(x);  q = h Wq -> [T, H, D];  k = h Wk, v = h Wv -> [T, Hkv, D]
    sliding_attention:  q, k = rope(q, k; rope_parameters["sliding_attention"])
                        keys with 0 <= i - j < sliding_window
    full_attention:     q, k = rope(q, k; rope_parameters["full_attention"])
                        keys with j <= i
    x = x + softmax(q k^T / sqrt(D)) v Wo
    h = N2(x);  p = softmax(h Wr) over all num_experts;  the top
    num_experts_per_tok renormalised to sum 1
    x = x + sum_e p_e (silu(h Wg_e) * (h Wu_e)) Wd_e      # no shared expert

then ``N(x) @ head`` (untied). ``rope`` rotates halves: ``[x1 cos - x2 sin,
x2 cos + x1 sin]`` over ``x[..., :D/2] | x[..., D/2:]`` at angle ``pos *
inv_i``. ``default``: ``inv_i = theta^(-2i/D)``. ``yarn``: ``inv_extra_i =
theta^(-2i/D)``, ``inv_inter_i = inv_extra_i / factor``, ``corr(r) = D
ln(original / (2 pi r)) / (2 ln theta)``, ``low = floor(corr(beta_fast))``,
``high = ceil(corr(beta_slow))``, ``ramp_i = clamp((i - low) / (high - low),
0, 1)``, ``inv_i = inv_inter_i ramp_i + inv_extra_i (1 - ramp_i)``, and
``cos``, ``sin`` times ``attention_factor``. The multi-token-prediction head
is not built (the configuration carries no key of it).

Departures from the textbook forward, for memory and time only: attention
goes over blocks of query rows (a window layer's block reads the keys its
band reaches); an expert is applied to the rows routed to it, found with
``nonzero`` up to ``_capacity`` rows an expert (``overflow`` counts the rows
past it: the check fails on any); weights are upcast where they are used.

Weights come in a neutral layout (matrices ``[in, out]``)::

    {"embed": [V, E], "norm": [E], "head": [E, V],
     "layers": [{"input_norm", "post_norm", "q", "k", "v", "o",
                 "router" [E, n], "gate"/"up" [n, E, F], "down" [n, F, E]}]}

Limits, and why
---------------
The check holds the system to this reference two ways, at the timed sizes
(readings over the seeds and each control's: PERF.md section 2).

**The tokens it chose**, teacher-forced over prompt + answer: at each answer
position the reference's largest logit minus its logit of the token the
engine chose. ``SERVE_MIN_AGREEING_MEL`` is the share of ALL positions at
which the engine must have chosen this reference's own argmax. The largest
gap is read and logged, never a limit: every expert is held, so every
near-tie of the router is one where the bf16 program and this reference
compute different experts, and a flip among a document's earlier tokens
reaches every later position through attention, steady or not
(``router_margin`` is the RELATIVE distance between the k-th and (k+1)-th
probability, the smallest over the layers; ``steady`` at
``ROUTER_MARGIN_MIN_MEL``). Sound seeds read a steady gap up to ~1 logit,
and the precision below bf16 only 2.3-2.9, so no limit has room on both
sides; with each layer's expert choice held to this reference's, bf16
rounding alone moves the logits by under 0.01.

**The keys and values it stored** (``cache_distance``): the pages the engine
holds for the document's last positions — the full-attention pool's, and the
window pool's kept at the document's boundary for later sharers — against
this reference's K and V there, the worst layer's relative distance,
``SERVE_CACHE_TOL_MEL``. Every later token of the document's sessions reads
them; the rotary of each kind is in the keys. The first layer's reads bf16's
own rounding (~0.0024); each later layer adds the router flips among the
document's tokens, so the fourth reads 0.09-0.15 on sound seeds.

``CONTROLS``: ``fp8_weights`` (the weights rounded to float8_e4m3, the
precision below the configuration's bf16) and ``no_yarn`` (the full layer
with the default frequencies and ``attention_factor`` 1). Each limit lies
between the bf16 engine's largest reading over the seeds on the chip and a
control's (PERF.md section 2): the stored K/V 0.148 against
``fp8_weights``' 0.64-0.68 and ``no_yarn``'s 1.23; agreement 21 of 32
against 0-2.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

SERVE_MIN_AGREEING_MEL = 0.5
ROUTER_MARGIN_MIN_MEL = 0.01
SERVE_CACHE_TOL_MEL = 0.3
CONTROLS = ("fp8_weights", "no_yarn")
_Q_BLOCK = 64
_ROWS = 2048        # rows of a layer computed at once


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    """float32 ``x`` rounded to float8_e4m3's grid, as float32: 3 bits of
    mantissa from 2**-6 up, steps of 2**-9 below (its subnormals). Built
    from ``reduce_precision`` and ``round``: a cast to float8 and back is a
    pair of converts that XLA may remove on a TPU."""
    x = _f32(x)
    return jnp.where(jnp.abs(x) >= 2.0 ** -6,
                     jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=3),
                     jnp.round(x * 512.0) / 512.0)


def _w(x, cfg: Dict):
    return _fp8(x) if cfg.get("control") == "fp8_weights" else _f32(x)


def steady(margin):
    return margin >= ROUTER_MARGIN_MIN_MEL


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(w)


def _blocks(fn, *arrays, rows: int = _ROWS):
    """``fn`` over blocks of rows (each array [T, ...]), concatenated."""
    t = arrays[0].shape[0]
    full = t // rows
    if full <= 1:
        return fn(*arrays)
    head = jax.lax.map(lambda xs: fn(*xs), tuple(
        a[:full * rows].reshape((full, rows) + a.shape[1:]) for a in arrays))
    head = head.reshape((full * rows,) + head.shape[2:])
    if full * rows == t:
        return head
    return jnp.concatenate([head, fn(*(a[full * rows:] for a in arrays))])


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def frequencies(rope: Dict, d: int):
    """``(inv [D/2] float32, attention factor)`` of one ``rope_parameters``
    entry, as the module docstring writes them."""
    theta = float(rope["rope_theta"])
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rope.get("rope_type", "default") == "default":
        return jnp.asarray(extra, jnp.float32), 1.0
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def corr(r):
        return d * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)
    return jnp.asarray(inv, jnp.float32), float(rope["attention_factor"])


def _rope_of(kind: str, cfg: Dict):
    rope = cfg["rope_parameters"][kind]
    if kind == "full_attention" and cfg.get("control") == "no_yarn":
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    return rope


def _rotary(x, rope: Dict):
    """x: [T, heads, D] at positions 0..T-1."""
    t, _, d = x.shape
    inv, factor = frequencies(rope, d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """Causal softmax attention (within ``window`` if given), q: [T, Hq, D],
    k/v: [T, Hkv, D], one block of query rows at a time; a window layer's
    block reads only the ``window + _Q_BLOCK`` keys before its last row."""
    t, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pad = -t % _Q_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _Q_BLOCK, hq, d)
    span = t + pad if window is None else window + _Q_BLOCK
    front = 0 if window is None else window
    kp = jnp.pad(k, ((front, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((front, pad), (0, 0), (0, 0)))

    def block(args):
        qb, lo = args
        first = 0 if window is None else lo       # in kp's rows
        kb = jax.lax.dynamic_slice_in_dim(kp, first, span)
        vb = jax.lax.dynamic_slice_in_dim(vp, first, span)
        cols = first - front + jnp.arange(span)   # positions
        rows = lo + jnp.arange(_Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, kb) / jnp.sqrt(jnp.float32(d))
        keep = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0)
        if window is not None:
            keep &= rows[:, None] - cols[None, :] < window
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vb)

    out = jax.lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * _Q_BLOCK))
    return out.reshape(-1, hq, d)[:t]


def attention(h, p, cfg: Dict, kind: str):
    """-> (the layer's attention output [T, E], k and v [T, Hkv, D] as the
    cache would hold them)."""
    t, d = h.shape[0], cfg["head_dim"]
    q = (h @ _w(p["q"], cfg)).reshape(t, -1, d)
    k = (h @ _w(p["k"], cfg)).reshape(t, -1, d)
    v = (h @ _w(p["v"], cfg)).reshape(t, -1, d)
    rope = _rope_of(kind, cfg)
    q, k = _rotary(q, rope), _rotary(k, rope)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    return _attention(q, k, v, window).reshape(t, -1) @ _w(p["o"], cfg), k, v


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _scores(h, router, cfg: Dict):
    return jax.nn.softmax(h @ _w(router, cfg), axis=-1)


def routing(h, router, cfg: Dict):
    """-> (indices [T, k], weights [T, k]) over all experts, float32."""
    top_v, top_i = jax.lax.top_k(_scores(h, router, cfg),
                                 cfg["num_experts_per_tok"])
    return top_i, top_v / jnp.sum(top_v, -1, keepdims=True)


def router_margin(h, router, cfg: Dict):
    """[T]: how far the k-th probability lies above the (k+1)-th, relative
    to it (every expert is held, so every near-tie counts)."""
    k = cfg["num_experts_per_tok"]
    top_v, _ = jax.lax.top_k(_scores(h, router, cfg), k + 1)
    return (top_v[:, k - 1] - top_v[:, k]) / top_v[:, k - 1]


def _swiglu(h, gate, up, down, cfg: Dict):
    return (jax.nn.silu(h @ _w(gate, cfg)) * (h @ _w(up, cfg))) @ _w(down, cfg)


def _capacity(t: int) -> int:
    """Rows an expert is applied to at once: every row. An expert of 64
    chosen 8 at a time sees an eighth of them on average, but the
    benchmark's random router has sent more than half of a 34k-token prompt
    to one expert, and a check that fails on its own capacity measures
    nothing."""
    return t


def moe(h, p, cfg: Dict):
    """-> (routed over every expert [T, E], rows routed to an expert past
    ``_capacity``: 0 or the result is wrong)."""
    t = h.shape[0]
    cap = _capacity(t)
    top_i, w = routing(h, p["router"], cfg)
    hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])

    def expert(carry, xs):
        out, over = carry
        e, gate, up, down = xs
        w_e = jnp.sum(jnp.where(top_i == e, w, 0.0), -1)           # [T]
        routed = jnp.any(top_i == e, -1)
        (rows,) = jnp.nonzero(routed, size=cap, fill_value=t)
        y = _swiglu(jnp.take(hp, rows, axis=0), gate, up, down, cfg)
        y = y * jnp.take(jnp.concatenate([w_e, jnp.zeros((1,))]), rows)[:, None]
        out = out.at[rows].add(y, mode="drop")
        return (out, over + jnp.maximum(jnp.sum(routed) - cap, 0)), None

    (out, over), _ = jax.lax.scan(
        expert, (jnp.zeros_like(h), jnp.int32(0)),
        (jnp.arange(p["gate"].shape[0], dtype=jnp.int32), p["gate"],
         p["up"], p["down"]))
    return out, over


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _forward(params: Dict, ids, cfg: Dict, first_row, rows, kv_from,
             kv_rows: int):
    """-> (logits of ``rows`` positions from ``first_row`` on, their smallest
    router margin over the layers, every layer's K and V [L, 2, kv_rows,
    Hkv, D] at positions ``kv_from ..``, rows past an expert's capacity)."""
    def cut(a):
        return jax.lax.dynamic_slice_in_dim(a, first_row, rows)

    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        margin = jnp.full((rows,), jnp.inf)
        kvs, over = [], jnp.int32(0)
        for p, kind in zip(params["layers"], cfg["layer_types"]):
            h = _norm(x, p["input_norm"], eps)
            out, k, v = attention(h, p, cfg, kind)
            kvs.append(jnp.stack([
                jax.lax.dynamic_slice_in_dim(a, kv_from, kv_rows)
                for a in (k, v)]))
            x = x + out
            h = _norm(x, p["post_norm"], eps)
            margin = jnp.minimum(margin, router_margin(cut(h), p["router"],
                                                       cfg))
            out, n = moe(h, p, cfg)
            x, over = x + out, over + n
        x = _norm(cut(x), params["norm"], eps)
        return x @ _w(params["head"], cfg), margin, jnp.stack(kvs), over


def logits(params: Dict, ids, cfg: Dict):
    """ids: [T] int -> float32 logits [T, V]."""
    t = ids.shape[0]
    return _forward(params, ids, cfg, 0, t, 0, 1)[0]


def answer_rows(params: Dict, ids, prompt_len, answer, cfg: Dict,
                kv_from=0, kv_rows: int = 1) -> Dict:
    """Teacher-forced over prompt + answer, everything the check compares at
    the ``A`` answer positions: ``gap`` [A] (the largest logit minus the
    logit of the token the system chose; NaN if an expert overflowed),
    ``margin`` [A], and ``kv`` [L, 2, kv_rows, Hkv, D], every layer's K and V
    at positions ``kv_from ..``."""
    rows, margin, kv, over = _forward(params, ids, cfg, prompt_len - 1,
                                      answer.shape[0], kv_from, kv_rows)
    took = jnp.take_along_axis(rows, answer[:, None], 1)[:, 0]
    gap = jnp.where(over > 0, jnp.nan, jnp.max(rows, -1) - took)
    return {"gap": gap, "margin": margin, "kv": kv}


def cache_distance(kept, want) -> np.ndarray:
    """What a system stored ``kept`` [L, 2, R, Hkv, D] against this
    reference's ``want``, on the host: [L, 2], each layer's K and V relative
    Frobenius distance."""
    kept, want = np.asarray(kept, np.float64), np.asarray(want, np.float64)
    axes = (2, 3, 4)
    return np.sqrt(np.sum(np.square(kept - want), axes)) / np.maximum(
        np.sqrt(np.sum(np.square(want), axes)), 1e-30)


_LAYER_KEYS = {
    "input_norm": "input_norm", "post_norm": "post_norm", "q": "q_proj",
    "k": "k_proj", "v": "v_proj", "o": "o_proj", "router": "moe.router",
    "gate": "moe.w_gate", "up": "moe.w_up", "down": "moe.w_down"}


def params_of(model) -> Dict:
    """``MellumForCausalLM``'s weights, as they are on the device, in this
    file's layout — arrays are shared, not copied."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    return {"embed": sd["embed_tokens"], "norm": sd["norm"],
            "head": sd["lm_head"],
            "layers": [{short: sd[f"layers.{i}.{name}"]
                        for short, name in _LAYER_KEYS.items()}
                       for i in range(len(model.layers))]}
