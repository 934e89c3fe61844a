"""The plain reference of ``Qwen3-Next-80B-A3B`` (``qwen3_next``): the layer
equations of ISSUE 33 in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.

No kernels, no pages, no cache, no state pool, no batching, no chunked form;
one sequence at a time, and nothing shared with ``paddle_tpu``. ``N(x) = x *
rsqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred weight). Published layer
``i`` is full attention iff ``(i + 1) % full_attention_interval == 0``, else
Gated DeltaNet; every layer is::

    x = x + mixer(N1(x));   x = x + moe(N2(x))

then ``N(x) @ head`` (untied).

*Gated attention.* ``[q | gate] = h Wq`` as [T, H, 2 D] split per head, ``k, v
= h Wk, h Wv`` [T, Hkv, D]; ``q, k = Nq(q), Nk(k)`` over the head; rotary
(halves rotated, theta ``rope_theta``) on the first ``partial_rotary_factor *
D`` dimensions; causal softmax of ``q k^T / sqrt(D)``; ``(attn *
sigmoid(gate)) Wo``.

*Gated DeltaNet.* ``[q | k | v | z] = h Wqkvz`` (Hk x Dk, Hk x Dk, Hv x Dv, Hv
x Dv), ``[b | a] = h Wba`` (Hv, Hv); ``[q | k | v] = silu(conv([q | k | v]))``,
a depthwise causal convolution of ``linear_conv_kernel_dim`` taps without
bias (tap ``j`` of channel ``c`` weighs the input ``K - 1 - j`` tokens back);
``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q, k``
L2-normalised per head (``x * rsqrt(sum(x^2) + 1e-6)``), ``q`` divided by
``sqrt(Dk)``; key head ``h // (Hv / Hk)`` serves value head ``h``. Then TOKEN
BY TOKEN per value head, state ``S`` [Dk, Dv]::

    S = exp(g_t) S;  r = v_t - S^T k_t;  S = S + k_t (beta_t r)^T;  o_t = S^T q_t

``y = rmsnorm(o_t; w_o, eps) * silu(z_t)`` per head (the norm first, its
weight plain, then the gate), ``out = y Wout``.

*Experts.* ``p = softmax(h Wr)`` over all ``router_width`` experts, the
``num_experts_per_tok`` largest renormalised to sum 1; expert ``e`` is
``(silu(h Wg) * h Wu) Wd``; ``moe(h) = sum p_e expert_e(h) + sigmoid(h . ws)
shared(h)``. The share: ``experts`` lists the global indices of the routed
experts whose weights are given; routing and the renormalisation always run
over all of them. The vocabulary given is the slice held.

Not built: the multi-token-prediction module (the catalog's ``config``
carries no key of it). Departures from the textbook forward, for memory and
time only: attention goes over blocks of query rows; an expert is applied to
the rows routed to it, found with ``nonzero`` up to ``_capacity`` rows an
expert (``overflow`` counts the rows past it: the check fails on any); and
weights are upcast where they are used.

``cfg`` is the configuration file's dictionary plus ``layers_run`` (the
published indices of the layers given), ``router_width`` (the published
``num_experts``) and ``experts`` (held here). Weights come in a neutral
layout (matrices ``[in, out]``)::

    {"embed": [V, E], "norm": [E], "head": [E, V],
     "layers": [{"input_norm", "post_norm", "router" [E, R], "gate"/"up" [n, E,
                 F], "down" [n, F, E], "shared_gate"/"shared_up" [E, Fs],
                 "shared_down" [Fs, E], "shared_score" [E, 1], and
                 full:  "q" [E, H 2 D], "k", "v", "q_norm", "k_norm", "o"
                 delta: "qkvz", "ba", "conv" [C, K], "A_log", "dt_bias",
                        "o_norm" [Dv], "out"}]}

Limits, and why
---------------
The check holds the system to this reference two ways, at the timed sizes
(readings over the seeds and each control's: PERF.md section 2).

**The tokens it chose**, teacher-forced over prompt + answer as
``reference_cohere2_moe``'s: at each answer position the reference's largest
logit minus its logit of the token the engine chose. The discrete step here
is the router's: where a token's k-th and (k+1)-th score nearly tie and one
of the two experts is held here, the bf16 program and this reference compute
different experts. ``router_margin`` is their RELATIVE distance (``(p_k -
p_k+1) / p_k``; ``inf`` where neither is held), the smallest over the layers:

* a position is STEADY when it is at least ``ROUTER_MARGIN_MIN_Q3N``;
* ``SERVE_LOGIT_TOL_Q3N`` bounds the largest gap over the steady positions
  (a steady position still answers to every flip among the tokens before
  it, so the sound engine reads up to 0.17 where Command A+'s read 0.02);
  ``SERVE_MIN_STEADY_Q3N``: a check with fewer saw too little; over ALL
  positions ``SERVE_MIN_AGREEING_Q3N`` is the share at which the engine chose
  the reference's own argmax. The control these are set against is
  ``fp8_weights``.

**The state and the tail it kept** (``state_distance``): the float32 delta
state and convolution tail the engine files at a prefix boundary against this
reference's after the same tokens.

* ``SERVE_STATE_TOL_Q3N`` bounds the worst value head's relative distance
  over all layers (a wrong snapshot, row or decay) and ``SERVE_TAIL_TOL_Q3N``
  the tail's (a wrong position or channel). Both read what the residual
  stream carries by then — bf16 rounding and every router flip among the
  brief's 8192 tokens, doubling about every layer (0.4% in the first delta
  layer, 6-20% in the sixth, by the seed) — so they are loose;
* ``SERVE_STATE_FIRST_TOL_Q3N`` bounds the worst head of the FIRST delta
  layer, whose input is the embedding itself: there the distance is the
  layer's own arithmetic (0.4-0.6%), and an update without its correction
  (``control="no_delta"`` drops ``- S^T k``) moves the heads that forget
  slowly by 3-17% and has to exceed it;
* ``SERVE_STATE_ROUNDING_TOL_Q3N`` bounds what the state's own rounding adds.
  The activations' bf16 noise moves every head by more than a bfloat16 state
  would, so no distance tells the two apart; their SHAPE does. A head whose
  decay forgets within a few tokens (the quarter with the largest ``A``)
  holds a state of rank <= 3 to float32's last bit, and so does the
  reference; their difference has rank <= ``_ROUNDING_RANK`` whatever the
  noise in ``k``, ``v``, ``beta`` and ``g``. A state rounded to bfloat16
  after every token (``control="bf16_state"``) adds white noise of 2**-9 an
  entry, which lies outside any such subspace: ``rounding`` is the energy of
  the difference past its ``_ROUNDING_RANK`` largest singular values,
  relative to the reference's norm, the largest over those heads.

``CONTROLS``: ``fp8_weights``, ``bf16_state`` (the reference a precision
lower) and ``no_delta`` (part of the mathematics left out).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

SERVE_LOGIT_TOL_Q3N = 0.4
SERVE_MIN_AGREEING_Q3N = 0.6
SERVE_MIN_STEADY_Q3N = 8
ROUTER_MARGIN_MIN_Q3N = 0.01
SERVE_STATE_TOL_Q3N = 0.45
SERVE_STATE_FIRST_TOL_Q3N = 0.015
SERVE_TAIL_TOL_Q3N = 0.25
SERVE_STATE_ROUNDING_TOL_Q3N = 1e-5
CONTROLS = ("fp8_weights", "bf16_state", "no_delta")
_ROUNDING_RANK = 8
_Q_BLOCK = 64
_ROWS = 2048        # rows of a layer computed at once


def _f32(x):
    return x.astype(jnp.float32)


def _bf16(x):
    """float32 ``x`` rounded to bfloat16's 8 bits of mantissa, as float32.
    ``reduce_precision`` and not a cast there and back: XLA removes such a
    pair of converts on a TPU."""
    return jax.lax.reduce_precision(_f32(x), exponent_bits=8,
                                    mantissa_bits=7)


def _fp8(x):
    """float32 ``x`` rounded to float8_e4m3's grid, as float32: 3 bits of
    mantissa from 2**-6 up, steps of 2**-9 below (its subnormals). Built
    from ``reduce_precision`` and ``round``: a cast to float8 and back is a
    pair of converts that XLA may remove on a TPU (this PR's first chip run
    read the first layer's state to five digits as the sound reference's)."""
    x = _f32(x)
    return jnp.where(jnp.abs(x) >= 2.0 ** -6,
                     jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=3),
                     jnp.round(x * 512.0) / 512.0)


def _w(x, cfg: Dict):
    return _fp8(x) if cfg.get("control") == "fp8_weights" else _f32(x)


def steady(margin):
    return margin >= ROUTER_MARGIN_MIN_Q3N


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + _f32(w))


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


def is_full(layer: int, cfg: Dict) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


# ---------------------------------------------------------------------------
# gated attention
# ---------------------------------------------------------------------------

def _rotary(x, cfg: Dict):
    """x: [T, heads, D] at positions 0..T-1: the first ``partial_rotary_factor
    * D`` dimensions rotated by halves, the rest passed through."""
    t, _, d = x.shape
    r = int(d * cfg["partial_rotary_factor"])
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, r, 2, dtype=jnp.float32)
                                       / r))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(q, k, v):
    """Causal softmax attention, q: [T, Hq, D], k/v: [T, Hkv, D], one block
    of query rows at a time."""
    t, hq, d = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    cols = jnp.arange(t)
    pad = -t % _Q_BLOCK
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _Q_BLOCK, hq, d)

    def block(args):
        qb, lo = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        rows = lo + jnp.arange(_Q_BLOCK)
        s = jnp.where((cols[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * _Q_BLOCK))
    return out.reshape(-1, hq, d)[:t]


def attention_mixer(h, p, cfg: Dict):
    t, d = h.shape[0], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qg = (h @ _w(p["q"], cfg)).reshape(t, -1, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ _w(p["k"], cfg)).reshape(t, -1, d)
    v = (h @ _w(p["v"], cfg)).reshape(t, -1, d)
    q = _rotary(_norm(q, p["q_norm"], eps), cfg)
    k = _rotary(_norm(k, p["k_norm"], eps), cfg)
    attn = _attention(q, k, v)
    return (attn * jax.nn.sigmoid(gate)).reshape(t, -1) @ _w(p["o"], cfg)


# ---------------------------------------------------------------------------
# Gated DeltaNet: the recurrence, token by token
# ---------------------------------------------------------------------------

def delta_dims(cfg: Dict):
    """(Hk, Dk, Hv, Dv, channels of the convolution, taps)."""
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    return hk, dk, hv, dv, 2 * hk * dk + hv * dv, cfg["linear_conv_kernel_dim"]


def delta_mixer(h, p, cfg: Dict, state_at: int = 0):
    """-> (out [T, E], the state after the first ``state_at`` tokens [Hv, Dk,
    Dv] and the convolution's tail there [K - 1, C]: its inputs at positions
    ``state_at - K + 1 .. state_at - 1``, zeros before the sequence)."""
    t = h.shape[0]
    hk, dk, hv, dv, c, taps = delta_dims(cfg)
    rep = hv // hk
    mixed = h @ _w(p["qkvz"], cfg)
    qkv, z = mixed[:, :c], mixed[:, c:].reshape(t, hv, dv)
    ba = h @ _w(p["ba"], cfg)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[:, hv:] + _f32(p["dt_bias"]))
    padded = jnp.concatenate([jnp.zeros((taps - 1, c), jnp.float32), qkv])
    tail = jax.lax.dynamic_slice_in_dim(padded, state_at, taps - 1)
    conv = sum(padded[j:j + t] * _f32(p["conv"])[:, j] for j in range(taps))
    conv = jax.nn.silu(conv)
    q = conv[:, :hk * dk].reshape(t, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    q = jnp.repeat(l2(q) / jnp.sqrt(jnp.float32(dk)), rep, axis=1)
    k = jnp.repeat(l2(k), rep, axis=1)
    control = cfg.get("control")

    def token(S, xs):
        qt, kt, vt, gt, bt = xs                      # [Hv, D], [Hv]
        S = jnp.exp(gt)[:, None, None] * S
        r = vt if control == "no_delta" else \
            vt - jnp.einsum("hkv,hk->hv", S, kt)
        S = S + kt[:, :, None] * (bt[:, None] * r)[:, None, :]
        if control == "bf16_state":
            S = _bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    def run(S, lo, n):
        return jax.lax.scan(token, S, tuple(
            a[lo:lo + n] for a in (q, k, v, g, beta)))

    kept, before = run(jnp.zeros((hv, dk, dv), jnp.float32), 0, state_at)
    _, after = run(kept, state_at, t - state_at)
    o = jnp.concatenate([before, after])
    y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * _f32(p["o_norm"])
    y = y * jax.nn.silu(z)
    return y.reshape(t, hv * dv) @ _w(p["out"], cfg), kept, tail


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
    """[T]: how far the k-th score lies above the (k+1)-th, relative to it,
    or infinity where neither of the two experts is held here."""
    k = cfg["num_experts_per_tok"]
    top_v, top_i = jax.lax.top_k(_scores(h, router, cfg), k + 1)
    held = jnp.isin(top_i[:, k - 1:], jnp.asarray(list(cfg["experts"]),
                                                  jnp.int32))
    return jnp.where(jnp.any(held, -1),
                     (top_v[:, k - 1] - top_v[:, k]) / top_v[:, k - 1],
                     jnp.inf)


def _swiglu(h, gate, up, down, cfg: Dict):
    return (jax.nn.silu(h @ _w(gate, cfg)) * (h @ _w(up, cfg))) @ _w(down, cfg)


def _capacity(t: int) -> int:
    """Rows an expert is applied to at once: every row of a short sequence,
    a quarter of a long one (an expert of 512 chosen ten at a time sees a
    fiftieth)."""
    return t if t <= 1024 else t // 4


def moe(h, p, cfg: Dict):
    """-> (routed over the experts given + the gated shared expert [T, E],
    rows routed to an expert past ``_capacity``: 0 or the result is wrong)."""
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
        (jnp.asarray(list(cfg["experts"]), jnp.int32), p["gate"], p["up"],
         p["down"]))
    shared = _blocks(lambda hb: jax.nn.sigmoid(hb @ _w(p["shared_score"], cfg))
                     * _swiglu(hb, p["shared_gate"], p["shared_up"],
                               p["shared_down"], cfg), h)
    return out + shared, over


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _forward(params: Dict, ids, cfg: Dict, first_row, rows,
             state_at: int = 0):
    """-> (logits of ``rows`` positions from ``first_row`` on (all if
    ``rows`` is None), their smallest router margin over the layers, every
    delta layer's state [L, Hv, Dk, Dv] and tail [L, K - 1, C] after
    ``state_at`` tokens, rows past an expert's capacity)."""
    def cut(a):
        return a if rows is None else \
            jax.lax.dynamic_slice_in_dim(a, first_row, rows)

    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        margin = jnp.full(cut(x).shape[:1], jnp.inf)
        states, tails, over = [], [], jnp.int32(0)
        for p, layer in zip(params["layers"], cfg["layers_run"]):
            h = _norm(x, p["input_norm"], eps)
            if is_full(layer, cfg):
                x = x + attention_mixer(h, p, cfg)
            else:
                out, kept, tail = delta_mixer(h, p, cfg, state_at)
                x = x + out
                states.append(kept)
                tails.append(tail)
            h = _norm(x, p["post_norm"], eps)
            margin = jnp.minimum(margin, router_margin(cut(h), p["router"],
                                                       cfg))
            out, n = moe(h, p, cfg)
            x, over = x + out, over + n
        x = _norm(cut(x), params["norm"], eps)
        hk, dk, hv, dv, c, taps = delta_dims(cfg)
        return x @ _w(params["head"], cfg), margin, \
            jnp.stack(states) if states else jnp.zeros((0, hv, dk, dv)), \
            jnp.stack(tails) if tails else jnp.zeros((0, taps - 1, c)), over


def logits(params: Dict, ids, cfg: Dict, first_row=0, rows=None):
    """ids: [T] int (indices into the vocabulary rows held) -> float32 logits
    [T, V_held], or of ``rows`` positions from ``first_row`` on."""
    return _forward(params, ids, cfg, first_row, rows)[0]


def answer_rows(params: Dict, ids, prompt_len, answer, cfg: Dict,
                state_at: int = 0) -> Dict:
    """Teacher-forced over prompt + answer, everything the check compares at
    the ``A`` answer positions: ``gap`` [A] (the largest logit minus the
    logit of the token the system chose; NaN if an expert overflowed),
    ``margin`` [A], ``logits`` [A, V], and ``states`` / ``tails``, every
    delta layer's after the first ``state_at`` tokens."""
    rows, margin, states, tails, over = _forward(
        params, ids, cfg, prompt_len - 1, answer.shape[0], state_at)
    took = jnp.take_along_axis(rows, answer[:, None], 1)[:, 0]
    gap = jnp.where(over > 0, jnp.nan, jnp.max(rows, -1) - took)
    return {"gap": gap, "margin": margin, "logits": rows, "states": states,
            "tails": tails}


def state_distance(kept, want, a_log):
    """How far the delta states a system kept ``kept`` [L, Hv, Dk, Dv] lie
    from this reference's ``want``, on the host: ``(by_head [L, Hv], worst,
    rounding)`` — each head's Frobenius distance relative to the reference's
    norm, the largest of them, and the module docstring's ``rounding``: over
    the quarter of each layer's heads with the largest ``A`` (``a_log`` [L,
    Hv]), the energy of the difference past its ``_ROUNDING_RANK`` largest
    singular values, relative to the reference's norm."""
    kept, want = np.asarray(kept, np.float64), np.asarray(want, np.float64)
    norm = np.maximum(np.sqrt(np.sum(np.square(want), (-1, -2))), 1e-30)
    by_head = np.sqrt(np.sum(np.square(kept - want), (-1, -2))) / norm
    n = max(1, kept.shape[1] // 4)
    rounding = 0.0
    for layer, a in enumerate(np.asarray(a_log, np.float64)):
        for h in np.argsort(a)[-n:]:
            s = np.linalg.svd(kept[layer, h] - want[layer, h],
                              compute_uv=False)
            rounding = max(rounding, float(np.sqrt(np.sum(np.square(
                s[_ROUNDING_RANK:]))) / norm[layer, h]))
    return by_head, float(by_head.max()), rounding


def tail_distance(kept, want) -> float:
    """The convolution tails a system kept [L, K - 1, C] against this
    reference's: the largest layer's relative distance."""
    kept, want = np.asarray(kept, np.float64), np.asarray(want, np.float64)
    kept = kept.reshape(want.shape)
    return float(np.max(
        np.sqrt(np.sum(np.square(kept - want), (-1, -2)))
        / np.maximum(np.sqrt(np.sum(np.square(want), (-1, -2))), 1e-30)))


def reference_config(conf: Dict, layers_run, router_width: int,
                     experts) -> Dict:
    """``cfg`` as this file reads it, from a configuration file's dictionary
    and the share it runs."""
    return dict(conf, layers_run=list(layers_run),
                router_width=int(router_width), experts=list(experts))


_LAYER_KEYS = {
    "input_norm": "input_norm", "post_norm": "post_norm",
    "q": "q_proj", "k": "k_proj", "v": "v_proj", "q_norm": "q_norm",
    "k_norm": "k_norm", "o": "o_proj",
    "qkvz": "in_qkvz", "ba": "in_ba", "conv": "conv", "A_log": "A_log",
    "dt_bias": "dt_bias", "o_norm": "o_norm", "out": "out_proj",
    "router": "moe.router", "gate": "moe.w_gate", "up": "moe.w_up",
    "down": "moe.w_down", "shared_gate": "moe.shared_gate",
    "shared_up": "moe.shared_up", "shared_down": "moe.shared_down",
    "shared_score": "moe.shared_score"}


def params_of(model) -> Dict:
    """``Qwen3NextForCausalLM``'s weights, as they are on the device, in this
    file's layout — arrays are shared, not copied."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    return {"embed": sd["embed_tokens"], "norm": sd["norm"],
            "head": sd["lm_head"],
            "layers": [{short: sd[f"layers.{i}.{name}"]
                        for short, name in _LAYER_KEYS.items()
                        if f"layers.{i}.{name}" in sd}
                       for i in range(len(model.layers))]}
