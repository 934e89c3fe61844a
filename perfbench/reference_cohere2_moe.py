"""The plain reference of ``command-a-plus-05-2026`` (``cohere2_moe``): the
layer equations of ISSUE 27 in ``jax.numpy``, float32.

No kernels, no cache, no batching, no sorting; one sequence at a time. For
layer l of kind ``layer_types[l]`` and input ``x`` [T, E]::

    h   = LayerNorm(x) * g                  # mean-subtracting, weight only, eps = layer_norm_eps
    q   = h Wq -> [T, H, D];  k = h Wk, v = h Wv -> [T, Hkv, D]     # no bias, no qk norm
    sliding_attention:  q, k = rope(q), rope(k)   # pairs (2i, 2i+1), theta = rope_theta
                        mask 0 <= i - j < sliding_window
    full_attention:     no positional encoding;  mask j <= i
    attn = softmax(q k^T / sqrt(D) + mask) v Wo
    s   = sigmoid(h Wr) over all num_experts;  S = top-k indices;  w_e = s_e / sum_{e' in S} s_e'
    E_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e
    routed = sum_{e in S, e held here} w_e E_e(h)
    shared = (1 / n_shared) sum_s Sh_s(h)
    y   = x + attn + routed + shared        # parallel block: one norm, both branches read h

then ``LayerNorm`` and ``logits = logit_scale * h E^T`` over the rows of the
tied embedding held here. ``first_k_dense_replace`` is 0: no dense layer.

The share: ``experts`` lists the global indices of the routed experts whose
weights are given (``[]`` for none, ``range(num_experts)`` for the uncut
layer); routing and the renormalisation always run over all
``num_experts``. Departures from the textbook forward, for memory only:
attention goes over blocks of query rows, experts are upcast and applied one
at a time to every token with a mask (dense, so nothing is sorted or
gathered), and a layer's weights are upcast where they are used.

Everything runs under ``jax.default_matmul_precision("highest")``. Weights
come in a neutral layout (matrices ``[in, out]``)::

    {"embed": [V_held, E], "norm": [E],
     "layers": [{"norm", "q", "k", "v", "o", "router" [E, num_experts],
                 "gate"/"up" [n, E, F], "down" [n, F, E],
                 "shared_gate"/"shared_up" [E, s*F], "shared_down" [s*F, E]}]}

Limits, and why
---------------
As ``reference.SERVE_LOGIT_TOL``'s, the comparison is teacher-forced over
prompt + answer: at each answer position, the reference's largest logit minus
its logit of the token the engine chose (0 where they agree). This model adds
a discrete step the dense one has not: where a token's k-th and (k+1)-th
router scores nearly tie and one of the two experts is held here, the bf16
program and the float32 reference compute different experts (the scores are
float32 on both sides; their *input* h is not), and that token's logits move
by up to 0.3 — as far as 8-bit weights move them. So the positions are
split by what the reference itself can tell (``router_margins``):

* a position is STEADY when, in every layer, the k-th and (k+1)-th score lie
  ``ROUTER_MARGIN_MIN`` apart or neither of the two experts is held here (a
  swap of two absent experts changes what is computed here by the
  renormalising sum alone, and the two scores are all but equal). 0.006 is
  1.5 bf16 roundings of a score near 0.9 (2**-8 = 0.0039): a router computed
  in bf16 flips below it, a float32 router on bf16 activations — this
  program — moves a score by about 0.002;
* ``SERVE_LOGIT_TOL_MOE`` bounds the largest gap over the steady positions,
  where rounding moves logits smoothly. It lies between this PR's two
  readings, each through ``serve_open_loop_moe._check`` on the chip: the
  largest the bf16 engine gave over its seeds, and what the same check reads
  with the reference's weights rounded to float8_e4m3, the nearest precision
  below the configuration's bf16 (``control="fp8_weights"``), which has to
  come out not correct (PERF.md section 2 has both);
* ``SERVE_MIN_STEADY`` — a check with fewer steady positions than this saw
  too little and is not correct either;
* over ALL positions, flipped ones included, ``SERVE_MIN_AGREEING_MOE`` is
  the share at which the engine chose the reference's own argmax: a token
  from a wrong page, position, mask or expert is wrong nearly everywhere,
  while a flip moves the argmax only where the two largest logits nearly tie.

``CONTROLS`` are the same reference computed a precision lower — weights in
float8_e4m3, K and V in int8 (per token and head, absmax), router scores in
bf16 — for the harness's switch (``PERFBENCH_CHECK_CONTROL``) and the tests:
what each reads is in PERF.md; the last two are not told from float32 by any
statistic over tokens alone, and PERF.md says why.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

SERVE_LOGIT_TOL_MOE = 0.1
SERVE_MIN_AGREEING_MOE = 0.75
SERVE_MIN_STEADY = 8
ROUTER_MARGIN_MIN = 0.006
CONTROLS = ("fp8_weights", "int8_kv", "bf16_router")
_Q_BLOCK = 64


def _f32(x):
    return x.astype(jnp.float32)


def _w(x, cfg: Dict):
    """A weight matrix as the reference computes with it: float32 of what is
    stored, or of its float8_e4m3 rounding under that control."""
    if cfg.get("control") == "fp8_weights":
        x = x.astype(jnp.float8_e4m3fn)
    return _f32(x)


def _int8(x):
    """``x`` [T, heads, D] rounded to 8 bits per (token, head), absmax."""
    scale = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(scale, 1e-30)) * scale


def _layer_norm(x, w, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w)


def _rotary(x, theta):
    """x: [T, heads, D]; interleaved pairs (2i, 2i+1) (``rope_gptj``)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(q, k, v, window):
    """Causal softmax attention (within ``window`` if given), q: [T, Hq, D],
    k/v: [T, Hkv, D]. One block of query rows at a time (``lax.map``: at 128
    heads and 6k keys a block's scores are 200 MB)."""
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
        keep = cols[None, :] <= rows[:, None]
        if window is not None:
            keep &= rows[:, None] - cols[None, :] < window
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * _Q_BLOCK))
    return out.reshape(-1, hq, d)[:t]


def _swiglu(h, gate, up, down, cfg: Dict):
    return (jax.nn.silu(h @ _w(gate, cfg)) * (h @ _w(up, cfg))) @ _w(down, cfg)


def _scores(h, router, cfg: Dict):
    """Router scores over all experts, [T, num_experts] float32."""
    if cfg.get("control") == "bf16_router":
        bf = jnp.bfloat16
        return _f32(jax.nn.sigmoid(_f32(h.astype(bf) @ router.astype(bf))
                                   ).astype(bf))
    return jax.nn.sigmoid(h @ _w(router, cfg))


def routing(h, router, top_k: int, cfg: Optional[Dict] = None):
    """-> (indices [T, k], weights [T, k]) over all experts, float32."""
    top_v, top_i = jax.lax.top_k(_scores(h, router, cfg or {}), top_k)
    return top_i, top_v / jnp.sum(top_v, -1, keepdims=True)


def router_margin(h, router, cfg: Dict, experts: Sequence[int]):
    """[T]: how far the k-th score lies above the (k+1)-th, or infinity
    where neither of the two experts is among ``experts``."""
    k = cfg["num_experts_per_tok"]
    top_v, top_i = jax.lax.top_k(_scores(h, router, cfg), k + 1)
    held = jnp.isin(top_i[:, k - 1:], jnp.asarray(list(experts), jnp.int32))
    return jnp.where(jnp.any(held, -1), top_v[:, k - 1] - top_v[:, k],
                     jnp.inf)


def moe(h, p, cfg: Dict, experts: Sequence[int]):
    """routed (over the experts given) + shared, [T, E] float32."""
    top_i, w = routing(h, p["router"], cfg["num_experts_per_tok"], cfg)
    out = jnp.zeros_like(h)
    for n, e in enumerate(experts):        # one expert's weights at a time
        w_e = jnp.sum(jnp.where(top_i == e, w, 0.0), -1, keepdims=True)
        out = out + w_e * _swiglu(h, p["gate"][n], p["up"][n], p["down"][n],
                                  cfg)
    s = cfg["num_shared_experts"]
    f = p["shared_gate"].shape[1] // s if s else 0
    for n in range(s):                     # the stored pair, one expert's
        cut = slice(n * f, (n + 1) * f)    # columns and rows at a time
        out = out + _swiglu(h, p["shared_gate"][:, cut], p["shared_up"][:, cut],
                            p["shared_down"][cut], cfg) / s
    return out


def attention(h, p, cfg: Dict, kind: str):
    t, d = h.shape[0], cfg["head_dim"]
    q = (h @ _w(p["q"], cfg)).reshape(t, -1, d)
    k = (h @ _w(p["k"], cfg)).reshape(t, -1, d)
    v = (h @ _w(p["v"], cfg)).reshape(t, -1, d)
    window = None
    if kind == "sliding_attention":
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    if cfg.get("control") == "int8_kv":
        k, v = _int8(k), _int8(v)
    return _attention(q, k, v, window).reshape(t, -1) @ _w(p["o"], cfg)


def layer(x, p, cfg: Dict, kind: str, experts: Sequence[int]):
    h = _layer_norm(x, p["norm"], cfg["layer_norm_eps"])
    return x + attention(h, p, cfg, kind) + moe(h, p, cfg, experts)


def _forward(params: Dict, ids, cfg: Dict, experts: Sequence[int],
             first_row, rows):
    """-> (logits of ``rows`` positions from ``first_row`` on (all if
    ``rows`` is None), their smallest ``router_margin`` over the layers)."""
    def cut(a):
        return a if rows is None else \
            jax.lax.dynamic_slice_in_dim(a, first_row, rows)

    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0))
        margin = jnp.full(cut(x).shape[:1], jnp.inf)
        for p, kind in zip(params["layers"], cfg["layer_types"]):
            h = cut(_layer_norm(x, p["norm"], cfg["layer_norm_eps"]))
            margin = jnp.minimum(margin, router_margin(h, p["router"], cfg,
                                                       experts))
            x = layer(x, p, cfg, kind, experts)
        x = _layer_norm(cut(x), params["norm"], cfg["layer_norm_eps"])
        return cfg.get("logit_scale", 1) * (x @ _w(params["embed"], cfg).T), \
            margin


def logits(params: Dict, ids, cfg: Dict, experts: Sequence[int],
           first_row=0, rows=None):
    """ids: [T] int (indices into the vocabulary rows held) -> float32
    logits [T, V_held], or of ``rows`` positions from ``first_row`` on (a
    traced scalar) — the head is applied to those rows only."""
    return _forward(params, ids, cfg, experts, first_row, rows)[0]


def chosen_logit_gaps(params: Dict, ids, prompt_len, answer, cfg: Dict,
                      experts: Sequence[int]):
    """As ``reference.chosen_logit_gaps``: teacher-forced over prompt +
    answer, the reference's largest logit minus its logit of the token the
    system chose, at each answer position — and beside it each position's
    smallest router margin (the module docstring's STEADY rule reads it).
    answer: [A] -> (float32 [A], float32 [A])."""
    rows, margin = _forward(params, ids, cfg, experts, prompt_len - 1,
                            answer.shape[0])
    chosen = jnp.take_along_axis(rows, answer[:, None], 1)[:, 0]
    return jnp.max(rows, -1) - chosen, margin


_LAYER_KEYS = {
    "norm": "norm", "q": "q_proj", "k": "k_proj", "v": "v_proj",
    "o": "o_proj", "router": "moe.router", "gate": "moe.w_gate",
    "up": "moe.w_up", "down": "moe.w_down", "shared_gate": "moe.shared_gate",
    "shared_up": "moe.shared_up", "shared_down": "moe.shared_down"}


def params_of(model) -> Dict:
    """``Cohere2MoeForCausalLM``'s weights, as they are on the device, in
    this file's layout — arrays are shared, not copied."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    return {"embed": sd["embed_tokens"], "norm": sd["norm"],
            "layers": [{short: sd[f"layers.{i}.{name}"]
                        for short, name in _LAYER_KEYS.items()
                        if f"layers.{i}.{name}" in sd}
                       for i in range(model.config.num_hidden_layers)]}
