"""The plain reference of ``MiniCPM-SALA`` (``minicpm_sala``): the layer
equations of ISSUE 31 in ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.

No kernels, no pages, no cache, no state pool, no batching; one sequence at a
time, and nothing shared with ``paddle_tpu``. For the residual stream ``x``
[T, E], ``a = scale_depth / sqrt(L_published)`` and ``norm`` = RMSNorm (eps
``rms_norm_eps``), every layer is::

    x = x + a * mixer(norm(x));   x = x + a * down(silu(gate h) * up h), h = norm(x)

``minicpm4`` (sparse softmax attention): ``q = h Wq`` [T, 32, D], ``k, v`` [T,
2, D], RMSNorm per head on q and k, no positions, scale ``1/sqrt(D)``.
Compressed keys ``Kc_j = mean(k[16j : 16j+32])``. The query at position ``t``
with more than ``dense_len`` tokens of context (``t + 1 > dense_len``) sees,
per KV head, the ``topk`` best blocks of 64 tokens: per query head ``p_h =
softmax_j(q_h . Kc_j / sqrt(D))`` over the kernels that end at or before
``t``; ``a_j`` = the sum of ``p_h`` over the 16 query heads of the KV head;
a block's score is the largest ``a_j`` over the kernels that overlap it; the
first ``init_blocks`` blocks and the ``window_size / 64`` blocks ending at
``t``'s own always count as best. Inside the chosen blocks attention is
causal; at or below ``dense_len`` it is causal over everything. Then ``(attn
* sigmoid(h Wg)) Wo``.

``lightning-attn``: ``q, k, v = h Wq, h Wk, h Wv`` [T, 32, D], RMSNorm per
head on q and k, rotary (theta ``rope_theta``, halves rotated) on q and k,
then TOKEN BY TOKEN ``S_t = lam_h S_{t-1} + k_t^T v_t`` and ``o_t = q_t S_t /
sqrt(D)`` with ``lam_h = exp(-s_h)``, ``s_h = 2**(-8 (h+1) / H) * (1 - l / (L
- 1) + 1e-5)`` for PUBLISHED layer ``l`` of ``L``; ``(norm(o) * sigmoid(h Wz))
Wo`` with the output norm over all ``H * D`` features.

Embeddings times ``scale_emb``; logits ``= norm(x) / (E / dim_model_base) @
head``. ``cfg`` is the configuration file's dictionary plus ``layers_run``
(the published indices of the layers given) and ``num_layers_published``.
Departures from the textbook forward, for memory only: rows go through a
layer in blocks, and weights are upcast where they are used.

Weights come in a neutral layout (matrices ``[in, out]``)::

    {"embed": [V, E], "norm": [E], "head": [E, V],
     "layers": [{"input_norm", "q", "k", "v", "gate", "o", "q_norm", "k_norm",
                 "post_norm", "mlp_gate", "mlp_up", "mlp_down", ("o_norm")}]}

Limits, and why
---------------
The check holds the system to this reference three ways, at the timed sizes
(readings: PERF.md section 2).

**The tokens it chose**, teacher-forced over prompt + answer as
``reference_cohere2_moe``'s: at each answer position the reference's
largest logit minus its logit of the token the engine chose. This model has
one discrete step: the choice of blocks. Where the ``topk``-th and the next
block score nearly tie, the bf16 program and this reference choose
different blocks and that token's logits move as far as a wrong page would
move them. So the reference returns, per answer position, the smallest
RELATIVE margin between the last block chosen and the first one not, over
the sparse layers and KV heads (``inf`` where nothing is dropped):

* a position is STEADY when that margin is at least ``BLOCK_MARGIN_MIN``,
  or exactly 0 (neighbouring blocks share the kernel that straddles their
  boundary, so where that kernel is the best of both, both have ONE number
  for a score on either side, and both sides take the lower block);
* ``SERVE_LOGIT_TOL_SALA`` bounds the largest gap over the steady
  positions; ``SERVE_MIN_STEADY``: a check with fewer saw too little; over
  ALL positions ``SERVE_MIN_AGREEING_SALA`` is the share at which the
  engine chose the reference's own argmax. The control these are set
  against is ``fp8_weights``; a token statistic moves only where a
  perturbation overturns an argmax, so it cannot see the state's precision
  nor one wrong block — the next two can.

**The state it kept** (``state_distance``): the float32 state the engine
files at a prefix boundary against this reference's after the same tokens.
``SERVE_STATE_TOL_SALA`` bounds the worst head's relative distance (a wrong
snapshot, row or decay); ``SERVE_STATE_ROUNDING_TOL_SALA`` bounds what the
state's own rounding adds, which ``control="bf16_state"`` — the state
rounded to bfloat16 after every token, the nearest precision below the
configuration's float32 — has to exceed.

**The blocks it chose** (``block_choice``'s ``chosen`` and ``away``): every
block a decode step's selection chose against the reference's at the same
position, layer and KV head. The scores are sums of softmax weights over
bf16 activations; a block may differ only where its score lies within
``BLOCK_MARGIN_MIN`` (relative) of the last chosen one's — the check counts
those — and a forced block lies infinitely far from it, so a dropped forced
block, a wrong page or a wrong pooling is a wrong block.

``CONTROLS``: ``bf16_state`` and ``fp8_weights`` (the reference a precision
lower, for the harness's switch), ``bf16_scores`` (the selection's ``q . Kc``
rounded to bfloat16) and ``no_forced`` (no block forced).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

SERVE_LOGIT_TOL_SALA = 0.01
SERVE_STATE_TOL_SALA = 0.07
SERVE_STATE_ROUNDING_TOL_SALA = 0.011
SERVE_MIN_AGREEING_SALA = 0.75
SERVE_MIN_STEADY = 8
BLOCK_MARGIN_MIN = 0.02
CONTROLS = ("bf16_state", "fp8_weights", "bf16_scores", "no_forced")
SPARSE = "minicpm4"
_ROWS = 2048        # rows of a layer computed at once
_Q_BLOCK = 16       # query rows whose scores against every key exist at once


def _f32(x):
    return x.astype(jnp.float32)


def _bf16(x):
    """float32 ``x`` rounded to bfloat16's 8 bits of mantissa, as float32.
    ``reduce_precision`` and not a cast there and back: XLA removes such a
    pair of converts on a TPU (``xla_allow_excess_precision``), and the
    control would then be the sound reference under another name."""
    return jax.lax.reduce_precision(_f32(x), exponent_bits=8,
                                    mantissa_bits=7)


def steady(margin):
    """Which positions' block choice both sides make alike (the module
    docstring's rule), for ``margin`` as ``answer_rows`` returns
    it."""
    return (margin >= BLOCK_MARGIN_MIN) | (margin == 0)


def _w(x, cfg: Dict):
    if cfg.get("control") == "fp8_weights":
        x = x.astype(jnp.float8_e4m3fn)
    return _f32(x)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(w)


def _blocks(fn, *arrays, rows: int = _ROWS):
    """``fn`` over blocks of rows (each array [T, ...]), concatenated: the
    whole blocks under one ``lax.map``, the rest in one call."""
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


def _rotary(x, first: int, theta):
    """x: [T, heads, D] at positions ``first + i``; halves rotated."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (first + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sparse_params(cfg: Dict) -> Dict:
    return dict(cfg["sparse_config"])


def slopes(heads: int, layer: int, layers: int):
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / heads) * (1.0 - layer / (layers - 1) + 1e-5)


# ---------------------------------------------------------------------------
# minicpm4: selection and masked softmax
# ---------------------------------------------------------------------------

def block_choice(q, kc, pos, cfg: Dict, n_m: int):
    """For queries ``q`` [Q, Hkv, rep, D] at positions ``pos`` [Q] and
    compressed keys ``kc`` [J, Hkv, D] (kernel j covers tokens ``stride * j
    .. stride * j + kernel``): ``(chosen [Q, Hkv, M] bool over ``n_m`` blocks,
    margin [Q], away [Q, Hkv, M])`` — ``margin`` the relative gap between
    the last block chosen and the first not, smallest over the KV heads,
    ``inf`` where nothing is dropped or the query is at or below
    ``dense_len``; ``away`` how far each block's score lies from the last
    chosen one's, relative to it (``inf`` for a forced block, a block past
    the query, and wherever nothing is dropped)."""
    sp = sparse_params(cfg)
    ks, st, bs = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    n_j = kc.shape[0]
    d = q.shape[-1]
    own = pos // bs
    m = jnp.arange(n_m)
    valid = m[None, :] <= own[:, None]                              # [Q, M]
    if n_j:
        qs, kcs = q, kc
        if cfg.get("control") == "bf16_scores":
            qs, kcs = _bf16(q), _bf16(kc)
        s = jnp.einsum("qgrd,jgd->qgrj", qs, kcs) / jnp.sqrt(jnp.float32(d))
        if cfg.get("control") == "bf16_scores":
            s = _bf16(s)
        j = jnp.arange(n_j)
        seen = (st * j + ks - 1)[None, :] <= pos[:, None]           # [Q, J]
        s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
        top = jnp.max(s, -1, keepdims=True)
        e = jnp.where(seen[:, None, None, :],
                      jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
        a = jnp.sum(p, axis=2)                                      # [Q,Hkv,J]
        overlap = ((st * j)[:, None] < (bs * (m + 1))[None, :]) & \
            ((st * j + ks)[:, None] > (bs * m)[None, :])            # [J, M]
        score = jnp.max(jnp.where(overlap[None, None], a[..., None], 0.0),
                        axis=2)                                     # [Q,Hkv,M]
    else:
        score = jnp.zeros((q.shape[0], q.shape[1], n_m), jnp.float32)
    forced = (m[None, :] < sp["init_blocks"]) | \
        (own[:, None] - m[None, :] < sp["window_size"] // bs)
    if cfg.get("control") == "no_forced":
        forced = jnp.zeros_like(forced)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(valid[:, None, :], score, -jnp.inf)
    k = sp["topk"]
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = (rank < k) & valid[:, None, :]
    ranked = jnp.take_along_axis(score, order, axis=-1)
    if n_m > k:
        last, nxt = ranked[..., k - 1], ranked[..., k]
        gap = jnp.where(jnp.isfinite(nxt) & jnp.isfinite(last),
                        (last - nxt) / jnp.maximum(last, 1e-30), jnp.inf)
        gap = jnp.where(nxt == -jnp.inf, jnp.inf, gap)
        margin = jnp.min(gap, axis=1)
        cut = jnp.where(jnp.isfinite(last) & jnp.isfinite(nxt), last,
                        jnp.nan)[..., None]
        away = jnp.abs(score - cut) / jnp.maximum(cut, 1e-30)
        away = jnp.where(jnp.isfinite(away), away, jnp.inf)
    else:
        margin = jnp.full(pos.shape, jnp.inf)
        away = jnp.full(score.shape, jnp.inf)
    dense = pos + 1 <= sp["dense_len"]
    chosen = chosen | (dense[:, None, None] & valid[:, None, :])
    return chosen, jnp.where(dense, jnp.inf, margin), \
        jnp.where(dense[:, None, None], jnp.inf, away)


def compressed_keys(k, cfg: Dict):
    """k: [T, Hkv, D] -> [J, Hkv, D], the mean of every whole kernel."""
    sp = sparse_params(cfg)
    ks, st = sp["kernel_size"], sp["kernel_stride"]
    t, hkv, d = k.shape
    n_j = max(0, (t - ks) // st + 1)
    return jnp.mean(k[(st * jnp.arange(n_j))[:, None] + jnp.arange(ks)[None]],
                    axis=1) if n_j else jnp.zeros((0, hkv, d), jnp.float32)


def sparse_attention(q, k, v, cfg: Dict):
    """q: [T, H, D], k/v: [T, Hkv, D] -> ([T, H, D], margin [T])."""
    bs = sparse_params(cfg)["block_size"]
    t, h, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    kc = compressed_keys(k, cfg)
    cols = jnp.arange(t)
    pad = -t % _Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _Q_BLOCK, hkv, rep, d)

    def block(args):
        qq, lo = args
        pos = lo + jnp.arange(_Q_BLOCK)
        chosen, margin, _ = block_choice(qq, kc, pos, cfg, -(-t // bs))
        keep = jnp.take(chosen, cols // bs, axis=2) & \
            (cols[None, None, :] <= pos[:, None, None])          # [Q,Hkv,T]
        s = jnp.einsum("qgrd,kgd->qgrk", qq, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(keep[:, :, None, :], s, -jnp.inf)
        out = jnp.einsum("qgrk,kgd->qgrd", jax.nn.softmax(s, -1), v)
        return out.reshape(_Q_BLOCK, h, d), margin

    out, margin = jax.lax.map(
        block, (qb, jnp.arange(qb.shape[0]) * _Q_BLOCK))
    return out.reshape(-1, h, d)[:t], margin.reshape(-1)[:t]


def sparse_mixer(h, p, cfg: Dict, cut=None):
    """-> (out [T, E], margin [T], the choice of the rows ``cut(...)``
    keeps: (chosen, away) as ``block_choice`` gives them, or None)."""
    t = h.shape[0]
    d = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]

    def proj(hb):
        q = _norm((hb @ _w(p["q"], cfg)).reshape(hb.shape[0], -1, d),
                  p["q_norm"], eps)
        return q.reshape(hb.shape[0], -1)
    q = _blocks(proj, h).reshape(t, -1, d)
    k = _norm((h @ _w(p["k"], cfg)).reshape(t, -1, d), p["k_norm"], eps)
    v = (h @ _w(p["v"], cfg)).reshape(t, -1, d)
    attn, margin = sparse_attention(q, k, v, cfg)
    out = _blocks(lambda ab, hb: (ab * jax.nn.sigmoid(hb @ _w(p["gate"], cfg)))
                  @ _w(p["o"], cfg), attn.reshape(t, -1), h)
    choice = None
    if cut is not None:
        qc = cut(q)
        chosen, _, away = block_choice(
            qc.reshape(qc.shape[0], k.shape[1], -1, d),
            compressed_keys(k, cfg), cut(jnp.arange(t)), cfg,
            -(-t // sparse_params(cfg)["block_size"]))
        choice = (chosen, away)
    return out, margin, choice


# ---------------------------------------------------------------------------
# lightning-attn: the recurrence, token by token
# ---------------------------------------------------------------------------

def lightning_mixer(h, p, cfg: Dict, layer: int, state_at: int = 0):
    """-> (out [T, E], the state after the first ``state_at`` tokens [H, D,
    D] float32 — zeros for 0)."""
    t = h.shape[0]
    d = cfg["lightning_head_dim"]
    heads = cfg["lightning_nh"]
    eps = cfg["rms_norm_eps"]
    lam = jnp.exp(-slopes(heads, layer, cfg["num_layers_published"]))
    bf16_state = cfg.get("control") == "bf16_state"

    def token(S, qkv):
        q, k, v = qkv                                            # [H, D]
        S = lam[:, None, None] * S + k[:, :, None] * v[:, None, :]
        if bf16_state:
            S = _bf16(S)
        return S, jnp.einsum("hd,hde->he", q, S) / jnp.sqrt(jnp.float32(d))

    def rows(S, hb, at):
        """A block of rows from state ``S``, the first at position ``at``."""
        n = hb.shape[0]
        q = _norm((hb @ _w(p["q"], cfg)).reshape(n, heads, d), p["q_norm"],
                  eps)
        k = _norm((hb @ _w(p["k"], cfg)).reshape(n, heads, d), p["k_norm"],
                  eps)
        v = (hb @ _w(p["v"], cfg)).reshape(n, heads, d)
        if cfg.get("lightning_use_rope", True):
            q = _rotary(q, at, cfg["rope_theta"])
            k = _rotary(k, at, cfg["rope_theta"])
        S, o = jax.lax.scan(token, S, (q, k, v))
        o = o.reshape(n, heads * d)
        if cfg.get("use_output_norm", True):
            o = _norm(o, p["o_norm"], eps)
        return S, (o * jax.nn.sigmoid(hb @ _w(p["gate"], cfg))) \
            @ _w(p["o"], cfg)

    def run(S, hs, at):
        """Rows ``hs`` from state ``S``, the first at position ``at``, in
        blocks of ``_ROWS`` -> (the state after them, [their outputs])."""
        n = hs.shape[0]
        full = n // _ROWS
        outs = []
        if full:
            S, head = jax.lax.scan(
                lambda S_, xs: rows(S_, *xs), S,
                (hs[:full * _ROWS].reshape(full, _ROWS, -1),
                 at + jnp.arange(full) * _ROWS))
            outs.append(head.reshape(full * _ROWS, -1))
        if full * _ROWS < n:
            S, rest = rows(S, hs[full * _ROWS:], at + full * _ROWS)
            outs.append(rest)
        return S, outs

    kept, before = run(jnp.zeros((heads, d, d), jnp.float32), h[:state_at], 0)
    _, after = run(kept, h[state_at:], state_at)
    return jnp.concatenate(before + after), kept


def _ffn(x, p, cfg: Dict, a):
    def f(xb):
        h = _norm(xb, p["post_norm"], cfg["rms_norm_eps"])
        return xb + a * ((jax.nn.silu(h @ _w(p["mlp_gate"], cfg))
                          * (h @ _w(p["mlp_up"], cfg)))
                         @ _w(p["mlp_down"], cfg))
    return _blocks(f, x)


def _forward(params: Dict, ids, cfg: Dict, first_row, rows,
             state_at: int = 0, choices: bool = False):
    """-> (logits of ``rows`` positions from ``first_row`` on (all if
    ``rows`` is None), their smallest block margin over the sparse
    layers, every lightning layer's state after ``state_at`` tokens [L_lin,
    H, D, D], with ``choices`` every sparse layer's ``(chosen, away)`` of
    those positions, each [rows, L_sparse, Hkv, M])."""
    def cut(a):
        return a if rows is None else \
            jax.lax.dynamic_slice_in_dim(a, first_row, rows)

    layers_run = cfg["layers_run"]
    a = cfg["scale_depth"] / jnp.sqrt(jnp.float32(cfg["num_layers_published"]))
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], ids, axis=0)) * cfg["scale_emb"]
        margin = jnp.full(cut(x).shape[:1], jnp.inf)
        states, chose = [], []
        for p, layer in zip(params["layers"], layers_run):
            h = _blocks(lambda xb, p=p: _norm(xb, p["input_norm"],
                                              cfg["rms_norm_eps"]), x)
            if cfg["mixer_types_published"][layer] == SPARSE:
                out, m, choice = sparse_mixer(h, p, cfg,
                                              cut if choices else None)
                margin = jnp.minimum(margin, cut(m))
                chose.append(choice)
            else:
                out, kept = lightning_mixer(h, p, cfg, layer, state_at)
                states.append(kept)
            x = _ffn(x + a * out, p, cfg, a)
        x = _norm(cut(x), params["norm"], cfg["rms_norm_eps"]) \
            / (cfg["hidden_size"] / cfg["dim_model_base"])
        return x @ _w(params["head"], cfg), margin, jnp.stack(states), \
            tuple(jnp.stack(c, 1) for c in zip(*chose)) if choices else ()


def logits(params: Dict, ids, cfg: Dict, first_row=0, rows=None):
    """ids: [T] int -> float32 logits [T, V], or of ``rows`` positions from
    ``first_row`` on (a traced scalar)."""
    return _forward(params, ids, cfg, first_row, rows)[0]


def answer_rows(params: Dict, ids, prompt_len, answer, cfg: Dict,
                state_at: int = 0) -> Dict:
    """Teacher-forced over prompt + answer, everything the check compares
    at the ``A`` answer positions: ``gap`` [A] (the largest logit minus the
    logit of the token the system chose), ``margin`` [A], ``logits`` [A,
    V], ``chosen`` and ``away`` [A, L_sparse, Hkv, M] (``block_choice``),
    and ``states`` [L_lin, H, D, D], every lightning layer's state after
    the first ``state_at`` tokens."""
    rows, margin, states, (chosen, away) = _forward(
        params, ids, cfg, prompt_len - 1, answer.shape[0], state_at, True)
    took = jnp.take_along_axis(rows, answer[:, None], 1)[:, 0]
    return {"gap": jnp.max(rows, -1) - took, "margin": margin,
            "logits": rows, "chosen": chosen, "away": away, "states": states}


def state_distance(kept, want, cfg: Dict):
    """How far the states a system kept ``kept`` [L_lin, H, D, D] lie from
    this reference's ``want``: ``(by_head [L_lin, H], worst, rounding)`` —
    each head's Frobenius distance relative to the reference's norm, the
    largest of them, and what the state's own rounding adds: the inputs'
    noise is the same for every head, an error made at every update piles
    up as a head forgets more slowly (``1 / sqrt(2 s_h)`` updates deep), so
    ``rounding`` is the root of the mean squared distance of the slowest
    quarter of heads less that of the fastest quarter (signed: negative
    where the fast heads lie further), the largest over the layers."""
    kept, want = _f32(jnp.asarray(kept)), _f32(jnp.asarray(want))
    by_head = jnp.sqrt(jnp.sum(jnp.square(kept - want), (-1, -2))) \
        / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(want), (-1, -2))), 1e-30)
    heads = by_head.shape[1]
    fast_first = jnp.argsort(-slopes(heads, 0, cfg["num_layers_published"]))
    sq = jnp.square(by_head[:, fast_first])
    n = max(1, heads // 4)
    more = jnp.mean(sq[:, -n:], 1) - jnp.mean(sq[:, :n], 1)
    return by_head, jnp.max(by_head), \
        jnp.max(jnp.sign(more) * jnp.sqrt(jnp.abs(more)))


def reference_config(conf: Dict, layers_run, layers_published: int,
                     mixers_published) -> Dict:
    """``cfg`` as this file reads it, from a configuration file's
    dictionary and the share it runs."""
    return dict(conf, layers_run=list(layers_run),
                num_layers_published=int(layers_published),
                mixer_types_published=list(mixers_published))


_LAYER_KEYS = {
    "input_norm": "input_norm", "q": "q_proj", "k": "k_proj", "v": "v_proj",
    "gate": "gate_proj", "o": "o_proj", "q_norm": "q_norm",
    "k_norm": "k_norm", "o_norm": "o_norm", "post_norm": "post_norm",
    "mlp_gate": "mlp_gate", "mlp_up": "mlp_up", "mlp_down": "mlp_down"}


def params_of(model) -> Dict:
    """``MiniCPMSalaForCausalLM``'s weights, as they are on the device, in
    this file's layout — arrays are shared, not copied."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    return {"embed": sd["embed_tokens"], "norm": sd["norm"],
            "head": sd["lm_head"],
            "layers": [{short: sd[f"layers.{i}.{name}"]
                        for short, name in _LAYER_KEYS.items()
                        if f"layers.{i}.{name}" in sd}
                       for i in range(len(model.layers))]}
