"""On-chip smoke tier: one fast test per op family, generated from ops.yaml.

All 800+ default-tier tests pin ``jax_platforms=cpu`` (conftest), which is
exactly how the real chip's missing lowerings survived five review rounds
(ROADMAP item 2). This module is the transfer proof: every op FAMILY in the
manifest (``paddle_tpu/ops/ops.yaml``) gets one tiny, fast invocation that
runs UNPINNED on real hardware —

    PADDLE_TPU_TIER=1 python -m pytest tests -m tpu

— and skips cleanly on CPU hosts (conftest adds the skip when no
accelerator is present). Green here means green CI finally says something
about the device the framework is named for; an op with no TPU lowering
degrades through the backend-fallback path (core/fallback.py) with a
warning instead of failing the tier.

Rot protection: the family list is DERIVED from ops.yaml at collection
time, and ``test_smoke_covers_every_family`` (unmarked — it runs in
tier-1, on CPU) fails the moment a new op lands in a family with no smoke
entry. Adding an op to an existing family costs nothing; adding a new
family means writing one ~3-line smoke fn here.
"""

import os
import re

import numpy as np
import pytest

OPS_YAML = os.path.join(os.path.dirname(__file__), os.pardir,
                        "paddle_tpu", "ops", "ops.yaml")


def _load_ops():
    """[{op, module (last segment), arity}] — tiny line parser so the test
    does not depend on a yaml library."""
    ops, cur = [], None
    with open(OPS_YAML) as f:
        for line in f:
            line = line.rstrip()
            if line.startswith("- op: "):
                cur = {"op": line[6:].strip(), "module": "?", "arity": 0}
                ops.append(cur)
            elif cur is not None and line.startswith("  module: "):
                cur["module"] = line[10:].strip().rsplit(".", 1)[-1]
            elif cur is not None and line.startswith("  args: "):
                sig = line[8:].strip().strip('"').strip("()")
                n = 0
                for part in sig.split(","):
                    part = part.strip()
                    if not part or "=" in part:
                        break
                    n += 1
                cur["arity"] = n
    return ops


# name-pattern rules run first (ordered); then the module map; _helpers
# splits by arity. Coarse on purpose: a family is "ops that exercise the
# same lowering surface", not a taxonomy.
_NAME_RULES = (
    (re.compile(r"conv"), "conv"),
    (re.compile(r"pool"), "pool"),
    (re.compile(r"dropout"), "dropout"),
    (re.compile(r"(_norm$|^normalize$)"), "norm"),
    (re.compile(r"embedding"), "embedding"),
    (re.compile(r"(attention|^softmax_mask_fuse)"), "attention"),
    (re.compile(r"(loss|entropy|_cost$)"), "loss"),
    (re.compile(r"^segment_"), "segment"),
    (re.compile(r"^(as_strided|strides|is_contiguous|view_as|view|unfold)$"),
     "strided"),
    (re.compile(r"^(bernoulli_|standard_gamma|top_p_sampling|binomial|"
                r"log_normal|cauchy_|geometric_)"), "sampling"),
)

_MODULE_FAMILIES = {
    "activation": "activation",
    "array": "tensor_array",
    "conv_pool": "resample",       # leftovers: interpolate/upsample/shuffle
    "creation": "creation",
    "flash_attention": "attention",
    "geometric": "segment",
    "indexing": "indexing",
    "linalg": "linalg",
    "loss_ops": "loss",
    "manipulation": "manipulation",
    "math": "math",
    "math_ext": "math_ext",
    "math_ext2": "math_ext2",
    "math_ext4": "math_ext4",
    "nn_ext": "nn_misc",
    "nn_ops": "nn_misc",
    "quant": "quantization",
    "reduce": "reduce",
}


def family_of(op: str, module: str, arity: int) -> str:
    for pat, fam in _NAME_RULES:
        if pat.search(op):
            return fam
    if module == "_helpers":
        return "elementwise_unary" if arity <= 1 else "elementwise_binary"
    return _MODULE_FAMILIES.get(module, module)


_OPS = _load_ops()
# + synthetic families for compiled SUBSYSTEM paths that no single ops.yaml
# entry covers: the serving engine's paged gather->step->scatter decode
# program is its own lowering surface (dynamic_slice/scatter over the page
# pool fused with the decode step), the online-shutdown contract
# (stop(drain=True) against a live step loop) exercises the compiled path
# from a background thread — host-sync + device-buffer lifetime behavior
# the offline run() drain cannot see — and the paged-attention Pallas
# decode kernel (ISSUE 13) has its own Mosaic lowering (scalar-prefetch
# page streaming + in-kernel int8 dequant) that only a real chip compiles
FAMILIES = sorted({family_of(o["op"], o["module"], o["arity"])
                   for o in _OPS}
                  | {"serving_decode", "serving_drain", "paged_attention"})


def _t(data, dtype="float32", stop_gradient=True):
    import paddle_tpu as paddle
    return paddle.to_tensor(np.asarray(data, dtype=dtype),
                            stop_gradient=stop_gradient)


def _rand(*shape):
    return np.random.default_rng(0).standard_normal(shape).astype("float32")


# One tiny invocation per family. Keep each under a second of compile on
# the chip: smallest shapes that still hit the family's real lowering.
def _smoke_activation():
    import paddle_tpu as paddle
    out = paddle.nn.functional.gelu(_t(_rand(4, 8))).numpy()
    assert out.shape == (4, 8) and np.isfinite(out).all()


def _smoke_attention():
    import paddle_tpu as paddle
    q = _t(_rand(1, 4, 2, 8))
    out = paddle.nn.functional.scaled_dot_product_attention(q, q, q)
    assert out.numpy().shape == (1, 4, 2, 8)


def _smoke_conv():
    import paddle_tpu as paddle
    out = paddle.nn.functional.conv2d(_t(_rand(1, 3, 8, 8)),
                                      _t(_rand(4, 3, 3, 3)))
    assert out.numpy().shape == (1, 4, 6, 6)


def _smoke_creation():
    import paddle_tpu as paddle
    out = paddle.full([2, 3], 7.0).numpy()
    np.testing.assert_allclose(out, np.full((2, 3), 7.0))


def _smoke_dropout():
    import paddle_tpu as paddle
    x = _t(_rand(4, 4))
    out = paddle.nn.functional.dropout(x, p=0.5, training=False).numpy()
    np.testing.assert_allclose(out, x.numpy())


def _smoke_elementwise_binary():
    import paddle_tpu as paddle
    a, b = _rand(3, 4), _rand(3, 4)
    np.testing.assert_allclose(paddle.add(_t(a), _t(b)).numpy(), a + b,
                               rtol=1e-6)


def _smoke_elementwise_unary():
    import paddle_tpu as paddle
    a = np.abs(_rand(3, 4)) + 0.1
    np.testing.assert_allclose(paddle.sqrt(_t(a)).numpy(), np.sqrt(a),
                               rtol=1e-6)


def _smoke_embedding():
    import paddle_tpu as paddle
    out = paddle.nn.functional.embedding(
        _t([[0, 2], [1, 3]], dtype="int64"), _t(_rand(8, 5)))
    assert out.numpy().shape == (2, 2, 5)


def _smoke_indexing():
    import paddle_tpu as paddle
    a = _rand(5, 3)
    out = paddle.index_select(_t(a), _t([0, 3], dtype="int64")).numpy()
    np.testing.assert_allclose(out, a[[0, 3]])


def _smoke_linalg():
    import paddle_tpu as paddle
    a, b = _rand(4, 3), _rand(3, 5)
    np.testing.assert_allclose(paddle.matmul(_t(a), _t(b)).numpy(), a @ b,
                               rtol=1e-4, atol=1e-5)


def _smoke_loss():
    import paddle_tpu as paddle
    out = paddle.nn.functional.mse_loss(_t(_rand(4, 2)), _t(_rand(4, 2)))
    assert np.isfinite(out.numpy()).all()


def _smoke_manipulation():
    import paddle_tpu as paddle
    a = _rand(2, 6)
    out = paddle.transpose(paddle.reshape(_t(a), [3, 4]), [1, 0]).numpy()
    np.testing.assert_allclose(out, a.reshape(3, 4).T)


def _smoke_math():
    import paddle_tpu as paddle
    a = _rand(3, 3)
    np.testing.assert_allclose(paddle.clip(_t(a), -0.5, 0.5).numpy(),
                               np.clip(a, -0.5, 0.5))


def _smoke_math_ext():
    import paddle_tpu as paddle
    out = paddle.cdist(_t(_rand(4, 3)), _t(_rand(5, 3))).numpy()
    assert out.shape == (4, 5) and (out >= 0).all()


def _smoke_math_ext2():
    import paddle_tpu as paddle
    a, b = _rand(2, 2), _rand(2, 2)
    out = paddle.block_diag([_t(a), _t(b)]).numpy()
    assert out.shape == (4, 4) and np.allclose(out[:2, :2], a)


def _smoke_math_ext4():
    import paddle_tpu as paddle
    a, b = _rand(3, 2), _rand(3, 2)
    np.testing.assert_allclose(paddle.add_n([_t(a), _t(b)]).numpy(), a + b,
                               rtol=1e-6)


def _smoke_nn_misc():
    import paddle_tpu as paddle
    out = paddle.nn.functional.linear(_t(_rand(4, 3)), _t(_rand(3, 5)))
    assert out.numpy().shape == (4, 5)


def _smoke_norm():
    import paddle_tpu as paddle
    out = paddle.nn.functional.layer_norm(
        _t(_rand(4, 8)), 8, weight=_t(np.ones(8)), bias=_t(np.zeros(8)))
    assert abs(float(out.numpy().mean())) < 1e-3


def _smoke_pool():
    import paddle_tpu as paddle
    out = paddle.nn.functional.max_pool2d(_t(_rand(1, 2, 8, 8)),
                                          kernel_size=2)
    assert out.numpy().shape == (1, 2, 4, 4)


def _smoke_quantization():
    import paddle_tpu as paddle
    w = _t(_rand(8, 4))
    qw, scale = paddle.nn.quant.weight_quantize(w)
    deq = paddle.nn.quant.weight_dequantize(qw, scale).numpy()
    np.testing.assert_allclose(deq, w.numpy(), atol=0.05)


def _smoke_reduce():
    import paddle_tpu as paddle
    a = _rand(3, 4)
    np.testing.assert_allclose(paddle.logsumexp(_t(a)).numpy(),
                               np.log(np.exp(a).sum()), rtol=1e-5)


def _smoke_resample():
    import paddle_tpu as paddle
    out = paddle.nn.functional.pixel_shuffle(_t(_rand(1, 4, 3, 3)), 2)
    assert out.numpy().shape == (1, 1, 6, 6)


def _smoke_sampling():
    import paddle_tpu as paddle
    out = paddle.standard_gamma(_t(np.full((64,), 2.0))).numpy()
    assert out.shape == (64,) and (out >= 0).all()


def _smoke_segment():
    import paddle_tpu as paddle
    out = paddle.geometric.segment_sum(
        _t([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]]),
        _t([0, 0, 1], dtype="int64")).numpy()
    np.testing.assert_allclose(out, [[4.0, 6.0], [5.0, 6.0]])


def _smoke_serving_decode():
    # the serving engine's compiled dense-tier decode program (gather pages
    # -> step -> scatter written page) on the real chip: 2 requests batched
    # continuously must decode the exact tokens of the dense bs=1 loop
    # over the SAME toy callables. paged_attention="off" names the tier:
    # the toys consume the dense stacked cache, and under auto a TPU
    # engine hands the step a PagedDecodeCache view instead
    import jax
    import jax.numpy as jnp
    from paddle_tpu import serving
    from paddle_tpu.core.tensor import Tensor as T

    L = H = 1
    D, M, V = 8, 32, 13
    posw = (jnp.arange(M, dtype=jnp.float32) + 1.0) / M
    ramp = (jnp.arange(D, dtype=jnp.float32) + 1.0) / D

    def readout(c, valid):                   # (B, H, M, D), (B, M) -> (B,)
        s = (c.astype(jnp.float32) * valid[:, None, :, None]
             * posw[None, None, :, None]).sum(axis=(1, 2, 3))
        return (s * 97.0).astype(jnp.int32) % V

    def step(tok, cache, t):
        c, td = cache._data, t._data.astype(jnp.int32)
        kv = ((tok._data[:, 0].astype(jnp.float32) + 1.0) / V)[:, None] * ramp

        def wr(cb, kvb, tb):
            page = jnp.broadcast_to(kvb[None, None, None, None, :],
                                    (L, 2, H, 1, D)).astype(cb.dtype)
            return jax.lax.dynamic_update_slice(cb, page, (0, 0, 0, tb, 0))

        c2 = jax.vmap(wr, in_axes=(2, 0, 0), out_axes=2)(c, kv, td)
        valid = (jnp.arange(M)[None, :] <= td[:, None]).astype(jnp.float32)
        return T(readout(c2[0, 0], valid)[:, None]), T(c2)

    def prefill(ids, cache):
        c, idsd = cache._data, ids._data
        lp = idsd.shape[1]
        kv = ((idsd[0].astype(jnp.float32) + 1.0) / V)[:, None] * ramp
        c = c.at[:, :, 0, :, :lp, :].set(
            jnp.broadcast_to(kv[None, :, :], (H, lp, D)).astype(c.dtype))
        valid = (jnp.arange(M) < lp)[None, :].astype(jnp.float32)
        return T(readout(c[0, 0], valid)[:, None]), T(c)

    prompts = [np.arange(8, dtype=np.int32) % V,
               (np.arange(8, dtype=np.int32) * 3) % V]

    def dense(prompt, n_new):
        cache = T(jnp.zeros((L, 2, 1, H, M, D), jnp.float32))
        tok, cache = prefill(T(jnp.asarray(prompt[None, :], jnp.int32)),
                             cache)
        toks, t = [int(np.asarray(tok._data)[0, 0])], prompt.size
        for _ in range(n_new - 1):
            tok, cache = step(tok, cache, T(jnp.asarray([t], jnp.int32)))
            toks.append(int(np.asarray(tok._data)[0, 0]))
            t += 1
        return toks

    cfg = serving.ServingConfig(num_layers=L, num_heads=H, head_dim=D,
                                max_len=M, max_batch=2, buckets=(1, 2),
                                page_size=8, paged_attention="off")
    eng = serving.Engine(prefill, step, cfg)
    futs = [eng.submit(serving.GenerationRequest(p, max_new_tokens=4))
            for p in prompts]
    eng.run()
    for p, f in zip(prompts, futs):
        assert f.result(timeout=30).tokens == dense(p, 4)


def _smoke_serving_drain():
    # the online-shutdown contract on the real chip: a live start() loop
    # decoding on-device must stop(drain=True) with every Future resolved,
    # every page back in the pool, and a second stop() a no-op — the
    # graceful-drain path does its compiled steps from the background
    # thread, which is exactly the surface the offline run() drain skips
    import jax.numpy as jnp
    from paddle_tpu import serving
    from paddle_tpu.core.tensor import Tensor as T

    L = H = 1
    D, M, V = 8, 32, 13
    ramp = (jnp.arange(D, dtype=jnp.float32) + 1.0) / D

    def step(tok, cache, t):
        c = cache._data
        nxt = (tok._data[:, 0] * 7 + t._data.astype(jnp.int32)) % V
        kv = ((nxt.astype(jnp.float32) + 1.0) / V)[:, None] * ramp
        c = c + 0.0 * kv.sum()          # touch the cache: keep the gather/
        return T(nxt[:, None].astype(jnp.int32)), T(c)  # scatter leg live

    def prefill(ids, cache):
        nxt = (ids._data.sum(axis=1).astype(jnp.int32)) % V
        return T(nxt[:, None]), T(cache._data)

    cfg = serving.ServingConfig(num_layers=L, num_heads=H, head_dim=D,
                                max_len=M, max_batch=2, buckets=(1, 2),
                                page_size=8, max_queue=8,
                                paged_attention="off")
    eng = serving.Engine(prefill, step, cfg).warmup()
    prompts = [np.arange(6, dtype=np.int32) % V,
               (np.arange(6, dtype=np.int32) * 5) % V]
    eng.start()
    import threading
    admitted = threading.Event()
    first = set()

    def on_tok(rid, _tok):
        first.add(rid)
        if len(first) >= len(prompts):
            admitted.set()

    futs = [eng.submit(serving.GenerationRequest(
                p, max_new_tokens=6, stream=on_tok)) for p in prompts]
    # drain finishes IN-FLIGHT work only (queued requests resolve
    # EngineStopped): wait for both to hold slots before shutting down
    assert admitted.wait(timeout=60)
    eng.stop(drain=True, timeout=60)
    eng.stop(drain=True, timeout=1)      # idempotent
    for f in futs:
        assert f.done()
        res = f.result(timeout=0)
        assert len(res.tokens) == 6 and res.finish_reason == "length"
    assert eng.kv.outstanding_pages == 0
    assert eng.active_requests == 0 and eng.queue_depth == 0


def _smoke_paged_attention():
    # the paged-attention decode kernel COMPILED (not interpreted) on the
    # real chip, pinned against the per-layer dense reference on both kv
    # storage legs — bf16 near-ulp, int8 bit-identical dequant grid
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.serving.kv_cache import quantize_pages

    rng = np.random.default_rng(0)
    B, H, D, ps, S, L = 2, 2, 128, 32, 3, 2
    P = 8
    assert pa.kernel_eligible(ps, D, jnp.bfloat16)
    assert pa.kernel_eligible(ps, D, jnp.int8)
    poolf = jnp.asarray(rng.standard_normal((P, L, 2, H, ps, D)),
                        jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    t = jnp.asarray([2 * ps + 5, ps - 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    layer = jnp.asarray(1, jnp.int32)
    q8, sc = quantize_pages(poolf)
    for pool, scales, tol in ((poolf.astype(jnp.bfloat16), None, 2e-2),
                              (q8, sc, 2e-2)):
        got = pa.paged_attention(q, kn, vn, pool, scales, tables, t,
                                 layer, page_size=ps, impl="kernel",
                                 interpret=False)
        want = pa.paged_attention_dense(q, kn, vn, pool, scales, tables,
                                        t, layer, page_size=ps)
        err = float(np.abs(np.asarray(got, np.float32)
                           - np.asarray(want, np.float32)).max())
        assert err <= tol, (pool.dtype, err)


def _smoke_strided():
    import paddle_tpu as paddle
    t = _t(np.arange(12, dtype="float32").reshape(3, 4))
    assert t.strides == [4, 1] and t.is_contiguous()
    out = paddle.as_strided(t, [2, 2], [4, 1]).numpy()
    np.testing.assert_allclose(out, [[0.0, 1.0], [4.0, 5.0]])


def _smoke_tensor_array():
    import paddle_tpu as paddle
    arr = paddle.tensor.create_array("float32")
    i = paddle.zeros([1], dtype="int64")
    paddle.tensor.array_write(_t([1.0, 2.0]), i, arr)
    out = paddle.tensor.array_read(arr, i).numpy()
    np.testing.assert_allclose(out, [1.0, 2.0])


SMOKE = {name[len("_smoke_"):]: fn for name, fn in list(globals().items())
         if name.startswith("_smoke_")}


def test_smoke_covers_every_family():
    """Tier-1 (CPU) rot gate: every family derivable from ops.yaml has a
    smoke entry, and the tier is big enough to mean something."""
    missing = sorted(set(FAMILIES) - set(SMOKE))
    assert not missing, (
        f"op families with no on-chip smoke test: {missing} — add a "
        f"_smoke_<family>() fn to tests/test_tpu_smoke.py")
    assert len(FAMILIES) >= 26, FAMILIES


@pytest.mark.tpu
@pytest.mark.parametrize("family", FAMILIES)
def test_family_smoke(family):
    SMOKE[family]()
