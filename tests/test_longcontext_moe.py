"""Ring attention / Ulysses / flash attention / MoE tests (8-dev CPU mesh)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


@pytest.fixture(autouse=True)
def _reset_topology():
    import paddle_tpu.distributed.topology as topo
    import paddle_tpu.distributed.fleet as fleet_mod
    saved = topo._hcg
    yield
    topo._hcg = saved
    fleet_mod._fleet_initialized = False


def _sep_mesh(sep=8):
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "sep_degree": sep}
    fleet.init(strategy=strategy)


def _ref_attention(q, k, v, causal):
    import jax, jax.numpy as jnp
    qh = np.swapaxes(q, 1, 2).astype(np.float32)
    kh = np.swapaxes(k, 1, 2).astype(np.float32)
    vh = np.swapaxes(v, 1, 2).astype(np.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = np.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        L = logits.shape[-1]
        mask = np.tril(np.ones((L, L), bool))
        logits = np.where(mask, logits, -1e30)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bhkd->bhqd", p, vh)
    return np.swapaxes(out, 1, 2)


def test_flash_attention_matches_reference():
    paddle.seed(0)
    B, L, H, D = 2, 128, 2, 16
    q = paddle.randn([B, L, H, D])
    k = paddle.randn([B, L, H, D])
    v = paddle.randn([B, L, H, D])
    for causal in (False, True):
        out = nn.functional.flash_attention(q, k, v, causal=causal)
        ref = _ref_attention(q.numpy(), k.numpy(), v.numpy(), causal)
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)


def _ref_attention_seg(q, k, v, causal, q_segs, kv_segs):
    """Masked reference: rows attend only same-segment keys (flash
    convention: fully-masked rows emit 0)."""
    qh = np.swapaxes(q, 1, 2).astype(np.float64)
    kh = np.swapaxes(k, 1, 2).astype(np.float64)
    vh = np.swapaxes(v, 1, 2).astype(np.float64)
    s = np.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(q.shape[-1])
    keep = (q_segs[:, None, :, None] == kv_segs[:, None, None, :])
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        keep = keep & np.tril(np.ones((lq, lk), bool), k=lk - lq)
    s = np.where(keep, s, -1e30)
    e = np.exp(s - s.max(-1, keepdims=True))
    denom = e.sum(-1, keepdims=True)
    p = np.where(keep.any(-1, keepdims=True), e / np.maximum(denom, 1e-300), 0.0)
    out = np.einsum("bhqk,bhkd->bhqd", p, vh)
    return np.swapaxes(out, 1, 2).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_mask_matches_reference(causal):
    """Padding/packed masks via segment ids stay on the flash kernel
    (interpret mode on CPU) and match the masked softmax reference —
    forward AND gradients (VERDICT r3 item 3)."""
    paddle.seed(1)
    B, L, H, D = 2, 256, 2, 16
    rng = np.random.default_rng(3)
    qn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
    kn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
    vn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
    # batch 0: two packed sequences; batch 1: one sequence + padding tail
    segs = np.zeros((B, L), np.int32)
    segs[0, : L // 2] = 1
    segs[0, L // 2:] = 2
    segs[1, : 3 * L // 4] = 1
    segs[1, 3 * L // 4:] = 0  # padding id (q rows there are don't-care)

    q = paddle.to_tensor(qn); q.stop_gradient = False
    k = paddle.to_tensor(kn); k.stop_gradient = False
    v = paddle.to_tensor(vn); v.stop_gradient = False
    st = paddle.to_tensor(segs)
    out = nn.functional.flash_attention(q, k, v, causal=causal,
                                        q_segment_ids=st, kv_segment_ids=st)
    ref = _ref_attention_seg(qn, kn, vn, causal, segs, segs)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)

    # gradients: finite + match AD through the masked XLA reference
    out.sum().backward()
    import jax.numpy as jnp
    import jax

    def ref_jax(qa, ka, va):
        from paddle_tpu.ops.flash_attention import _xla_attention
        o = _xla_attention(jnp.swapaxes(qa, 1, 2), jnp.swapaxes(ka, 1, 2),
                           jnp.swapaxes(va, 1, 2), causal,
                           1.0 / np.sqrt(D), jnp.asarray(segs),
                           jnp.asarray(segs))
        return jnp.swapaxes(o, 1, 2).sum()

    gq, gk, gv = jax.grad(ref_jax, argnums=(0, 1, 2))(qn, kn, vn)
    np.testing.assert_allclose(q.grad.numpy(), np.asarray(gq), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(k.grad.numpy(), np.asarray(gk), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(v.grad.numpy(), np.asarray(gv), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attn_unpadded_packed_sequences(causal):
    """flash_attn_unpadded: packed (total, H, D) + cu_seqlens == looping the
    per-sequence attention (the upstream varlen contract)."""
    paddle.seed(2)
    H, D = 2, 16
    lens = [128, 256, 128]  # 128-aligned total keeps the kernel path
    total = sum(lens)
    rng = np.random.default_rng(4)
    qn = rng.normal(0, 1, (total, H, D)).astype(np.float32)
    kn = rng.normal(0, 1, (total, H, D)).astype(np.float32)
    vn = rng.normal(0, 1, (total, H, D)).astype(np.float32)
    cu = np.cumsum([0] + lens).astype(np.int32)

    out = nn.functional.flash_attn_unpadded(
        paddle.to_tensor(qn), paddle.to_tensor(kn), paddle.to_tensor(vn),
        paddle.to_tensor(cu), paddle.to_tensor(cu), max(lens), max(lens),
        causal=causal)
    got = out.numpy()

    for i in range(len(lens)):
        s, e = cu[i], cu[i + 1]
        ref = _ref_attention(qn[None, s:e], kn[None, s:e], vn[None, s:e],
                             causal)[0]
        np.testing.assert_allclose(got[s:e], ref, rtol=1e-4, atol=1e-5,
                                   err_msg=f"sequence {i}")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_serial(causal):
    from paddle_tpu.distributed.fleet.context_parallel import ring_flash_attention
    _sep_mesh(8)
    paddle.seed(1)
    B, L, H, D = 1, 64, 2, 16  # L=64 over 8 devices -> 8 per shard
    q = paddle.randn([B, L, H, D])
    k = paddle.randn([B, L, H, D])
    v = paddle.randn([B, L, H, D])
    out = ring_flash_attention(q, k, v, causal=causal)
    ref = _ref_attention(q.numpy(), k.numpy(), v.numpy(), causal)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_grad_flows():
    from paddle_tpu.distributed.fleet.context_parallel import ring_flash_attention
    _sep_mesh(8)
    paddle.seed(2)
    q = paddle.randn([1, 32, 2, 8])
    q.stop_gradient = False
    k = paddle.randn([1, 32, 2, 8])
    v = paddle.randn([1, 32, 2, 8])
    out = ring_flash_attention(q, k, v, causal=True)
    out.sum().backward()
    assert q.grad is not None
    assert np.isfinite(q.grad.numpy()).all()


def test_ulysses_matches_serial():
    from paddle_tpu.distributed.fleet.context_parallel import ulysses_attention
    _sep_mesh(8)
    paddle.seed(3)
    B, L, H, D = 1, 64, 8, 16  # H=8 divisible by sep=8
    q = paddle.randn([B, L, H, D])
    k = paddle.randn([B, L, H, D])
    v = paddle.randn([B, L, H, D])
    out = ulysses_attention(q, k, v, causal=True)
    ref = _ref_attention(q.numpy(), k.numpy(), v.numpy(), True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_moe_forward_and_grad():
    paddle.seed(4)
    from paddle_tpu.incubate.moe import MoELayer
    d = 16
    experts = [nn.Sequential(nn.Linear(d, 32), nn.ReLU(), nn.Linear(32, d))
               for _ in range(4)]
    moe = MoELayer(d_model=d, experts=experts, gate={"type": "gshard", "top_k": 2})
    x = paddle.randn([2, 8, d])
    x.stop_gradient = False
    out = moe(x)
    assert out.shape == [2, 8, d]
    loss = out.sum() + 0.01 * moe.l_aux
    loss.backward()
    assert x.grad is not None
    # gate weights learn
    assert moe.gate.gate_proj.weight.grad is not None
    # most tokens routed (combine weights not all zero)
    assert float(paddle.abs(out).sum()) > 0


@pytest.mark.slow
def test_moe_switch_gate():
    paddle.seed(5)
    from paddle_tpu.incubate.moe import MoELayer
    d = 8
    experts = [nn.Linear(d, d) for _ in range(2)]
    moe = MoELayer(d_model=d, experts=experts, gate={"type": "switch"})
    out = moe(paddle.randn([4, 4, d]))
    assert out.shape == [4, 4, d]


@pytest.mark.slow
class TestFlashBackwardKernel:
    """The dedicated Pallas dq/dkv backward (recompute-from-lse) must match
    the XLA attention vjp exactly (reference invariant: flash_attn_grad
    kernels vs softmax attention AD)."""

    @pytest.mark.parametrize("lq,lk,causal", [(256, 256, True),
                                              (256, 256, False),
                                              (128, 256, True),
                                              (512, 512, True)])
    def test_bwd_matches_xla(self, lq, lk, causal):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.flash_attention import (_flash_core,
                                                    _xla_attention)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 3, lq, 64)).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.normal(size=(2, 3, lk, 64)).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.normal(size=(2, 3, lk, 64)).astype(np.float32) * 0.3)
        g = jnp.asarray(rng.normal(size=(2, 3, lq, 64)).astype(np.float32))
        sm = 1.0 / 8.0
        out_r, vjp_r = jax.vjp(
            lambda a, b, c: _xla_attention(a, b, c, causal, sm), q, k, v)
        out, vjp = jax.vjp(
            lambda a, b, c: _flash_core(a, b, c, causal, sm), q, k, v)
        assert float(jnp.abs(out - out_r).max()) < 1e-5
        for got, ref in zip(vjp(g), vjp_r(g)):
            assert float(jnp.abs(got - ref).max()) < 1e-4


class TestFlashTileFitting:
    def test_fit_block_divisors(self):
        from paddle_tpu.ops.flash_attention import _fit_block, _pallas_tileable
        assert _fit_block(1024, 512) == 512
        assert _fit_block(768, 512) == 384   # largest 128-multiple divisor
        assert _fit_block(1280, 512) == 256
        assert _fit_block(256, 512) == 256   # short seq: one full block
        # sub-128 sequences are NOT pallas-tileable: the backward kernels
        # slice lse/delta along the lane dim, which real-TPU Mosaic
        # requires 128-aligned (found on-chip by bench --smoke)
        assert _fit_block(96, 512) is None
        # unaligned lengths stay off the Pallas path (XLA fallback)
        assert _fit_block(1000, 512) is None
        assert _fit_block(1001, 512) is None
        assert _pallas_tileable(768, 768, 64, 512, 512)
        assert not _pallas_tileable(1000, 1000, 64, 512, 512)

    @pytest.mark.slow
    def test_mid_range_length_matches_xla(self):
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        np.random.seed(0)
        q = paddle.to_tensor(np.random.randn(1, 768, 4, 16).astype("float32"),
                             stop_gradient=False)
        out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        out.sum().backward()
        g_flash = np.asarray(q.grad.numpy()).copy()
        q2 = paddle.to_tensor(q.numpy(), stop_gradient=False)
        paddle.set_flags({"FLAGS_flash_impl": "xla"})
        try:
            out2 = F.scaled_dot_product_attention(q2, q2, q2, is_causal=True)
            out2.sum().backward()
        finally:
            paddle.set_flags({"FLAGS_flash_impl": "pallas"})
        np.testing.assert_allclose(out.numpy(), out2.numpy(), atol=2e-3)
        np.testing.assert_allclose(g_flash, np.asarray(q2.grad.numpy()),
                                   atol=2e-3)


class TestFusedEcMoe:
    def test_expert_choice_forward_backward(self):
        import paddle_tpu.incubate.nn as inn

        paddle.seed(0)
        moe = inn.FusedEcMoe(16, 32, num_experts=4)
        gate_proj = paddle.nn.Linear(16, 4)
        x = paddle.to_tensor(np.random.default_rng(0).normal(
            0, 1, (2, 8, 16)).astype(np.float32))
        x.stop_gradient = False
        out = moe(x, gate_proj(x))  # upstream signature: (x, gate logits)
        assert out.shape == [2, 8, 16]
        out.sum().backward()
        assert moe.w0.grad is not None and x.grad is not None
        assert gate_proj.weight.grad is not None  # gate grads flow to caller
        # balanced by construction: every expert processes exactly
        # capacity = T/E tokens, so all expert weights receive gradient
        assert float(np.abs(moe.w1.grad.numpy()).sum(axis=(1, 2)).min()) > 0
        with pytest.raises(ValueError):
            inn.FusedEcMoe(16, 32, 4, bias_attr=False)

    def test_fused_dropout_add(self):
        import paddle_tpu.incubate.nn as inn

        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        da = inn.FusedDropoutAdd(p=0.0)
        np.testing.assert_allclose(da(x, x).numpy(), 2.0)
        da_train = inn.FusedDropoutAdd(p=0.5)
        da_train.train()
        y = da_train(x, x).numpy()
        # residual always survives; dropped positions equal 1.0 exactly
        assert set(np.round(np.unique(y), 4)).issubset({1.0, 3.0})


class TestFlashDropout:
    """Round 5: attention-prob dropout runs IN the Pallas kernels (keep
    mask = stateless hash of absolute coordinates, regenerated by the
    backward) instead of falling back to materialized XLA attention."""

    def _qkv(self, L=256, B=2, H=2, D=16):
        paddle.seed(7)
        return (paddle.randn([B, L, H, D]), paddle.randn([B, L, H, D]),
                paddle.randn([B, L, H, D]))

    @pytest.mark.slow
    def test_dropout_statistical_parity(self):
        """E[dropout attention] == no-dropout attention: average over many
        seeds converges to the clean output (unbiasedness of the
        normalized-prob dropout formulation)."""
        q, k, v = self._qkv()
        clean = nn.functional.flash_attention(q, k, v, causal=True).numpy()
        acc = np.zeros_like(clean, dtype=np.float64)
        n = 24
        for s in range(n):
            out = nn.functional.flash_attention(
                q, k, v, dropout=0.3, causal=True, training=True,
                fixed_seed_offset=paddle.to_tensor([1000 + s], dtype="int32"))
            acc += out.numpy().astype(np.float64)
        mean = acc / n
        # elementwise SEM is large for p=0.3, n=24; compare on aggregate
        err = np.abs(mean - clean).mean() / (np.abs(clean).mean() + 1e-9)
        assert err < 0.15, err

    def test_dropout_deterministic_in_seed(self):
        q, k, v = self._qkv()
        kw = dict(dropout=0.2, causal=True, training=True)
        a = nn.functional.flash_attention(
            q, k, v, fixed_seed_offset=paddle.to_tensor([5], "int32"), **kw)
        b = nn.functional.flash_attention(
            q, k, v, fixed_seed_offset=paddle.to_tensor([5], "int32"), **kw)
        c = nn.functional.flash_attention(
            q, k, v, fixed_seed_offset=paddle.to_tensor([6], "int32"), **kw)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        assert np.abs(a.numpy() - c.numpy()).max() > 0

    def test_dropout_actually_drops(self):
        """Output must differ from the clean path and zero out some
        contributions (not a silent no-op)."""
        q, k, v = self._qkv()
        clean = nn.functional.flash_attention(q, k, v, causal=False).numpy()
        out = nn.functional.flash_attention(
            q, k, v, dropout=0.5, causal=False, training=True,
            fixed_seed_offset=paddle.to_tensor([3], "int32")).numpy()
        assert np.abs(out - clean).max() > 1e-3
        # eval mode: dropout off regardless
        ev = nn.functional.flash_attention(
            q, k, v, dropout=0.5, causal=False, training=False).numpy()
        np.testing.assert_allclose(ev, clean, rtol=1e-5, atol=1e-6)

    def test_dropout_grad_flows_and_matches_fallback(self):
        """Gradients through the kernel dropout path match AD through the
        XLA fallback formulation with the SAME mask — the backward's
        regenerated mask is the forward's."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.flash_attention import (_flash_core_drop,
                                                    _keep_tile)

        rng = np.random.default_rng(3)
        B, H, L, D = 1, 2, 256, 16
        q = jnp.asarray(rng.normal(0, 1, (B, H, L, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(0, 1, (B, H, L, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(0, 1, (B, H, L, D)).astype(np.float32))
        segs = jnp.zeros((B, L), jnp.int32)
        seed = jnp.asarray([11], jnp.int32)
        p_drop, scale = 0.25, 1.0 / np.sqrt(D)

        def kernel_loss(q, k, v):
            out = _flash_core_drop(q, k, v, segs, segs, seed, True, scale,
                                   p_drop)
            return (out * out).sum()

        def ref_loss(q, k, v):
            # same math, dense: softmax then the SAME hash mask
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            mask = jnp.tril(jnp.ones((L, L), bool))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            # bh index: the kernel grid maps (batch*head) to program_id(0)
            keeps = [
                _keep_tile(seed[0], bh, 0, 0, L, L, 1.0 - p_drop)
                for bh in range(B * H)]
            keep = jnp.stack(keeps).reshape(B, H, L, L)
            pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
            out = jnp.einsum("bhqk,bhkd->bhqd", pd, v)
            return (out * out).sum()

        lk, gk = jax.value_and_grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
        lr, gr = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(lk), float(lr), rtol=2e-4)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)

    def test_unpadded_dropout_stays_streaming(self):
        """flash_attn_unpadded with dropout routes the drop core (not the
        materializing parity path) and stays deterministic in the seed."""
        paddle.seed(1)
        total, H, D = 256, 2, 16
        q = paddle.randn([total, H, D])
        k = paddle.randn([total, H, D])
        v = paddle.randn([total, H, D])
        cu = paddle.to_tensor(np.array([0, 100, 256], np.int32))
        kw = dict(cu_seqlens_q=cu, cu_seqlens_k=cu, max_seqlen_q=156,
                  max_seqlen_k=156, dropout=0.2, causal=True, training=True)
        a = nn.functional.flash_attn_unpadded(
            q, k, v, fixed_seed_offset=paddle.to_tensor([9], "int32"), **kw)
        b = nn.functional.flash_attn_unpadded(
            q, k, v, fixed_seed_offset=paddle.to_tensor([9], "int32"), **kw)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        assert np.isfinite(a.numpy()).all()


class TestSDPADropoutRouting:
    """ISSUE 7 satellite (VERDICT r5 Weak #1): training-time dropout through
    ``scaled_dot_product_attention`` now stays on the flash kernel — the
    stale predicate that re-routed it to stored-probs XLA attention
    (re-materializing (Lq, Lk) probs, OOM at seq 8192) is gone."""

    def _qkv(self, B=2, L=128, H=2, D=16):
        paddle.seed(7)
        return (paddle.randn([B, L, H, D]), paddle.randn([B, L, H, D]),
                paddle.randn([B, L, H, D]))

    def _accel(self, monkeypatch):
        # SDPA keeps the fused XLA path on CPU hosts; flip only the ROUTING
        # predicate so the decision is exercised (the Pallas kernel itself
        # still runs in interpret mode here — same code path, same mask hash)
        import paddle_tpu.ops.nn_ops as nn_ops
        monkeypatch.setattr(nn_ops, "_sdpa_flash_backend_ok", lambda: True)

    def test_training_dropout_routes_to_flash_kernel(self, monkeypatch):
        """SDPA(dropout_p>0, training=True) == flash_attention(dropout=…)
        under the same generator state — only the in-kernel dropout path
        can reproduce the stateless coordinate-hash mask bit-exactly."""
        self._accel(monkeypatch)
        q, k, v = self._qkv()
        paddle.seed(123)
        out = nn.functional.scaled_dot_product_attention(
            q, k, v, dropout_p=0.25, is_causal=True, training=True)
        paddle.seed(123)
        ref = nn.functional.flash_attention(
            q, k, v, dropout=0.25, causal=True, training=True)
        np.testing.assert_array_equal(out.numpy(), ref.numpy())
        # and it actually dropped (not silently returning clean attention)
        clean = nn.functional.flash_attention(q, k, v, causal=True)
        assert np.abs(out.numpy() - clean.numpy()).max() > 1e-3

    def test_eval_mode_dropout_is_inert(self, monkeypatch):
        self._accel(monkeypatch)
        q, k, v = self._qkv()
        out = nn.functional.scaled_dot_product_attention(
            q, k, v, dropout_p=0.25, is_causal=True, training=False)
        ref = nn.functional.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5,
                                   atol=1e-6)

    def test_sdpa_dropout_grad_matches_dense_ad(self, monkeypatch):
        """Dense-AD parity THROUGH the public SDPA surface: backward of the
        routed kernel-dropout path equals jax AD through the dense softmax
        formulation with the SAME regenerated keep mask."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.flash_attention import (_dropout_seed,
                                                    _keep_tile)
        self._accel(monkeypatch)
        B, L, H, D = 1, 128, 2, 16
        rng = np.random.default_rng(5)
        qn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
        kn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
        vn = rng.normal(0, 1, (B, L, H, D)).astype(np.float32)
        p_drop, scale = 0.25, 1.0 / np.sqrt(D)

        # capture the seed SDPA will draw, then rewind the generator
        paddle.seed(77)
        seed = int(np.asarray(_dropout_seed(None)._data)[0])
        paddle.seed(77)

        q = paddle.to_tensor(qn, stop_gradient=False)
        k = paddle.to_tensor(kn, stop_gradient=False)
        v = paddle.to_tensor(vn, stop_gradient=False)
        out = nn.functional.scaled_dot_product_attention(
            q, k, v, dropout_p=p_drop, is_causal=True, training=True)
        (out * out).sum().backward()

        def ref_loss(qh, kh, vh):
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            keep = jnp.stack([
                _keep_tile(jnp.asarray(seed, jnp.int32), bh, 0, 0, L, L,
                           1.0 - p_drop)
                for bh in range(B * H)]).reshape(B, H, L, L)
            pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
            o = jnp.einsum("bhqk,bhkd->bhqd", pd, vh)
            return (o * o).sum()

        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(
            jnp.asarray(qn.transpose(0, 2, 1, 3)),
            jnp.asarray(kn.transpose(0, 2, 1, 3)),
            jnp.asarray(vn.transpose(0, 2, 1, 3)))
        for got, ref in zip((q.grad, k.grad, v.grad), gr):
            np.testing.assert_allclose(
                got.numpy(), np.asarray(ref).transpose(0, 2, 1, 3),
                rtol=2e-3, atol=2e-4)
