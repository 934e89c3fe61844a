"""Reduced-footprint optimizer state (the ≥1.5B-on-chip enabler).

bf16 m/v accumulators and master-weight-free bf16 AdamW (stochastic
rounding) must track the fp32-state trajectory — the loss-parity contract
that converts "halve the optimizer memory" from a flag into a usable
training mode. Reference keeps fp32 m/v + masters unconditionally
(upstream python/paddle/optimizer/adam.py, python/paddle/amp/); the narrow
variants are the TPU-native extension SURVEY §6's north star needs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn

D = 16


def _data(steps=24, batch=16):
    rng = np.random.default_rng(0)
    w_true = rng.normal(0, 1, (D, 1)).astype(np.float32)
    xs = rng.normal(0, 1, (steps, batch, D)).astype(np.float32)
    ys = xs @ w_true + 0.01 * rng.normal(0, 1, (steps, batch, 1)).astype(np.float32)
    return xs, ys


def _train(moment_dtype="float32", master=None, sr=True, fused=False,
           cast_bf16=False, steps=24, seed=5):
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(D, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-2, parameters=model.parameters(),
        use_multi_tensor=fused, moment_dtype=moment_dtype,
        use_master_weights=master, stochastic_rounding=sr)
    if cast_bf16:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16",
                                         master_weight=master)
    xs, ys = _data(steps)
    losses = []
    for i in range(steps):
        x, y = paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i])
        if cast_bf16:
            with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                out = model(x)
            loss = ((out.astype("float32") - y) ** 2).mean()
        else:
            loss = ((model(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return np.asarray(losses), opt


def test_bf16_moments_track_fp32_trajectory():
    ref, _ = _train(moment_dtype="float32")
    lo, opt = _train(moment_dtype="bfloat16")
    assert lo[-1] < 0.1 * lo[0], "bf16-moment training must converge"
    # trajectories stay in the same neighborhood throughout
    np.testing.assert_allclose(lo, ref, rtol=0.25, atol=0.02)
    # and the state really is narrow
    m = next(iter(opt._accumulators["moment1"].values()))
    assert m._data.dtype == jnp.bfloat16


def test_bf16_moments_track_fp32_trajectory_fused():
    ref, _ = _train(moment_dtype="float32", fused=True)
    lo, opt = _train(moment_dtype="bfloat16", fused=True)
    assert lo[-1] < 0.1 * lo[0]
    np.testing.assert_allclose(lo, ref, rtol=0.25, atol=0.02)
    assert opt._fused["m"]._data.dtype == jnp.bfloat16
    assert opt._fused["v"]._data.dtype == jnp.bfloat16


def test_master_free_bf16_matches_mastered_bf16():
    """The headline mode: bf16 params, NO fp32 masters, stochastic
    rounding. Must land in the same loss neighborhood as the master-weight
    run (the reference-equivalent baseline)."""
    ref, ref_opt = _train(cast_bf16=True, master=True)
    assert len(ref_opt._master_weights) > 0
    lo, opt = _train(cast_bf16=True, master=False, moment_dtype="bfloat16")
    assert len(opt._master_weights) == 0, "masters must not exist"
    assert lo[-1] < 0.15 * lo[0], "master-free bf16 training must converge"
    np.testing.assert_allclose(lo, ref, rtol=0.35, atol=0.05)


def test_master_free_fused_flat_buffer_is_bf16():
    lo, opt = _train(cast_bf16=True, master=False, moment_dtype="bfloat16",
                     fused=True)
    fs = opt._fused
    assert fs["master"]._data.dtype == jnp.bfloat16
    assert fs["m"]._data.dtype == jnp.bfloat16
    assert lo[-1] < 0.15 * lo[0]
    # total optimizer-state bytes: 3 bf16 buffers (flat, m, v) = 6 B/param
    per_param = sum(b._data.dtype.itemsize
                    for b in (fs["master"], fs["m"], fs["v"]))
    assert per_param == 6


@pytest.mark.slow
def test_master_free_without_sr_stalls_where_sr_learns():
    """Proof stochastic rounding is load-bearing: with a small LR the
    deterministic bf16 write-back loses sub-ulp updates and learns slower
    than SR over the same schedule."""
    paddle.seed(9)
    # single weight, tiny gradient updates relative to bf16 ulp at |w|~1
    w_sr = None
    outs = {}
    for sr in (True, False):
        paddle.seed(9)
        model = nn.Linear(1, 1, bias_attr=False)
        model.weight._set_data(jnp.asarray([[1.0]], jnp.bfloat16))
        opt = paddle.optimizer.SGD(learning_rate=1.0,
                                   parameters=model.parameters())
        opt._use_master_weights = False
        opt._stochastic_rounding = sr
        # constant tiny gradient: 1e-4 ≈ ulp(1.0)/80 for bf16
        for _ in range(4000):
            model.weight._grad = None
            model.weight.grad  # ensure attribute exists

            g = jnp.asarray([[1e-4]], jnp.bfloat16)
            from paddle_tpu.core.tensor import Tensor
            model.weight._grad = Tensor(g, stop_gradient=True)
            opt.step()
        outs[sr] = float(np.asarray(model.weight._data.astype(jnp.float32)))
    # deterministic rounding: w + 1e-4 rounds back to w every step
    assert abs(outs[False] - 1.0) < 1e-6
    # SR: E[delta] = -lr*g per step -> ~0.4 drop over 4000 steps
    assert outs[True] < 0.8


def test_stochastic_round_exact_values_unchanged():
    from paddle_tpu.optimizer import _stochastic_round_bf16
    exact = jnp.asarray([1.0, -2.5, 0.0, 3.140625], jnp.bfloat16)
    x32 = exact.astype(jnp.float32)
    for s in range(5):
        out = _stochastic_round_bf16(x32, jax.random.PRNGKey(s))
        np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                      np.asarray(x32))


def test_stochastic_round_is_unbiased():
    from paddle_tpu.optimizer import _stochastic_round_bf16
    # bf16 ulp at 1.0 is 2^-7 (7 mantissa bits); x = 1 + ulp/4 must round
    # up a quarter of the time, keeping E[out] = x
    ulp = 2.0 ** -7
    x = jnp.full((1 << 16,), 1.0 + 0.25 * ulp, jnp.float32)
    out = _stochastic_round_bf16(x, jax.random.PRNGKey(0)).astype(jnp.float32)
    frac_up = float(np.mean(np.asarray(out) > 1.0))
    assert 0.22 < frac_up < 0.28, frac_up
    mean = float(np.mean(np.asarray(out)))
    np.testing.assert_allclose(mean, 1.0 + 0.25 * ulp, rtol=3e-4)


def test_reduced_state_survives_to_static():
    """Whole-step compiled training with bf16 moments + master-free bf16
    params — the exact bench configuration — must run and learn."""
    paddle.seed(4)
    model = nn.Sequential(nn.Linear(D, 32), nn.Tanh(), nn.Linear(32, 1))
    opt = paddle.optimizer.AdamW(learning_rate=3e-2,
                                 parameters=model.parameters(),
                                 moment_dtype="bfloat16",
                                 use_master_weights=False)
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16", master_weight=False)
    xs, ys = _data(20)

    @paddle.jit.to_static
    def step(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            out = model(x)
        loss = ((out.astype("float32") - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    losses = [float(step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i])))
              for i in range(20)]
    assert losses[-1] < 0.2 * losses[0], losses


def test_int8_moments_track_fp32_trajectory():
    """8-bit block-quantized m/v (the bitsandbytes layout): trajectory in
    the fp32 neighborhood, state physically int8."""
    ref, _ = _train(moment_dtype="float32")
    lo, opt = _train(moment_dtype="int8")
    assert lo[-1] < 0.15 * lo[0], "int8-moment training must converge"
    np.testing.assert_allclose(lo, ref, rtol=0.35, atol=0.05)
    m = next(iter(opt._accumulators["moment1"].values()))
    assert m._data.dtype == jnp.int8
    s = next(iter(opt._accumulators["moment1_scale"].values()))
    assert s._data.dtype == jnp.float32


def test_int8_moments_master_free_end_to_end():
    lo, opt = _train(cast_bf16=True, master=False, moment_dtype="int8")
    assert len(opt._master_weights) == 0
    assert lo[-1] < 0.2 * lo[0], lo


def test_int8_rejects_fused_path():
    paddle.seed(3)
    m = nn.Linear(4, 4)
    with pytest.raises(ValueError, match="int8"):
        paddle.optimizer.AdamW(parameters=m.parameters(),
                               use_multi_tensor=True, moment_dtype="int8")


def test_q8_quantize_roundtrip():
    from paddle_tpu.optimizer import _q8_dequantize, _q8_quantize
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (1000,)).astype(np.float32) *
                    rng.uniform(0.001, 10, (1000,)).astype(np.float32))
    q, s = _q8_quantize(x)
    back = _q8_dequantize(q, s, (1000,))
    # per-block absmax: error bounded by absmax/254 per block
    err = np.abs(np.asarray(back - x))
    assert err.max() <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_q8_chunked_update_matches_single_chunk():
    """Round-4: the int8 update runs per-chunk under lax.map (so fp32
    transients stay O(chunk) at the 2B single-chip ceiling). Multi-chunk
    (tiny _Q8_CHUNK_ELEMS) must match the single-chunk trajectory — the
    blockwise quantization math is chunk-shape invariant, pinned BITWISE
    on the int8 moment state below. The fp32 weights get a few-ulp
    allowance: XLA does not promise identical fusion/fma ordering between
    a lax.map body and the equivalent straight-line program, and some CPU
    backends (this container's jax 0.4.37 among them) produce 1-ulp
    differences in the weight-update arithmetic."""
    import paddle_tpu.optimizer as optim

    def run(chunk_elems):
        paddle.seed(11)
        model = nn.Linear(64, 96)  # 6144 weights -> 3 blocks of 2048
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters(),
                                     moment_dtype="int8",
                                     stochastic_rounding=False)
        old = optim.Adam._Q8_CHUNK_ELEMS
        optim.Adam._Q8_CHUNK_ELEMS = chunk_elems
        try:
            x = paddle.to_tensor(
                np.random.default_rng(5).normal(0, 1, (8, 64))
                .astype(np.float32))
            for _ in range(4):
                loss = (model(x) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
        finally:
            optim.Adam._Q8_CHUNK_ELEMS = old
        return (np.asarray(model.weight._data.astype(jnp.float32)),
                np.asarray(next(iter(
                    opt._accumulators["moment1"].values()))._data))

    w_multi, m_multi = run(2048)          # 1 block/chunk -> 3 chunks
    w_single, m_single = run(8 * 1024 * 1024)  # everything in one chunk
    np.testing.assert_allclose(w_multi, w_single, rtol=0, atol=6e-8)
    np.testing.assert_array_equal(m_multi, m_single)


def test_q8_legacy_linear_v_checkpoint_converts_on_load():
    """Round-3 int8 checkpoints stored moment2 as LINEAR v; the current
    layout stores sqrt(v) under the versioned key moment2_sqrt. Loading a
    legacy dict must convert (binding raw would shrink v ~1000x)."""
    paddle.seed(13)
    model = nn.Linear(64, 32)
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters(),
                                 moment_dtype="int8",
                                 stochastic_rounding=False)
    p = model.weight
    n = p.size
    nb = -(-n // 2048)
    rng = np.random.default_rng(0)
    v_true = (rng.uniform(0.001, 1.0, (nb * 2048,)) ** 2).astype(np.float32)
    blocks = v_true.reshape(nb, 2048)
    scale = np.abs(blocks).max(1) / 127.0
    q_lin = np.clip(np.round(blocks / scale[:, None]), -127, 127) \
        .astype(np.int8)
    legacy = {
        "step": 3,
        f"{p.name}_moment2": paddle.to_tensor(q_lin),
        f"{p.name}_moment2_scale": paddle.to_tensor(scale.astype(np.float32)),
    }
    with pytest.warns(UserWarning, match="sqrt-space"):
        opt.set_state_dict(legacy)
    assert "moment2" not in opt._accumulators
    q = opt._accumulators["moment2_sqrt"][id(p)]._data
    s = opt._accumulators["moment2_sqrt_scale"][id(p)]._data
    got_v = (np.asarray(q, np.float32) * np.asarray(s)[:, None]) ** 2
    # reconstruction error bounded by double quantization, relative scale
    np.testing.assert_allclose(got_v.reshape(-1), v_true, atol=2e-2)


def test_q8_pallas_kernel_matches_chunked_path():
    """Round 5: the fused Pallas int8-Adam kernel (interpret mode on CPU)
    must track the chunked XLA path — same blockwise quantization rule,
    same sqrt-space v, same update math. int8 codes may differ by 1 at
    quantization boundaries (different fp32 fusion), params stay within
    float tolerance."""
    import jax
    import paddle_tpu.optimizer as optim
    from paddle_tpu.ops.q8_adam_pallas import q8_adam_update

    rng = np.random.default_rng(7)
    nb, B = 4, 2048
    n = nb * B
    base = rng.normal(0, 0.1, (nb, B)).astype(np.float32)
    grad = rng.normal(0, 0.01, (nb, B)).astype(np.float32)
    m_q = np.zeros((nb, B), np.int8)
    m_s = np.ones((nb, 1), np.float32)
    v_q = np.zeros((nb, B), np.int8)
    v_s = np.ones((nb, 1), np.float32)
    lr, wd, eps, b1, b2 = 1e-2, 0.01, 1e-8, 0.9, 0.999
    c1, c2 = 1.0 - b1, 1.0 - b2  # t = 1
    scalars = jnp.array([lr, wd, c1, c2, eps, b1, b2], jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)

    mq2, ms2, vq2, vs2, newb = q8_adam_update(
        jnp.asarray(m_q), jnp.asarray(m_s), jnp.asarray(v_q),
        jnp.asarray(v_s), jnp.asarray(base), jnp.asarray(grad),
        scalars, seed, use_sr=False, has_wd=True, interpret=True)

    # reference: the same math in numpy (the rule _q8_quantize pins)
    g32 = grad
    nm = b1 * (m_q.astype(np.float32) * m_s) + (1 - b1) * g32
    nv = b2 * (v_q.astype(np.float32) * v_s) ** 2 + (1 - b2) * g32 * g32
    msc = np.abs(nm).max(1, keepdims=True) / 127.0
    msc[msc == 0] = 1.0
    vsc = np.sqrt(nv).max(1, keepdims=True) / 127.0
    vsc[vsc == 0] = 1.0
    upd = base * (1 - lr * wd) - lr * (nm / c1) / (np.sqrt(nv / c2) + eps)

    # numpy promotes the python-float coefficients to float64 where the
    # kernel stays fp32 — a few-ulp gap on the tiny v scales is expected
    np.testing.assert_allclose(np.asarray(ms2), msc, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(vs2), vsc, rtol=2e-5)
    assert np.abs(np.asarray(mq2).astype(np.int32) -
                  np.clip(np.round(nm / msc), -127, 127)).max() <= 1
    np.testing.assert_allclose(np.asarray(newb), upd, rtol=1e-5, atol=1e-7)


def test_q8_pallas_routing_gate():
    """The Pallas route is TPU-only and block-multiple-only; CPU and
    ragged params stay on the chunked XLA path (this whole test file runs
    on CPU, so passing tests above already prove the fallback works)."""
    import jax
    assert jax.default_backend() == "cpu"  # test env contract
    paddle.seed(3)
    model = nn.Linear(64, 96)  # n=6144: block-multiple, but CPU -> XLA path
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters(),
                                 moment_dtype="int8",
                                 stochastic_rounding=False)
    x = paddle.to_tensor(np.ones((4, 64), np.float32))
    loss = (model(x) ** 2).mean()
    loss.backward()
    opt.step()  # must not raise (would, if Pallas ran on CPU)


# ---------------------------------------------------------------------------
# PR 32: the int8 state keeps the parameter's layout, the kernel walks it
# ---------------------------------------------------------------------------

def _q8_optimizer(shape, wd, seed=21):
    from paddle_tpu.static import create_parameter
    paddle.seed(seed)
    p = create_parameter(shape, "float32",
                         default_initializer=nn.initializer.Normal(0.0, 0.1))
    opt = paddle.optimizer.AdamW(1e-2, parameters=[p], weight_decay=wd,
                                 moment_dtype="int8",
                                 stochastic_rounding=False)
    return opt, p


def _q8_steps(opt, p, steps, first=0):
    """`steps` updates of the one parameter under seeded gradients whose
    blocks differ in scale by orders of magnitude."""
    shape = tuple(p.shape)
    for i in range(first, first + steps):
        rng = np.random.default_rng(100 + i)
        g = rng.normal(0, 1, shape) * 10.0 ** rng.integers(-4, 1, shape[:-1]
                                                           + (1,))
        loss = (p * paddle.to_tensor(g.astype(np.float32))).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()


def _q8_outputs(opt, p):
    """The update's five outputs, the state in its flat form."""
    from paddle_tpu.optimizer import _Q8_STATE
    out = [np.asarray(opt._accumulators[name][id(p)]._data)
           for name in _Q8_STATE]
    nb = out[1].size
    return [out[0].reshape(nb, 2048), out[1].reshape(nb),
            out[2].reshape(nb, 2048), out[3].reshape(nb),
            np.asarray(p._data.astype(jnp.float32))]


def _q8_routed(metrics):
    """(parameters the kernel walked in layout, elements relaid) of the
    last step."""
    snap = metrics.snapshot()
    return (snap["train.q8.in_layout_params"],
            snap["train.q8.relaid_elements"])


@pytest.fixture
def q8_kernel_on_cpu(monkeypatch):
    """Route the int8 update as a lone chip would, with the kernel in
    interpret mode: the test steers the backend question, the program has
    no option for it."""
    import functools

    from paddle_tpu.ops import q8_adam_pallas
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        q8_adam_pallas, "q8_adam_update",
        functools.partial(q8_adam_pallas.q8_adam_update, interpret=True))


_IN_LAYOUT_SHAPES = [(3, 64, 4096), (64, 2048), (2, 32, 6144)]


@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("shape", _IN_LAYOUT_SHAPES)
def test_q8_in_layout_kernel_matches_flat_kernel(shape, wd):
    """The kernel walking a parameter's own (R, C) view — two, one and
    three column blocks a row; 192 rows leave a ragged row group — against
    the same kernel over flat (nb, 2048) rows, the only geometry it had
    before PR 32: every block's codes and scales and the new parameter are
    the same bits."""
    from paddle_tpu.ops.q8_adam_pallas import q8_adam_update
    rows, cols = int(np.prod(shape[:-1])), shape[-1]
    k, nb = cols // 2048, rows * cols // 2048
    rng = np.random.default_rng(3)
    codes = lambda: jnp.asarray(rng.integers(-127, 128, (rows, cols)),
                                jnp.int8)
    scales = lambda: jnp.asarray(
        10.0 ** rng.uniform(-6, -1, (rows, k)), jnp.float32)
    state = [codes(), scales(), codes(), scales()]
    base = jnp.asarray(rng.normal(0, 0.1, (rows, cols)), jnp.float32)
    grad = jnp.asarray(rng.normal(0, 1, (rows, cols))
                       * 10.0 ** rng.integers(-4, 1, (rows, 1)), jnp.float32)
    scalars = jnp.array([1e-2, wd, 1 - 0.9 ** 3, 1 - 0.999 ** 3, 1e-8,
                         0.9, 0.999], jnp.float32)
    run = lambda *ops: q8_adam_update(
        *ops, scalars, jnp.zeros((1,), jnp.int32), use_sr=False,
        has_wd=bool(wd), interpret=True)
    flat = lambda x: x.reshape(nb, -1)
    got = run(*state, base, grad)
    want = run(*map(flat, state), flat(base), flat(grad))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(flat(a)), np.asarray(b))


def _assert_q8_outputs_close(got, want):
    """Kernel against chunked loop: the kernel forms 1 - beta in fp32 from
    an fp32 beta where the loop rounds Python's double, and XLA's CPU
    codegen contracts FMAs per fusion, so a code in ten thousand sits one
    step over a rounding boundary (as `test_q8_pallas_kernel_matches_
    chunked_path` has allowed since the kernel came)."""
    for i in (0, 2):
        d = np.abs(got[i].astype(np.int32) - want[i].astype(np.int32))
        assert d.max() <= 1 and (d != 0).mean() < 1e-3
    for i in (1, 3):
        np.testing.assert_allclose(got[i], want[i], rtol=1e-5)
    d = np.abs(got[4] - want[4])
    assert d.max() < 1e-3 and (d > 1e-6).mean() < 1e-3


def _assert_q8_route_matches_chunked_loop(shape, wd, routed, metrics,
                                          monkeypatch):
    """Three optimizer steps routed as a lone chip routes them (the caller
    holds `q8_kernel_on_cpu`) against the chunked loop over the same
    storage; `routed` is what the two gauges must read on the chip's
    route."""
    from paddle_tpu.optimizer import _Q8_STATE, _q8_shapes
    opt, p = _q8_optimizer(shape, wd)
    _q8_steps(opt, p, 3)
    assert [opt._accumulators[name][id(p)]._data.shape
            for name in _Q8_STATE] == list(_q8_shapes(shape)) * 2
    assert _q8_routed(metrics) == routed
    got = _q8_outputs(opt, p)

    monkeypatch.undo()                          # the chunked loop, as on CPU
    ref_opt, ref_p = _q8_optimizer(shape, wd)
    _q8_steps(ref_opt, ref_p, 3)
    _assert_q8_outputs_close(got, _q8_outputs(ref_opt, ref_p))
    assert _q8_routed(metrics) == (0, int(np.prod(shape)))


@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("shape", _IN_LAYOUT_SHAPES)
def test_q8_in_layout_update_matches_chunked_path(
        shape, wd, q8_kernel_on_cpu, metrics, monkeypatch):
    """State kept as (R, C) / (R, C // 2048), the kernel in layout, nothing
    relaid."""
    from paddle_tpu.optimizer import _q8_shapes
    rows, cols = int(np.prod(shape[:-1])), shape[-1]
    assert _q8_shapes(shape) == ((rows, cols), (rows, cols // 2048))
    _assert_q8_route_matches_chunked_loop(shape, wd, (1, 0), metrics,
                                          monkeypatch)


def test_q8_flat_rows_path_is_kept_for_narrow_last_dimension(
        q8_kernel_on_cpu, metrics, monkeypatch):
    """[4, 64, 1024]: whole blocks, but a row is half of one — the state
    stays (nb, 2048) / (nb,), the kernel takes flat rows as before and the
    gauge counts the parameter as relaid."""
    from paddle_tpu.optimizer import _q8_shapes
    shape = (4, 64, 1024)
    assert _q8_shapes(shape) == ((128, 2048), (128,))
    _assert_q8_route_matches_chunked_loop(
        shape, 0.01, (0, 4 * 64 * 1024), metrics, monkeypatch)


def test_q8_routed_gauges_over_a_model(q8_kernel_on_cpu, metrics):
    """A model's worth of shapes in one step: stacked matrices, the
    embedding and a stacked norm weight ([2, 2048]: rows of whole blocks)
    in layout; the narrow stacked projection relaid; the ragged bias on
    the chunked loop, which flattens everything."""
    from paddle_tpu.static import create_parameter
    paddle.seed(2)
    params = [create_parameter(s, "float32") for s in (
        (2, 8, 4096), (16, 2048), (2, 2048), (2, 8, 1024), (100,))]
    opt = paddle.optimizer.AdamW(1e-2, parameters=params,
                                 moment_dtype="int8",
                                 stochastic_rounding=False)
    sum((p * p).sum() for p in params).backward()
    opt.step()
    assert _q8_routed(metrics) == (3, 2 * 8 * 1024 + 100)


def test_q8_checkpoint_in_the_flat_layout_loads_and_continues():
    """A state saved before PR 32 — every moment (nb, 2048), every scale
    (nb,) — loads into the view this optimizer keeps and continues on the
    chunked path bit-equal to a run that was never interrupted; a state
    saved now loads the same way."""
    from paddle_tpu.optimizer import _Q8_STATE
    shape = (2, 32, 4096)
    opt, p = _q8_optimizer(shape, 0.01)
    _q8_steps(opt, p, 2)
    w_at_save = np.asarray(p._data)
    old = {"step": 2}
    for name, arr in zip(_Q8_STATE, _q8_outputs(opt, p)[:4]):
        old[f"{p.name}_{name}"] = paddle.to_tensor(arr)
    new = {k: (paddle.to_tensor(np.asarray(v._data))
               if isinstance(v, paddle.Tensor) else v)
           for k, v in opt.state_dict().items()}
    assert new[f"{p.name}_moment1"].shape == [64, 4096]
    _q8_steps(opt, p, 2, first=2)
    want = _q8_outputs(opt, p)

    for saved in (old, new):
        opt2, p2 = _q8_optimizer(shape, 0.01, seed=99)
        p2._set_data(jnp.asarray(w_at_save))
        opt2.set_state_dict({k.replace(p.name, p2.name): v
                             for k, v in saved.items()})
        m = opt2._accumulators["moment1"][id(p2)]._data
        s = opt2._accumulators["moment1_scale"][id(p2)]._data
        assert m.shape == (64, 4096) and s.shape == (64, 2)
        _q8_steps(opt2, p2, 2, first=2)
        for a, b in zip(_q8_outputs(opt2, p2), want):
            np.testing.assert_array_equal(a, b)
