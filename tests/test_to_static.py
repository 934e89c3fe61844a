"""to_static: compiled/eager equivalence, state functionalization, caching."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _fresh_pair(seed):
    paddle.seed(seed)
    m1 = nn.Sequential(nn.Linear(6, 12), nn.Tanh(), nn.Linear(12, 3))
    paddle.seed(seed)
    m2 = nn.Sequential(nn.Linear(6, 12), nn.Tanh(), nn.Linear(12, 3))
    return m1, m2


def test_forward_equivalence():
    m1, m2 = _fresh_pair(7)
    x = paddle.randn([4, 6])
    eager = m1(x).numpy()
    compiled_fn = paddle.jit.to_static(m2.forward)
    compiled = compiled_fn(x).numpy()
    np.testing.assert_allclose(eager, compiled, rtol=1e-5, atol=1e-6)


def test_train_step_equivalence():
    m1, m2 = _fresh_pair(11)
    o1 = paddle.optimizer.Adam(learning_rate=0.01, parameters=m1.parameters())
    o2 = paddle.optimizer.Adam(learning_rate=0.01, parameters=m2.parameters())
    x = paddle.randn([8, 6])
    y = paddle.randn([8, 3])

    def step(model, opt):
        loss = nn.functional.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    cstep = paddle.jit.to_static(lambda: step(m2, o2))
    for i in range(5):
        le = float(step(m1, o1))
        lc = float(cstep())
        assert abs(le - lc) < 1e-4, (i, le, lc)
    np.testing.assert_allclose(m1[0].weight.numpy(), m2[0].weight.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_batchnorm_buffers_update_under_jit():
    paddle.seed(3)
    m = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8))
    m.train()
    step = paddle.jit.to_static(m.forward)
    before = m[1]._mean.numpy().copy()
    step(paddle.randn([16, 4]) + 5.0)
    after = m[1]._mean.numpy()
    assert not np.allclose(before, after), "running mean must update through jit"


def test_rng_advances_under_jit():
    paddle.seed(0)
    d = nn.Dropout(0.5)
    d.train()
    f = paddle.jit.to_static(d.forward)
    a = f(paddle.ones([100])).numpy()
    b = f(paddle.ones([100])).numpy()
    assert not np.allclose(a, b), "dropout mask must differ between steps"


def test_shape_polymorphism_recompiles():
    m = nn.Linear(4, 2)
    f = paddle.jit.to_static(m.forward)
    y1 = f(paddle.randn([3, 4]))
    y2 = f(paddle.randn([7, 4]))
    assert y1.shape == [3, 2] and y2.shape == [7, 2]


def test_grads_cleared_after_compiled_step():
    m = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = m(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step(paddle.randn([2, 4]))
    assert all(p.grad is None for p in m.parameters())


def test_dynamic_shape_op_raises_under_jit():
    @paddle.jit.to_static
    def f(x):
        return paddle.nonzero(x)

    with pytest.raises(Exception):
        f(paddle.to_tensor([0.0, 1.0, 0.0]))


def test_iters_per_call_scan_matches_per_step():
    """scan-over-steps mode: K stacked batches through ONE compiled call give
    bit-identical training to K separate compiled steps."""
    import paddle_tpu.nn as nn

    def train(iters):
        paddle.seed(5)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     parameters=model.parameters(),
                                     use_multi_tensor=True)

        def step(x, y):
            loss = nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.default_rng(0)
        X = rng.normal(size=(8, 4, 8)).astype(np.float32)
        Y = rng.normal(size=(8, 4, 4)).astype(np.float32)
        if iters == 1:
            sf = paddle.jit.to_static(step)
            losses = [float(sf(paddle.to_tensor(X[i]), paddle.to_tensor(Y[i])))
                      for i in range(8)]
        else:
            sf = paddle.jit.to_static(step, iters_per_call=iters)
            losses = []
            for i in range(0, 8, iters):
                out = sf(paddle.to_tensor(X[i:i + iters]),
                         paddle.to_tensor(Y[i:i + iters]))
                losses.extend(np.asarray(out._data).tolist())
        return losses, [np.asarray(p._data) for p in model.parameters()]

    l1, p1 = train(1)
    l4, p4 = train(4)
    np.testing.assert_allclose(l1, l4, rtol=1e-5, atol=1e-6)
    for a, b in zip(p1, p4):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_iters_per_call_rejects_uncleared_grads():
    import paddle_tpu.nn as nn
    import pytest

    paddle.seed(0)
    model = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())

    @paddle.jit.to_static(iters_per_call=2)
    def bad_step(x):
        loss = model(x).mean()
        loss.backward()
        opt.step()
        return loss  # grads NOT cleared -> per-step value would escape scan

    x = paddle.to_tensor(np.ones((2, 2, 4), np.float32))
    with pytest.raises(RuntimeError, match="cleared within the step"):
        bad_step(x)


def test_iters_per_call_eager_fallback_matches():
    """With to_static globally disabled, an iters_per_call fn must still run
    K per-step iterations (not one call on the stacked batch)."""
    import paddle_tpu.nn as nn

    paddle.seed(9)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=model.parameters())

    @paddle.jit.to_static(iters_per_call=3)
    def step(x):
        loss = model(x).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.arange(3 * 2 * 4, dtype=np.float32)
                         .reshape(3, 2, 4) / 10.0)
    compiled = np.asarray(step(x)._data)

    paddle.seed(9)
    model2 = nn.Linear(4, 2)
    opt2 = paddle.optimizer.SGD(learning_rate=0.05,
                                parameters=model2.parameters())

    @paddle.jit.to_static(iters_per_call=3)
    def step2(x):
        loss = model2(x).mean()
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        return loss

    paddle.jit.enable_to_static(False)
    try:
        eager = np.asarray(step2(x)._data)
    finally:
        paddle.jit.enable_to_static(True)
    assert eager.shape == (3,)
    np.testing.assert_allclose(compiled, eager, rtol=1e-5, atol=1e-6)
    for a, b in zip(model.parameters(), model2.parameters()):
        np.testing.assert_allclose(np.asarray(a._data), np.asarray(b._data),
                                   rtol=1e-5, atol=1e-6)


def test_cloned_encoder_layers_own_their_buffers():
    """Round-1 TPU regression: TransformerEncoder clones shared the
    prototype's jax.Array for zero-variance params (biases, LN weights) and
    all buffers, so to_static donated the same buffer twice — the TPU
    runtime rejects that (INVALID_ARGUMENT). Clones must own their arrays."""
    import paddle_tpu.nn as nn

    layer = nn.TransformerEncoderLayer(
        d_model=16, nhead=2, dim_feedforward=32, dropout=0.0)
    enc = nn.TransformerEncoder(layer, 3)
    seen = {}
    for name, p in enc.state_dict().items():
        key = id(p._data)
        assert key not in seen, (
            f"{name} aliases {seen[key]}: donated twice under jit")
        seen[key] = name


def test_to_static_dedupes_aliased_state_donation():
    """Even if two live state tensors alias one array (e.g. hand-tied
    weights), the donated buffer list must stay unique."""
    import numpy as np
    import paddle_tpu as paddle

    a = paddle.nn.Linear(4, 4)
    b = paddle.nn.Linear(4, 4)
    b.weight._set_data(a.weight._data)  # deliberate alias

    @paddle.jit.to_static
    def f(x):
        return (a(x) + b(x)).sum()

    x = paddle.to_tensor(np.ones((2, 4), dtype="float32"))
    out = float(f(x))
    assert np.isfinite(out)


class TestDonatedArguments:
    """ISSUE 26: ``donate_argnums`` names the plain arguments a caller
    gives up beside the state (the serving engine's page pool)."""

    @staticmethod
    def _program(**kw):
        from paddle_tpu.core.tensor import Tensor

        def step(scale, pool, bias):
            return scale * 2.0, Tensor(pool._data.at[0].set(bias._data[0]))
        return paddle.jit.to_static(step, **kw)

    @staticmethod
    def _args():
        import jax.numpy as jnp
        return (jnp.full((3,), 2.0), jnp.zeros((4, 3)), jnp.ones((3,)))

    def test_named_argument_is_consumed_and_comes_back_aliased(self):
        from paddle_tpu.core.tensor import Tensor
        scale, pool, bias = self._args()
        f = self._program(donate_argnums=(1,))
        paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
        try:
            doubled, pool2 = f(Tensor(scale), Tensor(pool), Tensor(bias))
            text = f.compiled_text()
        finally:
            paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
        assert pool.is_deleted()
        assert not scale.is_deleted() and not bias.is_deleted()
        assert pool2._data.shape == (4, 3) and pool2._data.dtype == pool.dtype
        np.testing.assert_array_equal(np.asarray(pool2._data)[0], 1.0)
        np.testing.assert_array_equal(np.asarray(pool2._data)[1:], 0.0)
        np.testing.assert_array_equal(doubled.numpy(), 4.0)
        # the given-up input is the output's buffer: nothing to copy into
        assert "input_output_alias" in text.splitlines()[0]
        # the next call consumes what the last one returned
        _, pool3 = f(Tensor(scale), pool2, Tensor(bias))
        assert pool2._data.is_deleted() and not pool3._data.is_deleted()

    def test_argument_not_named_is_not_consumed(self):
        from paddle_tpu.core.tensor import Tensor
        scale, pool, bias = self._args()
        f = self._program()                      # the default: nothing given
        _, pool2 = f(Tensor(scale), Tensor(pool), Tensor(bias))
        assert not pool.is_deleted()
        np.testing.assert_array_equal(np.asarray(pool), 0.0)
        np.testing.assert_array_equal(np.asarray(pool2._data)[0], 1.0)

    def test_array_given_as_state_and_as_argument_is_copied_once(
            self, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import Tensor
        lin = paddle.nn.Linear(3, 3)

        @paddle.jit.to_static(donate_argnums=(0,))
        def f(pool):
            return pool + lin.weight

        copies = []
        real = jnp.copy
        monkeypatch.setattr(jnp, "copy",         # to_static's own jnp
                            lambda a: copies.append(a) or real(a))
        shared = lin.weight._data                # state AND given-up arg
        want = 2.0 * np.asarray(shared)
        out = f(Tensor(shared))
        np.testing.assert_allclose(out.numpy(), want)
        assert len(copies) == 1 and copies[0] is shared
        assert shared.is_deleted()
        # the state tensor was rebound to a live buffer, as always
        np.testing.assert_allclose(np.asarray(lin.weight._data), want / 2.0)

    def test_same_array_given_up_and_kept_is_copied(self):
        from paddle_tpu.core.tensor import Tensor
        scale, _, bias = self._args()
        f = self._program(donate_argnums=(1,))
        both = scale * 1.0                       # (3,) as scale AND as pool
        doubled, pool2 = f(Tensor(both), Tensor(both), Tensor(bias))
        assert not both.is_deleted()             # the copy was given up
        np.testing.assert_array_equal(doubled.numpy(), 4.0)
        np.testing.assert_array_equal(np.asarray(pool2._data), [1.0, 2.0, 2.0])

    def test_refused_where_an_eager_rerun_or_a_scan_would_read_it(self):
        with pytest.raises(ValueError, match="donate_argnums"):
            self._program(donate_argnums=(1,), full_graph=False)
        with pytest.raises(ValueError, match="donate_argnums"):
            self._program(donate_argnums=(1,), iters_per_call=2)


def test_full_graph_false_falls_back_to_eager():
    """SOT parity (upstream python/paddle/jit/sot/): tensor-data-dependent
    Python control flow breaks the graph; full_graph=False falls back to
    eager instead of raising."""
    import warnings

    import paddle_tpu as paddle

    def fn(x):
        if float(x.sum()) > 0:  # concrete read -> untraceable
            return x * 2
        return x - 1

    strict = paddle.jit.to_static(fn, full_graph=True)
    x = paddle.to_tensor(np.ones((2, 2), "float32"))
    with pytest.raises(Exception):
        strict(x)

    soft = paddle.jit.to_static(fn, full_graph=False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = soft(x)
        out2 = soft(x)  # second call keeps working (no re-warn needed)
    np.testing.assert_allclose(out.numpy(), np.full((2, 2), 2.0))
    np.testing.assert_allclose(out2.numpy(), np.full((2, 2), 2.0))
    assert any("falling back to compiled-segment" in str(x.message)
               for x in w)
