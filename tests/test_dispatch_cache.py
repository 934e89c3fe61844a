"""Signature-keyed compiled-op cache for eager dispatch (ISSUE 2).

Covers the cache contract end to end: hit/miss per signature component,
LRU eviction, unhashable-static and closure-array bypass, AMP interaction,
grad-vs-no_grad keying, the env kill-switch, capture-seam bypass guards
(to_static / lazy segments / static-graph hook), the fused nan check,
observability counters, and byte-identical numerics cache-on vs cache-off.
Plus the satellites: thread-safe RemovableHandle ids, ``shape_tuple()``,
and the ``to_tensor`` committed-array dtype cast.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.core import dispatch_cache as dcache
from paddle_tpu.core import lazy as lazy_mod
from paddle_tpu.core import tensor as tensor_mod
from paddle_tpu.core.tensor import apply, to_tensor


@pytest.fixture(autouse=True)
def _cache_on():
    prev = (dcache._ENABLED, dcache._MAXSIZE, dcache._WARMUP)
    dcache.configure(enabled=True, maxsize=256, warmup=2)
    dcache.cache_clear()
    yield
    dcache.configure(enabled=prev[0], maxsize=prev[1], warmup=prev[2])
    dcache.cache_clear()


def _t(shape=(4, 4), dtype="float32", grad=False, seed=0):
    rng = np.random.RandomState(seed)
    return to_tensor(rng.randn(*shape).astype(dtype), stop_gradient=not grad)


# ---------------------------------------------------------------------------
# hit/miss semantics per signature component
# ---------------------------------------------------------------------------

def test_repeat_signature_hits_after_warmup():
    x = _t()
    y1 = x * 2.0                      # cold miss
    y2 = x * 2.0                      # warm miss -> compiled + served
    y3 = x * 2.0                      # hit
    info = dcache.cache_info()
    assert info["misses"] == 2 and info["compiles"] == 1
    assert info["hits"] == 1
    for y in (y2, y3):
        np.testing.assert_array_equal(np.asarray(y1._data),
                                      np.asarray(y._data))


def test_closure_scalar_is_part_of_the_key():
    x = _t()
    for _ in range(3):
        x * 2.0
    hits = dcache.cache_info()["hits"]
    y = x * 3.0                       # same op/avals, different closure const
    assert dcache.cache_info()["hits"] == hits  # no false hit
    np.testing.assert_array_equal(np.asarray(y._data),
                                  np.asarray(x._data) * 3.0)


def test_shape_dtype_and_static_kwargs_key_components():
    a = _t((4, 4))
    for _ in range(3):
        a + a
    hits = dcache.cache_info()["hits"]
    b = _t((2, 8))
    b + b                             # different shape: no hit
    c = to_tensor(np.ones((4, 4), np.int64))
    c + c                             # different dtype: no hit
    assert dcache.cache_info()["hits"] == hits

    def f(x, scale=1.0):
        return x * scale

    for _ in range(3):
        apply("tk_scale", f, a, scale=2.0)
    hits = dcache.cache_info()["hits"]
    assert hits >= 1
    out = apply("tk_scale", f, a, scale=4.0)   # static kwarg keys the entry
    assert dcache.cache_info()["hits"] == hits
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(a._data) * 4.0)


def test_grad_vs_no_grad_are_distinct_entries():
    x = _t(grad=True)
    with paddle.no_grad():
        for _ in range(3):
            y = x * 5.0
        assert y.stop_gradient
    compiles_ng = dcache.cache_info()["compiles"]
    assert compiles_ng == 1
    for _ in range(3):
        y = x * 5.0
    assert not y.stop_gradient
    assert dcache.cache_info()["compiles"] == 2  # separate grad-keyed entry
    y.sum().backward()
    np.testing.assert_allclose(np.asarray(x.grad._data), 5.0, rtol=0)


def test_warmup_one_compiles_on_first_sighting():
    dcache.configure(warmup=1)
    x = _t()
    y1 = x * 9.0
    info = dcache.cache_info()
    assert info["compiles"] == 1 and info["misses"] == 1
    y2 = x * 9.0
    assert dcache.cache_info()["hits"] == 1
    np.testing.assert_array_equal(np.asarray(y1._data), np.asarray(y2._data))


def test_lru_eviction_is_bounded_and_counted():
    dcache.configure(maxsize=4)
    x = _t()
    for k in range(6):
        for _ in range(3):
            x * float(k)
    info = dcache.cache_info()
    assert info["size"] <= 4
    assert info["evictions"] > 0


# ---------------------------------------------------------------------------
# bypass: unhashable statics, closure arrays, capture seams
# ---------------------------------------------------------------------------

def test_unhashable_static_kwarg_bypasses_uncached():
    x = _t()
    marker = {object()}  # a set of an unhashable-by-value object

    def f(a, tag=None):
        return a + 1.0

    out = apply("tk_unhash", f, x, tag=marker)
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(x._data) + 1.0)
    info = dcache.cache_info()
    assert info["bypass"].get("static_unhashable", 0) >= 1
    assert info["compiles"] == 0


def test_closure_array_bypasses_uncached():
    x = _t()
    table = np.arange(16, dtype=np.float32).reshape(4, 4)
    for _ in range(3):
        out = apply("tk_closure_arr", lambda a: a + table, x)
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(x._data) + table)
    info = dcache.cache_info()
    assert info["bypass"].get("closure_array", 0) >= 3
    assert info["compiles"] == 0


def test_hashless_callable_op_bypasses_uncached():
    class HashlessOp:
        __hash__ = None                     # e.g. a dataclass with __eq__

        def __eq__(self, other):
            return self is other

        def __call__(self, a):
            return a * 3.0

    x = _t()
    for _ in range(3):
        y = apply("tk_hashless", HashlessOp(), x)
    np.testing.assert_array_equal(np.asarray(y._data),
                                  np.asarray(x._data) * 3.0)
    info = dcache.cache_info()
    assert info["bypass"].get("static_unhashable", 0) >= 3
    assert info["compiles"] == 0


def test_mutable_list_closure_is_content_keyed_not_stale():
    x = to_tensor(np.arange(6, dtype=np.float32))
    shape = [2, 3]
    for _ in range(3):
        y = paddle.reshape(x, shape)
    assert y.shape_tuple() == (2, 3)
    shape2 = [3, 2]
    y = paddle.reshape(x, shape2)     # content differs -> new key, no stale hit
    assert y.shape_tuple() == (3, 2)


def test_fresh_partial_per_call_is_structurally_keyed():
    import functools

    def base(a, scale):
        return a * scale

    x = _t()
    for _ in range(3):                # a FRESH partial object every call
        y = apply("tk_partial", functools.partial(base, scale=2.0), x)
    info = dcache.cache_info()
    assert info["compiles"] == 1 and info["hits"] >= 1
    np.testing.assert_array_equal(np.asarray(y._data),
                                  np.asarray(x._data) * 2.0)
    apply("tk_partial", functools.partial(base, scale=5.0), x)
    assert dcache.cache_info()["hits"] == info["hits"]  # kwarg keys it


def test_identity_key_churn_cannot_evict_compiled_entries():
    # never-repeating signatures (fresh callable objects) live in the
    # pending table; their churn must not flush hot compiled entries
    dcache.configure(maxsize=8)
    x = _t()
    for _ in range(3):
        x * 42.0                      # hot compiled entry

    class FreshOp:
        def __call__(self, a):
            return a + 0.0

    for _ in range(30):               # 30 distinct identity-keyed misses
        apply("tk_churn", FreshOp(), x)
    hits = dcache.cache_info()["hits"]
    x * 42.0                          # still served compiled
    assert dcache.cache_info()["hits"] == hits + 1


def test_persistent_nontrace_compile_failure_poisons_after_retries():
    x = _t()

    def tracer_hater(a):
        # legal eagerly; a NON-jax error under jit tracing (a ValueError,
        # not ConcretizationTypeError) — retried a bounded number of
        # times, then poisoned
        if type(a).__mro__[0].__name__.endswith("Tracer"):
            raise ValueError("no tracers here")
        return a * 2.0

    for _ in range(6):
        out = apply("tk_valueerr", tracer_hater, x)
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(x._data) * 2.0)
    info = dcache.cache_info()
    assert info["bypass"].get("compile_retry", 0) == 3
    assert info["bypass"].get("untraceable", 0) >= 2  # poisoned thereafter
    assert info["compiles"] == 0


def test_to_static_capture_bypasses_cache_and_traces_once():
    calls = {"n": 0}

    @paddle.jit.to_static
    def f(v):
        calls["n"] += 1
        return (v * 2.0 + 1.0).sum()

    x = _t(grad=True)
    r1 = f(x)
    r2 = f(x)
    assert calls["n"] == 1            # traced once, replayed compiled
    np.testing.assert_array_equal(np.asarray(r1._data), np.asarray(r2._data))
    info = dcache.cache_info()
    assert info["hits"] == 0 and info["compiles"] == 0
    assert info["bypass"].get("capture", 0) >= 2  # the traced op dispatches


def test_lazy_segment_mode_bypasses_cache():
    x = _t()
    with lazy_mod.segment_mode():
        y = x * 2.0
        z = y + 1.0
        got = float(z.sum())          # concrete read flushes the segment
    want = float((np.asarray(x._data) * 2.0 + 1.0).sum())
    assert got == pytest.approx(want)
    info = dcache.cache_info()
    assert info["hits"] == 0 and info["compiles"] == 0
    assert info["bypass"].get("capture", 0) >= 3


def test_static_graph_hook_bypasses_cache_and_sees_every_op():
    recorded = []
    assert tensor_mod._op_graph_hook is None
    tensor_mod._op_graph_hook = \
        lambda name, f, ins, outs: recorded.append(name)
    try:
        x = _t()
        for _ in range(3):
            x * 2.0
    finally:
        tensor_mod._op_graph_hook = None
    assert recorded.count("multiply") == 3
    info = dcache.cache_info()
    assert info["hits"] == 0 and info["compiles"] == 0
    assert info["bypass"].get("capture", 0) >= 3


def test_symbolic_input_bypasses_cache():
    # a Tensor wrapping a live jax tracer (e.g. user-level jax.jit around
    # paddle ops) must never be baked into a cached executable
    seen = {}

    def jf(a):
        t = tensor_mod.Tensor(a)
        out = t * 2.0
        seen["info"] = dcache.cache_info()
        return out._data

    r = jax.jit(jf)(jnp.ones((3,)))
    np.testing.assert_array_equal(np.asarray(r), 2.0 * np.ones((3,)))
    assert seen["info"]["bypass"].get("symbolic_input", 0) >= 1
    assert seen["info"]["compiles"] == 0


def test_untraceable_fn_is_poisoned_not_retried():
    x = _t()

    def branchy(a):
        # legal eagerly, ConcretizationTypeError under jit tracing
        if float(jnp.sum(a)) > 1e9:
            return a * 0.0
        return a * 2.0

    for _ in range(4):
        out = apply("tk_branchy", branchy, x)
    np.testing.assert_array_equal(np.asarray(out._data),
                                  np.asarray(x._data) * 2.0)
    info = dcache.cache_info()
    assert info["compiles"] == 0 and info["hits"] == 0
    assert info["bypass"].get("untraceable", 0) >= 2  # poisoned after 1 try


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------

def test_disabled_cache_touches_nothing():
    dcache.configure(enabled=False)
    x = _t(grad=True)
    y = (x * 2.0).sum()
    y.backward()
    info = dcache.cache_info()
    assert info["hits"] == info["misses"] == info["compiles"] == 0
    assert info["bypass"] == {}
    assert not info["enabled"]


def test_env_flag_parsing(monkeypatch):
    for raw, want in (("0", False), ("false", False), ("off", False),
                      ("no", False), ("1", True), ("true", True), ("", True)):
        monkeypatch.setenv("PADDLE_TPU_EAGER_CACHE", raw)
        assert dcache._env_enabled() is want, raw
    monkeypatch.delenv("PADDLE_TPU_EAGER_CACHE")
    assert dcache._env_enabled() is True
    monkeypatch.setenv("PADDLE_TPU_EAGER_CACHE_SIZE", "64")
    assert dcache._env_int("PADDLE_TPU_EAGER_CACHE_SIZE", 1024) == 64
    monkeypatch.setenv("PADDLE_TPU_EAGER_CACHE_SIZE", "bogus")
    assert dcache._env_int("PADDLE_TPU_EAGER_CACHE_SIZE", 1024) == 1024


# ---------------------------------------------------------------------------
# numerics: cache-on vs cache-off must match bit for bit
# ---------------------------------------------------------------------------

def _model_loss_and_grads(x, w):
    y = paddle.matmul(x, w)
    y = paddle.nn.functional.relu(y)
    y = paddle.nn.functional.softmax(y, axis=-1)
    loss = (y * y).mean()
    loss.backward()
    gx = np.asarray(x.grad._data).copy()
    gw = np.asarray(w.grad._data).copy()
    x.clear_grad()
    w.clear_grad()
    return np.asarray(loss._data).copy(), gx, gw


def test_numerics_identical_cache_on_vs_off():
    x = _t((8, 16), grad=True, seed=1)
    w = _t((16, 16), grad=True, seed=2)
    dcache.configure(enabled=False)
    ref = _model_loss_and_grads(x, w)
    dcache.configure(enabled=True)
    cold, compiling, hot = (_model_loss_and_grads(x, w) for _ in range(3))
    # within a tier, bitwise: the cold call still runs op by op like the
    # reference, and the two compiled calls run the same executables
    for r, g in zip(ref, cold):
        np.testing.assert_array_equal(r, g)
    for a, b in zip(compiling, hot):
        np.testing.assert_array_equal(a, b)
    # across tiers, ulp scale: a jitted op may contract a*b+c to an FMA
    # that per-op dispatch cannot (jaxlib 0.9.0's CPU codegen does; seen:
    # 1e-9 absolute on gradients of 1e-3). rtol is ~84 fp32 ulp, atol
    # covers elements that cancel to near zero.
    for r, g in zip(ref, hot):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-8)
    assert dcache.cache_info()["hits"] > 0


def test_numerics_match_under_amp():
    # bf16 note: the cached path fuses cast+matmul in one XLA program while
    # the eager path runs them op-by-op, so bf16 rounding may differ at eps
    # scale (~8e-3); fp32 paths stay bit-exact (see the test above). The
    # cached path must still be deterministic call-to-call.
    x = _t((8, 16), grad=True, seed=3)
    w = _t((16, 16), grad=True, seed=4)

    def run():
        with paddle.amp.auto_cast(level="O1"):
            loss = paddle.matmul(x, w).sum()
        loss.backward()
        g = np.asarray(x.grad._data).copy()
        x.clear_grad()
        w.clear_grad()
        return np.asarray(loss._data).copy(), g

    dcache.configure(enabled=False)
    ref = run()
    dcache.configure(enabled=True)
    outs = [run() for _ in range(4)]  # cold, compiling, hot, hot
    for got in outs:
        for r, g in zip(ref, got):
            np.testing.assert_allclose(r, g, rtol=2e-2, atol=2e-2)
    for r, g in zip(outs[2], outs[3]):  # hot path: deterministic, bit-exact
        np.testing.assert_array_equal(r, g)
    info = dcache.cache_info()
    assert info["hits"] > 0


def test_amp_scope_keys_separately_from_plain():
    x = _t((4, 8), grad=False, seed=5)
    w = _t((8, 8), grad=False, seed=6)
    for _ in range(3):
        plain = paddle.matmul(x, w)
    assert plain.dtype == jnp.float32
    with paddle.amp.auto_cast(level="O1"):
        for _ in range(3):
            low = paddle.matmul(x, w)
    assert low.dtype == jnp.bfloat16  # cached entry bakes the cast
    info = dcache.cache_info()
    assert info["compiles"] >= 2      # plain and amp entries are distinct


def test_int_input_grads_cached():
    # integer inputs ride through the cached vjp as float0 -> skipped
    x = _t((5, 4), grad=True, seed=7)
    idx = to_tensor(np.array([0, 2, 4]))
    dcache.configure(enabled=False)
    ref = paddle.gather(x, idx).sum()
    ref.backward()
    g_ref = np.asarray(x.grad._data).copy()
    x.clear_grad()
    dcache.configure(enabled=True)
    for _ in range(3):
        loss = paddle.gather(x, idx).sum()
        loss.backward()
        np.testing.assert_array_equal(np.asarray(x.grad._data), g_ref)
        x.clear_grad()


def test_double_grad_through_cached_nodes():
    def run():
        x = to_tensor(np.array([1.5, -2.0, 3.0], np.float32),
                      stop_gradient=False)
        y = (x * x * x).sum()
        (gx,) = paddle.grad(y, [x], create_graph=True)
        (ggx,) = paddle.grad(gx.sum(), [x])
        return np.asarray(gx._data).copy(), np.asarray(ggx._data).copy()

    dcache.configure(enabled=False)
    ref = run()
    dcache.configure(enabled=True)
    for _ in range(3):
        got = run()
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(r, g)


def test_multi_output_op_cached():
    x = to_tensor(np.arange(12, dtype=np.float32).reshape(6, 2),
                  stop_gradient=False)
    for _ in range(3):
        a, b, c = paddle.split(x, 3, axis=0)
    loss = (a.sum() + (b * 2).sum() + (c * 3).sum())
    loss.backward()
    want = np.repeat(np.array([1.0, 2.0, 3.0], np.float32), 2 * 2)
    np.testing.assert_array_equal(np.asarray(x.grad._data).ravel(), want)
    assert dcache.cache_info()["hits"] >= 1


def test_retain_graph_and_second_backward_error_with_cache():
    x = _t((3, 3), grad=True)
    for _ in range(3):
        loss = (x * 2.0).sum()
    loss.backward(retain_graph=True)
    loss.backward()                   # allowed: graph retained once
    with pytest.raises(RuntimeError):
        loss.backward()               # released now -> same error as seed


def test_backward_snapshots_closure_state_at_dispatch_time():
    # the seed's jax.vjp reads the op fn's closure AT DISPATCH; the cached
    # backward must too (warm_bwd), not at first backward() — a caller
    # mutating closure-held state in between must not change the grads
    x = to_tensor(np.arange(6, dtype=np.float32), stop_gradient=False)
    scale = [2.0]

    def f(a):
        return a * scale[0]

    for _ in range(3):                # third call serves from the cache
        y = apply("tk_snapshot", f, x)
    assert dcache.cache_info()["hits"] >= 1
    scale[0] = 100.0                  # mutate AFTER dispatch, BEFORE backward
    y.sum().backward()
    np.testing.assert_array_equal(np.asarray(x.grad._data),
                                  np.full(6, 2.0, np.float32))


def test_poisoned_entries_respect_the_lru_bound():
    dcache.configure(maxsize=4, warmup=1)
    x = _t()
    for k in range(8):                # 8 distinct untraceable signatures
        def branchy(a, _k=float(k)):
            if float(jnp.sum(a)) > 1e9:
                return a * 0.0
            return a * _k
        apply("tk_poison", branchy, x)
    info = dcache.cache_info()
    assert info["size"] <= 4
    assert info["evictions"] >= 4


# ---------------------------------------------------------------------------
# fused nan check
# ---------------------------------------------------------------------------

def test_check_nan_inf_fused_on_cached_path():
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        x = to_tensor(np.array([1.0, 2.0], np.float32))
        big = to_tensor(np.array([1e38, 1e38], np.float32))
        for _ in range(3):
            x * 2.0                   # finite: cached, no raise
        assert dcache.cache_info()["hits"] >= 1
        for _ in range(3):            # overflow -> inf on cold AND hot path
            with pytest.raises(FloatingPointError):
                big * 1e38
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})


def test_runtime_set_flags_invalidates_cached_entries():
    # op fns read flags at trace time (tpu_matmul_precision et al.): a
    # set_flags() must retire compiled entries, not serve the baked value
    x = _t((4, 4), seed=8)
    w = _t((4, 4), seed=9)
    for _ in range(3):
        paddle.matmul(x, w)
    hits = dcache.cache_info()["hits"]
    assert hits >= 1
    prev = paddle.get_flags("FLAGS_tpu_matmul_precision")[
        "FLAGS_tpu_matmul_precision"]
    paddle.set_flags({"FLAGS_tpu_matmul_precision": "high"})
    try:
        out_hi = paddle.matmul(x, w)      # must NOT hit the stale entry
        assert dcache.cache_info()["hits"] == hits
        dcache.configure(enabled=False)   # flag honored same as cache-off
        ref = paddle.matmul(x, w)
        np.testing.assert_array_equal(np.asarray(out_hi._data),
                                      np.asarray(ref._data))
    finally:
        paddle.set_flags({"FLAGS_tpu_matmul_precision": prev})
        dcache.configure(enabled=True)


def test_nan_check_flag_is_a_key_component():
    x = _t()
    for _ in range(3):
        x * 7.0
    compiles = dcache.cache_info()["compiles"]
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        for _ in range(3):
            x * 7.0                   # same op, nan-checked variant
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})
    assert dcache.cache_info()["compiles"] == compiles + 1


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_cache_counters_in_snapshot_and_prometheus():
    obs.reset()
    obs.enable()
    try:
        x = _t()
        for _ in range(3):
            x * 11.0
        table = np.ones((4, 4), np.float32)
        apply("tk_obs_bypass", lambda a: a + table, x)
        snap = obs.snapshot()
        assert snap.get("dispatch.cache_hits_total", 0) >= 1
        assert snap.get("dispatch.cache_misses_total", 0) >= 2
        assert snap.get("dispatch.cache_compiles_total", 0) >= 1
        bypass = snap.get("dispatch.cache_bypass_total", {})
        assert any("closure_array" in k for k in bypass)
        text = obs.prometheus_text()
        assert "dispatch_cache_hits_total" in text
        assert "dispatch_cache_bypass_total" in text
    finally:
        obs.disable()
        obs.reset()


def test_disabled_observability_leaves_hook_unset():
    assert dcache._obs_hook is None
    obs.enable()
    assert dcache._obs_hook is not None
    obs.disable()
    assert dcache._obs_hook is None


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_removable_handle_ids_unique_across_threads():
    ids = []
    lock = threading.Lock()

    def worker():
        t = _t((2,))
        got = [t.register_hook(lambda g: g).hook_id for _ in range(200)]
        with lock:
            ids.extend(got)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(ids) == len(set(ids)) == 1600


def test_shape_tuple_is_allocation_free_metadata():
    t = _t((3, 5))
    assert t.shape_tuple() == (3, 5)
    assert isinstance(t.shape_tuple(), tuple)
    # same object as the payload's shape: no per-access list build
    assert t.shape_tuple() is t._data.shape
    assert t.shape == [3, 5]          # the paddle-parity list view survives


def test_to_tensor_casts_committed_jax_array():
    committed = jax.device_put(np.arange(4, dtype=np.int32),
                               jax.devices("cpu")[0])
    t = to_tensor(committed, dtype="float32")
    assert t.dtype == jnp.float32
    np.testing.assert_array_equal(t.numpy(),
                                  np.arange(4, dtype=np.float32))
    tr = to_tensor(paddle.to_tensor(np.ones(3, np.int32)), dtype="float64")
    assert str(tr.dtype) in ("float64", "float32")  # x64 may be disabled
