"""ZeRO stages must PHYSICALLY shard, not just express intent.

SURVEY §7 hard-part 3: the risk on an SPMD compiler is that
with_sharding_constraint is silently undone and XLA re-gathers everything.
These tests pin the guarantees on the 8-device CPU mesh:

* stage 1: every optimizer accumulator array is laid out with dim 0 split
  over the sharding axis — per-device bytes ~= total/N;
* stage 2: the compiled train step reduce-scatters gradients (HLO text)
  instead of all-reducing them into full replicas;
* stage 3: parameter storage itself is sharded between steps, the step
  all-gathers on use (HLO text), and per-device argument bytes stay ~1/N.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.sharding import DygraphShardingOptimizer
from paddle_tpu.distributed.topology import (HybridCommunicateGroup,
                                             set_hybrid_communicate_group)

N = 8  # sharding degree == CPU mesh size
D = 64


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(D, 4 * D)
        self.fc2 = nn.Linear(4 * D, D)

    def forward(self, x):
        return self.fc2(paddle.tanh(self.fc1(x)))


def _per_device_fraction(arr):
    """max per-device shard bytes / total bytes."""
    shards = arr.addressable_shards
    total = arr.size * arr.dtype.itemsize
    per_dev = max(int(np.prod(s.data.shape)) * arr.dtype.itemsize
                  for s in shards)
    return per_dev / total, len(shards)


@pytest.fixture(autouse=True)
def _no_stale_state():
    """A compiled step threads every live parameter through its program. A
    model an earlier test file left in a reference cycle, laid out over
    another mesh, is live until the collector runs — and then the step is
    refused for "incompatible devices". Collect before building a world."""
    import gc
    gc.collect()
    yield


@pytest.fixture
def sharded_world():
    paddle.seed(0)
    hcg = HybridCommunicateGroup(sharding_degree=N)
    yield hcg
    set_hybrid_communicate_group(None)


def _make(stage, sharded_world):
    model = MLP()
    inner = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=model.parameters())
    opt = DygraphShardingOptimizer(inner, hcg=sharded_world, stage=stage)
    return model, inner, opt


def _step_fn(model, opt):
    @paddle.jit.to_static
    def step(x, y):
        loss = ((model(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return step


def _data(mesh=None):
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(0, 1, (16, D)).astype(np.float32))
    y = paddle.to_tensor(rng.normal(0, 1, (16, D)).astype(np.float32))
    if mesh is not None:
        # ZeRO's sharding group IS the data-parallel group: the batch is
        # split over the same axis the optimizer state shards over
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("sharding"))
        x._set_data(jax.device_put(x._data, sh))
        y._set_data(jax.device_put(y._data, sh))
    return x, y


def test_stage1_optimizer_state_bytes_per_device(sharded_world):
    model, inner, opt = _make(1, sharded_world)
    step = _step_fn(model, opt)
    x, y = _data()
    l0 = float(step(x, y))
    l1 = float(step(x, y))
    assert np.isfinite(l0) and np.isfinite(l1)

    checked = 0
    for slots in inner._accumulators.values():
        for acc in slots.values():
            arr = acc._data
            if arr.ndim == 0 or arr.shape[0] % N != 0:
                continue  # documented replication fallback for odd shapes
            frac, nsh = _per_device_fraction(arr)
            assert nsh == N
            assert frac <= 1.0 / N + 1e-9, (
                f"accumulator not sharded: {frac:.3f} of bytes on one device")
            checked += 1
    assert checked >= 4, "no sharded accumulators found — test is vacuous"


def test_stage2_compiled_step_reduce_scatters(sharded_world):
    model, inner, opt = _make(2, sharded_world)
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        step = _step_fn(model, opt)
        x, y = _data(sharded_world.mesh)
        float(step(x, y))
        txt = step.compiled_text()
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
    # the TPU SPMD partitioner emits a true reduce-scatter for this
    # pattern; the CPU emitter lowers the same semantics as
    # all-reduce + dynamic-slice. Either way the accumulator update must
    # consume a 1/N slice (the byte-level guarantee is pinned by the
    # stage-1/stage-3 tests).
    assert ("reduce-scatter" in txt
            or ("all-reduce" in txt and "dynamic-slice" in txt)), (
        "stage-2 step neither reduce-scatters nor slices gradients: "
        "optimizer updates are consuming fully replicated grads")
    # (a full-shape all-gather of the UPDATE is legitimate here — ZeRO
    # gathers updated param slices; accumulator-layout regressions are
    # caught byte-level by the stage-1/stage-3 tests)


def test_stage3_params_stay_sharded_and_gather_on_use(sharded_world):
    model, inner, opt = _make(3, sharded_world)
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    try:
        step = _step_fn(model, opt)
        x, y = _data()
        l0 = float(step(x, y))
        l1 = float(step(x, y))
        txt = step.compiled_text()
    finally:
        paddle.set_flags({"FLAGS_to_static_capture_lowered": False})
    assert np.isfinite(l0) and np.isfinite(l1)

    # storage between steps: parameters physically sharded
    checked = 0
    for p in model.parameters():
        arr = p._data
        if arr.ndim == 0 or arr.shape[0] % N != 0:
            continue
        frac, nsh = _per_device_fraction(arr)
        assert nsh == N
        assert frac <= 1.0 / N + 1e-9, (
            f"param {p.name} not sharded between steps ({frac:.3f})")
        checked += 1
    assert checked >= 2

    # the step gathers params on use (ZeRO-3 semantics)
    assert "all-gather" in txt, (
        "stage-3 step has no all-gather: either params were never sharded "
        "or XLA kept full replicas")


def test_stage3_convergence_matches_unsharded():
    """Sharding must not change numerics: same seed, same data, same loss
    trajectory as the plain optimizer."""
    rng = np.random.default_rng(0)
    x_np = rng.normal(0, 1, (16, D)).astype(np.float32)
    y_np = rng.normal(0, 1, (16, D)).astype(np.float32)

    paddle.seed(42)
    set_hybrid_communicate_group(None)
    ref_model = MLP()
    ref_opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=ref_model.parameters())
    ref_step = _step_fn(ref_model, ref_opt)
    ref = [float(ref_step(paddle.to_tensor(x_np), paddle.to_tensor(y_np)))
           for _ in range(5)]

    paddle.seed(42)
    hcg = HybridCommunicateGroup(sharding_degree=N)
    try:
        model = MLP()
        inner = paddle.optimizer.AdamW(learning_rate=1e-3,
                                       parameters=model.parameters())
        opt = DygraphShardingOptimizer(inner, hcg=hcg, stage=3)
        step = _step_fn(model, opt)
        got = [float(step(paddle.to_tensor(x_np), paddle.to_tensor(y_np)))
               for _ in range(5)]
    finally:
        set_hybrid_communicate_group(None)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6)
