"""Distributed stack tests on the 8-device CPU mesh.

Patterns per SURVEY.md §4: collective numerics vs numpy; hybrid-parallel
loss equality vs the serial run (the core invariant).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.distributed as dist


@pytest.fixture(autouse=True)
def _reset_topology():
    # each test builds its own topology
    import paddle_tpu.distributed.topology as topo
    import paddle_tpu.distributed.fleet as fleet_mod
    saved = topo._hcg
    yield
    topo._hcg = saved
    fleet_mod._fleet_initialized = False


def _vals(g, shape=(3,)):
    return [np.full(shape, float(i + 1), np.float32) for i in range(g)]


def test_all_reduce_sum():
    t = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    dist.all_reduce(t)
    expected = sum(range(1, 9))
    np.testing.assert_allclose(t.numpy(), np.full((8, 3), expected))


def test_all_reduce_max_min():
    t = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    dist.all_reduce(t, op=dist.ReduceOp.MAX)
    np.testing.assert_allclose(t.numpy(), np.full((8, 3), 8.0))
    t2 = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    dist.all_reduce(t2, op=dist.ReduceOp.MIN)
    np.testing.assert_allclose(t2.numpy(), np.full((8, 3), 1.0))


def test_all_gather():
    t = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    out = []
    dist.all_gather(out, t)
    assert len(out) == 8
    for i, o in enumerate(out):
        np.testing.assert_allclose(o.numpy(), np.full((3,), i + 1.0))


def test_reduce_scatter():
    # each rank contributes (8*2,) -> each rank gets its 2-chunk of the sum
    vals = [np.arange(16, dtype=np.float32) + 100 * i for i in range(8)]
    t = dist.shard_stack([paddle.to_tensor(v) for v in vals])
    out = paddle.zeros([8, 2])
    dist.reduce_scatter(out, t)
    total = np.sum(np.stack(vals), axis=0)  # (16,)
    np.testing.assert_allclose(out.numpy(), total.reshape(8, 2))


def test_broadcast_and_scatter():
    t = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    dist.broadcast(t, src=3)
    np.testing.assert_allclose(t.numpy(), np.full((8, 3), 4.0))


def test_alltoall_single():
    # rank i sends chunk j (value i*10+j) to rank j
    vals = [np.array([i * 10 + j for j in range(8)], np.float32)
            for i in range(8)]
    t = dist.shard_stack([paddle.to_tensor(v) for v in vals])
    out = paddle.zeros([8, 8])
    dist.alltoall_single(out, t)
    o = out.numpy()
    for i in range(8):
        np.testing.assert_allclose(o[i], [j * 10 + i for j in range(8)])


def test_ppermute_shift():
    t = dist.shard_stack([paddle.to_tensor(v) for v in _vals(8)])
    out = dist.ppermute_shift(t, offset=1)
    o = out.numpy()
    # rank i's value moved to rank (i+1) % 8
    for i in range(8):
        np.testing.assert_allclose(o[(i + 1) % 8], np.full((3,), i + 1.0))


def test_fleet_init_and_topology():
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_data_parallel_world_size() == 2
    assert hcg.get_model_parallel_world_size() == 4
    assert tuple(hcg.mesh.shape[a] for a in ("dp", "mp")) == (2, 4)
    topo = hcg.topology
    assert topo.world_size() == 8


def test_column_row_parallel_matches_serial():
    """TP forward/backward parity vs plain Linear (core invariant)."""
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8}
    fleet.init(strategy=strategy)

    paddle.seed(5)
    col = fleet.ColumnParallelLinear(16, 32, gather_output=False, has_bias=True)
    row = fleet.RowParallelLinear(32, 16, input_is_parallel=True, has_bias=True)
    # serial twin with identical weights
    lin1 = nn.Linear(16, 32)
    lin2 = nn.Linear(32, 16)
    lin1.weight._set_data(np.asarray(col.weight._data))
    lin1.bias._set_data(np.asarray(col.bias._data))
    lin2.weight._set_data(np.asarray(row.weight._data))
    lin2.bias._set_data(np.asarray(row.bias._data))

    x = paddle.randn([4, 16])
    x2 = paddle.to_tensor(x.numpy(), stop_gradient=False)
    x.stop_gradient = False

    y_mp = paddle.mean(paddle.tanh(row(col(x))))
    y_serial = paddle.mean(paddle.tanh(lin2(lin1(x2))))
    np.testing.assert_allclose(float(y_mp), float(y_serial), rtol=1e-5)

    y_mp.backward()
    y_serial.backward()
    np.testing.assert_allclose(np.asarray(col.weight.grad._data),
                               lin1.weight.grad.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(x.grad.numpy(), x2.grad.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_vocab_parallel_embedding():
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8}
    fleet.init(strategy=strategy)
    paddle.seed(1)
    emb = fleet.VocabParallelEmbedding(64, 8)
    ref = nn.Embedding(64, 8)
    ref.weight._set_data(np.asarray(emb.weight._data))
    ids = paddle.randint(0, 64, [4, 6])
    np.testing.assert_allclose(emb(ids).numpy(), ref(ids).numpy(), rtol=1e-6)


def test_dp_training_loss_parity():
    """Data-parallel sharded-batch training == serial training."""
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1}
    fleet.init(strategy=strategy)

    def build():
        paddle.seed(3)
        m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
        o = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
        return m, o

    x_np = np.random.default_rng(0).normal(size=(16, 8)).astype(np.float32)
    y_np = np.random.default_rng(1).normal(size=(16, 4)).astype(np.float32)

    m1, o1 = build()
    dp = paddle.DataParallel(m1)
    x = dp.shard_input(paddle.to_tensor(x_np))
    y = dp.shard_input(paddle.to_tensor(y_np))

    @paddle.jit.to_static
    def dstep():
        loss = nn.functional.mse_loss(dp(x), y)
        loss.backward()
        o1.step()
        o1.clear_grad()
        return loss

    m2, o2 = build()
    x2, y2 = paddle.to_tensor(x_np), paddle.to_tensor(y_np)

    def sstep():
        loss = nn.functional.mse_loss(m2(x2), y2)
        loss.backward()
        o2.step()
        o2.clear_grad()
        return loss

    for i in range(3):
        ld, ls = float(dstep()), float(sstep())
        assert abs(ld - ls) < 1e-4, (i, ld, ls)
    np.testing.assert_allclose(np.asarray(m1[0].weight._data),
                               m2[0].weight.numpy(), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_sharding_stage_parity():
    """ZeRO stages keep the same numerics as the plain optimizer."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "sharding_degree": 8}
    fleet.init(strategy=strategy)

    x_np = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
    y_np = np.random.default_rng(1).normal(size=(8, 8)).astype(np.float32)

    losses = {}
    for level in ("plain", "os", "os_g", "p_g_os"):
        paddle.seed(9)
        m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 8))
        o = paddle.optimizer.AdamW(learning_rate=0.01,
                                   parameters=m.parameters())
        if level != "plain":
            m, o = group_sharded_parallel(m, o, level=level)
        x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
        ls = []
        for _ in range(4):
            loss = nn.functional.mse_loss(m(x), y)
            loss.backward()
            o.step()
            o.clear_grad()
            ls.append(float(loss))
        losses[level] = ls
    for level in ("os", "os_g", "p_g_os"):
        np.testing.assert_allclose(losses[level], losses["plain"],
                                   rtol=2e-4, atol=1e-5)
    # stage-3 params are actually sharded
    # (dim0=32 divisible by 8 for first linear weight? 16x32: dim0=16 -> yes)


def test_auto_parallel_shard_and_reshard():
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), dim_names=["x", "y"])
    t = dist.shard_tensor(np.arange(64, dtype=np.float32).reshape(8, 8),
                          mesh, [dist.Shard(0), dist.Shard(1)])
    assert t.shape == [8, 8]
    np.testing.assert_allclose(t.numpy(),
                               np.arange(64, dtype=np.float32).reshape(8, 8))
    r = dist.reshard(t, mesh, [dist.Replicate(), dist.Replicate()])
    np.testing.assert_allclose(r.numpy(), t.numpy())


def test_distributed_checkpoint_roundtrip(tmp_path):
    m = nn.Linear(8, 8)
    sd = m.state_dict()
    path = str(tmp_path / "dist_ckpt")
    dist.save_state_dict(sd, path)
    m2 = nn.Linear(8, 8)
    sd2 = m2.state_dict()
    # remap keys to the same names
    dist.load_state_dict(sd2, path)
    np.testing.assert_allclose(np.asarray(sd2["weight"]._data),
                               np.asarray(sd["weight"]._data))


@pytest.mark.slow
def test_sharded_embedding_deepfm_step():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.deepfm import DeepFM, DeepFMConfig
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8}
    fleet.init(strategy=strategy)
    paddle.seed(11)
    cfg = DeepFMConfig.tiny()
    model = DeepFM(cfg, sharded=True)
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=model.parameters())
    ids = paddle.randint(0, cfg.sparse_feature_number,
                         [16, cfg.num_sparse_fields])
    dense = paddle.randn([16, cfg.dense_feature_dim])
    labels = paddle.randint(0, 2, [16])
    first = None
    for _ in range(5):
        loss = model.loss(ids, dense, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
    assert float(loss) < first


def test_pipeline_layer_seg_method_layer_name_splits_at_named_blocks():
    # ISSUE 14 satellite (ADVICE r5): seg_method="layer:Name" must place
    # stage starts AT the named blocks, not hand back even cuts
    from paddle_tpu.distributed.fleet.pipeline_parallel import PipelineLayer
    built = [(nn.Linear(2, 2), None),          # embedding side
             (nn.Tanh(), None),
             (nn.Linear(2, 2), None),
             (nn.Tanh(), None),
             (nn.Linear(2, 2), None),
             (nn.Sigmoid(), None)]             # head side
    bounds = PipelineLayer._segment(built, 3, "layer:Linear")
    assert bounds[0] == 0 and bounds[-1] == len(built)
    # stages 1.. start exactly on Linear blocks
    for b in bounds[1:-1]:
        assert type(built[b][0]).__name__ == "Linear"
    assert sorted(bounds) == bounds and len(bounds) == 4


def test_pipeline_layer_seg_method_too_few_named_blocks_warns():
    # fewer named blocks than stages: loud warning + fallback counter +
    # count-balanced cuts (the old code silently linspace'd ALWAYS)
    import warnings as _warnings
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.fleet.pipeline_parallel import PipelineLayer
    built = [(nn.Tanh(), None), (nn.Linear(2, 2), None),
             (nn.Tanh(), None), (nn.Tanh(), None)]
    obs.enable()
    before = obs.snapshot().get("pipeline.seg_method_fallbacks_total", 0)
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        bounds = PipelineLayer._segment(built, 2, "layer:Linear")
    assert bounds == [0, 2, 4]           # count-balanced fallback
    assert any("found only 1 'Linear'" in str(x.message) for x in w)
    assert obs.snapshot()["pipeline.seg_method_fallbacks_total"] \
        == before + 1


@pytest.mark.slow
def test_pipeline_layer_microbatch_parity():
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import LayerDesc, PipelineLayer
    from paddle_tpu.distributed.fleet.pipeline_parallel import PipelineParallel
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 2}
    strategy.pipeline_configs = {"accumulate_steps": 4}
    fleet.init(strategy=strategy)

    paddle.seed(21)
    pipe = PipelineLayer(
        layers=[LayerDesc(nn.Linear, 8, 16), LayerDesc(nn.Tanh),
                LayerDesc(nn.Linear, 16, 8), LayerDesc(nn.Linear, 8, 4)],
        num_stages=2,
        loss_fn=nn.MSELoss())
    opt1 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=pipe.parameters())
    pp = PipelineParallel(pipe, strategy=strategy)

    # serial twin
    paddle.seed(21)
    serial = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8),
                           nn.Linear(8, 4))
    opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=serial.parameters())

    x_np = np.random.default_rng(2).normal(size=(8, 8)).astype(np.float32)
    y_np = np.random.default_rng(3).normal(size=(8, 4)).astype(np.float32)

    loss_pp = float(pp.train_batch(
        (paddle.to_tensor(x_np), paddle.to_tensor(y_np)), optimizer=opt1))
    loss_serial = nn.functional.mse_loss(serial(paddle.to_tensor(x_np)),
                                         paddle.to_tensor(y_np))
    loss_serial.backward()
    opt2.step()
    np.testing.assert_allclose(loss_pp, float(loss_serial), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pipe.run_function[0].weight._data),
                               serial[0].weight.numpy(), rtol=1e-4, atol=1e-5)
