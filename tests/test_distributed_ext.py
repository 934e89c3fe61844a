"""Tests for the distributed extension batch: fleet.utils.recompute,
parallelize plans, unshard_dtensor, passes, rpc (in-process), MoE dispatch
utils, and distribution transforms."""

import subprocess
import sys
import textwrap

import numpy as np
import pytest
from scipy.stats import lognorm, norm

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.distributed.fleet.utils import (LocalFS, recompute,
                                                recompute_sequential)


class TestRecompute:
    def _zero_grads(self, *tensors):
        for t in tensors:
            t.clear_grad()

    def test_matches_plain_backward(self):
        paddle.seed(0)
        lin1, lin2 = nn.Linear(8, 8), nn.Linear(8, 8)
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"),
                             stop_gradient=False)

        def block(inp):
            return lin2(nn.functional.relu(lin1(inp)))

        y_ref = block(x)
        y_ref.sum().backward()
        gx = np.asarray(x.grad.numpy()).copy()
        gw = np.asarray(lin1.weight.grad.numpy()).copy()
        self._zero_grads(x, lin1.weight, lin1.bias, lin2.weight, lin2.bias)

        y = recompute(block, x)
        np.testing.assert_allclose(y.numpy(), y_ref.numpy(), atol=1e-6)
        y.sum().backward()
        np.testing.assert_allclose(np.asarray(x.grad.numpy()), gx, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lin1.weight.grad.numpy()), gw,
                                   atol=1e-5)

    def test_preserves_rng(self):
        paddle.seed(7)
        drop = nn.Dropout(0.5)
        drop.train()
        x = paddle.to_tensor(np.random.randn(64,).astype("float32"),
                             stop_gradient=False)
        y = recompute(lambda v: drop(v) * v, x)
        y.sum().backward()  # re-run must see the SAME dropout mask
        # if the mask differed, grads would mismatch the forward's zeros
        out = np.asarray(y.numpy())
        g = np.asarray(x.grad.numpy())
        np.testing.assert_allclose((out == 0), (g == 0))

    def test_no_grad_passthrough(self):
        x = paddle.to_tensor(np.ones((2, 2), "float32"))  # stop_gradient
        y = recompute(lambda v: v * 3, x)
        np.testing.assert_allclose(y.numpy(), 3.0)

    def test_sequential_segments(self):
        paddle.seed(0)
        seq = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 8))
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"),
                             stop_gradient=False)
        y_ref = seq(x)
        y = recompute_sequential({"segments": 2}, seq, x)
        np.testing.assert_allclose(y.numpy(), y_ref.numpy(), atol=1e-6)
        y.sum().backward()
        assert x.grad is not None

    def test_under_to_static(self):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 8))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())

        @paddle.jit.to_static
        def step(inp):
            out = recompute(lambda v: model(v), inp)
            loss = (out * out).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        l1 = float(step(paddle.to_tensor(
            np.random.randn(4, 8).astype("float32"))))
        l2 = float(step(paddle.to_tensor(
            np.random.randn(4, 8).astype("float32"))))
        assert np.isfinite(l1) and np.isfinite(l2)


class TestParallelize:
    def test_col_row_plans(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                                dim_names=["dp", "mp"])

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(16, 32)
                self.fc2 = nn.Linear(32, 16)

            def forward(self, x):
                return self.fc2(nn.functional.relu(self.fc1(x)))

        m = M()
        dist.parallelize(m, mesh=mesh, config={"mp_config": {
            "parallelize_plan": {"fc1": dist.ColWiseParallel(),
                                 "fc2": dist.RowWiseParallel()}}})
        assert str(m.fc1.weight._data.sharding.spec) == \
            "PartitionSpec(None, 'mp')"
        assert str(m.fc2.weight._data.sharding.spec) == \
            "PartitionSpec('mp', None)"
        out = m(paddle.to_tensor(np.random.randn(4, 16).astype("float32")))
        assert out.shape == [4, 16]
        assert np.isfinite(out.numpy()).all()

    def test_requires_mesh(self):
        dist.set_mesh(None) if hasattr(dist, "set_mesh") else None
        import paddle_tpu.distributed.auto_parallel_api as apa
        old = apa._global_mesh
        apa._global_mesh = None
        try:
            with pytest.raises(ValueError, match="mesh"):
                dist.parallelize(nn.Linear(2, 2), config={})
        finally:
            apa._global_mesh = old

    def test_unshard_dtensor(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                                dim_names=["x", "y"])
        st = dist.shard_tensor(np.random.randn(8, 4).astype("float32"), mesh,
                               [dist.Shard(0), dist.Replicate()])
        un = dist.unshard_dtensor(st)
        assert un.shape == [8, 4]
        np.testing.assert_allclose(un.numpy(), st.numpy())

    def test_to_distributed(self):
        model = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        m2, o2 = dist.to_distributed(model, opt)
        out = m2(paddle.to_tensor(np.random.randn(2, 4).astype("float32")))
        assert out.shape == [2, 4]


class TestMoEUtils:
    def test_global_scatter_gather_single_proc(self):
        x = paddle.to_tensor(np.random.randn(6, 4).astype("float32"))
        lc = paddle.to_tensor(np.array([4, 2], "int64"))
        out = dist.global_scatter(x, lc, lc)
        np.testing.assert_allclose(out.numpy(), x.numpy())
        back = dist.global_gather(out, lc, lc)
        np.testing.assert_allclose(back.numpy(), x.numpy())


class TestPasses:
    def test_registry_and_manager(self):
        from paddle_tpu.distributed.passes import PassManager, new_pass
        p = new_pass("fuse_gemm_epilogue")
        assert "fuse_gemm_epilogue" in repr(p)
        pm = PassManager([p, new_pass("auto_parallel_recompute")])
        pm.apply()
        assert all(x.applied for x in pm._passes)


class TestLocalFS:
    def test_roundtrip(self, tmp_path):
        fs = LocalFS()
        d = str(tmp_path / "sub")
        fs.mkdirs(d)
        assert fs.is_exist(d) and fs.is_dir(d)
        f = str(tmp_path / "sub" / "a.txt")
        fs.touch(f)
        assert fs.is_file(f)
        dirs, files = fs.ls_dir(str(tmp_path))
        assert dirs == ["sub"]
        fs.delete(d)
        assert not fs.is_exist(d)


class TestRPC:
    @pytest.mark.slow
    def test_two_process_rpc(self, tmp_path):
        script = textwrap.dedent("""
            import os, sys, time
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax; jax.config.update("jax_platforms", "cpu")
            sys.path.insert(0, %r)
            import paddle_tpu.distributed.rpc as rpc

            def mul(a, b):
                return a * b

            rank = int(sys.argv[1])
            rpc.init_rpc(f"w{rank}", rank=rank, world_size=2,
                         master_endpoint="127.0.0.1:29574")
            if rank == 0:
                assert rpc.rpc_sync("w1", mul, args=(6, 7)) == 42
                fut = rpc.rpc_async("w1", mul, args=(2, 4))
                assert fut.result() == 8
                assert len(rpc.get_all_worker_infos()) == 2
                print("RPC_SUBTEST_OK")
            else:
                time.sleep(2.5)
            rpc.shutdown()
        """) % "/root/repo"
        p = tmp_path / "rpc_test.py"
        p.write_text(script)
        w1 = subprocess.Popen([sys.executable, str(p), "1"])
        out = subprocess.run([sys.executable, str(p), "0"],
                             capture_output=True, text=True, timeout=60)
        w1.wait(timeout=30)
        assert "RPC_SUBTEST_OK" in out.stdout, out.stdout + out.stderr


class TestDistributionTransforms:
    def test_lognormal_via_exp_transform(self):
        from paddle_tpu.distribution import (ExpTransform, Normal,
                                             TransformedDistribution)
        ln = TransformedDistribution(Normal(0.0, 1.0), [ExpTransform()])
        v = np.array([0.5, 1.0, 2.0], "float32")
        np.testing.assert_allclose(
            np.asarray(ln.log_prob(paddle.to_tensor(v)).numpy()),
            lognorm.logpdf(v, 1.0), atol=1e-5)

    def test_affine_transform(self):
        from paddle_tpu.distribution import (AffineTransform, Normal,
                                             TransformedDistribution)
        d = TransformedDistribution(Normal(0.0, 1.0),
                                    [AffineTransform(3.0, 2.0)])
        v = np.array([0.5, 1.0, 2.0], "float32")
        np.testing.assert_allclose(
            np.asarray(d.log_prob(paddle.to_tensor(v)).numpy()),
            norm.logpdf(v, 3, 2), atol=1e-5)
        s = d.sample((2000,))
        assert abs(float(s.numpy().mean()) - 3.0) < 0.3

    def test_transform_inverse_roundtrip(self):
        from paddle_tpu.distribution import (ChainTransform, SigmoidTransform,
                                             TanhTransform)
        x = paddle.to_tensor(np.random.randn(5).astype("float32"))
        for t in (SigmoidTransform(), TanhTransform(),
                  ChainTransform([TanhTransform(), SigmoidTransform()])):
            y = t.forward(x)
            back = t.inverse(y)
            np.testing.assert_allclose(back.numpy(), x.numpy(), atol=1e-4)

    def test_tanh_log_det(self):
        from paddle_tpu.distribution import TanhTransform
        t = TanhTransform()
        x = paddle.to_tensor(np.array([0.3], "float32"))
        ld = float(t.forward_log_det_jacobian(x))
        ref = np.log(1 - np.tanh(0.3) ** 2)
        assert abs(ld - ref) < 1e-5


class TestReviewFixes5:
    def test_transformed_discrete_base_sample(self):
        from paddle_tpu.distribution import (AffineTransform, Bernoulli,
                                             TransformedDistribution)
        d = TransformedDistribution(Bernoulli(0.5), [AffineTransform(0.0, 2.0)])
        s = d.sample((100,))
        vals = set(np.unique(np.asarray(s.numpy())).tolist())
        assert vals <= {0.0, 2.0}

    def test_rpc_async_wrapper_has_wait(self):
        from concurrent.futures import Future
        from paddle_tpu.distributed.rpc import FutureWrapper
        f = Future()
        f.set_result(11)
        w = FutureWrapper(f)
        assert w.wait() == 11 and w.done()
        assert not hasattr(Future, "wait")

    @pytest.mark.slow
    def test_yolo_loss_gt_score_scales_objectness(self):
        from paddle_tpu.vision import ops as vops
        cn, na = 2, 1
        gtb = paddle.to_tensor(np.array([[[0.5, 0.5, 0.4, 0.4]]], "float32"))
        gtl = paddle.to_tensor(np.zeros((1, 1), "int32"))
        x = paddle.to_tensor(np.zeros((1, na * (5 + cn), 4, 4), "float32"))
        l_full = float(vops.yolo_loss(x, gtb, gtl, anchors=[13, 13],
                                      anchor_mask=[0], class_num=cn,
                                      ignore_thresh=0.7, downsample_ratio=8,
                                      gt_score=paddle.to_tensor(
                                          np.ones((1, 1), "float32"))).sum())
        l_half = float(vops.yolo_loss(x, gtb, gtl, anchors=[13, 13],
                                      anchor_mask=[0], class_num=cn,
                                      ignore_thresh=0.7, downsample_ratio=8,
                                      gt_score=paddle.to_tensor(
                                          np.full((1, 1), 0.5, "float32"))).sum())
        assert l_full != l_half  # objectness target follows the score

    def test_model_average_no_reset_cliff(self):
        from paddle_tpu.core.tensor import Parameter
        from paddle_tpu.incubate.optimizer import ModelAverage
        p = Parameter(np.array([1.0], "float32"), name="ma_cliff")
        ma = ModelAverage(0.5, parameters=[p], min_average_window=2,
                          max_average_window=4)
        for _ in range(5):  # crosses the max window
            ma.step()
        with ma.apply():
            # average of a constant parameter must stay that constant
            np.testing.assert_allclose(np.asarray(p.numpy()), [1.0],
                                       rtol=1e-6)


# ---------------------------------------------------------------------------
# round-3 tail: gather / get_group / split (upstream paddle.distributed)
# ---------------------------------------------------------------------------

def test_gather_and_get_group():
    import paddle_tpu.distributed as dist

    paddle.distributed.init_parallel_env()
    gl = []
    t = dist.shard_stack([paddle.to_tensor(np.full(2, float(i), np.float32))
                          for i in range(8)])
    dist.gather(t, gl, dst=0)
    assert len(gl) == 8
    np.testing.assert_allclose(gl[3].numpy(), 3.0)
    assert dist.get_group(0) is not None


def test_split_functional_mp():
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import set_hybrid_communicate_group

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        x = paddle.to_tensor(np.random.default_rng(0).normal(
            0, 1, (4, 8)).astype(np.float32))
        y = dist.split(x, (8, 16), operation="linear", axis=1,
                       gather_out=True, name="ut_s1")
        assert y.shape == [4, 16]
        # cached layer: same weights on reuse
        y2 = dist.split(x, (8, 16), operation="linear", axis=1,
                        gather_out=True, name="ut_s1")
        np.testing.assert_allclose(y.numpy(), y2.numpy())
        yr = dist.split(x, (8, 16), operation="linear", axis=0,
                        name="ut_s2")
        assert yr.shape == [4, 16]
        ids = paddle.to_tensor(np.array([[1, 5, 9]], np.int64))
        e = dist.split(ids, (100, 8), operation="embedding", name="ut_e1")
        assert e.shape == [1, 3, 8]
        with pytest.raises(ValueError):
            dist.split(x, (8, 16), operation="conv", name="ut_bad")
    finally:
        set_hybrid_communicate_group(None)
