"""Compiled (shard_map+ppermute) pipeline schedule vs serial reference."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from paddle_tpu.distributed.fleet.tpu_pipeline import (pipelined_forward,
                                                       stack_stage_params)

S, M, B, D = 4, 8, 2, 16


def _setup():
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
    rng = np.random.default_rng(0)
    per_stage = [{"w": jnp.asarray(rng.normal(0, 0.3, (D, D)).astype(np.float32)),
                  "b": jnp.asarray(rng.normal(0, 0.1, (D,)).astype(np.float32))}
                 for _ in range(S)]
    micro = jnp.asarray(rng.normal(0, 1, (M, B, D)).astype(np.float32))
    return mesh, per_stage, micro


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_pipelined_forward_matches_serial():
    mesh, per_stage, micro = _setup()
    stacked = stack_stage_params(per_stage, mesh, "pp")
    out = pipelined_forward(_stage_fn, stacked, micro, mesh, "pp")
    ref = micro
    for p in per_stage:
        ref = _stage_fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_pipelined_grad_matches_serial():
    mesh, per_stage, micro = _setup()
    stacked = stack_stage_params(per_stage, mesh, "pp")

    def loss_fn(params, mi):
        return jnp.sum(pipelined_forward(_stage_fn, params, mi, mesh, "pp") ** 2)

    g = jax.grad(loss_fn)(stacked, micro)

    def ref_loss(params_list, mi):
        y = mi
        for p in params_list:
            y = _stage_fn(p, y)
        return jnp.sum(y ** 2)

    gref = jax.grad(ref_loss)(per_stage, micro)
    for s in range(S):
        np.testing.assert_allclose(np.asarray(g["w"][s]),
                                   np.asarray(gref[s]["w"]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(g["b"][s]),
                                   np.asarray(gref[s]["b"]), atol=1e-4)


# ---------------------------------------------------------------------------
# Fleet-wired pipeline: PipelineLayer -> PipelinedStack, loss parity vs serial
# ---------------------------------------------------------------------------

import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.pipeline_parallel import (LayerDesc,
                                                            PipelineLayer,
                                                            SharedLayerDesc)
from paddle_tpu.distributed.topology import (HybridCommunicateGroup,
                                             set_hybrid_communicate_group)

D, NBLK = 16, 8


class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(D, D)

    def forward(self, x):
        return paddle.tanh(self.fc(x))


class Head(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(D, 4)

    def forward(self, x):
        return self.fc(x)


class Emb(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(D, D)

    def forward(self, x):
        return self.fc(x)


def _mse(out, label):
    return ((out - label) ** 2).mean()


def _build_pipeline_layer():
    return PipelineLayer(
        layers=[LayerDesc(Emb)] + [LayerDesc(Block) for _ in range(NBLK)]
        + [LayerDesc(Head)],
        loss_fn=_mse)


def _train(model_like, params, data, labels, steps=4, lr=0.1):
    opt = paddle.optimizer.SGD(learning_rate=lr, parameters=params)
    losses = []
    for i in range(steps):
        if hasattr(model_like, "train_batch"):
            loss = model_like.train_batch(
                (data, labels), optimizer=opt)
        else:
            loss = _mse(model_like(data), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("dp,pp", [
    pytest.param(1, 2, marks=pytest.mark.slow),
    pytest.param(1, 4, marks=pytest.mark.slow),
    pytest.param(2, 4, marks=pytest.mark.slow),
])
def test_fleet_pipeline_parity_vs_serial(dp, pp):
    rng = np.random.default_rng(7)
    data_np = rng.normal(0, 1, (8, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (8, 4)).astype(np.float32)

    # serial reference: same seed -> identical init
    paddle.seed(123)
    set_hybrid_communicate_group(None)
    serial = _build_pipeline_layer()
    s_losses = _train(serial, serial.parameters(),
                      paddle.to_tensor(data_np), paddle.to_tensor(label_np))

    # pipelined: rebuild with the same seed under a dp x pp mesh
    paddle.seed(123)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": pp}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_pipeline_layer()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None, "pipelined path not taken"
        p_losses = _train(wrapped, wrapped.parameters(),
                          paddle.to_tensor(data_np),
                          paddle.to_tensor(label_np))
    finally:
        set_hybrid_communicate_group(None)

    np.testing.assert_allclose(p_losses, s_losses, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_fleet_pipeline_shared_embedding_grads():
    """Tied embed/head (SharedLayerDesc): both uses hit one parameter and
    its gradient is the sum of both paths — no explicit allreduce needed."""

    class TiedEmb(nn.Layer):
        def __init__(self):
            super().__init__()
            self.weight = self.create_parameter([D, D], dtype="float32")

        def forward(self, x):
            return paddle.matmul(x, self.weight)

    def head_fwd(layer, x):
        return paddle.matmul(x, layer.weight.t())

    def build():
        return PipelineLayer(
            layers=[SharedLayerDesc("emb", TiedEmb),
                    LayerDesc(Block), LayerDesc(Block),
                    LayerDesc(Block), LayerDesc(Block),
                    SharedLayerDesc("emb", TiedEmb, forward_func=head_fwd)],
            loss_fn=_mse)

    rng = np.random.default_rng(3)
    data_np = rng.normal(0, 1, (4, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (4, D)).astype(np.float32)

    paddle.seed(77)
    set_hybrid_communicate_group(None)
    serial = build()
    s_losses = _train(serial, serial.parameters(), paddle.to_tensor(data_np),
                      paddle.to_tensor(label_np))

    paddle.seed(77)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = build()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None
        # the tied weight must appear exactly ONCE in the engine's param
        # list (same object serves embed and head; duplication would break
        # the summed-gradient tying)
        params = wrapped.parameters()
        assert len({id(p) for p in params}) == len(params)
        tied_obj = model._shared["emb"].weight
        assert sum(1 for p in params if p is tied_obj) == 1
        p_losses = _train(wrapped, wrapped.parameters(),
                          paddle.to_tensor(data_np),
                          paddle.to_tensor(label_np))
    finally:
        set_hybrid_communicate_group(None)

    np.testing.assert_allclose(p_losses, s_losses, rtol=2e-4, atol=2e-5)


def test_non_uniform_stack_falls_back():
    """hetero_pipeline=False restores the documented grad-accumulation
    fallback for stacks the uniform engine cannot place."""
    paddle.seed(5)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"hetero_pipeline": False}
        fleet.init(is_collective=True, strategy=strategy)
        model = PipelineLayer(layers=[LayerDesc(Emb), LayerDesc(Head)],
                              loss_fn=_mse)
        with pytest.warns(UserWarning, match="grad-accumulation"):
            wrapped = fleet.distributed_model(model)
        assert wrapped._engine is None
    finally:
        set_hybrid_communicate_group(None)


def test_hetero_shape_varying_stack_dismantles_to_fallback():
    """Round 5: a shape-VARYING non-uniform stack gets the hetero engine at
    construction; the first call's boundary-shape validation DISMANTLES it
    (weights unpacked back into the original blocks) and training
    continues on the grad-accumulation fallback — the pre-round-5 UX for
    such stacks, with a warning instead of a silent engine."""
    from paddle_tpu.distributed.fleet.tpu_pipeline import HeteroPipelinedStack
    paddle.seed(5)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(9)
        model = PipelineLayer(layers=[LayerDesc(Emb), LayerDesc(Head)],
                              loss_fn=_mse)
        wrapped = fleet.distributed_model(model)
        assert isinstance(wrapped._engine, HeteroPipelinedStack)
        x = paddle.to_tensor(
            np.random.default_rng(0).normal(0, 1, (4, D)).astype(np.float32))
        with pytest.warns(UserWarning, match="Dismantled"):
            out = wrapped(x)
        assert wrapped._engine is None
        assert out.shape == [4, 4]
        # the dismantled weights are the originals: the fallback output
        # matches a same-seed serial twin
        paddle.seed(9)
        set_hybrid_communicate_group(None)
        twin = PipelineLayer(layers=[LayerDesc(Emb), LayerDesc(Head)],
                             loss_fn=_mse)
        np.testing.assert_allclose(out.numpy(), twin(x).numpy(),
                                   rtol=1e-5, atol=1e-6)
    finally:
        set_hybrid_communicate_group(None)


class WideBlock(nn.Layer):
    def __init__(self):
        super().__init__()
        self.up = nn.Linear(D, 2 * D)
        self.down = nn.Linear(2 * D, D)

    def forward(self, x):
        return x + self.down(paddle.tanh(self.up(x)))


class NarrowBlock(nn.Layer):
    def __init__(self):
        super().__init__()
        self.up = nn.Linear(D, D // 2)
        self.down = nn.Linear(D // 2, D)

    def forward(self, x):
        return x + self.down(paddle.nn.functional.relu(self.up(x)))


class GatedBlock(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(D, D)
        self.gate = nn.Linear(D, D)

    def forward(self, x):
        return x + self.fc(x) * paddle.nn.functional.sigmoid(self.gate(x))


def _build_hetero_layer():
    # aperiodic mix: no stage-periodic run exists, but every block is
    # shape-preserving (B, D) -> (B, D)
    descs = [LayerDesc(Emb), LayerDesc(WideBlock), LayerDesc(NarrowBlock),
             LayerDesc(WideBlock), LayerDesc(GatedBlock),
             LayerDesc(NarrowBlock), LayerDesc(GatedBlock), LayerDesc(Head)]
    return PipelineLayer(layers=descs, loss_fn=_mse)


@pytest.mark.slow
def test_hetero_pipeline_parity_vs_serial():
    """Round 5 (VERDICT r4 #4): non-uniform stacks train with REAL stage
    placement — switch-branch stages in the ppermute scan — and match the
    serial model's loss trajectory."""
    from paddle_tpu.distributed.fleet.tpu_pipeline import HeteroPipelinedStack
    rng = np.random.default_rng(21)
    data_np = rng.normal(0, 1, (8, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (8, 4)).astype(np.float32)

    paddle.seed(77)
    set_hybrid_communicate_group(None)
    serial = _build_hetero_layer()
    s_losses = _train(serial, serial.parameters(),
                      paddle.to_tensor(data_np), paddle.to_tensor(label_np))

    paddle.seed(77)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_hetero_layer()
        wrapped = fleet.distributed_model(model)
        assert isinstance(wrapped._engine, HeteroPipelinedStack), \
            "hetero engine not selected"
        p_losses = _train(wrapped, wrapped.parameters(),
                          paddle.to_tensor(data_np),
                          paddle.to_tensor(label_np))
    finally:
        set_hybrid_communicate_group(None)

    np.testing.assert_allclose(p_losses, s_losses, rtol=2e-4, atol=2e-5)


def test_hetero_pipeline_stage_placement_physical():
    """Each device stores only its stage's (padded) fused weights, and the
    compiled schedule really hops activations (collective-permute in HLO)."""
    from paddle_tpu.distributed.fleet.tpu_pipeline import HeteroPipelinedStack
    paddle.seed(3)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 4}
        strategy.pipeline_configs = {"accumulate_steps": 4}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_hetero_layer()
        wrapped = fleet.distributed_model(model)
        eng = wrapped._engine
        assert isinstance(eng, HeteroPipelinedStack)
        buf = eng._buffers["float32"]._data
        S = 4
        assert buf.shape[0] == S
        shards = buf.addressable_shards
        assert len(shards) >= S
        per_dev = {sh.device for sh in shards}
        assert len(per_dev) >= S  # spread over the pp axis, 1 row each
        for sh in shards:
            assert sh.data.shape[0] == 1  # one stage row per device

        # HLO of the schedule carries the ppermute hop
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.distributed.fleet.tpu_pipeline import pipelined_forward
        mesh = eng._mesh
        rows = {dt: eng._buffers[dt]._data for dt in eng._dtypes}
        micro = jnp.zeros((4, 2, D), jnp.float32)

        def fn(rows, micro):
            def stage_fn(rows_local, h):
                stage = jax.lax.axis_index("pp")
                return jax.lax.switch(
                    stage, [lambda h, s=s: eng._branch(s)(rows_local, h)
                            for s in range(S)], h)
            return pipelined_forward(stage_fn, rows, micro, mesh, "pp")

        hlo = jax.jit(fn).lower(rows, micro).compile().as_text()
        assert "collective-permute" in hlo
    finally:
        set_hybrid_communicate_group(None)


@pytest.mark.slow
def test_interleaved_vpp_parity_vs_serial():
    """virtual_pp_degree=2 (interleaved placement, upstream VPP parity):
    same numerics as serial; the option exists for schedule parity."""
    rng = np.random.default_rng(31)
    data_np = rng.normal(0, 1, (8, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (8, 4)).astype(np.float32)

    paddle.seed(55)
    set_hybrid_communicate_group(None)
    serial = _build_pipeline_layer()
    s_losses = _train(serial, serial.parameters(),
                      paddle.to_tensor(data_np), paddle.to_tensor(label_np))

    paddle.seed(55)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"accumulate_steps": 2,
                                     "virtual_pp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_pipeline_layer()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None and wrapped._engine._V == 2
        p_losses = _train(wrapped, wrapped.parameters(),
                          paddle.to_tensor(data_np),
                          paddle.to_tensor(label_np))
    finally:
        set_hybrid_communicate_group(None)

    np.testing.assert_allclose(p_losses, s_losses, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_engine_state_dict_roundtrip_and_eval():
    """Review regression: after engine construction, state_dict/forward on
    the wrapper must reflect the TRAINED stacked params (not the stale
    truncated PipelineLayer), and eval_batch must not inherit the training
    microbatch split."""
    paddle.seed(11)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"accumulate_steps": 4}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_pipeline_layer()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.normal(0, 1, (8, D)).astype(np.float32))
        y = paddle.to_tensor(rng.normal(0, 1, (8, 4)).astype(np.float32))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=wrapped.parameters())
        before = {k: np.asarray(v._data).copy()
                  for k, v in wrapped.state_dict().items()}
        wrapped.train_batch((x, y), optimizer=opt)
        after = wrapped.state_dict()
        changed = any(not np.allclose(before[k], np.asarray(v._data))
                      for k, v in after.items())
        assert changed, "state_dict does not reflect trained params"
        # roundtrip
        wrapped.set_state_dict(after)
        # eval on a batch size (6) NOT divisible by accumulate_steps (4)
        x6 = paddle.to_tensor(rng.normal(0, 1, (6, D)).astype(np.float32))
        y6 = paddle.to_tensor(rng.normal(0, 1, (6, 4)).astype(np.float32))
        loss = wrapped.eval_batch((x6, y6))
        assert np.isfinite(float(loss))
        # direct use of the consumed PipelineLayer is an error, not silence
        import pytest as _pytest
        with _pytest.raises(RuntimeError):
            model(x)
    finally:
        set_hybrid_communicate_group(None)


@pytest.mark.slow
def test_fleet_pipeline_parity_compiled_fast():
    """Fast-subset guard for the pipelined engine: pp=2 under to_static,
    2 steps, loss parity vs serial (full matrix in the slow-marked tests)."""
    rng = np.random.default_rng(9)
    data_np = rng.normal(0, 1, (8, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (8, 4)).astype(np.float32)

    def compiled_losses(model_like, params, is_pp):
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)

        @paddle.jit.to_static
        def step(x, y):
            if is_pp:
                return model_like.train_batch((x, y), optimizer=opt)
            loss = _mse(model_like(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x, y = paddle.to_tensor(data_np), paddle.to_tensor(label_np)
        return [float(step(x, y)) for _ in range(2)]

    paddle.seed(321)
    set_hybrid_communicate_group(None)
    serial = _build_pipeline_layer()
    ref = compiled_losses(serial, serial.parameters(), False)

    paddle.seed(321)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = _build_pipeline_layer()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None
        got = compiled_losses(wrapped, wrapped.parameters(), True)
        # eager train_batch path too (one step): first-loss must equal the
        # serial first loss (same init, same data)
        paddle.seed(321)
        set_hybrid_communicate_group(None)
        strategy2 = fleet.DistributedStrategy()
        strategy2.hybrid_configs = {"dp_degree": 1, "pp_degree": 2}
        strategy2.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy2)
        model2 = _build_pipeline_layer()
        wrapped2 = fleet.distributed_model(model2)
        opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                    parameters=wrapped2.parameters())
        eager_loss = float(wrapped2.train_batch(
            (paddle.to_tensor(data_np), paddle.to_tensor(label_np)),
            optimizer=opt2))
        np.testing.assert_allclose(eager_loss, ref[0], rtol=2e-4)
    finally:
        set_hybrid_communicate_group(None)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Heterogeneous (periodic) stacks: BERT-shaped alternating entries pipeline
# ---------------------------------------------------------------------------

class Attnish(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(D, D)

    def forward(self, x):
        return x + paddle.tanh(self.fc(x))


class MLPish(nn.Layer):
    def __init__(self):
        super().__init__()
        self.up = nn.Linear(D, 2 * D)
        self.down = nn.Linear(2 * D, D)

    def forward(self, x):
        return x + self.down(paddle.nn.functional.gelu(self.up(x)))


def test_find_uniform_run_periodic():
    from paddle_tpu.distributed.fleet.tpu_pipeline import find_uniform_run

    # (Attn, MLP) x 8 over 4 stages: period 2, 16 entries usable
    entries = []
    for _ in range(8):
        entries.append((Attnish(), None))
        entries.append((MLPish(), None))
    assert find_uniform_run(entries, 4) == (0, 16)
    # with edges around it
    bounded = [(Emb(), None)] + entries + [(Head(), None)]
    start, used = find_uniform_run(bounded, 4)
    assert (start, used) == (1, 16)
    # 6 repeats over 4 stages: only 4 repeats (8 entries) usable
    short = entries[:12]
    start, used = find_uniform_run(short, 4)
    assert used == 8


@pytest.mark.parametrize("dp,pp", [
    pytest.param(1, 2, marks=pytest.mark.slow),
    pytest.param(2, 4, marks=pytest.mark.slow),
])
def test_fleet_pipeline_periodic_stack_parity(dp, pp):
    """BERT-shaped PipelineLayer (alternating attention/MLP entries) takes
    the truly pipelined path and matches the serial trajectory."""
    def build():
        layers = [LayerDesc(Emb)]
        for _ in range(4):
            layers.append(LayerDesc(Attnish))
            layers.append(LayerDesc(MLPish))
        layers.append(LayerDesc(Head))
        return PipelineLayer(layers=layers, loss_fn=_mse)

    rng = np.random.default_rng(11)
    data_np = rng.normal(0, 1, (8, D)).astype(np.float32)
    label_np = rng.normal(0, 1, (8, 4)).astype(np.float32)

    paddle.seed(321)
    set_hybrid_communicate_group(None)
    serial = build()
    s_losses = _train(serial, serial.parameters(),
                      paddle.to_tensor(data_np), paddle.to_tensor(label_np))

    paddle.seed(321)
    try:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": dp, "pp_degree": pp}
        strategy.pipeline_configs = {"accumulate_steps": 2}
        fleet.init(is_collective=True, strategy=strategy)
        model = build()
        wrapped = fleet.distributed_model(model)
        assert wrapped._engine is not None, "periodic stack must pipeline"
        assert wrapped._engine._k == (4 // pp) * 2  # repeats/stage x period
        p_losses = _train(wrapped, wrapped.parameters(),
                          paddle.to_tensor(data_np),
                          paddle.to_tensor(label_np))
    finally:
        set_hybrid_communicate_group(None)

    np.testing.assert_allclose(p_losses, s_losses, rtol=2e-4, atol=2e-5)
