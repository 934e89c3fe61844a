"""4-process x 2-device hybrid (dp2 x mp4) worker (SURVEY §4 TestDistBase).

Launched by tests/test_multiprocess.py. Each process owns TWO cpu devices;
the four processes form the 8-device global mesh (dp=2, mp=4). The train
step is ONE pjit program with megatron-style TP (column-parallel w1,
row-parallel w2) over ``mp`` and the batch sharded over ``dp`` — XLA
inserts the cross-process collectives. Rank 0 prints the loss trajectory;
at the end every process participates in a distributed checkpoint save
(per-process shards via orbax), which the test then loads SINGLE-process
on a different topology (reshard-on-load across process counts).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# this worker IS a slice of the virtual CPU mesh: 2 local cpu devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import paddle_tpu as paddle

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

B, D, H = 8, 16, 32


def main():
    out_dir = sys.argv[1]
    paddle.distributed.init_parallel_env()
    assert jax.process_count() == 4, jax.process_count()
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    devs = jax.devices()
    assert len(devs) == 8, devs

    mesh = Mesh(np.array(devs).reshape(2, 4), ("dp", "mp"))
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P("dp"))
    col_sh = NamedSharding(mesh, P(None, "mp"))   # w1: (D, H) col-parallel
    row_sh = NamedSharding(mesh, P("mp", None))   # w2: (H, 1) row-parallel

    rng = np.random.default_rng(0)
    x_np = rng.normal(0, 1, (B, D)).astype(np.float32)
    y_np = rng.normal(0, 1, (B, 1)).astype(np.float32)
    w1_np = rng.normal(0, 0.3, (D, H)).astype(np.float32)
    w2_np = rng.normal(0, 0.3, (H, 1)).astype(np.float32)

    def make(sharding, host):
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    x = make(batch_sh, x_np)
    y = make(batch_sh, y_np)
    w1 = make(col_sh, w1_np)
    w2 = make(row_sh, w2_np)

    @jax.jit
    def step(w1, w2, x, y):
        def loss_fn(w1, w2):
            h = jnp.tanh(x @ w1)      # col-parallel: h sharded over mp
            pred = h @ w2             # row-parallel: psum over mp by XLA
            return jnp.mean((pred - y) ** 2)
        loss, (g1, g2) = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2)
        return w1 - 0.1 * g1, w2 - 0.1 * g2, loss

    losses = []
    for _ in range(4):
        w1, w2, loss = step(w1, w2, x, y)
        losses.append(float(jax.device_get(jax.device_put(loss, repl))))
    if rank == 0:
        print("losses " + " ".join(f"{v:.6f}" for v in losses), flush=True)

    # distributed checkpoint: every process saves only its addressable
    # shards; the test reloads single-process on a DIFFERENT topology
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.checkpoint import save_state_dict
    state = {"model": {"w1": Tensor(w1), "w2": Tensor(w2)},
             "meta": {"steps": Tensor(jnp.asarray(4.0))}}
    save_state_dict(state, out_dir)
    if rank == 0:
        print("ckpt_saved", flush=True)


if __name__ == "__main__":
    main()
