"""2-process data-parallel worker (SURVEY §4 TestDistBase pattern).

Launched by tests/test_multiprocess.py via paddle_tpu.distributed.launch.
Each process owns ONE cpu device; init_parallel_env bootstraps
jax.distributed from the launcher's env contract; the train step runs as a
pjit program over the 2-device global mesh, with the batch assembled from
per-process local shards. Rank 0 prints the loss trajectory for the parity
check against a single-process run.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# this worker IS a slice of the virtual CPU mesh: 1 local cpu device
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import paddle_tpu as paddle

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main():
    paddle.distributed.init_parallel_env()
    assert jax.process_count() == 2, jax.process_count()
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = jax.process_count()

    devs = jax.devices()
    assert len(devs) == world, devs
    mesh = Mesh(np.array(devs), ("dp",))
    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("dp"))

    # cross-process collective sanity: psum of (rank+1) over dp == 3
    local = np.full((1, 4), float(rank + 1), np.float32)
    garr = jax.make_array_from_process_local_data(row, local)
    total = jax.jit(lambda a: jnp.sum(a[:, 0]),
                    out_shardings=repl)(garr)
    np.testing.assert_allclose(np.asarray(total), 3.0)
    if rank == 0:
        print("allreduce_ok 3.0", flush=True)

    # DP train step parity: global batch 4, each process feeds its half
    D = 8
    rng = np.random.default_rng(0)
    x_np = rng.normal(0, 1, (4, D)).astype(np.float32)
    y_np = rng.normal(0, 1, (4, 1)).astype(np.float32)
    w0 = (np.arange(D, dtype=np.float32).reshape(D, 1) / D) - 0.5

    half = slice(rank * 2, rank * 2 + 2)
    x = jax.make_array_from_process_local_data(row, x_np[half])
    y = jax.make_array_from_process_local_data(row, y_np[half])
    w = jax.device_put(w0, repl)

    @jax.jit
    def step(w, x, y):
        def loss_fn(w):
            return jnp.mean((x @ w - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - 0.1 * g, loss

    losses = []
    for _ in range(5):
        w, loss = step(w, x, y)
        losses.append(float(jax.device_get(
            jax.device_put(loss, repl))))
    if rank == 0:
        print("losses " + " ".join(f"{v:.6f}" for v in losses), flush=True)


if __name__ == "__main__":
    main()
