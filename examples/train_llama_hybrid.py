"""BASELINE config #4: Llama under hybrid parallel (dp x mp mesh).

Run on the virtual CPU mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/train_llama_hybrid.py --dp 2 --mp 4
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--mp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    need = args.dp * args.mp

    import jax

    import paddle_tpu as paddle

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.core.tensor import _state_registry
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    devs = jax.devices()
    if len(devs) < need:
        # never a quiet switch to another backend: the virtual CPU mesh is
        # something the caller asks for (see the module docstring)
        raise SystemExit(
            f"--dp {args.dp} --mp {args.mp} needs {need} devices; jax sees "
            f"{len(devs)} on platform {devs[0].platform!r}. For the virtual "
            f"CPU mesh run with JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    mesh = Mesh(np.array(devs[:need]).reshape(args.dp, args.mp),
                ("dp", "mp"))

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=8,
                           kv_heads=8, inter=256, max_pos=128)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def spec_for(name):
        if any(k in name for k in ("q_proj", "k_proj", "v_proj",
                                   "gate_proj", "up_proj")):
            return P(None, "mp")   # column parallel
        if any(k in name for k in ("o_proj", "down_proj")):
            return P("mp", None)   # row parallel
        return P()

    with mesh:
        for name, p in model.state_dict().items():
            p._set_data(jax.device_put(
                p._data, NamedSharding(mesh, spec_for(name))))
        sharded = {id(p) for p in model.state_dict().values()}
        for t in _state_registry.alive():
            if id(t) not in sharded:
                t._set_data(jax.device_put(t._data, NamedSharding(mesh, P())))

        @paddle.jit.to_static
        def step(ids):
            loss, _ = model(ids, labels=ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.default_rng(0)
        for i in range(args.steps):
            ids = jax.device_put(
                rng.integers(0, cfg.vocab_size, (args.dp * 2, 64),
                             dtype=np.int32),
                NamedSharding(mesh, P("dp", None)))
            loss = step(paddle.Tensor(ids))
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
