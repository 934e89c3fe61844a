"""AOT compile-check: Llama-2-7B train step under dp x mp x pp hybrid.

The v5e dev chip cannot hold 7B for training, so this proves the NORTH-STAR
config LOWERS AND COMPILES: full 7B shapes (h=4096, inter=11008, L=32,
vocab=32000), AdamW fp32 state, bf16 compute, on an 8-device virtual mesh
(dp=2, mp=2, pp=2) with the same structure the framework uses on hardware —
blocks stacked over pp and scanned within each stage (jax.checkpoint),
megatron TP sharding over mp, batch over dp. Everything is ShapeDtypeStruct
specs — no 7B of host RAM is touched; jax.jit(...).lower().compile() on the
CPU backend exercises the full SPMD partitioner.

Run: python benchmarks/aot_7b_check.py       (writes AOT_7B.json)
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import paddle_tpu as paddle

if len(jax.devices()) < 8:
    raise SystemExit(
        "aot_7b_check needs 8 devices; for the virtual CPU mesh run with "
        "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8")

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# 7B geometry
V, H, I, L, HEADS = 32000, 4096, 11008, 32, 32
DP, MP, PP = 2, 2, 2
STAGE_LAYERS = L // PP
B, S, MICRO = 8, 2048, 4
HEAD_DIM = H // HEADS


def main():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(DP, MP, PP),
                ("dp", "mp", "pp"))

    def spec(shape, dtype, *pspec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*pspec)))

    # per-block leaves stacked (PP, STAGE_LAYERS, ...): pp shards dim 0;
    # megatron TP shards the projection feature dims over mp
    def block_specs(dtype):
        return {
            "wq": spec((PP, STAGE_LAYERS, H, H), dtype, "pp", None, None, "mp"),
            "wk": spec((PP, STAGE_LAYERS, H, H), dtype, "pp", None, None, "mp"),
            "wv": spec((PP, STAGE_LAYERS, H, H), dtype, "pp", None, None, "mp"),
            "wo": spec((PP, STAGE_LAYERS, H, H), dtype, "pp", None, "mp", None),
            "w_gate": spec((PP, STAGE_LAYERS, H, I), dtype, "pp", None, None, "mp"),
            "w_up": spec((PP, STAGE_LAYERS, H, I), dtype, "pp", None, None, "mp"),
            "w_down": spec((PP, STAGE_LAYERS, I, H), dtype, "pp", None, "mp", None),
            "ln1": spec((PP, STAGE_LAYERS, H), dtype, "pp", None, None),
            "ln2": spec((PP, STAGE_LAYERS, H), dtype, "pp", None, None),
        }

    params_specs = {
        "embed": spec((V, H), jnp.float32, "mp", None),
        "norm": spec((H,), jnp.float32, None),
        "head": spec((H, V), jnp.float32, None, "mp"),
        "blocks": block_specs(jnp.float32),
    }
    # AdamW fp32 state mirrors the param tree
    adam_specs = {
        "m": params_specs, "v": params_specs,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    ids_spec = spec((B, S), jnp.int32, "dp", None)

    def rms(x, w):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-5)
        return (y * w).astype(x.dtype)

    def block(p, j, x):
        h = rms(x, p["ln1"][j])
        q = (h @ p["wq"][j].astype(h.dtype)).reshape(*h.shape[:-1], HEADS, HEAD_DIM)
        k = (h @ p["wk"][j].astype(h.dtype)).reshape(*h.shape[:-1], HEADS, HEAD_DIM)
        v = (h @ p["wv"][j].astype(h.dtype)).reshape(*h.shape[:-1], HEADS, HEAD_DIM)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(HEAD_DIM)
        mask = jnp.tril(jnp.ones((h.shape[-2], h.shape[-2]), bool))
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
        a = jax.nn.softmax(logits, -1).astype(h.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(h.shape)
        x = x + o @ p["wo"][j].astype(h.dtype)
        h2 = rms(x, p["ln2"][j])
        ff = (jax.nn.silu(h2 @ p["w_gate"][j].astype(h.dtype))
              * (h2 @ p["w_up"][j].astype(h.dtype)))
        return x + ff @ p["w_down"][j].astype(h.dtype)

    def stage_fn(stage_params, x):
        # scan the stage's layers; checkpoint each layer body
        def body(h, j):
            return jax.checkpoint(
                lambda hh: block(stage_params, j, hh))(h), None
        out, _ = jax.lax.scan(body, x, jnp.arange(STAGE_LAYERS))
        return out

    from paddle_tpu.distributed.fleet.tpu_pipeline import pipelined_forward

    def loss_fn(params, ids):
        x = params["embed"].astype(jnp.bfloat16)[ids]  # (B, S, H) bf16
        micro = x.reshape(MICRO, B // MICRO, S, H)
        blocks_nostage = params["blocks"]  # leaves (PP, SL, ...)
        out = pipelined_forward(
            lambda sp, h: stage_fn(sp, h), blocks_nostage, micro, mesh,
            axis="pp", remat=True, batch_axis="dp")
        x = out.reshape(B, S, H)
        x = rms(x, params["norm"])
        logits = (x @ params["head"].astype(x.dtype)).astype(jnp.float32)
        tgt = ids[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1], -1)
        nll = -jnp.take_along_axis(lp, tgt[..., None], -1)
        return jnp.mean(nll)

    def train_step(params, adam, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        t = adam["step"] + 1
        b1, b2, lr, eps = 0.9, 0.95, 1e-4, 1e-8
        new_m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                             adam["m"], grads)
        new_v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                             adam["v"], grads)
        tf = t.astype(jnp.float32)
        new_p = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** tf))
            / (jnp.sqrt(v / (1 - b2 ** tf)) + eps),
            params, new_m, new_v)
        return new_p, {"m": new_m, "v": new_v, "step": t}, loss

    n_params = (V * H + H + H * V
                + PP * STAGE_LAYERS * (4 * H * H + 3 * H * I + 2 * H))
    print(f"7B config: {n_params/1e9:.2f}B params, mesh dp={DP} mp={MP} "
          f"pp={PP}, {STAGE_LAYERS} scanned layers/stage")

    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    lowered = jitted.lower(params_specs, adam_specs, ids_spec)
    print("lowered OK")
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    per_dev_args = ma.argument_size_in_bytes / 1e9
    print(f"compiled OK: per-device args {per_dev_args:.2f}GB, "
          f"temp {ma.temp_size_in_bytes/1e9:.2f}GB, "
          f"output {ma.output_size_in_bytes/1e9:.2f}GB")
    result = {
        "config": "llama2_7b dp2 x mp2 x pp2, scan-layers + remat, "
                  "bf16 compute / fp32 AdamW",
        "params_b": round(n_params / 1e9, 3),
        "lowered": True,
        "compiled": True,
        "per_device_argument_gb": round(per_dev_args, 3),
        "per_device_temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "per_device_output_gb": round(ma.output_size_in_bytes / 1e9, 3),
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "AOT_7B.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote", out_path)


if __name__ == "__main__":
    main()
