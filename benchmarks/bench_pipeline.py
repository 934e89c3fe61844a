"""Pipeline-parallel schedule: activation-memory profile + step time.

Evidence for the GPipe-with-remat schedule choice (SURVEY §7 hard-part 1):
1F1B's advantage over plain GPipe is bounding live activations at O(S)
microbatches instead of O(M). Under XLA, `jax.checkpoint` on the stage body
achieves the same bound inside the scan — only the per-tick boundary
activation rides the carry; block internals are recomputed in backward.
This script measures the compiled backward's temp-buffer footprint with and
without remat (XLA memory_analysis), and the cached step time.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
     python benchmarks/bench_pipeline.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import paddle_tpu as paddle

if len(jax.devices()) < 4:
    raise SystemExit(
        "bench_pipeline needs 4 devices; for the virtual CPU mesh run with "
        "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4")

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from paddle_tpu.distributed.fleet.tpu_pipeline import (pipelined_forward,
                                                       stack_stage_params)

S, M, B, L, D = 4, 8, 4, 128, 256


def main():
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))
    rng = np.random.default_rng(0)
    per_stage = [
        {"w1": jnp.asarray(rng.normal(0, 0.02, (D, 4 * D)).astype(np.float32)),
         "w2": jnp.asarray(rng.normal(0, 0.02, (4 * D, D)).astype(np.float32))}
        for _ in range(S)]
    micro = jnp.asarray(rng.normal(0, 1, (M, B, L, D)).astype(np.float32))
    stacked = stack_stage_params(per_stage, mesh, "pp")

    def stage(p, x):
        return jnp.tanh(jnp.tanh(x @ p["w1"]) @ p["w2"]) + x

    rows = {}
    for remat in (True, False):
        def loss(params, mi, _remat=remat):
            out = pipelined_forward(stage, params, mi, mesh, "pp",
                                    remat=_remat)
            return jnp.sum(out ** 2)

        g = jax.jit(jax.grad(loss))
        compiled = g.lower(stacked, micro).compile()
        ma = compiled.memory_analysis()
        g(stacked, micro)  # warm
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(g(stacked, micro))
        dt = (time.perf_counter() - t0) / 10
        rows[remat] = (ma.temp_size_in_bytes / 1e6, dt * 1e3)
        print(f"remat={remat}: temp={rows[remat][0]:.1f}MB "
              f"step={rows[remat][1]:.1f}ms")
    ratio = rows[False][0] / rows[True][0]
    print(f"activation-memory reduction from remat: {ratio:.2f}x "
          f"(S={S}, M={M}: GPipe+remat holds the O(S) boundary activations "
          f"1F1B targets)")

    bubble_and_overlap(mesh, per_stage, stacked, stage)
    vpp_comparison(mesh, per_stage, stage)


# ---------------------------------------------------------------------------
# Bubble measurement + ppermute-overlap evidence + VPP refutation
# ---------------------------------------------------------------------------

def bubble_and_overlap(mesh, per_stage, stacked, stage):
    """Measure the fill/drain cost directly.

    In the compiled SPMD scan every stage computes every tick, so the
    pipeline 'bubble' is not idle time — it is WASTED COMPUTE on the
    (S - 1) fill/drain ticks: utilization = M / (M + S - 1), the same
    fraction 1F1B loses to its bubble. Two consequences this measures:

    * per-microbatch time should scale as (M + S - 1) / M — doubling M
      must NOT double step time;
    * vs the grad-accumulation fallback (serial M x full-model fwd+bwd on
      every device, no stage placement) the pipelined step trades the
      (M + S - 1)/M waste for 1/S of the per-device parameter memory and
      compute-per-device.
    """
    import jax

    print("\n-- bubble: per-microbatch tick scaling (model: (M+S-1)/M) --")
    times = {}
    for m in (4, 8, 16):
        micro = jnp.asarray(np.random.default_rng(0).normal(
            0, 1, (m, B, L, D)).astype(np.float32))

        def loss(params, mi):
            return jnp.sum(pipelined_forward(stage, params, mi, mesh,
                                             "pp") ** 2)

        g = jax.jit(jax.grad(loss))
        jax.block_until_ready(g(stacked, micro))
        t0 = time.perf_counter()
        for _ in range(8):
            jax.block_until_ready(g(stacked, micro))
        dt = (time.perf_counter() - t0) / 8
        times[m] = dt
        model = (m + S - 1) / m
        print(f"M={m:2d}: step={dt * 1e3:7.1f}ms  per-mb={dt / m * 1e3:6.1f}ms"
              f"  waste-model={model:.3f}  bubble={(S - 1) / (m + S - 1):.1%}")
    # measured per-microbatch ratio M=4 vs M=16 should approach the model
    meas = (times[4] / 4) / (times[16] / 16)
    model = ((4 + S - 1) / 4) / ((16 + S - 1) / 16)
    print(f"per-mb time ratio M=4/M=16: measured {meas:.2f} "
          f"vs fill/drain model {model:.2f}")

    # serial grad-accumulation fallback: every device runs the full model
    micro = jnp.asarray(np.random.default_rng(0).normal(
        0, 1, (M, B, L, D)).astype(np.float32))

    def serial_loss(params_list, mi):
        total = 0.0
        for k in range(M):
            y = mi[k]
            for p in params_list:
                y = stage(p, y)
            total = total + jnp.sum(y ** 2)
        return total

    gs = jax.jit(jax.grad(serial_loss))
    jax.block_until_ready(gs(per_stage, micro))
    t0 = time.perf_counter()
    for _ in range(8):
        jax.block_until_ready(gs(per_stage, micro))
    dts = (time.perf_counter() - t0) / 8
    print(f"grad-accum fallback (full model on every device): "
          f"{dts * 1e3:.1f}ms vs pipelined {times[M] * 1e3:.1f}ms "
          f"(pipelined also holds only 1/{S} of the params per device)")

    # ppermute/compute overlap evidence: the compiled HLO issues the
    # collective-permute asynchronously (start/done pair with compute
    # scheduled between) — the XLA analogue of NCCL-stream overlap
    def loss8(params, mi):
        return jnp.sum(pipelined_forward(stage, params, mi, mesh, "pp") ** 2)

    txt = jax.jit(jax.grad(loss8)).lower(stacked, micro).compile().as_text()
    starts = txt.count("collective-permute-start")
    dones = txt.count("collective-permute-done")
    async_pairs = starts > 0 and dones > 0
    print(f"CPU HLO: {starts} collective-permute-start / {dones} -done pairs "
          f"({'ASYNC' if async_pairs else 'sync (CPU backend lowers ppermute synchronously)'})")

    # the claim that matters is about the TPU backend: AOT-compile the same
    # scan+ppermute structure against a virtual v5e 2x2 topology (no chips
    # needed) and count the async start/done pairs the TPU scheduler emits
    try:
        from jax.experimental import topologies
        from jax.sharding import Mesh as _Mesh, PartitionSpec as _P
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        tmesh = _Mesh(np.array(topo.devices).reshape(4), ("pp",))

        def tbody(x):
            w = jnp.zeros((D, D), jnp.bfloat16)

            def tick(c, _):
                y = jnp.tanh(c @ w)
                return jax.lax.ppermute(
                    y, "pp", [(i, (i + 1) % 4) for i in range(4)]), None

            out, _ = jax.lax.scan(tick, x, None, length=8)
            return out

        tf = jax.shard_map(tbody, mesh=tmesh, in_specs=_P("pp"),
                           out_specs=_P("pp"))
        ttxt = jax.jit(tf).lower(jax.ShapeDtypeStruct((4 * B * L, D),
                                                      jnp.bfloat16)) \
            .compile().as_text()
        ts, td = (ttxt.count("collective-permute-start"),
                  ttxt.count("collective-permute-done"))
        print(f"TPU (v5e:2x2 AOT) HLO: {ts} collective-permute-start / "
              f"{td} -done pairs — the TPU scheduler issues the hop "
              f"asynchronously and overlaps it with the next tick's compute")
    except Exception as e:  # AOT topology unavailable in some environments
        print(f"TPU AOT overlap check unavailable: {type(e).__name__}")


def vpp_comparison(mesh, per_stage, stage):
    """Interleaved/VPP schedule, measured in the same SPMD-scan form.

    VPP splits each stage into V chunks to shrink the 1F1B bubble from
    (S-1)/(M+S-1) toward (S-1)/(V*M+S-1) — but that win exists only when
    the bubble is IDLE time a runtime can fill. In the compiled SPMD scan
    there is no idle: every device computes every tick, and splitting
    stages into V chunks deepens the pipeline to S*V positions, growing
    the wasted fill/drain ticks to (S*V - 1) chunk-ticks. Predicted cost
    ratio vs GPipe-scan: (M + S*V - 1) / (V * (M + S - 1) / V) ... i.e.
    (M/V + S - 1/V) / (M + S - 1) per unit work — WORSE for V > 1 at the
    same M. This measures that prediction.
    """
    import jax

    V = 2
    # uniform comparison model: S*V square matmul chunks; GPipe groups V
    # consecutive chunks per stage body, VPP pipelines them individually
    rng = np.random.default_rng(1)
    chunks = [{"w": jnp.asarray(rng.normal(0, 0.05, (D, D)).astype(np.float32))}
              for _ in range(S * V)]

    def chunk_body(p, x):
        return jnp.tanh(x @ p["w"])

    # GPipe view of the same model: stage s = chunks [s*V, (s+1)*V)
    per_stage = [{f"w{v}": chunks[s * V + v]["w"] for v in range(V)}
                 for s in range(S)]

    def stage(p, x):
        for v in range(V):
            x = jnp.tanh(x @ p[f"w{v}"])
        return x

    SV = S * V
    stacked_chunks = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, 0), *chunks)
    from jax.sharding import NamedSharding, PartitionSpec as P
    stacked_chunks = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("pp", *([None] * (a.ndim - 1))))),
        stacked_chunks)

    micro = jnp.asarray(np.random.default_rng(0).normal(
        0, 1, (M, B, L, D)).astype(np.float32))

    def local_fn(chunks_local, mi):
        # chunks_local leaves: (V, ...) — this device's V chunk slices
        dev = jax.lax.axis_index("pp")
        T = M + SV - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def vary(x):
            try:
                return jax.lax.pcast(x, "pp", to="varying")
            except ValueError:
                return x

        # act[v]: activation entering this device's v-th chunk
        acts = [vary(jnp.zeros_like(mi[0])) for _ in range(V)]
        out0 = vary(jnp.zeros((M,) + mi.shape[1:], mi.dtype))

        def tick(carry, t):
            acts, out_buf = carry
            new_acts = []
            for v in range(V):
                x_in = acts[v]
                if v == 0:
                    mb = jnp.clip(t, 0, M - 1)
                    x_in = jnp.where(dev == 0, mi[mb], x_in)
                y = chunk_body(
                    jax.tree_util.tree_map(lambda a: a[v], chunks_local),
                    x_in)
                new_acts.append(y)
            # last chunk of last device records output
            rec = t - (SV - 1)
            valid = jnp.logical_and(dev == S - 1,
                                    jnp.logical_and(rec >= 0, rec < M))
            out_buf = jax.lax.cond(
                valid,
                lambda ob: jax.lax.dynamic_update_index_in_dim(
                    ob, new_acts[-1], jnp.clip(rec, 0, M - 1), 0),
                lambda ob: ob, out_buf)
            # route: chunk v feeds chunk v+1 locally; last chunk hops devices
            hopped = jax.lax.ppermute(new_acts[-1], "pp", perm)
            carried = [hopped] + new_acts[:-1]
            return (carried, out_buf), None

        (acts, out_buf), _ = jax.lax.scan(tick, (acts, out0),
                                          jnp.arange(M + SV - 1))
        out_buf = jnp.where(dev == S - 1, out_buf, jnp.zeros_like(out_buf))
        return jax.lax.psum(out_buf, "pp")

    n_dims = jax.tree_util.tree_map(
        lambda a: P("pp", *([None] * (a.ndim - 1))), stacked_chunks)
    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(n_dims, P()), out_specs=P())

    def vpp_loss(params, mi):
        return jnp.sum(mapped(params, mi) ** 2)

    g = jax.jit(jax.grad(vpp_loss))
    jax.block_until_ready(g(stacked_chunks, micro))
    t0 = time.perf_counter()
    for _ in range(8):
        jax.block_until_ready(g(stacked_chunks, micro))
    dt_vpp = (time.perf_counter() - t0) / 8

    def gpipe_loss(params, mi):
        return jnp.sum(pipelined_forward(stage, params, mi, mesh, "pp") ** 2)

    stacked = stack_stage_params(per_stage, mesh, "pp")
    g2 = jax.jit(jax.grad(gpipe_loss))
    jax.block_until_ready(g2(stacked, micro))
    t0 = time.perf_counter()
    for _ in range(8):
        jax.block_until_ready(g2(stacked, micro))
    dt_gp = (time.perf_counter() - t0) / 8

    # each tick costs one stage-equivalent in both schedules (V chunks of
    # 1/V work vs one full stage body); only the tick counts differ
    pred = (M + SV - 1) / (M + S - 1)
    print(f"\n-- VPP (V={V}) in the SPMD scan: measured {dt_vpp * 1e3:.1f}ms "
          f"vs GPipe-scan {dt_gp * 1e3:.1f}ms "
          f"(ratio {dt_vpp / dt_gp:.2f}, fill/drain model {pred:.2f}) --")
    print("VPP deepens the compiled pipeline without any idle time to "
          "recover; GPipe-scan's waste already equals 1F1B's bubble "
          "fraction (S-1)/(M+S-1) — raise accumulate_steps to shrink it.")


if __name__ == "__main__":
    main()
