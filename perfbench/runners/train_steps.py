"""Runner: one captured training step after another.

The ``bench.py`` / ``chip_smoke.leg_train`` job: ``amp.decorate(level="O2")``,
the configuration's optimizer, the step wrapped by ``paddle.jit.
capture_step``, every step ended by a host read of the loss. With a ``mesh``
in the configuration's ``train`` block the model goes through ``fleet.init``
+ ``fleet.distributed_model`` first (``chip_smoke.leg_hybrid``).

Each step gets a fresh batch of token ids drawn from ``--seed`` on the host.
The window opens after two warm-up steps (the first compiles, or loads the
program from the cache) and closes at the end of the step during which
``--seconds`` ran out, so the rate is whole steps over the time they took.
"""

from __future__ import annotations

import json
import time
from typing import Dict

import numpy as np

from .. import harness, reference
from ..harness import log

WARM_STEPS = 2
SLICE_S = 3.0


def run(ctx: Dict) -> Dict:
    conf, traffic = ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    dev = harness.open_device(chips, ctx["on_chip"])

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.llama import LlamaForCausalLM

    compiles = harness.CompileCounter()
    obs.enable()
    paddle.set_flags({"FLAGS_to_static_capture_lowered": True})
    dep = conf["train"]
    mesh = dep.get("mesh")
    if mesh:
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": mesh["dp"],
                                   "mp_degree": mesh["mp"]}
        fleet.init(is_collective=True, strategy=strategy)
        log(f"mesh: {fleet.get_hybrid_communicate_group().mesh}")

    cfg = harness.llama_config(conf, scan_layers=dep["scan_layers"],
                               recompute=dep["recompute"])
    paddle.seed(harness.fold_seed(seed))
    model = LlamaForCausalLM(cfg)
    o = dep["optimizer"]
    opt = getattr(paddle.optimizer, o["name"])(
        learning_rate=o["learning_rate"], parameters=model.parameters(),
        **o["args"])
    model, opt = paddle.amp.decorate(model, opt, level=dep["amp_level"],
                                     dtype=dep["amp_dtype"],
                                     master_weight=False)
    net, put = model, paddle.to_tensor
    if mesh:
        net = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(opt)

        def put(a):
            return net.shard_input(paddle.to_tensor(a))
    log(f"built: {model.num_params():,} parameters; {harness.hbm_line()}")

    def body(ids):
        with paddle.amp.auto_cast(level=dep["amp_level"],
                                  dtype=dep["amp_dtype"]):
            loss, _ = net(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.capture_step(body)
    batch, seq = traffic["batch"], traffic["seq"]
    rng = np.random.default_rng([seed, 5])

    def draw():
        return rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)

    # -- correct: the first step's loss against the reference's ----------
    first = draw()
    ref_fn = jax.jit(lambda p, x: jnp.mean(jnp.stack(
        [reference.loss(p, x[i], conf) for i in range(batch)])))
    want = float(ref_fn(harness.reference_params(model), jnp.asarray(first)))
    got = float(np.asarray(step(put(first))._data))       # compiles
    rel = abs(got - want) / abs(want)
    check = {"loss": got, "reference_loss": want, "relative_difference": rel,
             "tolerance": reference.TRAIN_LOSS_TOL,
             "correct": bool(np.isfinite(got)
                             and rel <= reference.TRAIN_LOSS_TOL)}
    log("reference check:", json.dumps(check))
    for _ in range(WARM_STEPS - 1):
        float(np.asarray(step(put(draw()))._data))
    log(f"warm: {compiles.count} backend compiles; {harness.hbm_line()}")

    # -- the window ---------------------------------------------------------
    prof = harness.ProfilerSlice(ctx["workload"]) if ctx["trace"] else None
    slice_len = min(SLICE_S, seconds / 2)
    slice_steps, c0 = 0, compiles.count
    losses, step_ms = [], []
    w0 = time.monotonic()
    while True:
        t = time.monotonic()
        if t - w0 >= seconds:
            break
        if prof is not None:
            if prof.t0 is None and t - w0 >= (seconds - slice_len) / 2:
                prof.start()
                t = time.monotonic()
            elif prof.running and t - prof.t0 >= slice_len:
                prof.stop()
                t = time.monotonic()
        losses.append(float(np.asarray(step(put(draw()))._data)))
        step_ms.append((time.monotonic() - t) * 1e3)
        if prof is not None and prof.running:
            slice_steps += 1
    w1 = time.monotonic()
    if prof is not None and prof.running:
        prof.stop()
    tokens = len(losses) * batch * seq
    return {
        "window": [w0, w1], "requests": [], "spans": [],
        "counters": {"start": {}, "end": {}},
        "trace": prof.load() if prof is not None else None,
        "values": {"setup_s": w0 - ctx["t_start"],
                   "tokens_per_s": tokens / (w1 - w0),
                   "step_ms": step_ms, "slice_steps": slice_steps,
                   "compiles_in_window": compiles.count - c0,
                   "hbm_peak_bytes": harness.hbm_peak(chips)},
        "correct": check["correct"], "attempted": len(losses),
        "failed": int(sum(not np.isfinite(x) for x in losses)),
        "model": conf, "traffic": traffic, "peaks": dev["peaks"],
        "device": dev["device"],
    }
