"""Llama decoder train throughput across sizes/sequence lengths.

Usage: python benchmarks/bench_llama.py [--hidden 1024] [--layers 8]
       [--batch 16] [--seq 1024] [--scan-k 4] [--steps 20]
Same metric as the repo-root bench.py (the benchmark of record), but
parameterized for sweeps.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import json
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--inter", type=int, default=2816)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--scan-k", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="GQA kv heads (0 = same as --heads)")
    ap.add_argument("--state", choices=["fp32", "bf16", "int8"],
                    default="fp32",
                    help="optimizer state: fp32 masters+moments (reference "
                         "behavior), bf16 moments + master-weight-free "
                         "bf16 params with stochastic rounding, or int8 "
                         "block-quantized moments (2 B/param of m+v)")
    ap.add_argument("--q8-chunk", type=int, default=0,
                    help="int8-state chunk size in elements (0 = default); "
                         "bigger = fewer serial optimizer chunks, more "
                         "transient HBM")
    ap.add_argument("--q8-unroll", type=int, default=0,
                    help="chunks per int8-update loop iteration "
                         "(0 = default)")
    ap.add_argument("--q8-window", type=int, default=0,
                    help="params in flight in the int8 update "
                         "(0 = default)")
    ap.add_argument("--scan-layers", action="store_true",
                    help="stack identical decoder layers under lax.scan")
    ap.add_argument("--recompute", action="store_true",
                    help="activation checkpointing on the layer body")
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    from paddle_tpu.observability import cost

    device = paddle.device.describe()
    on_tpu = device["platform"] == "tpu"
    # the one peaks table (an unknown chip raises); a CPU run has no peak,
    # carries no MFU and says cpu in its benchmark name
    peak = cost.device_peaks(device["kind"])["peak_flops"] if on_tpu else None
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=args.hidden,
                      intermediate_size=args.inter,
                      num_hidden_layers=args.layers,
                      num_attention_heads=args.heads,
                      num_key_value_heads=args.kv_heads or args.heads,
                      max_position_embeddings=max(2048, args.seq),
                      scan_layers=args.scan_layers,
                      recompute=args.recompute)
    model = LlamaForCausalLM(cfg)
    bf16_state = args.state in ("bf16", "int8")
    # narrow state: bf16 (6 B/param) or int8 block-quantized (4 B/param)
    # moments + no fp32 masters (params update in bf16 with stochastic
    # rounding) vs the reference's 16 B/param. The big scan-stacked params
    # make the per-param (unfused) path the fast one here.
    moment = {"fp32": "float32", "bf16": "bfloat16",
              "int8": "int8"}[args.state]
    if args.q8_chunk:
        paddle.optimizer.Adam._Q8_CHUNK_ELEMS = args.q8_chunk
    if args.q8_unroll:
        paddle.optimizer.Adam._Q8_UNROLL = args.q8_unroll
    if args.q8_window:
        paddle.optimizer.Adam._Q8_PARAM_WINDOW = args.q8_window
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        use_multi_tensor=not args.scan_layers and args.state != "int8",
        moment_dtype=moment,
        use_master_weights=False if bf16_state else None)
    if on_tpu:
        model, opt = paddle.amp.decorate(
            model, opt, level="O2", dtype="bfloat16",
            master_weight=False if bf16_state else None)

    @paddle.jit.to_static(iters_per_call=args.scan_k)
    def train_step(ids):
        with paddle.amp.auto_cast(enable=on_tpu, level="O2",
                                  dtype="bfloat16"):
            loss, _ = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.scan_k, args.batch, args.seq),
        dtype=np.int32))
    for _ in range(2):
        loss = train_step(ids)
    np.asarray(loss._data)
    steps_run = (args.steps // args.scan_k) * args.scan_k
    t0 = time.perf_counter()
    for _ in range(steps_run // args.scan_k):
        loss = train_step(ids)
    np.asarray(loss._data)
    dt = time.perf_counter() - t0
    tok = args.batch * args.seq * steps_run / dt
    mfu = round(tok * model.flops_per_token(args.seq) / peak, 4) \
        if peak else None
    print(json.dumps({
        "benchmark": "llama_train" if on_tpu else "llama_train_cpu_smoke",
        "tokens_per_sec": round(tok, 1),
        "mfu": mfu, "params": model.num_params(),
        "hidden": args.hidden, "layers": args.layers, "batch": args.batch,
        "seq": args.seq, "scan_k": args.scan_k, "state": args.state,
        "scan_layers": args.scan_layers, "recompute": args.recompute,
        "final_loss": round(float(np.asarray(loss._data).reshape(-1)[-1]), 4),
        "device": device,
    }))


if __name__ == "__main__":
    main()
