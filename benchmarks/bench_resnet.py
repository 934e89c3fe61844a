"""ResNet-50 training/inference throughput (BASELINE config #1).

Usage: python benchmarks/bench_resnet.py [--batch 64] [--steps 10]
Prints one JSON line with images/sec (the PaddleClas-style metric).
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
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--eval", action="store_true", help="inference only")
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    on_tpu = jax.devices()[0].platform == "tpu"
    paddle.seed(0)
    model = paddle.vision.models.resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(
        args.batch, 3, args.image_size, args.image_size)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, args.batch).astype(np.int64))

    if args.eval:
        model.eval()

        @paddle.jit.to_static
        def step(x):
            with paddle.no_grad(), paddle.amp.auto_cast(enable=on_tpu,
                                                        level="O2"):
                return model(x)
    else:
        @paddle.jit.to_static
        def step(x, y=None):
            with paddle.amp.auto_cast(enable=on_tpu, level="O2"):
                loss = nn.functional.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    fargs = (x,) if args.eval else (x, y)
    for _ in range(2):  # compile + post-materialization warmup
        out = step(*fargs)
    np.asarray(out._data if hasattr(out, "_data") else out)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = step(*fargs)
    _ = np.asarray((out._data if hasattr(out, "_data") else out))
    dt = time.perf_counter() - t0

    print(json.dumps({
        "benchmark": "resnet50_" + ("infer" if args.eval else "train"),
        "images_per_sec": round(args.batch * args.steps / dt, 1),
        "batch": args.batch, "image_size": args.image_size,
        "device": str(jax.devices()[0]),
    }))


if __name__ == "__main__":
    main()
