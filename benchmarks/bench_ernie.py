"""BASELINE config #2: ERNIE-3.0 fine-tune throughput on the real chip.

The reference published no number (BASELINE.md); this records ours:
sequence-classification fine-tune steps/sec and examples/sec for the
ernie3_medium trunk (6 layers, h=768) in bf16 AMP O2 under whole-step
to_static.

Run: python benchmarks/bench_ernie.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.ernie import ErnieConfig, ErnieForSequenceClassification

B, L, STEPS = 32, 128, 30


def main():
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    paddle.seed(0)
    cfg = ErnieConfig.ernie3_medium() if on_tpu else ErnieConfig.tiny()
    model = ErnieForSequenceClassification(cfg, num_classes=2)
    opt = paddle.optimizer.AdamW(learning_rate=5e-5, weight_decay=0.01,
                                 parameters=model.parameters(),
                                 use_multi_tensor=True)
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")

    @paddle.jit.to_static
    def step(ids, label):
        with paddle.amp.auto_cast(enable=on_tpu, level="O2",
                                  dtype="bfloat16"):
            loss, _ = model(ids, labels=label)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (B, L), dtype=np.int32))
    label = paddle.to_tensor(rng.integers(0, 2, (B,)).astype(np.int64))

    for _ in range(3):  # compile + cache warm
        loss = step(ids, label)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        loss = step(ids, label)
    final = float(loss)
    dt = (time.perf_counter() - t0) / STEPS
    print(f"device: {jax.devices()[0]}")
    print(f"ernie3_medium fine-tune: {1.0 / dt:.1f} steps/s, "
          f"{B / dt:,.0f} examples/s, {B * L / dt:,.0f} tokens/s "
          f"(batch {B}, seq {L}, final loss {final:.4f})")


if __name__ == "__main__":
    main()
