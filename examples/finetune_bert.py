"""BASELINE config #2: BERT/ERNIE sequence-classification fine-tune."""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle



def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--model", choices=["bert", "ernie"], default="ernie")
    args = ap.parse_args()

    paddle.seed(0)
    if args.model == "bert":
        from paddle_tpu.models.bert import (BertConfig,
                                            BertForSequenceClassification)
        cfg = BertConfig(vocab_size=1000, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256)
        model = BertForSequenceClassification(cfg, num_classes=2)
    else:
        from paddle_tpu.models.ernie import (ErnieConfig,
                                             ErnieForSequenceClassification)
        cfg = ErnieConfig(vocab_size=1000, hidden_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=256)
        model = ErnieForSequenceClassification(cfg, num_classes=2)

    sched = paddle.optimizer.lr.LinearWarmup(
        paddle.optimizer.lr.PolynomialDecay(5e-4, args.steps), 5, 0.0, 5e-4)
    opt = paddle.optimizer.AdamW(learning_rate=sched, weight_decay=0.01,
                                 parameters=model.parameters())
    rng = np.random.default_rng(0)

    @paddle.jit.to_static
    def step(ids, label):
        loss, _ = model(ids, labels=label)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for i in range(args.steps):
        ids_np = rng.integers(0, 1000, (8, 64), dtype=np.int32)
        # synthetic rule: class = parity of the first token
        ids = paddle.to_tensor(ids_np)
        label = paddle.to_tensor((ids_np[:, 0] % 2).astype(np.int64))
        loss = step(ids, label)
        sched.step()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
