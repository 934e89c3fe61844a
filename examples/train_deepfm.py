"""BASELINE config #5: DeepFM on the sharded-embedding (PS -> ICI) path."""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle

from paddle_tpu.models.deepfm import DeepFM, DeepFMConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args()

    paddle.seed(0)
    cfg = DeepFMConfig(sparse_feature_number=10000, sparse_feature_dim=8,
                        num_sparse_fields=26, dense_feature_dim=13,
                        fc_sizes=(128, 64))
    model = DeepFM(cfg)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    bce = paddle.nn.BCEWithLogitsLoss()
    rng = np.random.default_rng(0)

    @paddle.jit.to_static
    def step(sparse, dense, label):
        logit = model(sparse, dense)
        loss = bce(logit.reshape([-1]), label)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for i in range(args.steps):
        sparse_np = rng.integers(0, 10000, (args.batch, 26), dtype=np.int64)
        dense_np = rng.normal(0, 1, (args.batch, 13)).astype(np.float32)
        # synthetic click rule so AUC is learnable
        label_np = ((sparse_np[:, 0] % 7 < 3) ^
                    (dense_np[:, 0] > 0)).astype(np.float32)
        loss = step(paddle.to_tensor(sparse_np), paddle.to_tensor(dense_np),
                    paddle.to_tensor(label_np))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
