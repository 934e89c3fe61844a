"""BASELINE config #3: PP-YOLOE detection training step + decoded eval."""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle

from paddle_tpu.models.ppyoloe import PPYOLOE, PPYOLOEConfig


def synth_batch(rng, b=2, size=320, m=3, c=20):
    imgs = rng.normal(size=(b, size, size, 3)).astype(np.float32)  # NHWC
    centers = rng.uniform(20, size - 20, (b, m, 2))
    wh = rng.uniform(16, 80, (b, m, 2))
    boxes = np.concatenate([centers - wh / 2, centers + wh / 2],
                           -1).astype(np.float32)
    labels = rng.integers(0, c, (b, m)).astype(np.int32)
    mask = np.ones((b, m), np.float32)
    return imgs, labels, boxes, mask


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=320)
    args = ap.parse_args()

    paddle.seed(0)
    model = PPYOLOE(PPYOLOEConfig.tiny(num_classes=20))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    rng = np.random.default_rng(0)
    imgs, labels, boxes, mask = synth_batch(rng, size=args.image_size)
    t = tuple(paddle.to_tensor(v) for v in (imgs, labels, boxes, mask))

    @paddle.jit.to_static
    def step(img, lab, box, msk):
        out = model.loss(img, lab, box, msk)
        out["loss"].backward()
        opt.step()
        opt.clear_grad()
        return out["loss"]

    for i in range(args.steps):
        loss = step(*t)
        print(f"step {i}: loss {float(loss):.4f}")

    model.eval()
    dets = model.predict(t[0])
    print("predict output:", [getattr(d, "shape", None) for d in dets]
          if isinstance(dets, (tuple, list)) else dets.shape)


if __name__ == "__main__":
    main()
