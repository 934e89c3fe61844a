"""BASELINE config #1: ResNet-50 classification (PaddleClas surface)."""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle

import paddle_tpu.nn as nn
import paddle_tpu.vision as vision


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--arch", default="resnet50")
    args = ap.parse_args()

    paddle.seed(0)
    model = getattr(vision.models, args.arch)(num_classes=100)
    opt = paddle.optimizer.Momentum(
        learning_rate=paddle.optimizer.lr.CosineAnnealingDecay(0.1,
                                                               args.steps),
        momentum=0.9, parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    ds = vision.datasets.Cifar100(
        mode="train", transform=vision.transforms.Compose([
            vision.transforms.Resize(args.image_size),
            vision.transforms.Normalize(mean=[0.5] * 3, std=[0.5] * 3)]))
    loader = paddle.io.DataLoader(ds, batch_size=args.batch, shuffle=True)

    @paddle.jit.to_static
    def step(img, label):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = loss_fn(model(img), label)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    it = iter(loader)
    for i in range(args.steps):
        loss = step(*next(it))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f} lr {opt.get_lr():.4f}")
        opt._learning_rate.step()
    paddle.save(model.state_dict(), "/tmp/resnet_example.pdparams")
    print("saved /tmp/resnet_example.pdparams")


if __name__ == "__main__":
    main()
