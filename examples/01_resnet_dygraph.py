"""BASELINE config 1 — ResNet image classification, dygraph.

Full shape of the reference recipe (vision zoo + DataLoader workers +
AMP O1 + Momentum with LR schedule) at toy scale; on hardware switch to
resnet50, ImageNet via paddle.vision.datasets.ImageFolder, batch 256.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from a source checkout

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset


class FakeImages(Dataset):
    def __len__(self):
        return 64

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return (rs.randn(3, 32, 32).astype("float32"),
                np.int64(i % 10))


def main():
    paddle.seed(0)
    model = paddle.vision.models.resnet18(num_classes=10)
    sched = paddle.optimizer.lr.CosineAnnealingDecay(
        learning_rate=0.01, T_max=10)
    opt = paddle.optimizer.Momentum(learning_rate=sched, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    loss_fn = paddle.nn.CrossEntropyLoss()
    loader = DataLoader(FakeImages(), batch_size=16, shuffle=True,
                        num_workers=2)
    for epoch in range(2):
        for x, y in loader:
            with paddle.amp.auto_cast(level="O1"):
                loss = loss_fn(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        sched.step()
        print(f"epoch {epoch}: loss {float(loss):.4f} "
              f"lr {sched.get_lr():.4f}")


if __name__ == "__main__":
    main()
