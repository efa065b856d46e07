"""MNIST LeNet end-to-end training via the hapi high-level API.

Exercises the full stack: vision dataset -> DataLoader -> nn.Layer ->
`paddle.Model.fit` (compiled train step through jit.to_static) with a
streaming `paddle.metric.Accuracy` and callback-reported progress —
the reference's `hapi/model.py:1750` usage shape.

Run:  python examples/mnist_lenet.py [--epochs 5] [--eager]
CPU:  JAX_PLATFORMS=cpu python examples/mnist_lenet.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.optimizer as optim  # noqa: E402
from paddle_tpu.hapi import Model  # noqa: E402
from paddle_tpu.io import DataLoader  # noqa: E402
from paddle_tpu.metric import Accuracy  # noqa: E402
from paddle_tpu.vision import transforms as T  # noqa: E402
from paddle_tpu.vision.datasets import MNIST  # noqa: E402
from paddle_tpu.vision.models import LeNet  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--eager", action="store_true",
                    help="skip jit compilation (debug mode)")
    ap.add_argument("--n-per-class", type=int, default=600)
    ap.add_argument("--save-dir", default=None)
    args = ap.parse_args()

    paddle.seed(0)
    tf = T.Compose([T.ToTensor(), T.Normalize(0.5, 0.5)])
    train_ds = MNIST(mode="train", transform=tf,
                     n_per_class=args.n_per_class)
    test_ds = MNIST(mode="test", transform=tf,
                    n_per_class=max(args.n_per_class // 6, 50))
    train_dl = DataLoader(train_ds, batch_size=args.batch_size,
                          shuffle=True, drop_last=True, num_workers=2)
    test_dl = DataLoader(test_ds, batch_size=256)
    print(f"train={len(train_ds)} test={len(test_ds)} "
          f"synthetic={train_ds.synthetic}")

    net = LeNet(num_classes=10)
    sched = optim.lr.CosineAnnealingDecay(args.lr, T_max=args.epochs)
    model = Model(net)
    model.prepare(
        optimizer=optim.AdamW(learning_rate=sched,
                              parameters=net.parameters(),
                              weight_decay=1e-4),
        loss=nn.CrossEntropyLoss(),
        metrics=[Accuracy()],
        jit=not args.eager)
    model.summary()

    model.fit(train_dl, eval_data=test_dl, epochs=args.epochs,
              log_freq=20, verbose=2, save_dir=args.save_dir)

    final = model.evaluate(test_dl, verbose=0)["acc"]
    print(f"FINAL test accuracy: {final * 100:.2f}%")
    assert final > 0.97, f"convergence gate failed: {final}"
    print("MNIST milestone PASSED (>97%)")


if __name__ == "__main__":
    main()
