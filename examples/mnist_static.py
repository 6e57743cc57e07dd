"""Static-graph training, fluid style (the reference's book/01 MNIST
chapter shape): Program/Executor, feed/fetch, save_inference_model.

Run: python examples/mnist_static.py        (~30s on CPU)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid
from paddle_tpu.vision.datasets import MNIST


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", [-1, 1, 28, 28])
        label = fluid.data("label", [-1, 1], dtype="int64")
        x = fluid.layers.reshape(img, [-1, 784])
        h = fluid.layers.fc(x, 128, act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        acc = fluid.layers.accuracy(fluid.layers.softmax(logits), label)
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    return main, startup, loss, acc, logits, img


def main():
    train = MNIST(mode="train")
    xs = np.stack([train[i][0] for i in range(512)])
    ys = np.stack([train[i][1] for i in range(512)]).reshape(-1, 1)

    prog, startup, loss, acc, logits, img = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    for epoch in range(3):
        perm = np.random.RandomState(epoch).permutation(len(xs))
        for i in range(0, len(xs), 64):
            b = perm[i:i + 64]
            lv, av = exe.run(prog, feed={"img": xs[b], "label": ys[b]},
                             fetch_list=[loss, acc])
        print(f"epoch {epoch}: loss={float(np.asarray(lv).ravel()[0]):.4f} "
              f"acc={float(np.asarray(av).ravel()[0]):.3f}")

    out_dir = "/tmp/mnist_infer_model"
    fluid.io.save_inference_model(out_dir, ["img"], [logits], exe,
                                  main_program=prog)
    print(f"inference model saved to {out_dir}")


if __name__ == "__main__":
    main()
