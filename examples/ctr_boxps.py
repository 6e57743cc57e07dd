"""Wide&Deep CTR over the BoxPS tier: a 2^40 feasign space whose table
lives in host RAM; only each pass's working set occupies device memory,
and consecutive passes are double-buffered (the next pass's host staging
overlaps this pass's training).

Run: python examples/ctr_boxps.py           (~40s on CPU)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed.ps.box import get_box_wrapper, \
    reset_box_wrappers


def write_pass_files(tmp, rng, n_files=2, lines=64, slots=4):
    paths = []
    for i in range(n_files):
        rows = []
        for _ in range(lines):
            feas = rng.randint(0, 2 ** 40, slots, dtype=np.int64)
            feat = rng.randn(4)
            label = float(feat.sum() > 0)
            # MultiSlot line: <n> v...  per use_var — ids is ONE slot of
            # `slots` feasigns, then 4 dense floats, then the label
            rows.append(" ".join(
                ["%d" % slots] + ["%d" % f for f in feas]
                + ["4"] + ["%f" % v for v in feat] + ["1 %f" % label]))
        p = os.path.join(tmp, f"part{i}.txt")
        with open(p, "w") as f:
            f.write("\n".join(rows) + "\n")
        paths.append(p)
    return paths


def main():
    reset_box_wrappers()
    slots, dim = 4, 8
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        ids = fluid.data("ids", [-1, slots], dtype="int64")
        dense = fluid.data("dense", [-1, 4])
        label = fluid.data("label", [-1, 1])
        get_box_wrapper("ctr_box", dim=dim, init_kind="gaussian",
                        init_scale=0.01)
        emb = fluid.layers.pull_box_sparse(ids, dim, table_name="ctr_box")
        deep = fluid.layers.concat(
            [fluid.layers.reshape(emb, [-1, slots * dim]), dense], axis=1)
        h = fluid.layers.fc(deep, 32, act="relu")
        logit = fluid.layers.fc(h, 1) + fluid.layers.fc(dense, 1)
        loss = fluid.layers.mean(
            fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))
        fluid.optimizer.SGDOptimizer(0.05).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    # each "day" of data is one BoxPS pass; train_passes double-buffers
    import tempfile
    rng = np.random.RandomState(0)
    datasets = []
    with tempfile.TemporaryDirectory() as tmp:
        for day in range(3):
            d = os.path.join(tmp, f"day{day}")
            os.makedirs(d)
            ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
            ds.set_batch_size(16)
            ds.set_use_var([ids, dense, label])
            ds.set_filelist(write_pass_files(d, rng))
            ds.load_into_memory()
            datasets.append(ds)
        results = exe.train_passes(main_p, datasets, fetch_list=[loss],
                                   print_period=1000)
    box = get_box_wrapper("ctr_box")
    for day, res in enumerate(results):
        lv = float(np.asarray(res[0][0]).ravel()[0])
        print(f"pass {day}: loss={lv:.4f}")
    print(f"host table rows: {box.host_rows()} (id space 2^40; device "
          f"cache held only each pass's working set)")


if __name__ == "__main__":
    main()
