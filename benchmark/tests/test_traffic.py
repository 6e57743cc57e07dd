"""The traffic generator is a pure function of its arguments: the same seed
gives the same batches, another seed others, and the features are valid."""
import numpy as np

from benchmark.harness.registry import Registry

REG = Registry()
KIND = REG.module("traffic_kinds", "bert_pretrain.py")
CFG = {"vocab_size": 1000}


def _mix(**kw):
    mix = REG.mix("pretrain_seq128")
    mix.update(seq_len=32, max_predictions_per_seq=5, distinct_batches=3)
    mix.update(kw)
    return mix


def test_same_seed_same_batches_other_seed_other_batches():
    a = KIND.generate(_mix(), CFG, 7, 4)
    b = KIND.generate(_mix(), CFG, 7, 4)
    c = KIND.generate(_mix(), CFG, 8, 4)
    assert len(a) == 3
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert any((x["input_ids"] != y["input_ids"]).any() for x, y in zip(a, c))
    # the check's stream is not the training stream
    d = KIND.generate(_mix(), CFG, 7, 4, n_batches=1, stream=1)
    assert (d[0]["input_ids"] != a[0]["input_ids"]).any()


def test_full_length_features():
    (b,) = KIND.generate(_mix(), CFG, 1, 6, n_batches=1)
    assert b["input_ids"].shape == (6, 32) and b["input_ids"].dtype == np.int32
    assert b["input_mask"].min() == 1.0
    assert b["masked_lm_weights"].sum() == 6 * 5
    pos = b["masked_lm_positions"]
    assert (np.diff(pos, axis=1) > 0).all() and pos.max() < 32
    assert set(np.unique(b["segment_ids"])) <= {0, 1}
    assert b["next_sentence_labels"].shape == (6, 1)
    assert 0 <= b["input_ids"].min() and b["input_ids"].max() < 1000


def test_variable_length_is_data_only():
    mix = _mix(length={"dist": "uniform", "min": 8})
    (b,) = KIND.generate(mix, CFG, 1, 64, n_batches=1)
    lens = b["input_mask"].sum(1).astype(int)
    assert lens.min() >= 8 and lens.max() <= 32 and len(set(lens)) > 1
    for i in range(64):
        n = int(b["masked_lm_weights"][i].sum())
        assert 1 <= n <= 5
        assert (b["masked_lm_positions"][i, :n] < lens[i]).all()
        assert (b["input_ids"][i, lens[i]:] == 0).all()
        assert (b["masked_lm_ids"][i, n:] == 0).all()
