"""Batches of BERT pretraining features (``create_pretraining_data.py`` of
google-research/bert: ``input_ids``, ``input_mask``, ``segment_ids``,
``masked_lm_positions/ids/weights``, ``next_sentence_labels``), drawn from a
seed.  One general generator; a mix is a file of its parameters:

``seq_len``, ``samples_per_chip`` (sequences a step on each chip),
``max_predictions_per_seq``;
``distinct_batches`` (how many different batches the in-memory dataset
holds: the loop cycles them); ``length`` — ``{"dist": "full"}`` for
sequences that fill ``seq_len``, or ``{"dist": "uniform", "min": a}`` for real
lengths uniform in ``[a, seq_len]`` with the tail padded (``input_mask`` 0)
and predictions only inside the real length at ``masked_lm_prob``;
``layout`` — fields set on ``BuildStrategy`` for this job (the division of
the work over the chips is part of a training job's traffic).

Token ids are uniform over the vocabulary: the cost of a dense training step
does not depend on which ids it sees.
"""
from __future__ import annotations

import numpy as np


def generate(mix, cfg, seed, batch, n_batches=None, stream=0):
    """``n_batches`` (default: the mix's ``distinct_batches``) feed dicts of
    ``batch`` sequences each, a pure function of the arguments."""
    rng = np.random.default_rng([int(seed), int(stream)])
    seq = mix["seq_len"]
    n_pred = mix["max_predictions_per_seq"]
    length = mix.get("length", {"dist": "full"})
    out = []
    for _ in range(n_batches or mix["distinct_batches"]):
        if length["dist"] == "full":
            lens = np.full(batch, seq)
        elif length["dist"] == "uniform":
            lens = rng.integers(length["min"], seq + 1, batch)
        else:
            raise ValueError(f"unknown length distribution {length!r}")
        cols = np.arange(seq)[None, :]
        mask = (cols < lens[:, None])
        split = (lens * rng.uniform(0.25, 0.75, batch)).astype(np.int64)
        if length["dist"] == "full":     # the README's pairs: all predicted
            n_real = np.full(batch, n_pred)
        else:
            share = np.round(lens * mix.get("masked_lm_prob", 0.15))
            n_real = np.clip(share, 1, n_pred).astype(np.int64)
        # n_pred distinct positions inside each real length, sorted
        keys = rng.random((batch, seq))
        keys[~mask] = 2.0
        positions = np.sort(np.argsort(keys, axis=1)[:, :n_pred], axis=1)
        weights = (np.arange(n_pred)[None, :] < n_real[:, None])
        positions = np.where(weights, positions, 0)
        out.append({
            "input_ids": np.where(mask, rng.integers(
                0, cfg["vocab_size"], (batch, seq)), 0).astype(np.int32),
            "input_mask": mask.astype(np.float32),
            "segment_ids": ((cols >= split[:, None]) & mask).astype(np.int32),
            "masked_lm_positions": positions.astype(np.int32),
            "masked_lm_ids": np.where(weights, rng.integers(
                0, cfg["vocab_size"], (batch, n_pred)), 0).astype(np.int32),
            "masked_lm_weights": weights.astype(np.float32),
            "next_sentence_labels": rng.integers(
                0, 2, (batch, 1)).astype(np.int32),
        })
    return out
