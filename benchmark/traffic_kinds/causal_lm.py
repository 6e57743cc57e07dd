"""Batches of packed next-token training sequences (``input_ids`` and
``labels``, the inputs shifted by one), drawn from a seed.  One general
generator; a mix is a file of its parameters:

``seq_len``, ``samples_per_chip`` (sequences a step on each chip);
``distinct_batches`` (how many different batches the in-memory dataset holds:
the loop cycles them); ``length`` — ``{"dist": "full"}``: documents packed to
``seq_len``, no padding and no boundary mask; ``id_dist`` — ``{"dist":
"uniform"}``: ids uniform over the configuration's ``vocab_size`` (the held
slice of a sliced vocabulary); ``layout`` — fields set on ``BuildStrategy``
for this job.

A dense step's cost does not depend on the ids; a sparse-expert step's does,
through the routing: with seeded random weights an id's embedding decides its
first layer's experts, so a mix of skewed ids would skew the held experts'
load.  No mix has one yet: the distribution comes with the cell that uses it.
"""
from __future__ import annotations

import numpy as np


def generate(mix, cfg, seed, batch, n_batches=None, stream=0):
    """``n_batches`` (default: the mix's ``distinct_batches``) feed dicts of
    ``batch`` sequences each, a pure function of the arguments."""
    rng = np.random.default_rng([int(seed), int(stream)])
    seq, vocab = mix["seq_len"], cfg["vocab_size"]
    if mix.get("length", {"dist": "full"})["dist"] != "full":
        raise ValueError(f"unknown length distribution {mix['length']!r}")
    if mix.get("id_dist", {"dist": "uniform"})["dist"] != "uniform":
        raise ValueError(f"unknown id distribution {mix['id_dist']!r}")
    out = []
    for _ in range(n_batches or mix["distinct_batches"]):
        tokens = rng.integers(0, vocab, (batch, seq + 1))
        out.append({"input_ids": tokens[:, :-1].astype(np.int32),
                    "labels": tokens[:, 1:].astype(np.int32)})
    return out
