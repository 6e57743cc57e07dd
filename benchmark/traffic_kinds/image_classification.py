"""Batches of labelled images, drawn from a seed.  One general generator; a
mix is a file of its parameters: ``image_size``, ``samples_per_chip``,
``distinct_batches`` (how many different batches the in-memory dataset holds:
the loop cycles them) and ``layout`` (fields set on ``BuildStrategy`` for
this job).  Channels and classes are the configuration's.

Images are float32 NHWC, standard normal per value (a normalised image's
scale); labels uniform over the classes.  The cost of a dense training step
does not depend on what the pixels show.
"""
from __future__ import annotations

import numpy as np


def generate(mix, cfg, seed, batch, n_batches=None, stream=0):
    """``n_batches`` (default: the mix's ``distinct_batches``) feed dicts of
    ``batch`` images each, a pure function of the arguments."""
    rng = np.random.default_rng([int(seed), int(stream)])
    size = mix["image_size"]
    out = []
    for _ in range(n_batches or mix["distinct_batches"]):
        out.append({
            "image": rng.standard_normal(
                (batch, size, size, cfg["image_channels"]), dtype=np.float32),
            "label": rng.integers(0, cfg["num_classes"],
                                  (batch, 1)).astype(np.int32),
        })
    return out
