"""The one traffic generator. A traffic mix is a data file under
``traffic/`` (batch, sequence length, K-FAC schedule, pool size); this reads
it and yields host batches made from the seed.

Copied from the program's own synthetic feed
(``kfac_pytorch_tpu/training/data.py::synthetic_batches``): a small pool of
pre-generated batches, cycled, so that the host's random number generator is
out of the measured loop. Every seed gives the same sizes; only the values
differ. The pool's batches all differ from each other, so the first steps
(the ones the output check follows) see rows that all differ.
"""

from __future__ import annotations

import numpy as np


def make_pool(traffic, cfg, chips, seed):
    """``pool`` host batches ``(inputs, labels)`` for the global batch."""
    rng = np.random.default_rng(seed)
    n = traffic["per_chip_batch"] * chips
    pool = []
    for _ in range(traffic["pool"]):
        if traffic["kind"] == "images":
            im = cfg["image_size"]
            x = rng.standard_normal((n, im, im, cfg["image_channels"]), dtype=np.float32)
            y = rng.integers(0, cfg["num_classes"], size=n, dtype=np.int32)
        elif traffic["kind"] == "tokens":
            # one stream of seq_len + 1 ids a row: inputs and next-token targets
            ids = rng.integers(
                0, cfg["vocab_size"], size=(n, traffic["seq_len"] + 1), dtype=np.int32
            )
            x = np.ascontiguousarray(ids[:, :-1])
            y = np.ascontiguousarray(ids[:, 1:])
        else:
            raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
        pool.append((x, y))
    return pool


def feed(pool):
    """``step -> host batch``: the pool, cycled from step 0."""
    return lambda step: pool[step % len(pool)]
