"""``datagen.make`` with the matrix held as float32: a configuration's ``data``
block with ``"dtype": "float32"`` -> (X float32 [n, f], levels, y).

The blocks are ``datagen._block``'s own, each from ``default_rng([seed,
block])``, so for one seed and size ``levels`` and ``y`` are ``datagen.make``'s
to the bit, whatever the number of threads; only ``X`` differs, and every
level (0 .. 255) is a float32 exactly. A C-contiguous float32 matrix is what
``lightgbm_tpu.Dataset`` takes as it is (``lightgbm_tpu.basic.keeps_float32``):
half the bytes of the float64 cells' matrix on the host and on the wire."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import datagen


def make(spec: dict, seed: int, threads: int = 8):
    """-> (X float32 [n, f], levels uint8 [n, f], y float32 [n])."""
    if spec.get("dtype", "float32") != "float32":
        raise ValueError("datagen_f32 makes float32 matrices; the data block "
                         f"asks for {spec['dtype']!r}")
    n, f = int(spec["rows"]), int(spec["features"])
    if not 2 <= spec["levels"] <= 256 or spec["uniform_columns"] < 7:
        raise ValueError("data block: levels in 2..256, >= 7 uniform columns")
    X = np.empty((n, f), np.float32)
    L = np.empty((n, f), np.uint8)
    y = np.empty(n, np.float32)
    cuts = list(range(0, n, datagen.BLOCK_ROWS)) + [n]
    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(lambda b: datagen._block(seed, b, cuts[b], cuts[b + 1],
                                               spec, X, L, y),
                      range(len(cuts) - 1)))
    return X, L, y
