"""The one data generator: a configuration's ``data`` block -> (X, levels, y).

Every feature is an integer LEVEL in ``0 .. levels-1`` held as float64 (what
``lightgbm_tpu.Dataset`` turns any dense input into, so ``np.asarray`` of the
result is no copy). With ``levels <= max_bin`` (and every level common enough
to be in the program's bin sample) each level is a bin of its own whatever
rows the program samples for its bin search, so the plain reference can bin
by value and never needs the program's tables. Columns listed as
``uniform`` draw levels evenly (what quantile bins of a continuous feature
look like after binning); ``skewed`` columns draw ``min(levels-1,
floor(Exp(1) * skew_scale))``, a count-like heavy tail.

The label follows ``bench.py make_higgs_like``'s rule (copied; the original is
listed in PERF.md for deletion) on the standardised first seven columns.

Rows are made in blocks, each from ``default_rng([seed, block])``, so the same
seed gives the same matrix whatever the number of threads.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 20


def _block(seed: int, b: int, lo: int, hi: int, spec: dict, X, L, y) -> None:
    n, f = hi - lo, spec["features"]
    levels, n_uniform = spec["levels"], spec["uniform_columns"]
    r = np.random.default_rng([seed, b])
    lv = np.empty((n, f), np.uint8)
    lv[:, :n_uniform] = r.integers(0, levels, (n, n_uniform), dtype=np.uint8)
    if f > n_uniform:
        e = r.standard_exponential((n, f - n_uniform), dtype=np.float32)
        np.multiply(e, np.float32(spec["skew_scale"]), out=e)
        np.minimum(e, np.float32(levels - 1), out=e)
        lv[:, n_uniform:] = e.astype(np.uint8)
    L[lo:hi] = lv
    X[lo:hi] = lv                      # uint8 -> float64, in place
    z = (lv[:, :7].astype(np.float32) - np.float32((levels - 1) / 2.0)) \
        / np.float32(levels / 3.4641016)            # sd of a uniform level
    logit = (z[:, 0] * z[:, 1] + 0.5 * z[:, 2] - 0.3 * z[:, 3] * z[:, 4]
             + 0.2 * np.abs(z[:, 5]) + 0.1 * z[:, 6])
    noise = r.standard_normal(n, dtype=np.float32)
    y[lo:hi] = (logit + np.float32(spec["label_noise"]) * noise > 0)


def make(spec: dict, seed: int, threads: int = 8):
    """-> (X float64 [n, f], levels uint8 [n, f], y float32 [n])."""
    n, f = int(spec["rows"]), int(spec["features"])
    if not 2 <= spec["levels"] <= 256 or spec["uniform_columns"] < 7:
        raise ValueError("data block: levels in 2..256, >= 7 uniform columns")
    X = np.empty((n, f), np.float64)
    L = np.empty((n, f), np.uint8)
    y = np.empty(n, np.float32)
    cuts = list(range(0, n, BLOCK_ROWS)) + [n]
    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(lambda b: _block(seed, b, cuts[b], cuts[b + 1], spec,
                                       X, L, y), range(len(cuts) - 1)))
    return X, L, y
