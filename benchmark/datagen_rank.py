"""The generator of a ranking configuration: its ``data`` block ->
(X, levels, grades, lengths).

The columns are ``datagen.make``'s (integer LEVELS held as float64, each level
a bin of its own; see that file). What a ranking job adds:

* ``lengths`` int64 [nq]: documents per query, in row order (query q is the
  contiguous rows ``sum(lengths[:q]) .. sum(lengths[:q+1])``). The law is
  stated, not fitted to a file: ``clip(rint(LogNormal(mu, query_len_sigma)),
  query_len_min, query_len_max)`` with ``mu`` solved so that the clipped mean
  is ``query_len_mean``; lengths are drawn until they sum to ``rows`` and the
  last query is cut to fit, so the rows are WHOLE queries and one-document
  queries are present (they carry no pair and a zero gradient).
* ``grades`` float32 [n]: relevance 0 .. len(grade_shares)-1 by thresholds of
  a latent score, ``bench.py make_higgs_like``'s rule on the standardised
  first seven columns (as ``datagen.py`` has it) plus ``label_noise`` x
  N(0, 1); the thresholds are the quantiles of the generated latents that
  give each grade its stated share.

Everything is a function of ``--seed`` alone (the thread count changes
nothing: ``datagen.make`` draws by row block, the noise here by row block too).
"""
from __future__ import annotations

import numpy as np

import datagen


def solve_mu(mean: float, sigma: float, lo: int, hi: int) -> float:
    """``mu`` of the log-normal whose rounded, clipped draw has this mean;
    bisection on a fixed sample, so every seed shares one law."""
    z = np.random.default_rng(12345).standard_normal(1 << 18)
    a, b = 0.0, float(np.log(hi))
    for _ in range(60):
        mu = 0.5 * (a + b)
        m = np.clip(np.rint(np.exp(mu + sigma * z)), lo, hi).mean()
        a, b = (mu, b) if m < mean else (a, mu)
    return mu


def query_lengths(spec: dict, seed: int) -> np.ndarray:
    rows = int(spec["rows"])
    lo, hi = int(spec["query_len_min"]), int(spec["query_len_max"])
    mean, sigma = float(spec["query_len_mean"]), float(spec["query_len_sigma"])
    if not 1 <= lo <= mean <= hi:
        raise ValueError("data block: query_len_min <= mean <= query_len_max")
    mu = solve_mu(mean, sigma, lo, hi)
    r = np.random.default_rng([seed, 991])
    c = np.clip(np.rint(np.exp(mu + sigma * r.standard_normal(
        int(rows / mean * 1.5) + 1024))), lo, hi).astype(np.int64)
    ends = np.cumsum(c)
    k = int(np.searchsorted(ends, rows))          # the query that reaches `rows`
    c = c[:k + 1].copy()
    c[k] = rows - (ends[k - 1] if k else 0)
    return c


def grades_of(levels: np.ndarray, spec: dict, seed: int) -> np.ndarray:
    n = levels.shape[0]
    nl = int(spec["levels"])
    latent = np.empty(n, np.float32)
    for b, lo in enumerate(range(0, n, datagen.BLOCK_ROWS)):
        hi = min(n, lo + datagen.BLOCK_ROWS)
        z = (levels[lo:hi, :7].astype(np.float32) - np.float32((nl - 1) / 2.0)) \
            / np.float32(nl / 3.4641016)
        logit = (z[:, 0] * z[:, 1] + 0.5 * z[:, 2] - 0.3 * z[:, 3] * z[:, 4]
                 + 0.2 * np.abs(z[:, 5]) + 0.1 * z[:, 6])
        noise = np.random.default_rng([seed, 992, b]).standard_normal(
            hi - lo, dtype=np.float32)
        latent[lo:hi] = logit + np.float32(spec["label_noise"]) * noise
    shares = np.asarray(spec["grade_shares"], np.float64)
    if abs(shares.sum() - 1.0) > 1e-9 or len(shares) != int(spec["grades"]):
        raise ValueError("data block: grade_shares sum to 1, one per grade")
    cuts = np.quantile(latent, np.cumsum(shares)[:-1])
    return np.searchsorted(cuts, latent, side="right").astype(np.float32)


def make(spec: dict, seed: int, threads: int = 8):
    """-> (X float64 [n, f], levels uint8 [n, f], grades float32 [n],
    lengths int64 [nq])."""
    X, levels, _binary = datagen.make(spec, seed, threads)
    return X, levels, grades_of(levels, spec, seed), query_lengths(spec, seed)
