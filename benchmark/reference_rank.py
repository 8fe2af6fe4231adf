"""The plain reference of one boosting iteration of a LAMBDARANK booster, and
the comparison that decides ``correct`` in a ranking cell (kind ``rank_loop``).

What is objective-free comes from ``reference.py`` unchanged (a tree's routing
tables, leaf values, gains, the best cut of a histogram, ``decide``); what a
ranking job adds is here:

* ``rank_grads``: the lambdarank gradient of LightGBM's
  ``rank_objective.hpp:81-166`` in plain float32 ``jax.numpy``, per query over
  blocks of ``QBLOCK`` queries padded to the longest one, nothing of the
  program's: a stable descending sort for the ranks, discounts
  ``1 / log2(2 + rank)``, ``inv_max_dcg`` at ``max_position`` (float64, host),
  EVERY pair with ``label_i > label_j``, ``delta = (gain_i - gain_j) *
  |disc_i - disc_j| * inv_max_dcg``, divided by ``0.01 + |s_i - s_j|`` when
  the query's scores are not all equal, ``lambda = 2 / (1 + exp(2 sigmoid
  (s_i - s_j)))``, ``hessian = lambda (2 - lambda)``, no weights. Rows get
  their sums with no scatter: queries are contiguous, so the valid entries of
  the ``[nq, W]`` table in row-major order ARE the rows.
* ``compare``: ``reference.compare``'s judgement with those gradients in the
  sigmoid's place. A rank is a step function of the scores, so the gradient of
  step k is computed at the PROGRAM's scores after step k-1 (zeros before the
  first: ``boost_from_score`` is 0 for lambdarank), which ``score`` ties to
  the chain of the program's own leaf values through the reference's routing.
  Step 1 has every score equal (normalisation off), steps 2 and 3 have it on.
  ``ndcg10`` stands where ``loss`` does: the gap between NDCG@10 of the
  program's scores and of the reference's own chain after each step.

``lower=True`` also carries the CONTROL (gradients and hessians rounded to
bfloat16) and two FAULTS emulated at the same leaves and nodes: half of the
batch left out (``half``), and every query's pairs beyond its first
``TAIL_KEPT`` documents left out (``tail``: what a layout that drops the long
tail would compute; ``plants_rank.tail_pairs_left_out`` plants it in the
program). It also reads what the choice of the PROGRAM's scores costs
(``own``): the same judgement with every step's gradient computed at the
reference's OWN chain instead (its own leaf values through its own routing,
nothing of the program's from the first step on): the gap between the two
gradients, and the program's leaf values held against the own chain's.
"""
from __future__ import annotations

import numpy as np

import reference
from reference import BLOCK

QBLOCK = 512            # [512, 139, 139] float32 = 39.6 MB a temporary
TAIL_KEPT = 64
NDCG_AT = 10


def default_label_gain(n: int = 31) -> np.ndarray:
    return np.asarray([float(2 ** i - 1) for i in range(n)], np.float64)


def query_tables(lengths: np.ndarray):
    """-> (start [nb, QBLOCK] int32, length [nb, QBLOCK] int32, W): the
    queries in row order, the last block padded with empty queries."""
    lengths = np.asarray(lengths, np.int64)
    nq = len(lengths)
    nqp = -(-nq // QBLOCK) * QBLOCK
    start = np.zeros(nqp, np.int32)
    start[:nq] = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    ln = np.zeros(nqp, np.int32)
    ln[:nq] = lengths
    return (start.reshape(-1, QBLOCK), ln.reshape(-1, QBLOCK),
            int(lengths.max()))


def padded(values: np.ndarray, lengths: np.ndarray, fill):
    """[n] in row order -> [nq, W] a query a line, ``fill`` beyond its end."""
    lengths = np.asarray(lengths, np.int64)
    W = int(lengths.max())
    valid = np.arange(W)[None, :] < lengths[:, None]
    out = np.full(valid.shape, fill, values.dtype)
    out[valid] = values
    return out, valid


def inv_max_dcg(grades: np.ndarray, lengths: np.ndarray, at: int,
                label_gain: np.ndarray) -> np.ndarray:
    """1 / (the best DCG@at a query's labels allow), 0 where that is 0."""
    lab, _ = padded(grades.astype(np.int64), lengths, -1)
    top = -np.sort(-lab, axis=1)[:, :at]
    gain = np.where(top >= 0, label_gain[np.clip(top, 0, None)], 0.0)
    dcg = (gain / np.log2(np.arange(top.shape[1]) + 2.0)).sum(axis=1)
    return np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)


def _rank_passes(n: int, W: int, sigmoid: float, label_gain: np.ndarray):
    import jax
    import jax.numpy as jnp
    gain_of = jnp.asarray(label_gain, jnp.float32)

    def gather(score, grades, st, ln):
        pos = jnp.arange(W, dtype=jnp.int32)
        idx = jnp.minimum(st[:, None] + pos[None, :], n - 1)
        valid = pos[None, :] < ln[:, None]
        return (jnp.where(valid, score[idx], -jnp.inf),
                jnp.where(valid, grades[idx], -1), valid)

    def ranks(s):
        order = jnp.argsort(-s, axis=1, stable=True)     # the document at each rank
        return jnp.argsort(order, axis=1)                # the rank of each document

    @jax.jit
    def grads(score, grades, start, length, imd):
        """-> [2, nb, QBLOCK, W, 2]: (all pairs, the tail's pairs left out) x
        (lambda, hessian) of every slot; slots beyond a query's end read 0."""
        def block(_, inp):
            st, ln, im = inp
            s, lab, valid = gather(score, grades, st, ln)
            disc = 1.0 / jnp.log2(ranks(s).astype(jnp.float32) + 2.0)
            gain = gain_of[jnp.clip(lab, 0)]
            best = jnp.max(s, axis=1)
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
            ok = ((lab[:, :, None] > lab[:, None, :])
                  & valid[:, :, None] & valid[:, None, :])
            ds = jnp.where(ok, s[:, :, None] - s[:, None, :], 0.0)
            delta = ((gain[:, :, None] - gain[:, None, :])
                     * jnp.abs(disc[:, :, None] - disc[:, None, :])
                     * im[:, None, None])
            delta = jnp.where((best != worst)[:, None, None],
                              delta / (0.01 + jnp.abs(ds)), delta)
            p = 2.0 / (1.0 + jnp.exp(2.0 * ds * sigmoid))
            lam = -p * delta
            hes = 2.0 * p * (2.0 - p) * delta
            kept = jnp.arange(W) < TAIL_KEPT
            out = []
            for mask in (ok, ok & kept[None, :, None] & kept[None, None, :]):
                l = jnp.where(mask, lam, 0.0)
                h = jnp.where(mask, hes, 0.0)
                out.append(jnp.stack([l.sum(axis=2) - l.sum(axis=1),
                                      h.sum(axis=2) + h.sum(axis=1)], axis=-1))
            return None, jnp.stack(out)
        _, t = jax.lax.scan(block, None, (start, length, imd))
        return jnp.swapaxes(t, 0, 1)

    @jax.jit
    def ndcg(scores, grades, start, length, imd):
        """NDCG@NDCG_AT summed over each block's queries [nb, V], for [V, n]
        scores; a query whose best DCG is 0 counts 1, as the source's metric
        has it."""
        def block(_, inp):
            st, ln, im = inp
            def one(sc):
                s, lab, valid = gather(sc, grades, st, ln)
                r = ranks(s)
                g = jnp.where(valid & (r < NDCG_AT), gain_of[jnp.clip(lab, 0)]
                              / jnp.log2(r.astype(jnp.float32) + 2.0), 0.0)
                v = jnp.where(im > 0, g.sum(axis=1) * im, 1.0)
                return jnp.where(ln > 0, v, 0.0).sum()
            return None, jax.vmap(one)(scores)
        return jax.lax.scan(block, None, (start, length, imd))[1]

    return grads, ndcg


def _stats_pass(n_levels: int, lower: bool):
    """``reference._passes``' ``stats`` with the gradients handed in: per-block
    leaf sums [nb, L, C] and node histograms over even and odd blocks
    [2, F, B, S * C]; C = (count, g, h[, g16, h16, g_tail, h_tail, g_own,
    h_own])."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST

    def onehot_leaf(x, t):
        d = (jnp.dot(x, t["feat"], precision=hp) <= t["thr"]).astype(jnp.float32)
        cnt = (jnp.dot(d, t["leaf_l"], precision=hp)
               + jnp.dot(1.0 - d, t["leaf_r"], precision=hp))
        samp = (jnp.dot(d, t["samp_l"], precision=hp)
                + jnp.dot(1.0 - d, t["samp_r"], precision=hp))
        return ((cnt == t["leaf_depth"]).astype(jnp.float32),
                (samp == t["samp_depth"]).astype(jnp.float32))

    def bf(a):
        # reduce_precision, not a cast pair: see reference._passes
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    @jax.jit
    def stats(levels, gh, t):
        """levels [nb, BLOCK, F]; gh [nb, BLOCK, 2 or 6] (g, h[, g_tail,
        h_tail, g_own, h_own])."""
        def block(carry, inp):
            lv, w4, par = inp
            o, m = onehot_leaf(lv.astype(jnp.float32), t)
            g, h = w4[:, 0], w4[:, 1]
            cols = [jnp.ones_like(g), g, h]
            if lower:
                cols += [bf(g), bf(h)] + [w4[:, c] for c in range(2, 6)]
            w = jnp.stack(cols, axis=1)
            leaf = jnp.dot(o.T, w, precision=hp)
            xo = (lv[:, :, None] == jnp.arange(n_levels, dtype=lv.dtype)
                  ).astype(jnp.float32)
            mw = (m[:, :, None] * w[:, None, :]).reshape(lv.shape[0], -1)
            hist = jnp.einsum("nfb,nw->fbw", xo, mw, precision=hp)
            return carry.at[par].add(hist), leaf
        nb, _, f = levels.shape
        c = 9 if lower else 3
        init = jnp.zeros((2, f, n_levels, t["samp_depth"].shape[0] * c),
                         jnp.float32)
        hist, leaf = jax.lax.scan(block, init, (levels, gh, jnp.arange(nb) % 2))
        return leaf, hist

    return stats


class RankGrads:
    """The reference's gradient of one ranking data set: tables once, then
    ``at(scores)`` -> float32 [n, 4] on the host (g, h, g_tail, h_tail)."""

    def __init__(self, grades, lengths, params):
        import jax.numpy as jnp
        self.n = int(len(grades))
        if int(np.sum(lengths)) != self.n:
            raise ValueError("query lengths must sum to the rows")
        gain = np.asarray(params.get("label_gain") or default_label_gain())
        start, ln, W = query_tables(lengths)
        _, self.valid = padded(np.zeros(self.n, np.float32), lengths, 0.0)
        nqp = start.size
        imd = np.zeros(nqp, np.float64)
        imd[:len(lengths)] = inv_max_dcg(grades, lengths,
                                         int(params.get("max_position", 20)), gain)
        imd10 = np.zeros(nqp, np.float64)
        imd10[:len(lengths)] = inv_max_dcg(grades, lengths, NDCG_AT, gain)
        self.nq = len(lengths)
        self.pairs_real = int(np.sum(np.asarray(lengths, np.int64) ** 2))
        self.grades = jnp.asarray(grades.astype(np.int32))
        self.start, self.length = jnp.asarray(start), jnp.asarray(ln)
        self.imd = jnp.asarray(imd.reshape(start.shape), jnp.float32)
        self.imd10 = jnp.asarray(imd10.reshape(start.shape), jnp.float32)
        self._grads, self._ndcg = _rank_passes(
            self.n, W, float(params.get("sigmoid", 1.0)), gain)

    def at(self, scores) -> np.ndarray:
        t = np.asarray(self._grads(scores, self.grades, self.start,
                                   self.length, self.imd))
        t = t.reshape(2, -1, t.shape[3], 2)[:, :self.nq]      # [2, nq, W, 2]
        return np.concatenate([t[0][self.valid], t[1][self.valid]], axis=1)

    def ndcg(self, scores) -> np.ndarray:
        """Mean NDCG@10 over the queries for [V, n] scores -> float64 [V]."""
        return np.asarray(self._ndcg(scores, self.grades, self.start,
                                     self.length, self.imd10),
                          np.float64).sum(axis=0) / self.nq


def compare(levels, grades, lengths, trees, prog_scores, params, seed,
            n_sampled=3, lower=False):
    """levels uint8 [n, F] (host), grades float32 [n], lengths int64 [nq],
    trees: the program's first K trees (modeltext.parse_trees), prog_scores:
    K device arrays [n], the program's training scores after each step.
    -> {"numbers", "detail", "pairs_real"[, "control", "half", "tail", "own",
    "unchanged"]}."""
    import jax.numpy as jnp
    n, F = levels.shape
    if n % BLOCK:
        raise ValueError(f"rows must be a multiple of {BLOCK}")
    nb = n // BLOCK
    L = int(params["num_leaves"])
    B = int(params["levels"])
    lr, lam = float(params["learning_rate"]), float(params.get("lambda_l2", 0.0))
    min_rows = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    _, apply, _, sq_change = reference._passes(B, lower)
    stats = _stats_pass(B, lower)
    rg = RankGrads(grades, lengths, params)
    K = len(trees)
    rng = np.random.default_rng([seed, 77])

    lev_d = jnp.asarray(levels.reshape(nb, BLOCK, F))
    # ref, chain of the program's values[, bf16, half, tail, the own chain]
    V = 6 if lower else 2
    scores = jnp.zeros((V, nb, BLOCK), jnp.float32)
    base = jnp.zeros((1, nb, BLOCK), jnp.float32)
    ndcg0 = float(rg.ndcg(base.reshape(1, n))[0])

    names = ("leaf_value", "leaf_value_rows", "split_gain", "best_split",
             "ndcg10")
    num = {"trees_short": float(max(0, K - len(prog_scores))),
           "leaf_count": 0.0, "score": 0.0, **{k: 0.0 for k in names}}
    ctl = {k: 0.0 for k in names}
    half = {"leaf_count": 0.0, **{k: 0.0 for k in names}}
    tail = {k: 0.0 for k in names}
    own = {"grad_gap": 0.0, "hess_gap": 0.0, "rows_differ": 0.0,
           "prog_leaf_value_rows": 0.0, **{k: 0.0 for k in names}}
    detail = []
    even = np.arange(nb) % 2 == 0
    for k, tree in enumerate(trees):
        nl, ni = tree["num_leaves"], tree["num_leaves"] - 1
        if nl < 2 or k > len(prog_scores):
            num["leaf_count"] += 1.0       # a stump where splits exist
            continue
        sampled = [0] + (sorted(rng.choice(np.arange(1, ni), min(n_sampled, ni - 1),
                                           replace=False).tolist()) if ni > 1 else [])
        sampled += [0] * (1 + n_sampled - len(sampled))   # one shape, one compile
        tt = reference.tree_tables(tree, F, L, sampled)
        t = {a: jnp.asarray(b) for a, b in tt.items()}
        gh = rg.at(jnp.zeros(n, jnp.float32) if k == 0 else prog_scores[k - 1])
        if lower:
            gh_own = rg.at(scores[5].reshape(n))[:, :2]
            for name, c in (("grad_gap", 0), ("hess_gap", 1)):
                own[name] = max(own[name], float(
                    np.linalg.norm(gh_own[:, c] - gh[:, c].astype(np.float64))
                    / np.linalg.norm(gh[:, c].astype(np.float64))))
            own["rows_differ"] = max(own["rows_differ"], float(np.sum(
                np.abs(gh_own[:, 0] - gh[:, 0])
                > 1e-6 * np.abs(gh[:, 0]).max())))
            gh = np.concatenate([gh, gh_own], axis=1)
        gh_d = jnp.asarray(gh[:, :6 if lower else 2].reshape(nb, BLOCK, -1))
        leaf_b, hist = stats(lev_d, gh_d, t)
        leaf_b = np.asarray(leaf_b, np.float64)[:, :nl]            # [nb, nl, C]
        hist = np.asarray(hist, np.float64).reshape(2, F, B, len(sampled), -1)
        desc = (tt["leaf_l"] + tt["leaf_r"])[:ni, :nl].astype(np.float64)

        def answers(lb, cg, ch):
            G, H = lb[:, :, cg].sum(0), lb[:, :, ch].sum(0)
            return (lb[:, :, 0].sum(0), reference.leaf_values(G, H, lr, lam),
                    reference.gains(desc, G, H, tree, lam))
        cnt, v_ref, gain_ref = answers(leaf_b, 1, 2)
        v_prog = tree["leaf_value"][:nl]
        worst = reference.worst_leaf(tree, desc, leaf_b[:, :, 1].sum(0),
                                     leaf_b[:, :, 2].sum(0), cnt, v_prog, v_ref,
                                     0.0, lr, lam)
        vfloor = float(np.median(np.abs(v_ref)))
        gfloor = float(np.median(gain_ref))

        def read(d, v, gain):
            d["leaf_value"] = max(d["leaf_value"], reference._rel(v, v_ref, vfloor))
            d["leaf_value_rows"] = max(d["leaf_value_rows"],
                                       reference._wrel(v, v_ref, cnt))
            d["split_gain"] = max(d["split_gain"],
                                  reference._rel(gain, gain_ref, gfloor))
        num["leaf_count"] += float(np.sum(cnt != tree["leaf_count"][:nl]))
        read(num, v_prog, tree["split_gain"][:ni])
        vals = [v_ref, v_prog]
        # the best cut at the sampled nodes, against the one the tree took
        full = hist.sum(0)
        for j, node in enumerate(sampled):
            hc, hg, hh = (full[:, :, j, c] for c in (0, 1, 2))
            g_all = reference.best_split(hc, hg, hh, 2 * min_rows, min_hess, lam)
            f_p = int(tree["split_feature"][node])
            t_p = int(np.floor(tree["threshold"][node]))
            g_one = reference.best_split(hc, hg, hh, 1, 0.0, lam)
            taken, best = g_one[f_p, min(max(t_p, 0), B - 2)], g_all.max()
            if not (np.isfinite(best) and best > 0):
                continue
            num["best_split"] = max(num["best_split"],
                                    float(max(0.0, best - taken) / best))
            if lower:
                for d, (a, b, c), hsrc, floor_rows in (
                        (ctl, (0, 3, 4), full, 2 * min_rows),
                        (half, (0, 1, 2), hist[0], min_rows),
                        (tail, (0, 5, 6), full, 2 * min_rows),
                        (own, (0, 7, 8), full, 2 * min_rows)):
                    g_low = reference.best_split(
                        *(hsrc[:, :, j, q] for q in (a, b, c)), floor_rows,
                        min_hess, lam)
                    if not np.isfinite(g_low.max()):
                        continue
                    pick = np.unravel_index(np.argmax(g_low), g_low.shape)
                    d["best_split"] = max(d["best_split"],
                                          float(max(0.0, best - g_one[pick]) / best))
        if lower:
            _, v16, gain16 = answers(leaf_b, 3, 4)
            read(ctl, v16, gain16)
            ch, vh, gh_half = answers(leaf_b[even], 1, 2)
            half["leaf_count"] += float(np.sum(ch != cnt))
            read(half, vh, 2.0 * gh_half)
            _, vt_, gain_t = answers(leaf_b, 5, 6)
            read(tail, vt_, gain_t)
            _, v_own, gain_own = answers(leaf_b, 7, 8)
            read(own, v_own, gain_own)
            own["prog_leaf_value_rows"] = max(
                own["prog_leaf_value_rows"],
                reference._wrel(v_prog, v_own, cnt))
            vals += [v16, vh, vt_, v_own]
        vt = np.zeros((V, L), np.float32)
        for i, v in enumerate(vals):
            vt[i, :nl] = v
        scores = apply(lev_d, t, scores, jnp.asarray(vt))
        nd = rg.ndcg(scores.reshape(V, n))
        if k < len(prog_scores):
            ps = prog_scores[k].reshape(1, nb, BLOCK)
            ndp = float(rg.ndcg(ps.reshape(1, n))[0])
            num["ndcg10"] = max(num["ndcg10"], abs(ndp - nd[0]) / nd[0])
            rms = float(np.sqrt(np.asarray(sq_change(scores[:1], base),
                                           np.float64).sum() / n))
            gap = float(jnp.max(jnp.abs(ps[0] - scores[1])))
            num["score"] = max(num["score"], gap / rms)
        if lower:
            for d, i in ((ctl, 2), (half, 3), (tail, 4), (own, 5)):
                d["ndcg10"] = max(d["ndcg10"], abs(nd[i] - nd[0]) / nd[0])
        detail.append({"tree": k, "leaves": int(nl), "sampled": sampled,
                       "ndcg10_ref": float(nd[0]), "worst_leaf": worst})
    # the change of the scores over the steps, as a gap of norms
    ch = np.sqrt(np.asarray(sq_change(scores, base), np.float64).sum(1))
    if prog_scores:
        ps = prog_scores[min(K, len(prog_scores)) - 1].reshape(1, nb, BLOCK)
        cp = float(np.sqrt(np.asarray(sq_change(ps, base), np.float64).sum()))
        num["update_norm"] = abs(cp - ch[0]) / ch[0] if ch[0] > 0 else np.inf
    out = {"numbers": num, "detail": detail, "ndcg10_0": ndcg0,
           "pairs_real": rg.pairs_real}
    if lower:
        for d, i in ((ctl, 2), (half, 3), (tail, 4), (own, 5)):
            d["update_norm"] = abs(ch[i] - ch[0]) / ch[0]
        last = detail[-1]["ndcg10_ref"] if detail else ndcg0
        out.update(control=ctl, half=half, tail=tail, own=own,
                   unchanged={"ndcg10": abs(ndcg0 - last) / last,
                              "update_norm": 1.0, "score": 1.0})
    return out
