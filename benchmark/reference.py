"""The plain reference of one boosting iteration of binary GBDT, and the
comparison that decides ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, in blocks of
rows, no kernels; it imports nothing of the program and is given nothing the
program made except the answer it judges: the trees (structure, thresholds,
leaf values, leaf counts, split gains) and the training scores after each of
the first steps. From the raw levels and labels it recomputes, for each of
those trees in turn, along a trajectory of its own:

* gradients and hessians of the log loss at its own scores;
* the leaf every row lands in, by the tree's real-valued thresholds (so the
  program's binning and partition are judged by the exact leaf counts);
* per-leaf sums -> its own leaf values, and per-node sums -> its own gains;
* at the root and a few nodes drawn from the seed, the whole histogram of the
  node's rows -> the best split there is, against the one the tree took;
* its own scores and loss after the step.

``lower=True`` also carries the CONTROL: the same sums with gradients and
hessians rounded to bfloat16 (the nearest precision below the float32 the
configuration states), read at the same leaves and nodes as the program's
answer is; and the FAULT "half of the batch left out": the same from the even
blocks alone.
"""
from __future__ import annotations

from functools import partial

import numpy as np

BLOCK = 32768


# ---------------------------------------------------------------------------
# tables of one tree, padded to L leaves
# ---------------------------------------------------------------------------

def tree_tables(tree: dict, n_features: int, L: int, sampled: list[int]):
    """Dense tables that route rows without a gather: which feature each node
    tests, and for every leaf (and sampled node) which ancestors it must have
    gone left or right at."""
    nl = tree["num_leaves"]
    ni = nl - 1
    feat = np.zeros((n_features, L - 1), np.float32)
    thr = np.full(L - 1, np.inf, np.float32)
    leaf_l = np.zeros((L - 1, L), np.float32)
    leaf_r = np.zeros((L - 1, L), np.float32)
    node_l = np.zeros((L - 1, L - 1), np.float32)
    node_r = np.zeros((L - 1, L - 1), np.float32)
    leaf_depth = np.full(L, -1.0, np.float32)
    node_depth = np.full(L - 1, -1.0, np.float32)
    for i in range(ni):
        feat[tree["split_feature"][i], i] = 1.0
        # levels are whole numbers and thresholds lie between them
        thr[i] = np.float32(tree["threshold"][i])
    stack = [(0, [], [])] if ni else []
    while stack:
        node, went_l, went_r = stack.pop()
        node_l[went_l, node] = 1.0
        node_r[went_r, node] = 1.0
        node_depth[node] = len(went_l) + len(went_r)
        for child, wl, wr in ((tree["left_child"][node], went_l + [node], went_r),
                              (tree["right_child"][node], went_l, went_r + [node])):
            if child < 0:
                leaf_l[wl, ~child] = 1.0
                leaf_r[wr, ~child] = 1.0
                leaf_depth[~child] = len(wl) + len(wr)
            else:
                stack.append((int(child), wl, wr))
    if not ni:
        leaf_depth[0] = 0.0
    s = np.asarray(sampled, np.int64)
    return {"feat": feat, "thr": thr, "leaf_l": leaf_l, "leaf_r": leaf_r,
            "leaf_depth": leaf_depth, "samp_l": node_l[:, s],
            "samp_r": node_r[:, s], "samp_depth": node_depth[s]}


# ---------------------------------------------------------------------------
# device passes (built lazily: jax is imported by the caller's process)
# ---------------------------------------------------------------------------

def _passes(n_levels: int, lower: bool):
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

    def grads(s, y):
        p = jax.nn.sigmoid(s)
        return p - y, p * (1.0 - p)

    def bf(a):
        # not ``astype(bfloat16).astype(float32)``: the TPU compiler drops that
        # pair as excess precision, and the control then reads 0 (PR 25, call B)
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    @jax.jit
    def stats(levels, y, s, t):
        """-> per-block leaf sums [nb, L, C], node histograms over even and
        odd blocks [2, S, F, B, C]; C = (count, g, h[, g16, h16])."""
        def block(carry, inp):
            lv, yb, sb, par = inp
            x = lv.astype(jnp.float32)
            o, m = onehot_leaf(x, t)
            g, h = grads(sb, yb)
            cols = [jnp.ones_like(g), g, h] + ([bf(g), bf(h)] if lower else [])
            w = jnp.stack(cols, axis=1)                          # [n, C]
            leaf = jnp.dot(o.T, w, precision=hp)                 # [L, C]
            xo = (lv[:, :, None] == jnp.arange(n_levels, dtype=lv.dtype)
                  ).astype(jnp.float32)                          # [n, F, B]
            mw = (m[:, :, None] * w[:, None, :]).reshape(lv.shape[0], -1)
            hist = jnp.einsum("nfb,nw->fbw", xo, mw, precision=hp)
            carry = carry.at[par].add(hist)
            return carry, leaf
        nb, _, f = levels.shape
        c = 5 if lower else 3
        init = jnp.zeros((2, f, n_levels, t["samp_depth"].shape[0] * c),
                         jnp.float32)
        par = jnp.arange(nb) % 2
        hist, leaf = jax.lax.scan(block, init, (levels, y, s, par))
        return leaf, hist

    @partial(jax.jit, donate_argnums=(2,))
    def apply(levels, t, scores, values):
        """scores [V, nb, n] += values [V, L] at each row's leaf."""
        def block(_, inp):
            lv, sb = inp
            o, _m = onehot_leaf(lv.astype(jnp.float32), t)
            return None, sb + jnp.dot(values, o.T, precision=hp)
        _, out = jax.lax.scan(block, None,
                              (levels, jnp.swapaxes(scores, 0, 1)))
        return jnp.swapaxes(out, 0, 1)

    @jax.jit
    def loss(scores, y):
        """Summed log loss per block, for [V, nb, n] scores."""
        l = jnp.logaddexp(0.0, scores) - y[None] * scores
        return l.sum(axis=2)

    @jax.jit
    def sq_change(scores, base):
        return ((scores - base) ** 2).sum(axis=2)

    return stats, apply, loss, sq_change


# ---------------------------------------------------------------------------
# host arithmetic on the sums (float64)
# ---------------------------------------------------------------------------

def leaf_values(G, H, lr, lam):
    return -G / (H + lam) * lr


def gains(desc, G, H, tree, lam):
    """Gain of each split of the tree from per-leaf sums."""
    ni = tree["num_leaves"] - 1
    nG, nH = desc @ G, desc @ H
    def side(c):
        return ((G[~c], H[~c]) if c < 0 else (nG[c], nH[c]))
    out = np.zeros(ni)
    for i in range(ni):
        (gl, hl), (gr, hr) = side(tree["left_child"][i]), side(tree["right_child"][i])
        out[i] = (gl * gl / (hl + lam) + gr * gr / (hr + lam)
                  - nG[i] ** 2 / (nH[i] + lam))
    return out


def best_split(hist_c, hist_g, hist_h, min_rows, min_hess, lam):
    """Gains of every (feature, level<=t) cut of one node's histogram
    [F, B]; cuts that leave a child under the floors read -inf."""
    cl, gl, hl = (np.cumsum(a, axis=1)[:, :-1] for a in (hist_c, hist_g, hist_h))
    ct, gt, ht = hist_c.sum(1)[:1], hist_g.sum(1)[:1], hist_h.sum(1)[:1]
    cr, gr, hr = ct - cl, gt - gl, ht - hl
    ok = (cl >= min_rows) & (cr >= min_rows) & (hl >= min_hess) & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        g = gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)
    return np.where(ok, g, -np.inf)


def worst_leaf(tree, desc, G, H, cnt, v_prog, v_ref, bias, lr, lam) -> dict:
    """The leaf whose value lies farthest from the reference's, with what a
    reader needs to see why: its rows and sums, and its parent's value as the
    program wrote it and as the reference's sums give it."""
    floor = float(np.median(np.abs(v_ref - bias)))
    gap = np.abs(v_prog - v_ref) / np.maximum(np.abs(v_ref - bias), floor)
    w = int(np.argmax(gap))
    kids = np.stack([tree["left_child"], tree["right_child"]], axis=1)
    par, side = (int(a[0]) for a in np.nonzero(kids == ~w))
    sib = int(kids[par, 1 - side])
    nG, nH = desc @ G, desc @ H
    return {"leaf": w, "gap": float(gap[w]), "rows": float(cnt[w]),
            "value": float(v_prog[w] - bias), "ref_value": float(v_ref[w] - bias),
            "G": float(G[w]), "H": float(H[w]), "median_abs_value": floor,
            "parent": par, "side": "LR"[side],
            "parent_rows": float(tree["internal_count"][par]),
            "parent_value": float(tree["internal_value"][par] - bias),
            "parent_ref_value": float(-nG[par] / (nH[par] + lam) * lr),
            "sibling": sib,
            "sibling_rows": float(tree["leaf_count"][~sib] if sib < 0
                                  else tree["internal_count"][sib])}


def _rel(a, b, floor):
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor), initial=0.0))


def _wrel(a, b, w):
    """The gap as the rows feel it: root of the row-weighted mean square of
    a - b over that of b."""
    return float(np.sqrt(np.sum(w * (a - b) ** 2) / np.sum(w * b ** 2)))


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def compare(levels, y, trees, prog_scores, params, seed, n_sampled=3,
            lower=False):
    """levels uint8 [n, F] (host), y float32 [n] (host), trees: the program's
    first K trees (modeltext.parse_trees), prog_scores: K device arrays [n],
    the program's training scores after each of those steps.
    -> {"numbers": {name: value}, "control": {...}, "half": {...},
        "unchanged": {...}} (the last three only with ``lower``)."""
    import jax
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
    stats, apply, loss_fn, sq_change = _passes(B, lower)
    K = len(trees)
    rng = np.random.default_rng([seed, 77])

    lev_d = jnp.asarray(levels.reshape(nb, BLOCK, F))
    y_d = jnp.asarray(y.reshape(nb, BLOCK))
    pavg = float(np.mean(y, dtype=np.float64))
    init = float(np.log(pavg / (1.0 - pavg)))
    V = 4 if lower else 2      # ref, chain of the program's values[, bf16, half]
    scores = jnp.full((V, nb, BLOCK), init, jnp.float32)
    base = jnp.full((1, nb, BLOCK), init, jnp.float32)
    loss0 = float(np.asarray(loss_fn(base, y_d), np.float64).sum() / n)

    num = {"trees_short": float(max(0, K - len(prog_scores))),
           "leaf_count": 0.0, "leaf_value": 0.0, "leaf_value_rows": 0.0,
           "split_gain": 0.0, "best_split": 0.0, "score": 0.0, "loss": 0.0}
    ctl = {"leaf_value": 0.0, "leaf_value_rows": 0.0, "split_gain": 0.0,
           "best_split": 0.0, "loss": 0.0}
    half = {"leaf_count": 0.0, "leaf_value": 0.0, "leaf_value_rows": 0.0,
            "split_gain": 0.0, "best_split": 0.0, "loss": 0.0}
    detail = []
    even = np.arange(nb) % 2 == 0
    for k, tree in enumerate(trees):
        nl, ni = tree["num_leaves"], tree["num_leaves"] - 1
        if nl < 2:
            num["leaf_count"] += 1.0       # a stump where splits exist
            continue
        sampled = [0] + (sorted(rng.choice(np.arange(1, ni), min(n_sampled, ni - 1),
                                           replace=False).tolist()) if ni > 1 else [])
        sampled += [0] * (1 + n_sampled - len(sampled))   # one shape, one compile
        tt = tree_tables(tree, F, L, sampled)
        t = {a: jnp.asarray(b) for a, b in tt.items()}
        leaf_b, hist = stats(lev_d, y_d, scores[0], t)
        leaf_b = np.asarray(leaf_b, np.float64)[:, :nl]            # [nb, nl, C]
        hist = np.asarray(hist, np.float64).reshape(2, F, B, len(sampled), -1)
        desc = (tt["leaf_l"] + tt["leaf_r"])[:ni, :nl].astype(np.float64)
        bias = init if k == 0 else 0.0

        def answers(lb, cg, ch):
            G, H = lb[:, :, cg].sum(0), lb[:, :, ch].sum(0)
            return (lb[:, :, 0].sum(0), leaf_values(G, H, lr, lam) + bias,
                    gains(desc, G, H, tree, lam))
        cnt, v_ref, gain_ref = answers(leaf_b, 1, 2)
        v_prog = tree["leaf_value"][:nl]
        worst = worst_leaf(tree, desc, leaf_b[:, :, 1].sum(0), leaf_b[:, :, 2].sum(0),
                           cnt, v_prog, v_ref, bias, lr, lam)
        vfloor = float(np.median(np.abs(v_ref - bias)))
        gfloor = float(np.median(gain_ref))
        num["leaf_count"] += float(np.sum(cnt != tree["leaf_count"][:nl]))
        num["leaf_value"] = max(num["leaf_value"],
                                _rel(v_prog - bias, v_ref - bias, vfloor))
        num["leaf_value_rows"] = max(num["leaf_value_rows"],
                                     _wrel(v_prog - bias, v_ref - bias, cnt))
        num["split_gain"] = max(num["split_gain"],
                                _rel(tree["split_gain"][:ni], gain_ref, gfloor))
        vals = [v_ref, v_prog]
        # the best cut at the sampled nodes, against the one the tree took
        full = hist.sum(0)
        for j, node in enumerate(sampled):
            hc, hg, hh = (full[:, :, j, c] for c in (0, 1, 2))
            g_all = best_split(hc, hg, hh, 2 * min_rows, min_hess, lam)
            f_p = int(tree["split_feature"][node])
            t_p = int(np.floor(tree["threshold"][node]))
            g_one = best_split(hc, hg, hh, 1, 0.0, lam)
            taken, best = g_one[f_p, min(max(t_p, 0), B - 2)], g_all.max()
            if np.isfinite(best) and best > 0:
                num["best_split"] = max(num["best_split"],
                                        float(max(0.0, best - taken) / best))
            if lower:
                for name, (a, b, c), hsrc, floor_rows in (
                        ("ctl", (0, 3, 4), full, 2 * min_rows),
                        ("half", (0, 1, 2), hist[0], min_rows)):
                    g_low = best_split(*(hsrc[:, :, j, q] for q in (a, b, c)),
                                       floor_rows, min_hess, lam)
                    if not np.isfinite(g_low.max()) or not best > 0:
                        continue
                    pick = np.unravel_index(np.argmax(g_low), g_low.shape)
                    gap = float(max(0.0, best - g_one[pick]) / best)
                    d = ctl if name == "ctl" else half
                    d["best_split"] = max(d["best_split"], gap)
        if lower:
            _, v16, gain16 = answers(leaf_b, 3, 4)
            ctl["leaf_value"] = max(ctl["leaf_value"],
                                    _rel(v16 - bias, v_ref - bias, vfloor))
            ctl["leaf_value_rows"] = max(ctl["leaf_value_rows"],
                                         _wrel(v16 - bias, v_ref - bias, cnt))
            ctl["split_gain"] = max(ctl["split_gain"],
                                    _rel(gain16, gain_ref, gfloor))
            ch, vh, gh = answers(leaf_b[even], 1, 2)
            half["leaf_count"] += float(np.sum(ch != cnt))
            half["leaf_value"] = max(half["leaf_value"],
                                     _rel(vh - bias, v_ref - bias, vfloor))
            half["leaf_value_rows"] = max(half["leaf_value_rows"],
                                          _wrel(vh - bias, v_ref - bias, cnt))
            half["split_gain"] = max(half["split_gain"],
                                     _rel(2.0 * gh, gain_ref, gfloor))
            vals += [v16, vh]
        vt = np.zeros((V, L), np.float32)
        for i, v in enumerate(vals):
            vt[i, :nl] = v - bias
        # tree 1's values carry the init score, which the scores already hold
        scores = apply(lev_d, t, scores, jnp.asarray(vt))
        ls = np.asarray(loss_fn(scores, y_d), np.float64).sum(1) / n
        if k < len(prog_scores):
            ps = prog_scores[k].reshape(1, nb, BLOCK)
            lp = float(np.asarray(loss_fn(ps, y_d), np.float64).sum() / n)
            num["loss"] = max(num["loss"], abs(lp - ls[0]) / ls[0])
            rms = float(np.sqrt(np.asarray(sq_change(scores[:1], base),
                                           np.float64).sum() / n))
            gap = float(jnp.max(jnp.abs(ps[0] - scores[1])))
            num["score"] = max(num["score"], gap / rms)
        if lower:
            ctl["loss"] = max(ctl["loss"], abs(ls[2] - ls[0]) / ls[0])
            half["loss"] = max(half["loss"], abs(ls[3] - ls[0]) / ls[0])
        detail.append({"tree": k, "leaves": int(nl), "sampled": sampled,
                       "loss_ref": float(ls[0]), "worst_leaf": worst})
    # the change of the scores over the steps, as a gap of norms
    ch = np.sqrt(np.asarray(sq_change(scores, base), np.float64).sum(1))
    if prog_scores:
        ps = prog_scores[min(K, len(prog_scores)) - 1].reshape(1, nb, BLOCK)
        cp = float(np.sqrt(np.asarray(sq_change(ps, base), np.float64).sum()))
        num["update_norm"] = abs(cp - ch[0]) / ch[0] if ch[0] > 0 else np.inf
    out = {"numbers": num, "detail": detail, "loss0": loss0}
    if lower:
        ctl["update_norm"] = abs(ch[2] - ch[0]) / ch[0]
        half["update_norm"] = abs(ch[3] - ch[0]) / ch[0]
        last = detail[-1]["loss_ref"] if detail else loss0
        out.update(control=ctl, half=half,
                   unchanged={"loss": abs(loss0 - last) / last,
                              "update_norm": 1.0, "score": 1.0})
    return out


def decide(numbers: dict, limits: dict):
    """-> (correct, [(name, value, limit)]): every number named in ``limits``
    must be there and at or under its limit."""
    rows = [(k, float(numbers.get(k, np.inf)), float(v))
            for k, v in limits.items()]
    ok = bool(rows) and all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
