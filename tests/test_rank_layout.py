"""The lambdarank pair gradient laid out by query length
(objectives/objective.py ``pair_class_widths``, ``LambdarankNDCG``):

* against the benchmark's plain reference (``benchmark/reference_rank.py``:
  stable sort, every pair, nothing of the program's) over lengths that sit on
  and beside every class edge, on random, all-equal and tied scores and a
  query of one grade;
* the one-class case against the function every earlier release ran
  (kept here as the reference the python guide asks for), bit for bit;
* what the lowered step no longer holds: a scatter;
* the registry: two windows whose class tables share their buckets share one
  compiled step;
* the gauges and the scope the benchmark's readers and a trace viewer read.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import fit_gbdt
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata
from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.objectives import objective as O
from lightgbm_tpu.ops import step_cache

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path[:0] = [str(BENCH)]
import reference_rank  # noqa: E402

EDGES = [1, 2, 7, 8, 9, 64, 139]


def _objective(counts, lab, **params):
    cfg = Config().set({"objective": "lambdarank", **params})
    obj = create_objective("lambdarank", cfg)
    obj.init(Metadata(label=np.asarray(lab, np.float32),
                      group=np.asarray(counts)), int(np.sum(counts)))
    return obj


def _grads(obj, score):
    g, h = jax.jit(obj.gradient_builder())(jnp.asarray(score),
                                            obj.gradient_aux())
    return np.asarray(g), np.asarray(h)


def _scores(kind, n, rng):
    if kind == "equal":
        return np.zeros(n, np.float32)
    if kind == "tied":           # what leaf values give: few distinct scores
        return (rng.integers(0, 4, n) * 0.25).astype(np.float32)
    return rng.standard_normal(n).astype(np.float32)


# -- (a) against the plain reference ------------------------------------------------

@pytest.mark.parametrize("scores", ["random", "equal", "tied"])
def test_layout_by_length_matches_the_plain_reference(scores):
    """Tolerance: both sides sum the same float32 pair terms (the ranks are
    integers and agree exactly: same scores, same stable order), in another
    order: the program over a class's width, the reference over 139 slots.
    A sum of up to 138 terms of mixed sign regroups within a few ulp of the
    LARGEST partial sum, so the bound is on the gap over the query-wide
    scale max|g|, not over each |g|: 2e-6 = ~16 ulp of float32."""
    rng = np.random.default_rng(7)
    counts = np.asarray(EDGES * 4 + [3, 5, 17, 30, 100, 12] * 3)
    n = int(counts.sum())
    lab = rng.integers(0, 5, n)
    lab[:1] = 2
    one_grade = np.cumsum(counts)[10]          # a query of one grade: no pair
    lab[one_grade:one_grade + counts[11]] = 3
    obj = _objective(counts, lab)
    assert len(obj._pair_classes) > 1
    score = _scores(scores, n, rng)
    g, h = _grads(obj, score)
    ref = reference_rank.RankGrads(lab.astype(np.float32), counts,
                                   {"sigmoid": 1.0, "max_position": 20})
    gh = ref.at(jnp.asarray(score))
    assert ref.pairs_real == int(np.sum(counts.astype(np.int64) ** 2))
    assert obs.default_registry().snapshot()["gauges"]["rank/pairs_real"] \
        == ref.pairs_real
    for mine, theirs in ((g, gh[:, 0]), (h, gh[:, 1])):
        assert np.abs(mine - theirs).max() <= 2e-6 * np.abs(theirs).max()
    qb = np.concatenate([[0], np.cumsum(counts)])
    for q in np.nonzero(counts == 1)[0]:       # one document: no pair, +0.0
        assert g[qb[q]] == 0.0 and h[qb[q]] == 0.0
    assert not g[one_grade:one_grade + counts[11]].any()
    assert np.all(h >= 0.0) and h.sum() > 0.0


# -- (b) the one-class case is the function every release ran ------------------------

@jax.jit
def _parent_lambdarank_grads(score, labels, q_idx, q_valid, inv_max_dcg,
                             label_gain, sigmoid):
    """objectives/objective.py ``_lambdarank_grads`` as PR 33 had it: one
    vmap over all queries padded to the longest, an argsort and a scatter a
    query for the ranks, two scatter-adds onto the rows."""
    def one_query(idx, valid, imd):
        s = jnp.where(valid, score[idx], -jnp.inf)
        lab = jnp.where(valid, labels[idx], -1)
        q = idx.shape[0]
        order = jnp.argsort(-s, stable=True)
        rank_of = jnp.zeros(q, jnp.int32).at[order].set(
            jnp.arange(q, dtype=jnp.int32))
        discount = 1.0 / jnp.log2(rank_of.astype(jnp.float32) + 2.0)
        best = jnp.max(jnp.where(valid, s, -jnp.inf))
        worst = jnp.min(jnp.where(valid, s, jnp.inf))
        norm_on = best != worst
        gain = label_gain[jnp.clip(lab, 0)]
        pair_ok = ((lab[:, None] > lab[None, :])
                   & valid[:, None] & valid[None, :])
        ds = s[:, None] - s[None, :]
        delta = ((gain[:, None] - gain[None, :])
                 * jnp.abs(discount[:, None] - discount[None, :]) * imd)
        delta = jnp.where(norm_on, delta / (0.01 + jnp.abs(ds)), delta)
        p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * ds * sigmoid))
        p_hess = p_lambda * (2.0 - p_lambda)
        p_lambda = jnp.where(pair_ok, -p_lambda * delta, 0.0)
        p_hess = jnp.where(pair_ok, 2.0 * p_hess * delta, 0.0)
        return (jnp.sum(p_lambda, axis=1) - jnp.sum(p_lambda, axis=0),
                jnp.sum(p_hess, axis=1) + jnp.sum(p_hess, axis=0))

    lam_q, hes_q = jax.vmap(one_query)(q_idx, q_valid, inv_max_dcg)
    n = score.shape[0]
    flat_idx, flat_valid = q_idx.reshape(-1), q_valid.reshape(-1)
    lam = jnp.zeros(n, score.dtype).at[flat_idx].add(
        jnp.where(flat_valid, lam_q.reshape(-1), 0.0))
    hes = jnp.zeros(n, score.dtype).at[flat_idx].add(
        jnp.where(flat_valid, hes_q.reshape(-1), 0.0))
    return lam, hes


def _parent_tables(counts, lab, label_gain, at):
    """``LambdarankNDCG.init``'s two loops over the queries, as PR 33 had
    them."""
    qb = np.concatenate([[0], np.cumsum(counts)])
    nq, qmax = len(counts), int(counts.max())
    idx = np.zeros((nq, qmax), np.int32)
    valid = np.zeros((nq, qmax), bool)
    imd = np.zeros(nq, np.float64)
    for q in range(nq):
        c = counts[q]
        idx[q, :c] = np.arange(qb[q], qb[q + 1])
        valid[q, :c] = True
        top = np.sort(lab[qb[q]:qb[q + 1]])[::-1][:at]
        dcg = np.sum(label_gain[top] / np.log2(np.arange(len(top)) + 2.0))
        imd[q] = 1.0 / dcg if dcg > 0 else 0.0
    return idx, valid, imd


@pytest.mark.parametrize("counts", [np.full(60, 20), np.asarray(
    [18, 20, 13, 20, 19, 11, 16, 20, 17, 15] * 5)], ids=["even", "ragged"])
@pytest.mark.parametrize("scores", ["random", "equal", "tied"])
def test_one_class_is_the_parents_function_bit_for_bit(counts, scores):
    """A job whose one padded block spends at most ``PAIR_SLOTS_BOUND`` slots
    a real pair keeps ONE class of width qmax: the same pair block, the same
    reductions over the same axis widths, the rows' sums now gathered where
    they were scattered (0 + x = x). The init tables hold the same values
    as the loops gave."""
    rng = np.random.default_rng(31)
    n = int(counts.sum())
    lab = rng.integers(0, 5, n)
    obj = _objective(counts, lab)
    assert [c["lab"].shape[1] for c in obj._pair_classes] == [counts.max()]
    idx, valid, imd = _parent_tables(counts, lab, obj.label_gain,
                                     obj.optimize_pos_at)
    assert np.array_equal(imd, obj.inv_max_dcg)
    score = _scores(scores, n, rng)
    g, h = _grads(obj, score)
    g0, h0 = _parent_lambdarank_grads(
        jnp.asarray(score), jnp.asarray(lab.astype(np.int32)), idx, valid,
        imd.astype(np.float32), obj.label_gain.astype(np.float32),
        obj.sigmoid)
    assert np.array_equal(g, np.asarray(g0))
    assert np.array_equal(h, np.asarray(h0))


def test_classes_follow_the_observed_lengths():
    bound = O.PAIR_SLOTS_BOUND
    # one class while the padded block stays within the bound
    assert O.pair_class_widths(np.full(60, 20), 64) == [20]
    # a heavy tail: powers of two from 8, the widest cut to qmax, and the
    # slots of the classes within the bound where the one block was not
    rng = np.random.default_rng(3)
    counts = np.clip(np.rint(np.exp(2.78 + 0.9 * rng.standard_normal(4000))),
                     1, 139).astype(np.int64)
    real = float(np.sum(counts ** 2))
    assert step_cache.pow2_bucket(4000, 16) * 139 ** 2 > bound * real
    widths = O.pair_class_widths(counts, step_cache.pow2_bucket(4000, 16))
    assert widths == [8, 16, 32, 64, 128, 139]
    obj = _objective(counts, rng.integers(0, 5, int(counts.sum())))
    gauges = obs.default_registry().snapshot()["gauges"]
    assert [c["lab"].shape[1] for c in obj._pair_classes] == widths
    assert gauges["rank/pairs_real"] == real
    assert gauges["rank/pair_slots"] == sum(
        c["lab"].shape[0] * c["lab"].shape[1] ** 2 for c in obj._pair_classes)
    assert gauges["rank/pair_slots"] <= bound * real
    # empty classes are left out: nothing between 9 and 64 documents
    assert O.pair_class_widths(np.asarray([3, 4, 5, 3, 70, 100] * 50), 512) \
        == [8, 100]


def test_blocks_of_queries_give_the_unblocked_sums(monkeypatch):
    """A class whose ``[nq_c, w, w]`` passes the byte budget runs a block of
    queries at a time: the same pair terms a query; the compiler may group
    a block's reductions otherwise than the whole class's, so the sums agree
    to a few ulp of the largest (the bound of the reference's test)."""
    rng = np.random.default_rng(5)
    counts = np.asarray([3, 9, 30, 7, 12, 25, 2, 16] * 8)
    n = int(counts.sum())
    obj = _objective(counts, rng.integers(0, 4, n))
    score = _scores("random", n, rng)
    whole = _grads(obj, score)
    monkeypatch.setattr(O, "PAIR_BLOCK_BYTES", 4 * 32 * 32 * 4)   # 4 queries
    blocked = _grads(obj, score)
    for a, b in zip(whole, blocked):
        assert np.abs(a - b).max() <= 2e-6 * np.abs(a).max()
        assert np.abs(a).max() > 0


# -- (c) a small train through Dataset(group=) against the reference's trees ---------

def test_three_trees_through_dataset_group_match_the_reference():
    """``Dataset(group=)`` -> ``Booster`` -> ``update()`` x 3 on integer
    levels, judged by ``reference_rank.compare`` as the benchmark's cell is:
    exact leaf counts, the best cut at the sampled nodes, leaf values, the
    scores. Limits: float32 sums of 32,768 rows against float64-across-blocks
    ones; 1e-4 is 50x over what this size reads and 10x under what gradients
    rounded to bfloat16 read (the control, asserted below)."""
    import lightgbm_tpu as lgb
    import datagen_rank
    import modeltext
    spec = {"rows": 32768, "features": 12, "levels": 32, "uniform_columns": 12,
            "label_noise": 0.5, "query_len_min": 1, "query_len_max": 139,
            "query_len_mean": 23.7, "query_len_sigma": 0.9, "grades": 5,
            "grade_shares": [0.26, 0.36, 0.28, 0.08, 0.02]}
    X, levels, grades, lengths = datagen_rank.make(spec, 17, threads=2)
    assert lengths.sum() == 32768 and lengths.min() == 1
    params = {"objective": "lambdarank", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 0,
              "min_sum_hessian_in_leaf": 10.0, "lambda_l2": 0.0, "verbose": -1}
    ds = lgb.Dataset(X, label=grades, group=lengths, params=dict(params))
    bst = lgb.Booster(dict(params), ds)
    scores = []
    for _ in range(3):
        bst.update()
        scores.append(bst._gbdt.train_scores()[0])
    trees = modeltext.parse_trees(bst.model_to_string())
    assert [t["num_leaves"] for t in trees] == [15, 15, 15]
    out = reference_rank.compare(levels, grades, lengths, trees, scores,
                                 {**params, "levels": 32}, seed=17,
                                 lower=True)
    num = out["numbers"]
    assert num["trees_short"] == 0 and num["leaf_count"] == 0
    assert num["best_split"] <= 1e-4, num
    assert num["leaf_value_rows"] <= 1e-4 and num["score"] <= 1e-4, num
    assert num["update_norm"] <= 1e-4 and num["ndcg10"] <= 1e-4, num
    assert out["control"]["leaf_value_rows"] > 1e-4, out["control"]
    assert out["tail"]["leaf_value_rows"] > 1e-3, out["tail"]
    # the gradient at the reference's OWN chain agrees with the one at the
    # program's scores (a rank is a step function of the scores)
    print("own", out["own"])
    assert out["own"]["grad_gap"] <= 1e-4, out["own"]
    assert out["own"]["prog_leaf_value_rows"] <= 1e-4, out["own"]


# -- the lowered step, the registry, the names ---------------------------------------

def _rank_booster(counts, seed=3, **params):
    rng = np.random.default_rng(seed)
    n = int(np.sum(counts))
    X = rng.normal(size=(n, 6))
    y = np.clip((X[:, 0] * 2 + rng.normal(size=n)) // 1.0, 0, 3)
    return fit_gbdt(X, y.astype(np.float32), {"objective": "lambdarank",
                                              **params},
                    num_round=2, group=np.asarray(counts, np.int64))


def _primitives(jaxpr) -> set:
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= _primitives(sub)
    return out


def test_lowered_ranking_step_holds_no_scatter_and_the_pair_scope():
    """No scatter-add of padded slots onto rows and no per-query scatter
    for the ranks: the gradient's part of the lowered step (the scope
    ``lgbm/gradients``) gathers and reduces only."""
    counts = np.asarray([1, 2, 7, 8, 9, 64, 139, 30, 17] * 6)
    g = _rank_booster(counts)
    assert len(g.objective._pair_classes) > 1
    text = g.lower_step().as_text(debug_info=True)
    assert "lgbm/gradients/rank_pairs" in text
    grad_lines = [ln for ln in text.splitlines() if "lgbm/gradients" in ln]
    assert any("gather" in ln for ln in grad_lines)
    assert not [ln for ln in grad_lines if "scatter" in ln]
    prims = _primitives(jax.make_jaxpr(g.objective.gradient_builder())(
        jnp.zeros(int(counts.sum()), jnp.float32),
        g.objective.gradient_aux()).jaxpr)
    assert "gather" in prims
    assert not [p for p in prims if "scatter" in p or "sort" in p], prims


def test_refit_of_a_ranking_booster_runs_the_pair_block_as_one_program():
    """``refit_existing`` is the eager caller of ``get_gradients``: it must
    run the jitted builder (one program, the step's own function; op by op
    every ``[nq_c, w, w]`` temporary would be materialised). Refitting on
    the data the trees were grown from with ``decay_rate`` 0 re-learns each
    tree's leaf outputs from gradients at the refit scores of the trees
    before it, which is what training did: the same outputs, to float32
    sums in another order (``.at[leaf].add`` against the histogram's)."""
    from lightgbm_tpu.io.dataset import TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from conftest import TEST_PARAMS
    counts = np.asarray([1, 2, 7, 8, 9, 64, 139, 30, 17] * 6, np.int64)
    g = _rank_booster(counts)
    obj = g.objective
    assert len(obj._pair_classes) > 1
    score = np.random.default_rng(9).standard_normal(
        int(counts.sum())).astype(np.float32)
    eager = obj.get_gradients(jnp.asarray(score))
    assert hasattr(obj._eager_grads, "lower")           # a jitted function
    for a, b in zip(eager, _grads(obj, score)):
        np.testing.assert_array_equal(np.asarray(a), b)

    rng = np.random.default_rng(3)                      # _rank_booster's data
    n = int(counts.sum())
    X = rng.normal(size=(n, 6))
    y = np.clip((X[:, 0] * 2 + rng.normal(size=n)) // 1.0, 0, 3)
    cfg = Config().set({**TEST_PARAMS, "objective": "lambdarank"})
    ds = TpuDataset(cfg).construct_from_matrix(
        X, Metadata(label=y.astype(np.float32), group=counts))
    obj2 = create_objective("lambdarank", cfg)
    obj2.init(ds.metadata, ds.num_data)
    new = GBDT()
    new.load_model_from_string(g.model_to_string())
    new.init_from_loaded(cfg, ds, obj2, [])
    before = np.asarray(g.predict_raw(X))
    new.refit_existing(1.0)                             # keeps every output
    np.testing.assert_allclose(np.asarray(new.predict_raw(X)), before,
                               atol=1e-6)
    new.refit_existing(0.0)                             # re-learns them all
    np.testing.assert_allclose(np.asarray(new.predict_raw(X)), before,
                               rtol=1e-4, atol=1e-5)


def test_windows_whose_class_tables_share_buckets_share_one_step():
    """Two windows of the same rows whose queries differ, class by class,
    inside the same pow2 buckets: one compile, the second a registry hit."""
    a = np.asarray([5, 12, 30, 60, 3, 9, 20, 100, 7, 14] * 10)      # 2600 rows
    b = np.asarray([6, 11, 31, 59, 4, 10, 19, 100, 8, 12] * 10)     # 2600 rows
    assert a.sum() == b.sum()
    s0 = step_cache.stats()
    g1 = _rank_booster(a, seed=1)
    s1 = step_cache.stats()
    g2 = _rank_booster(b, seed=2)
    s2 = step_cache.stats()
    assert g1._cache_eligible and len(g1.objective._pair_classes) > 1
    assert [c["lab"].shape for c in g1.objective._pair_classes] \
        == [c["lab"].shape for c in g2.objective._pair_classes]
    assert s1["misses"] - s0["misses"] >= 1
    assert s2["misses"] - s1["misses"] == 0 and s2["hits"] - s1["hits"] >= 1
    assert s2["compiles"] == s1["compiles"]
