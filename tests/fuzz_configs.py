"""Randomized config fuzz harness (NOT collected by pytest — run
directly): train/predict/save/load across random parameter
combinations, asserting no crash, finite predictions, and exact
save->load parity.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python tests/fuzz_configs.py

Covers objective x boosting x bagging x feature_fraction x depth x
regularization x EFB x quantized-hist x tree_learner interactions that
the targeted test suite samples only pointwise. ~1 min/case on one CPU
core (XLA compiles dominate).
"""
import os, sys, traceback
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import numpy as np
import lightgbm_tpu as lgb

N_CASES = 70
fails = []

for case in range(N_CASES):
    r = np.random.default_rng(case)
    n = int(r.integers(300, 1200))
    f = int(r.integers(3, 10))
    X = r.normal(size=(n, f))
    has_cat = r.random() < 0.3
    if has_cat:
        X[:, 0] = r.integers(0, int(r.integers(3, 20)), n)
    obj = r.choice(["binary", "regression", "regression_l1", "huber",
                    "multiclass", "poisson", "quantile"])
    K = int(r.integers(2, 5)) if obj == "multiclass" else 1
    if obj == "binary":
        y = (X[:, 1] > 0).astype(np.float64)
    elif obj == "multiclass":
        y = np.clip(np.round(np.abs(X[:, 1]) * K / 2), 0, K - 1)
    elif obj == "poisson":
        y = np.round(np.abs(X[:, 1]) * 2)
    else:
        y = X[:, 1] * 1.5 + 0.3 * r.normal(size=n)
    params = {
        "objective": obj, "verbose": -1,
        "num_leaves": int(r.integers(3, 32)),
        "max_bin": int(r.choice([15, 63, 255])),
        "min_data_in_leaf": int(r.integers(1, 30)),
        "learning_rate": float(r.uniform(0.05, 0.4)),
        "max_depth": int(r.choice([-1, 3, 6])),
        "lambda_l1": float(r.choice([0.0, 0.5])),
        "lambda_l2": float(r.choice([0.0, 1.0])),
        "min_gain_to_split": float(r.choice([0.0, 0.1])),
        "boosting": str(r.choice(["gbdt", "gbdt", "dart", "goss"])),
        "bagging_fraction": float(r.choice([1.0, 0.7])),
        "bagging_freq": int(r.choice([0, 1, 3])),
        "feature_fraction": float(r.choice([1.0, 0.8])),
        "enable_bundle": bool(r.random() < 0.3),
        "tpu_quantized_hist": bool(r.random() < 0.3),
        # count-proxy / 4-bit packed tiers: auto vs forced-off (they
        # auto-engage under quant + serial/data + no-EFB/cat gates,
        # packed additionally at max_bin <= 16)
        "tpu_count_proxy": int(r.choice([-1, 0])),
        "tpu_packed_bins": int(r.choice([-1, 0])),
    }
    if obj == "multiclass":
        params["num_class"] = K
    if has_cat:
        params["categorical_feature"] = "0"
    if params["boosting"] == "goss":
        params["bagging_freq"] = 0
        params["bagging_fraction"] = 1.0
    if r.random() < 0.25:
        params["tree_learner"] = str(r.choice(["data", "voting"]))
    nrounds = int(r.integers(3, 12))
    tag = f"case{case} {obj} {params['boosting']} " \
          f"leaves={params['num_leaves']} bin={params['max_bin']} " \
          f"tl={params.get('tree_learner', 'serial')} " \
          f"efb={params['enable_bundle']} q={params['tpu_quantized_hist']}"
    try:
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.train(params, ds, nrounds, verbose_eval=False,
                        keep_training_booster=True)
        p = np.asarray(bst.predict(X))
        assert np.isfinite(p).all(), "non-finite predictions"
        s = bst.model_to_string()
        p2 = np.asarray(lgb.Booster(model_str=s).predict(X))
        assert np.abs(p - p2).max() < 1e-5, \
            f"save/load diff {np.abs(p - p2).max()}"
        lf = bst.predict(X[:64], pred_leaf=True)
        assert np.isfinite(lf).all()
    except Exception as e:
        fails.append((tag, repr(e)))
        print(f"FAIL {tag}: {e}", flush=True)
        traceback.print_exc()
    else:
        print(f"ok   {tag}", flush=True)

print(f"\n{N_CASES - len(fails)}/{N_CASES} passed", flush=True)
for t, e in fails:
    print("FAILED:", t, e)
sys.exit(1 if fails else 0)
