"""Test configuration: force an 8-device virtual CPU platform so that
multi-chip sharding (data/feature/voting parallel learners) is exercised
in-process — fixing the reference's distributed-test gap (SURVEY.md §4.4:
the reference has no multi-node test at all).

Must run before jax is imported anywhere.
"""
import os

# The suite runs on the CPU backend (JAX_PLATFORMS is honoured by this
# installation): a developer shell without it must not grab — or fail
# to find — an accelerator. The chip is exercised by chip_smoke.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)
# No persistent compile cache here: ops/autotune.py ensure_compile_cache
# is the ONE place that sets jax_compilation_cache_dir, and it leaves
# the CPU backend alone unless JAX_COMPILATION_CACHE_DIR is set.
# NOTE: jax_disable_most_optimizations was evaluated for the
# compile-bound suite (compiles are ~60% of a typical engine-test
# slice even with the cross-booster step cache) and rejected: it
# halves compile time but de-optimizes the RUNTIME code so badly that
# iteration-heavy tests (DART replay, CV) dominate — the full suite
# got slower, not faster.

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Small standard shapes keep XLA compile time per distinct grower shape
# bounded; every test that can share a shape should use these.
TEST_PARAMS = {"num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 20}


def fit_gbdt(X, y, params, num_round=30, weight=None, group=None,
             valid=None):
    """Train a GBDT the low-level way (shared by many tests)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import TpuDataset, Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.metrics import create_metrics

    full = dict(TEST_PARAMS)
    full.update(params)
    cfg = Config().set(full)
    md = Metadata(label=y, weight=weight, group=group)
    ds = TpuDataset(cfg).construct_from_matrix(
        X, md, categorical=cfg.categorical_feature)
    obj = create_objective(cfg.objective, cfg)
    if obj is not None:
        obj.init(ds.metadata, ds.num_data)
    metrics = create_metrics(cfg.metric or [cfg.objective], cfg,
                             ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, metrics)
    if valid is not None:
        Xv, yv = valid
        vd = ds.create_valid(Xv, Metadata(label=yv))
        vm = create_metrics(cfg.metric or [cfg.objective], cfg,
                            vd.metadata, vd.num_data)
        g.add_valid_data(vd, vm)
    for _ in range(num_round):
        if g.train_one_iter():
            break
    g.finish_training()
    return g


def adopt_benchmark_tests(stem, into):
    """Run ``benchmark/tests/<stem>.py`` in tier-1: import that file by
    its path and put its tests and fixtures into ``into`` (the calling
    test module's ``globals()``), so pytest collects each case once,
    here, under this conftest. The benchmark's files are the yardstick
    and stay where they are, unedited; a program change that breaks what
    they read fails in this suite before any chip time is spent."""
    import importlib.util
    from pathlib import Path
    path = (Path(__file__).resolve().parent.parent / "benchmark" / "tests"
            / f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    into.update({k: v for k, v in vars(mod).items()
                 if k.startswith("test_")
                 or hasattr(v, "_fixture_function_marker")})

    @pytest.fixture(autouse=True)
    def _guards_count_from_zero():
        """A run's guards read these counters whole (a benchmark run is a
        process of its own); an earlier test file of this worker may have
        retried or failed a candidate on purpose."""
        from lightgbm_tpu.obs import registry as obs
        for name in ("retry/retries", "autotune/candidates_failed"):
            c = obs.counter(name)
            c.add(-c.value)
        yield
    into["_guards_count_from_zero"] = _guards_count_from_zero


@pytest.fixture(scope="session", autouse=True)
def _step_cache_suite_guard():
    """Regression guard for the compiled-step registry
    (ops/step_cache.py): a full suite run trains hundreds of boosters
    in one process, many with identical geometry — if the registry
    records plenty of misses but not a single hit, a closure
    re-capture regression has silently put every booster back on its
    own compile (the ~19 min PR-4 wall-clock). Small selections that
    train only a handful of boosters stay under the miss threshold and
    are exempt."""
    yield
    from lightgbm_tpu.ops import step_cache
    s = step_cache.stats()
    if s["enabled"] and s["misses"] > 20:
        assert s["hits"] > 0, (
            "step cache recorded %(misses)d compiles and ZERO hits "
            "across the suite — cross-booster step reuse has regressed "
            "(every booster is re-compiling its fused step)" % s)


@pytest.fixture
def lock_order():
    """Run a thread-hammer test with the runtime lock-order detector
    armed (lightgbm_tpu/analysis/lockorder.py): locks created inside
    the test via the named-lock factories are tracked, the known
    module-level locks are swapped for the window, and the test fails
    if the recorded acquisition graph has a cycle — "no deadlock yet"
    becomes a checked property of exactly the interleavings the
    hammer generates. Production pays nothing: detection is off
    everywhere else."""
    from lightgbm_tpu.analysis import lockorder
    with lockorder.detecting() as mon:
        yield mon
    mon.assert_acyclic()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def make_binary(n=1280, f=10, seed=0):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, f))
    logit = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] - 0.25 * X[:, 3]
    y = (logit + 0.1 * r.normal(size=n) > 0).astype(np.float32)
    return X, y


def make_regression(n=1280, f=10, seed=1):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, f))
    y = (2.0 * X[:, 0] + X[:, 1] ** 2 + 0.5 * X[:, 2] * X[:, 3]
         + 0.1 * r.normal(size=n)).astype(np.float32)
    return X, y


def make_multiclass(n=1280, f=10, k=4, seed=2):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, f))
    centers = r.normal(size=(k, f)) * 2.0
    d = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
    y = np.argmin(d + 0.5 * r.normal(size=(n, k)), axis=1).astype(np.float32)
    return X, y


def rank_auc(y, scores):
    """Hand-rolled Mann-Whitney AUC (no sklearn in the image)."""
    import numpy as np
    order = np.argsort(scores)
    ranks = np.empty(len(scores), dtype=np.float64)
    ranks[order] = np.arange(len(scores))
    pos = np.asarray(y) > 0.5
    npos, nneg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - npos * (npos - 1) / 2) / max(
        npos * nneg, 1)
