#!/usr/bin/env python
"""GPU-backend dry run — the Triton tier's counterpart of ``chip_smoke.py``.

Two phases, each recorded in the one-line verdict so the artifact
cannot drift from the test suite:

1. **Interpret-mode parity slice** (any backend): runs
   ``pytest -m gpu_tier`` in a fresh CPU-pinned subprocess — the
   bit-parity certificates of both GPU histogram kernels and the GPU
   forest kernel against their XLA oracles, the device-kind autotune
   arms, and the per-backend step-cache keying. "OK" here means the
   kernels are bit-correct wherever Pallas-Triton can lower.
2. **Native GPU smoke** (only when ``backend_kind() == "gpu"``): a
   real timed training run asserting the pallas-gpu route actually
   engaged (WaveGrowerConfig.route on the live booster), that a
   same-geometry retrain is a pure compiled-step registry hit, and
   that the persistent XLA compile cache (tpu_compile_cache auto-on
   for GPU) populated its directory. Skipped with the reason printed
   — device kind and the capability that gated it — on hosts without
   a GPU, mirroring bench.py --parity's recorded-skip contract.

Run from the repo root: ``python tools/dryrun_gpu.py``.
Exit 0 = every phase that could run passed; skips are not failures.
"""
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parity_slice() -> str:
    """pytest -m gpu_tier in a CPU-pinned subprocess (fresh jax: the
    parent may have initialized a GPU backend, the slice must not)."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "-q", "-m",
         "gpu_tier", "-p", "no:cacheprovider"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=1800)
    tail = (proc.stdout or "").strip().splitlines()[-1:] or ["(no out)"]
    if proc.returncode != 0:
        raise SystemExit(
            f"pytest -m gpu_tier failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return tail[0]


def _gpu_smoke() -> str:
    """Timed native smoke: route engagement + registry hit + compile
    cache population. Caller guarantees backend_kind() == 'gpu'."""
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.metrics import create_metrics
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import autotune, step_cache

    import jax
    autotune.ensure_compile_cache()            # auto-on for GPU
    cache_dir = jax.config.jax_compilation_cache_dir

    r = np.random.default_rng(0)
    X = r.normal(size=(4096, 8))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)

    def train():
        cfg = Config().set({"objective": "binary", "num_leaves": 15,
                            "max_bin": 63, "min_data_in_leaf": 5})
        ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
        obj = create_objective("binary", cfg)
        obj.init(ds.metadata, ds.num_data)
        mets = create_metrics(["auc"], cfg, ds.metadata, ds.num_data)
        g = GBDT()
        g.init(cfg, ds, obj, mets)
        for _ in range(5):
            g.train_one_iter()
        return g

    s0 = step_cache.stats()
    t0 = time.perf_counter()
    g1 = train()
    t1 = time.perf_counter()
    assert g1._grower_cfg.route == "pallas-gpu", (
        f"pallas-gpu route did not engage on a GPU backend "
        f"(route={g1._grower_cfg.route!r})")
    g2 = train()
    t2 = time.perf_counter()
    s2 = step_cache.stats()
    d = {k: s2[k] - s0[k] for k in ("hits", "misses")}
    assert d["hits"] >= 1, f"retrain must hit the step registry ({d})"
    cached = sum(len(fs) for _, _, fs in os.walk(cache_dir))
    assert cached > 0, (
        "persistent compile cache stayed empty on GPU — "
        "ensure_compile_cache policy regressed")
    assert np.allclose(np.asarray(g1.predict_raw(X[:256])),
                       np.asarray(g2.predict_raw(X[:256])))
    return (f"run1={t1 - t0:.2f}s run2={t2 - t1:.2f}s "
            f"registry(hits={d['hits']},misses={d['misses']}) "
            f"compile_cache_files={cached}")


def dryrun_gpu() -> None:
    try:
        from lightgbm_tpu.ops import autotune
    except ImportError:                # invoked from outside the repo
        sys.path.insert(0, REPO)
        from lightgbm_tpu.ops import autotune

    if not autotune.gpu_pallas_supported():
        print("dryrun_gpu: SKIP — jax.experimental.pallas.triton not "
              "importable; the pallas-gpu route is gated off and the "
              "parity slice has nothing to certify "
              f"[device_kind={autotune.device_kind()}]")
        return

    parity = _parity_slice()

    from lightgbm_tpu.utils.device import backend_kind
    if backend_kind() == "gpu":
        smoke = _gpu_smoke()
        print(f"dryrun_gpu: OK — parity slice: {parity}; "
              f"native smoke [{autotune.device_kind()}]: {smoke}")
    else:
        print(f"dryrun_gpu: OK — parity slice: {parity}; native smoke "
              f"SKIP — no GPU visible "
              f"[device_kind={autotune.device_kind()}]; interpret-mode "
              "bit-parity is the certificate that transfers")


if __name__ == "__main__":
    dryrun_gpu()
