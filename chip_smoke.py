#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that lightgbm_tpu still starts on
the chip.

Drives the main path once through the entry points a user calls —
``lightgbm_tpu.train`` / ``Booster.predict`` / ``save_model`` + reload,
and the fork's windowed retrain-and-score driver ``lightgbm_tpu.lrb.main``
— on ONE TPU, at the widths of the models the repo supports (HIGGS:
28 features, 255 leaves, 63 bins; LRB: 53 features, 31 leaves, 50
iterations per window), with rows as the depth that is cut. It checks
what comes out by the repo's own means (Pallas kernels against the XLA
oracles on the same device arrays, the forest kernel against host tree
traversal, holdout AUC, save/reload), asserts from the boosters' OWN
state that nothing stood in for the chip path (histogram route
``pallas-tpu`` and not interpreted, zero autotune candidates failed,
device ingest engaged, forest kernel taken, zero degraded LRB windows,
zero retries), and fails if any phase fails: there is no try/except
around a phase and no "skipped" that still prints ok.

    python chip_smoke.py             # one chip — what the driver runs
    python chip_smoke.py --chips 4   # ONLY the multi-chip path and what
                                     # it is compared with

Everything is one process (a chip belongs to one process at a time; the
script spawns nothing) and all data is generated from ``--seed``. With
no TPU it exits non-zero before training anything and prints no result.
The LAST line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Every other line is an observation (per-phase wall seconds, cold vs
second call, compile seconds): smoke observations, not a benchmark.
The phase functions take their sizes as an argument so that a scratch
script can rehearse them at toy sizes on the CPU backend
(``on_chip=False`` skips only the assertions that name the TPU); the
command line has no such switch.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import NamedTuple

import numpy as np


class Sizes(NamedTuple):
    """Row counts and iteration counts — the DEPTH of the run. Widths
    (features, leaves, bins, window-model shape) are never cut."""
    train_rows: int = 1_000_000        # HIGGS-width training set
    valid_rows: int = 100_000          # rides the step as passenger rows
    holdout_rows: int = 200_000        # predict + AUC floor
    iters: int = 10
    leaves: int = 255
    auc_floor: float = 0.85            # holdout AUC after `iters` rounds
                                       # (0.871 at 200k rows on the CPU route)
    oracle_rows: int = 65_536          # kernel-vs-oracle compare
    host_rows: int = 20_000            # forest kernel vs host traversal
    registry_rows: tuple = (10_000, 9_000)   # one 16384-row bucket
    registry_leaves: int = 63
    lrb_window: int = 100_000          # requests per window
    lrb_windows: int = 3
    lrb_sample: int = 3_600            # training rows drawn per window
    lrb_objects: int = 20_000
    multichip_iters: int = 5
    rank_queries: int = 4096           # lambdarank train; lengths 1..139


REAL = Sizes()

HIGGS_FEATURES = 28                    # bench.py:1-16, Experiments.rst
HIGGS_PARAMS = {"objective": "binary", "metric": "auc",
                "num_leaves": 255, "max_bin": 63, "learning_rate": 0.1,
                "min_data_in_leaf": 20, "verbose": 0}


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    """A smoke check: printed when it holds, fatal when it does not
    (a plain ``assert`` would vanish under ``python -O``)."""
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {what}")
    say(f"ok: {what}")


def counters() -> dict:
    from lightgbm_tpu.obs import registry as obs
    return dict(obs.default_registry().counter_items())


def moved(before: dict, name: str) -> int:
    return counters().get(name, 0) - before.get(name, 0)


def higgs_like(n_rows: int, seed: int):
    """HIGGS-shaped task (bench.py make_higgs_like): 28 continuous
    features, nonlinear boundary, balanced classes."""
    r = np.random.default_rng(seed)
    X = r.normal(size=(n_rows, HIGGS_FEATURES)).astype(np.float32)
    logit = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] - 0.3 * X[:, 3] * X[:, 4]
             + 0.2 * np.abs(X[:, 5]) + 0.1 * X[:, 6])
    y = (logit + 0.5 * r.normal(size=n_rows) > 0).astype(np.float32)
    return X, y


def auc(y, s) -> float:
    order = np.argsort(s, kind="stable")
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(len(s))
    pos = np.asarray(y) > 0.5
    npos, nneg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - npos * (npos - 1) / 2)
                 / max(npos * nneg, 1))


def trees_of(bst) -> str:
    """Model text minus the parameters block (knobs legitimately differ
    between compared runs)."""
    return bst.model_to_string().split("parameters:")[0]


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    """TPU or exit non-zero, before anything else."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"[smoke] no TPU: jax found platform {d.platform!r} "
              f"({d.device_kind}); refusing to run", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"[smoke] --chips {chips} needs {chips} local chips, jax "
              f"found {len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    import jaxlib
    from importlib import metadata
    say(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} "
        f"libtpu={metadata.version('libtpu')}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase: train (one tier)
# ---------------------------------------------------------------------------

def _fit(params, data, iters):
    import lightgbm_tpu as lgb
    X, y, Xv, yv = data
    dtrain = lgb.Dataset(X, label=y)
    dvalid = lgb.Dataset(Xv, label=yv, reference=dtrain)
    evals: dict = {}
    t0 = time.monotonic()
    bst = lgb.train(params, dtrain, num_boost_round=iters,
                    valid_sets=[dvalid], valid_names=["valid"],
                    evals_result=evals, verbose_eval=False,
                    keep_training_booster=True)
    # drain the dispatch queue so the wall covers the device work
    float(np.asarray(bst._gbdt.train_scores()[0, :1])[0])
    return bst, evals, time.monotonic() - t0


def phase_train(tier: str, data, sz: Sizes, on_chip: bool = True,
                sync_recheck: bool = False):
    """``lgb.train`` with a valid set at HIGGS widths; asserts from the
    booster's own state what it resolved to."""
    from lightgbm_tpu.ops import step_cache
    from lightgbm_tpu.utils import timing
    params = dict(HIGGS_PARAMS, num_leaves=sz.leaves)
    if tier == "proxy":
        params["tpu_quantized_hist"] = True
    c0, s0, a0 = counters(), step_cache.stats(), timing.seconds("autotune")
    bst, evals, cold = _fit(params, data, sz.iters)
    s1, a1 = step_cache.stats(), timing.seconds("autotune")
    rep = bst.device_report()
    say(f"train[{tier}] resolved: " + json.dumps(
        {k: v for k, v in rep.items() if k != "bins_shards"}))

    check(bst.current_iteration() == sz.iters,
          f"train[{tier}]: {sz.iters} iterations trained")
    vauc = evals["valid"]["auc"]
    check(len(vauc) == sz.iters and all(np.isfinite(vauc)),
          f"train[{tier}]: valid AUC per iteration finite "
          f"({vauc[0]:.4f} -> {vauc[-1]:.4f})")
    check(rep["learner_mode"] == "serial" and rep["num_devices"] == 1,
          f"train[{tier}]: serial learner on one device")
    if tier == "proxy":
        check(rep["precision"] == "int8" and rep["count_proxy"]
              and rep["wave_size"] == min(64, sz.leaves - 1),
              f"train[proxy]: int8 count-proxy tier engaged, "
              f"W={rep['wave_size']} waves")
    else:
        check(rep["precision"] == "highest"
              and rep["exact_variant"] in ("hilo5", "hilo4", "hilo3"),
              f"train[exact]: f32-grade hi/lo tier "
              f"({rep['exact_variant']}, W={rep['wave_size']})")
    failed = moved(c0, "autotune/candidates_failed")
    tuned = moved(c0, "autotune/tuned_keys")
    hits = moved(c0, "autotune/cache_hits")
    check(failed == 0, f"train[{tier}]: 0 autotune candidates failed")
    if on_chip:
        check(rep["platform"] == "tpu", f"train[{tier}]: booster on TPU")
        check(rep["route"] == "pallas-tpu" and rep["fused_pallas"]
              and not rep["interpret"],
              f"train[{tier}]: histogram route pallas-tpu, fused kernel, "
              f"interpret=False")
        check(tuned + hits >= 1,
              f"train[{tier}]: autotuner engaged ({tuned} key(s) timed, "
              f"{hits} served from the tuning cache), chunk="
              f"{rep['chunk']}")
        check(rep["device_ingest"]
              and moved(c0, "ingest/rows_device")
              >= sz.train_rows + sz.valid_rows
              and moved(c0, "ingest/h2d_bytes") > 0,
              f"train[{tier}]: device ingest engaged "
              f"({moved(c0, 'ingest/rows_device')} rows binned on "
              f"device, {moved(c0, 'ingest/h2d_bytes')} bytes h2d)")
    check(rep["step_cache_eligible"]
          and rep["score_rows"] >= rep["num_data"],
          f"train[{tier}]: step-registry eligible, "
          f"{rep['num_data']} rows in a {rep['score_rows']}-row bucket")

    # second call: same geometry -> registry hit, tuning-cache hit
    bst2, _, warm = _fit(params, data, sz.iters)
    s2 = step_cache.stats()
    check(s2["misses"] == s1["misses"] and s2["hits"] > s1["hits"],
          f"train[{tier}]: second call reused the compiled step "
          f"(registry +{s2['hits'] - s1['hits']} hit, +0 miss)")
    check(trees_of(bst2) == trees_of(bst),
          f"train[{tier}]: second call trained the identical model")
    say(f"train[{tier}] wall: cold {cold:.1f}s (autotune "
        f"{a1 - a0:.1f}s, step compile "
        f"{s1['compile_s'] - s0['compile_s']:.1f}s), second call "
        f"{warm:.1f}s — smoke observation, not a benchmark")
    if sync_recheck:
        phase_sync_recheck(bst2)
    return bst


def phase_sync_recheck(bst) -> None:
    """A transport-era workaround, re-observed on this backend: the
    code drains queues with a scalar readback because
    ``block_until_ready`` was once seen returning early. Queue three
    iterations, then time both in turn."""
    import jax
    g = bst._gbdt
    float(np.asarray(g._scores[0, :1])[0])     # compile the readback
    for _ in range(3):
        bst.update()
    t0 = time.monotonic()
    jax.block_until_ready(g._scores)
    t1 = time.monotonic()
    float(np.asarray(g._scores[0, :1])[0])
    t2 = time.monotonic()
    say(f"sync re-check: block_until_ready waited {t1 - t0:.3f}s for 3 "
        f"queued iterations; the scalar readback after it took "
        f"{1e3 * (t2 - t1):.2f} ms (a readback that had to wait for the "
        f"device would take as long as the first number)")


# ---------------------------------------------------------------------------
# phase: compare (Pallas kernels vs the XLA oracles, same device arrays)
# ---------------------------------------------------------------------------

def phase_compare(tier_report: dict, sz: Sizes, seed: int,
                  on_chip: bool = True) -> None:
    """The tier's kernels against plain routes that use no Pallas
    kernel, on the same device arrays, under the contract the CPU
    parity suite holds (tests/test_wave_ops.py, test_exact_tier.py):
    partition and counts bit-equal, f32-grade sums to 1e-4 of the
    largest bin (int8 sums exactly equal); then ONE tree grown by the
    wave grower on the Pallas route vs the two-pass XLA route — the
    same partition of the rows, the same splits."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_wave import (
        fused_partition_histogram_pallas, fused_partition_histogram_xla,
        wave_histogram_pallas, wave_histogram_xla)
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    rep = tier_report
    int8 = rep["precision"] == "int8"
    proxy = rep["count_proxy"]
    variant = rep["exact_variant"] or "hilo5"
    tier = "proxy" if proxy else "exact"
    interp = not on_chip
    N, B = sz.oracle_rows, 64
    F = HIGGS_FEATURES + (-HIGGS_FEATURES) % 8
    W = rep["wave_size"]
    # the fused kernel at the chunk the booster trains with (the very
    # program the tuner timed); the root kernel and the grower pair at a
    # small chunk — legality and arithmetic do not change with the
    # chunk, Mosaic compile time does
    chunk = min(rep["chunk"], N)
    small_chunk = min(chunk, 4096)
    r = np.random.default_rng(seed)
    bins = np.zeros((F, N), np.uint8)
    bins[:HIGGS_FEATURES] = r.integers(0, 63, (HIGGS_FEATURES, N))
    if int8:
        g = r.integers(-127, 128, N).astype(np.float32)
        h = r.integers(0, 128, N).astype(np.float32)
        scale = (0.01, 0.02)
    else:
        g = r.normal(size=N).astype(np.float32)
        h = r.uniform(0.05, 0.25, N).astype(np.float32)
        scale = None
    mask = (r.random(N) < 0.8).astype(np.float32)
    g, h = g * mask, h * mask
    n_leaves = 2 * W
    leaf = r.integers(0, n_leaves, N).astype(np.int32)
    # a wave splitting leaves 0..W-1 into (k, n_leaves + k) at mid-bin
    wl = np.arange(W, dtype=np.int32)
    new_ids = (n_leaves + wl).astype(np.int32)
    feat = r.integers(0, HIGGS_FEATURES, W).astype(np.int32)
    tbin = r.integers(8, 55, W).astype(np.int32)
    zeros = np.zeros(W, np.int32)
    small = np.where(r.random(W) < 0.5, wl, new_ids).astype(np.int32)
    tbl = np.concatenate([np.stack([
        wl, new_ids, feat, tbin, zeros, zeros, zeros,
        np.full(W, 63, np.int32), small, zeros]),
        np.zeros((8, W), np.int32)])
    d = {k: jnp.asarray(v) for k, v in dict(
        bins=bins, g=g, h=h, mask=mask, leaf=leaf, tbl=tbl, wl=wl,
        new_ids=new_ids, feat=feat, tbin=tbin, small=small).items()}
    zb = jnp.zeros(W, bool)

    t0 = time.monotonic()
    out = fused_partition_histogram_pallas(
        d["bins"], d["g"], d["h"], d["mask"], d["leaf"], d["tbl"],
        num_bins=B, chunk=chunk, interpret=interp,
        precision=rep["precision"], gh_scale=scale, any_cat=False,
        count_proxy=proxy, variant=variant)
    ref = fused_partition_histogram_xla(
        d["bins"], d["g"], d["h"], d["mask"], d["leaf"], d["wl"],
        d["new_ids"], d["feat"], d["tbin"], zb, zb,
        jnp.zeros((W, 8), jnp.int32), d["small"], jnp.zeros(W, jnp.int32),
        jnp.zeros(W, jnp.int32), jnp.full(W, 63, jnp.int32), num_bins=B,
        count_proxy=proxy, gh_scale=scale)
    leaf_p, hist_p = np.asarray(out[0]), np.asarray(out[1])
    leaf_x, hist_x = np.asarray(ref[0]), np.asarray(ref[1])
    check(np.array_equal(leaf_p, leaf_x),
          f"compare[{tier}]: fused kernel partitions {N} rows exactly "
          f"like the XLA oracle")
    nch = hist_p.shape[-1]              # proxy kernel: 2 channels
    _hist_close(hist_p, hist_x[..., :nch], int8, f"compare[{tier}]: "
                f"fused kernel histograms [W={W}, F={F}, B={B}]")
    if proxy:
        check(np.array_equal(np.asarray(out[2]), np.asarray(ref[2])),
              f"compare[{tier}]: exact per-slot moved-row counts equal")

    wave = np.asarray(wave_histogram_pallas(
        d["bins"], d["g"], d["h"],
        jnp.where(d["mask"] > 0, d["leaf"], -1), d["wl"], num_bins=B,
        chunk=small_chunk, interpret=interp, precision=rep["precision"],
        gh_scale=scale, count_proxy=proxy, variant=variant))
    wave_x = np.asarray(wave_histogram_xla(
        d["bins"], d["g"], d["h"],
        jnp.where(d["mask"] > 0, d["leaf"], -1), d["wl"], num_bins=B))
    if int8:
        wave_x = wave_x * np.asarray([scale[0], scale[1], 1.0],
                                     np.float32)
    _hist_close(wave, wave_x[..., :wave.shape[-1]], int8,
                f"compare[{tier}]: wave (root) kernel histograms")
    if not int8:
        _root_kernel_checks(d, r, tier=tier, trained=variant, B=B,
                            chunk=small_chunk, interp=interp)
        _wave_split_checks(d, r, tier=tier, trained=variant, B=B,
                           chunk=small_chunk, interp=interp)

    # one tree through the whole grower at the trained wave width:
    # Pallas route vs the two-pass XLA route. Depth is held to 4 on a
    # target with six informative features, so every split has a clear
    # winner: the two routes' f32 gains differ in their last digits
    # (hi/lo MXU sums vs scatter order), and deep in a 255-leaf tree
    # near-equal gains of DIFFERENT leaves swap ranks inside a wave —
    # a relabelled tree, or at the leaf budget a different one — which
    # says nothing about the kernels (seen on the chip: rank swap at
    # split 73 of 254, gains 0.30359 vs 0.30363). The trees are compared
    # as PARTITIONS, which is blind to numbering.
    L = 2 * W + 1
    hp = SplitParams(min_data_in_leaf=20.0, has_cat=False,
                     count_lb=proxy)
    meta = FeatureMeta(
        num_bin=np.concatenate([np.full(HIGGS_FEATURES, 63, np.int32),
                                np.ones(F - HIGGS_FEATURES, np.int32)]),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        monotone=np.zeros(F, np.int32),
        penalty=np.ones(F, np.float32), is_cat=np.zeros(F, np.int32))
    base = dict(num_leaves=L, num_bins=B, wave_size=W, max_depth=4,
                hp=hp, precision=rep["precision"],
                exact_variant=variant, count_proxy=proxy)
    if on_chip:
        fast = make_wave_grower(WaveGrowerConfig(
            chunk=small_chunk, route="pallas-tpu", **base), meta)
    else:       # rehearsal: the same fused kernel, interpreted
        fast = make_wave_grower(WaveGrowerConfig(
            chunk=small_chunk, fused=True, **base), meta)
    plain = make_wave_grower(WaveGrowerConfig(
        fused=False, route="two-pass", **base), meta)
    z = (bins[:6].astype(np.float32) - 31.0) / 18.0
    logit = (z[0] * z[1] + 0.5 * z[2] - 0.3 * z[3] * z[4]
             + 0.2 * np.abs(z[5]))
    y = (logit + 0.3 * r.normal(size=N) > 0).astype(np.float32)
    grad = jnp.asarray(0.5 - y)
    hess = jnp.full(N, 0.25, jnp.float32)
    fmask = jnp.asarray(np.arange(F) < HIGGS_FEATURES)
    rec_f, leaf_f = fast(d["bins"], grad, hess, d["mask"], fmask)
    rec_x, leaf_x = plain(d["bins"], grad, hess, d["mask"], fmask)
    check(fast.resolved["fused_pallas"]
          and fast.resolved["interpret"] == interp
          and not plain.resolved["fused_pallas"]
          and not plain.resolved["fused_xla"],
          f"compare[{tier}]: grower routes are the fused Pallas kernel "
          f"(interpret={interp}) vs two-pass XLA (no Pallas)")
    nl = int(rec_f.num_leaves)
    lf, lx = np.asarray(leaf_f), np.asarray(leaf_x)
    pairs = np.unique(np.stack([lf, lx]), axis=1)
    check(nl == int(rec_x.num_leaves) > 8 and pairs.shape[1] == nl,
          f"compare[{tier}]: one depth-4, {nl}-leaf tree grown on the "
          f"Pallas route partitions all {N} rows exactly like the XLA "
          f"route's (leaf sets in bijection)")

    def splits(rec):
        return sorted(zip(np.asarray(rec.split_feature)[:nl - 1].tolist(),
                          np.asarray(rec.split_bin)[:nl - 1].tolist()))

    check(splits(rec_f) == splits(rec_x),
          f"compare[{tier}]: the same {nl - 1} (feature, bin) splits")
    dv = float(np.abs(np.asarray(rec_f.leaf_output)[pairs[0]]
                      - np.asarray(rec_x.leaf_output)[pairs[1]]).max())
    check(dv <= 1e-4, f"compare[{tier}]: leaf outputs within 1e-4 "
          f"(max |diff| {dv:.2e})")
    say(f"compare[{tier}] wall: {time.monotonic() - t0:.1f}s")


def _root_kernel_checks(d, r, *, tier, trained, B, chunk, interp) -> None:
    """The root pass's kernel of its own (a two-digit split of the bin
    axis; the bf16 tiers) against the wave kernel's slot 0 with one live
    leaf, bit for bit, on the device's own bf16 operands and
    DEFAULT-precision dot (the CPU suite interprets both in f32): every
    layout the grower's predicate sends there (hilo5, hilo4 whose counts
    ride a fifth row where the wave kernel runs a second dot, hilo3,
    plain bf16), at the smoke's 64 bins (16 high digits, eight features
    a dot) and at 255 (32 high digits, four a dot), and at a chunk the
    root kernel halves."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_wave import (root_histogram_pallas,
                                            wave_histogram_pallas)
    F, N = d["bins"].shape
    in_root = jnp.where(d["mask"] > 0, 0, -1)
    one_slot = jnp.where(d["wl"] == 0, 0, -1)
    wide = jnp.asarray(r.integers(0, 255, (F, N)).astype(np.uint8))
    layouts = {"hilo5": dict(precision="highest", variant="hilo5"),
               "hilo4": dict(precision="highest", variant="hilo4"),
               "hilo3": dict(precision="highest", variant="hilo3"),
               "bf16": dict(precision="default", variant=None)}

    def pair(bins, nb, layout, root_chunk, wave_chunk):
        # hilo3's gate: the hessian IS the bag mask
        h = d["mask"] if layout == "hilo3" else d["h"]
        kw = dict(num_bins=nb, interpret=interp, **layouts[layout])
        root = np.asarray(root_histogram_pallas(
            bins, d["g"], h, in_root, chunk=root_chunk, **kw))
        slot0 = np.asarray(wave_histogram_pallas(
            bins, d["g"], h, in_root, one_slot, chunk=wave_chunk,
            **kw))[:1]
        return root, slot0

    for layout in layouts:
        for bins, nb in ((d["bins"], B), (wide, 255)):
            root, slot0 = pair(bins, nb, layout, chunk, chunk)
            check(root.shape == (1, F, nb, 3)
                  and np.array_equal(root, slot0),
                  f"compare[{tier}]: root kernel [1, F={F}, B={nb}, 3] "
                  f"({layout}{', trained' if layout == trained else ''}) "
                  f"equals the wave kernel's slot 0 bit for bit")
    # asked for 32768 rows a step, 5 channels x 256 bins walk 16384
    # (autotune.root_hist_tiling): the wave kernel's sums AT 16384
    root, slot0 = pair(wide, 255, "hilo5", 32768, 16384)
    check(np.array_equal(root, slot0),
          f"compare[{tier}]: root kernel asked 32768 rows a step equals "
          f"the wave kernel's slot 0 at 16384 bit for bit")


def _wave_split_checks(d, r, *, tier, trained, B, chunk, interp) -> None:
    """The fused kernel's flush by slot (the staged rows in slot order,
    each 128-row block dotted against the slots it holds by the root
    kernel's two digits; the compacting bf16 tiers) against its one-hot
    dot over the same staged rows, on the device's own bf16 operands:
    the same partition, counts bit-equal, sums to 1e-4 of the largest
    bin (the same products, added in another order), and at least one
    (block, slot) pair a block; under the four layouts, at the smoke's
    64 bins (eight features a dot) and at 255 (four), one resident
    block and two feature tiles of 32 rows."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_wave import fused_partition_histogram_pallas
    F, N = d["bins"].shape
    W = min(int(d["tbl"].shape[1]), 24)       # every layout's cap
    tbl = d["tbl"][:, :W]
    wide = jnp.asarray(r.integers(0, 255, (2 * F, N)).astype(np.uint8))
    layouts = {"hilo5": dict(precision="highest", variant="hilo5"),
               "hilo4": dict(precision="highest", variant="hilo4"),
               "hilo3": dict(precision="highest", variant="hilo3"),
               "bf16": dict(precision="default")}
    for layout, kw in layouts.items():
        # hilo3's gate: the hessian IS the bag mask
        h = d["mask"] if layout == "hilo3" else d["h"]
        for bins, nb, tile in ((d["bins"], B, None), (wide, 255, None),
                               (wide, 255, 32)):
            by_slot, one_hot = (fused_partition_histogram_pallas(
                bins, d["g"], h, d["mask"], d["leaf"], tbl, num_bins=nb,
                chunk=chunk, interpret=interp, any_cat=False, compact=True,
                feature_tile=tile, split=split, **kw)
                for split in (True, False))
            what = (f"compare[{tier}]: flush by slot [W={W}, "
                    f"F={bins.shape[0]}, B={nb}] ({layout}"
                    f"{', trained' if layout == trained else ''}"
                    f"{', two feature tiles' if tile else ''})")
            work = np.asarray(by_slot[2])
            check(np.array_equal(np.asarray(by_slot[0]),
                                 np.asarray(one_hot[0]))
                  and 0 < work[1] <= work[2] < work[1] + 16 * W,
                  what + f": the one-hot dot's partition; {work[2]} "
                  f"pairs over {work[1]} blocks")
            _hist_close(np.asarray(by_slot[1]), np.asarray(one_hot[1]),
                        False, what)


def _hist_close(got, ref, int8: bool, what: str) -> None:
    if int8:
        check(np.array_equal(got, ref), what + " equal the XLA "
              "oracle's exactly (integer sums)")
        return
    check(np.array_equal(got[..., 2], ref[..., 2]),
          what + ": counts bit-equal")
    tol = 1e-4 * max(float(np.abs(ref).max()), 1.0)
    err = float(np.abs(got - ref).max())
    check(err <= tol, what + f": g/h sums within 1e-4 of the largest "
          f"bin (max |diff| {err:.3e} <= {tol:.3e})")


# ---------------------------------------------------------------------------
# phase: predict
# ---------------------------------------------------------------------------

def phase_predict(bst, holdout, sz: Sizes, tag: str,
                  on_chip: bool = True, full: bool = True) -> None:
    """``Booster.predict`` through the fused forest kernel vs host tree
    traversal (models/tree.py); holdout AUC floor; with ``full`` also
    pred_leaf / pred_contrib on a slice and save_model -> reload."""
    import lightgbm_tpu as lgb
    Xh, yh = holdout
    c0 = counters()
    t0 = time.monotonic()
    p = bst.predict(Xh)
    cold = time.monotonic() - t0
    t0 = time.monotonic()
    p2 = bst.predict(Xh)
    warm = time.monotonic() - t0
    check(p.shape == (len(Xh),) and np.isfinite(p).all()
          and p.min() >= 0.0 and p.max() <= 1.0,
          f"predict[{tag}]: {len(Xh)} finite probabilities")
    check(np.array_equal(p, p2), f"predict[{tag}]: second call identical")
    a = auc(yh, p)
    check(a >= sz.auc_floor, f"predict[{tag}]: holdout AUC {a:.4f} >= "
          f"floor {sz.auc_floor} after {bst.current_iteration()} "
          f"iterations")
    if on_chip:
        check(moved(c0, "predict/forest_kernel_calls") >= 2
              and moved(c0, "predict/forest_surrenders") == 0,
              f"predict[{tag}]: fused forest kernel taken "
              f"({moved(c0, 'predict/forest_kernel_calls')} compiled-"
              f"kernel dispatches, 0 surrenders to the XLA scan)")
        check(moved(c0, "autotune/candidates_failed") == 0,
              f"predict[{tag}]: 0 forest-tile candidates failed")
    g = bst._gbdt
    g._ensure_host_trees()
    Xs = Xh[:sz.host_rows].astype(np.float64)
    host = np.zeros(len(Xs), np.float64)
    for t in g.models:
        host += t.predict(Xs)
    raw = bst.predict(Xs, raw_score=True)
    err = float(np.abs(raw - host).max())
    check(err <= 1e-5, f"predict[{tag}]: device raw scores == host tree "
          f"traversal on {len(Xs)} rows (max |diff| {err:.2e} <= 1e-5)")
    say(f"predict[{tag}] wall: cold {cold:.1f}s, second call "
        f"{warm:.2f}s for {len(Xh)} rows x {bst.num_trees()} trees — "
        f"smoke observation, not a benchmark")
    if not full:
        return
    Xl = Xs[:2048]
    leaves = bst.predict(Xl, pred_leaf=True)
    host_l = np.stack([t.predict_leaf_index(Xl) for t in g.models], 1)
    check(leaves.shape == host_l.shape and np.array_equal(leaves, host_l),
          f"predict[{tag}]: pred_leaf == host traversal "
          f"{tuple(leaves.shape)}")
    Xc = Xs[:4]           # TreeSHAP is a per-row python recursion
    contrib = bst.predict(Xc, pred_contrib=True)
    cerr = float(np.abs(contrib.sum(axis=1) - host[:4]).max())
    check(contrib.shape == (4, HIGGS_FEATURES + 1) and cerr <= 1e-5,
          f"predict[{tag}]: pred_contrib rows sum to the raw score "
          f"(max |diff| {cerr:.2e})")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        path = os.path.join(td, "model.txt")
        bst.save_model(path)
        again = lgb.Booster(model_file=path)
        pr = again.predict(Xs)
    check(np.array_equal(pr, p[:len(Xs)]),
          f"predict[{tag}]: save_model -> reload -> identical "
          f"predictions on {len(Xs)} rows")


# ---------------------------------------------------------------------------
# phase: the source workload (windowed retrain-and-score)
# ---------------------------------------------------------------------------

def write_trace(path: str, n_requests: int, n_objects: int,
                seed: int) -> int:
    """Zipf-popular objects with heavy-tailed sizes: ``seq id size
    cost`` per line, the reference binary's trace format. Returns the
    mean request size in bytes (to size the cache against)."""
    r = np.random.default_rng(seed)
    ranks = np.arange(1, n_objects + 1)
    p = (1.0 / ranks ** 0.9)
    ids = r.choice(n_objects, size=n_requests, p=p / p.sum())
    sizes = (2 ** r.integers(6, 18, n_objects)).astype(np.int64)
    with open(path, "w") as fh:
        fh.write("\n".join(
            f"{i + 1} {oid} {sizes[oid]} 1" for i, oid in enumerate(ids)))
        fh.write("\n")
    return int(sizes[ids].mean())


def phase_lrb(sz: Sizes, seed: int, on_chip: bool = True) -> None:
    """``lightgbm_tpu.lrb.main`` in-process on a generated trace: 53
    features, 31 leaves, 50 iterations per window (SURVEY.md §0)."""
    from lightgbm_tpu import lrb
    from lightgbm_tpu.ops import step_cache
    from lightgbm_tpu.utils import timing
    n_req = sz.lrb_window * sz.lrb_windows + 1
    c0, s0, a0 = counters(), step_cache.stats(), timing.seconds("autotune")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        trace, result = (os.path.join(td, "trace.txt"),
                         os.path.join(td, "result.txt"))
        mean_size = write_trace(trace, n_req, sz.lrb_objects, seed)
        # a cache of ~5% of one window's bytes: OPT admits some
        # requests and refuses others, so every window has both labels
        cache_bytes = mean_size * sz.lrb_window // 20
        t0 = time.monotonic()
        # argv of the reference binary: tracePath cacheSize windowSize
        # sampleSize cutoff sampling [resultFile]; sampling=2 draws a
        # uniform-random sample, so every window trains a different row
        # count inside one shape bucket
        lrb.main([trace, str(cache_bytes), str(sz.lrb_window),
                  str(sz.lrb_sample), "0.5", "2", result])
        wall = time.monotonic() - t0
        with open(result) as fh:
            lines = [ln.rstrip("\n") for ln in fh if ln.strip()]
    s1, a1 = step_cache.stats(), timing.seconds("autotune")
    for ln in lines:
        say("lrb| " + ln[:300])
    degraded = moved(c0, "lrb/windows_degraded")
    if degraded:
        reasons = {k: v - c0.get(k, 0) for k, v in counters().items()
                   if k.startswith("lrb/degraded_reason/")
                   and v - c0.get(k, 0)}
        say(f"lrb degraded windows: {degraded}, reasons {reasons} "
            f"(each window's degrade_reason is in the lines above)")
    check(moved(c0, "lrb/windows_total") == sz.lrb_windows,
          f"lrb: {sz.lrb_windows} windows of {sz.lrb_window} requests "
          f"processed")
    check(degraded == 0, "lrb: 0 degraded windows")
    check(moved(c0, "lrb/windows_failed") == 0, "lrb: 0 failed windows")
    misses = s1["misses"] - s0["misses"]
    hits = s1["hits"] - s0["hits"]
    check(hits >= 1 and hits + misses == sz.lrb_windows,
          f"lrb: later windows hit the step registry (+{hits} hit, "
          f"+{misses} miss over {sz.lrb_windows} windows)")
    check(moved(c0, "autotune/candidates_failed") == 0,
          "lrb: 0 autotune candidates failed")
    if on_chip:
        check(moved(c0, "predict/forest_kernel_calls") >= 1
              and moved(c0, "predict/forest_surrenders") == 0,
              "lrb: window scoring took the fused forest kernel")
    say(f"lrb wall: {wall:.1f}s for {n_req} requests (autotune "
        f"{a1 - a0:.1f}s, step compile "
        f"{s1['compile_s'] - s0['compile_s']:.1f}s) — smoke "
        f"observation, not a benchmark")


# ---------------------------------------------------------------------------
# phase: lambdarank (the pair gradient laid out by query length)
# ---------------------------------------------------------------------------

def phase_rank(sz: Sizes, seed: int, on_chip: bool = True) -> None:
    """The program's lambdas and hessians against the benchmark's plain
    reference (``benchmark/reference_rank.py``) on 64 queries of lengths
    1..139 at all-equal, random and tied scores; then a three-tree
    lambdarank train through ``Dataset(group=)`` on the kernels' route."""
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import create_objective
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    import reference_rank
    r = np.random.default_rng(seed + 11)
    counts = np.concatenate([[1, 2, 7, 8, 9, 64, 139], np.clip(np.rint(np.exp(
        2.2 + r.standard_normal(57))), 1, 139)]).astype(np.int64)
    n = int(counts.sum())
    lab = r.integers(0, 5, n)
    obj = create_objective("lambdarank",
                           Config().set({"objective": "lambdarank"}))
    obj.init(Metadata(label=lab.astype(np.float32), group=counts), n)
    fn = jax.jit(obj.gradient_builder())
    ref = reference_rank.RankGrads(lab.astype(np.float32), counts,
                                   {"sigmoid": 1.0, "max_position": 20})
    worst = 0.0
    for score in (np.zeros(n, np.float32),
                  r.standard_normal(n).astype(np.float32),
                  (r.integers(0, 4, n) * 0.25).astype(np.float32)):
        g, h = (np.asarray(a) for a in fn(jnp.asarray(score),
                                          obj.gradient_aux()))
        gh = ref.at(jnp.asarray(score))
        worst = max(worst,
                    float(np.abs(g - gh[:, 0]).max() / np.abs(gh[:, 0]).max()),
                    float(np.abs(h - gh[:, 1]).max() / np.abs(gh[:, 1]).max()))
    check(len(obj._pair_classes) > 1 and worst <= 1e-5,
          f"rank: lambdas and hessians of {len(counts)} queries of 1..139 "
          f"documents in {len(obj._pair_classes)} width classes match the "
          f"plain reference (widest gap {worst:.2e} of the largest, limit "
          f"1e-5: float32 sums in another order, exp and log2 of the chip)")
    counts = np.clip(np.rint(np.exp(2.78 + 0.9 * r.standard_normal(
        sz.rank_queries))), 1, 139).astype(np.int64)
    X, y = higgs_like(int(counts.sum()), seed + 12)
    grades = np.clip(np.rint(X[:, 0] * X[:, 1] + X[:, 2] + 1.5), 0,
                     4).astype(np.float32)
    params = dict(HIGGS_PARAMS, objective="lambdarank", metric="ndcg",
                  num_leaves=sz.leaves, min_data_in_leaf=0,
                  min_sum_hessian_in_leaf=10.0)
    bst = lgb.Booster(params, lgb.Dataset(X, label=grades, group=counts,
                                          params=dict(params)))
    for _ in range(3):
        bst.update()
    rep = bst.device_report()
    if on_chip:
        check(rep["route"] == "pallas-tpu" and rep["fused_pallas"]
              and not rep["interpret"],
              f"rank: trained on the Mosaic route ({rep['route']})")
    scores = np.asarray(bst._gbdt.train_scores()[0])[:len(grades)]
    ref = reference_rank.RankGrads(grades, counts, {})
    nd = ref.ndcg(jnp.asarray(np.stack([np.zeros_like(scores), scores])))
    check(bst.current_iteration() == 3 and np.all(np.isfinite(scores))
          and nd[1] > nd[0] + 0.05,
          f"rank: three lambdarank trees over {len(counts)} queries "
          f"({len(grades)} rows) raise NDCG@10 {nd[0]:.4f} -> {nd[1]:.4f}")


# ---------------------------------------------------------------------------
# phase: the step registry's promise
# ---------------------------------------------------------------------------

def phase_registry(sz: Sizes, seed: int) -> None:
    """Two boosters whose row counts fall in one bucket share one
    compiled step, and the bucketed model equals the exact-shape one
    (the row axis and the bin axis pad: 63 observed bins ride a 64-bin
    bucket) — the property that lets every booster share one step."""
    from lightgbm_tpu.ops import step_cache
    n_a, n_b = sz.registry_rows
    params = dict(HIGGS_PARAMS, num_leaves=sz.registry_leaves)
    X, y = higgs_like(n_a + 4096, seed + 7)
    va = (X[n_a:], y[n_a:])

    def fit(n, **extra):
        return _fit(dict(params, **extra), (X[:n], y[:n]) + va, 5)[0]

    s0 = step_cache.stats()
    a = fit(n_a)
    s1 = step_cache.stats()
    b = fit(n_b)
    s2 = step_cache.stats()
    ra, rb = a.device_report(), b.device_report()
    check(ra["score_rows"] == rb["score_rows"] > n_a,
          f"registry: {n_a} and {n_b} rows land in one "
          f"{ra['score_rows']}-row bucket")
    check(s1["misses"] - s0["misses"] == 1
          and s2["misses"] == s1["misses"] and s2["hits"] > s1["hits"],
          "registry: the second booster reused the first one's compiled "
          "step (+0 miss)")
    exact = fit(n_b, tpu_row_bucket=0)
    s3 = step_cache.stats()
    re_ = exact.device_report()
    # (on the TPU rows also pad to the kernel chunk, so the two row
    # counts can coincide; the bin axis always differs)
    check(s3["misses"] - s2["misses"] == 1
          and re_["num_bins"] < rb["num_bins"]
          and re_["score_rows"] <= rb["score_rows"],
          f"registry: the exact-shape run compiled its own program "
          f"({re_['score_rows']} rows x {re_['num_bins']} bins vs the "
          f"{rb['score_rows']}-row x {rb['num_bins']}-bin bucket)")
    same = trees_of(b) == trees_of(exact)
    pb, pe = b.predict(X[:4096], raw_score=True), \
        exact.predict(X[:4096], raw_score=True)
    diff = float(np.abs(pb - pe).max())
    say(f"registry: bucketed model == exact-shape model bit-for-bit: "
        f"{same} (max |raw score diff| {diff:.3e})")
    # the promise is bit-parity; what MUST hold even if a backend's
    # reductions regroup under padding is the f32 tolerance
    check(same or diff <= 1e-4,
          "registry: bucketed vs exact-shape model "
          + ("identical" if same else f"within 1e-4 (NOT bit-equal — "
             f"a finding, see CHANGES.md)"))


# ---------------------------------------------------------------------------
# --chips 4: the multi-chip path and what it is compared with
# ---------------------------------------------------------------------------

def phase_multichip(sz: Sizes, seed: int, chips: int = 4,
                    on_chip: bool = True) -> None:
    """``tree_learner=data`` over the local chips vs the serial model
    on ONE of them, in this process, under the contract the CPU suite
    holds (tests/test_multichip.py): f32 within 1e-5, quantized psum
    equal to single-chip quantized."""
    X, y = higgs_like(sz.train_rows + sz.valid_rows, seed)
    data = (X[:sz.train_rows], y[:sz.train_rows],
            X[sz.train_rows:], y[sz.train_rows:])
    Xp = X[sz.train_rows:sz.train_rows + 4096]
    # the tile and the exact-tier layout are pinned: this phase is about
    # the mesh, the shards and the collective — the tuner is the
    # one-chip run's business, and on four chips every compiled
    # candidate is charged four times. Growth is stopped by
    # min_data_in_leaf (at most leaves/2 of them) before the leaf budget
    # can bind, and noise-level gains are gated out: the cross-chip f32 psum
    # regroups sums, near-equal gains of different leaves then swap
    # ranks inside a wave, and AT the budget a swapped rank is a
    # different tree — which would make the 1e-5 contract a coin toss
    # that says nothing about the collective.
    base = dict(HIGGS_PARAMS, num_leaves=sz.leaves, tpu_hist_chunk=8192,
                tpu_exact_tier="hilo4",
                min_data_in_leaf=max(20, sz.train_rows
                                     // (sz.leaves // 2)),
                min_gain_to_split=1e-3)
    models = {}
    for quant in (False, True):
        tier = "int8+psum" if quant else "f32"
        for learner in ("serial", "data"):
            c0 = counters()
            params = dict(base, tree_learner=learner)
            if quant:
                params["tpu_quantized_hist"] = True
                if learner == "data":
                    params["tpu_quantized_psum"] = 1
            bst, evals, wall = _fit(params, data, sz.multichip_iters)
            rep = bst.device_report()
            models[tier, learner] = bst
            say(f"multichip[{tier}/{learner}] resolved: " + json.dumps(
                rep) + f" wall {wall:.1f}s")
            check(moved(c0, "autotune/candidates_failed") == 0,
                  f"multichip[{tier}/{learner}]: 0 autotune candidates "
                  f"failed")
            if on_chip:
                check(rep["platform"] == "tpu"
                      and rep["route"] == "pallas-tpu"
                      and rep["fused_pallas"] and not rep["interpret"],
                      f"multichip[{tier}/{learner}]: route pallas-tpu, "
                      f"fused kernel per shard, interpret=False")
            if learner == "serial":
                check(bst.num_devices == 1
                      and bst.learner_mode == "serial",
                      f"multichip[{tier}/serial]: one chip")
                continue
            check(bst.num_devices == chips
                  and bst.learner_mode == "data"
                  and moved(c0, "learner/serial_fallbacks") == 0,
                  f"multichip[{tier}/data]: num_devices == {chips}, "
                  f"learner_mode == 'data', no serial fallback")
            shards = rep["bins_shards"]
            devs = {d for d, _ in shards}
            widths = [s[1] for _, s in shards]
            per = rep["bins_shape"][1] // chips
            check(len(shards) == chips and len(devs) == chips
                  and all(w == per for w in widths)
                  and per >= sz.train_rows // chips,
                  f"multichip[{tier}/data]: bin matrix "
                  f"{rep['bins_shape']} lives as {chips} shards of "
                  f"{per} rows on {chips} distinct devices "
                  f"({sorted(devs)})")
            if quant:
                check(rep["quant_psum"]
                      and rep["psum_wire"].startswith("int"),
                      f"multichip[{tier}/data]: integer psum wire "
                      f"({rep['psum_wire']})")
            txt = bst._gbdt.lower_step().compile().as_text()
            check("all-reduce" in txt,
                  f"multichip[{tier}/data]: the compiled step contains "
                  f"an all-reduce ({txt.count('all-reduce')} mentions, "
                  f"{txt.count('tpu_custom_call')} Mosaic kernels)")
            if on_chip:
                check("tpu_custom_call" in txt,
                      f"multichip[{tier}/data]: ... and Mosaic kernels")
        ser, par = models[tier, "serial"], models[tier, "data"]
        if not quant:
            # higgs_like's matrix is float32 and goes in as it is
            # (basic.keeps_float32): the shards' bins are the one chip's
            n = sz.train_rows
            check(bool(np.array_equal(
                np.asarray(par._gbdt._bins_dev)[:, :n],
                np.asarray(ser._gbdt._bins_dev)[:, :n])),
                f"multichip: float32 sharded bins == one-chip bins "
                f"({n} rows x {HIGGS_FEATURES})")
        ps = ser.predict(Xp, raw_score=True)
        pd_ = par.predict(Xp, raw_score=True)
        diff = float(np.abs(ps - pd_).max())
        ser._gbdt._ensure_host_trees()
        nleaf = max(t.num_leaves for t in ser._gbdt.models)
        check(np.isfinite(pd_).all() and nleaf < sz.leaves,
              f"multichip[{tier}]: {chips}-chip predictions finite "
              f"(trees stop at {nleaf} leaves, under the {sz.leaves}-"
              f"leaf budget)")
        check(bool(np.allclose(pd_, ps, rtol=1e-5, atol=1e-5)),
              f"multichip[{tier}]: {chips}-chip model == one-chip model "
              f"to rtol=atol=1e-5 on {len(Xp)} rows (max |raw score "
              f"diff| {diff:.2e}; model text identical: "
              f"{trees_of(ser) == trees_of(par)})")


# ---------------------------------------------------------------------------

def run(chips: int, seed: int, sz: Sizes = REAL) -> dict:
    t_all = time.monotonic()
    device = phase_device(chips)
    import lightgbm_tpu                                    # noqa: F401
    c0 = counters()
    if chips > 1:
        phase_multichip(sz, seed, chips)
    else:
        X, y = higgs_like(sz.train_rows + sz.valid_rows
                          + sz.holdout_rows, seed)
        a, b = sz.train_rows, sz.train_rows + sz.valid_rows
        data = (X[:a], y[:a], X[a:b], y[a:b])
        holdout = (X[b:], y[b:])
        exact = phase_train("exact", data, sz, sync_recheck=True)
        phase_compare(exact.device_report(), sz, seed)
        phase_predict(exact, holdout, sz, "exact")
        proxy = phase_train("proxy", data, sz)
        phase_compare(proxy.device_report(), sz, seed)
        phase_predict(proxy, holdout, sz, "proxy", full=False)
        phase_registry(sz, seed)
        phase_rank(sz, seed)
        phase_lrb(sz, seed)
    check(moved(c0, "retry/retries") == 0
          and moved(c0, "retry/giveups") == 0,
          "no operation was retried (retry/retries == 0, "
          "retry/giveups == 0)")
    check(moved(c0, "autotune/candidates_failed") == 0,
          "0 autotune candidates failed over the whole run")
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    say(f"compile cache: {cache_dir} "
        f"({sum(len(f) for _, _, f in os.walk(cache_dir))} files)")
    say(f"total wall: {time.monotonic() - t_all:.1f}s")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = ONLY the multi-chip path and what it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=22)
    args = ap.parse_args(argv)
    device = run(args.chips, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
