#!/usr/bin/env python3
"""Rehearsals that cost no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py tiny --workload <cell> [--rows 65536] [--leaves 15]
        drives the SAME harness functions as run.py at a tiny size on the CPU
        backend: traffic kind, reference, comparison, limits. It prints counts
        and the compared numbers only. The measurement path (run.py) keeps its
        TPU gate; nothing printed here is a device metric.
    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py aot --workload <cell>
        compiles the cell's whole training step at its REAL shape for a
        described v5e and prints ``memory_analysis()``: what a later PR needs
        to reckon a new cell's bytes against the 25% floor without a chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as harness


def tiny(args) -> int:
    bench, cell, config, traffic = harness.load_cell(args.workload)
    over = {"data": {"rows": args.rows}, "params": {"num_leaves": args.leaves}}
    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=args.seconds, trace=0)
    device = {"platform": "cpu", "kind": "rehearsal", "count": 1}
    line, res = harness.measure(ns, bench, cell, config, traffic, device,
                                on_chip=False, overrides=over, lower=True)
    ref = res["facts"]["reference"]
    print(json.dumps({
        "rehearsal": "cpu, tiny size: counts and compared numbers only",
        "attempted": line["attempted"], "failed": line["failed"],
        "numbers": res["numbers"], "control": ref.get("control"),
        "half": ref.get("half"), "unchanged": ref.get("unchanged"),
        "correct_under_limits": line["correct"]}, indent=1))
    return 0


def aot(args) -> int:
    """The cell's whole training step (``ops/step_cache.build_train_step``:
    gradients, wave grower with both Mosaic kernels, leaf gather, score
    update) at the cell's REAL shape, compiled for a described v5e. The same
    construction as ``tests/test_tpu_compile.py`` uses, with the layout the
    exact tier resolves to on the chip (hilo4, wave of
    ``autotune.EXACT_TIER_CAPS``, the configuration's row chunk). Shapes only: no data is
    made and nothing runs."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    _bench, _cell, config, _traffic = harness.load_cell(args.workload)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import autotune, step_cache
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import WaveGrowerConfig, make_wave_grower
    from lightgbm_tpu.utils import device
    device.on_tpu = lambda: True          # the grower asks which backend it is on
    device.backend_kind = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    N = step_cache.bucket_rows(args.rows or int(config["data"]["rows"]))
    F = -(-int(config["data"]["features"]) // 8) * 8       # models/gbdt.py pads to 8
    B = 1 << (int(config["params"]["max_bin"])).bit_length()
    L = int(config["params"]["num_leaves"])
    tier = "hilo4"
    gcfg = WaveGrowerConfig(
        num_leaves=L, num_bins=B, wave_size=autotune.EXACT_TIER_CAPS[tier],
        chunk=int(config["params"].get("tpu_hist_chunk")
                  or autotune.DEFAULT_HIST_CHUNK), precision="highest",
        exact_variant=tier, route="pallas-tpu", hp=SplitParams(has_cat=False))
    meta = FeatureMeta(
        num_bin=np.full(F, B, np.int32), missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32), monotone=np.zeros(F, np.int32),
        penalty=np.ones(F, np.float32), is_cat=np.zeros(F, np.int32))
    grower = make_wave_grower(gcfg, meta)
    obj = create_objective("binary", Config().set({"objective": "binary"}))
    obj.init(Metadata(label=(np.arange(N) % 2).astype(np.float32)), N)
    step = step_cache.build_train_step(
        grower=grower, K=1, n_score=N, n_total=N, valid_slices=(),
        num_leaves=L, grad_fn=obj.gradient_builder(), renew_alpha=None,
        sample_hook=None, mesh=None, row_sharded=False)

    def like(a):
        a = np.asarray(a)
        return spec(a.shape, a.dtype)

    lowered = step.lower(
        spec((F, N), jnp.uint8), spec((1, N), jnp.float32), (),
        spec((N,), jnp.float32), spec((F,), jnp.bool_), spec((), jnp.float32),
        spec((1,), jnp.float32), spec((1, 1), jnp.float32),
        spec((1, 1), jnp.float32), spec((2,), jnp.uint32), spec((N,), jnp.bool_),
        FeatureMeta(*[like(a) for a in meta]),
        {"obj": jax.tree_util.tree_map(like, obj.gradient_aux()), "renew": None})
    m = lowered.compile().memory_analysis()
    gib = 2.0 ** 30
    print(json.dumps({
        "rehearsal": "AOT compile for a described v5e: bytes, no times",
        "workload": args.workload, "rows": N, "padded_features": F, "bins": B,
        "num_leaves": L, "tier": tier, "wave": gcfg.wave_size, "chunk": gcfg.chunk,
        "arguments_gib": m.argument_size_in_bytes / gib,
        "temporaries_gib": m.temp_size_in_bytes / gib,
        "outputs_gib": m.output_size_in_bytes / gib,
        "aliased_gib": m.alias_size_in_bytes / gib,
        "step_gib": (m.argument_size_in_bytes + m.temp_size_in_bytes) / gib,
        "share_of_16_gib_pct": 100.0 * (m.argument_size_in_bytes
                                        + m.temp_size_in_bytes) / (16 * gib)},
        indent=1))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("tiny", "aot"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--leaves", type=int, default=15)
    a = ap.parse_args()
    if a.what == "tiny" and not a.rows:
        a.rows = 65536
    sys.exit(tiny(a) if a.what == "tiny" else aot(a))
