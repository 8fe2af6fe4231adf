#!/usr/bin/env python3
"""Reads, in ONE process on the cell's chips, what a data-parallel cell's
limits of ``correct`` are set from:

    python3 benchmark/calibrate_dp.py --workload criteo_dp4.train --seeds 11,12,13 \
        --plant-seeds 11 --seconds 1 --out chiprun_out/cal_dp.jsonl \
        [--params tree_learner=serial] [--plant-seconds 1] [--stop-after-s 400]

``calibrate.py``'s readings for kind ``train_loop_dp``, made so that four chips
are held no longer than they must be: a seed's data is made once and its
``Dataset`` is constructed once; the sound run (``run.measure`` with
``lower=True``: the program, the bfloat16 CONTROL and the emulated "half of the
rows left out" from one pass of the reference) and then each fault of
``plants_dp.py`` (``one_chip_left_out``, then ``half_left_out``, which sets row
weights on that ``Dataset`` and so comes last) build their boosters on it.
``--params`` runs every seed once more along another path of the program (say
``tree_learner=serial`` on the same rows: where a seed reads ``correct`` false
under ``data`` and under ``serial`` alike, the fault is not the sum's), on a
``Dataset`` of its own. One JSON line a run, with the numbers that went over the
cell's limits as its configuration has them. ``setup_s`` means nothing here;
``row_iters_per_s`` is the cell's own number only where ``--seconds`` is the
benchmark's ``run_seconds`` (then a sound run doubles as a run of the cell on
that seed; ``--plant-seconds`` keeps the faults' windows short). Four chips are
dear: ``--stop-after-s`` starts no further run once that much wall has gone.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import run as harness
from calibrate import over


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plant-seeds", default="")
    ap.add_argument("--plants", default="one_chip_left_out,half_left_out")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--plant-seconds", type=float, default=None)
    ap.add_argument("--stop-after-s", type=float, default=float("inf"))
    ap.add_argument("--out", required=True)
    ap.add_argument("--params", default="")
    ap.add_argument("--cpu", action="store_true", help="rehearsal of this tool "
                    "on the CPU's virtual devices: 65,536 rows, 15 leaves, "
                    "tpu_ingest=1; reads no limit")
    args = ap.parse_args()
    started = time.monotonic()
    bench, cell, config, traffic = harness.load_cell(args.workload)
    harness.place_cache()
    device = ({"platform": "cpu", "kind": "rehearsal", "count": cell["chips"]}
              if args.cpu else harness.gate(int(cell["chips"])))
    cut = ({"data": {"rows": 65536},
            "params": {"num_leaves": 15, "tpu_ingest": 1}}
           if args.cpu else {"data": {}, "params": {}})
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    witness = {k: json.loads(v) if v[:1].isdigit() else v for k, v in
               (kv.split("=", 1) for kv in args.params.split(",") if kv)}
    import plants_dp
    kind = harness.load_module(harness.HERE / "kinds" / f"{traffic['kind']}.py")
    plain_make, plain_system = kind._loop.datagen.make, kind.System
    made, kept = {}, {}          # seed -> its data, its Dataset

    def make(spec, seed, threads):
        if seed not in made:
            made.clear()
            made[seed] = plain_make(spec, seed, threads)
        return made[seed]
    kind._loop.datagen.make = make

    def one(seed: int, plant: str, over_params=None) -> None:
        t0 = time.monotonic()
        if t0 - started > args.stop_after_s:
            print(f"[calibrate_dp] {t0 - started:.0f}s gone: seed {seed} "
                  f"{plant or 'sound'} not run", file=sys.stderr, flush=True)
            return
        seconds = (args.plant_seconds if plant and args.plant_seconds
                   else args.seconds)
        base = plants_dp.ALL[plant](kind) if plant else plain_system

        def make_system(params, X, y, spans):
            if over_params:
                return base(params, X, y, spans)
            if seed not in kept:
                kept.clear()
            system = base(params, X, y, spans, ds=kept.get(seed))
            kept[seed] = system.ds
            return system
        kind.make_system = make_system
        from lightgbm_tpu.obs import registry as obs
        c0 = obs.default_registry().counter_items()
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=seconds, trace=0)
        line, res = harness.measure(
            ns, bench, cell, config, traffic, device, on_chip=not args.cpu,
            overrides={"data": cut["data"],
                       "params": {**cut["params"], **(over_params or {})}},
            lower=not plant, kind=kind)
        ref, limits = res["facts"]["reference"], res["limits"]
        row = {"seed": seed, "plant": plant or None, "params": over_params,
               "seconds": seconds, "done": res["facts"]["done"],
               "window_s": res["facts"]["window_s"],
               # what the seed's trees cost: the program's work counters
               # over this run (its three first trees and the window's)
               "hist": {k: v - c0.get(k, 0) for k, v in
                        obs.default_registry().counter_items().items()
                        if k.startswith("hist/")},
               "detail": ref["detail"], "wall_s": time.monotonic() - t0,
               "correct": line["correct"], "numbers": res["numbers"],
               "over": over(res["numbers"], limits),
               "peak_bytes": res["memory_peak_bytes"],
               "row_iters_per_s": res["end_to_end"]["train_row_iters_per_s"]}
        for k in ("control", "half", "unchanged"):
            if k in ref:
                row[k] = ref[k]
                # the emulation's leaf counts differ by construction: not counted
                row[k + "_over"] = over({**res["numbers"], **ref[k]}, limits,
                                        ("leaf_count",))
        with out.open("a") as fh:
            fh.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)

    planted = {int(s) for s in args.plant_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        one(seed, "")
        if seed in planted:
            for plant in (p for p in args.plants.split(",") if p):
                one(seed, plant)
        if witness:
            one(seed, "", witness)
    return 0


if __name__ == "__main__":
    sys.exit(main())
