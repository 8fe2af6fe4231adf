#!/usr/bin/env python3
"""Reads, in ONE process, what the limits of ``correct`` are set from:

    python3 benchmark/calibrate.py --workload criteo_share.train --seeds 11,12,13 \
        --seconds 1 --out chiprun_out/cal.jsonl [--plant half_left_out --plant-seeds 11] \
        [--params tpu_exact_tier=hilo4]

Each seed is one whole run through ``run.measure`` (the harness's own path:
data, ``Dataset``, the first steps, a window of ``--seconds``, the reference)
with ``lower=True``, so the reference also reads the CONTROL (bfloat16
gradients and hessians in the program's place) and the FAULT "half of the
rows left out" at the same leaves and nodes. ``--plant`` then drives a fault
of ``plants.py`` planted in the PROGRAM through the same path; ``--params``
runs every seed a second time on the same ``Dataset`` with those params put
over the configuration's: another path of the program as a second witness
where a seed reads at fault. One JSON line
per run, with the numbers that went over the cell's limits as they stand in
its configuration. One process, so the seeds share the compiled programs;
``setup_s`` means nothing here and no speed is reported from it."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import run as harness


def over(numbers: dict, limits: dict, leave_out=()) -> list[str]:
    import reference
    lim = {k: v for k, v in limits.items() if k not in leave_out}
    return [k for k, v, l in reference.decide(numbers, lim)[1] if not v <= l]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--plant", default="")
    ap.add_argument("--plant-seeds", default="")
    ap.add_argument("--params", default="", help="k=v,k=v put over the "
                    "configuration's params in a second run of every seed, on "
                    "the same Dataset: another path of the program as witness")
    ap.add_argument("--cpu", action="store_true", help="rehearsal of this tool: "
                    "no TPU gate, 65,536 rows, 15 leaves; reads no limit")
    args = ap.parse_args()
    bench, cell, config, traffic = harness.load_cell(args.workload)
    harness.place_cache()
    device = ({"platform": "cpu", "kind": "rehearsal", "count": 1} if args.cpu
              else harness.gate(int(cell["chips"])))
    base_cut = ({"data": {"rows": 65536}, "params": {"num_leaves": 15}}
                if args.cpu else {"data": {}, "params": {}})
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    witness = {k: json.loads(v) if v[:1].isdigit() else v for k, v in
               (kv.split("=", 1) for kv in args.params.split(",") if kv)}
    kept = {}                # seed -> the Dataset its first run constructed

    def keep_dataset(kind, seed):
        class Kept(kind.System):
            def __init__(self, params, X, y, spans):
                import lightgbm_tpu as lgb
                if seed not in kept:
                    kept.clear()
                    super().__init__(params, X, y, spans)
                    kept[seed] = self.ds
                else:
                    self.ds = kept[seed]
                    self.bst = lgb.Booster(dict(params), self.ds)
        return Kept

    def one(seed: int, plant: str, over_params=None) -> None:
        import plants
        t0 = time.monotonic()
        kind = harness.load_module(harness.HERE / "kinds" / f"{traffic['kind']}.py")
        if plant:
            kind.make_system = plants.ALL[plant](kind)
        elif witness:
            kind.make_system = keep_dataset(kind, seed)
        cut = {"data": base_cut["data"],
               "params": {**base_cut["params"], **(over_params or {})}}
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        line, res = harness.measure(ns, bench, cell, config, traffic, device,
                                    on_chip=not args.cpu, overrides=cut,
                                    lower=not plant, kind=kind)
        ref, limits = res["facts"]["reference"], res["limits"]
        row = {"seed": seed, "plant": plant or None, "params": over_params,
               "detail": ref["detail"], "wall_s": time.monotonic() - t0,
               "correct": line["correct"], "numbers": res["numbers"],
               "over": over(res["numbers"], limits),
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

    for seed in (int(s) for s in args.seeds.split(",") if s):
        one(seed, "")
        if witness:
            one(seed, "", witness)
    for seed in (int(s) for s in args.plant_seeds.split(",") if s):
        one(seed, args.plant)
    return 0


if __name__ == "__main__":
    sys.exit(main())
