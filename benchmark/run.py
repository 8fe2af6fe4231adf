#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run, one process per chip. It reads ``BENCHMARK.json`` for
the cell, the configuration's file for the sizes and limits, the traffic file
for the mix, and drives the traffic's kind (``kinds/<kind>.py``). The last
line of stdout is the result object; nothing is printed there when JAX finds
no TPU, fewer chips than the cell asks for, or no program to measure.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"
sys.path[:0] = [str(HERE), str(ROOT)]     # the yardstick's modules, the program


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def gate(chips: int) -> dict:
    """A TPU with enough chips, or no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        print(f"[bench] needs {chips} TPU chip(s); jax found {len(devs)} x "
              f"{d.platform} ({d.device_kind})", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def place_cache() -> None:
    """One compile cache, at a fixed path inside the checkout unless the
    operator placed one; the program takes whichever jax was given
    (ops/autotune.ensure_compile_cache) and keeps its tuning JSON there too."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".lgbm_tpu_cache"))


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def make_context(args, config, traffic, on_chip: bool, overrides=None,
                 lower=False):
    from spans import Spans
    spans = Spans()
    trace_dir = OUT / "trace" / args.workload
    ctx = SimpleNamespace(
        config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace) and on_chip, spans=spans, on_chip=on_chip,
        t_process=T_PROCESS, overrides=overrides or {}, lower=lower,
        trace_dir=trace_dir)

    def start_trace():
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
        spans.annotate = True

    def stop_trace():
        import jax
        spans.annotate = False
        jax.profiler.stop_trace()

    ctx.start_trace, ctx.stop_trace = start_trace, stop_trace
    return ctx


def per_layer(bench, cell_name: str, facts: dict) -> dict:
    """Each per-layer metric through the reader of its own; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell_name):
            continue
        value = load_module(HERE / "readers" / f"{m['name']}.py").read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def measure(args, bench, cell, config, traffic, device, *, on_chip=True,
            overrides=None, lower=False, kind=None):
    """Everything of a run after the look for a chip: drive the traffic's
    kind, decide ``correct``, reduce the trace, build the result object.
    -> (line, res). Tests and the CPU rehearsal enter here with
    ``on_chip=False`` (no device metric is then made) and a cut size; a test
    hands in the ``kind`` module with a broken system planted in it."""
    kind = kind or load_module(HERE / "kinds" / f"{traffic['kind']}.py")
    ctx = make_context(args, config, traffic, on_chip=on_chip,
                       overrides=overrides, lower=lower)
    res = kind.run(ctx)

    import reference
    correct, rows = reference.decide(res["numbers"], res["limits"])
    device = dict(device, memory_peak_bytes=int(res["memory_peak_bytes"]))
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if ctx.trace:
        import tracereduce
        facts = res["facts"]
        facts["peaks"] = peaks_for(device["kind"])
        facts["trace"] = tracereduce.reduce_dir(ctx.trace_dir,
                                                traffic.get("kernels", {}))
        line["metrics"] = per_layer(bench, cell["name"], facts)
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        line["breakdown"] = {"device_ops": facts["trace"]["device_ops"][:10],
                             "idle_gaps": facts["trace"]["idle_gaps"][:10]}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        line["metrics"] = {
            m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell["name"])}
    line["device"] = device
    line["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    sys.stdout.flush()
    for k in sorted(set(res["numbers"]) - set(res["limits"])):
        print(f"[bench] read {k}: {res['numbers'][k]:.6g} (not compared)",
              file=sys.stderr)
    for k, v, lim in rows:
        print(f"[bench] check {k}: {v:.6g} (limit {lim:g})"
              f"{'' if v <= lim else '  <-- over'}", file=sys.stderr)
    sys.stderr.flush()
    return line, res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    bench, cell, config, traffic = load_cell(args.workload)
    if not (ROOT / "lightgbm_tpu").is_dir():
        print("[bench] no program to measure beside the benchmark",
              file=sys.stderr)
        return 4
    place_cache()
    device = gate(int(cell["chips"]))
    peaks_for(device["kind"])          # an unknown kind stops here, not after the run
    line, _res = measure(args, bench, cell, config, traffic, device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # no interpreter teardown: jax's threads are done
