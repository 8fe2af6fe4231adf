"""What the PROGRAM says about itself inside a traced window.

``tracereduce.py`` reads the device's operations and the benchmark's own
``bench/`` spans. This module reads, from the same ``.xplane.pb`` and with
``tracereduce``'s functions, what the program put there:

- its host spans. Every span of the program (``lightgbm_tpu/obs/trace.py``
  ``span``, ``utils/timing.py`` ``phase``) is a ``TraceAnnotation`` named
  ``lgbm/<name>`` inside any open profiler session, on the device trace's
  clock. ``spans`` lists those that lie inside ``bench/window`` with their
  parent (the span that encloses them on their thread's line) and their self
  time (duration less what their children cover);
- per device-operation name the number of launches (events on ``XLA Ops``
  that touch the window), which with ``kernels`` (the traffic file's name
  patterns) gives the launches of a kernel group;
- the device's idle gaps, each put down to the innermost ``lgbm/`` span open
  when it began (``nothing`` where none was). Printed to stderr as
  ``[bench] idle by program span: ...``; the result line's ``breakdown``
  stays ``run.py``'s.

A reader calls ``of(facts)``: the reduction of the trace the run just wrote
under ``.bench_out/trace/<cell>/`` (it is still there when the readers run),
made once and kept in ``facts["progtrace"]``. Where there is no trace it is
``None``; where the program has no such span (the parent of the PR that
brought this file) the lists are empty and a reader returns ``None``.
``registry_timer`` / ``registry_gauge`` read the program's own registry in
this process for the set-up metrics, and return ``None`` for a name the
program never recorded, so that 0.0 stays a reading.

By hand:

    python3 benchmark/progtrace.py show <trace.xplane.pb>
    python3 benchmark/progtrace.py cut <in.xplane.pb> <out.xplane.pb> <seconds> [<skip_seconds>]

``cut`` is ``tests/cut_trace.py`` keeping the ``lgbm/`` spans beside the
``bench/`` ones; ``tests/recorded_pr26.xplane.pb`` was made so.

Not read: device self time by ``jax.named_scope``. The fused step wraps its
parts in ``lgbm/...`` scopes, but on the v5e a device event carries none: its
name is the HLO text without ``metadata={...}``, its own stats are offsets and
durations, and the ``op_name`` sits in a stat of the event's METADATA entry,
which ``jax.profiler.ProfileData`` does not show (looked at on the chip,
PR 26). The scopes are there for whoever opens the trace in a viewer.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import tracereduce as tr

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_ROOT = ROOT / ".bench_out" / "trace"
PROGRAM_PREFIX = "lgbm/"


# -- host spans ----------------------------------------------------------------

def nest(events):
    """[(name, start, end)] of ONE line -> [(name, start, end, parent, self)]
    by start time: ``parent`` is the index (in the returned order) of the
    event that encloses it, ``self`` its duration less its children's."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [None] * len(ev)
    covered = [0.0] * len(ev)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = parent[i] = stack[-1]
            covered[p] += min(e, ev[p][2]) - s
        stack.append(i)
    return [(n, s, e, parent[i], max(0.0, e - s - covered[i]))
            for i, (n, s, e) in enumerate(ev)]


def program_spans(pd, lo, hi):
    """The ``lgbm/`` spans that lie inside [lo, hi], over every host line:
    [{"name" (prefix off), "start", "end", "self", "parent" (name or None),
    "thread"}]."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            mine = [(e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(PROGRAM_PREFIX)]
            nested = nest(mine)
            for n, s, e, p, self_ns in nested:
                if s < lo or e > hi:
                    continue
                out.append({
                    "name": n[len(PROGRAM_PREFIX):], "start": s, "end": e,
                    "self": self_ns, "thread": line.name,
                    "parent": (None if p is None
                               else nested[p][0][len(PROGRAM_PREFIX):])})
    return out


def span_means(spans):
    """{name: {"n", "mean_ms", "self_mean_ms"}} over the listed spans."""
    acc: dict[str, list[float]] = {}
    for sp in spans:
        a = acc.setdefault(sp["name"], [0, 0.0, 0.0])
        a[0] += 1
        a[1] += sp["end"] - sp["start"]
        a[2] += sp["self"]
    return {k: {"n": n, "mean_ms": d / n / 1e6, "self_mean_ms": s / n / 1e6}
            for k, (n, d, s) in acc.items()}


def attribute(gap, spans) -> str:
    """The innermost (shortest) program span open when the gap began."""
    open_ = [(sp["end"] - sp["start"], sp["name"]) for sp in spans
             if sp["start"] <= gap[0] < sp["end"]]
    return min(open_)[1] if open_ else "nothing"


# -- device operations -----------------------------------------------------------

def device_facts(pd, lo, hi):
    """-> (launches {op: n}, gaps [(start, end)], planes) over the device
    planes, launches averaged over the planes."""
    launches: dict[str, float] = {}
    gaps, planes = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        events = [(n, max(s, lo), min(e, hi))
                  for line in plane.lines if line.name == tr.OPS_LINE
                  for n, s, e in tr._events(line) if e > lo and s < hi]
        if not events:
            continue
        planes += 1
        for n, _, _ in events:
            launches[n] = launches.get(n, 0) + 1
        gaps += tr.gaps(tr.union([(s, e) for _, s, e in events]), lo, hi)
    n = max(planes, 1)
    return {k: v / n for k, v in launches.items()}, gaps, planes


def group_launches(launches: dict, kernels: dict) -> dict:
    """{group: launches} for the traffic file's name patterns, matched as
    ``tracereduce.reduce`` matches them."""
    return {g: sum(v for k, v in launches.items()
                   if any(p.lower() in k.lower() for p in pats))
            for g, pats in (kernels or {}).items()}


# -- the whole reduction -----------------------------------------------------------

def reduce(pd, kernels: dict | None = None) -> dict:
    win = [(s, e) for n, s, e in tr.host_spans(pd) if n == tr.WINDOW_SPAN]
    if not win:
        return {"spans": [], "span_means": {}, "launches": {},
                "group_launches": {}, "idle_by_span": [], "planes": 0}
    lo, hi = win[0]
    spans = program_spans(pd, lo, hi)
    launches, gaps, planes = device_facts(pd, lo, hi)
    idle: dict[str, float] = {}
    for g in gaps:
        who = attribute(g, spans)
        idle[who] = idle.get(who, 0.0) + (g[1] - g[0])
    return {
        "spans": spans, "span_means": span_means(spans),
        "launches": launches,
        "group_launches": group_launches(launches, kernels),
        "idle_by_span": [[k, v / max(planes, 1) / 1e9] for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])],
        "planes": planes,
    }


def newest_trace():
    """-> (path of the newest ``.xplane.pb`` under ``.bench_out/trace/``,
    the cell whose directory it lies in), or None."""
    found = sorted(TRACE_ROOT.glob("*/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    return found[-1], found[-1].relative_to(TRACE_ROOT).parts[0]


def kernels_of(cell: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = next((w["traffic"] for w in bench["workloads"]
                    if w["name"] == cell), None)
    if traffic is None:
        return {}
    return json.loads((HERE / "traffic" / f"{traffic}.json").read_text()
                      ).get("kernels", {})


def of(facts: dict):
    """The reduction of this run's trace, made once; None without a trace."""
    if "progtrace" not in facts:
        found = newest_trace()
        red = None
        if found is not None:
            path, cell = found
            red = reduce(tr.load(path), kernels_of(cell))
            print("[bench] idle by program span: " + (", ".join(
                f"{k} {1e3 * v:.3f} ms" for k, v in red["idle_by_span"])
                or "no gap"), file=sys.stderr, flush=True)
            print("[bench] program spans in the window: " + (", ".join(
                f"{k} n={v['n']} mean={v['mean_ms']:.3f} ms "
                f"self={v['self_mean_ms']:.3f} ms"
                for k, v in sorted(red["span_means"].items())) or "none"),
                file=sys.stderr, flush=True)
        print("[bench] program set-up timers: " + (", ".join(
            f"{n} {total:.3f} s x{calls}" for n, total, calls in setup_timers())
            or "none"), file=sys.stderr, flush=True)
        facts["progtrace"] = red
    return facts["progtrace"]


# -- the program's registry, for the set-up metrics ----------------------------------

SETUP_PREFIXES = ("binning/", "ingest/", "init/", "autotune", "step_cache/")


def setup_timers():
    """[(name, seconds, calls)] of the program's set-up spans, longest first."""
    from lightgbm_tpu.obs import registry as obs
    return sorted(((n, total, calls) for n, total, calls, _max in
                   obs.default_registry().timer_items()
                   if n.startswith(SETUP_PREFIXES)), key=lambda r: -r[1])


def registry_timer(name: str):
    """Total seconds of the program's timer ``name``; None if the program
    never recorded under it (0.0 from a timer that is there is a reading)."""
    from lightgbm_tpu.obs import registry as obs
    for n, total, _calls, _max in obs.default_registry().timer_items():
        if n == name:
            return total
    return None


def registry_gauge(name: str):
    from lightgbm_tpu.obs import registry as obs
    return obs.default_registry().snapshot()["gauges"].get(name)


# -- by hand -----------------------------------------------------------------------

def cut(src, dst, seconds, skip=0.0):
    """``tests/cut_trace.py`` with the ``lgbm/`` spans kept. That file is not
    this PR's to edit, so its one prefix (``tracereduce.SPAN_PREFIX``, used
    with ``str.startswith``) is widened for the call and put back: a tool run
    by hand in a process of its own, where nothing else reads the name."""
    sys.path.insert(0, str(HERE / "tests"))
    import cut_trace
    prefix = tr.SPAN_PREFIX
    tr.SPAN_PREFIX = (prefix, PROGRAM_PREFIX)
    try:
        cut_trace.main(src, dst, seconds, skip)
    finally:
        tr.SPAN_PREFIX = prefix


if __name__ == "__main__":
    if sys.argv[1] == "cut":
        cut(sys.argv[2], sys.argv[3], float(sys.argv[4]),
            float(sys.argv[5]) if len(sys.argv) > 5 else 0.0)
    else:
        red = reduce(tr.load(sys.argv[2]),
                     {"hist": ["wave_histogram_pallas",
                               "fused_partition_histogram_pallas"]})
        for k in ("span_means", "group_launches", "idle_by_span"):
            print(k, json.dumps(red[k], indent=1))
        for k, v in sorted(red["launches"].items(), key=lambda kv: -kv[1])[:30]:
            print(f"  {v:8.1f} x {k}")
