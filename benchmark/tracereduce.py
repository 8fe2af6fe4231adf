"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

Device planes are those named ``/device:TPU:<n>``; of their lines only the one
that carries the device's own operations is read (``XLA Ops``), because the
other lines repeat the same time under other names. An operation that encloses
others (a ``while`` and its body) is charged only its SELF time, so the self
times add up to the busy time: the union of all the operations' intervals (a
``while`` between two operations of its body is the device at work, not a gap
the host could close). The benchmark's host
spans arrive through ``TraceAnnotation`` as events named ``bench/<span>`` on a
host plane, on the same clock; ``bench/window`` is the traced window.
"""
from __future__ import annotations

from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """A device event carries its whole HLO text; the instruction's name is
    what stands before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(short_name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def self_times(events):
    """[(name, start, end)] of one line -> [(name, self_ns)]: each
    operation's time less that of the operations it encloses."""
    ev = sorted(events, key=lambda e: (e[1], -(e[2])))
    covered = [0.0] * len(ev)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            covered[p] += min(e, ev[p][2]) - s
        stack.append(i)
    return [(ev[i][0], max(0.0, ev[i][2] - ev[i][1] - covered[i]))
            for i in range(len(ev))]


def union(intervals, lo=None, hi=None):
    """Merged, clipped intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi):
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def host_spans(pd):
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans += [e for e in _events(line) if e[0].startswith(SPAN_PREFIX)]
    return spans


def attribute(gap, spans) -> str:
    """The innermost host span open when the gap began."""
    s0 = gap[0]
    open_ = [(e - s, n) for n, s, e in spans
             if s <= s0 < e and n != WINDOW_SPAN]
    return min(open_)[1][len(SPAN_PREFIX):] if open_ else "nothing"


def reduce(pd, kernels: dict | None = None) -> dict:
    """-> busy_s, window_s (averaged over the device planes), device_ops and
    idle_gaps as [[name, seconds]] by falling time, op_seconds {name: s}, and
    kernel_s {group: s} for the name patterns in ``kernels``."""
    spans = host_spans(pd)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    per_plane, ops, gap_by = [], {}, {}
    for plane in planes:
        lines = [l for l in plane.lines if l.name == OPS_LINE]
        events = [e for l in lines for e in _events(l)]
        if not events:
            continue
        lo, hi = win[0] if win else (min(e[1] for e in events),
                                     max(e[2] for e in events))
        events = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if e > lo and s < hi]
        selfs = self_times(events)
        busy = union([(s, e) for _, s, e in events])
        per_plane.append((sum(e - s for s, e in busy), hi - lo))
        for name, ns in selfs:
            ops[name] = ops.get(name, 0.0) + ns
        for g in gaps(busy, lo, hi):
            who = attribute(g, spans)
            gap_by[who] = gap_by.get(who, 0.0) + (g[1] - g[0])
    if not per_plane:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "op_seconds": {}, "kernel_s": {},
                "planes": 0}
    n = len(per_plane)
    ops = {k: v / n / 1e9 for k, v in ops.items()}
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    kernel_s = {}
    for group, pats in (kernels or {}).items():
        hit = [v for k, v in ops.items()
               if any(p.lower() in k.lower() for p in pats)]
        if hit:
            kernel_s[group] = sum(hit)
    return {
        "busy_s": sum(b for b, _ in per_plane) / n / 1e9,
        "window_s": sum(w for _, w in per_plane) / n / 1e9,
        "device_ops": [[k, v] for k, v in ranked],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gap_by.items(), key=lambda kv: -kv[1])],
        "op_seconds": ops, "kernel_s": kernel_s, "planes": n,
    }


def reduce_dir(trace_dir, kernels: dict | None = None) -> dict:
    return reduce(load(find_xplane(trace_dir)), kernels)


if __name__ == "__main__":       # look at a trace by hand
    import sys
    pd = load(sys.argv[1])
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            ev = _events(line)
            print(f"  line {line.name!r}: {len(ev)} events")
    r = reduce(pd)
    print({k: r[k] for k in ("busy_s", "window_s", "planes")})
    for k, v in r["device_ops"][:40]:
        print(f"  {v:10.6f} s  {k}")
    print(r["idle_gaps"][:10])
