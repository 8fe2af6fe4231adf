#!/usr/bin/env python3
"""How ``recorded.xplane.pb`` was made: a slice of a trace recorded on the chip,
cut to the lines the reduction reads (the device's ``XLA Ops`` and the host's
``bench/`` spans), operation names shortened to the instruction's name.

    python3 benchmark/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb> <seconds> [<skip_seconds>]

Times are kept to the picosecond the profiler wrote, so every sum the test
pins can be read off the original too."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import tracereduce as tr


def main(src, dst, seconds, skip=0.0):
    from jax.profiler import ProfileData
    pd = tr.load(src)
    spans = tr.host_spans(pd)
    t_lo = min(s for n, s, _ in spans if n == tr.WINDOW_SPAN) + skip * 1e9
    t_hi = t_lo + seconds * 1e9
    out, pid = [], 0
    for plane in pd.planes:
        dev = plane.name.startswith("/device:TPU:")
        names: dict[str, int] = {}
        lines = []
        for line in plane.lines:
            if dev and line.name != tr.OPS_LINE:
                continue
            ev = [e for e in tr._events(line)
                  if (dev or e[0].startswith(tr.SPAN_PREFIX))
                  and e[2] > t_lo and e[1] < t_hi]
            if not ev:
                continue
            base = int(t_lo)
            rows = []
            for name, s, e in ev:
                s, e = max(s, t_lo), min(e, t_hi)     # the window span is clipped
                mid = names.setdefault(name, len(names) + 1)
                rows.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{int(round((s - base) * 1000))} duration_ps: "
                            f"{int(round((e - s) * 1000))} }}")
            lines.append(f'  lines {{ id: {len(lines) + 1} name: "{line.name}" '
                         f"timestamp_ns: {base}\n" + "\n".join(rows) + "\n  }")
        if not lines:
            continue
        pid += 1
        meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                         f'"{n}" }} }}' for n, i in names.items())
        out.append(f'planes {{\n  id: {pid} name: "{plane.name}"\n'
                   + "\n".join(lines) + "\n" + meta + "\n}")
    Path(dst).write_bytes(
        ProfileData.text_proto_to_serialized_xspace("\n".join(out)))
    print(f"{dst}: {Path(dst).stat().st_size} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]),
         float(sys.argv[4]) if len(sys.argv) > 4 else 0.0)
