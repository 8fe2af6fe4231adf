"""Device self time by the PROGRAM's own ``lgbm/`` scopes.

``tracereduce.py`` gives the traced window's self time by operation name
(``op_seconds``: ``fusion.347``, ``fused_partition_histogram_pallas.8``; the
mean chip where there are several). A device event names no scope, so this
module asks the program which scope each name runs under:
``lightgbm_tpu.obs.op_scopes()`` lowers the steps the process compiled (and
the stop check's download) from the signatures it kept and reads
``metadata={op_name=...}`` off the compiled text. It is asked here, after the
window and the reference, so nothing of it falls in ``setup_s`` or the window;
the program's span ``obs/op_scopes`` says what the asking took.

``of(facts)`` makes the split once and keeps it in ``facts["scope_split"]``:

- ``kernels``: the ``hist`` kernels' own time (the traffic files'
  ``kernels.hist`` names, ``HIST_KERNELS``), whatever scope they run under;
- ``by_scope``: every other operation's time by its scope, ``None`` for an
  operation the table maps to no ``lgbm/`` scope and for one it does not
  know (another program's, the harness's fence);
- ``busy``, ``done``: the window's busy seconds and iterations.

It prints one line to stderr, ``[bench] device time by program scope``: each
scope's milliseconds an iteration, the largest XLA operations with their
scopes, the largest of no scope, what the parts add up to against the busy
time; and a line with the table's size and build time. It is ``None`` where
the program publishes no table (the parent of the PR that brought this
file), where the trace holds no operation or the window no iteration. The readers ``step.gradients_ms_per_iter``,
``step.hist_xla_ms_per_iter``, ``step.split_find_ms_per_iter``,
``step.bookkeep_ms_per_iter`` and ``step.unscoped_pct`` read it.
"""
from __future__ import annotations

import sys
import time
import traceback

HIST_KERNELS = ("wave_histogram_pallas", "fused_partition_histogram_pallas")
SPLIT = {
    "gradients": ("lgbm/gradients",),
    "hist_xla": ("lgbm/root_hist", "lgbm/wave/hist"),
    "split_find": ("lgbm/wave/split_find", "lgbm/wave/split_sync"),
    "bookkeep": ("lgbm/wave/bookkeep",),
}
# the collectives' own scopes sit inside the histogram scopes; not hist_xla
PSUM_SCOPES = ("lgbm/root_hist/psum", "lgbm/wave/hist_psum")


def under(scope, parent: str) -> bool:
    """``scope`` is ``parent`` or lies inside it, component by component:
    ``lgbm/wave/hist_psum`` is not under ``lgbm/wave/hist``."""
    return scope is not None and (scope == parent
                                  or scope.startswith(parent + "/"))


def part_of(scope) -> str | None:
    """Which of ``SPLIT``'s parts a scope's time belongs to, if any."""
    if any(under(scope, p) for p in PSUM_SCOPES):
        return None
    for part, parents in SPLIT.items():
        if any(under(scope, p) for p in parents):
            return part
    return None


def program_table():
    """The program's ``{op name: scope}``, or None where it has none."""
    try:
        from lightgbm_tpu import obs
    except ImportError:
        return None
    ask = getattr(obs, "op_scopes", None)
    if ask is None:
        return None
    t0 = time.monotonic()
    try:
        table = ask()
    except Exception:            # noqa: BLE001 — a metric reader must not
        traceback.print_exc()    # take the run's result line down with it
        return None
    timer = obs.timer("obs/op_scopes")
    print(f"[bench] op-scope table: {len(table)} op names, built in "
          f"{timer.total:.3f}s of the program's span ({timer.count} "
          f"build(s); {time.monotonic() - t0:.3f}s asked here, after the "
          f"window)", file=sys.stderr, flush=True)
    return table


def split(op_seconds: dict, table: dict) -> dict:
    """-> ``{"kernels": s, "by_scope": {scope or None: s}}``."""
    kernels, by_scope = 0.0, {}
    for name, s in op_seconds.items():
        if any(k in name.lower() for k in HIST_KERNELS):
            kernels += s
            continue
        scope = table.get(name)
        by_scope[scope] = by_scope.get(scope, 0.0) + s
    return {"kernels": kernels, "by_scope": by_scope}


def part_seconds(sp: dict, part: str) -> float:
    return sum(s for scope, s in sp["by_scope"].items()
               if part_of(scope) == part)


def of(facts):
    if "scope_split" in facts:
        return facts["scope_split"]
    facts["scope_split"] = None
    tr = facts.get("trace") or {}
    ops = tr.get("op_seconds") or {}
    if not ops or not tr.get("busy_s") or not facts.get("done"):
        return None
    table = program_table()
    if table is None:
        return None
    sp = split(ops, table)
    sp.update(busy=tr["busy_s"], done=facts["done"])
    facts["scope_split"] = sp
    _print(sp, ops, table)
    return sp


def _print(sp, ops, table) -> None:
    ms = 1e3 / sp["done"]
    ranked = sorted(sp["by_scope"].items(), key=lambda kv: -kv[1])
    xla = sorted(((n, s) for n, s in ops.items()
                  if not any(k in n.lower() for k in HIST_KERNELS)),
                  key=lambda kv: -kv[1])
    unscoped = [(n, s) for n, s in xla if table.get(n) is None][:5]
    total = sp["kernels"] + sum(sp["by_scope"].values())
    print("[bench] device time by program scope (ms an iteration): "
          + f"hist kernels {sp['kernels'] * ms:.4g}, "
          + ", ".join(f"{scope or 'no scope'} {s * ms:.4g}"
                      for scope, s in ranked)
          + "; largest XLA ops: " + ", ".join(
              f"{n} {table.get(n) or 'no scope'} {s * ms:.4g}"
              for n, s in xla[:12])
          + "; of no scope: " + (", ".join(
              f"{n}{'' if n in table else ' (unknown)'} {s * ms:.4g}"
              for n, s in unscoped) or "none")
          + f"; all {total * ms:.6g} against busy {sp['busy'] * ms:.6g}",
          file=sys.stderr, flush=True)
