"""Per-phase wall-clock accounting.

TPU-native counterpart of the reference's TIMETAG instrumentation
(reference: src/treelearner/serial_tree_learner.cpp:14-41 init/hist/
split timers, src/boosting/gbdt.cpp:253-256 per-iteration elapsed).
Phase accumulation lives in the obs metrics registry
(obs/registry.py) — thread-safe, so the ingest prefetch worker can
record from off-thread while the main thread accumulates training
phases — and every phase lands in the run report's phase table
(obs/recorder.py). jax dispatch is async, so a phase's bucket holds the
HOST time it spent issuing work; queued device time lands in whichever
later phase first synchronizes. Callers that need exact device
attribution ``.watch(out)`` their output (sync at phase exit).

When profiling is active (obs/profiler.py ProfileWindow), each phase
additionally wraps its block in a ``jax.profiler.TraceAnnotation`` so
the engine's phase names show up as spans in XLA/Perfetto traces.
When the engine's own tracer is active (obs/trace.py, config
``tpu_trace``), each phase also records a span on the calling thread's
trace row — one file shows the ingest worker's phases interleaved with
the main thread's.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from ..obs import registry as _obs
from ..obs import trace as _trace
from . import log

# emit jax TraceAnnotations around phases (toggled by the profiler
# window; off by default — the annotation objects are cheap but not
# free, and most runs are not being traced)
_annotate = False


def set_trace_annotations(on: bool) -> None:
    global _annotate
    _annotate = bool(on)


class _PhaseHandle:
    """Yielded by ``phase``; lets device phases register the output
    whose completion the phase should wait for at exit."""
    __slots__ = ("out",)

    def __init__(self):
        self.out = None

    def watch(self, out):
        """Register a (pytree of) device array(s): the phase blocks on
        it at exit, so queued device time is attributed HERE instead of
        leaking into whichever later phase first synchronizes."""
        self.out = out
        return out


@contextmanager
def phase(name: str):
    """Accumulate the wall time spent inside the block.

    jax dispatch is async: a phase that merely ISSUES device work
    records only the issue time, and the device time lands in whichever
    later phase first synchronizes — silently misattributed. Device
    phases therefore ``.watch(out)`` their output on the yielded
    handle, which forces completion at phase exit, before the clock
    stops."""
    ann = None
    if _annotate:
        try:
            import jax
            ann = jax.profiler.TraceAnnotation(f"lgbm/{name}")
            ann.__enter__()
        except Exception:               # noqa: BLE001 — annotation is
            ann = None                  # an aid, never a failure mode
    tracer = _trace.active()
    span_t0 = tracer.now_us() if tracer is not None else 0.0
    t0 = time.monotonic()
    h = _PhaseHandle()
    try:
        yield h
    finally:
        if h.out is not None:
            _sync(h.out)
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:           # noqa: BLE001
                pass
        # bounded-cardinality: phase names are call-site string
        # literals (the timing.phase sites in this repo)
        _obs.timer(name).add(time.monotonic() - t0)
        if tracer is not None:
            # same block, same clock stop: every phase is also a span
            # in the cross-thread trace (obs/trace.py) — the ingest
            # worker's phases land on their own tid row
            tracer.complete(name, "phase", span_t0)


def add(name: str, seconds: float) -> None:
    # bounded-cardinality: caller-literal timer names (bench phases)
    _obs.timer(name).add(seconds)


def reset() -> None:
    _obs.default_registry().reset_timers()


def seconds(prefix: str) -> float:
    """Total accumulated seconds of every phase whose name starts with
    ``prefix`` (e.g. "autotune" sums all per-kernel tuning phases)."""
    return sum(total for name, total, _, _ in
               _obs.default_registry().timer_items()
               if name.startswith(prefix))


def _sync(out) -> None:
    """Force completion of a dispatched jax computation with a real
    device->host scalar readback: the transfer stream is ordered, so
    one scalar drains the queue. (Chosen over block_until_ready for a
    backend that no longer exists; on the in-process chip the two were
    observed to agree — chip_smoke.py prints the comparison — and the
    readback is kept because it also feeds ``transfer/d2h_syncs``.)"""
    import numpy as np
    try:
        import jax
        leaves = [x for x in jax.tree_util.tree_leaves(out)
                  if hasattr(x, "dtype")]
    except ImportError:
        leaves = []
    if leaves:
        x = leaves[0]
        np.asarray(x.ravel()[:1] if getattr(x, "ndim", 0) else x)
        _obs.counter("transfer/d2h_syncs").add(1)


def measure(fn, *args, repeats: int = 5, warmup: int = 1) -> float:
    """Median-of-``repeats`` wall seconds of ``fn(*args)`` with a device
    sync per call — the autotuner's measurement harness (the reference
    times its GPU kernel variants the same way, docs/GPU-Performance).
    ``warmup`` untimed calls absorb compilation."""
    for _ in range(max(warmup, 0)):
        _sync(fn(*args))
    times = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def report() -> str:
    """One line per phase, sorted by total seconds DESCENDING so the
    dominant phase is always the first line; columns: total, calls,
    mean, max."""
    items = sorted(_obs.default_registry().timer_items(),
                   key=lambda r: -r[1])
    lines = []
    for name, total, n, mx in items:
        n = max(n, 1)
        lines.append(f"  {name:<24s} {total:9.3f} s  ({n} calls, "
                     f"{1000.0 * total / n:.2f} ms avg, "
                     f"{1000.0 * mx:.2f} ms max)")
    return "\n".join(lines)


def log_report(header: str = "phase timings") -> None:
    """Log and RESET — each report covers one run's deltas."""
    body = report()
    if body:
        log.info("%s:\n%s", header, body)
        reset()
