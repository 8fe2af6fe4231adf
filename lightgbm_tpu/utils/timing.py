"""Per-phase wall-clock accounting.

TPU-native counterpart of the reference's TIMETAG instrumentation
(reference: src/treelearner/serial_tree_learner.cpp:14-41 init/hist/
split timers, src/boosting/gbdt.cpp:253-256 per-iteration elapsed).

``phase(name)`` IS ``obs/trace.span(name)`` — the program's one span
site — plus ``.watch``: it records name, start, end, thread, parent
and cause, adds its seconds to the registry timer of its name
(obs/registry.py — thread-safe, so the ingest prefetch worker records
from off-thread while the main thread accumulates training phases;
``seconds()``/``report()`` and the run report's phase table read
those timers), is a ``jax.profiler.TraceAnnotation("lgbm/<name>")``
inside ANY open profiler session (a benchmark's, ``tpu_profile_dir``'s,
an operator's own), and an event in the ``tpu_trace`` ring (and,
through the ring only, the flight sink) where one is configured. jax
dispatch is async, so a
phase's timer holds the HOST time it spent issuing work; queued device
time lands in whichever later phase first synchronizes. Callers that
need exact device attribution ``.watch(out)`` their output (sync at
phase exit).
"""
from __future__ import annotations

import time

from ..obs import registry as _obs
from ..obs import trace as _trace
from . import log


class _Phase(_trace.Span):
    """A span that can wait for its device output before its clock
    stops, and can mark where the device's memory peak last rose.
    Accounting-grade: it reaches the flight sink through the
    ``tpu_trace`` ring only (obs/trace.Span.to_sinks)."""
    __slots__ = ("out", "mem_peak")
    to_sinks = False

    def __init__(self, name, cat, args, mem_peak):
        super().__init__(name, cat, args)
        self.out = None
        self.mem_peak = mem_peak

    def watch(self, out):
        """Register a (pytree of) device array(s): the phase blocks on
        it at exit, so queued device time is attributed HERE instead of
        leaking into whichever later phase first synchronizes."""
        self.out = out
        return out

    def __exit__(self, *exc):
        if self.out is not None:
            _sync(self.out)
        swallow = super().__exit__(*exc)
        if self.mem_peak:           # after the clock has stopped
            mark_mem_peak(self.name)
        return swallow


def phase(name: str, cat: str = "phase", args=None,
          mem_peak: bool = False) -> _Phase:
    """``with phase("binning/bin_matrix") as ph:`` — a span
    (obs/trace.span) whose handle can ``.watch(out)``.

    jax dispatch is async: a phase that merely ISSUES device work
    records only the issue time, and the device time lands in whichever
    later phase first synchronizes — silently misattributed. Device
    phases therefore ``.watch(out)`` their output on the yielded
    handle, which forces completion at phase exit, before the clock
    stops.

    ``mem_peak`` (the coarse set-up phases only) sets the gauge
    ``mem/peak_bytes@<name>`` at exit from the fullest local device's
    ``peak_bytes_in_use``: the high-water mark is monotone, so the
    phase at which it last rose is where the process's peak comes
    from."""
    return _Phase(name, cat, args, mem_peak)


def mark_mem_peak(name: str) -> None:
    """Set ``mem/peak_bytes@<name>`` (``phase(mem_peak=True)`` does it at
    its exit; a ``trace.span`` site calls it after its block)."""
    peak = _device_peak_bytes()
    if peak is not None:
        # bounded-cardinality: the four coarse set-up phases
        _obs.gauge(f"mem/peak_bytes@{name}").set(peak)


def _device_peak_bytes():
    """``peak_bytes_in_use`` of the fullest local device; None where
    the backend keeps no memory statistics (the CPU), and while jax
    has brought no backend up yet — a host-only phase must not be the
    one that initializes it (jax.distributed has to come first)."""
    try:
        import jax
        # no public probe says "is a backend up" without bringing one up
        from jax._src.xla_bridge import backends_are_initialized
    except ImportError:             # the gauge is an aid: without the
        return None                 # probe it stays unset, nothing breaks
    if not backends_are_initialized():
        return None
    peaks = [s["peak_bytes_in_use"]
             for s in (d.memory_stats() for d in jax.local_devices())
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


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
