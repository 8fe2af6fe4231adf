"""The span site, and a ring-buffered Chrome trace-event recorder for
the whole pipeline.

**One span API.** ``span(name)`` (and ``utils/timing.phase``, which is
``span`` plus ``.watch``) is the only way the program marks a stretch
of its own work. A span records its name, start and end, the thread it
ran on, the span that enclosed it on that thread (``parent``) and, for
work another thread handed over, the span that submitted it (``cause``,
carried by ``carry``); ``args`` ride along (an iteration span carries
``it``). Every span, always, adds its seconds to the registry timer of
its name (obs/registry.py: what ``timing.seconds`` and the run report
read) and, while a profiler session is open, is a
``jax.profiler.TraceAnnotation("lgbm/<name>")`` in the profiler's
trace, on the device trace's clock — whoever opened the session: a
benchmark, ``tpu_profile_dir``, an operator's own
``jax.profiler.start_trace``. (An annotation made outside a session is
inert, so none is made: the span asks the profiler and goes on.) Where
jax has no profiler the span does without. With ``tpu_trace`` set
(below) the span is also an event in this module's ring; registered
sinks (the flight recorder) are fed by the ring and, with no ring, by
the ``span`` sites directly (``Span.to_sinks``).

The run report (obs/recorder.py) answers "how long did iteration 140
take"; this module answers "what was every thread DOING while it ran".
One trace file shows the ingest prefetch worker slicing chunk k+1 while
the main thread dispatches chunk k's bin kernel, the step-cache
compiling (or hitting) the fused step, each boosting iteration, and —
in the sliding-window driver (lrb.py) — the derive/train/evaluate
phases of every window, all on a shared clock.

Output is the Chrome trace-event JSON format (the ``traceEvents``
array form), loadable in Perfetto (ui.perfetto.dev) and chrome://
tracing:

- spans are complete events (``ph == "X"``: ``ts``/``dur`` in
  microseconds, ``pid``/``tid`` integers);
- point-in-time markers (watchdog firings, step-cache hits/misses) are
  instant events (``ph == "i"``, thread scope);
- thread names are emitted as ``ph == "M"`` metadata records so
  Perfetto labels the ingest worker row "ingest-prefetch" instead of a
  bare thread id.

Design constraints (the registry's rules, obs/registry.py):

- **Thread-safe.** Spans are recorded from the ingest worker, the
  pipelined-eval path and the exporter thread concurrently; every
  mutation takes one lock. Events are appended at span EXIT (complete
  events carry their duration), so a span records with a single locked
  append — no cross-thread begin/end pairing.
- **Bounded.** The buffer is a ring (``tpu_trace_buffer`` events,
  config.py): a million-iteration serving loop keeps the LAST N events
  instead of growing without bound; ``dropped_events`` counts what the
  ring evicted (surfaced in the written file's metadata).
- **Importable without jax.** Standard library only at load time —
  utils/timing.py imports this module exactly like the registry;
  ``jax.profiler`` is looked up on the first span.
- **Off is cheap.** With no tracer, no sink and no profiler session a
  span is two clock reads, a timer add and one look at the profiler
  (about 3 us on the host, PERF.md); no ring lock is taken.

The module-global tracer is installed by ``configure`` (drivers call
``ensure_from_config`` with any Config/dict carrying ``tpu_trace``) and
the buffer is flushed to disk by ``write()`` — called by
RunRecorder.finish (which also cross-links ``meta.trace_path``), by
lrb.py after every window (so a live loop always has a current trace on
disk), and at interpreter exit as a safety net.
"""
from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

from ..analysis import lockorder
from ..utils.fileio import atomic_write
from . import identity
from . import registry as _registry

__all__ = [
    "Tracer", "Span", "configure", "ensure_from_config", "stop",
    "active", "enabled", "span", "current", "carry", "instant",
    "write", "config_get", "add_sink", "remove_sink",
]


def config_get(config, key: str, default=None):
    """Read a knob off a Config object (attribute) or a raw params
    dict (key) — the one accessor behind the telemetry daemons'
    ``ensure_from_config`` seams (this module and obs/export.py), so
    the two cannot drift. Returns ``default`` for missing OR
    explicitly-None values."""
    if isinstance(config, dict):
        v = config.get(key, default)
    else:
        v = getattr(config, key, default)
    return default if v is None else v

DEFAULT_BUFFER_EVENTS = 65536
MIN_BUFFER_EVENTS = 1024

# event sinks: callables fed EVERY recorded event dict, tracer or not
# (the flight recorder's always-on span ring, obs/flight.py). Fed
# outside the tracer's lock; a sink must be cheap and never raise.
_sinks: list = []
# fallback clock for sink-only events (no tracer installed): same
# perf_counter µs convention as Tracer.now_us, epoch at module import
_sink_t0_ns = time.perf_counter_ns()


def add_sink(fn) -> None:
    """Register an event sink (idempotent — re-registration of the
    same callable is a no-op)."""
    if fn not in _sinks:
        _sinks.append(fn)


def remove_sink(fn) -> None:
    if fn in _sinks:
        _sinks.remove(fn)


def _feed_sinks(ev: dict) -> None:
    for s in tuple(_sinks):
        try:
            s(ev)
        except Exception:               # noqa: BLE001 — a sink must
            pass                        # never break the traced path


def _sink_only_event(name: str, cat: str, ph: str, ts_us: float,
                     dur_us: Optional[float] = None,
                     args: Optional[dict] = None) -> None:
    """Record an event for the sinks when NO tracer is installed (the
    flight ring keeps span evidence even with tpu_trace off)."""
    ev = {"name": name, "cat": cat, "ph": ph, "ts": round(ts_us, 3),
          "pid": os.getpid(), "tid": _native_tid()}
    if ph == "X":
        ev["dur"] = round(max(dur_us or 0.0, 0.0), 3)
    elif ph == "i":
        ev["s"] = "t"
    if args:
        ev["args"] = args
    _stamp_rank(ev)
    _feed_sinks(ev)


def _stamp_rank(ev: dict) -> None:
    """Rank (and, once past the first re-shard, incarnation) into the
    event args under a multi-process world — per-event identity so a
    merged timeline (tools/trace_summary.py --merge) attributes every
    span without filename context. Free single-process."""
    if not identity.is_multiprocess():
        return
    args = ev.setdefault("args", {})
    args.setdefault("rank", identity.rank())
    inc = identity.incarnation()
    if inc:
        args.setdefault("inc", inc)


def _sink_now_us() -> float:
    return (time.perf_counter_ns() - _sink_t0_ns) / 1000.0


def _native_tid() -> int:
    try:
        return threading.get_native_id()
    except Exception:                   # noqa: BLE001 — pre-3.8 fallback
        return threading.get_ident() & 0x7FFFFFFF


class Tracer:
    """Ring-buffered trace-event recorder; one per process normally
    (the module global), private instances for tests."""

    def __init__(self, path: str, capacity: int = DEFAULT_BUFFER_EVENTS):
        self.path = str(path)
        self.capacity = max(int(capacity), MIN_BUFFER_EVENTS)
        self._lock = lockorder.named_lock("obs.trace._lock")
        self._events: deque = deque(maxlen=self.capacity)
        self._threads: dict = {}        # tid -> thread name
        self._dropped = 0
        self._pid = os.getpid()
        self._t0_ns = time.perf_counter_ns()
        self._started_unix = time.time()

    def resize(self, capacity: int) -> None:
        """Change the ring capacity in place, keeping the newest
        events (a later config naming the same trace path but a larger
        tpu_trace_buffer must not be silently ignored)."""
        capacity = max(int(capacity), MIN_BUFFER_EVENTS)
        with self._lock:
            if capacity == self.capacity:
                return
            self.capacity = capacity
            self._events = deque(self._events, maxlen=capacity)

    # -- clock ---------------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since tracer start — the shared ``ts`` clock
        (perf_counter is monotonic and thread-consistent)."""
        return (time.perf_counter_ns() - self._t0_ns) / 1000.0

    # -- recording -----------------------------------------------------------

    def _append(self, ev: dict) -> None:
        _stamp_rank(ev)
        with self._lock:
            if len(self._events) == self.capacity:
                self._dropped += 1
            self._events.append(ev)
        _feed_sinks(ev)                 # outside the ring lock

    def _register_thread(self, tid: int) -> None:
        if tid not in self._threads:
            name = threading.current_thread().name
            with self._lock:
                self._threads.setdefault(tid, name)

    def complete(self, name: str, cat: str, start_us: float,
                 args: Optional[dict] = None,
                 end_us: Optional[float] = None) -> None:
        """Record a finished span [start_us, end_us or now] on the
        CALLING thread (complete events pair begin/end in one record,
        so cross-thread spans can never mis-nest)."""
        tid = _native_tid()
        self._register_thread(tid)
        end = self.now_us() if end_us is None else end_us
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": round(start_us, 3),
              "dur": round(max(end - start_us, 0.0), 3),
              "pid": self._pid, "tid": tid}
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, cat: str = "event",
                args: Optional[dict] = None) -> None:
        """Record a point-in-time marker on the calling thread."""
        tid = _native_tid()
        self._register_thread(tid)
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": round(self.now_us(), 3),
              "pid": self._pid, "tid": tid}
        if args:
            ev["args"] = args
        self._append(ev)

    @contextmanager
    def span(self, name: str, cat: str = "phase",
             args: Optional[dict] = None):
        t0 = self.now_us()
        try:
            yield
        finally:
            self.complete(name, cat, t0, args)

    # -- stats / serialization ----------------------------------------------

    @property
    def dropped_events(self) -> int:
        with self._lock:
            return self._dropped

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def trace_document(self) -> dict:
        """The Perfetto-loadable JSON document for the current buffer:
        thread-name metadata records first, then the ring's events."""
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
            dropped = self._dropped
        ident = identity.identity()
        pname = "lightgbm_tpu"
        if ident["world"] > 1:
            pname = f"lightgbm_tpu r{ident['machine_rank']}"
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": pname}},
                # the full identity record as process metadata, so a
                # merged multi-rank file keeps each process labeled
                {"name": "process_labels", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"labels": (
                     f"rank {ident['machine_rank']}/{ident['world']} "
                     f"inc {ident['incarnation']}")}}]
        for tid, tname in sorted(threads.items()):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": self._pid, "tid": tid,
                         "args": {"name": tname}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "schema": "lightgbm-tpu/trace",
                "version": 1,
                "started_unix": round(self._started_unix, 3),
                "dropped_events": dropped,
                "identity": ident,
            },
        }

    def write(self) -> str:
        """Dump the current buffer to ``path`` (atomic tmp+rename, the
        run-report discipline — utils/fileio.py). Idempotent —
        callable after every window of a live loop; each write
        replaces the file with the ring's current contents."""
        doc = self.trace_document()
        with atomic_write(self.path) as fh:
            json.dump(doc, fh)
        return self.path


# ---------------------------------------------------------------------------
# module-global tracer (the engine's default; tests build private ones)
# ---------------------------------------------------------------------------

_tracer: Optional[Tracer] = None
_atexit_installed = False


def configure(path: str, capacity: int = DEFAULT_BUFFER_EVENTS) -> Tracer:
    """Install (or re-target) the process-global tracer. Idempotent for
    the same path — the running buffer is kept so early spans (dataset
    ingest before the booster exists) survive. Re-targeting to a NEW
    path flushes the old tracer's buffer to its own file first, so
    spans recorded after its last write are not silently dropped."""
    global _tracer, _atexit_installed
    if _tracer is not None and _tracer.path == str(path):
        # honor a LARGER buffer knob on same-path reconfigure; never
        # shrink mid-run (a later caller with the default capacity —
        # e.g. a params dict without tpu_trace_buffer — must not drop
        # the events an earlier explicit knob sized the ring for)
        if capacity > _tracer.capacity:
            _tracer.resize(capacity)
        return _tracer
    if _tracer is not None:
        write()                 # never-raises flush of the old buffer
    _tracer = Tracer(path, capacity)
    if not _atexit_installed:
        # safety net: a crashed/interrupted run still leaves a trace
        atexit.register(write)
        _atexit_installed = True
    return _tracer


def ensure_from_config(config) -> Optional[Tracer]:
    """Install the global tracer when ``tpu_trace`` is set on a Config
    (attribute) or params dict (key); called from dataset construction
    and the training drivers — whichever runs first wins the buffer."""
    path = str(config_get(config, "tpu_trace", "") or "")
    if not path:
        return None
    # one trace file per rank (obs/identity.py): world>1 must never
    # atomic-replace a peer's buffer with its own
    path = identity.rank_suffixed(path)
    cap = int(config_get(config, "tpu_trace_buffer",
                         DEFAULT_BUFFER_EVENTS) or DEFAULT_BUFFER_EVENTS)
    return configure(path, cap)


def stop() -> None:
    """Uninstall the global tracer (tests) without writing."""
    global _tracer
    _tracer = None


def active() -> Optional[Tracer]:
    return _tracer


def enabled() -> bool:
    return _tracer is not None


# ---------------------------------------------------------------------------
# the span site
# ---------------------------------------------------------------------------

_tls = threading.local()        # .stack: open spans; .cause: see carry()
_ids = itertools.count(1)       # next() is atomic under the GIL
_annotation_cls = None          # unresolved; False = jax has no profiler
# a TraceMe name carries its arguments as "name#k=v,k=v#"
_ANN_UNSAFE = str.maketrans({"#": "_", ",": ";", "=": ":"})


def _resolve_annotation_cls():
    """``jax.profiler.TraceAnnotation``, looked up once; False where jax
    (or its profiler) is missing — the span then does without."""
    global _annotation_cls
    try:
        from jax.profiler import TraceAnnotation as cls
    except Exception:                   # noqa: BLE001 — absence == off
        cls = False
    _annotation_cls = cls
    return cls


def _annotation(sp: "Span"):
    """An entered ``TraceAnnotation("lgbm/<name>")`` carrying the span's
    links and scalar arguments. Called only while a profiler session is
    open: an annotation made outside one would be inert."""
    kw = sp.links()
    for k, v in (sp.args or {}).items():
        if isinstance(v, str):
            kw[k] = v.translate(_ANN_UNSAFE)
        elif isinstance(v, (int, float, bool)):
            kw[k] = v
    ann = _annotation_cls("lgbm/" + sp.name, **kw)
    ann.__enter__()
    return ann


class Span:
    """One stretch of the program's own work; a context manager.

    ``parent`` is the span open on this thread when this one began,
    ``cause`` the span that handed the work to this thread (``carry``),
    set on a thread's outermost spans only — an inner span reaches it
    through ``parent``. ``args`` may be added to while the span is
    open; they ride on the ring/sink event written at exit.

    ``to_sinks``: whether the span reaches the sinks when NO tracer is
    installed. True here, so the always-on flight ring keeps the coarse
    spans (ingest chunks, step compiles, lrb windows, requests) as
    evidence; utils/timing.phase — per-iteration accounting — turns it
    off, or a 256-slot ring would hold nothing but the last iterations.
    Under ``tpu_trace`` every span is in the ring, and the ring feeds the
    sinks."""
    __slots__ = ("name", "cat", "args", "id", "parent", "cause", "tid",
                 "t0_ns", "t1_ns", "_ann")
    to_sinks = True

    def __init__(self, name: str, cat: str = "phase",
                 args: Optional[dict] = None):
        self.name, self.cat, self.args = name, cat, args
        self.id = 0
        self.parent = self.cause = self._ann = None
        self.tid = self.t0_ns = self.t1_ns = 0

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def links(self) -> dict:
        """{"id", "parent", "cause"} as span ids, the latter two only
        where there is one."""
        out = {"id": self.id}
        if self.parent is not None:
            out["parent"] = self.parent.id
        if self.cause is not None:
            out["cause"] = self.cause.id
        return out

    def __enter__(self):
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        self.id = next(_ids)
        self.tid = threading.get_native_id()
        if stack:
            self.parent = stack[-1]
        else:
            self.cause = getattr(_tls, "cause", None)
        stack.append(self)
        cls = _annotation_cls
        if cls is None:
            cls = _resolve_annotation_cls()
        if cls and cls.is_enabled():
            self._ann = _annotation(self)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = self.t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:             # exits out of order (a generator
            stack.remove(self)          # closed late): keep the rest sound
        # bounded-cardinality: span names are call-site string literals
        _registry.timer(self.name).add((t1 - self.t0_ns) / 1e9)
        tr = _tracer
        if tr is not None or (_sinks and self.to_sinks):
            args = self.links()
            if self.args:
                args.update(self.args)
            if tr is not None:
                tr.complete(self.name, self.cat,
                            (self.t0_ns - tr._t0_ns) / 1000.0, args,
                            end_us=(t1 - tr._t0_ns) / 1000.0)
            else:
                _sink_only_event(
                    self.name, self.cat, "X",
                    (self.t0_ns - _sink_t0_ns) / 1000.0,
                    dur_us=(t1 - self.t0_ns) / 1000.0, args=args)
        return False


def span(name: str, cat: str = "phase",
         args: Optional[dict] = None) -> Span:
    """``with span("ingest/chunk", cat="ingest", args={...}):`` — THE
    span site (module docstring). Always a registry timer; an
    annotation in any open profiler session; an event in the ring under
    ``tpu_trace`` and in every sink (the always-on flight ring keeps
    span evidence even with ``tpu_trace`` off)."""
    return Span(name, cat, args)


def current() -> Optional[Span]:
    """The innermost span open on the calling thread."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def carry(fn):
    """Bind the calling thread's open span to ``fn`` as the ``cause``
    of the spans ``fn`` opens on whichever thread runs it: hand
    ``carry(work)`` to an executor and the worker's spans name the span
    that queued them."""
    cause = current()

    def run(*a, **kw):
        prev = getattr(_tls, "cause", None)
        _tls.cause = cause
        try:
            return fn(*a, **kw)
        finally:
            _tls.cause = prev
    return run


def instant(name: str, cat: str = "event",
            args: Optional[dict] = None) -> None:
    tr = _tracer
    if tr is not None:
        tr.instant(name, cat, args)
    elif _sinks:
        _sink_only_event(name, cat, "i", _sink_now_us(), args=args)


_write_warned = False


def write() -> Optional[str]:
    """Flush the global tracer's buffer to its path; None when off.
    Never raises — tracing is an observability aid, not a failure
    mode (the atexit hook runs this) — but the FIRST failure logs a
    warning so an unwritable tpu_trace path is not a silent no-trace
    run (the run-report 'could not write' pattern)."""
    global _write_warned
    tr = _tracer
    if tr is None:
        return None
    try:
        return tr.write()
    except OSError as e:
        if not _write_warned:
            _write_warned = True
            try:
                from ..utils import log
                log.warning("could not write trace %s: %s", tr.path, e)
            except Exception:       # noqa: BLE001 — atexit teardown
                pass
        return None
