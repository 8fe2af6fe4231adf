"""Cross-thread span tracer (lightgbm_tpu/obs/trace.py): trace-event
JSON schema, ring-buffer bounds, multi-thread hammer, timing.phase and
step-cache integration, watchdog instants, and the end-to-end LRB
two-window trace (spans from the ingest worker AND the main thread in
one Perfetto-loadable file).

Run with ``pytest -m obs``.
"""
import json
import threading

import pytest

from lightgbm_tpu.obs import trace
from lightgbm_tpu.obs.trace import Tracer

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """Every test leaves the process-global tracer uninstalled."""
    trace.stop()
    yield
    trace.stop()


# -- schema round-trip -------------------------------------------------------

def _valid_event(ev):
    assert ev["ph"] in ("X", "i", "M"), ev
    assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    if ev["ph"] == "X":
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    elif ev["ph"] == "i":
        assert isinstance(ev["ts"], (int, float))
        assert ev["s"] in ("t", "p", "g")
    else:            # metadata: thread/process name + rank labels
        assert ev["name"] in ("thread_name", "process_name",
                              "process_labels")
        if ev["name"] == "process_labels":
            assert "labels" in ev["args"]    # Chrome labels record
        else:
            assert "name" in ev["args"]


def test_trace_event_schema_roundtrip(tmp_path):
    """Spans + instants -> write -> parse: every event satisfies the
    Chrome trace-event contract (valid ph/ts/pid/tid) and the document
    is the Perfetto-loadable traceEvents form."""
    path = str(tmp_path / "t.json")
    tr = Tracer(path)
    with tr.span("outer", cat="window", args={"window": 1}):
        with tr.span("inner", cat="iteration", args={"it": 3}):
            pass
    tr.instant("marker", cat="event", args={"why": "test"})
    assert tr.write() == path
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["schema"] == "lightgbm-tpu/trace"
    assert doc["otherData"]["dropped_events"] == 0
    for ev in doc["traceEvents"]:
        _valid_event(ev)
    spans = {e["name"]: e for e in doc["traceEvents"]
             if e["ph"] == "X"}
    assert set(spans) == {"outer", "inner"}
    # nesting: inner lies within outer on the same thread
    o, i = spans["outer"], spans["inner"]
    assert o["tid"] == i["tid"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    assert i["args"] == {"it": 3}
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "marker"
    # thread-name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])


def test_trace_write_idempotent_and_atomic(tmp_path):
    """write() replaces the file with the ring's current contents —
    callable after every window of a live loop."""
    path = str(tmp_path / "t.json")
    tr = Tracer(path)
    with tr.span("a"):
        pass
    tr.write()
    first = json.load(open(path))
    with tr.span("b"):
        pass
    tr.write()
    second = json.load(open(path))
    n_first = sum(e["ph"] == "X" for e in first["traceEvents"])
    n_second = sum(e["ph"] == "X" for e in second["traceEvents"])
    assert (n_first, n_second) == (1, 2)


# -- ring buffer -------------------------------------------------------------

def test_ring_buffer_bounds_and_dropped_count(tmp_path):
    """The buffer keeps the most recent ``capacity`` events and counts
    what it evicted (capacity floors at MIN_BUFFER_EVENTS)."""
    tr = Tracer(str(tmp_path / "t.json"), capacity=10)
    assert tr.capacity == trace.MIN_BUFFER_EVENTS
    n = tr.capacity + 100
    for i in range(n):
        tr.instant(f"e{i}")
    assert tr.event_count() == tr.capacity
    assert tr.dropped_events == 100
    doc = tr.trace_document()
    assert doc["otherData"]["dropped_events"] == 100
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "i"]
    assert names[0] == "e100" and names[-1] == f"e{n - 1}"


def test_multithread_span_hammer(tmp_path):
    """N threads record spans + instants concurrently (the ingest
    worker / exporter / main-thread mix): no exceptions, no lost
    events below capacity, one tid row per thread."""
    tr = Tracer(str(tmp_path / "t.json"), capacity=100_000)
    N, M = 8, 500
    errs = []

    def work(k):
        try:
            for i in range(M):
                with tr.span(f"w{k}", cat="hammer", args={"i": i}):
                    pass
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work, args=(k,), name=f"ham-{k}")
               for k in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert tr.event_count() == N * M
    assert tr.dropped_events == 0
    doc = tr.trace_document()
    span_tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert len(span_tids) == N
    # every hammer thread got a thread_name metadata record
    named = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {f"ham-{k}" for k in range(N)} <= named


# -- module-global API -------------------------------------------------------

def test_write_failure_warns_once_and_returns_none(tmp_path):
    """An unwritable tpu_trace path is not a silent no-trace run: the
    first failed flush warns, later ones stay quiet, training-side
    callers just see None."""
    from lightgbm_tpu.utils import log
    bad_parent = tmp_path / "f"
    bad_parent.write_text("")               # file where a dir is needed
    trace.configure(str(bad_parent / "sub" / "t.json"))
    trace._write_warned = False
    lines = []
    prev_level = log.get_level()
    log.set_level(log.LogLevel.INFO)        # earlier tests may pin FATAL
    log.set_callback(lines.append)
    try:
        assert trace.write() is None
        assert trace.write() is None
    finally:
        log.set_callback(None)
        log.set_level(prev_level)
        trace._write_warned = False
    assert sum("could not write trace" in ln for ln in lines) == 1


def test_global_tracer_off_is_noop(tmp_path):
    assert not trace.enabled()
    with trace.span("ignored"):
        pass
    trace.instant("ignored")
    assert trace.write() is None


def test_configure_and_ensure_from_config(tmp_path):
    path = str(tmp_path / "t.json")
    assert trace.ensure_from_config({"no_trace_here": 1}) is None
    tr = trace.ensure_from_config({"tpu_trace": path,
                                   "tpu_trace_buffer": "2048"})
    assert tr is not None and tr.capacity == 2048
    assert trace.enabled()
    # same path: idempotent (buffer survives)
    with trace.span("kept"):
        pass
    assert trace.ensure_from_config({"tpu_trace": path}) is tr
    assert tr.event_count() == 1
    # Config-object flavor
    from lightgbm_tpu.config import Config
    cfg = Config().set({"tpu_trace": path})
    assert trace.ensure_from_config(cfg) is tr
    assert trace.write() == path
    assert json.load(open(path))["traceEvents"]


def test_configure_same_path_grows_buffer(tmp_path):
    """A later config naming the same path with a LARGER
    tpu_trace_buffer grows the ring in place (events kept); a smaller
    or default capacity never shrinks it mid-run."""
    path = str(tmp_path / "t.json")
    tr = trace.configure(path, capacity=2048)
    with trace.span("kept"):
        pass
    assert trace.configure(path, capacity=8192) is tr
    assert tr.capacity == 8192
    assert tr.event_count() == 1
    trace.configure(path)                   # default (65536 > 8192): grows
    assert tr.capacity == trace.DEFAULT_BUFFER_EVENTS
    assert trace.configure(path, capacity=1024) is tr
    assert tr.capacity == trace.DEFAULT_BUFFER_EVENTS  # never shrinks


def test_configure_retarget_flushes_old_buffer(tmp_path):
    """Re-targeting the global tracer to a new path first flushes the
    old buffer to its own file — post-flush spans (a predict after
    train's finish()) are never silently dropped."""
    a, b = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    trace.configure(a)
    with trace.span("late-span"):
        pass
    tr_b = trace.configure(b)
    assert tr_b.path == b
    doc = json.load(open(a))
    assert [e["name"] for e in doc["traceEvents"]
            if e["ph"] == "X"] == ["late-span"]


def test_atomic_write_failure_leaves_no_debris(tmp_path):
    """utils/fileio.atomic_write: a failing write keeps the original
    file intact and removes the temp file."""
    import os

    from lightgbm_tpu.utils.fileio import atomic_write
    path = str(tmp_path / "f.json")
    with atomic_write(path) as fh:
        fh.write("good")
    with pytest.raises(RuntimeError):
        with atomic_write(path) as fh:
            fh.write("partial")
            raise RuntimeError("boom")
    assert open(path).read() == "good"
    assert os.listdir(tmp_path) == ["f.json"]


def test_timing_phase_emits_trace_span(tmp_path):
    """Every timing.phase block is also a span on the active trace —
    same name, recorded on the calling thread."""
    from lightgbm_tpu.utils import timing
    tr = trace.configure(str(tmp_path / "t.json"))
    with timing.phase("unit/traced_phase"):
        pass
    doc = tr.trace_document()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in spans] == ["unit/traced_phase"]
    assert spans[0]["cat"] == "phase"
    timing.reset()


def test_step_cache_events_and_watchdog_instant(tmp_path):
    """Step-cache hits/misses and watchdog firings land as trace
    events exactly where they happened."""
    from lightgbm_tpu.obs.recorder import RunRecorder
    from lightgbm_tpu.obs.registry import MetricsRegistry
    from lightgbm_tpu.ops import step_cache
    tr = trace.configure(str(tmp_path / "t.json"))
    key = ("trace-test-key",)
    step_cache.get_step(key, lambda: (lambda *a: a))
    step_cache.get_step(key, lambda: (lambda *a: a))
    rec = RunRecorder(watchdog_factor=3.0,
                      registry=MetricsRegistry()).start()
    for it in range(1, 10):
        rec.observe_iteration(it, 0.01)
    rec.observe_iteration(10, 0.5)          # 50x the trailing median
    rec.finish()
    names = [e["name"] for e in tr.trace_document()["traceEvents"]
             if e["ph"] == "i"]
    assert "step_cache/miss" in names
    assert "step_cache/hit" in names
    wd = [e for e in tr.trace_document()["traceEvents"]
          if e["ph"] == "i" and e["name"] == "watchdog/slow_iteration"]
    assert wd and wd[0]["args"]["it"] == 10


# -- end-to-end: the acceptance run ------------------------------------------

def test_lrb_two_window_trace_end_to_end(tmp_path):
    """A single lrb run with tpu_trace set produces ONE
    Perfetto-loadable trace containing spans from >= 2 threads (main +
    ingest prefetch worker) and >= 3 span kinds (window, iteration,
    ingest chunk), plus per-window derive/train/evaluate walls in the
    results."""
    import io

    from lightgbm_tpu.lrb import LrbDriver, synthetic_trace
    path = str(tmp_path / "lrb_trace.json")
    out = io.StringIO()
    drv = LrbDriver(cache_size=1 << 16, window_size=256,
                    sample_size=128, cutoff=0.5, sampling=1,
                    result_file=out,
                    extra_params={"tpu_trace": path,
                                  "num_iterations": 8,
                                  # force the device-ingest pipeline so
                                  # the prefetch worker thread records
                                  "tpu_ingest": 1})
    for seq, oid, size, cost in synthetic_trace(512, n_objects=60):
        drv.process_request(seq, oid, size, cost)
    assert len(drv.results) == 2
    # per-window phase table: derive/train/evaluate wall seconds
    r2 = drv.results[1]
    assert r2["derive_s"] >= 0 and r2["train_s"] > 0
    assert r2["evaluate_s"] >= 0          # window 2 scored window 1's model
    assert r2["window_wall_s"] >= r2["train_s"]
    q = drv.window_wall_quantiles()
    assert q and q["p50"] > 0 and q["p99"] >= q["p50"]

    # the trace was flushed DURING the run (after each window)
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    for ev in evs:
        _valid_event(ev)
    spans = [e for e in evs if e["ph"] == "X"]
    cats = {e["cat"] for e in spans}
    assert {"window", "iteration", "ingest"} <= cats, cats
    assert len({e["tid"] for e in spans}) >= 2, \
        "expected spans from main + ingest worker threads"
    names = {e["name"] for e in spans}
    assert {"window", "lrb/derive", "lrb/train", "train/iteration",
            "ingest/prep_chunk", "ingest/chunk"} <= names, names
    # the ingest worker's spans are on a different tid than the window
    win_tids = {e["tid"] for e in spans if e["name"] == "window"}
    prep_tids = {e["tid"] for e in spans
                 if e["name"] == "ingest/prep_chunk"}
    assert prep_tids and not (prep_tids & win_tids)
    # ... and each names the bin_matrix span that queued it as its cause
    bin_ids = {e["args"]["id"] for e in spans
               if e["name"] == "binning/bin_matrix"}
    assert {e["args"]["cause"] for e in spans
            if e["name"] == "ingest/prep_chunk"} <= bin_ids
