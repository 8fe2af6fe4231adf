"""Observability subsystem (lightgbm_tpu/obs/): registry semantics,
thread-safety under a hammer, run-report round-trip + versioning, the
slow-iteration watchdog, profiler smoke, end-to-end run reports from
both training drivers, and the phase-attribution lint.

Run with ``pytest -m obs``.
"""
import json
import os
import re
import threading

import pytest

from conftest import TEST_PARAMS, make_binary, make_regression

from lightgbm_tpu.obs.recorder import (RUN_REPORT_SCHEMA,
                                       RUN_REPORT_VERSION, RunRecorder,
                                       load_run_report)
from lightgbm_tpu.obs.registry import MetricsRegistry
from lightgbm_tpu.utils import log, timing

pytestmark = pytest.mark.obs

PKG = os.path.join(os.path.dirname(__file__), os.pardir, "lightgbm_tpu")


@pytest.fixture(autouse=True)
def _info_log_level():
    """Pin the global log level: earlier suite tests pass verbose=-1,
    which flips the process-wide level to FATAL and would swallow the
    info/warning lines these tests capture."""
    prev = log.get_level()
    log.set_level(log.LogLevel.INFO)
    yield
    log.set_level(prev)


# -- registry semantics ------------------------------------------------------

def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.add()
    c.add(41)
    assert c.value == 42
    assert reg.counter("c") is c           # get-or-create returns same
    g = reg.gauge("g")
    assert g.value is None
    g.set(7)
    g.set(3.5)
    assert g.value == 3.5
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 42
    assert snap["gauges"]["g"] == 3.5


def test_histogram_buckets_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 2.0, 3.0, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(10.5)
    # ranks: p25 -> first bucket (<=1), p50 -> <=2, p75 -> <=4,
    # p100 -> overflow reports the observed max
    assert h.percentile(0.25) == 1.0
    assert h.percentile(0.5) == 2.0
    assert h.percentile(0.75) == 4.0
    assert h.percentile(1.0) == 5.0
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["overflow"] == 1
    assert snap["min"] == 0.5 and snap["max"] == 5.0
    empty = reg.histogram("empty")
    assert empty.percentile(0.5) is None


def test_timer_total_count_max():
    reg = MetricsRegistry()
    t = reg.timer("t")
    t.add(0.25)
    t.add(1.0)
    t.add(0.5)
    assert t.count == 3
    assert t.total == pytest.approx(1.75)
    assert t.max == 1.0
    assert reg.snapshot()["phases"]["t"]["calls"] == 3


def test_timing_feeds_registry_and_report_order():
    """timing.add/phase store in the obs registry; report() sorts by
    total DESC and shows a max column."""
    timing.reset()
    timing.add("small", 0.001)
    timing.add("big", 2.0)
    timing.add("big", 1.0)
    with timing.phase("phased"):
        pass
    from lightgbm_tpu.obs import registry as obs
    items = {n: (tot, cnt) for n, tot, cnt, _ in
             obs.default_registry().timer_items()}
    assert items["big"][1] == 2 and items["phased"][1] == 1
    rep = timing.report()
    lines = rep.splitlines()
    assert lines[0].split()[0] == "big"     # dominant phase first
    assert "ms max" in lines[0]
    assert timing.seconds("big") == pytest.approx(3.0)
    timing.reset()
    assert timing.report() == ""


# -- thread-safety hammer ----------------------------------------------------

def test_registry_hammer_thread_safety():
    """N threads x M mutations on shared instruments (the ingest
    prefetch worker records from off-thread while the main thread
    accumulates phases): totals must be exact, no lost updates."""
    reg = MetricsRegistry()
    N, M = 8, 2000
    errs = []

    def work():
        try:
            c = reg.counter("bytes")
            t = reg.timer("phase")
            h = reg.histogram("lat")
            for i in range(M):
                c.add(3)
                t.add(0.001)
                h.observe(0.002)
                reg.gauge("hbm").set(i)
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work) for _ in range(N)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert reg.counter("bytes").value == 3 * N * M
    t = reg.timer("phase")
    assert t.count == N * M
    # every addition is the same fp op under the lock -> deterministic
    ref = 0.0
    for _ in range(N * M):
        ref += 0.001
    assert t.total == ref
    assert reg.histogram("lat").count == N * M


def test_timing_module_hammer_thread_safety():
    """The module-level timing API (the one the ingest worker calls)
    under the same hammer — the historical race was here."""
    timing.reset()
    N, M = 8, 1000

    def work():
        for _ in range(M):
            timing.add("hammer/add", 0.0001)
            with timing.phase("hammer/phase"):
                pass

    threads = [threading.Thread(target=work) for _ in range(N)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    from lightgbm_tpu.obs import registry as obs
    items = {n: cnt for n, _, cnt, _ in
             obs.default_registry().timer_items()}
    assert items["hammer/add"] == N * M
    assert items["hammer/phase"] == N * M
    timing.reset()


# -- run report --------------------------------------------------------------

def _small_report(path):
    reg = MetricsRegistry()
    reg.counter("ingest/h2d_bytes").add(1234)
    rec = RunRecorder(path=path, meta={"driver": "test"},
                      registry=reg).start()
    rec.observe_iteration(1, 0.01)
    rec.observe_iteration(2, 0.02)
    rec.record_eval(2, "training", "l2", 0.5)
    return rec.finish(leaves_per_iteration=[[7], [9]],
                      waves_per_iteration=[1, 1],
                      extra={"note": "x"})


@pytest.mark.parametrize("name", ["run.json", "run.jsonl"])
def test_run_report_roundtrip(tmp_path, name):
    path = str(tmp_path / name)
    built = _small_report(path)
    assert built["schema"] == RUN_REPORT_SCHEMA
    loaded = load_run_report(path)
    assert loaded["version"] == RUN_REPORT_VERSION
    assert loaded["meta"]["driver"] == "test"
    its = loaded["iterations"]
    assert [r["it"] for r in its] == [1, 2]
    assert its[0]["wall_s"] == pytest.approx(0.01)
    assert its[0]["leaves"] == [7] and its[1]["waves"] == 1
    assert its[1]["evals"]["training"]["l2"] == 0.5
    assert loaded["counters"]["ingest/h2d_bytes"] == 1234
    assert "train/iteration_s" in loaded["histograms"]
    assert loaded["extra"]["note"] == "x"


def test_run_report_version_refused(tmp_path):
    path = str(tmp_path / "run.json")
    _small_report(path)
    with open(path) as fh:
        doc = json.load(fh)
    doc["version"] = RUN_REPORT_VERSION + 1
    with open(path, "w") as fh:
        json.dump(doc, fh)
    with pytest.raises(ValueError, match="version"):
        load_run_report(path)
    with open(path, "w") as fh:
        json.dump({"schema": "something-else", "version": 1}, fh)
    with pytest.raises(ValueError, match="schema"):
        load_run_report(path)


def test_recorder_finish_idempotent(tmp_path):
    rec = RunRecorder(path=str(tmp_path / "r.json"),
                      registry=MetricsRegistry()).start()
    rec.observe_iteration(1, 0.01)
    first = rec.finish()
    assert first["iterations"]
    assert rec.finish() == {}               # second call is a no-op


# -- watchdog ----------------------------------------------------------------

def test_watchdog_triggers_on_slow_iteration():
    reg = MetricsRegistry()
    rec = RunRecorder(watchdog_factor=3.0, registry=reg).start()
    lines = []
    log.set_callback(lines.append)
    try:
        for it in range(1, 10):             # arm the trailing median
            rec.observe_iteration(it, 0.01)
        assert not any("slow iteration" in ln for ln in lines)
        rec.observe_iteration(10, 0.2)      # 20x the median
    finally:
        log.set_callback(None)
        rec.finish()
    hits = [ln for ln in lines if "slow iteration 10" in ln]
    assert hits and "phase table" in hits[0]
    assert reg.counter("watchdog/slow_iterations").value == 1


def test_watchdog_sync_spans_judged_separately():
    """Periodic drain iterations (kind="sync") legitimately absorb the
    queued dispatch backlog; they must be compared against other sync
    spans, not the issue-only iteration median — otherwise every drain
    interval would false-positive on an async backend."""
    reg = MetricsRegistry()
    rec = RunRecorder(watchdog_factor=3.0, registry=reg).start()
    lines = []
    log.set_callback(lines.append)
    try:
        for it in range(1, 41):
            if it % 8 == 0:             # the drain: 50x the issue time
                rec.observe_iteration(it, 0.5, kind="sync")
            else:
                rec.observe_iteration(it, 0.01)
    finally:
        log.set_callback(None)
        report = rec.finish()
    assert not any("slow iteration" in ln for ln in lines)
    assert report["iterations"][7]["sync"] is True
    assert "sync" not in report["iterations"][0]


def test_watchdog_disabled_at_zero_factor():
    rec = RunRecorder(watchdog_factor=0.0,
                      registry=MetricsRegistry()).start()
    lines = []
    log.set_callback(lines.append)
    try:
        for it in range(1, 10):
            rec.observe_iteration(it, 0.01)
        rec.observe_iteration(10, 5.0)
    finally:
        log.set_callback(None)
        rec.finish()
    assert not any("slow iteration" in ln for ln in lines)


# -- structured log prefix ---------------------------------------------------

def test_log_run_context_prefix():
    lines = []
    log.set_callback(lines.append)
    try:
        log.info("bare")
        log.set_run_context(lambda: (12.34, 140))
        log.info("prefixed")
        log.set_run_context(lambda: (1.0, None))
        log.info("no-iter")
        log.set_run_context(None)
        log.info("bare again")
    finally:
        log.set_run_context(None)
        log.set_callback(None)
    assert lines[0] == "[LightGBM-TPU] [Info] bare\n"
    assert lines[1] == "[LightGBM-TPU] [Info] [t+12.3s it=140] prefixed\n"
    assert lines[2] == "[LightGBM-TPU] [Info] [t+1.0s] no-iter\n"
    assert lines[3] == "[LightGBM-TPU] [Info] bare again\n"


def test_set_callback_thread_safe_under_writes():
    """set_callback flips while worker threads log: no exceptions, and
    every line lands in exactly one sink or stderr."""
    stop = threading.Event()
    errs = []

    def writer():
        try:
            while not stop.is_set():
                # debug under the default INFO level: the line is
                # filtered after the locked state read, so the race is
                # exercised without spamming stderr between flips
                log.debug("hammer line")
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    sink = []
    threads = [threading.Thread(target=writer) for _ in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(200):
            log.set_callback(sink.append)
            log.set_callback(None)
    finally:
        stop.set()
        for th in threads:
            th.join()
        log.set_callback(None)
    assert not errs


# -- profiler ----------------------------------------------------------------

def test_profile_window_smoke(tmp_path):
    """tpu_profile_dir on the CPU backend produces a trace directory
    with capture files (skip where the profiler is unavailable)."""
    from lightgbm_tpu.obs import profiler as prof
    if not prof.profiler_available():
        pytest.skip("jax.profiler unavailable")
    import jax.numpy as jnp
    d = tmp_path / "trace"
    pw = prof.ProfileWindow(str(d), iters=2)
    for i in range(1, 5):
        pw.iter_begin(i)
        jnp.sum(jnp.arange(256)).block_until_ready()
        pw.iter_end(i)
    pw.close()
    if not pw.enabled:
        pytest.skip("start_trace failed on this backend")
    files = [p for p in d.rglob("*") if p.is_file()]
    assert files, "profiler produced no trace files"


def test_profile_window_iters_bracketing(monkeypatch, tmp_path):
    """iters=N starts at iteration 2 and stops after N iterations;
    iters=0 spans the whole run until close()."""
    from lightgbm_tpu.obs import profiler as prof
    calls = []
    monkeypatch.setattr(prof, "profiler_available", lambda: True)

    class FakeProfiler:
        @staticmethod
        def start_trace(d):
            calls.append(("start", d))

        @staticmethod
        def stop_trace():
            calls.append(("stop", None))

    import jax
    monkeypatch.setattr(jax, "profiler", FakeProfiler)
    pw = prof.ProfileWindow(str(tmp_path), iters=2)
    for i in range(1, 6):
        pw.iter_begin(i)
        pw.iter_end(i)
    pw.close()
    assert [c[0] for c in calls] == ["start", "stop"]
    calls.clear()
    pw = prof.ProfileWindow(str(tmp_path), iters=0)
    pw.iter_begin(1)
    pw.iter_end(1)
    assert [c[0] for c in calls] == ["start"]   # open until close
    pw.close()
    assert [c[0] for c in calls] == ["start", "stop"]


# -- end-to-end run reports --------------------------------------------------

def _fit_with_report(path, n_iter=8):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.metrics import create_metrics
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    X, y = make_regression(n=640)
    cfg = Config().set({**TEST_PARAMS, "objective": "regression",
                        "metric": "l2", "num_iterations": n_iter,
                        "is_provide_training_metric": True,
                        "tpu_run_report": path})
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("regression", cfg)
    obj.init(ds.metadata, ds.num_data)
    mets = create_metrics(["l2"], cfg, ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, mets)
    g.train()
    return g


def test_gbdt_train_writes_run_report(tmp_path):
    """The acceptance-shaped run: a CPU-backend training with
    tpu_run_report set produces a parseable report with per-iteration
    timings, the phase table, and >= 3 ingest/transfer counters."""
    path = str(tmp_path / "run.json")
    g = _fit_with_report(path, n_iter=8)
    rep = load_run_report(path)
    its = rep["iterations"]
    assert 1 <= len(its) <= 8
    assert all(r["wall_s"] > 0 for r in its)
    # leaves filled from ONE stacked download at finish; waves derived
    assert all(len(r["leaves"]) == 1 and r["leaves"][0] >= 1
               for r in its)
    assert all(r["waves"] >= 1 for r in its)
    # eval values captured per iteration
    assert its[0]["evals"]["training"]["l2"] > 0
    # phase table present, sorted by total desc
    totals = [v["total_s"] for v in rep["phases"].values()]
    assert totals == sorted(totals, reverse=True)
    assert "train/step_dispatch" in rep["phases"]
    assert rep["phases"]["train/step_dispatch"]["calls"] >= len(its)
    # >= 3 ingest/transfer counters (host binner + bulk upload + syncs)
    xfer = {k: v for k, v in rep["counters"].items()
            if k.startswith(("ingest/", "transfer/"))}
    assert len(xfer) >= 3, xfer
    assert rep["meta"]["driver"] == "gbdt.train"
    assert rep["extra"]["trained_iterations"] == g.iter_
    # the run prefix was uninstalled at finish
    lines = []
    log.set_callback(lines.append)
    try:
        log.info("post-run")
    finally:
        log.set_callback(None)
    assert "[t+" not in lines[0]


def test_engine_train_writes_run_report(tmp_path):
    """python-API path: engine.train with tpu_run_report spans
    iterations via the internal callback and writes the report."""
    from lightgbm_tpu.basic import Dataset
    from lightgbm_tpu.engine import train

    X, y = make_binary(n=640)
    path = str(tmp_path / "engine_run.jsonl")
    params = {**TEST_PARAMS, "objective": "binary", "metric": "auc",
              "tpu_run_report": path}
    d = Dataset(X, label=y)
    # valid = train set: exercises eval recording without compiling a
    # second valid-passenger grower shape (keeps tier-1 fast)
    bst = train(params, d, num_boost_round=4, valid_sets=[d],
                verbose_eval=False)
    assert bst.current_iteration() >= 1
    rep = load_run_report(path)
    assert rep["meta"]["driver"] == "engine.train"
    assert len(rep["iterations"]) >= 1
    assert all(r["wall_s"] > 0 for r in rep["iterations"])
    # the valid set's metric flowed through evaluation_result_list
    ev = rep["iterations"][0].get("evals", {})
    assert any("auc" in m for ds_m in ev.values() for m in ds_m)


# -- phase-attribution lint --------------------------------------------------

# phases that measure dispatch-issue time BY DESIGN (documented in
# models/gbdt.py: the fused step is async; its device time is drained
# by train/queue_drain and the pipelined eval materialization)
_WATCH_ALLOWLIST = {"train/step_dispatch"}
# a block "synchronizes itself" when it materializes to host or runs
# the self-syncing measure harness
_SYNC_TOKENS = (".watch(", "np.asarray", "timing.measure", "measure(")
_DISPATCH_TOKENS = ("jnp.", "jax.")


def _phase_blocks(path):
    """Yield (phase_name, block_text) for every `with timing.phase(...)`
    in a source file (block = following lines with deeper indent)."""
    src = open(path).read().splitlines()
    pat = re.compile(r"with timing\.phase\(\s*f?[\"']([^\"']+)[\"']")
    for i, ln in enumerate(src):
        m = pat.search(ln)
        if not m:
            continue
        indent = len(ln) - len(ln.lstrip())
        body = [ln]
        for nxt in src[i + 1:]:
            if nxt.strip() and (len(nxt) - len(nxt.lstrip())) <= indent:
                break
            body.append(nxt)
        yield m.group(1), "\n".join(body)


def test_phase_blocks_register_watch():
    """Every timing.phase block in ops/ and models/ that dispatches jax
    work must .watch(...) its output (or synchronize explicitly) so
    device time is attributed to the phase that issued it — otherwise
    it silently lands in whichever later phase first syncs."""
    offenders = []
    for sub in ("ops", "models"):
        root = os.path.join(PKG, sub)
        for fn in sorted(os.listdir(root)):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            for name, block in _phase_blocks(path):
                dispatches = any(t in block for t in _DISPATCH_TOKENS)
                synced = any(t in block for t in _SYNC_TOKENS)
                if (dispatches and not synced
                        and name not in _WATCH_ALLOWLIST):
                    offenders.append(f"{sub}/{fn}: {name}")
    assert not offenders, (
        "timing.phase blocks dispatch jax work without .watch()/sync "
        f"(device time will be misattributed): {offenders}")


# -- log-bucketed latency quantiles ------------------------------------------

def test_log_buckets_shape():
    from lightgbm_tpu.obs.registry import LATENCY_BUCKETS_S, log_buckets
    b = log_buckets(1e-3, 1.0, per_decade=10)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1.0
    assert len(b) == 31                     # 3 decades x 10 + 1
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.1, rel=1e-9) for r in ratios)
    # the preset spans predict-dispatch to window-wall magnitudes
    assert LATENCY_BUCKETS_S[0] == pytest.approx(1e-6)
    assert LATENCY_BUCKETS_S[-1] >= 60.0


def test_quantiles_vs_numpy_percentile():
    """Interpolated histogram quantiles track numpy.percentile within
    one log-bucket's resolution on a realistic latency mixture."""
    import numpy as np

    from lightgbm_tpu.obs.registry import (MetricsRegistry,
                                           latency_histogram)
    rng = np.random.default_rng(3)
    # bimodal: fast path ~2ms + slow tail ~80ms (the serving shape)
    fast = rng.lognormal(np.log(2e-3), 0.25, size=4000)
    slow = rng.lognormal(np.log(8e-2), 0.3, size=250)
    samples = np.concatenate([fast, slow])
    reg = MetricsRegistry()
    h = latency_histogram("lat", reg)
    for v in samples:
        h.observe(float(v))
    bucket_ratio = 10 ** (1 / 12)           # adjacent bound spacing
    for q in (0.5, 0.9, 0.95, 0.99):
        est = h.percentile(q)
        ref = float(np.percentile(samples, 100 * q))
        assert ref / bucket_ratio <= est <= ref * bucket_ratio, \
            f"q={q}: est {est:g} vs numpy {ref:g}"
    # interpolation stays inside the observed range
    assert h.percentile(1.0) == pytest.approx(samples.max())
    snap = h.snapshot()
    assert snap["p95"] is not None and snap["p50"] < snap["p95"]


def test_quantiles_exact_degenerate_cases():
    from lightgbm_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    h = reg.histogram("one", buckets=(1.0, 2.0))
    h.observe(1.5)
    # a single sample reports itself regardless of bucket width
    assert h.percentile(0.5) == pytest.approx(1.5)
    const = reg.histogram("const", buckets=(1.0, 2.0))
    for _ in range(100):
        const.observe(1.5)
    for q in (0.01, 0.5, 0.99):
        assert const.percentile(q) == pytest.approx(1.5)
    assert reg.histogram("one").quantiles() == {
        "p50": pytest.approx(1.5), "p95": pytest.approx(1.5),
        "p99": pytest.approx(1.5), "p999": pytest.approx(1.5)}


# -- live metrics exporter ---------------------------------------------------

def _prom_lines_ok(text):
    """Every non-comment line is `name{labels} value` with a legal
    Prometheus metric name."""
    pat = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$")
    lines = [ln for ln in text.splitlines() if ln and
             not ln.startswith("#")]
    assert lines, "no samples rendered"
    for ln in lines:
        assert pat.match(ln), f"bad exposition line: {ln!r}"
    return lines


def test_prometheus_text_rendering():
    from lightgbm_tpu.obs.export import prometheus_text
    from lightgbm_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("ingest/h2d_bytes").add(1234)
    reg.gauge("device/hbm_bytes_in_use").set(5e8)
    reg.timer("train/step_dispatch").add(0.25)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = prometheus_text(reg.snapshot())
    lines = _prom_lines_ok(text)
    joined = "\n".join(lines)
    assert "lgbm_tpu_ingest_h2d_bytes_total 1234" in joined
    assert "lgbm_tpu_device_hbm_bytes_in_use 500000000" in joined
    assert "lgbm_tpu_train_step_dispatch_seconds_total 0.25" in joined
    assert "lgbm_tpu_train_step_dispatch_calls_total 1" in joined
    # histogram: cumulative buckets + +Inf == count
    assert 'lgbm_tpu_lat_bucket{le="0.1"} 1' in joined
    assert 'lgbm_tpu_lat_bucket{le="1"} 2' in joined
    assert 'lgbm_tpu_lat_bucket{le="+Inf"} 3' in joined
    assert "lgbm_tpu_lat_count 3" in joined


def test_exporter_writes_during_run(tmp_path):
    """The exporter snapshots the registry DURING a run: .prom is
    replaced and .jsonl appended on the interval while counters are
    still moving — not a finish-time artifact."""
    import time as _t

    from lightgbm_tpu.obs.export import MetricsExporter
    from lightgbm_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    base = str(tmp_path / "live")
    ex = MetricsExporter(base_path=base, interval_s=0.05,
                         registry=reg).start()
    try:
        deadline = _t.monotonic() + 5.0
        while ex.snapshots_written < 3 and _t.monotonic() < deadline:
            reg.counter("work/items").add(1)
            _t.sleep(0.01)
        assert ex.snapshots_written >= 3
        # files exist and parse WHILE the run is still going
        text = open(ex.prom_path).read()
        _prom_lines_ok(text)
        assert "lgbm_tpu_work_items_total" in text
        rows = [json.loads(ln) for ln in open(ex.jsonl_path)]
        assert len(rows) >= 2
        assert rows[0]["ts"] <= rows[-1]["ts"]
        assert rows[-1]["counters"]["work/items"] >= 1
        # time series is append-only: later rows never lose counts
        counts = [r["counters"].get("work/items", 0) for r in rows]
        assert counts == sorted(counts)
    finally:
        ex.stop()
    # suffix stripping: pointing the knob at the .jsonl works too
    ex2 = MetricsExporter(base_path=base + ".jsonl", interval_s=5,
                          registry=reg)
    assert ex2.base_path == base


def test_exporter_http_endpoint(tmp_path):
    """GET /metrics over the stdlib server scrapes a live registry;
    /metrics.json returns the raw snapshot; others 404."""
    import urllib.error
    import urllib.request

    from lightgbm_tpu.obs.export import MetricsExporter
    from lightgbm_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("serve/requests").add(7)
    ex = MetricsExporter(base_path=str(tmp_path / "m"), interval_s=60,
                         port=0, registry=reg).start()
    try:
        port = ex.http_port
        assert port
        url = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            assert r.status == 200
            body = r.read().decode()
        _prom_lines_ok(body)
        assert "lgbm_tpu_serve_requests_total 7" in body
        with urllib.request.urlopen(f"{url}/metrics.json",
                                    timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["counters"]["serve/requests"] == 7
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/other", timeout=10)
    finally:
        ex.stop()
    # the port is released after stop
    assert ex.http_port is None


def test_exporter_ensure_from_config_and_shutdown(tmp_path):
    from lightgbm_tpu.obs import export as obs_export
    obs_export.shutdown()
    try:
        assert obs_export.ensure_from_config({}) is None
        ex = obs_export.ensure_from_config(
            {"tpu_metrics_export": str(tmp_path / "g"),
             "tpu_metrics_interval_s": "30"})
        assert ex is not None and ex.interval_s == 30.0
        # later boosters JOIN the running exporter
        assert obs_export.ensure_from_config(
            {"tpu_metrics_export": str(tmp_path / "g")}) is ex
        assert os.path.exists(ex.prom_path)   # immediate first snapshot
    finally:
        obs_export.shutdown()
    assert obs_export.global_exporter() is None


def test_exporter_survives_port_in_use(tmp_path):
    """A taken (or bogus) HTTP port degrades to file-only export with
    a warning — never an exception out of GBDT init."""
    from lightgbm_tpu.obs.export import MetricsExporter
    from lightgbm_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    ex1 = MetricsExporter(base_path=str(tmp_path / "a"), interval_s=60,
                          port=0, registry=reg).start()
    lines = []
    log.set_callback(lines.append)
    try:
        ex2 = MetricsExporter(base_path=str(tmp_path / "b"),
                              interval_s=60, port=ex1.http_port,
                              registry=reg).start()
        assert ex2.http_port is None        # no server, no crash
        assert os.path.exists(ex2.prom_path)  # files still flow
        ex2.stop()
        ex3 = MetricsExporter(base_path=str(tmp_path / "c"),
                              interval_s=60, port=70000,
                              registry=reg).start()
        assert ex3.http_port is None
        ex3.stop()
    finally:
        log.set_callback(None)
        ex1.stop()
    assert sum("metrics HTTP endpoint" in ln for ln in lines) == 2


def test_exporter_unwritable_path_warns_once(tmp_path):
    """An unwritable export destination logs ONE diagnostic and keeps
    the run alive (snapshots keep silently retrying)."""
    from lightgbm_tpu.obs.export import MetricsExporter
    from lightgbm_tpu.obs.registry import MetricsRegistry
    bad = str(tmp_path / "f")
    (tmp_path / "f").write_text("")         # file where a DIR is needed
    lines = []
    log.set_callback(lines.append)
    try:
        ex = MetricsExporter(base_path=bad + "/sub/base",
                             interval_s=60,
                             registry=MetricsRegistry()).start()
        ex._write_once()                     # second failure: no spam
        ex.stop(final_snapshot=True)         # third: still quiet
    finally:
        log.set_callback(None)
    assert sum("metrics export" in ln and "failing" in ln
               for ln in lines) == 1


def test_exporter_config_mismatch_warns(tmp_path):
    from lightgbm_tpu.obs import export as obs_export
    obs_export.shutdown()
    lines = []
    log.set_callback(lines.append)
    try:
        ex = obs_export.ensure_from_config(
            {"tpu_metrics_export": str(tmp_path / "a")})
        assert obs_export.ensure_from_config(
            {"tpu_metrics_export": str(tmp_path / "b")}) is ex
    finally:
        log.set_callback(None)
        obs_export.shutdown()
    assert any("ignored for this process" in ln for ln in lines)


def test_lrb_window_wall_quantiles_per_driver():
    """A second driver's quantile summary must not inherit an earlier
    run's windows (the process-global instrument stays cumulative for
    the exporter; the summary is per-run)."""
    import io

    from lightgbm_tpu.lrb import LrbDriver
    d1 = LrbDriver(cache_size=1 << 16, window_size=256,
                   sample_size=128, cutoff=0.5, sampling=1,
                   result_file=io.StringIO())
    d1._wall_hist.observe(42.0)             # stand-in for a slow run
    assert d1.window_wall_quantiles()["p99"] == pytest.approx(42.0)
    d2 = LrbDriver(cache_size=1 << 16, window_size=256,
                   sample_size=128, cutoff=0.5, sampling=1,
                   result_file=io.StringIO())
    assert d2.window_wall_quantiles() is None


def test_training_run_feeds_live_exporter(tmp_path):
    """The config-wired path: a training run with tpu_metrics_export
    set starts the process-global exporter from GBDT.init and registry
    snapshots land on disk while the run proceeds."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.obs import export as obs_export
    from lightgbm_tpu.objectives import create_objective

    obs_export.shutdown()
    try:
        X, y = make_regression(n=640)
        cfg = Config().set({**TEST_PARAMS, "objective": "regression",
                            "num_iterations": 3,
                            "tpu_metrics_export": str(tmp_path / "live"),
                            "tpu_metrics_interval_s": 0.05})
        ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
        obj = create_objective("regression", cfg)
        obj.init(ds.metadata, ds.num_data)
        g = GBDT()
        g.init(cfg, ds, obj, ())
        ex = obs_export.global_exporter()
        assert ex is not None, "GBDT.init did not start the exporter"
        g.train()
        assert ex.snapshots_written >= 1
        rows = [json.loads(ln) for ln in open(ex.jsonl_path)]
        assert rows and rows[-1]["counters"]
        _prom_lines_ok(open(ex.prom_path).read())
    finally:
        obs_export.shutdown()


# -- report <-> trace cross-link ---------------------------------------------

def test_run_report_meta_gains_trace_path(tmp_path):
    """A training run with BOTH tpu_run_report and tpu_trace set
    cross-links them: the report's meta carries trace_path and the
    trace file exists with iteration spans by the time finish()
    returns."""
    from lightgbm_tpu.obs import trace
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    report_path = str(tmp_path / "run.json")
    trace_path = str(tmp_path / "run_trace.json")
    trace.stop()
    try:
        X, y = make_regression(n=640)
        cfg = Config().set({**TEST_PARAMS, "objective": "regression",
                            "num_iterations": 3,
                            "tpu_run_report": report_path,
                            "tpu_trace": trace_path})
        ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
        obj = create_objective("regression", cfg)
        obj.init(ds.metadata, ds.num_data)
        g = GBDT()
        g.init(cfg, ds, obj, ())
        g.train()
        rep = load_run_report(report_path)
        assert rep["meta"]["trace_path"] == trace_path
        doc = json.load(open(trace_path))
        names = {e["name"] for e in doc["traceEvents"]
                 if e["ph"] == "X"}
        assert "train/iteration" in names
        assert "train/step_dispatch" in names
    finally:
        trace.stop()


def test_obs_marker_registered():
    """`pytest -m obs` must select this suite: the marker is declared
    in pyproject (unknown markers would warn and select nothing)."""
    with open(os.path.join(PKG, os.pardir, "pyproject.toml")) as fh:
        doc = fh.read()
    assert re.search(r'^\s*"obs:', doc, re.M), \
        "pytest marker 'obs' missing from pyproject.toml"
