"""The one span API (lightgbm_tpu/obs/trace.py ``span``, utils/timing.py
``phase``): what a span records, that it always feeds its registry timer,
that any open ``jax.profiler`` session sees it, that the step cache
attributes jax's compile events to its own span only, and that the device
side carries stable names (``name=`` on every ``pallas_call``,
``jax.named_scope`` in the fused step).

Run with ``pytest -m obs``.
"""
import ast
import functools
import json
import threading
from pathlib import Path

import numpy as np
import pytest

from conftest import TEST_PARAMS, make_binary
from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.obs import trace
from lightgbm_tpu.utils import timing

pytestmark = pytest.mark.obs

ROOT = Path(__file__).resolve().parent.parent
OPS = ROOT / "lightgbm_tpu" / "ops"
# the scopes of the fused step, as ISSUE 26 item 4 lists them
STEP_SCOPES = ("lgbm/gradients", "lgbm/root_hist", "lgbm/wave/hist",
               "lgbm/wave/split_find", "lgbm/wave/bookkeep",
               "lgbm/leaf_values", "lgbm/score_update", "lgbm/valid_scores")


@pytest.fixture(autouse=True)
def _no_global_tracer():
    trace.stop()
    yield
    trace.stop()


@pytest.fixture
def events():
    """Every event the span site emits, through a sink (no tracer)."""
    got = []
    trace.add_sink(got.append)
    yield got
    trace.remove_sink(got.append)


def _timer(name):
    return {n: (total, calls) for n, total, calls, _ in
            obs.default_registry().timer_items()}.get(name, (0.0, 0))


def _booster(n=640, valid=False, **params):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    X, y = make_binary(n=n)
    cfg = Config().set({**TEST_PARAMS, "objective": "binary", **params})
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, ())
    if valid:
        Xv, yv = make_binary(n=256, seed=7)
        g.add_valid_data(ds.create_valid(Xv, Metadata(label=yv)), ())
    return g


# -- what a span records -----------------------------------------------------

def test_spans_nest_and_carry_parent(events):
    assert trace.current() is None
    with trace.span("unit/outer", cat="window", args={"window": 1}) as o:
        assert trace.current() is o and o.parent is None
        with trace.span("unit/inner", cat="serve") as i:
            assert trace.current() is i and i.parent is o
            with timing.phase("unit/innermost") as ph:
                assert trace.current() is ph and ph.parent is i
        assert trace.current() is o
    assert trace.current() is None
    inner, outer = events                   # written at exit: inner first
    assert (inner["name"], outer["name"]) == ("unit/inner", "unit/outer")
    assert inner["args"] == {"id": i.id, "parent": o.id}
    assert outer["args"] == {"id": o.id, "window": 1}
    assert inner["tid"] == outer["tid"] == threading.get_native_id()
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert o.seconds >= i.seconds >= ph.seconds > 0.0


def test_a_phase_reaches_the_sinks_through_the_ring_only(events, tmp_path):
    """Per-iteration accounting stays out of the always-on flight ring
    (256 slots) unless ``tpu_trace`` asked for everything."""
    with timing.phase("unit/fine", cat="iteration", args={"it": 1}):
        pass
    assert events == []
    tr = trace.configure(str(tmp_path / "t.json"))
    with timing.phase("unit/fine", cat="iteration", args={"it": 2}) as ph:
        pass
    (ev,) = events
    assert ev["name"] == "unit/fine" and ev["cat"] == "iteration"
    assert ev["args"] == {"id": ph.id, "it": 2}
    assert tr.event_count() == 1


def test_worker_span_names_the_span_that_queued_it(events):
    """``prefetch`` hands thunks to its worker thread: the worker's spans
    carry the consumer's open span as ``cause``, and the consumer's wait
    for the worker is a span of its own."""
    from lightgbm_tpu.io.ingest import prefetch

    def prep(k):
        with trace.span("ingest/prep_chunk", cat="ingest",
                        args={"rows": k}):
            return k

    with timing.phase("binning/bin_matrix") as outer:
        got = list(prefetch((lambda k=k: prep(k)) for k in range(5)))
    assert got == list(range(5))
    preps = [e for e in events if e["name"] == "ingest/prep_chunk"]
    waits = [e for e in events if e["name"] == "ingest/prefetch_wait"]
    assert len(preps) == len(waits) == 5
    for e in preps:
        assert e["args"]["cause"] == outer.id and "parent" not in e["args"]
        assert e["tid"] != outer.tid
    for e in waits:
        assert e["args"]["parent"] == outer.id and e["tid"] == outer.tid
    # off the carried call the worker's cause is gone again
    assert trace.carry(lambda: trace.current())() is None


def test_prefetch_outside_the_ingest_emits_no_wait_span(events):
    """``ingest/prefetch_wait`` is the ingest's timer (``ingest.stall_s``
    reads it): the stacked predictor's use of ``prefetch`` asks for none."""
    from lightgbm_tpu.io.ingest import prefetch
    before = _timer("ingest/prefetch_wait")
    assert list(prefetch(((lambda k=k: k) for k in range(4)),
                         wait_span=None)) == list(range(4))
    assert events == [] and _timer("ingest/prefetch_wait") == before


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_ingest_counts_the_rows_each_route_binned(dtype):
    """``ingest/rows_counted`` (rows the count by compares binned, either
    route) beside ``ingest/rows_device`` and ``ingest/f32_rows`` (which
    ``guard.float64_route`` and ``ingest.us_per_row`` read: float32 rows
    alone), and the ingest's spans feed their timers."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    X, y = make_binary(n=700)
    before = dict(obs.default_registry().counter_items())
    prep, chunk = _timer("ingest/prep_chunk"), _timer("ingest/chunk")
    cfg = Config().set({**TEST_PARAMS, "objective": "binary",
                        "tpu_ingest": 1, "tpu_ingest_chunk_rows": 256})
    ds = TpuDataset(cfg).construct_from_matrix(X.astype(dtype),
                                               Metadata(label=y))
    assert ds.bins_t_dev is not None
    after = dict(obs.default_registry().counter_items())
    moved = lambda n: after.get(n, 0) - before.get(n, 0)
    assert moved("ingest/rows_device") == moved("ingest/rows_counted") \
        == 700
    assert moved("ingest/f32_rows") == (700 if dtype == np.float32 else 0)
    assert moved("ingest/h2d_chunks") == 3
    assert _timer("ingest/prep_chunk")[1] == prep[1] + 3
    assert _timer("ingest/chunk")[1] == chunk[1] + 3


def test_span_adds_to_its_timer_with_no_tracer_and_no_profiler():
    assert not trace.enabled()
    before = _timer("unit/always")
    for _ in range(3):
        with trace.span("unit/always"):
            pass
    with timing.phase("unit/always"):
        pass
    total, calls = _timer("unit/always")
    assert calls == before[1] + 4 and total > before[0]
    assert timing.seconds("unit/always") == total


def test_mem_peak_gauge_is_set_at_the_exit_of_a_marked_phase(monkeypatch):
    monkeypatch.setattr(timing, "_device_peak_bytes", lambda: 12345)
    with timing.phase("unit/coarse", mem_peak=True):
        pass
    with timing.phase("unit/fine"):
        pass
    gauges = obs.default_registry().snapshot()["gauges"]
    assert gauges["mem/peak_bytes@unit/coarse"] == 12345.0
    assert "mem/peak_bytes@unit/fine" not in gauges
    # a backend without memory statistics (the CPU) sets nothing
    monkeypatch.setattr(timing, "_device_peak_bytes", lambda: None)
    with timing.phase("unit/coarse_cpu", mem_peak=True):
        pass
    assert "mem/peak_bytes@unit/coarse_cpu" not in \
        obs.default_registry().snapshot()["gauges"]


# -- any profiler session sees the spans --------------------------------------

def _host_events(trace_dir, prefix="lgbm/"):
    from jax.profiler import ProfileData
    (path,) = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def test_profiler_session_holds_the_iteration_and_its_dispatch(tmp_path):
    """Nothing is switched on: the test opens ``jax.profiler`` itself, as a
    benchmark or an operator would, and the written ``.xplane.pb`` holds
    ``lgbm/train/iteration`` with ``lgbm/train/step_dispatch`` inside."""
    import jax
    g = _booster()
    g.train_one_iter()                          # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            g.train_one_iter()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    its = [e for e in ev if e[0] == "lgbm/train/iteration"]
    disp = [e for e in ev if e[0] == "lgbm/train/step_dispatch"]
    assert len(its) == len(disp) == 2
    assert sorted(e[3]["it"] for e in its) == [2, 3]
    for (_, s, e, st), (_, ds, de, dst) in zip(sorted(its, key=lambda x: x[1]),
                                               sorted(disp, key=lambda x: x[1])):
        assert s <= ds and de <= e
        assert dst["parent"] == st["id"]
    kids = {e[0] for e in ev if e[3].get("parent") == its[0][3]["id"]}
    assert {"lgbm/train/prepare", "lgbm/train/step_dispatch",
            "lgbm/train/record"} <= kids
    # once the session is closed a span is no annotation at all
    with trace.span("unit/after") as after:
        assert after._ann is None


def test_model_text_identical_with_and_without_a_profiler_session(tmp_path):
    import jax
    plain = _booster()
    for _ in range(4):
        plain.train_one_iter()
    traced = _booster()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            traced.train_one_iter()
    finally:
        jax.profiler.stop_trace()
    a, b = plain.model_to_string(), traced.model_to_string()
    assert a.encode() == b.encode() and "Tree=3" in a


# -- the step cache's compile span ---------------------------------------------

def test_compile_events_outside_the_compile_span_are_not_counted():
    """jax's monitoring listeners are process-wide; the step cache counts
    an event only while ITS span is open on the thread that compiles."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import step_cache
    EV = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    step_cache._listen()
    counters = lambda: obs.default_registry().counter_items()
    t0, c0 = _timer("step_cache/backend_compile"), counters()
    # a real compile of another program, and raw events, with no span open
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    step_cache._on_jax_duration(EV, 5.0)
    step_cache._on_jax_event(HIT)
    step_cache._on_jax_event(MISS)
    # ... and under a span that is not the step cache's
    with trace.span("unit/not_the_compile"):
        jax.jit(lambda x: x * 5 + 1)(jnp.arange(7)).block_until_ready()
    assert _timer("step_cache/backend_compile") == t0
    assert counters().get("step_cache/persistent_hits", 0) == \
        c0.get("step_cache/persistent_hits", 0)
    assert counters().get("step_cache/persistent_misses", 0) == \
        c0.get("step_cache/persistent_misses", 0)

    # the first dispatch of a registry step: its compile is counted, once
    key = ("test_spans", "compile-span")
    step = step_cache.get_step(key, lambda: jax.jit(lambda x: x * 7 + 1))
    step(jnp.arange(7)).block_until_ready()
    t1 = _timer("step_cache/backend_compile")
    assert t1[1] == t0[1] + 1 and t1[0] > t0[0]
    step(jnp.arange(7)).block_until_ready()     # second dispatch: no span
    assert _timer("step_cache/backend_compile") == t1

    # a compile request the persistent cache serves reads 0.0 s, 1 hit; a
    # compile on another thread meanwhile is not this span's
    args = {"backend_compile_s": 0.0, "persistent_hits": 0,
            "persistent_misses": 0}
    with timing.phase(step_cache.COMPILE_SPAN, cat="cache", args=args):
        other = threading.Thread(
            target=step_cache._on_jax_duration, args=(EV, 9.0))
        other.start()
        other.join(10)
        assert not other.is_alive()
        step_cache._on_jax_event(HIT)
        step_cache._on_jax_duration(EV, 4.0)    # the fetch's bracket
        step_cache._on_jax_event(MISS)
        step_cache._on_jax_duration(EV, 2.5)    # a real compile
    t2 = _timer("step_cache/backend_compile")
    assert t2[1] == t1[1] + 2 and t2[0] == pytest.approx(t1[0] + 2.5)
    assert args == {"backend_compile_s": 2.5, "persistent_hits": 1,
                    "persistent_misses": 1}
    assert counters()["step_cache/persistent_hits"] == \
        c0.get("step_cache/persistent_hits", 0) + 1


def test_a_hit_with_no_bracket_after_it_does_not_zero_the_next_compile():
    """A persistent-cache hit marks the NEXT duration event of its span as
    a fetch. Where none follows inside the span, the mark goes with the
    span: the thread's next real compile is booked in full."""
    from lightgbm_tpu.ops import step_cache
    key = ("test_spans", "hit-without-bracket")
    step_cache.get_step(key, lambda: lambda: step_cache._on_jax_event(
        "/jax/compilation_cache/cache_hits"))()
    t0 = _timer("step_cache/backend_compile")
    args = {"backend_compile_s": 0.0, "persistent_hits": 0,
            "persistent_misses": 0}
    with timing.phase(step_cache.COMPILE_SPAN, cat="cache", args=args):
        step_cache._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 2.5)
    assert _timer("step_cache/backend_compile")[0] == \
        pytest.approx(t0[0] + 2.5)
    assert args["backend_compile_s"] == 2.5


def test_compile_span_carries_the_geometry_key(events):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import step_cache
    key = ("test_spans", "geometry", 255, "hilo5")
    step_cache.get_step(key, lambda: jax.jit(lambda x: x - 2))(jnp.arange(3))
    (ev,) = [e for e in events if e["name"] == step_cache.COMPILE_SPAN]
    assert ev["cat"] == "cache" and ev["args"]["key"] == repr(key)
    assert len(ev["args"]["geometry"]) == 12
    assert ev["args"]["backend_compile_s"] >= 0.0
    assert not any(k.startswith("_") for k in ev["args"])


# -- stable names on the device side --------------------------------------------

def test_lowered_step_carries_every_scope():
    g = _booster(valid=True)
    text = g.lower_step().as_text(debug_info=True)
    for scope in STEP_SCOPES:
        assert scope + "/" in text, scope


def _pallas_calls(path):
    """[(enclosing function, ``name=`` constant or None)] of a file."""
    out = []
    for fn in ast.walk(ast.parse(path.read_text())):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = [k.value.value for k in node.keywords
                        if k.arg == "name"
                        and isinstance(k.value, ast.Constant)]
                out.append((fn.name, name[0] if name else None))
    return out


@pytest.mark.parametrize("module,kernel", [
    ("hist_wave.py", "wave_histogram_pallas"),
    ("hist_wave.py", "fused_partition_histogram_pallas"),
    ("predict.py", "leaf_gather_pallas"),
    ("stacked_predict.py", "forest_predict_pallas"),
])
def test_every_pallas_call_is_named_after_its_entry_point(module, kernel):
    """Five ``pallas_call``s in ops/, each with ``name=`` its jitted entry
    point's own name: whichever of the two the compiler shows in a trace,
    the name is the same and stays put. The one exception is on purpose:
    the root pass's kernel of its own (``root_histogram_pallas``) keeps the
    name ``wave_histogram_pallas`` the root pass has always run under, so
    whoever groups a tree's histogram passes by kernel name (``benchmark/
    traffic/train.json``) goes on counting it."""
    calls = dict(_pallas_calls(OPS / module))
    assert calls[kernel] == kernel
    if module == "hist_wave.py":
        assert calls["root_histogram_pallas"] == "wave_histogram_pallas"
    all_calls = [c for m in ("hist_wave.py", "predict.py",
                             "stacked_predict.py")
                 for c in _pallas_calls(OPS / m)]
    assert len(all_calls) == 5 and all(n for _, n in all_calls)


def _pallas_calls_traced(fn, *args):
    """The ``pallas_call`` equations ``fn`` traces to, nested ones too."""
    import jax
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _traced_kernel_names(fn, *args):
    return [e.params["name"] for e in _pallas_calls_traced(fn, *args)]


@pytest.mark.parametrize("kernel", ["wave_histogram_pallas",
                                    "fused_partition_histogram_pallas",
                                    "leaf_gather_pallas"])
def test_traced_kernel_name_holds_the_benchmarks_substring(kernel):
    """The names the training step's kernels reach the compiler with still
    contain what ``benchmark/traffic/train.json`` (and the ledger's
    ``breakdown``) match device operations by."""
    import jax
    import jax.numpy as jnp
    S = jax.ShapeDtypeStruct
    N, F, B, W = 8192, 8, 64, 14
    kw = dict(num_bins=B, chunk=4096, precision="highest", gh_scale=None,
              count_proxy=False, packed4=False, num_features=None,
              variant="hilo4")
    rows = (S((F, N), jnp.uint8), S((N,), jnp.float32), S((N,), jnp.float32))
    if kernel == "leaf_gather_pallas":
        from lightgbm_tpu.ops.predict import leaf_gather_pallas as fn
        args = (S((15,), jnp.float32), S((N,), jnp.int32))
        patterns = ["leaf_gather_pallas"]
    else:
        from lightgbm_tpu.ops import hist_wave
        fn = functools.partial(getattr(hist_wave, kernel), **kw)
        args = rows + ((S((N,), jnp.int32), S((W,), jnp.int32))
                       if kernel == "wave_histogram_pallas" else
                       (S((N,), jnp.float32), S((N,), jnp.int32),
                        S((18, W), jnp.int32)))
        patterns = json.loads((ROOT / "benchmark" / "traffic" / "train.json")
                              .read_text())["kernels"]["hist"]
    (name,) = _traced_kernel_names(fn, *args)
    assert name == kernel and any(p in name for p in patterns)


@pytest.mark.parametrize("kernel", ["wave_histogram_pallas",
                                    "fused_partition_histogram_pallas"])
def test_tiled_kernels_keep_their_names(kernel):
    """Walking feature tiles (a forced tile of 32 of 72 bin rows) is the
    same ``pallas_call`` under the same name: the benchmark's
    ``kernel.*`` metrics read the tiled kernels by the patterns they
    read the untiled ones by."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import hist_wave
    S = jax.ShapeDtypeStruct
    N, F, B, W = 8192, 72, 64, 14
    fn = functools.partial(getattr(hist_wave, kernel), num_bins=B,
                           chunk=4096, variant="hilo5", feature_tile=32)
    rows = (S((F, N), jnp.uint8), S((N,), jnp.float32), S((N,), jnp.float32))
    args = rows + ((S((N,), jnp.int32), S((W,), jnp.int32))
                   if kernel == "wave_histogram_pallas" else
                   (S((N,), jnp.float32), S((N,), jnp.int32),
                    S((18, W), jnp.int32)))
    (name,) = _traced_kernel_names(fn, *args)
    assert name == kernel


def test_grower_sets_the_tile_and_pool_gauges():
    """``hist/feature_tiles`` (tiles a histogram pass walks; 1 where one
    resident block holds every feature) and ``mem/hist_pool_bytes`` (the
    pool of every leaf's histogram) are set where the grower is built
    (a step the registry serves is not traced again); ``benchmark/readers/
    kernel.feature_tiles.py`` and ``step.hist_pool_gib.py`` read them."""
    from lightgbm_tpu.ops import autotune
    reg = obs.default_registry()
    for name in ("hist/feature_tiles", "mem/hist_pool_bytes"):
        reg.gauge(name).set(-1.0)
    g = _booster()
    g.train_one_iter()
    gauges = reg.snapshot()["gauges"]
    cfg = g._grower_cfg
    f_hist = g.train_data.num_features + g._pad_features
    assert gauges["mem/hist_pool_bytes"] == \
        cfg.num_leaves * f_hist * cfg.num_bins * 3 * 4
    # the CPU's route runs no Mosaic kernel: no tiles to count
    assert cfg.route == "fused-xla" and gauges["hist/feature_tiles"] == -1.0
    # the same booster's shapes on the kernels' route: one tile (a
    # grower under tiles sets 3: tests/test_wide_features.py)
    _geom, tiles = autotune.hist_feature_tiling(
        F=f_hist, B=cfg.num_bins, W=cfg.wave_size, fused=True,
        chunk=cfg.chunk or autotune.DEFAULT_HIST_CHUNK,
        variant=cfg.exact_variant)
    assert tiles == 1


# -- what a span costs ------------------------------------------------------------

def test_span_off_path_stays_in_microseconds():
    """No tracer, no sink, no profiler session: a span is two clock reads,
    a timer add and a look at the profiler. PERF.md reports the number;
    this only keeps a regression of two orders of magnitude out."""
    import time
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("unit/cost"):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    assert per_span_us < 500.0, per_span_us


def _pallas_out_shapes(fn, *args):
    """Shapes of the one ``pallas_call``'s own outputs in ``fn``."""
    (eqn,) = _pallas_calls_traced(fn, *args)
    return [v.aval.shape for v in eqn.outvars]


@pytest.mark.parametrize("tile", [None, 32], ids=["one-tile", "tiled"])
def test_flush_by_slot_keeps_the_fused_kernels_name(tile):
    """A shape on the digit split's side of the fused kernel's rule (255
    bins, a dot dear enough to compact for: autotune.wave_split_applies)
    is the same ``pallas_call`` under the same name; its sums leave the
    kernel by slot, plane and digit and its SMEM output holds two
    scalars (blocks, pairs) where the one-hot dot's holds one:
    ``step.passes_per_iter`` goes on counting 16.0 a tree and
    ``kernel.root_ms_per_iter`` stays the root's."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import autotune, hist_wave
    S = jax.ShapeDtypeStruct
    N, F, B, W = 8192, 72, 255, 24
    args = (S((F, N), jnp.uint8), S((N,), jnp.float32), S((N,), jnp.float32),
            S((N,), jnp.float32), S((N,), jnp.int32), S((18, W), jnp.int32))
    geom = autotune.hist_geometry(F=F, B=B, W=W)
    assert autotune.fused_wave_split(
        geom=geom, variant="hilo5", compact_tile=autotune.hist_compact_tile(
            geom=geom, chunk=4096)) is not None
    rows, tiles = (32, 3) if tile else (F, 1)
    for split, hist, scalars in (
            (None, (tiles * W, -(-rows // 4), 24, 128), 2),
            (False, (tiles * rows, 256, 128), 1)):
        fn = functools.partial(
            hist_wave.fused_partition_histogram_pallas, num_bins=B,
            chunk=4096, variant="hilo5", feature_tile=tile, split=split)
        (name,) = _traced_kernel_names(fn, *args)
        assert name == "fused_partition_histogram_pallas"
        assert _pallas_out_shapes(fn, *args) == [hist, (1, N), (scalars,)]
        out = jax.eval_shape(fn, *args)
        assert out[1].shape == (W, F, B, 3) and out[2].shape == (3,)


@pytest.mark.parametrize("tile", [None, 32], ids=["one-tile", "tiled"])
def test_root_kernel_reaches_the_compiler_under_the_root_passs_name(tile):
    """The root pass's kernel of its own is a Mosaic call named
    ``wave_histogram_pallas``, as the root pass has always been: the
    benchmark's ``hist`` group (``step.passes_per_iter`` 16.0 a tree,
    ``kernel.ms_per_pass``) and ``kernel.root_ms_per_iter`` find it by
    that substring."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_wave import root_histogram_pallas
    S = jax.ShapeDtypeStruct
    N, F = 8192, 72
    fn = functools.partial(root_histogram_pallas, num_bins=255, chunk=4096,
                           variant="hilo5", feature_tile=tile)
    (name,) = _traced_kernel_names(
        fn, S((F, N), jnp.uint8), S((N,), jnp.float32),
        S((N,), jnp.float32), S((N,), jnp.int32))
    patterns = json.loads((ROOT / "benchmark" / "traffic" / "train.json")
                          .read_text())["kernels"]["hist"]
    assert name == "wave_histogram_pallas" and name in patterns


def test_grower_on_the_kernels_route_sets_the_root_macs_gauge(monkeypatch):
    """``hist/root_macs`` (MACs the root's dot spends on a row of a
    feature, as the kernel was built) is set where the grower is built,
    beside ``hist/feature_tiles``; ``benchmark/readers/kernel.root_macs.py``
    reads it. The grown step carries the root kernel inside the scope
    ``lgbm/root_hist``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    reg = obs.default_registry()
    # put back at the end what the gauge held: the next test on this
    # worker reads its own grower's value or the one before
    monkeypatch.setattr(reg.gauge("hist/root_macs"), "_value", -1.0)
    monkeypatch.setattr(reg.gauge("hist/wave_macs"), "_value", -1.0)
    f, n, B = 8, 1024, 255
    meta = FeatureMeta(
        num_bin=np.full(f, B, np.int32), missing_type=np.zeros(f, np.int32),
        default_bin=np.zeros(f, np.int32), monotone=np.zeros(f, np.int32),
        penalty=np.ones(f, np.float32))
    cfg = WaveGrowerConfig(
        num_leaves=7, num_bins=B, wave_size=4, chunk=512,
        route="pallas-tpu", hp=SplitParams(min_data_in_leaf=5, has_cat=False))
    grow = make_wave_grower(cfg, meta, jit=False)
    assert reg.snapshot()["gauges"]["hist/root_macs"] == 5 * 8 * 128
    # and what one block-dot of a wave pass spends, beside it: this narrow
    # a shape does not compact, so the one-hot dot's (the flush by slot's
    # 5 x 8 x 128: tests/test_wave_split.py)
    assert reg.snapshot()["gauges"]["hist/wave_macs"] == 256 * 128
    S = jax.ShapeDtypeStruct
    text = jax.jit(grow).lower(
        S((f, n), jnp.uint8), S((n,), jnp.float32), S((n,), jnp.float32),
        S((n,), jnp.float32), S((f,), jnp.bool_)).as_text(debug_info=True)
    assert "lgbm/root_hist/" in text
    # the CPU's own route runs no Mosaic kernel: no dot to price, and
    # not the value of the grower before
    _booster().train_one_iter()
    assert reg.snapshot()["gauges"]["hist/root_macs"] == 0.0
    assert reg.snapshot()["gauges"]["hist/wave_macs"] == 0.0


@pytest.mark.parametrize("tile", [None, 32], ids=["one-tile", "tiled"])
def test_grower_sets_the_row_take_gauge(monkeypatch, tile):
    """``hist/row_take_bytes`` (the bytes one wave pass's row takes read
    through ``take_rows``) is set as the grower is traced, where the rows
    are known: the W parents' rows of the ``[L, F, B, 3]`` f32 pool, and
    where the fused kernel walks feature tiles the W split columns of N
    bin bytes its wrapper takes too."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import autotune
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    from lightgbm_tpu.ops.wave_grower import (WaveGrowerConfig,
                                              make_wave_grower)
    reg = obs.default_registry()
    monkeypatch.setattr(reg.gauge("hist/row_take_bytes"), "_value", -1.0)
    if tile:
        real = autotune.hist_feature_tile
        monkeypatch.setattr(autotune, "hist_feature_tile",
                            lambda **kw: real(**{**kw, "force": tile}))
    jax.clear_caches()
    f, n, B, W = 72, 1024, 255, 4
    meta = FeatureMeta(
        num_bin=np.full(f, B, np.int32), missing_type=np.zeros(f, np.int32),
        default_bin=np.zeros(f, np.int32), monotone=np.zeros(f, np.int32),
        penalty=np.ones(f, np.float32))
    cfg = WaveGrowerConfig(
        num_leaves=7, num_bins=B, wave_size=W, chunk=512,
        route="pallas-tpu", hp=SplitParams(min_data_in_leaf=5, has_cat=False))
    grow = make_wave_grower(cfg, meta, jit=False)
    assert reg.snapshot()["gauges"]["hist/feature_tiles"] == \
        (3 if tile else 1)                             # 72 rows / 32
    S = jax.ShapeDtypeStruct
    jax.jit(grow).lower(
        S((f, n), jnp.uint8), S((n,), jnp.float32), S((n,), jnp.float32),
        S((n,), jnp.float32), S((f,), jnp.bool_))
    jax.clear_caches()
    pool_rows = W * f * B * 3 * 4
    assert reg.snapshot()["gauges"]["hist/row_take_bytes"] == \
        pool_rows + (W * n if tile else 0)


def test_ranking_objective_sets_its_gauges_and_names_its_scope():
    """``rank/pairs_real`` (sum of n_q^2) and ``rank/pair_slots`` (slots
    the layout evaluates an iteration) are set where the objective builds
    its query tables, a class of tables a width;
    ``benchmark/readers/rank.pair_slots_ratio.py`` reads the two. The pair
    block runs inside ``lgbm/gradients/rank_pairs``."""
    from conftest import fit_gbdt
    rng = np.random.default_rng(9)
    counts = np.asarray([1, 3, 8, 9, 20, 33, 70, 5, 2, 12] * 8)
    n = int(counts.sum())
    X = rng.normal(size=(n, 5))
    y = rng.integers(0, 4, n).astype(np.float32)
    g = fit_gbdt(X, y, {"objective": "lambdarank"}, num_round=1,
                 group=counts)
    gauges = obs.default_registry().snapshot()["gauges"]
    classes = g.objective._pair_classes
    # widths 8 .. 64 and the widest cut to the longest query, 70
    assert [c["lab"].shape[1] for c in classes] == [8, 16, 32, 64, 70]
    assert sum(int((c["lab"][:, 0] >= 0).sum()) for c in classes) \
        == len(counts)
    assert gauges["rank/pairs_real"] == float(np.sum(counts ** 2))
    assert gauges["rank/pair_slots"] == sum(
        c["lab"].shape[0] * c["lab"].shape[1] ** 2 for c in classes)
    assert "lgbm/gradients/rank_pairs/" in \
        g.lower_step().as_text(debug_info=True)


# -- the collectives of the parallel learners (PR 36) --------------------------

def _mesh_booster(learner, n=1280, **params):
    from lightgbm_tpu.utils.device import get_devices
    if len(get_devices()) < 4:
        pytest.skip("needs a 4-device mesh")
    return _booster(n=n, tree_learner=learner, num_machines=4, **params)


@pytest.mark.parametrize("learner, scopes", [
    ("data", ("lgbm/wave/hist_psum", "lgbm/root_hist/psum")),
    ("feature", ("lgbm/wave/split_sync",)),
])
def test_lowered_parallel_step_names_its_collectives(learner, scopes):
    """The sum of the wave histograms, the root's sum and the split sync
    each sit under a scope of their own (the data-parallel learner needs no
    split sync: every chip finds the same splits in the summed histograms;
    the feature-parallel learner's is ``sync_best_splits``). The serial
    step has none of them."""
    text = _mesh_booster(learner).lower_step().as_text(debug_info=True)
    for scope in scopes:
        assert scope + "/" in text, scope
    serial = _booster().lower_step().as_text(debug_info=True)
    for scope in ("lgbm/wave/hist_psum", "lgbm/root_hist/psum",
                  "lgbm/wave/split_sync"):
        assert scope not in serial, scope


def test_data_parallel_counters_are_shapes_times_passes():
    """``comm/psum_bytes``: a 3-leaf tree is one root pass and two wave
    passes (the first splits the root, the second one of its children) of
    a ``[W, F_pad, B, 3]`` float32 block each (off the chip the root has no
    kernel of its own and sums a whole wave too), by hand;
    ``comm/psum_passes`` the passes; ``comm/devices`` the mesh;
    ``hist/rows_dotted_max_shard`` (the ``pmax`` beside the sum) at least a
    quarter of ``hist/rows_dotted``, and fed by the data learner alone."""
    reg = obs.default_registry()
    before = dict(reg.counter_items())
    g = _mesh_booster("data", num_leaves=3)
    assert reg.snapshot()["gauges"]["comm/devices"] == 4.0
    trees = 4
    for _ in range(trees):
        g.train_one_iter()
    g.finish_training()
    after = dict(reg.counter_items())
    d = lambda k: after.get(k, 0) - before.get(k, 0)
    cfg = g._grower_cfg
    assert g._grower.resolved["root_slots"] == cfg.wave_size    # no kernel here
    block = cfg.wave_size * g._f_pad * cfg.num_bins * 3 * 4
    assert d("hist/trees_counted") == trees
    assert d("comm/psum_passes") == 3 * trees
    assert d("comm/psum_bytes") == 3 * trees * block
    assert 4 * d("hist/rows_dotted_max_shard") >= d("hist/rows_dotted") >= 0
    rec = g.records[0]
    assert rec.wave_work.shape == (5,)
    assert int(rec.wave_work[4]) == 2
    # a serial booster's records stay [3] and feed neither counter
    s = _booster(num_leaves=3)
    s.train_one_iter()
    s.finish_training()
    assert s.records[0].wave_work.shape == (3,)
    assert dict(reg.counter_items()).get("comm/psum_bytes", 0) == \
        after.get("comm/psum_bytes", 0)
    assert reg.snapshot()["gauges"]["comm/devices"] == 1.0


def test_short_mesh_is_said_and_counted():
    """``num_machines`` beyond the devices found: the learner trains over
    what there is, warns once and counts every time."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import learners
    from lightgbm_tpu.utils.device import get_devices
    have = len(get_devices())
    if have < 2:
        pytest.skip("needs a mesh")
    c = obs.counter("learner/mesh_short")
    n0 = c.value
    cfg = Config().set({"tree_learner": "data", "num_machines": have + 3})
    assert learners.training_mesh(cfg).devices.size == have
    assert learners.training_mesh(cfg).devices.size == have
    assert c.value == n0 + 2
    cfg = Config().set({"tree_learner": "data", "num_machines": have})
    learners.training_mesh(cfg)
    assert c.value == n0 + 2
