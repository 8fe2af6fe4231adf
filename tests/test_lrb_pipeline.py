"""Pipelined retrain-while-serve LRB loop (lrb.py) + its two perf
layers: vectorized derive/OPT bit-parity against the scalar reference
transliterations, pipelined-vs-sequential result parity, paced-stream
wall win, serving-during-retrain liveness, degrade/swap-suppression,
the trainer-thread fault drills, and the device-resident ingest chunk
ring's h2d ledger (io/ingest.py ChunkRing).
"""
import io
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from lightgbm_tpu import lrb
from lightgbm_tpu.obs import registry as obs

pytestmark = pytest.mark.lrb

FAST = {"num_iterations": 4, "verbose": -1}


def _driver(mode, window=300, sample=150, extra=None, **kw):
    params = dict(FAST)
    params["tpu_lrb_pipeline"] = mode
    params.update(extra or {})
    return lrb.LrbDriver(1 << 16, window, sample, 0.5, 1,
                         result_file=io.StringIO(),
                         extra_params=params, **kw)


def _feed(drv, n, objects=60):
    for seq, oid, size, cost in lrb.synthetic_trace(n, objects):
        drv.process_request(seq, oid, size, cost)


def _fill_window(drv, n, n_ids=8, seed=0, big_sizes=False):
    """An adversarial window: heavy id repeats (>50 occurrences, the
    gap-deque cap), same id at different sizes (insert-size vs
    current-size eviction credit), label runs (insert/evict run-start
    propagation), and optionally sizes that drive cache_avail <= 0."""
    rng = np.random.default_rng(seed)
    w = drv.window
    hi = (1 << 22) if big_sizes else 5000
    for i in range(n):
        w.ids.append(int(rng.integers(0, n_ids)))
        w.sizes.append(int(rng.integers(1, hi)))
        w.costs.append(float(rng.random()))
        w.has_next.append(bool(rng.random() < 0.6))
        w.volume.append(int(rng.integers(0, 1 << 20)))
        w.byte_sum += w.sizes[-1]


# -- vectorized hot loops: bit-parity vs the scalar oracles ------------------

def test_vectorized_opt_bit_parity():
    drv = _driver(0)
    _fill_window(drv, 400)
    drv._calculate_opt_scalar()
    want = (drv.window.to_cache.copy(), drv._opt_hits,
            drv._opt_byte_hits)
    drv._calculate_opt()
    np.testing.assert_array_equal(drv.window.to_cache, want[0])
    assert (drv._opt_hits, drv._opt_byte_hits) == want[1:]


def test_vectorized_opt_budget_cutoff():
    """The scalar loop admits while the running volume is <= budget
    and BREAKS past it — the vectorized exclusive-cumsum mask must
    land on exactly the same boundary item."""
    drv = _driver(0, window=4, sample=4)
    drv.cache_size = 10                   # budget = 10 * 4 = 40
    w = drv.window
    for vol, size in ((15, 3), (25, 5), (1, 7), (999, 9)):
        w.ids.append(1)
        w.sizes.append(size)
        w.costs.append(1.0)
        w.has_next.append(True)
        w.volume.append(vol)
        w.byte_sum += size
    drv._calculate_opt_scalar()
    want = drv.window.to_cache.copy()
    drv._calculate_opt()
    np.testing.assert_array_equal(drv.window.to_cache, want)
    # items 15+25+1 admitted (cum-before 0/15/40 <= 40), 999 cut off
    assert list(drv.window.to_cache) == [True, True, True, False]


@pytest.mark.parametrize("sampling", [0, 1, 2])
@pytest.mark.parametrize("big_sizes", [False, True])
def test_vectorized_derive_bit_parity(sampling, big_sizes):
    drv = _driver(0, window=400, sample=170)
    if big_sizes:
        drv.cache_size = 1 << 20          # avail goes <= 0 mid-window
    _fill_window(drv, 400, big_sizes=big_sizes)
    drv._calculate_opt()
    drv.rng = np.random.default_rng(42)
    l_s, x_s = drv._derive_features_scalar(sampling)
    drv.rng = np.random.default_rng(42)
    l_v, x_v = drv._derive_features(sampling)
    np.testing.assert_array_equal(l_s, l_v)
    assert x_s.shape == x_v.shape
    np.testing.assert_array_equal(x_s, x_v)


def test_vectorized_derive_empty_and_single():
    drv = _driver(0)
    labels, X = drv._derive_features(0)
    assert labels.shape == (0,) and X.shape == (0, lrb.NUM_FEATURES)
    _fill_window(drv, 1)
    drv._calculate_opt()
    l_s, x_s = drv._derive_features_scalar(0)
    l_v, x_v = drv._derive_features(0)
    np.testing.assert_array_equal(l_s, l_v)
    np.testing.assert_array_equal(x_s, x_v)


# -- pipelined vs sequential: field-for-field parity -------------------------

PARITY_KEYS = ("window", "eval_rows", "fp_rate", "fn_rate",
               "train_rows", "opt_obj_hit_ratio", "opt_byte_hit_ratio",
               "staleness_windows", "degraded", "degrade_reason")


def _run_modes(n=1800, window=300, sample=150, extra=None):
    out = {}
    for mode in (1, 0):
        drv = _driver(mode, window, sample, extra=extra)
        _feed(drv, n)
        res = drv.results                 # drains the pipeline
        out[mode] = (drv, res)
        drv.close()
    return out


def test_pipelined_matches_sequential():
    swaps0 = obs.counter("lrb/model_swaps").value
    runs = _run_modes()
    drv_p, res_p = runs[1]
    drv_s, res_s = runs[0]
    assert len(res_p) == len(res_s) == 6
    for a, b in zip(res_s, res_p):
        for k in PARITY_KEYS:
            assert a.get(k) == b.get(k), (k, a.get(k), b.get(k))
    # swap-at-boundary: the pipelined run published exactly one model
    # per successfully trained window, and only those
    trained = sum(1 for r in res_p if not r.get("degraded"))
    assert obs.counter("lrb/model_swaps").value - swaps0 == trained
    # every pipelined window carries the overlap instrument
    assert all("overlap_s" in r for r in res_p)
    # the serve histogram is PER-REQUEST: one observation per scored
    # row, not one per micro-batch
    assert drv_p._serve_hist.count == sum(r.get("eval_rows", 0)
                                          for r in res_p)
    assert drv_p._serve_batch_hist.count < drv_p._serve_hist.count


def test_pipelined_beats_sequential_wall_at_rate():
    """The acceptance run: a >= 6-window synthetic trace offered at an
    LRB-realistic rate (bounded-buffer pacing, calibrated from a warm
    pass). The sequential loop stalls the stream for every window's
    train+evaluate wall; the pipelined loop trains while the stream
    goes on — a structural, not statistical, difference, so it is
    counted and not timed: requests that arrive while the trainer
    holds a window. (Two walls of about a second each, mostly the
    pacing's own sleeps, were compared here before; on a host with no
    idle core the trainer thread gets no gap to run in and that
    comparison fell either way.)"""
    import time
    n, window, sample = 3072, 512, 256
    extra = {"num_iterations": 6}
    reqs = list(lrb.synthetic_trace(n, 80))

    warm = _driver(0, window, sample, extra=extra)
    for r in reqs:
        warm.process_request(*r)
    train_walls = [r["train_s"] for r in warm.results if "train_s" in r]
    warm.close()
    gap16 = 16.0 * 2.5 * float(np.median(train_walls)) / window

    def paced(mode):
        drv = _driver(mode, window, sample, extra=extra)
        during_retrain = 0
        nxt = time.monotonic()
        for i, r in enumerate(reqs):
            if i % 16 == 0:
                nxt += gap16
                delay = nxt - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                else:
                    nxt = time.monotonic()
            during_retrain += drv.training_in_flight()
            drv.process_request(*r)
        drv.drain()
        res = drv.results
        drv.close()
        return res, during_retrain

    res_s, during_s = paced(0)
    res_p, during_p = paced(1)
    assert len(res_s) == len(res_p) >= 6
    for a, b in zip(res_s, res_p):
        for k in PARITY_KEYS:
            assert a.get(k) == b.get(k), (k, a.get(k), b.get(k))
    assert sum(r.get("overlap_s", 0) for r in res_p) > 0
    assert during_s == 0, \
        f"{during_s} requests met a training in flight in sequential mode"
    # every boundary but the last hands its window to the trainer and
    # takes the next request at once
    assert during_p >= len(res_p) - 1, \
        f"{during_p} requests arrived while the pipelined trainer held " \
        f"a window, over {len(res_p)} windows"


# -- serving-during-retrain liveness -----------------------------------------

def test_serving_stays_live_during_retrain(lock_order):
    """predict_live returns while the trainer thread provably holds a
    window (parked on the test gate), serving the previous model.
    Runs under the lock-order detector: the swap/join/serving-lock
    acquisition graph of a mid-window serve must stay acyclic."""
    reqs = list(lrb.synthetic_trace(600, 60))
    drv = _driver(1)
    for r in reqs[:300]:
        drv.process_request(*r)           # window 1 trains + publishes
    drv.drain()
    assert drv.booster is not None
    gate = threading.Event()
    drv._train_gate = gate
    for r in reqs[300:]:
        drv.process_request(*r)
    # window 2's boundary submitted its training; the trainer is
    # parked on the gate — training is in flight RIGHT NOW
    assert drv._train_started.wait(timeout=30)
    assert drv.training_in_flight()
    probe = np.zeros((8, lrb.NUM_FEATURES))
    out = drv.predict_live(probe)
    assert out is not None and np.asarray(out).shape == (8,)
    assert drv.training_in_flight(), \
        "the serve call must not have waited the trainer out"
    gate.set()
    drv._train_gate = None
    res = drv.results
    assert len(res) == 2 and not res[1].get("degraded")
    drv.close()


def test_concurrent_drain_joins_once():
    """results/booster drain from any thread; concurrent drains must
    not both run the join body (double-counted staleness, duplicate
    result lines)."""
    import time
    reqs = list(lrb.synthetic_trace(600, 60))
    out = io.StringIO()
    params = dict(FAST)
    params["tpu_lrb_pipeline"] = 1
    drv = lrb.LrbDriver(1 << 16, 300, 150, 0.5, 1, result_file=out,
                        extra_params=params)
    for r in reqs[:300]:
        drv.process_request(*r)
    drv.drain()
    gate = threading.Event()
    drv._train_gate = gate
    for r in reqs[300:]:
        drv.process_request(*r)           # window 2 parked on the gate
    assert drv._train_started.wait(timeout=30)
    got = []
    readers = [threading.Thread(target=lambda: got.append(
        len(drv.results))) for _ in range(4)]
    for t in readers:
        t.start()
    time.sleep(0.2)
    gate.set()
    drv._train_gate = None
    for t in readers:
        t.join(timeout=30)
    assert got == [2, 2, 2, 2]
    assert out.getvalue().count("window 2:") == 1
    assert len(drv.results) == 2
    drv.close()


def test_chunk_ring_bypassed_when_matrix_exceeds_capacity():
    """A matrix wider than the ring's slot capacity must take the
    plain path (every slot would be evicted before reuse — pure
    overhead) with identical bins and an empty ring."""
    from lightgbm_tpu import capi
    from lightgbm_tpu.io.ingest import ChunkRing
    rng = np.random.default_rng(9)
    X = rng.normal(size=(1000, 4))
    params = {"tpu_ingest": 1, "tpu_ingest_chunk_rows": 64,
              "max_bin": 15, "verbose": -1}       # 16 chunks > cap 8
    ring = ChunkRing()
    ds_r = capi.LGBM_DatasetCreateFromMat(X, parameters=params,
                                          ring=ring)
    got = np.asarray(ds_r.construct().bins_t_dev)
    ds_p = capi.LGBM_DatasetCreateFromMat(X, parameters=params)
    want = np.asarray(ds_p.construct().bins_t_dev)
    np.testing.assert_array_equal(got, want)
    assert not ring._slots, "bypass must not pin resident chunks"


# -- degrade: swap suppression + fault drills --------------------------------

def test_degraded_window_suppresses_swap():
    from lightgbm_tpu.utils import faults
    swaps0 = obs.counter("lrb/model_swaps").value
    faults.configure("lrb.window_train@2")
    try:
        drv = _driver(1)
        _feed(drv, 900)
        res = drv.results
    finally:
        faults.clear()
    assert [r.get("degraded") for r in res] == [None, True, None]
    assert "InjectedFault" in res[1]["degrade_reason"]
    assert [r["staleness_windows"] for r in res] == [0, 1, 0]
    # windows 1 and 3 published; window 2's swap never happened
    assert obs.counter("lrb/model_swaps").value - swaps0 == 2
    # ... and the loop kept serving window 1's model through window 3
    assert res[2].get("eval_rows", 0) > 0
    assert drv.booster is not None
    drv.close()


def test_every_window_failing_degrades_not_deadlocks():
    """The raise drill on EVERY window: the trainer thread dies clean
    each time, nothing ever publishes, the loop completes the whole
    trace degraded — no deadlock, no exception."""
    from lightgbm_tpu.utils import faults
    faults.configure("lrb.window_train@1+")
    try:
        drv = _driver(1)
        _feed(drv, 900)
        res = drv.results
    finally:
        faults.clear()
    assert len(res) == 3
    assert all(r.get("degraded") for r in res)
    assert drv.booster is None
    assert [r["staleness_windows"] for r in res] == [0, 0, 0]
    drv.close()


_KILL_CHILD = """
import io, sys
from lightgbm_tpu import lrb
d = lrb.LrbDriver(1 << 16, 300, 150, 0.5, 1, result_file=io.StringIO(),
                  extra_params={"num_iterations": 2,
                                "tpu_lrb_pipeline": 1})
for seq, oid, size, cost in lrb.synthetic_trace(900, 60):
    d.process_request(seq, oid, size, cost)
d.drain()
print("SURVIVED-THE-DRILL")
"""


def test_kill_drill_trainer_thread_dies_clean():
    """``lrb.window_train@1:kill`` SIGKILLs from the TRAINER thread:
    the process must die promptly (no deadlocked join, no survivor
    output) — the crash drill the degrade path cannot absorb."""
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_CHILD],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "LGBM_TPU_FAULTS": "lrb.window_train@1:kill"})
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-500:])
    assert "SURVIVED-THE-DRILL" not in proc.stdout


# -- device-resident ingest chunk ring ---------------------------------------

def test_chunk_ring_bit_identical_fewer_h2d():
    """Ingest-level: two same-geometry constructions through one ring
    — the second window's smaller matrix reuses the resident slot
    (stale rows beyond its live region must read as pad), bins are
    bit-identical to ring-less ingest, and the h2d ledger shrinks."""
    from lightgbm_tpu import capi
    from lightgbm_tpu.io.ingest import ChunkRing
    rng = np.random.default_rng(5)
    params = {"tpu_ingest": 1, "max_bin": 63, "verbose": -1}
    X1 = rng.normal(size=(500, 12))
    X2 = rng.normal(size=(200, 12))       # smaller: stale-tail case
    ring = ChunkRing()

    def bins(X, ring=None):
        h0 = obs.counter("ingest/h2d_bytes").value
        ds = capi.LGBM_DatasetCreateFromMat(X, parameters=params,
                                            ring=ring)
        out = np.asarray(ds.construct().bins_t_dev)
        return out, obs.counter("ingest/h2d_bytes").value - h0

    want1, h_plain1 = bins(X1)
    want2, h_plain2 = bins(X2)
    got1, h_ring1 = bins(X1, ring)
    got2, h_ring2 = bins(X2, ring)
    np.testing.assert_array_equal(got1, want1)
    np.testing.assert_array_equal(got2, want2)
    assert h_ring1 < h_plain1 and h_ring2 < h_plain2
    assert obs.counter("ingest/ring_saved_bytes").value > 0


def test_lrb_ring_fewer_h2d_bytes_per_window():
    """Driver-level acceptance: the windowed loop with tpu_lrb_ring
    ships fewer h2d bytes per window than full re-ingest, with
    bit-identical training results (fp/fn parity)."""
    def run(ring):
        drv = _driver(1, extra={"num_iterations": 3, "tpu_ingest": 1,
                                "tpu_lrb_ring": ring})
        h0 = obs.counter("ingest/h2d_bytes").value
        _feed(drv, 900)
        res = drv.results
        drv.close()
        return res, obs.counter("ingest/h2d_bytes").value - h0

    res_plain, h_plain = run(0)
    res_ring, h_ring = run(1)
    assert h_ring < h_plain / 4, (h_ring, h_plain)
    for a, b in zip(res_plain, res_ring):
        for k in ("fp_rate", "fn_rate", "train_rows", "degraded"):
            assert a.get(k) == b.get(k), (k, a.get(k), b.get(k))


# -- serve-latency accounting + registry -------------------------------------

def test_observe_n_per_request_normalization():
    reg = obs.MetricsRegistry()
    h = obs.latency_histogram("t", reg)
    h.observe_n(0.010, 64)                # one 64-row micro-batch
    h.observe(2.0)                        # one slow single request
    assert h.count == 65
    assert h.sum == pytest.approx(0.010 * 64 + 2.0)
    # p50 ranks REQUESTS: the 64 fast requests dominate the median
    assert h.percentile(0.5) < 0.05
    assert h.percentile(0.99) > 1.0
    h.observe_n(5.0, 0)                   # n=0 is a no-op
    assert h.count == 65


def test_main_result_file_context_managed_and_flushed(tmp_path):
    """lrb.main() with a resultFile: the handle is context-managed
    (closed on exit) and every window's line plus the summary reaches
    disk."""
    trace_path = tmp_path / "trace.txt"
    lines = [f"{seq} {oid} {size} {cost}"
             for seq, oid, size, cost in lrb.synthetic_trace(600, 60)]
    trace_path.write_text("\n".join(lines) + "\n")
    out_path = tmp_path / "result.txt"
    lrb.main([str(trace_path), str(1 << 16), "300", "150", "0.5", "1",
              str(out_path)])
    text = out_path.read_text()
    assert "window 1:" in text and "window 2:" in text
    assert "window_wall" in text
    assert "serve_latency" in text        # per-request quantiles line
