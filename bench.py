"""Benchmark: HIGGS-class GBDT training throughput on one chip.

Mirrors the reference's headline experiment (docs/Experiments.rst:104-113:
LightGBM CPU trains HIGGS — 11M rows x 28 features, 500 iterations,
num_leaves=255 — in 238.5 s on a 2x E5-2670v3 box; the GPU docs recommend
max_bin=63 for device runs, docs/GPU-Performance.rst:111-127). HIGGS
itself cannot be downloaded here (no egress), so an equally-sized
synthetic binary task with the same shape parameters is used and the
result is normalized to row-iterations/second for comparison against the
published reference wall-clock.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
vs_baseline > 1.0 means faster than the reference's published HIGGS
CPU number (its strongest in-repo headline baseline).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

# reference headline: 11M rows x 500 iters in 238.5 s  (Experiments.rst)
BASELINE_ROWS = 11_000_000
BASELINE_ITERS = 500
BASELINE_SECONDS = 238.5
BASELINE_ROW_ITERS_PER_S = BASELINE_ROWS * BASELINE_ITERS / BASELINE_SECONDS


def make_higgs_like(n_rows: int, n_features: int = 28, seed: int = 7):
    """Synthetic HIGGS-shaped task: 28 continuous features, nonlinear
    decision boundary, balanced classes."""
    r = np.random.default_rng(seed)
    X = r.normal(size=(n_rows, n_features)).astype(np.float32)
    logit = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] - 0.3 * X[:, 3] * X[:, 4]
             + 0.2 * np.abs(X[:, 5]) + 0.1 * X[:, 6])
    y = (logit + 0.5 * r.normal(size=n_rows) > 0).astype(np.float32)
    return X, y


HOLDOUT_ROWS = 500_000


def _lrb_probe_batch(rows: int) -> np.ndarray:
    """A plausible LRB feature batch (inter-arrival gaps, log2 size,
    log2 available bytes, cost) for the live-scoring thread — the
    predictions' values don't matter, the serving path they exercise
    does."""
    from lightgbm_tpu.lrb import HISTFEATURES, NUM_FEATURES
    r = np.random.default_rng(3)
    X = np.zeros((rows, NUM_FEATURES), np.float64)
    X[:, :8] = r.integers(1, 500, size=(rows, 8)).astype(np.float64)
    X[:, HISTFEATURES] = np.round(
        100.0 * np.log2(r.integers(64, 16384, rows)))
    X[:, HISTFEATURES + 1] = round(100.0 * np.log2(1 << 16))
    X[:, HISTFEATURES + 2] = 1.0
    return X


def lrb_stream_bench(args) -> dict:
    """The streaming retrain-while-serve bench (ROADMAP item 3): the
    SAME synthetic multi-window trace through the LRB loop twice in
    one process — sequential then pipelined — at an LRB-realistic
    request RATE, with a scorer thread firing ``predict_live``
    micro-batches against the published model the whole time.

    The feeder paces requests with a minimum inter-arrival gap (a
    bounded-buffer upstream: a retrain stall pushes every later
    arrival out — backpressure, not an infinite burst buffer), with
    the rate auto-calibrated from an untimed warm pass so one window
    of requests spans ~2.5x the window's warm training wall
    (``--lrb-rate`` overrides; 0 = closed-loop, no pacing). Under
    that load the comparison is structural, not scheduling luck: the
    sequential loop stalls the stream for every window's whole
    derive+train+evaluate wall, the pipelined loop absorbs training
    into the stream's idle gaps — so pipelined sustains the offered
    rate and wins end-to-end wall by ~the total training time.

    Reported: end-to-end wall for both modes, sustained trace
    requests/s (N / wall), serve p50/p99 split by whether a trainer
    thread was mid-window when the probe fired (the during-retrain
    tail is the number this workload exists to bound), and the
    model-staleness lag."""
    import io
    import threading
    import time as _time

    from lightgbm_tpu import lrb
    from lightgbm_tpu.obs import registry as obs_registry

    windows = args.lrb_windows
    rows = args.lrb_window_rows
    sample = min(args.lrb_sample, rows)
    iters = args.lrb_iters
    if args.quick:
        windows, rows = min(windows, 6), min(rows, 1024)
        sample, iters = min(sample, 256), min(iters, 8)
    reqs = list(lrb.synthetic_trace(windows * rows,
                                    max(rows // 8, 50)))
    base = {"num_iterations": iters, "verbose": "-1"}
    probe = _lrb_probe_batch(args.lrb_serve_batch)

    # untimed full-trace warm pass: pays the one-off per-geometry
    # step/predict compiles (every window can land in its own shape
    # bucket) so neither timed mode carries a cold tail the other
    # skipped, AND yields the warm per-window training wall the
    # request rate is calibrated from
    warm = lrb.LrbDriver(1 << 16, rows, sample, 0.5, 1,
                         result_file=io.StringIO(),
                         extra_params={**base, "tpu_lrb_pipeline": 0},
                         serve_batch=args.lrb_serve_batch)
    for seq, oid, size, cost in reqs:
        warm.process_request(seq, oid, size, cost)
    warm.predict_live(probe)
    train_walls = [r["train_s"] for r in warm.results
                   if "train_s" in r]
    warm.close()
    rate = args.lrb_rate
    if rate < 0:        # auto: one window of arrivals ~ 2.5x train
        t_win = 2.5 * (np.median(train_walls) if train_walls else 0.5)
        rate = rows / max(t_win, 1e-3)
    # pacing in bursts of 16 keeps sleep syscalls off the per-request
    # path; a stall rebases the clock (bounded buffer: missed arrival
    # slots are lost, not replayed as an instant burst)
    gap16 = 16.0 / rate if rate > 0 else 0.0

    def run(mode):
        drv = lrb.LrbDriver(1 << 16, rows, sample, 0.5, 1,
                            result_file=io.StringIO(),
                            extra_params={**base,
                                          "tpu_lrb_pipeline": mode},
                            serve_batch=args.lrb_serve_batch)
        stop = threading.Event()
        reg = obs_registry.MetricsRegistry()
        hist_d = obs_registry.latency_histogram("serve_during", reg)
        hist_b = obs_registry.latency_histogram("serve_between", reg)

        def score_loop():
            while not stop.is_set():
                in_flight = drv.training_in_flight()
                t0 = _time.monotonic()
                out = drv.predict_live(probe)
                dt = _time.monotonic() - t0
                if out is None:         # no model published yet
                    _time.sleep(0.002)
                    continue
                (hist_d if in_flight else hist_b).observe(dt)
                _time.sleep(0.002)      # a bounded probe rate

        th = threading.Thread(target=score_loop, name="lrb-scorer",
                              daemon=True)
        th.start()
        t0 = _time.monotonic()
        nxt = t0
        for i, (seq, oid, size, cost) in enumerate(reqs):
            if gap16 and i % 16 == 0:
                nxt += gap16
                delay = nxt - _time.monotonic()
                if delay > 0:
                    _time.sleep(delay)
                else:
                    nxt = _time.monotonic()
            drv.process_request(seq, oid, size, cost)
        drv.drain()
        wall = _time.monotonic() - t0
        stop.set()
        th.join(timeout=10)
        res = drv.results
        degraded = drv.degraded_windows()
        drv.close()
        return res, wall, hist_d, hist_b, degraded

    res_s, wall_s, _, _, deg_s = run(0)
    res_p, wall_p, hist_d, hist_b, deg_p = run(1)
    n_s = n_p = len(reqs)

    parity_keys = ("eval_rows", "fp_rate", "fn_rate", "train_rows",
                   "staleness_windows", "degraded", "degrade_reason")
    mismatches = sum(1 for a, b in zip(res_s, res_p)
                     for k in parity_keys if a.get(k) != b.get(k))
    stale = [r.get("staleness_windows", 0) for r in res_p] or [0]

    def q_ms(hist, q):
        v = hist.percentile(q)
        return None if v is None else round(1e3 * v, 3)

    stream = {
        "windows": windows, "window_rows": rows,
        "sample_rows": sample, "iters": iters,
        "offered_requests_per_s": round(rate, 1),
        "wall_sequential_s": round(wall_s, 3),
        "wall_pipelined_s": round(wall_p, 3),
        "speedup": round(wall_s / max(wall_p, 1e-9), 3),
        "requests_per_s": round(n_p / max(wall_p, 1e-9), 1),
        "requests_per_s_sequential": round(n_s / max(wall_s, 1e-9), 1),
        "serve_p50_during_retrain_ms": q_ms(hist_d, 0.5),
        "serve_p99_during_retrain_ms": q_ms(hist_d, 0.99),
        "serve_p50_between_ms": q_ms(hist_b, 0.5),
        "serve_p99_between_ms": q_ms(hist_b, 0.99),
        "requests_during_retrain": hist_d.count,
        "staleness_p99_windows": round(
            float(np.percentile(stale, 99)), 3),
        "overlap_s_total": round(
            sum(r.get("overlap_s", 0.0) for r in res_p), 3),
        "degraded_windows": deg_p,
        "degraded_windows_sequential": deg_s,
        "result_parity_mismatches": mismatches,
    }
    print(f"# lrb-stream: {windows} windows x {rows} rows — wall "
          f"seq {wall_s:.2f}s vs pipe {wall_p:.2f}s "
          f"(speedup {stream['speedup']:.2f}x), "
          f"{stream['requests_per_s']:.0f} requests/s, p99 during "
          f"retrain {stream['serve_p99_during_retrain_ms']} ms "
          f"({hist_d.count} reqs mid-retrain), staleness p99 "
          f"{stream['staleness_p99_windows']} windows",
          file=sys.stderr)
    return stream


FLEET_FEATURES = 16


def _fleet_model_str(rows: int, iters: int) -> str:
    """Train one small binary booster through the capi surface and
    return its model text — the artifact every fleet tenant is
    registered from. Same text, same tree geometry: the predict
    registry compiles ONE program and serves all K tenants off it."""
    from lightgbm_tpu import capi
    rng = np.random.default_rng(17)
    X = rng.normal(size=(rows, FLEET_FEATURES))
    y = (X[:, 0] + 0.5 * X[:, 1]
         + 0.25 * rng.normal(size=rows) > 0).astype(np.float64)
    ds = capi.LGBM_DatasetCreateFromMat(X)
    capi.LGBM_DatasetSetField(ds, "label", y)
    booster = capi.LGBM_BoosterCreate(
        ds, {"objective": "binary", "num_leaves": 31, "verbose": "-1"})
    for _ in range(iters):
        capi.LGBM_BoosterUpdateOneIter(booster)
    return capi.LGBM_BoosterSaveModelToString(booster)


def fleet_bench(args) -> dict:
    """The multi-tenant coalesced-serving bench (serve/): one
    ScoringDaemon, K same-geometry tenants registered from ONE model
    text, scored over real localhost HTTP in two phases —

      sequential   each tenant's requests issued one at a time, one
                   tenant after another: no concurrency, so the
                   coalescer never merges anything (the K-separate-
                   processes fleet this subsystem replaces)
      coalesced    K paced client threads offered ~2x the sequential
                   phase's per-tenant rate (the lrb-stream feeder's
                   burst-paced clock-rebase loop), so requests from
                   different tenants genuinely overlap and the
                   dispatcher drains them as shared device batches

    Reported: aggregate requests/s for both phases, per-tenant client
    p50/p99, the coalesced-batch-rows histogram, the predict-registry
    hit rate across registration + serving (K-1 of K registrations
    reuse the first tenant's compiled program), shed/queue-reject
    counters, and the daemon's admission budget state."""
    import threading
    import time as _time

    from lightgbm_tpu.obs import registry as obs_registry
    from lightgbm_tpu.ops import predict_cache
    from lightgbm_tpu.serve import FleetClient, ScoringDaemon, ShedError
    from lightgbm_tpu.serve import coalescer as serve_coalescer

    tenants = max(args.fleet_tenants, 1)
    reqs = max(args.fleet_requests, 8)
    rows = max(args.fleet_rows, 1)
    streams = max(args.fleet_streams, 1)
    if args.quick:
        reqs = min(reqs, 80)
    names = [f"tenant_{i:02d}" for i in range(tenants)]
    # a deliberately non-trivial forest: per-batch predict dispatch is
    # the cost coalescing amortizes, so a toy model would measure only
    # fixed HTTP overhead (not clamped under --quick for the same
    # reason)
    model_str = _fleet_model_str(rows=2048, iters=args.fleet_iters)
    X = np.random.default_rng(29).normal(size=(rows, FLEET_FEATURES))

    before = predict_cache.stats()
    retries0 = obs_registry.counter("retry/retries").value
    daemon = ScoringDaemon(port=0, coalesce_us=args.fleet_coalesce_us,
                           slo_p99_ms=args.fleet_slo_p99_ms).start()
    try:
        client = FleetClient(daemon.url)
        for t in names:
            client.register(t, model_str, warm_rows=rows)
        # one warm request per tenant over the wire so neither timed
        # phase carries a first-request cost the other skipped
        for t in names:
            client.predict(t, X)

        # phase 1: uncoalesced sequential streams
        t0 = _time.monotonic()
        for t in names:
            for _ in range(reqs):
                client.predict(t, X)
        wall_seq = _time.monotonic() - t0
        seq_rps = tenants * reqs / max(wall_seq, 1e-9)

        # phase 2: K tenants x M concurrent paced streams, offered 2x
        # the sequential per-tenant rate in aggregate — sustained only
        # if coalescing actually buys overlapping requests a shared
        # device batch. M > 1 puts several same-tenant requests in
        # flight at once, so the dispatcher gets real merges (one
        # synchronous stream per tenant would cap every coalesced
        # batch at a single request).
        per_stream = max(reqs // streams, 1)
        per_rate = 2.0 * seq_rps / tenants
        gap8 = 8.0 * streams / per_rate if per_rate > 0 else 0.0
        lat = {t: [] for t in names}
        shed = {t: 0 for t in names}
        errors = []
        agg_hist = obs_registry.latency_histogram(
            "fleet/client_latency_s")

        def stream(t):
            c = FleetClient(daemon.url)
            nxt = _time.monotonic()
            for i in range(per_stream):
                if gap8 and i % 8 == 0:
                    nxt += gap8
                    delay = nxt - _time.monotonic()
                    if delay > 0:
                        _time.sleep(delay)
                    else:
                        nxt = _time.monotonic()
                s = _time.monotonic()
                try:
                    c.predict(t, X)
                except ShedError:
                    shed[t] += 1
                    continue
                except Exception as e:  # noqa: BLE001 — a failed
                    # request is a result (errors gate below), not a
                    # bench abort
                    errors.append(f"{t}: {e}")
                    continue
                dt = _time.monotonic() - s
                lat[t].append(dt)       # list.append: thread-safe
                agg_hist.observe(dt)

        threads = [threading.Thread(target=stream, args=(t,),
                                    name=f"fleet-{t}-{j}", daemon=True)
                   for t in names for j in range(streams)]
        t0 = _time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = _time.monotonic() - t0
        done = sum(len(v) for v in lat.values())
        rps = done / max(wall, 1e-9)

        cache = predict_cache.stats()
        lookups = ((cache["hits"] - before["hits"])
                   + (cache["misses"] - before["misses"]))
        hit_rate = ((cache["hits"] - before["hits"]) / lookups
                    if lookups else None)
        batch_hist = obs_registry.histogram(
            "fleet/coalesced_batch_rows",
            serve_coalescer.ROW_BUCKETS).snapshot()
        stats = daemon.stats()

        def q_ms(vals, q):
            return (round(1e3 * float(np.percentile(vals, q)), 3)
                    if vals else None)

        out = {
            "tenants": tenants,
            "requests_per_tenant": per_stream * streams,
            "rows_per_request": rows, "streams_per_tenant": streams,
            "coalesce_us": args.fleet_coalesce_us,
            "requests_per_s": round(rps, 1),
            "requests_per_s_sequential": round(seq_rps, 1),
            "coalescing_speedup": round(rps / max(seq_rps, 1e-9), 3),
            "offered_per_tenant_requests_per_s": round(per_rate, 1),
            "per_tenant": {
                t: {"requests": len(lat[t]),
                    "p50_ms": q_ms(lat[t], 50),
                    "p99_ms": q_ms(lat[t], 99),
                    "shed": shed[t]}
                for t in names},
            "registry_hit_rate": (round(hit_rate, 4)
                                  if hit_rate is not None else None),
            "registry_lookups": lookups,
            "coalesced_batch_rows": {
                "batches": batch_hist["count"],
                "mean": (round(batch_hist["sum"]
                               / batch_hist["count"], 2)
                         if batch_hist["count"] else None),
                "p50": batch_hist["p50"], "p99": batch_hist["p99"],
                "buckets": batch_hist["buckets"]},
            "shed_total": stats["shed_total"],
            "queue_rejects": stats["queue_rejects"],
            "requests_total": stats["requests_total"],
            "client_retries": (obs_registry.counter(
                "retry/retries").value - retries0),
            "errors": len(errors),
            "slo_admission": daemon.slo_report(),
        }
    finally:
        daemon.stop()
    if errors:
        print(f"# fleet: {len(errors)} failed requests, first: "
              f"{errors[0]}", file=sys.stderr)
    worst = max((v["p99_ms"] or 0.0)
                for v in out["per_tenant"].values())
    print(f"# fleet: {tenants} tenants x {reqs} requests — "
          f"{out['requests_per_s']:.0f} requests/s coalesced vs "
          f"{out['requests_per_s_sequential']:.0f} sequential "
          f"({out['coalescing_speedup']:.2f}x), worst-tenant p99 "
          f"{worst} ms, mean batch "
          f"{out['coalesced_batch_rows']['mean']} rows, registry hit "
          f"rate {out['registry_hit_rate']}, shed {out['shed_total']}",
          file=sys.stderr)
    return out


def make_ctr_sparse(n_rows: int, n_features: int, density: float,
                    seed: int = 11):
    """Synthetic CTR-shaped sparse task: ~density*F active hashed
    features per row with small integer-ish values (one-hot-with-
    counts, the ad-click shape), labels from a sparse linear logit.
    O(nnz) generation — the dense matrix never exists here either."""
    from lightgbm_tpu.io.sparse import SparseMatrix
    rng = np.random.default_rng(seed)
    k = max(1, int(round(n_features * density)))
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), k)
    cols = rng.integers(0, n_features, size=n_rows * k)
    key = rows * n_features + cols
    _, first = np.unique(key, return_index=True)   # drop dup cells
    rows, cols = rows[first], cols[first]
    vals = rng.integers(1, 16, size=len(rows)).astype(np.float64)
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(rows, minlength=n_rows))])
    w = rng.normal(size=n_features)
    logits = np.zeros(n_rows)
    np.add.at(logits, rows, w[cols] * np.log1p(vals))
    y = (logits + 0.5 * rng.normal(size=n_rows) > 0).astype(np.float32)
    sm = SparseMatrix(vals, cols.astype(np.int64),
                      indptr.astype(np.int64), (n_rows, n_features))
    return sm, y


def sparse_route_run(args) -> dict:
    """ONE route of the sparse bench, run in its own process so each
    route's ru_maxrss watermark is its own (--sparse-route {dense,csr}):
    the SAME synthetic CSR workload trained through the dense-densified
    path or the CSR-native route, reporting wall, throughput, host peak
    RSS and a tree-section hash (the parent asserts cross-route
    parity)."""
    import hashlib
    import resource

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import autotune

    sm, y = make_ctr_sparse(args.sparse_rows, args.sparse_features,
                            args.sparse_density)
    t0 = time.time()
    cfg = Config().set({
        "objective": "binary", "max_bin": args.max_bin,
        "num_leaves": min(args.leaves, 63), "min_data_in_leaf": 20,
        "learning_rate": 0.1, "tpu_stop_check_interval": 10_000,
        "tpu_quantized_hist": not args.no_quant,
        "tpu_ingest": 0 if args.no_ingest else -1,
    })
    X = sm.to_dense() if args.sparse_route == "dense" else sm
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, [])
    ingest_s = time.time() - t0
    t0 = time.time()
    for _ in range(args.sparse_iters):
        g.train_one_iter()
    float(np.asarray(g._scores[0, :1])[0])      # drain the queue
    train_s = time.time() - t0
    # model parity across routes: the tree sections only (the
    # parameters: block echoes per-route knobs)
    trees = g.model_to_string().split("\nparameters:\n")[0]
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "route": args.sparse_route,
        "rows": args.sparse_rows, "features": args.sparse_features,
        "nnz": sm.nnz, "density": round(sm.density, 5),
        "iters": args.sparse_iters,
        "ingest_s": round(ingest_s, 3),
        "train_s": round(train_s, 3),
        "rows_per_s": round(
            args.sparse_rows * args.sparse_iters / max(train_s, 1e-9),
            1),
        "peak_rss_mb": round(rss_kb / 1024.0, 1),
        "sparse_hist_tier": bool(g._grower_cfg.sparse_hist),
        "model_sha1": hashlib.sha1(trees.encode()).hexdigest(),
        "device_kind": autotune.device_kind(),
    }


def sparse_bench(args) -> dict:
    """The sparse CTR workload bench (--sparse): the same CSR matrix
    trained dense-densified vs CSR-native, each route in a fresh
    subprocess so 'peak host RSS' is per-route truth (ru_maxrss is a
    process-lifetime high-water mark). Appends both routes + the RSS
    ratio to the JSON line; refuses silently-diverged models."""
    import subprocess

    if args.quick:
        args.sparse_rows = min(args.sparse_rows, 20_000)
        args.sparse_iters = min(args.sparse_iters, 8)
    routes = {}
    for route in ("dense", "csr"):
        cmd = [sys.executable, __file__, "--sparse-route", route,
               "--sparse-rows", str(args.sparse_rows),
               "--sparse-features", str(args.sparse_features),
               "--sparse-density", str(args.sparse_density),
               "--sparse-iters", str(args.sparse_iters),
               "--max-bin", str(args.max_bin),
               "--leaves", str(args.leaves)]
        if args.no_quant:
            cmd.append("--no-quant")
        if args.no_ingest:
            cmd.append("--no-ingest")
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            print(proc.stderr[-2000:], file=sys.stderr)
            raise RuntimeError(f"sparse route {route!r} failed "
                               f"(exit {proc.returncode})")
        routes[route] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"# sparse {route}: {routes[route]['rows_per_s']:.0f} "
              f"rows/s, peak RSS {routes[route]['peak_rss_mb']:.0f} MB "
              f"(ingest {routes[route]['ingest_s']:.2f}s, train "
              f"{routes[route]['train_s']:.2f}s)", file=sys.stderr)
    parity = (routes["dense"]["model_sha1"]
              == routes["csr"]["model_sha1"])
    if not parity:
        print("# WARNING: sparse routes trained DIFFERENT models",
              file=sys.stderr)
    out = {
        "rows": args.sparse_rows, "features": args.sparse_features,
        "density": routes["csr"]["density"],
        "nnz": routes["csr"]["nnz"], "iters": args.sparse_iters,
        "routes": {k: {kk: vv for kk, vv in v.items()
                       if kk not in ("rows", "features", "nnz",
                                     "density", "iters")}
                   for k, v in routes.items()},
        "peak_rss_ratio": round(
            routes["dense"]["peak_rss_mb"]
            / max(routes["csr"]["peak_rss_mb"], 1e-9), 3),
        "model_parity": parity,
        "device_kind": routes["csr"]["device_kind"],
    }
    print(f"# sparse bench: dense {routes['dense']['peak_rss_mb']:.0f}"
          f" MB vs csr {routes['csr']['peak_rss_mb']:.0f} MB peak RSS "
          f"({out['peak_rss_ratio']:.2f}x), model parity {parity}",
          file=sys.stderr)
    return out


def make_rank_stream(path: str, n_rows: int, n_features: int,
                     qsize: int, seed: int = 13) -> int:
    """Write a synthetic ranking dataset straight to disk in bounded
    blocks (label + features CSV with a .query sidecar of fixed-size
    queries) — the WRITER never holds the full matrix, so the loader
    under test owns the whole RSS story. Graded 0..3 relevance from a
    per-query-shifted linear score (the learning-to-rank shape).
    Returns the written row count (whole queries only)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=n_features)
    n_rows -= n_rows % qsize
    block = max(65_536 // qsize, 1) * qsize
    with open(path, "w") as fh:
        for r0 in range(0, n_rows, block):
            k = min(block, n_rows - r0)
            X = rng.normal(size=(k, n_features))
            qoff = rng.normal(size=k // qsize).repeat(qsize)
            s = X @ w + qoff + rng.normal(size=k)
            lab = np.clip(np.floor((s - s.mean())
                          / max(float(s.std()), 1e-9) + 2.0), 0, 3)
            np.savetxt(fh, np.column_stack([lab, X]), delimiter=",",
                       fmt="%.6g")
    with open(path + ".query", "w") as fh:
        for _ in range(n_rows // qsize):
            fh.write(f"{qsize}\n")
    return n_rows


def rank_route_run(args) -> dict:
    """ONE route of the ranking bench, run in its own process so each
    route's ru_maxrss watermark is its own (--rank-route
    {memory,ooc}): the SAME on-disk ranking file loaded through the
    in-memory one-round loader or the out-of-core streaming route
    (tpu_out_of_core=1), then lambdarank trained plain AND under
    hashed GOSS, with a same-geometry retrain to surface the
    step-cache hit rate. The parent asserts cross-route model
    parity (OOC is bit-identical by construction)."""
    import hashlib
    import resource

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.loader import DatasetLoader
    from lightgbm_tpu.metrics import create_metrics
    from lightgbm_tpu.models.boosting import create_boosting
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.obs import registry as obs_registry
    from lightgbm_tpu.ops import autotune, step_cache

    base = {
        "objective": "lambdarank", "max_bin": args.max_bin,
        "num_leaves": min(args.leaves, 63), "min_data_in_leaf": 20,
        "learning_rate": 0.1, "tpu_stop_check_interval": 10_000,
        "tpu_quantized_hist": not args.no_quant,
        "tpu_ingest": 0 if args.no_ingest else -1,
    }
    if args.rank_route == "ooc":
        base["tpu_out_of_core"] = 1
    cfg = Config().set(base)
    t0 = time.time()
    ds = DatasetLoader(cfg).load_from_file(args.rank_file)
    ingest_s = time.time() - t0

    def fit(goss: bool):
        obj = create_objective("lambdarank", cfg)
        obj.init(ds.metadata, ds.num_data)
        mets = create_metrics(["ndcg"], cfg, ds.metadata,
                              ds.num_data)
        g = create_boosting("goss") if goss else GBDT()
        g.init(cfg, ds, obj, mets)
        t1 = time.time()
        for _ in range(args.rank_iters):
            g.train_one_iter()
        float(np.asarray(g._scores[0, :1])[0])     # drain the queue
        wall = time.time() - t1
        evals = {e[0]: round(float(e[1]), 5)
                 for e in g.get_eval_at(0)}
        trees = g.model_to_string().split("\nparameters:\n")[0]
        return wall, evals, hashlib.sha1(trees.encode()).hexdigest()

    train_s, ndcg, sha = fit(False)
    goss_s, ndcg_goss, _ = fit(True)
    # same-geometry retrains: both objective families must now ride
    # the registry (hit rate 1.0 = the windows-2+ zero-compile story)
    s0 = step_cache.stats()
    fit(False)
    fit(True)
    s1 = step_cache.stats()
    hits = s1["hits"] - s0["hits"]
    misses = s1["misses"] - s0["misses"]
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "route": args.rank_route,
        "rows": ds.num_data,
        "queries": int(ds.metadata.num_queries),
        "iters": args.rank_iters,
        "ingest_s": round(ingest_s, 3),
        "train_s": round(train_s, 3),
        "train_goss_s": round(goss_s, 3),
        "rows_per_s": round(
            ds.num_data * args.rank_iters / max(train_s, 1e-9), 1),
        "ndcg": ndcg,
        "ndcg_goss": ndcg_goss,
        "retrain_step_cache": {
            "hits": hits, "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 3)},
        "ooc_blocks": obs_registry.counter("ooc/blocks").value,
        "peak_rss_mb": round(rss_kb / 1024.0, 1),
        "model_sha1": sha,
        "device_kind": autotune.device_kind(),
    }


def rank_bench(args) -> dict:
    """The ranking workload bench (--rank): one on-disk lambdarank
    dataset loaded in-memory vs out-of-core, each route in a fresh
    subprocess so 'peak host RSS' is per-route truth (the --sparse
    methodology). Reports NDCG (plain + hashed GOSS), rows/s, the
    OOC peak-RSS ratio and the same-geometry retrain step-cache hit
    rate; refuses silently-diverged models (OOC promises BIT parity)."""
    import os
    import subprocess
    import tempfile

    if args.quick:
        args.rank_rows = min(args.rank_rows, 20_000)
        args.rank_iters = min(args.rank_iters, 8)
    routes = {}
    with tempfile.TemporaryDirectory(prefix="rank_bench_") as td:
        path = os.path.join(td, "rank.csv")
        t0 = time.time()
        n = make_rank_stream(path, args.rank_rows, args.rank_features,
                             args.rank_qsize)
        print(f"# rank data: {n} rows ({args.rank_qsize}-row queries) "
              f"written in {time.time()-t0:.1f}s", file=sys.stderr)
        for route in ("memory", "ooc"):
            cmd = [sys.executable, __file__, "--rank-route", route,
                   "--rank-file", path,
                   "--rank-rows", str(n),
                   "--rank-features", str(args.rank_features),
                   "--rank-qsize", str(args.rank_qsize),
                   "--rank-iters", str(args.rank_iters),
                   "--max-bin", str(args.max_bin),
                   "--leaves", str(args.leaves)]
            if args.no_quant:
                cmd.append("--no-quant")
            if args.no_ingest:
                cmd.append("--no-ingest")
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                print(proc.stderr[-2000:], file=sys.stderr)
                raise RuntimeError(f"rank route {route!r} failed "
                                   f"(exit {proc.returncode})")
            routes[route] = json.loads(
                proc.stdout.strip().splitlines()[-1])
            r = routes[route]
            print(f"# rank {route}: {r['rows_per_s']:.0f} rows/s, "
                  f"peak RSS {r['peak_rss_mb']:.0f} MB (ingest "
                  f"{r['ingest_s']:.2f}s, train {r['train_s']:.2f}s, "
                  f"retrain hit rate "
                  f"{r['retrain_step_cache']['hit_rate']:.0%})",
                  file=sys.stderr)
    parity = (routes["memory"]["model_sha1"]
              == routes["ooc"]["model_sha1"])
    if not parity:
        print("# WARNING: rank routes trained DIFFERENT models",
              file=sys.stderr)
    out = {
        "rows": n, "features": args.rank_features,
        "qsize": args.rank_qsize, "iters": args.rank_iters,
        "routes": {k: {kk: vv for kk, vv in v.items()
                       if kk not in ("rows", "iters")}
                   for k, v in routes.items()},
        "peak_rss_ratio": round(
            routes["memory"]["peak_rss_mb"]
            / max(routes["ooc"]["peak_rss_mb"], 1e-9), 3),
        "step_cache_hit_rate":
            routes["ooc"]["retrain_step_cache"]["hit_rate"],
        "model_parity": parity,
        "device_kind": routes["ooc"]["device_kind"],
    }
    print(f"# rank bench: memory "
          f"{routes['memory']['peak_rss_mb']:.0f} MB vs ooc "
          f"{routes['ooc']['peak_rss_mb']:.0f} MB peak RSS "
          f"({out['peak_rss_ratio']:.2f}x), model parity {parity}",
          file=sys.stderr)
    return out


# default SLO specs per bench mode (obs/slo.py grammar): generous
# ceilings — the section exists to put budget/burn/p99.9 numbers in
# the artifact (gated for SHAPE by tools/check_bench_regression.py),
# not to fail a shared-host run on scheduling noise. --slo overrides.
DEFAULT_SLO_TRAIN = "predict_p99_ms<5000;degraded_window_rate<0.5"
DEFAULT_SLO_STREAM = ("serve_p99_ms<5000;staleness_windows<=8;"
                      "degraded_window_rate<0.5")
# fleet bench: client-observed wire latency (generic hist form,
# threshold in seconds) + a ceiling on how much of the offered load
# admission control may shed before the artifact flags itself
DEFAULT_SLO_FLEET = ("hist:fleet/client_latency_s:p99 < 5;"
                     "ratio:fleet/shed_total|fleet/requests_total"
                     " <= 0.5")


def slo_section(spec: str) -> dict:
    """Evaluate ``spec`` against the run's live registry state and
    return the bench JSON's ``slo`` section: overall compliance,
    remaining error budget, burn rate, the p99.9 serving tails
    (obs/registry.py quantiles now reach past p99), and one compact
    row per objective. Installed as the process-global engine so a
    live exporter's /slo endpoint reports the same budgets."""
    from lightgbm_tpu.obs import registry as obs_registry
    from lightgbm_tpu.obs import slo as obs_slo
    # one idempotence rule: a running engine with the same spec text
    # keeps its burn/latch state, anything else is replaced
    # (obs/slo.py ensure_from_config)
    eng = obs_slo.ensure_from_config({"tpu_slo": spec})
    rep = eng.report(fresh=True)

    def p999_ms(name):
        # bounded-cardinality: called with two literal names
        v = obs_registry.latency_histogram(name).percentile(0.999)
        return None if v is None else round(1e3 * v, 3)

    return {
        "spec": spec,
        "ok": rep.get("ok"),
        "violating": rep.get("violating", 0),
        "budget_remaining_min": rep.get("budget_remaining_min"),
        "burn_rate_max": rep.get("burn_rate_max"),
        "predict_p999_ms": p999_ms("predict/latency_s"),
        "serve_p999_ms": p999_ms("lrb/serve_latency_s"),
        "objectives": [
            {k: r[k] for k in ("name", "ok", "current", "threshold",
                               "budget_remaining", "burn_rate")}
            for r in rep.get("specs", [])],
    }


def _auc(y, s):
    """Holdout AUC through the engine's own metric implementation."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metrics import create_metrics
    (m,) = create_metrics(["auc"], Config(), Metadata(label=y), len(y))
    return float(m.eval(np.asarray(s, np.float64), None)[0][1])


# ---------------------------------------------------------------------------
# Reference-parity harness (bench.py --parity)
# ---------------------------------------------------------------------------

# the reference's own GPU-vs-CPU quality bar: test AUC within ~4e-4 of
# the CPU engine at 63 bins (docs/GPU-Performance.rst) — the ceiling
# the measured-parity gate asserts when reference LightGBM is present
PARITY_AUC_TOL = 4e-4


def _metric_tag(kind=None) -> str:
    """Device-kind suffix every headline metric string carries.
    tools/check_bench_regression.py compares runs by metric-string
    equality, so the stamp makes a CPU number structurally incomparable
    with a GPU or TPU trajectory — the checker refuses instead of
    ratioing across backends. ``kind``: the device kind a child process
    reported (the --sparse/--rank parents never touch jax themselves)."""
    if kind is None:
        from lightgbm_tpu.ops import autotune
        kind = autotune.device_kind()
    return f" [{kind}]"


def _import_reference_lightgbm():
    """The reference engine, if this host can import it: the
    ``lightgbm`` PyPI package, else the fork's python-package under
    /root/reference. Returns (module, skip_reason) — exactly one is
    None. The skip reason records the device kind and every import
    path attempted, so a parity skip in a cross-backend sweep log is
    self-explaining."""
    attempted = ["lightgbm (sys.path)"]
    try:
        import lightgbm as ref
        return ref, None
    except ImportError as e:
        first = str(e)
    from lightgbm_tpu.ops import autotune
    dk = autotune.device_kind()
    ref_pkg = "/root/reference/python-package"
    if os.path.isdir(ref_pkg):
        attempted.append(ref_pkg)
        sys.path.insert(0, ref_pkg)
        try:
            import lightgbm as ref
            return ref, None
        except Exception as e:  # noqa: BLE001 — a fork without a built
            # lib_lightgbm.so raises OSError from its loader
            return None, (f"reference fork at {ref_pkg} not importable:"
                          f" {e} [device_kind={dk}; attempted: "
                          f"{', '.join(attempted)}]")
        finally:
            sys.path.remove(ref_pkg)
    attempted.append(f"{ref_pkg} (absent)")
    return (None,
            f"lightgbm not importable ({first}) and no fork at "
            f"{ref_pkg} [device_kind={dk}; attempted: "
            f"{', '.join(attempted)}]")


def _train_reference(args, X, y, X_test, y_test):
    """Train reference LightGBM CPU on the SAME synthetic data and
    measure {wall, auc}. Returns (stats dict, None) or
    (None, skip_reason)."""
    ref, reason = _import_reference_lightgbm()
    if ref is None:
        return None, reason
    params = {
        "objective": "binary", "metric": "auc",
        "num_leaves": args.leaves, "max_bin": args.max_bin,
        "learning_rate": 0.1, "min_data_in_leaf": 20,
        "verbose": -1,
    }
    # hand float32 over as-is — the reference bins float32 natively,
    # and a float64 copy of the 11M-row matrix would add ~2.5 GB of
    # peak RSS to a process already holding the engine's state
    t0 = time.time()
    dtrain = ref.Dataset(X, label=y)
    booster = ref.train(params, dtrain, num_boost_round=args.iters)
    wall = time.time() - t0
    pred = booster.predict(X_test, raw_score=True)
    return {
        # end-to-end wall: the reference's Dataset is lazy, so binning
        # happens inside train() — this wall covers bin + train, the
        # same span the engine tiers' wall_s covers (dataset construct
        # + all iterations incl. compile); vs_measured compares the
        # two LIKE walls, never a steady-state rate against an
        # all-inclusive one
        "ref_wall_s": round(wall, 2),
        "row_iters_per_s": round(args.rows * args.iters / max(wall, 1e-9)
                                 / 1e6, 4),
        "auc_ref": round(_auc(y_test, pred), 6),
        "version": getattr(ref, "__version__", "unknown"),
    }, None


def _train_tpu_tier(args, X, y, X_test, y_test, tier: str) -> dict:
    """Train ONE tier of this engine on the same data and measure
    {tpu_wall, steady row-iters/s, holdout AUC}. ``tier``: "exact" =
    the f32-grade hi/lo histogram path (autotuned variant), "proxy" =
    int8 quantization + count-proxy (the headline tier)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata, TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    cfg = Config().set({
        "objective": "binary", "metric": "auc",
        "num_leaves": args.leaves, "max_bin": args.max_bin,
        "learning_rate": 0.1, "min_data_in_leaf": 20,
        "tpu_stop_check_interval": 10_000,
        "tpu_quantized_hist": tier == "proxy",
        "tpu_ingest": 0 if args.no_ingest else -1,
    })
    # wall_s spans dataset construction through the last iteration's
    # readback — the SAME span the reference's lazy Dataset + train()
    # wall covers, so vs_measured is a like-for-like wall ratio
    t_all = time.time()
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, [])

    def sync():
        return float(np.asarray(g._scores[0, :1])[0])

    t1 = time.time()
    g.train_one_iter()
    sync()
    compile_s = time.time() - t1
    t0 = time.time()
    for _ in range(args.iters - 1):
        g.train_one_iter()
    sync()
    train_s = time.time() - t0
    wall = time.time() - t_all
    parts = []
    for r0 in range(0, len(X_test), 20_000):
        parts.append(np.asarray(g.predict_raw(X_test[r0:r0 + 20_000])))
    auc = _auc(y_test, np.concatenate(parts))
    out = {
        "wall_s": round(wall, 2),
        "compile_s": round(compile_s, 2),
        "train_s": round(train_s, 2),
        # steady-state rate (post-compile iterations): the regression
        # tool's exact-tier floor gates THIS; vs_measured uses wall_s
        "row_iters_per_s": round(
            args.rows * (args.iters - 1) / max(train_s, 1e-9) / 1e6, 4),
        "auc_tpu": round(auc, 6),
    }
    if tier == "exact":
        out["exact_variant"] = g._grower_cfg.exact_variant
        out["wave_size"] = g._grower_cfg.wave_size
    return out


def parity_bench(args, data=None) -> dict:
    """The measured reference-parity harness (--parity): BOTH of this
    engine's tiers (exact hi/lo and int8 count-proxy) AND reference
    LightGBM CPU trained on the SAME synthetic HIGGS-shaped data,
    recording {auc_ref, auc_tpu, ref_wall, tpu_wall} so the perf
    ledger's ``vs_measured`` stands on a measured run instead of the
    published number — and asserting the reference's own quality bar
    (|auc_ref - auc_tpu| <= 4e-4 at 63 bins, GPU-Performance.rst).
    When reference LightGBM cannot be imported the ref fields are null
    and ``skip_reason`` records why — a recorded skip, not a silent
    pass. ``data`` reuses the standard bench's already-generated
    (X, y, X_test, y_test)."""
    from lightgbm_tpu.ops import autotune

    if data is not None:
        X, y, X_test, y_test = data
    else:
        X, y = make_higgs_like(args.rows + HOLDOUT_ROWS)
        X_test, y_test = X[args.rows:], y[args.rows:]
        X, y = X[:args.rows], y[:args.rows]

    tiers = {}
    for tier in ("exact", "proxy"):
        tiers[tier] = _train_tpu_tier(args, X, y, X_test, y_test, tier)
        print(f"# parity {tier}: {tiers[tier]['train_s']:.1f}s train, "
              f"{tiers[tier]['row_iters_per_s']:.3f} M row-iters/s, "
              f"AUC {tiers[tier]['auc_tpu']:.5f}", file=sys.stderr)
    ref, skip = _train_reference(args, X, y, X_test, y_test)
    if ref is not None:
        print(f"# parity ref: {ref['ref_wall_s']:.1f}s wall, AUC "
              f"{ref['auc_ref']:.5f}", file=sys.stderr)
    else:
        print(f"# parity ref: SKIPPED — {skip}", file=sys.stderr)

    ok = True
    for tier, t in tiers.items():
        if ref is not None:
            t["ref_wall_s"] = ref["ref_wall_s"]
            t["auc_ref"] = ref["auc_ref"]
            t["auc_delta"] = round(abs(t["auc_tpu"] - ref["auc_ref"]), 6)
            # like-for-like wall ratio: BOTH walls span dataset
            # construction through the last trained iteration (the
            # reference's Dataset is lazy — its wall includes binning)
            t["vs_measured"] = round(
                ref["ref_wall_s"] / max(t["wall_s"], 1e-9), 3)
            if t["auc_delta"] > args.parity_auc_tol:
                ok = False
        else:
            t["ref_wall_s"] = t["auc_ref"] = None
            t["auc_delta"] = t["vs_measured"] = None
    return {
        "rows": args.rows, "iters": args.iters, "leaves": args.leaves,
        "max_bin": args.max_bin,
        "device_kind": autotune.device_kind(),
        "ref_available": ref is not None,
        "skip_reason": skip,
        "ref": ref,
        "auc_tol": args.parity_auc_tol,
        "tiers": tiers,
        "ok": ok,
    }


def _require_tpu() -> None:
    """The one gate in front of every device section: what this file
    prints are device metrics, and a run that found no TPU must fail
    rather than write CPU numbers under their names (a CPU container
    once produced a whole trajectory point that way)."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"bench.py: refusing to run — jax found platform "
                 f"{d.platform!r} ({d.device_kind}), not a TPU; bench "
                 f"numbers are device metrics (chip_smoke.py is the "
                 f"quickest check that the chip is there)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=11_000_000)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--quick", action="store_true",
                    help="tiny smoke run (64k rows, 20 iters)")
    ap.add_argument("--no-quant", action="store_true",
                    help="disable int8 histogram quantization "
                         "(f32-grade hi/lo accumulation instead)")
    ap.add_argument("--no-ingest", action="store_true",
                    help="disable the streamed device ingest path "
                         "(host binner + bulk upload instead)")
    ap.add_argument("--learner", default="serial",
                    choices=["serial", "data", "voting"],
                    help="tree learner: 'data' shards rows over every "
                         "visible chip and psums wave histograms over "
                         "ICI — the multi-chip path for the v5e-8 "
                         "north-star target (falls back to serial on "
                         "one device)")
    ap.add_argument("--retrain", type=int, default=0, metavar="K",
                    help="after the timed run, train K fresh boosters "
                         "back-to-back on the same data (the lrb.py "
                         "sliding-window pattern) and report warm vs "
                         "cold compile time + step-cache hit rate in "
                         "the JSON output")
    ap.add_argument("--serve", action="store_true",
                    help="after training, run the serving-latency "
                         "bench: p50/p95/p99 per-request latency and "
                         "sustained rows/s at 1/64/4096-row batches "
                         "through the geometry-keyed predict registry "
                         "(ops/predict_cache.py), reported under "
                         "'serve' in the JSON line")
    ap.add_argument("--serve-seconds", type=float, default=2.0,
                    help="measurement budget per serve batch size "
                         "(default 2.0s, after 2 warmup requests)")
    ap.add_argument("--run-report", default="",
                    help="write the run-report artifact here "
                         "(tpu_run_report; .jsonl for line-delimited). "
                         "The JSON line's phase breakdown comes from "
                         "this report's phase table either way.")
    ap.add_argument("--lrb-stream", action="store_true",
                    help="run ONLY the streaming retrain-while-serve "
                         "bench (lrb.py pipelined vs sequential on a "
                         "synthetic multi-window trace, with a live "
                         "scorer thread) and emit its JSON line — "
                         "unit requests/s, details under 'lrb_stream'")
    ap.add_argument("--no-lrb-stream", action="store_true",
                    help="skip the compact lrb-stream section the "
                         "standard bench appends to its JSON/report")
    ap.add_argument("--lrb-windows", type=int, default=8)
    ap.add_argument("--lrb-window-rows", type=int, default=4096)
    ap.add_argument("--lrb-sample", type=int, default=512)
    ap.add_argument("--lrb-iters", type=int, default=10)
    ap.add_argument("--lrb-serve-batch", type=int, default=32)
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the multi-tenant coalesced-serving "
                         "bench (serve/): one scoring daemon, K "
                         "same-geometry tenants over localhost HTTP, "
                         "sequential uncoalesced streams vs K paced "
                         "concurrent streams; emits a standalone JSON "
                         "line (unit requests/s, details under "
                         "'fleet')")
    ap.add_argument("--fleet-tenants", type=int, default=4)
    ap.add_argument("--fleet-requests", type=int, default=300,
                    help="requests per tenant per phase (default 300;"
                         " --quick clamps to 80)")
    ap.add_argument("--fleet-rows", type=int, default=4,
                    help="rows per request (default 4 — the "
                         "small-batch shape coalescing exists for)")
    ap.add_argument("--fleet-iters", type=int, default=150,
                    help="boosting rounds for the shared fleet model "
                         "(default 150 — big enough that per-batch "
                         "predict dispatch, the cost coalescing "
                         "amortizes, dominates fixed HTTP overhead)")
    ap.add_argument("--fleet-streams", type=int, default=2,
                    help="concurrent client streams per tenant in the "
                         "coalesced phase (default 2: several "
                         "same-tenant requests in flight is what "
                         "makes per-tick merging visible)")
    ap.add_argument("--fleet-coalesce-us", type=int, default=2000,
                    help="coalescer max-wait (tpu_fleet_coalesce_us)")
    ap.add_argument("--fleet-slo-p99-ms", type=float, default=250.0,
                    help="per-tenant p99 admission threshold for the "
                         "bench daemon (tpu_fleet_slo_p99_ms); 0 "
                         "disables shedding")
    ap.add_argument("--slo", default="",
                    help="SLO spec string (obs/slo.py grammar) for the "
                         "JSON line's 'slo' section — budget remaining, "
                         "burn rate, p99.9 tails; default: a generous "
                         "built-in set per bench mode")
    ap.add_argument("--lrb-rate", type=float, default=-1.0,
                    help="offered request rate (requests/s) for the "
                         "lrb-stream feeder; -1 = auto-calibrate so "
                         "one window of arrivals spans ~2.5x the warm "
                         "training wall; 0 = closed loop (no pacing)")
    ap.add_argument("--sparse", action="store_true",
                    help="run ONLY the sparse CTR workload bench: the "
                         "same synthetic CSR matrix trained "
                         "dense-densified vs CSR-native (io/sparse.py)"
                         ", each route in its own subprocess so host "
                         "peak RSS is per-route; emits a standalone "
                         "JSON line (unit rows/s, details under "
                         "'sparse')")
    ap.add_argument("--sparse-route", default="",
                    choices=["", "dense", "csr"],
                    help="(internal) run ONE sparse-bench route in "
                         "this process and print its JSON")
    ap.add_argument("--sparse-rows", type=int, default=200_000)
    ap.add_argument("--sparse-features", type=int, default=256)
    ap.add_argument("--sparse-density", type=float, default=0.01,
                    help="fraction of explicit cells in the synthetic "
                         "CTR workload (default ~1%%)")
    ap.add_argument("--sparse-iters", type=int, default=30)
    ap.add_argument("--rank", action="store_true",
                    help="run ONLY the ranking workload bench: one "
                         "on-disk lambdarank dataset loaded in-memory "
                         "vs out-of-core (tpu_out_of_core=1), each "
                         "route in its own subprocess for a clean "
                         "peak-RSS watermark; NDCG (plain + hashed "
                         "GOSS), rows/s, OOC RSS ratio and the "
                         "same-geometry retrain step-cache hit rate "
                         "(JSON details under 'rank')")
    ap.add_argument("--rank-route", default="",
                    choices=["", "memory", "ooc"],
                    help="(internal) run ONE rank-bench route in this "
                         "process and print its JSON")
    ap.add_argument("--rank-file", default="",
                    help="(internal) pre-written ranking CSV for "
                         "--rank-route")
    ap.add_argument("--rank-rows", type=int, default=200_000)
    ap.add_argument("--rank-features", type=int, default=16)
    ap.add_argument("--rank-qsize", type=int, default=50,
                    help="rows per synthetic query (default 50)")
    ap.add_argument("--rank-iters", type=int, default=30)
    ap.add_argument("--parity", action="store_true",
                    help="append the measured reference-parity "
                         "harness to the standard bench: train BOTH "
                         "tiers of this engine (exact hi/lo and int8 "
                         "count-proxy) and reference LightGBM CPU on "
                         "the same synthetic data, record {auc_ref, "
                         "auc_tpu, ref_wall, tpu_wall} per tier under "
                         "'parity' in the JSON line (plus a "
                         "'vs_measured' sibling of vs_baseline), and "
                         "assert |auc_ref - auc_tpu| <= "
                         "--parity-auc-tol (exit 1 on a miss, after "
                         "the JSON is emitted); a missing reference "
                         "records a skip reason instead")
    ap.add_argument("--parity-auc-tol", type=float,
                    default=PARITY_AUC_TOL,
                    help="measured AUC-parity ceiling vs reference "
                         "LightGBM (default 4e-4, the reference's own "
                         "GPU-vs-CPU bar at 63 bins)")
    args = ap.parse_args()
    if args.slo:
        # refuse a malformed spec NOW, not after an hours-long run
        # when slo_section() would crash before the JSON line is
        # emitted (the config.py tpu_slo validation rule)
        from lightgbm_tpu.obs.slo import parse_specs
        try:
            parse_specs(args.slo)
        except ValueError as e:
            ap.error(str(e))
    if args.quick:
        args.rows, args.iters, args.leaves = 65_536, 20, 63

    # --sparse/--rank run each route in a child process that needs the
    # chip, and a chip belongs to ONE process at a time: those two
    # parents stay off jax entirely (the children pass this gate, and
    # report the device kind the parent stamps into its metric)
    if not (args.sparse or args.rank):
        _require_tpu()

    if args.sparse_route:
        print(json.dumps(sparse_route_run(args)))
        return

    if args.rank_route:
        print(json.dumps(rank_route_run(args)))
        return

    if args.rank:
        rank = rank_bench(args)
        print(json.dumps({
            "rank": rank,
            "metric": (f"lambdarank ranking training "
                       f"({rank['rows']} rows x "
                       f"{rank['features']} feat, "
                       f"{rank['qsize']}-row queries, "
                       f"{rank['iters']} iters, out-of-core)"
                       + _metric_tag(rank["device_kind"])),
            "value": rank["routes"]["ooc"]["rows_per_s"],
            "unit": "rows/s",
        }))
        return

    if args.sparse:
        sparse = sparse_bench(args)
        print(json.dumps({
            "sparse": sparse,
            "metric": (f"sparse CTR GBDT training "
                       f"({sparse['rows']} rows x "
                       f"{sparse['features']} feat, density "
                       f"{sparse['density']:g}, "
                       f"{sparse['iters']} iters)"
                       + _metric_tag(sparse["device_kind"])),
            "value": sparse["routes"]["csr"]["rows_per_s"],
            "unit": "rows/s",
        }))
        return

    if args.fleet:
        from lightgbm_tpu.ops import autotune as _autotune
        _autotune.ensure_compile_cache()
        fleet = fleet_bench(args)
        print(json.dumps({
            "fleet": fleet,
            "slo": slo_section(args.slo or DEFAULT_SLO_FLEET),
            "metric": ("fleet coalesced serving "
                       f"({fleet['tenants']} tenants x "
                       f"{fleet['requests_per_tenant']} requests, "
                       f"{fleet['rows_per_request']}-row requests)"
                       + _metric_tag()),
            "value": fleet["requests_per_s"],
            "unit": "requests/s",
        }))
        return

    if args.lrb_stream:
        from lightgbm_tpu.ops import autotune as _autotune
        _autotune.ensure_compile_cache()
        stream = lrb_stream_bench(args)
        print(json.dumps({
            "lrb_stream": stream,
            "slo": slo_section(args.slo or DEFAULT_SLO_STREAM),
            "metric": ("LRB streaming retrain-while-serve "
                       f"({stream['windows']} windows x "
                       f"{stream['window_rows']} rows, sample "
                       f"{stream['sample_rows']}, "
                       f"{stream['iters']} iters)" + _metric_tag()),
            "value": stream["requests_per_s"],
            "unit": "requests/s",
        }))
        return

    # persistent compile cache: the grower/predict kernels compile once
    # per machine instead of once per process (~30-60 s saved per run);
    # shares the autotuner's cache-dir scheme (ops/autotune.py)
    from lightgbm_tpu.ops import autotune
    autotune.ensure_compile_cache()

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import TpuDataset, Metadata
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.metrics import create_metrics

    # run recorder (obs/recorder.py): per-iteration wall times, HBM and
    # transfer-byte samples; the phase table it snapshots at finish()
    # IS the JSON line's phase breakdown (no hand-rolled sub-phase
    # bookkeeping here)
    from lightgbm_tpu.obs.recorder import RunRecorder
    from lightgbm_tpu.utils import timing
    recorder = RunRecorder(
        path=args.run_report,
        meta={"driver": "bench", "rows": args.rows, "iters": args.iters,
              "leaves": args.leaves, "max_bin": args.max_bin,
              "learner": args.learner,
              "quantized": not args.no_quant,
              "ingest": "host" if args.no_ingest else "auto"}).start()

    t0 = time.time()
    # +holdout: the reference's headline quality number is TEST-set AUC
    # (docs/Experiments.rst:125-127); the timed training uses args.rows
    X, y = make_higgs_like(args.rows + HOLDOUT_ROWS)
    X_test, y_test = X[args.rows:], y[args.rows:]
    X, y = X[:args.rows], y[:args.rows]
    timing.add("bench/datagen", time.time() - t0)
    print(f"# data gen: {time.time()-t0:.1f}s", file=sys.stderr)

    cfg = Config().set({
        "objective": "binary", "metric": "auc",
        "num_leaves": args.leaves, "max_bin": args.max_bin,
        "learning_rate": 0.1, "min_data_in_leaf": 20,
        # run every iteration on device; no periodic host sync inside
        "tpu_stop_check_interval": 10_000,
        # int8 gradient quantization: exact int32 histogram sums of
        # stochastically-rounded int8 g/h at 2x MXU rate (the train-AUC
        # printed below shows quality parity with the f32 path)
        "tpu_quantized_hist": not args.no_quant,
        "tree_learner": args.learner,
        # streamed device ingest (io/ingest.py): -1 auto-enables on a
        # real TPU; --no-ingest pins the host binner for A/B runs
        "tpu_ingest": 0 if args.no_ingest else -1,
        "tpu_run_report": args.run_report,
    })
    t0 = time.time()
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective("binary", cfg)
    obj.init(ds.metadata, ds.num_data)
    mets = create_metrics(["auc"], cfg, ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, mets)      # kernel autotuning happens here
    binning_init_s = time.time() - t0
    tune_s = timing.seconds("autotune")
    # ingest sub-phases (timing.phase accumulators, device-synced at
    # phase exit), reported DISJOINT: find_bins = sampled boundary
    # search; device_xfer = host->device transfer issue (chunked
    # device_put on the streamed path — nested inside the bin_matrix
    # phase, so it is subtracted back out — plus the bulk [F, N]
    # upload on the host path); bin_matrix = the value->bin mapping
    # itself (device kernel time on the streamed path)
    find_bins_s = timing.seconds("binning/find_bins")
    ingest_xfer_s = timing.seconds("binning/device_xfer")
    bin_matrix_s = max(
        timing.seconds("binning/bin_matrix") - ingest_xfer_s, 0.0)
    device_xfer_s = ingest_xfer_s + timing.seconds("init/upload_bins")
    print(f"# binning+init: {binning_init_s:.1f}s "
          f"(find_bins {find_bins_s:.1f}s, bin_matrix {bin_matrix_s:.1f}s, "
          f"device_xfer {device_xfer_s:.1f}s, "
          f"kernel autotune: {tune_s:.1f}s)", file=sys.stderr)

    import numpy as _np

    def sync():
        # stop the clock on a real device->host readback of the last
        # iteration's scores (the transfer is ordered behind every
        # queued step). Written for a backend that no longer exists;
        # on the in-process chip block_until_ready was observed to wait
        # as well (chip_smoke.py prints the comparison) — the readback
        # is correct either way
        return float(_np.asarray(g._scores[0, :1])[0])

    # one warm-up iteration compiles the grower (a warm persistent
    # compile cache + tuning cache make this step mostly iter0)
    t0 = time.time()
    with recorder.iteration(1):
        g.train_one_iter()
        sync()
    compile_s = time.time() - t0
    timing.add("bench/compile_iter0", compile_s)
    print(f"# compile+iter0: {compile_s:.1f}s", file=sys.stderr)

    t0 = time.time()
    for i in range(args.iters - 1):
        # per-iteration spans are dispatch-issue time (jax async); the
        # sync below attributes queued device time to the run total
        with recorder.iteration(i + 2):
            g.train_one_iter()
    sync()
    train_s = time.time() - t0
    timing.add("bench/train", train_s)
    (_, auc, _), = g.get_eval_at(0)
    # holdout predict in serving-shaped batches: each batch's wall
    # (dispatch + device->host materialize) feeds the log-bucketed
    # predict/latency_s instrument (obs/registry.py latency_histogram),
    # so the JSON line reports p50/p95/p99 — the measurement bed the
    # bench --serve path will stand on. The first batch carries the
    # forest kernel's tune+compile (the cold-start tail, reported as
    # max/p99, not hidden).
    from lightgbm_tpu.obs import registry as obs_registry
    # divides HOLDOUT_ROWS exactly: every batch is one jit shape, so
    # the cold compile really is only in batch 1 (a ragged tail batch
    # would pay a second compile and fake a latency outlier)
    pred_batch = 20_000
    lat = obs_registry.latency_histogram("predict/latency_s")
    t0 = time.time()
    parts = []
    for r0 in range(0, len(X_test), pred_batch):
        tb = time.time()
        parts.append(np.asarray(g.predict_raw(X_test[r0:r0 + pred_batch])))
        lat.observe(time.time() - tb)
    test_raw = np.concatenate(parts)
    test_auc = _auc(y_test, test_raw)
    pred_s = time.time() - t0
    timing.add("bench/predict_holdout", pred_s)
    lat_q = lat.quantiles()
    print(f"# {args.iters} iters in {train_s:.1f}s  train-AUC={auc:.5f}  "
          f"test-AUC={test_auc:.5f}  "
          f"(holdout predict {HOLDOUT_ROWS} rows x "
          f"{len(g.records) or len(g.models)} trees: {pred_s:.1f}s; "
          f"{pred_batch}-row batch latency "
          + " ".join(f"{k}={1e3 * v:.1f}ms" for k, v in lat_q.items()
                     if v is not None)
          + ")", file=sys.stderr)

    # phase breakdown: the tuning win (tune ~0 on a warm tuning cache)
    # and the compile-cache win (compile+iter0 collapses to iter0 on a
    # warm XLA cache) are both visible here. Re-read the accumulator:
    # the forest kernel tunes during the first predict, after the
    # init-time snapshot above.
    tune_s = timing.seconds("autotune")
    print(f"# phase breakdown: tune={tune_s:.1f}s "
          f"compile+iter0={compile_s:.1f}s train={train_s:.1f}s",
          file=sys.stderr)

    row_iters_per_s = args.rows * (args.iters - 1) / max(train_s, 1e-9)
    # the run report's phase table IS the emitted breakdown: every
    # timing.phase the run touched (binning/find_bins, binning/
    # bin_matrix, binning/device_xfer, init/upload_bins, autotune/*,
    # train/step_dispatch, ...) plus the bench/* spans added above —
    # no hand-maintained sub-phase arithmetic to drift
    # per-iteration psum payloads (data learner): the same accounting
    # gbdt.train records into its run report, through the same public
    # helpers (one stacked leaf download drives both)
    leaves, waves = (g.leaves_and_waves() if g.num_devices > 1
                     else ([], []))
    comm = g.record_comm_bytes(recorder, waves) if waves else None
    # None (JSON null) when accounting is unavailable (serial/voting):
    # a literal 0 would read as "zero cross-chip bytes"
    comm_per_iter = round(float(np.mean(comm))) if comm else None

    # --retrain K: the lrb.py per-window pattern — K FRESH boosters on
    # the same data. With the compiled-step registry warm from the run
    # above, each retrain's first step should dispatch in ~0s (a cache
    # hit) instead of re-paying the cold compile.
    from lightgbm_tpu.ops import step_cache
    retrain = None
    if args.retrain > 0:
        warm_first = []
        s0 = step_cache.stats()
        t_retrain = time.time()
        for r in range(args.retrain):
            gr_ = GBDT()
            gr_.init(cfg, ds, obj, mets)
            t0 = time.time()
            gr_.train_one_iter()
            sync_r = float(_np.asarray(gr_._scores[0, :1])[0])  # noqa: F841
            warm_first.append(time.time() - t0)
            for _ in range(4):
                gr_.train_one_iter()
            float(_np.asarray(gr_._scores[0, :1])[0])
        s1 = step_cache.stats()
        hits, misses = s1["hits"] - s0["hits"], s1["misses"] - s0["misses"]
        retrain = {
            "boosters": args.retrain,
            "cold_compile_s": round(compile_s, 3),
            "warm_first_step_s": round(float(np.mean(warm_first)), 3),
            "hits": hits, "misses": misses,
            "hit_rate": round(hits / max(hits + misses, 1), 3),
            "total_s": round(time.time() - t_retrain, 2),
        }
        print(f"# retrain x{args.retrain}: warm first-step "
              f"{retrain['warm_first_step_s']:.3f}s vs cold compile "
              f"{compile_s:.1f}s, step-cache hit rate "
              f"{retrain['hit_rate']:.0%}", file=sys.stderr)

    # --serve: the online-inference half of the ledger. Per-request
    # wall (dispatch + device->host materialize) at serving-shaped
    # batch sizes, through the SAME public predict entry a model
    # server would call — micro-batches pad to pow2 serve buckets and
    # dispatch through the geometry-keyed predict registry, so every
    # batch size 1..bucket rides one warm compiled program.
    from lightgbm_tpu.obs import reqlog as obs_reqlog
    from lightgbm_tpu.ops import predict_cache
    serve = None
    if args.serve:
        serve = {"batches": {}}
        pc0 = predict_cache.stats()
        for b in (1, 64, 4096):
            # bounded-cardinality: b in (1, 64, 4096)
            hist = obs_registry.latency_histogram(
                f"serve/latency_s_b{b}")
            n_test = len(X_test)
            for _ in range(2):          # warmup: compile + registry
                g.predict_raw(X_test[:b])
            reqs = rows = 0
            t0 = time.time()
            t_end = t0 + args.serve_seconds
            while time.time() < t_end:
                r0 = (reqs * b) % max(n_test - b, 1)
                # request-scoped (obs/reqlog.py): each serve request
                # gets a monotonic id carried through the predict
                # stack (spans tagged, serve bucket noted) and ONE
                # wide event — the same identity a model server's
                # stream would carry
                rid = obs_reqlog.next_request_id()
                tb = time.time()
                with obs_reqlog.request(rid) as rctx:
                    g.predict_raw(X_test[r0:r0 + b])
                dt = time.time() - tb
                hist.observe(dt)
                obs_reqlog.record(
                    "request", req_id=rid, path="bench/serve", rows=b,
                    latency_ms=round(1e3 * dt, 3),
                    serve_bucket=rctx.bucket)
                reqs += 1
                rows += b
            wall = time.time() - t0
            q = hist.quantiles((0.5, 0.95, 0.99))
            serve["batches"][str(b)] = {
                "requests": reqs,
                "rows_per_s": round(rows / max(wall, 1e-9), 1),
                **{f"{k}_ms": (None if v is None
                               else round(1e3 * v, 3))
                   for k, v in q.items()},
            }
            print(f"# serve b={b}: {reqs} reqs, "
                  f"{serve['batches'][str(b)]['rows_per_s']:.0f} "
                  "rows/s, "
                  + " ".join(f"{k}={1e3 * v:.2f}ms"
                             for k, v in q.items() if v is not None),
                  file=sys.stderr)
        pc1 = predict_cache.stats()
        serve["predict_cache"] = {
            k: pc1[k] - pc0[k] for k in ("hits", "misses", "stacks",
                                         "extends")}

    # compact streaming retrain-while-serve section (bench hygiene:
    # the trajectory point captures requests/s + during-retrain p99 +
    # staleness, so BENCH_r0x diffs show the serving story too)
    stream = None
    if not args.no_lrb_stream:
        stream = lrb_stream_bench(args)
        recorder.meta["lrb_stream"] = stream

    # --parity: the measured reference-parity harness — both tiers of
    # this engine and reference LightGBM CPU on the SAME data, so the
    # trajectory carries separate exact-tier / proxy-tier throughput
    # lines and vs_measured stands on a measured reference run
    parity = None
    if args.parity:
        parity = parity_bench(args, data=(X, y, X_test, y_test))
        recorder.meta["parity"] = parity

    # SLO/error-budget section: evaluated over the run's own predict/
    # serve histograms (p99.9 now rides the quantile readout); the
    # regression tool validates the section's shape
    slo = slo_section(args.slo or DEFAULT_SLO_TRAIN)
    recorder.meta["slo"] = slo

    recorder.meta["step_cache"] = step_cache.stats()
    recorder.meta["predict_cache"] = predict_cache.stats()
    report = recorder.finish(
        leaves_per_iteration=leaves or None,
        waves_per_iteration=waves or None,
        extra={
        "train_s": round(train_s, 2), "compile_s": round(compile_s, 2),
        "mesh_devices": g.num_devices,
        "comm_bytes_per_iter": comm_per_iter,
        "train_auc": round(float(auc), 5),
        "test_auc": round(float(test_auc), 5)})
    result = {
        "phases": {name: round(rec["total_s"], 2)
                   for name, rec in report["phases"].items()},
        "counters": {k: v for k, v in report["counters"].items()
                     if k.startswith(("ingest/", "transfer/", "comm/"))},
        "ingest": "host" if args.no_ingest else "auto",
        "chips": g.num_devices,
        "comm_bytes_per_iter": comm_per_iter,
        "step_cache": step_cache.stats(),
        "predict_cache": predict_cache.stats(),
        "serve": serve,
        "retrain": retrain,
        "lrb_stream": stream,
        "slo": slo,
        "parity": parity,
        "device_kind": autotune.device_kind(),
        "train_auc": round(float(auc), 5),
        "test_auc": round(float(test_auc), 5),
        # quantiles from the log-bucketed histogram, not a sample list:
        # the same instrument a live exporter scrape sees
        "predict_latency": {
            "batch_rows": pred_batch,
            "batches": lat.count,
            "mean_ms": round(1e3 * lat.sum / max(lat.count, 1), 3),
            **{f"{k}_ms": (None if v is None else round(1e3 * v, 3))
               for k, v in lat_q.items()},
        },
        "metric": ("HIGGS-class GBDT training throughput "
                   f"({args.rows} rows x 28 feat, {args.leaves} leaves, "
                   f"{args.max_bin} bins, {args.iters} iters, "
                   f"{g.num_devices}"
                   " chip(s))" + _metric_tag()),
        "value": round(row_iters_per_s / 1e6, 3),
        "unit": "M row-iters/s",
        "vs_baseline": round(row_iters_per_s / BASELINE_ROW_ITERS_PER_S, 3),
        # the measured sibling: the parity harness's like-for-like
        # wall ratio for the tier this headline ran (proxy unless
        # --no-quant) — ref wall / engine wall, both spanning dataset
        # construction through the last iteration. Null (with
        # parity.skip_reason recorded) when the reference is
        # unavailable or --parity was not requested.
        "vs_measured": (
            parity["tiers"]["exact" if args.no_quant
                            else "proxy"]["vs_measured"]
            if parity else None),
    }
    print(json.dumps(result))
    if parity is not None and not parity["ok"]:
        print(f"# PARITY FAILURE: AUC delta vs measured reference "
              f"exceeds {args.parity_auc_tol:g}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
