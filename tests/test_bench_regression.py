"""Bench-regression gate (tools/check_bench_regression.py): artifact
normalization (raw bench JSON + BENCH_r0x wrappers, tail-AUC
recovery), schema validation, trajectory comparison semantics, and a
slow-marked end-to-end run of ``bench.py --quick`` through the tool.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_bench_regression as cbr  # noqa: E402

pytestmark = pytest.mark.obs


def _fresh(metric="M", value=50.0, test_auc=0.927, **kw):
    d = {"metric": metric, "value": value, "unit": "M row-iters/s",
         "test_auc": test_auc}
    d.update(kw)
    return d


# -- normalization -----------------------------------------------------------

def test_load_bench_raw_and_wrapper(tmp_path):
    raw = _fresh()
    p = tmp_path / "fresh.json"
    p.write_text(json.dumps(raw))
    assert cbr.load_bench(str(p))["value"] == 50.0
    # BENCH_r0x wrapper: numbers under "parsed", AUC only in the tail
    wrapper = {"rc": 0,
               "tail": "# 500 iters in 112.1s  train-AUC=0.93202  "
                       "test-AUC=0.92726  (holdout...)",
               "parsed": {"metric": "M", "value": 48.954,
                          "unit": "M row-iters/s"}}
    norm = cbr.load_bench(wrapper)
    assert norm["value"] == 48.954
    assert norm["test_auc"] == pytest.approx(0.92726)
    assert norm["train_auc"] == pytest.approx(0.93202)


def test_trajectory_orders_numerically(tmp_path):
    """r10 must sort AFTER r9 (lexicographic order would pin the gate
    to a stale baseline once the run index grows a digit)."""
    for name in ("BENCH_r9.json", "BENCH_r10.json", "BENCH_r2.json"):
        (tmp_path / name).write_text("{}")
    names = [os.path.basename(p) for p in cbr.trajectory(str(tmp_path))]
    assert names == ["BENCH_r2.json", "BENCH_r9.json", "BENCH_r10.json"]


def test_repo_trajectory_loads_and_self_passes(tmp_path):
    """A trajectory of driver-shaped wrapper records ({"parsed": ...,
    "tail": ...} — the shape the repo's round-1..5 records had)
    normalizes, and the latest point compared against itself passes
    (the tool's identity check). Written here: the repo no longer
    carries on-chip records from a retired backend."""
    metric = ("HIGGS-class GBDT training throughput (11000000 rows x "
              "28 feat, 255 leaves, 63 bins, 500 iters, 1 chip(s))")
    for n, (value, tr, te) in enumerate(
            [(21.3, 0.9310, 0.9262), (48.954, 0.93202, 0.92726)], 1):
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "tail": f"# compile+iter0: 18.8s\n# 500 iters in 112.1s  "
                    f"train-AUC={tr}  test-AUC={te}  (holdout predict "
                    f"500000 rows x 500 trees: 16.5s)\n",
            "parsed": {"metric": metric, "value": value,
                       "unit": "M row-iters/s", "vs_baseline": 2.1}}))
    points = cbr.trajectory(str(tmp_path))
    assert len(points) == 2
    latest = cbr.load_bench(points[-1])
    assert not cbr.check_schema(latest)
    assert latest["value"] == 48.954
    assert latest.get("test_auc") == pytest.approx(0.92726), \
        "tail AUC recovery failed on the wrapper trajectory"
    assert cbr.compare(latest, latest) == []


# -- schema ------------------------------------------------------------------

def test_check_schema():
    assert cbr.check_schema(_fresh()) == []
    assert cbr.check_schema({"unit": "rows"})   # several problems
    bad_lat = _fresh(predict_latency={"p50_ms": 1.0, "p95_ms": None,
                                      "p99_ms": 2.0})
    assert any("p95" in p for p in cbr.check_schema(bad_lat))
    good_lat = _fresh(predict_latency={"p50_ms": 1.0, "p95_ms": 2.0,
                                       "p99_ms": 3.0})
    assert cbr.check_schema(good_lat) == []
    # malformed artifact must be REPORTED, not crash the validator
    assert any("not a dict" in p for p in
               cbr.check_schema(_fresh(predict_latency="n/a")))


# -- comparison semantics ----------------------------------------------------

def test_compare_throughput_and_auc():
    base = _fresh(value=49.0, test_auc=0.927)
    assert cbr.compare(_fresh(value=45.0, test_auc=0.9275), base) == []
    # throughput: 20% tolerance boundary
    probs = cbr.compare(_fresh(value=35.0), base)
    assert probs and "throughput regression" in probs[0]
    assert cbr.compare(_fresh(value=39.3), base) == []
    # quality: absolute AUC drop beyond tolerance
    probs = cbr.compare(_fresh(test_auc=0.920), base)
    assert probs and "quality regression" in probs[0]
    # a fresh run that LOST the AUC field cannot silently pass
    fresh = _fresh()
    del fresh["test_auc"]
    assert any("no test_auc" in p for p in cbr.compare(fresh, base))


def test_compare_latency_gate():
    """predict_latency p50/p99 within --latency-tol of a baseline that
    carries the quantiles; old baselines without the field gate
    nothing; a fresh run that lost the field cannot silently pass."""
    lat = {"p50_ms": 10.0, "p95_ms": 15.0, "p99_ms": 20.0}
    base = _fresh(predict_latency=dict(lat))
    # within 50%: pass
    ok = _fresh(predict_latency={"p50_ms": 14.0, "p95_ms": 21.0,
                                 "p99_ms": 29.0})
    assert cbr.compare(ok, base) == []
    # p50 beyond tolerance: regression names the quantile
    slow = _fresh(predict_latency={"p50_ms": 16.0, "p95_ms": 16.0,
                                   "p99_ms": 21.0})
    probs = cbr.compare(slow, base)
    assert probs and "latency regression" in probs[0] \
        and "p50_ms" in probs[0]
    # p99 tail regression caught independently of a healthy p50
    tail = _fresh(predict_latency={"p50_ms": 9.0, "p95_ms": 16.0,
                                   "p99_ms": 40.0})
    probs = cbr.compare(tail, base)
    assert len(probs) == 1 and "p99_ms" in probs[0]
    # tolerance flag respected
    assert cbr.compare(slow, base, latency_tol=1.0) == []
    # baseline predates the field: nothing to gate
    assert cbr.compare(_fresh(predict_latency={"p50_ms": 999.0,
                                               "p95_ms": 999.0,
                                               "p99_ms": 999.0}),
                       _fresh()) == []
    # fresh LOST the field vs a baseline that has it
    probs = cbr.compare(_fresh(), base)
    assert any("no predict_latency" in p for p in probs)
    # cross-workload refusal still wins over everything
    probs = cbr.compare(_fresh(metric="other", predict_latency=lat),
                        base)
    assert len(probs) == 1 and "not comparable" in probs[0]


def test_cli_latency_tol_flag(tmp_path):
    """--latency-tol reaches the comparison (exit 1 at the default,
    exit 0 when widened)."""
    base_dir = tmp_path / "repo"
    base_dir.mkdir()
    lat = {"p50_ms": 10.0, "p95_ms": 15.0, "p99_ms": 20.0}
    (base_dir / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": _fresh(value=49.0, predict_latency=lat)}))
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_fresh(
        value=49.0, predict_latency={"p50_ms": 18.0, "p95_ms": 20.0,
                                     "p99_ms": 22.0})))
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir),
                     "--latency-tol", "1.0"]) == 0


def test_compare_refuses_cross_workload():
    base = _fresh(metric="HIGGS 11000000 rows")
    probs = cbr.compare(_fresh(metric="quick 65536 rows", value=1.0),
                        base)
    assert len(probs) == 1 and "not comparable" in probs[0]


def test_compare_refuses_cross_backend():
    """bench.py stamps the device kind into the metric string
    (bench._metric_tag), so a CPU number is structurally incomparable
    with a GPU or TPU trajectory point — compare() refuses instead of
    ratioing across backends."""
    shape = "HIGGS-class GBDT training throughput (65536 rows)"
    base = _fresh(metric=shape + " [NVIDIA H100]")
    probs = cbr.compare(_fresh(metric=shape + " [cpu]", value=1.0),
                        base)
    assert len(probs) == 1 and "not comparable" in probs[0]
    # the refusal names both stamps so a sweep log is self-explaining
    assert "[cpu]" in probs[0] and "[NVIDIA H100]" in probs[0]


def test_metric_tag_matches_device_kind():
    """The stamp bench.py appends is exactly the autotuner's device
    kind in brackets — the same value the parity section records, so
    the metric-string gate and _parity_comparable agree on identity."""
    sys.path.insert(0, REPO)
    import bench
    from lightgbm_tpu.ops import autotune
    assert bench._metric_tag() == f" [{autotune.device_kind()}]"


def test_cli_cross_backend_exit_2_and_walkback(tmp_path):
    """A fresh CPU run against a trajectory whose NEWEST point was
    recorded on GPU: baseline selection filters on metric equality, so
    it walks back past the non-matching-backend point to the newest
    same-device one and gates there; a fresh run from a backend with
    no trajectory point at all is refused (exit 2), never ratioed
    against another device's numbers."""
    shape = "HIGGS-class GBDT training throughput (65536 rows)"
    base_dir = tmp_path / "repo"
    base_dir.mkdir()
    (base_dir / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": _fresh(metric=shape + " [cpu]", value=49.0)}))
    (base_dir / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": _fresh(metric=shape + " [NVIDIA H100]",
                          value=490.0)}))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fresh(metric=shape + " [cpu]",
                                    value=48.0)))
    # walk-back past the newer GPU point: 48 passes the 49 CPU floor
    # (against the GPU point it would read as a 10x regression)
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_fresh(metric=shape + " [cpu]",
                                      value=10.0)))
    # ...and the walked-back point still GATES (exit 1 = regression)
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1
    tpu = tmp_path / "tpu.json"
    tpu.write_text(json.dumps(_fresh(metric=shape + " [TPU v4]",
                                     value=700.0)))
    # no TPU point anywhere on the trajectory: refusal, exit 2
    assert cbr.main([str(tpu), "--baseline-dir", str(base_dir)]) == 2


def test_cli_pass_fail_and_exit_codes(tmp_path):
    base_dir = tmp_path / "repo"
    base_dir.mkdir()
    (base_dir / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": _fresh(value=49.0)}))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fresh(value=48.0)))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_fresh(value=10.0)))
    garbled = tmp_path / "garbled.json"
    garbled.write_text(json.dumps({"unit": "bananas"}))
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    assert cbr.main([str(bad), "--baseline-dir", str(base_dir)]) == 1
    assert cbr.main([str(garbled), "--baseline-dir",
                     str(base_dir)]) == 2
    assert cbr.main([str(bad), "--schema-only"]) == 0


# -- lrb-stream (retrain-while-serve) gate -----------------------------------

def _stream(requests_per_s=230.0, staleness=0.0, p99d=45.0, **kw):
    d = {"windows": 8, "window_rows": 2048,
         "requests_per_s": requests_per_s,
         "staleness_p99_windows": staleness,
         "serve_p99_during_retrain_ms": p99d,
         "speedup": 2.5}
    d.update(kw)
    return d


def test_check_schema_lrb_stream():
    # the standalone --lrb-stream line: unit requests/s + stream block
    standalone = {"metric": "LRB streaming retrain-while-serve (8...)",
                  "value": 230.0, "unit": "requests/s",
                  "lrb_stream": _stream()}
    assert cbr.check_schema(standalone) == []
    # a training line CARRYING the appended stream section
    assert cbr.check_schema(_fresh(lrb_stream=_stream())) == []
    # requests/s without the block is a shape problem
    assert any("lrb_stream" in p for p in cbr.check_schema(
        {"metric": "m", "value": 1.0, "unit": "requests/s"}))
    # missing gate fields are named
    broken = _stream()
    del broken["requests_per_s"]
    assert any("requests_per_s" in p
               for p in cbr.check_schema(_fresh(lrb_stream=broken)))
    # during-retrain p99 may be null (fast trainer), not a wrong type
    assert cbr.check_schema(_fresh(lrb_stream=_stream(p99d=None))) == []
    assert any("serve_p99_during_retrain_ms" in p for p in
               cbr.check_schema(_fresh(lrb_stream=_stream(p99d="n/a"))))
    assert any("not a dict" in p
               for p in cbr.check_schema(_fresh(lrb_stream="n/a")))


def _sparse_block(**kw):
    d = {"rows": 200_000, "features": 256, "density": 0.0098,
         "nnz": 501_760, "iters": 30,
         "routes": {
             "dense": {"route": "dense", "ingest_s": 3.2,
                       "train_s": 41.0, "rows_per_s": 146341.0,
                       "peak_rss_mb": 1410.2,
                       "sparse_hist_tier": False,
                       "model_sha1": "aa"},
             "csr": {"route": "csr", "ingest_s": 0.8, "train_s": 39.5,
                     "rows_per_s": 151898.0, "peak_rss_mb": 620.4,
                     "sparse_hist_tier": True, "model_sha1": "aa"}},
         "peak_rss_ratio": 2.273, "model_parity": True}
    d.update(kw)
    return d


def test_check_schema_sparse():
    # the standalone --sparse line: unit rows/s + sparse block
    standalone = {"metric": "sparse CTR GBDT training (200000 rows x "
                            "256 feat, density 0.0098, 30 iters)",
                  "value": 151898.0, "unit": "rows/s",
                  "sparse": _sparse_block()}
    assert cbr.check_schema(standalone) == []
    # rows/s without the block is a shape problem
    assert any("sparse" in p for p in cbr.check_schema(
        {"metric": "m", "value": 1.0, "unit": "rows/s"}))
    # missing route metrics are named per route
    broken = _sparse_block()
    del broken["routes"]["csr"]["peak_rss_mb"]
    assert any("routes.csr.peak_rss_mb" in p for p in cbr.check_schema(
        dict(standalone, sparse=broken)))
    no_dense = _sparse_block()
    del no_dense["routes"]["dense"]
    assert any("routes.dense" in p for p in cbr.check_schema(
        dict(standalone, sparse=no_dense)))
    # diverged models across routes fail the artifact outright
    assert any("model_parity" in p for p in cbr.check_schema(
        dict(standalone, sparse=_sparse_block(model_parity=False))))
    # wrong container types are reported, not crashed on
    assert any("not a dict" in p for p in cbr.check_schema(
        dict(standalone, sparse="n/a")))
    assert any("sparse.routes" in p for p in cbr.check_schema(
        dict(standalone, sparse=_sparse_block(routes=7))))
    # cross-workload refusal still wins: a sparse line never compares
    # against a HIGGS training baseline
    assert cbr.compare(standalone, _fresh())[0].startswith(
        "not comparable")


def _rank_block(**kw):
    d = {"rows": 200_000, "features": 16, "qsize": 50, "iters": 30,
         "routes": {
             "memory": {"route": "memory", "queries": 4000,
                        "ingest_s": 5.1, "train_s": 62.0,
                        "rows_per_s": 96774.0, "peak_rss_mb": 1810.0,
                        "ndcg": {"ndcg@1": 0.91, "ndcg@5": 0.87},
                        "ndcg_goss": {"ndcg@1": 0.90, "ndcg@5": 0.86},
                        "retrain_step_cache": {"hits": 2, "misses": 0,
                                               "hit_rate": 1.0},
                        "model_sha1": "bb"},
             "ooc": {"route": "ooc", "queries": 4000, "ingest_s": 6.3,
                     "train_s": 63.0, "rows_per_s": 95238.0,
                     "peak_rss_mb": 705.0,
                     "ndcg": {"ndcg@1": 0.91, "ndcg@5": 0.87},
                     "ndcg_goss": {"ndcg@1": 0.90, "ndcg@5": 0.86},
                     "retrain_step_cache": {"hits": 2, "misses": 0,
                                            "hit_rate": 1.0},
                     "model_sha1": "bb"}},
         "peak_rss_ratio": 2.567, "step_cache_hit_rate": 1.0,
         "model_parity": True}
    d.update(kw)
    return d


def test_check_schema_rank():
    # the standalone --rank line: unit rows/s + rank block (the
    # section key disambiguates it from --sparse, which shares the
    # unit)
    standalone = {"metric": "lambdarank ranking training (200000 rows "
                            "x 16 feat, 50-row queries, 30 iters, "
                            "out-of-core)",
                  "value": 95238.0, "unit": "rows/s",
                  "rank": _rank_block()}
    assert cbr.check_schema(standalone) == []
    # missing route metrics are named per route
    broken = _rank_block()
    del broken["routes"]["ooc"]["peak_rss_mb"]
    assert any("rank.routes.ooc.peak_rss_mb" in p
               for p in cbr.check_schema(dict(standalone, rank=broken)))
    no_mem = _rank_block()
    del no_mem["routes"]["memory"]
    assert any("rank.routes.memory" in p for p in cbr.check_schema(
        dict(standalone, rank=no_mem)))
    # NDCG must survive as a non-empty numeric dict — the quality
    # ledger must not silently disappear
    no_ndcg = _rank_block()
    no_ndcg["routes"]["ooc"]["ndcg"] = {}
    assert any("rank.routes.ooc.ndcg" in p for p in cbr.check_schema(
        dict(standalone, rank=no_ndcg)))
    # the step-cache hit rate and RSS ratio are the PR's headline
    # observables — a line that lost them fails shape
    for k in ("peak_rss_ratio", "step_cache_hit_rate"):
        gone = _rank_block()
        del gone[k]
        assert any(f"rank.{k}" in p for p in cbr.check_schema(
            dict(standalone, rank=gone)))
    # OOC promises BIT parity: diverged models fail the artifact
    assert any("model_parity" in p for p in cbr.check_schema(
        dict(standalone, rank=_rank_block(model_parity=False))))
    # wrong container types are reported, not crashed on
    assert any("not a dict" in p for p in cbr.check_schema(
        dict(standalone, rank="n/a")))
    assert any("rank.routes" in p for p in cbr.check_schema(
        dict(standalone, rank=_rank_block(routes=7))))
    # cross-workload refusal still wins — a rank line never compares
    # against a sparse line even though they share the rows/s unit
    sparse_line = {"metric": "sparse CTR GBDT training (...)",
                   "value": 151898.0, "unit": "rows/s",
                   "sparse": _sparse_block()}
    assert cbr.compare(standalone, sparse_line)[0].startswith(
        "not comparable")


def test_compare_rank_gate():
    metric = ("lambdarank ranking training (200000 rows x 16 feat, "
              "50-row queries, 30 iters, out-of-core)")

    def line(**kw):
        return {"metric": metric, "value": 95238.0, "unit": "rows/s",
                "rank": _rank_block(**kw)}

    def with_ooc(**route_kw):
        blk = _rank_block()
        blk["routes"]["ooc"].update(route_kw)
        return {"metric": metric, "value": 95238.0, "unit": "rows/s",
                "rank": blk}

    base = line()
    # same numbers: pass
    assert cbr.compare(line(), base) == []
    # NDCG floor (--auc-tol): ranking quality must not silently decay
    probs = cbr.compare(
        with_ooc(ndcg={"ndcg@1": 0.80, "ndcg@5": 0.87}), base)
    assert probs and "ranking-quality regression" in probs[0]
    assert "ndcg@1" in probs[0]
    # within the tolerance: pass
    assert cbr.compare(
        with_ooc(ndcg={"ndcg@1": 0.9095, "ndcg@5": 0.87}), base) == []
    # OOC peak-RSS ceiling (--latency-tol slack): RSS creep back
    # toward the in-memory watermark is the regression OOC prevents
    probs = cbr.compare(with_ooc(peak_rss_mb=1500.0), base)
    assert probs and "out-of-core RSS regression" in probs[0]
    assert cbr.compare(with_ooc(peak_rss_mb=900.0), base) == []
    # a fresh run that LOST the section against a carrier is a problem
    lost = {"metric": metric, "value": 95238.0, "unit": "rows/s"}
    probs = cbr.compare(lost, base)
    assert probs and "no rank section" in probs[0]
    # a baseline without the section gates nothing
    assert cbr.compare(line(), lost) == []
    # headline rows/s still rides the generic value floor
    slow = line()
    slow["value"] = 10_000.0
    probs = cbr.compare(slow, base)
    assert probs and "throughput regression" in probs[0]


def test_compare_lrb_stream_gate():
    base = _fresh(lrb_stream=_stream(requests_per_s=200.0,
                                     staleness=0.0))
    # within tolerance: pass
    assert cbr.compare(_fresh(lrb_stream=_stream(
        requests_per_s=190.0, staleness=0.5)), base) == []
    # sustained requests/s floor (same 20% tolerance as throughput)
    probs = cbr.compare(_fresh(lrb_stream=_stream(
        requests_per_s=100.0)), base)
    assert probs and "serving-throughput regression" in probs[0]
    # staleness lag ceiling: absolute slack in windows
    probs = cbr.compare(_fresh(lrb_stream=_stream(staleness=2.0)),
                        base)
    assert probs and "staleness regression" in probs[0]
    assert cbr.compare(_fresh(lrb_stream=_stream(staleness=2.0)),
                       base, staleness_slack=3.0) == []
    # old baselines without the section gate nothing
    assert cbr.compare(_fresh(lrb_stream=_stream(
        requests_per_s=1.0, staleness=99.0)), _fresh()) == []
    # a fresh run that LOST the section cannot silently pass
    probs = cbr.compare(_fresh(), base)
    assert any("no lrb_stream.requests_per_s" in p for p in probs)
    # cross-workload refusal still wins
    probs = cbr.compare(_fresh(metric="other",
                               lrb_stream=_stream()), base)
    assert len(probs) == 1 and "not comparable" in probs[0]
    # a baseline with a DIFFERENT stream shape gates nothing: the
    # training metric string does not embed the stream geometry, so
    # requests/s from a 4x-larger window is not a comparable floor
    assert cbr.compare(
        _fresh(lrb_stream=_stream(requests_per_s=10.0,
                                  window_rows=512)), base) == []


def test_cli_lrb_stream_walks_back_to_latest_carrier(tmp_path):
    """When the newest trajectory point predates the stream bench,
    the lrb-stream fields gate against the LATEST same-workload point
    that carries them — old points gate nothing beyond that."""
    base_dir = tmp_path / "repo"
    base_dir.mkdir()
    (base_dir / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": _fresh(value=49.0,
                          lrb_stream=_stream(requests_per_s=200.0))}))
    (base_dir / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": _fresh(value=49.0)}))      # newest: no stream block
    slow_serve = tmp_path / "fresh.json"
    slow_serve.write_text(json.dumps(_fresh(
        value=49.0, lrb_stream=_stream(requests_per_s=50.0))))
    assert cbr.main([str(slow_serve), "--baseline-dir",
                     str(base_dir)]) == 1
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fresh(
        value=49.0, lrb_stream=_stream(requests_per_s=195.0))))
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    # --staleness-slack reaches the comparison
    lagged = tmp_path / "lagged.json"
    lagged.write_text(json.dumps(_fresh(
        value=49.0, lrb_stream=_stream(requests_per_s=200.0,
                                       staleness=0.8))))
    assert cbr.main([str(lagged), "--baseline-dir",
                     str(base_dir)]) == 0
    assert cbr.main([str(lagged), "--baseline-dir", str(base_dir),
                     "--staleness-slack", "0.25"]) == 1
    # a newest point carrying a DIFFERENT stream shape must not
    # disable the gate either: walk back to the same-shape carrier
    (base_dir / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": _fresh(value=49.0,
                          lrb_stream=_stream(requests_per_s=5000.0,
                                             window_rows=256))}))
    assert cbr.main([str(slow_serve), "--baseline-dir",
                     str(base_dir)]) == 1
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    # a fresh run that LOST the section cannot hide behind a newest
    # point that also lacks it: the walk-back still finds the carrier
    lost = tmp_path / "lost.json"
    lost.write_text(json.dumps(_fresh(value=49.0)))
    assert cbr.main([str(lost), "--baseline-dir", str(base_dir)]) == 1


# -- fleet serving (bench.py --fleet) gate -----------------------------------

def _fleet_block(requests_per_s=130.0, worst_p99=70.0, **kw):
    d = {"tenants": 4, "requests_per_tenant": 300,
         "rows_per_request": 4, "streams_per_tenant": 2,
         "coalesce_us": 2000,
         "requests_per_s": requests_per_s,
         "requests_per_s_sequential": 78.0,
         "coalescing_speedup": 1.66,
         "per_tenant": {
             f"tenant_{i:02d}": {"requests": 300, "p50_ms": 30.0,
                                 "p99_ms": (worst_p99 if i == 0
                                            else 55.0),
                                 "shed": 0}
             for i in range(4)},
         "registry_hit_rate": 0.75, "registry_lookups": 8,
         "coalesced_batch_rows": {"batches": 505, "mean": 2.4,
                                  "p50": 2.0, "p99": 8.0},
         "shed_total": 0, "queue_rejects": 0,
         "requests_total": 1364, "client_retries": 0}
    d.update(kw)
    return d


def _fleet_doc(metric="fleet coalesced serving (4 tenants x 300 "
                      "requests, 4-row requests)", **kw):
    # top-level value pinned so these tests exercise the FLEET gates,
    # not the generic throughput floor (which reads ``value``)
    d = {"metric": metric, "unit": "requests/s", "value": 130.0,
         "fleet": _fleet_block()}
    d.update(kw)
    return d


def test_check_schema_fleet():
    # the standalone --fleet line: unit requests/s + fleet block, and
    # it must NOT be mistaken for an lrb_stream artifact
    assert cbr.check_schema(_fleet_doc()) == []
    # missing gate fields are named
    for k in ("requests_per_s", "requests_per_s_sequential",
              "shed_total", "queue_rejects", "tenants"):
        broken = _fleet_block()
        del broken[k]
        assert any(f"fleet.{k}" in p for p in
                   cbr.check_schema(_fleet_doc(fleet=broken)))
    # per-tenant quantiles must be numeric; null is a problem (a shed
    # count of 0 is fine, a MISSING quantile is lost evidence)
    broken = _fleet_block()
    broken["per_tenant"]["tenant_00"]["p99_ms"] = None
    assert any("per_tenant.tenant_00.p99_ms" in p for p in
               cbr.check_schema(_fleet_doc(fleet=broken)))
    assert any("per_tenant" in p for p in cbr.check_schema(
        _fleet_doc(fleet=_fleet_block(per_tenant={}))))
    assert any("per_tenant.t is" in p for p in cbr.check_schema(
        _fleet_doc(fleet=_fleet_block(per_tenant={"t": "n/a"}))))
    # registry hit rate: null only legitimate with zero lookups
    assert any("registry_hit_rate null" in p for p in cbr.check_schema(
        _fleet_doc(fleet=_fleet_block(registry_hit_rate=None))))
    assert cbr.check_schema(_fleet_doc(fleet=_fleet_block(
        registry_hit_rate=None, registry_lookups=0))) == []
    assert any("registry_hit_rate is" in p for p in cbr.check_schema(
        _fleet_doc(fleet=_fleet_block(registry_hit_rate="n/a"))))
    # batch-size histogram must exist (coalescing evidence)
    assert any("coalesced_batch_rows" in p for p in cbr.check_schema(
        _fleet_doc(fleet=_fleet_block(coalesced_batch_rows=None))))
    assert any("coalesced_batch_rows.batches" in p
               for p in cbr.check_schema(_fleet_doc(
                   fleet=_fleet_block(coalesced_batch_rows={}))))
    # wrong container type is reported, not crashed on
    assert any("not a dict" in p for p in
               cbr.check_schema(_fleet_doc(fleet="n/a")))


def test_compare_fleet_gate():
    base = _fleet_doc()
    # within tolerance: pass
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=110.0, worst_p99=90.0)), base) == []
    # aggregate requests/s floor (same 20% tolerance as throughput)
    probs = cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=60.0)), base)
    assert probs and "fleet-throughput regression" in probs[0]
    # worst-tenant p99 ceiling — no tenant's tail may quietly rot
    # behind a healthy aggregate
    probs = cbr.compare(_fleet_doc(fleet=_fleet_block(
        worst_p99=500.0)), base)
    assert probs and "fleet-latency regression" in probs[0] \
        and "worst-tenant p99" in probs[0]
    # tolerance knobs reach both gates
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=60.0)), base, throughput_tol=0.6) == []
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        worst_p99=500.0)), base, latency_tol=9.0) == []
    # old baselines without the section gate nothing
    no_fleet = dict(_fleet_doc())
    del no_fleet["fleet"]
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=1.0, worst_p99=9999.0)), no_fleet) == []
    # a fresh run that LOST the section cannot silently pass
    probs = cbr.compare(no_fleet, base)
    assert any("no fleet.requests_per_s" in p for p in probs)
    assert any("no fleet per-tenant p99_ms" in p for p in probs)
    # a baseline with a DIFFERENT fleet shape gates nothing: 8-tenant
    # requests/s is not a comparable floor for a 4-tenant run
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=1.0, tenants=8)), base) == []
    assert cbr.compare(_fleet_doc(fleet=_fleet_block(
        requests_per_s=1.0, streams_per_tenant=8)), base) == []
    # cross-workload refusal still wins: a fleet line never compares
    # against a HIGGS training baseline
    probs = cbr.compare(_fleet_doc(), _fresh())
    assert len(probs) == 1 and "not comparable" in probs[0]


def test_cli_fleet_walks_back_to_latest_carrier(tmp_path):
    """When the newest trajectory point predates the fleet bench, the
    fleet fields gate against the LATEST same-workload point carrying
    a comparable shape — old points gate nothing beyond that."""
    base_dir = tmp_path / "repo"
    base_dir.mkdir()
    (base_dir / "BENCH_r01.json").write_text(json.dumps(
        {"parsed": _fleet_doc()}))
    (base_dir / "BENCH_r02.json").write_text(json.dumps(
        {"parsed": _fleet_doc(fleet=None)}))  # newest: no fleet block
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_fleet_doc(fleet=_fleet_block(
        requests_per_s=40.0))))
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fleet_doc(fleet=_fleet_block(
        requests_per_s=125.0))))
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    # the tolerance flags reach the walked-back comparison
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir),
                     "--throughput-tol", "0.8"]) == 0
    tail = tmp_path / "tail.json"
    tail.write_text(json.dumps(_fleet_doc(fleet=_fleet_block(
        worst_p99=500.0))))
    assert cbr.main([str(tail), "--baseline-dir", str(base_dir)]) == 1
    assert cbr.main([str(tail), "--baseline-dir", str(base_dir),
                     "--latency-tol", "9.0"]) == 0
    # a newest point carrying a DIFFERENT fleet shape must not disable
    # the gate either: walk back to the same-shape carrier
    (base_dir / "BENCH_r03.json").write_text(json.dumps(
        {"parsed": _fleet_doc(fleet=_fleet_block(
            requests_per_s=5000.0, tenants=16))}))
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0


# -- the slo section (obs/slo.py budget report in bench JSON) ----------------

def _slo_block(**kw):
    d = {"spec": "predict_p99_ms<5000;degraded_window_rate<0.5",
         "ok": True, "violating": 0,
         "budget_remaining_min": 0.98, "burn_rate_max": 0.02,
         "predict_p999_ms": 41.5, "serve_p999_ms": None,
         "objectives": [
             {"name": "predict_p99_ms", "ok": True, "current": 12.0,
              "threshold": 5000.0, "budget_remaining": 0.98,
              "burn_rate": 0.02},
             {"name": "degraded_window_rate", "ok": True,
              "current": None, "threshold": 0.5,
              "budget_remaining": 1.0, "burn_rate": 0.0}]}
    d.update(kw)
    return d


# -- measured-parity section (bench.py --parity) -----------------------------

def _parity(ref_available=True, exact_rate=0.02, proxy_rate=0.034,
            auc_delta=1e-4, ok=True, **kw):
    tier = lambda rate: {  # noqa: E731
        "wall_s": 100.0, "row_iters_per_s": rate,
        "auc_tpu": 0.8626,
        "ref_wall_s": 120.0 if ref_available else None,
        "auc_ref": 0.8627 if ref_available else None,
        "auc_delta": auc_delta if ref_available else None,
    }
    d = {"rows": 65536, "iters": 20, "leaves": 63, "max_bin": 63,
         "device_kind": "cpu", "ref_available": ref_available,
         "skip_reason": None if ref_available else "no lightgbm here",
         "auc_tol": 4e-4, "ok": ok,
         "tiers": {"exact": tier(exact_rate), "proxy": tier(proxy_rate)}}
    d.update(kw)
    return d


def test_check_schema_parity_section():
    assert cbr.check_schema(_fresh(parity=_parity())) == []
    assert cbr.check_schema(
        _fresh(parity=_parity(ref_available=False))) == []
    # tier numbers missing
    bad = _parity()
    del bad["tiers"]["exact"]["row_iters_per_s"]
    assert any("exact.row_iters_per_s" in p
               for p in cbr.check_schema(_fresh(parity=bad)))
    # reference measured but its fields lost
    bad = _parity()
    bad["tiers"]["proxy"]["auc_ref"] = None
    assert any("proxy.auc_ref" in p
               for p in cbr.check_schema(_fresh(parity=bad)))
    # unavailable reference must record why
    bad = _parity(ref_available=False)
    bad["skip_reason"] = ""
    assert any("skip_reason" in p
               for p in cbr.check_schema(_fresh(parity=bad)))
    assert any("not a dict" in p
               for p in cbr.check_schema(_fresh(parity=[1])))


def test_parity_quality_problems_are_self_gates():
    """A measured AUC miss fails the fresh artifact with no baseline
    needed; skipped-reference runs assert nothing."""
    assert cbr.parity_quality_problems(_fresh(parity=_parity())) == []
    bad = cbr.parity_quality_problems(
        _fresh(parity=_parity(auc_delta=9e-4, ok=False)))
    assert any("AUC delta" in p for p in bad)
    assert any("parity.ok" in p for p in bad)
    assert cbr.parity_quality_problems(
        _fresh(parity=_parity(ref_available=False))) == []


def test_compare_parity_exact_tier_floor():
    base = _fresh(parity=_parity(exact_rate=0.02))
    # within tolerance: pass
    ok = _fresh(parity=_parity(exact_rate=0.0185))
    assert cbr._compare_parity(ok, base, 0.20) == []
    # exact tier regressed beyond the floor
    slow = _fresh(parity=_parity(exact_rate=0.01))
    got = cbr._compare_parity(slow, base, 0.20)
    assert any("exact-tier throughput regression" in p for p in got)
    # lost the section against a carrier
    got = cbr._compare_parity(_fresh(), base, 0.20)
    assert any("no parity section" in p for p in got)
    # different shape/device gates nothing
    other = _fresh(parity=_parity(exact_rate=0.001, rows=11_000_000))
    assert cbr._compare_parity(other, base, 0.20) == []
    # a baseline without the section gates nothing
    assert cbr._compare_parity(ok, _fresh(), 0.20) == []


def test_cli_parity_self_gate_and_floor(tmp_path):
    """End-to-end through main(): a failing measured-parity artifact
    exits 1 even against a trajectory that predates the section, and
    the exact-tier floor gates against a carrier point."""
    base_dir = tmp_path / "traj"
    base_dir.mkdir()
    (base_dir / "BENCH_r1.json").write_text(json.dumps(
        _fresh(parity=_parity(exact_rate=0.02))))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fresh(parity=_parity(exact_rate=0.019))))
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0
    bad_q = tmp_path / "bad_quality.json"
    bad_q.write_text(json.dumps(
        _fresh(parity=_parity(auc_delta=9e-4, ok=False))))
    assert cbr.main([str(bad_q), "--baseline-dir",
                     str(base_dir)]) == 1
    # --schema-only must ALSO refuse a recorded quality miss: quick
    # parity runs are metric-refused against the full trajectory, so
    # schema-only is the mode that validates them
    assert cbr.main([str(bad_q), "--schema-only"]) == 1
    assert cbr.main([str(ok), "--schema-only"]) == 0
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_fresh(parity=_parity(exact_rate=0.01))))
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1


def test_cli_parity_walks_back_to_latest_carrier(tmp_path):
    """A newer trajectory point that predates the parity section must
    not mask the exact-tier floor of an older carrier."""
    base_dir = tmp_path / "traj"
    base_dir.mkdir()
    (base_dir / "BENCH_r1.json").write_text(json.dumps(
        _fresh(parity=_parity(exact_rate=0.02))))
    (base_dir / "BENCH_r2.json").write_text(json.dumps(_fresh()))
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_fresh(parity=_parity(exact_rate=0.01))))
    assert cbr.main([str(slow), "--baseline-dir", str(base_dir)]) == 1
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_fresh(parity=_parity(exact_rate=0.02))))
    assert cbr.main([str(ok), "--baseline-dir", str(base_dir)]) == 0


def test_check_schema_slo_section():
    # a valid section passes; absence is fine too (old artifacts)
    assert cbr.check_schema(_fresh(slo=_slo_block())) == []
    assert cbr.check_schema(_fresh()) == []
    # budget fields must be numeric-or-null, never a string
    bad = cbr.check_schema(_fresh(
        slo=_slo_block(budget_remaining_min="lots")))
    assert any("budget_remaining_min" in p for p in bad)
    bad = cbr.check_schema(_fresh(slo=_slo_block(burn_rate_max=True)))
    assert any("burn_rate_max" in p for p in bad)
    bad = cbr.check_schema(_fresh(slo=_slo_block(predict_p999_ms="x")))
    assert any("predict_p999_ms" in p for p in bad)
    # per-objective budget state is REQUIRED, not optional
    objs = _slo_block()["objectives"]
    del objs[0]["budget_remaining"]
    bad = cbr.check_schema(_fresh(slo=_slo_block(objectives=objs)))
    assert any("objectives[0].budget_remaining" in p for p in bad)
    bad = cbr.check_schema(_fresh(slo=_slo_block(objectives="none")))
    assert any("objectives" in p for p in bad)
    bad = cbr.check_schema(_fresh(slo=_slo_block(ok="yes")))
    assert any("slo.ok" in p for p in bad)
    bad = cbr.check_schema(_fresh(slo=[1, 2]))
    assert any("slo is list" in p for p in bad)
    # a section that lost its spec string is a shape problem
    blk = _slo_block()
    del blk["spec"]
    assert any("slo.spec" in p for p in cbr.check_schema(
        _fresh(slo=blk)))


def test_slo_violations_are_notes_not_gates():
    """A violated SLO is an operator signal: field_notes reports it,
    compare() does not fail on it, and cross-workload refusal still
    wins over everything."""
    blk = _slo_block(ok=False, violating=1,
                     budget_remaining_min=-2.0)
    blk["objectives"][0]["ok"] = False
    fresh = _fresh(slo=blk)
    assert cbr.check_schema(fresh) == []       # shape is still valid
    notes = cbr.field_notes(fresh)
    assert any("SLO violations" in n and "predict_p99_ms" in n
               for n in notes)
    # same-workload compare ignores the slo values entirely
    assert cbr.compare(fresh, _fresh(value=50.0)) == []
    # cross-workload refusal unchanged
    got = cbr.compare(fresh, _fresh(metric="OTHER"))
    assert len(got) == 1 and got[0].startswith("not comparable")


# -- multichip elastic-drill artifacts (MULTICHIP_r06+) ----------------------

def _drill_doc(**kw):
    d = {
        "schema": cbr.MULTICHIP_DRILL_SCHEMA, "version": 1,
        "drill": "elastic_resume",
        "workload": {"n": 2048, "f": 8, "iterations": 8},
        "world_sizes": {"train": 2, "resume": 1},
        "kill": {"rank": 1, "iteration": 5, "survivor_exit_code": 17,
                 "survivor_error": "rank 1 of 2 unresponsive",
                 "survivor_named_ranks": [1]},
        "resume": {"from_iteration": 4, "total_iterations": 8},
        "per_host_ingest_rows": [1024, 1024],
        "model_parity": True, "parity_kind": "bit_identical",
        "train_auc": 0.98, "resumed_auc": 0.98,
        "wall_s": {"uninterrupted": 20.0},
    }
    d.update(kw)
    return d


def test_multichip_drill_pass_and_cli(tmp_path):
    schema, regressions, notes = cbr.check_multichip_drill(_drill_doc())
    assert schema == [] and regressions == []
    assert any("ingest" in n for n in notes)
    p = tmp_path / "drill.json"
    p.write_text(json.dumps(_drill_doc()))
    assert cbr.main([str(p)]) == 0


def test_multichip_drill_parity_false_fails():
    _, regressions, _ = cbr.check_multichip_drill(
        _drill_doc(model_parity=False))
    assert any("model_parity=false" in r for r in regressions)


def test_multichip_drill_schema_refusals(tmp_path):
    schema, _, _ = cbr.check_multichip_drill(_drill_doc(version=2))
    assert any("version" in s for s in schema)
    schema, _, _ = cbr.check_multichip_drill(
        _drill_doc(world_sizes={"train": 1, "resume": 1}))
    assert any("SHRINKING" in s for s in schema)
    d = _drill_doc()
    del d["model_parity"]
    schema, _, _ = cbr.check_multichip_drill(d)
    assert any("model_parity" in s for s in schema)
    d = _drill_doc(per_host_ingest_rows=[1024])
    schema, _, _ = cbr.check_multichip_drill(d)
    assert any("per_host_ingest_rows" in s for s in schema)
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(_drill_doc(version=2)))
    assert cbr.main([str(p)]) == 2


def test_multichip_drill_survivor_and_rows_gates():
    d = _drill_doc()
    d["kill"]["survivor_named_ranks"] = []
    _, regressions, _ = cbr.check_multichip_drill(d)
    assert any("named" in r for r in regressions)
    # a survivor that HUNG (killed by the launcher timeout, -9) or
    # crashed (1) is a no-hang regression, not a pass
    for bad in (-9, 1):
        d = _drill_doc()
        d["kill"]["survivor_exit_code"] = bad
        _, regressions, _ = cbr.check_multichip_drill(d)
        assert any("EXIT_PEER_LOST" in r for r in regressions), bad
    _, regressions, _ = cbr.check_multichip_drill(
        _drill_doc(per_host_ingest_rows=[2048, 0]))
    assert any("without its data shard" in r for r in regressions)
    _, regressions, _ = cbr.check_multichip_drill(
        _drill_doc(per_host_ingest_rows=[512, 512]))
    assert any("dropped" in r for r in regressions)


def _scaling_doc(**kw):
    d = {
        "schema": cbr.MULTICHIP_SCALING_SCHEMA, "version": 1,
        "workload": {"n": 2048, "f": 8, "iterations": 6},
        "points": [
            {"world": 1, "throughput_rows_per_s": 1700.0,
             "comm_bytes_per_iter": None, "psum_stall_s": None,
             "ckpt_hidden_s": 0.03, "wire": "", "psum_slots": 1,
             "model_sha": "aa"},
            {"world": 2, "throughput_rows_per_s": 900.0,
             "comm_bytes_per_iter": 172032, "psum_stall_s": 0.02,
             "ckpt_hidden_s": 0.04, "wire": "int32", "psum_slots": 2,
             "model_sha": "aa"},
        ],
        "model_parity": True, "parity_kind": "bit_identical",
        "checkpoint": {"hidden_s": 0.04},
        "autoscale": {"drill": "autoscale_grow_shrink",
                      "worlds": [2, 4, 2], "window": 3,
                      "iterations": 9, "reshard_total": 2,
                      "model_parity": True,
                      "parity_kind": "bit_identical"},
    }
    d.update(kw)
    return d


def test_multichip_scaling_pass_and_cli(tmp_path):
    schema, regressions, notes = cbr.check_multichip_scaling(
        _scaling_doc())
    assert schema == [] and regressions == []
    assert any("hidden" in n for n in notes)
    p = tmp_path / "scaling.json"
    p.write_text(json.dumps(_scaling_doc()))
    assert cbr.main([str(p)]) == 0


def test_multichip_scaling_parity_and_reshard_regressions():
    _, regressions, _ = cbr.check_multichip_scaling(
        _scaling_doc(model_parity=False))
    assert any("mesh-size invariance" in r for r in regressions)
    doc = _scaling_doc()
    doc["autoscale"]["model_parity"] = False
    _, regressions, _ = cbr.check_multichip_scaling(doc)
    assert any("elastic autoscale is broken" in r for r in regressions)
    doc = _scaling_doc()
    doc["autoscale"]["reshard_total"] = 0
    _, regressions, _ = cbr.check_multichip_scaling(doc)
    assert any("never" in r for r in regressions)


def test_multichip_scaling_schema_refusals(tmp_path):
    assert cbr.check_multichip_scaling(
        _scaling_doc(version=2))[0]
    assert cbr.check_multichip_scaling(
        _scaling_doc(points=[]))[0]
    doc = _scaling_doc()
    doc["points"] = list(reversed(doc["points"]))     # worlds 2, 1
    assert any("strictly increasing" in s for s in
               cbr.check_multichip_scaling(doc)[0])
    doc = _scaling_doc()
    doc["points"][1]["psum_stall_s"] = "fast"
    assert any("psum_stall_s" in s for s in
               cbr.check_multichip_scaling(doc)[0])
    doc = _scaling_doc()
    del doc["autoscale"]
    assert any("autoscale" in s for s in
               cbr.check_multichip_scaling(doc)[0])
    # the CLI maps a schema refusal to exit 2
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(_scaling_doc(points=[])))
    assert cbr.main([str(p)]) == 2


def test_multichip_r07_artifact_passes_gate():
    """The committed MULTICHIP_r07 artifact (the real measured scaling
    curve + autoscale drill) must stay green through its own gate."""
    path = os.path.join(REPO, "MULTICHIP_r07.json")
    assert cbr.main([path]) == 0
    doc = json.loads(open(path).read())
    assert doc["model_parity"] is True
    assert doc["autoscale"]["model_parity"] is True
    assert doc["autoscale"]["reshard_total"] >= 1
    assert [p["world"] for p in doc["points"]] == [1, 2, 4]
    shas = {p["model_sha"] for p in doc["points"]}
    assert len(shas) == 1, "scaling points trained different models"


def test_baseline_flag_and_shape_aware_selection(tmp_path):
    """(PR16) trajectory baseline selection: a point flagged
    ``"baseline": false`` (the quick-shape r06 ledger entry) never
    becomes the comparison floor, and among eligible points the gate
    prefers the newest one whose metric string MATCHES the fresh
    run's workload shape."""
    full = "11M rows x 28 feat"
    quick = "65536 rows x 28 feat"
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        _fresh(metric=full, value=50.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        _fresh(metric=quick, value=400.0, baseline=False)))
    fresh = tmp_path / "fresh.json"
    # full-size fresh: compares against r01 (r02 is flagged off), so a
    # value that would be a crash vs r02's 400 still passes vs 50
    fresh.write_text(json.dumps(_fresh(metric=full, value=49.0)))
    assert cbr.main([str(fresh), "--baseline-dir",
                     str(tmp_path)]) == 0
    # quick-shape fresh: no eligible matching-metric point -> the gate
    # refuses the cross-shape comparison instead of passing it
    fresh.write_text(json.dumps(_fresh(metric=quick, value=400.0)))
    assert cbr.main([str(fresh), "--baseline-dir",
                     str(tmp_path)]) == 2
    # un-flag r02: now the quick shape has a true baseline and passes
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        _fresh(metric=quick, value=400.0)))
    assert cbr.main([str(fresh), "--baseline-dir",
                     str(tmp_path)]) == 0
    # and the full shape still walks back to r01 over the newer r02
    fresh.write_text(json.dumps(_fresh(metric=full, value=49.0)))
    assert cbr.main([str(fresh), "--baseline-dir",
                     str(tmp_path)]) == 0


def test_all_baselines_flagged_off_is_a_refusal(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        _fresh(baseline=False)))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_fresh()))
    assert cbr.main([str(fresh), "--baseline-dir",
                     str(tmp_path)]) == 2


def test_multichip_r06_artifact_passes_gate():
    """The committed MULTICHIP_r06 artifact (the real drill run) must
    stay green through its own gate."""
    path = os.path.join(REPO, "MULTICHIP_r06.json")
    assert cbr.main([path]) == 0
    doc = json.loads(open(path).read())
    assert doc["model_parity"] is True
    assert doc["world_sizes"] == {"train": 2, "resume": 1}


# -- the device gate ---------------------------------------------------------

def test_bench_refuses_to_run_without_a_tpu():
    """bench.py prints device metrics: on a process whose jax backend
    is not a TPU it must exit non-zero BEFORE generating data or
    training anything, and print no JSON result line (a CPU container
    once wrote a whole trajectory point under device-metric names).
    The --sparse/--rank parents stay off jax; their route children hit
    the same gate."""
    for extra in ([], ["--sparse-route", "csr"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"), "--quick",
             *extra],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert "refusing to run" in proc.stderr
        assert "not a TPU" in proc.stderr
        assert proc.stdout.strip() == ""
