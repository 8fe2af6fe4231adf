"""Fault-tolerance suite (pytest -m faults).

Every recovery path is PROVEN with deterministic fault injection
(lightgbm_tpu/utils/faults.py), not hoped for:

- kill-and-resume bit-parity: a subprocess is SIGKILLed mid-train and
  resumed from its checkpoint bundle; the final model is byte-identical
  to the uninterrupted run's (serial here; the sharded-state path is
  the slow-marked twin);
- the degrade-don't-die lrb loop: an injected window-train failure
  leaves the loop serving the stale model with correct counters and a
  staleness gauge in the Prometheus export;
- injected transient ingest/transfer failures recover via the bounded
  backoff retry (utils/retry.py), bit-exact;
- a checkpoint-write failure warns and never corrupts training or the
  previous checkpoint;
- snapshots are atomic and pruned; truncated/corrupt model text and
  checkpoint bundles are refused with one-line errors.
"""
import glob
import io
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata, TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.utils import checkpoint as ckpt
from lightgbm_tpu.utils import faults, retry
from lightgbm_tpu.utils.log import LightGBMError

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Fault plans are process-global: never leak one into the next
    test (or the rest of the suite)."""
    yield
    faults.clear()


def make_binary(seed=0, n=400, f=6):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
          "min_data_in_leaf": 5, "num_iterations": 12,
          "bagging_freq": 3, "bagging_fraction": 0.7,
          "feature_fraction": 0.8}


def build_booster(params):
    cfg = Config().set(dict(params))
    X, y = make_binary()
    ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
    obj = create_objective(cfg.objective, cfg)
    obj.init(ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, [])
    return g


def trees_only(model_str):
    """The model text minus the parameters block (the checkpoint knobs
    themselves land there and must not fail the comparison)."""
    return model_str.split("\nparameters:\n")[0]


def counter(name):
    return obs.default_registry().snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# faults.py / retry.py units
# ---------------------------------------------------------------------------

def test_fault_spec_occurrences_and_actions():
    faults.configure("p.a@2;p.b@1,3:transient;p.c@2+")
    faults.check("p.a")                      # occurrence 1: clean
    with pytest.raises(faults.InjectedFault) as ei:
        faults.check("p.a")                  # occurrence 2: fires
    assert not ei.value.transient
    faults.check("p.a")                      # 3: clean again
    with pytest.raises(faults.InjectedFault) as ei:
        faults.check("p.b")
    assert ei.value.transient
    faults.check("p.b")                      # 2: clean
    with pytest.raises(faults.InjectedFault):
        faults.check("p.b")                  # 3: fires
    faults.check("p.c")                      # 1: clean
    for _ in range(3):                       # 2+: every call fires
        with pytest.raises(faults.InjectedFault):
            faults.check("p.c")
    assert faults.counts()["p.a"] == 3


def test_fault_spec_probability_is_seeded():
    def fire_pattern(seed):
        faults.clear()      # same-spec re-arming is a no-op by design
        faults.configure("p.x@p0.5", seed=seed)
        out = []
        for _ in range(20):
            try:
                faults.check("p.x")
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    a, b = fire_pattern(7), fire_pattern(7)
    assert a == b and 0 < sum(a) < 20
    assert fire_pattern(8) != a


def test_fault_spec_rejects_garbage():
    with pytest.raises(ValueError, match="unknown action"):
        faults.configure("p.a@1:explode")
    with pytest.raises(ValueError, match="point@N"):
        faults.configure("no-at-sign")
    faults.configure("")                     # empty disarms
    assert not faults.active()


def test_fault_sleep_action_stalls_without_raising():
    """The latency action (shed drills): the call stalls for the
    configured milliseconds and then proceeds normally — no exception,
    no flight dump, only the wall-clock damage."""
    import time as _t
    faults.configure("p.s@2+:sleep40")
    t0 = _t.perf_counter()
    faults.check("p.s")                      # occurrence 1: clean
    assert _t.perf_counter() - t0 < 0.030
    t0 = _t.perf_counter()
    faults.check("p.s")                      # 2+: stalls, returns
    assert _t.perf_counter() - t0 >= 0.030
    assert faults.counts()["p.s"] == 2
    for bad in ("p.s@1:sleepX", "p.s@1:sleep-5", "p.s@1:sleep"):
        with pytest.raises(ValueError, match="sleep<ms>"):
            faults.configure(bad)


def test_retry_recovers_transient_and_fails_fast():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise faults.InjectedFault("flaky", transient=True)
        return "ok"

    pol = retry.RetryPolicy(attempts=4, base_s=0.0, seed=1)
    r0 = counter("retry/retries")
    assert retry.call(flaky, what="unit", policy=pol) == "ok"
    assert calls["n"] == 3
    assert counter("retry/retries") - r0 == 2

    calls2 = {"n": 0}

    def count_broken():
        calls2["n"] += 1
        raise ValueError("a real bug")

    with pytest.raises(ValueError):
        retry.call(count_broken, what="unit", policy=pol)
    assert calls2["n"] == 1                  # non-transient: no retry

    g0 = counter("retry/giveups")
    with pytest.raises(faults.InjectedFault):
        retry.call(lambda: (_ for _ in ()).throw(
            faults.InjectedFault("always", transient=True)),
            what="unit", policy=retry.RetryPolicy(attempts=2, base_s=0.0))
    assert counter("retry/giveups") - g0 == 1


def test_retry_classifies_runtime_strings():
    assert retry.is_transient(RuntimeError(
        "UNAVAILABLE: failed to connect to all addresses"))
    assert retry.is_transient(TimeoutError())
    assert not retry.is_transient(ValueError("shape mismatch"))
    # out of HBM on a chip attached to this process is as true on the
    # fourth attempt as on the first — never retried
    assert not retry.is_transient(RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"))


# ---------------------------------------------------------------------------
# checkpoint bundle IO + refusals
# ---------------------------------------------------------------------------

def test_checkpoint_loader_one_line_refusals(tmp_path):
    p = tmp_path / "ckpt_iter_3.json"
    p.write_text('{"schema": "lightgbm-tpu/checkpoint", "version')
    with pytest.raises(ValueError, match="corrupt checkpoint"):
        ckpt.load_checkpoint(str(p))
    p.write_text('{"schema": "something-else"}')
    with pytest.raises(ValueError, match="not a checkpoint bundle"):
        ckpt.load_checkpoint(str(p))
    p.write_text(json.dumps({"schema": ckpt.CHECKPOINT_SCHEMA,
                             "version": 999}))
    with pytest.raises(ValueError, match="version 999"):
        ckpt.load_checkpoint(str(p))
    p.write_text(json.dumps({"schema": ckpt.CHECKPOINT_SCHEMA,
                             "version": ckpt.CHECKPOINT_VERSION}))
    with pytest.raises(ValueError, match="missing 'iteration'"):
        ckpt.load_checkpoint(str(p))
    d = tmp_path / "empty"
    d.mkdir()
    with pytest.raises(ValueError, match="no ckpt_iter_"):
        ckpt.resolve_resume(str(d))


def test_checkpoint_config_mismatch_is_actionable(tmp_path):
    g = build_booster(PARAMS)
    for _ in range(4):
        g.train_one_iter()
    ckpt.save_checkpoint(g, str(tmp_path))
    other = build_booster(dict(PARAMS, learning_rate=0.3))
    bundle = ckpt.resolve_resume(str(tmp_path))
    with pytest.raises(ValueError, match="different training config"):
        ckpt.restore(other, bundle)


def test_checkpoint_missing_sidecar_refused_and_dir_skips(tmp_path):
    g = build_booster(PARAMS)
    for _ in range(6):
        g.train_one_iter()
    ckpt.save_checkpoint(g, str(tmp_path))          # iter 6 (valid)
    # a newer bundle whose sidecar is gone (crash between writes /
    # partial copy): direct load refuses, dir resolve SKIPS to 6
    newer = tmp_path / "ckpt_iter_9.json"
    bundle = json.loads(
        (tmp_path / "ckpt_iter_6.json").read_text())
    bundle["iteration"] = 9
    bundle["scores_file"] = "ckpt_iter_9.scores.npz"
    newer.write_text(json.dumps(bundle))
    with pytest.raises(ValueError, match="sidecar"):
        ckpt.load_checkpoint(str(newer))
    resolved = ckpt.resolve_resume(str(tmp_path))
    assert resolved["iteration"] == 6


def test_checkpoint_volatile_knobs_do_not_change_fingerprint():
    a = Config().set(dict(PARAMS))
    b = Config().set(dict(PARAMS, tpu_checkpoint_dir="/tmp/x",
                          tpu_run_report="/tmp/r.json",
                          num_iterations=500))
    c = Config().set(dict(PARAMS, learning_rate=0.31))
    assert ckpt.config_fingerprint(a) == ckpt.config_fingerprint(b)
    assert ckpt.config_fingerprint(a) != ckpt.config_fingerprint(c)
    # cluster topology is volatile BY DESIGN: elastic resume means a
    # 2-process checkpoint restores under 1 process (different rank /
    # coordinator / world) without a fingerprint refusal
    d = Config().set(dict(PARAMS, tpu_num_machines=2,
                          tpu_machine_rank=1,
                          tpu_coordinator="host:123",
                          tpu_collective_timeout_s=7.5))
    assert ckpt.config_fingerprint(a) == ckpt.config_fingerprint(d)


def test_checkpoint_world_mismatch_named_in_refusal(tmp_path):
    """Resuming a sharded checkpoint under a mismatched world size
    over DIFFERENT data gets a dedicated one-line error naming both
    world sizes and pointing at the elastic re-shard path's
    requirement (same data) — not the generic shape message."""
    g = build_booster(PARAMS)
    for _ in range(4):
        g.train_one_iter()
    ckpt.save_checkpoint(g, str(tmp_path))
    bundle_path = ckpt.list_checkpoints(str(tmp_path))[0][1]
    bundle = json.loads(open(bundle_path).read())
    assert bundle["world"]["processes"] == 1          # written 1-proc
    # doctor the bundle into "written by a 2-process run over other
    # data": different world, different row count, wider score buffer
    bundle["world"].update(processes=2, devices=2, n_real=640,
                           n_score=768)
    open(bundle_path, "w").write(json.dumps(bundle))
    with np.load(ckpt.scores_path(bundle_path)) as z:
        k = z["scores"].shape[0]
    with open(ckpt.scores_path(bundle_path), "wb") as fh:
        np.savez_compressed(fh, scores=np.zeros((k, 768), np.float32))
    # drop the mapper record: this refusal matrix entry targets the
    # WORLD mismatch, not the (also different) binning
    bundle.pop("mappers")
    open(bundle_path, "w").write(json.dumps(bundle))
    fresh = build_booster(PARAMS)
    with pytest.raises(ValueError, match=r"2-process run.*1 process"):
        ckpt.restore(fresh, ckpt.resolve_resume(str(tmp_path)))


def test_checkpoint_elastic_reshard_same_data(tmp_path):
    """A world-size change over the SAME data re-shards instead of
    refusing: real rows carry verbatim into this run's (different-
    width) score buffer; the pad region keeps fresh-init values."""
    g = build_booster(PARAMS)
    for _ in range(4):
        g.train_one_iter()
    ckpt.save_checkpoint(g, str(tmp_path))
    bundle_path = ckpt.list_checkpoints(str(tmp_path))[0][1]
    bundle = json.loads(open(bundle_path).read())
    # provenance: every bundle stamps the writer's telemetry identity
    # (never part of the resume fingerprint)
    assert bundle["identity"]["machine_rank"] == 0
    n_real = bundle["world"]["n_real"]
    with np.load(ckpt.scores_path(bundle_path)) as z:
        saved = z["scores"]
    # pretend a 2-process run wrote it at a different aligned width;
    # the pad carries garbage the re-shard must ignore
    wider = np.pad(saved, ((0, 0), (0, 64)), constant_values=7.0)
    bundle["world"].update(processes=2, devices=2,
                           n_score=wider.shape[1])
    open(bundle_path, "w").write(json.dumps(bundle))
    with open(ckpt.scores_path(bundle_path), "wb") as fh:
        np.savez_compressed(fh, scores=wider)
    fresh = build_booster(PARAMS)
    from lightgbm_tpu.obs import identity
    inc0 = identity.incarnation()
    it = ckpt.restore(fresh, ckpt.resolve_resume(str(tmp_path)))
    assert it == 4
    # the re-shard starts a new incarnation of this process's
    # telemetry identity (obs/identity.py — Design.md §6e)
    assert identity.incarnation() == inc0 + 1
    got = np.asarray(fresh.train_scores())
    np.testing.assert_array_equal(got, saved[:, :n_real])
    # and the resumed booster keeps training
    fresh.train_one_iter()


def test_checkpoint_mapper_mismatch_refused(tmp_path):
    """A dataset binned differently from the checkpointed run is
    refused by fingerprint — restored thresholds would silently
    shift — and mappers_from_bundle reconstructs the original binning
    so an elastic resume can inject it."""
    g = build_booster(PARAMS)
    for _ in range(3):
        g.train_one_iter()
    ckpt.save_checkpoint(g, str(tmp_path))
    bundle = ckpt.resolve_resume(str(tmp_path))

    # same config, different data -> different mappers
    cfg = Config().set(dict(PARAMS))
    X2, y2 = make_binary(seed=99)
    ds2 = TpuDataset(cfg).construct_from_matrix(X2, Metadata(label=y2))
    obj = create_objective(cfg.objective, cfg)
    obj.init(ds2.metadata, ds2.num_data)
    other = GBDT()
    other.init(cfg, ds2, obj, [])
    with pytest.raises(ValueError, match="different bin mappers"):
        ckpt.restore(other, bundle)

    # the bundle's mappers reconstruct the ORIGINAL binning exactly
    full = ckpt.mappers_from_bundle(bundle)
    assert len(full) == g.train_data.num_total_features
    ds3 = TpuDataset(cfg).construct_from_matrix(
        *(lambda X, y: (X, Metadata(label=y)))(*make_binary()),
        mappers=full)
    assert [m.feature_info() for m in ds3.mappers] == \
        [m.feature_info() for m in g.train_data.mappers]
    assert ckpt.mapper_fingerprint(ds3.mappers) == \
        bundle["mappers"]["hash"]


# ---------------------------------------------------------------------------
# resume bit-parity (in-process; the subprocess kill drill is below)
# ---------------------------------------------------------------------------

def test_resume_bit_parity_in_process(tmp_path):
    g1 = build_booster(PARAMS)
    g1.train(-1, "")
    m1 = trees_only(g1.model_to_string())

    g2 = build_booster(dict(PARAMS, tpu_checkpoint_dir=str(tmp_path),
                            tpu_checkpoint_freq=4))
    g2.train(-1, "")
    assert trees_only(g2.model_to_string()) == m1, \
        "writing checkpoints perturbed training"

    g3 = build_booster(PARAMS)
    g3.train(-1, "", resume_from=str(tmp_path / "ckpt_iter_8.json"))
    assert trees_only(g3.model_to_string()) == m1, \
        "resumed run diverged from the uninterrupted one"


def test_resume_continued_training_counts_additional_rounds(tmp_path):
    """Resume of a CONTINUED-training run (input_model): the
    checkpoint stores TOTAL tree groups while the loop counts
    additional rounds — the resumed run must train exactly the
    remaining additional rounds, matching the unkilled continued run."""
    from lightgbm_tpu.metrics import create_metrics  # noqa: F401

    g0 = build_booster(dict(PARAMS, num_iterations=4))
    g0.train(-1, "")
    base_model = g0.model_to_string()

    def continued(extra):
        cfg = Config().set(dict(PARAMS, num_iterations=8, **extra))
        X, y = make_binary()
        ds = TpuDataset(cfg).construct_from_matrix(
            X, Metadata(label=y))
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        g = GBDT()
        g.load_model_from_string(base_model)
        g.init_from_loaded(cfg, ds, obj, [])
        return g

    g1 = continued({})
    g1.train(-1, "")
    assert g1.current_iteration == 12            # 4 base + 8 additional
    m1 = trees_only(g1.model_to_string())
    g2 = continued({"tpu_checkpoint_dir": str(tmp_path),
                    "tpu_checkpoint_freq": 3})
    g2.train(-1, "")
    assert trees_only(g2.model_to_string()) == m1
    # bundle at TOTAL iteration 7 == additional round 3
    g3 = continued({})
    g3.train(-1, "", resume_from=str(tmp_path / "ckpt_iter_7.json"))
    assert g3.current_iteration == 12, \
        "resume retrained the wrong number of additional rounds"
    assert trees_only(g3.model_to_string()) == m1


def test_resume_bit_parity_sharded_state(tmp_path):
    """Sharded-state path (tree_learner=data over the 8-device virtual
    CPU mesh): checkpoint at 6, resume, byte-identical final model."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device test platform")
    P = dict(PARAMS, tree_learner="data")
    P.pop("bagging_freq"), P.pop("bagging_fraction")
    P["num_iterations"] = 8
    g1 = build_booster(P)
    assert g1.learner_mode == "data" and g1.num_devices > 1
    g1.train(-1, "")
    m1 = trees_only(g1.model_to_string())
    g2 = build_booster(dict(P, tpu_checkpoint_dir=str(tmp_path),
                            tpu_checkpoint_freq=3))
    g2.train(-1, "")
    assert trees_only(g2.model_to_string()) == m1
    g3 = build_booster(P)
    g3.train(-1, "", resume_from=str(tmp_path / "ckpt_iter_6.json"))
    assert trees_only(g3.model_to_string()) == m1


# ---------------------------------------------------------------------------
# background checkpoint writer (tpu_ckpt_async)
# ---------------------------------------------------------------------------

def _writer_job(directory, it):
    """A minimal but schema-valid (bundle, sidecar) write job — enough
    for load_checkpoint to accept the result."""
    path = os.path.join(str(directory), f"ckpt_iter_{it}.json")
    arrays = {"train": np.zeros(3, np.float32)}
    bundle = {"schema": ckpt.CHECKPOINT_SCHEMA,
              "version": ckpt.CHECKPOINT_VERSION,
              "iteration": it, "model": "", "state": {},
              "config_hash": "x",
              "scores_file": os.path.basename(ckpt.scores_path(path))}
    return (str(directory), path, arrays, bundle, 10)


def test_async_writer_commits_in_order_and_drains(tmp_path):
    w = ckpt.AsyncCheckpointWriter()
    try:
        assert w.submit(*_writer_job(tmp_path, 3))
        assert w.submit(*_writer_job(tmp_path, 6))
        assert w.drain(timeout=30)
        for it in (3, 6):
            b = ckpt.load_checkpoint(
                str(tmp_path / f"ckpt_iter_{it}.json"))
            assert int(b["iteration"]) == it
        assert w.failures == 0
        assert w.write_seconds > 0
        assert obs.gauge("ckpt/queue_depth").value == 0
    finally:
        assert w.close(timeout=10)
    assert not w.submit(*_writer_job(tmp_path, 9))    # closed refuses


def test_async_writer_full_queue_drops_oldest(tmp_path, monkeypatch):
    import threading
    started, release, wrote = (threading.Event(), threading.Event(),
                               [])

    def stalling(directory, path, arrays, bundle, keep):
        wrote.append(int(bundle["iteration"]))
        started.set()
        release.wait(10)
        return path

    monkeypatch.setattr(ckpt, "_commit_bundle", stalling)
    w = ckpt.AsyncCheckpointWriter(maxsize=1)
    try:
        w.submit(*_writer_job(tmp_path, 1))       # in flight
        assert started.wait(10)
        w.submit(*_writer_job(tmp_path, 2))       # queued
        w.submit(*_writer_job(tmp_path, 3))       # full: 2 dropped
        release.set()
        assert w.drain(timeout=30)
        assert wrote == [1, 3]                    # superseded job gone
    finally:
        w.close(timeout=10)


def test_async_writer_failure_warns_and_training_continues(
        tmp_path, monkeypatch):
    real = ckpt._commit_bundle

    def broken(*a):
        raise RuntimeError("disk full")

    f0 = counter("checkpoint/write_failures")
    monkeypatch.setattr(ckpt, "_commit_bundle", broken)
    w = ckpt.AsyncCheckpointWriter()
    try:
        w.submit(*_writer_job(tmp_path, 3))
        assert w.drain(timeout=30)
        assert w.failures == 1
        assert counter("checkpoint/write_failures") - f0 == 1
        monkeypatch.setattr(ckpt, "_commit_bundle", real)
        w.submit(*_writer_job(tmp_path, 6))       # writer survives
        assert w.drain(timeout=30)
        assert ckpt.load_checkpoint(
            str(tmp_path / "ckpt_iter_6.json"))["iteration"] == 6
    finally:
        w.close(timeout=10)


def test_resolve_resume_drains_pending_background_writes(
        tmp_path, monkeypatch):
    import time as _time
    real = ckpt._commit_bundle

    def delayed(*a):
        _time.sleep(0.3)
        return real(*a)

    monkeypatch.setattr(ckpt, "_commit_bundle", delayed)
    w = ckpt.new_writer()                 # registered: resolve_resume
    try:                                  # must drain it itself
        w.submit(*_writer_job(tmp_path, 9))
        b = ckpt.resolve_resume(str(tmp_path))
        assert int(b["iteration"]) == 9
    finally:
        w.close(timeout=10)


# ---------------------------------------------------------------------------
# kill-and-resume subprocess drill
# ---------------------------------------------------------------------------

_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
mode, outdir, learner = sys.argv[1], sys.argv[2], sys.argv[3]
if learner == "data":
    # mirror tests/conftest.py's 8-device virtual CPU platform
    import jax
    jax.config.update("jax_num_cpu_devices", 8)
import numpy as np
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata, TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.utils import log
log.set_level(0)

r = np.random.default_rng(0)
X = r.normal(size=(400, 6))
y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
          "min_data_in_leaf": 5, "num_iterations": 12,
          "bagging_freq": 3, "bagging_fraction": 0.7,
          "tree_learner": learner,
          "tpu_checkpoint_dir": outdir, "tpu_checkpoint_freq": 3}
import json
params.update(json.loads(os.environ.get("LGBM_TPU_TEST_EXTRA_PARAMS",
                                        "{}")))
cfg = Config().set(params)
ds = TpuDataset(cfg).construct_from_matrix(X, Metadata(label=y))
obj = create_objective(cfg.objective, cfg)
obj.init(ds.metadata, ds.num_data)
g = GBDT(); g.init(cfg, ds, obj, [])
g.train(-1, "", resume_from=outdir if mode == "resume" else "")
with open(os.path.join(outdir, f"model_{mode}.txt"), "w") as fh:
    fh.write(g.model_to_string().split("\nparameters:\n")[0])
"""


def _run_child(script, mode, outdir, learner="serial", extra_env=None):
    env = dict(os.environ)
    env.pop("LGBM_TPU_FAULTS", None)
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, script, mode, outdir, learner],
        capture_output=True, text=True, timeout=420, env=env)


@pytest.fixture(scope="module")
def child_script(tmp_path_factory):
    p = tmp_path_factory.mktemp("drill") / "child.py"
    p.write_text(_CHILD)
    return str(p)


def _kill_resume_drill(child_script, outdir, learner):
    os.makedirs(outdir, exist_ok=True)
    # 1) uninterrupted baseline
    r = _run_child(child_script, "plain", outdir, learner)
    assert r.returncode == 0, r.stderr[-2000:]
    # 2) killed mid-train: SIGKILL at the start of iteration 9 — the
    #    checkpoints at 3 and 6 are on disk, 9's never happens
    r = _run_child(child_script, "kill", outdir, learner,
                   extra_env={"LGBM_TPU_FAULTS": "train.iter@9:kill"})
    assert r.returncode == -signal.SIGKILL, \
        f"child was not SIGKILLed (rc={r.returncode}): {r.stderr[-500:]}"
    assert os.path.exists(os.path.join(outdir, "ckpt_iter_6.json"))
    # 3) resumed from the checkpoint dir (newest valid bundle)
    r = _run_child(child_script, "resume", outdir, learner)
    assert r.returncode == 0, r.stderr[-2000:]
    plain = open(os.path.join(outdir, "model_plain.txt")).read()
    resumed = open(os.path.join(outdir, "model_resume.txt")).read()
    assert resumed == plain, \
        "kill->resume did not reproduce the uninterrupted model"


def test_kill_and_resume_bit_parity_subprocess(child_script, tmp_path):
    _kill_resume_drill(child_script, str(tmp_path), "serial")


@pytest.mark.slow
def test_kill_and_resume_bit_parity_sharded(child_script, tmp_path):
    _kill_resume_drill(child_script, str(tmp_path), "data")


def test_kill_and_resume_async_writer_no_torn_bundle(child_script,
                                                     tmp_path):
    """(PR16) the kill drill with the BACKGROUND writer on: SIGKILL can
    land with a write still in the writer queue or mid-flight, but
    atomic_write + sidecar-then-bundle ordering hold on the writer
    thread too — every bundle on disk must load cleanly (no torn
    bundle) and the newest one must resume bit-identically. Separate
    dirs keep the killed run's checkpoints unpolluted by the
    baseline's."""
    plain_dir = str(tmp_path / "plain")
    kill_dir = str(tmp_path / "kill")
    os.makedirs(plain_dir)
    os.makedirs(kill_dir)
    async_env = {"LGBM_TPU_TEST_EXTRA_PARAMS": '{"tpu_ckpt_async": 1}'}
    r = _run_child(child_script, "plain", plain_dir,
                   extra_env=async_env)
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run_child(child_script, "kill", kill_dir,
                   extra_env=dict(async_env,
                                  LGBM_TPU_FAULTS="train.iter@9:kill"))
    assert r.returncode == -signal.SIGKILL, \
        f"child was not SIGKILLed (rc={r.returncode}): {r.stderr[-500:]}"
    entries = ckpt.list_checkpoints(kill_dir)
    assert entries, "killed run left no checkpoints"
    for _, p in entries:
        ckpt.load_checkpoint(p)          # schema + sidecar intact
    r = _run_child(child_script, "resume", kill_dir,
                   extra_env=async_env)
    assert r.returncode == 0, r.stderr[-2000:]
    plain = open(os.path.join(plain_dir, "model_plain.txt")).read()
    resumed = open(os.path.join(kill_dir, "model_resume.txt")).read()
    assert resumed == plain, \
        "kill->resume with the async writer did not reproduce the " \
        "uninterrupted model"


# ---------------------------------------------------------------------------
# degrade-don't-die lrb loop
# ---------------------------------------------------------------------------

def _drive_lrb(n_requests=1200, window=300, faults_spec=None,
               budget=None):
    from lightgbm_tpu import lrb
    if faults_spec:
        faults.configure(faults_spec)
    out = io.StringIO()
    drv = lrb.LrbDriver(1 << 16, window, 120, 0.5, 1, result_file=out,
                        extra_params={"num_iterations": 4,
                                      "verbose": -1},
                        window_budget_s=budget)
    for seq, oid, size, cost in lrb.synthetic_trace(n_requests, 60):
        drv.process_request(seq, oid, size, cost)
    faults.clear()
    return drv


def test_lrb_injected_window_failure_serves_stale_model():
    f0 = counter("lrb/windows_failed")
    drv = _drive_lrb(faults_spec="lrb.window_train@2")
    res = drv.results
    assert len(res) == 4
    # window 2's training failed; it is marked degraded with the reason
    assert res[1]["degraded"] is True
    assert "InjectedFault" in res[1]["degrade_reason"]
    assert res[1]["staleness_windows"] == 1
    # window 3 retrained: staleness resets
    assert "degraded" not in res[2]
    assert res[2]["staleness_windows"] == 0
    # EVERY window after the first was evaluated — the loop kept
    # serving (window 3's eval ran against window 1's stale model)
    assert all(r.get("eval_rows", 0) > 0 for r in res[1:])
    assert drv.degraded_windows() == 1
    assert counter("lrb/windows_failed") - f0 == 1
    # ... and the whole story is visible in the Prometheus export
    from lightgbm_tpu.obs.export import prometheus_text
    txt = prometheus_text(obs.default_registry().snapshot())
    assert "lgbm_tpu_lrb_windows_failed_total" in txt
    assert "lgbm_tpu_lrb_windows_degraded_total" in txt
    assert "lgbm_tpu_lrb_model_staleness_windows" in txt


def test_lrb_transient_window_failure_retries_in_place():
    r0 = counter("retry/retries")
    drv = _drive_lrb(faults_spec="lrb.window_train@2:transient")
    assert drv.degraded_windows() == 0       # retry absorbed the fault
    assert counter("retry/retries") - r0 >= 1
    assert all(r["staleness_windows"] == 0 for r in drv.results)


def test_lrb_window_budget_degrades_not_dies():
    drv = _drive_lrb(budget=0.0)             # every window blows it
    assert len(drv.results) == 4
    assert drv.degraded_windows() == 4
    assert all("WindowBudgetExceeded" in r["degrade_reason"]
               for r in drv.results)
    # no model ever trained; the loop still completed the whole trace
    assert drv.booster is None


def test_lrb_malformed_trace_lines_skipped(tmp_path):
    from lightgbm_tpu import lrb
    trace_path = tmp_path / "trace.txt"
    lines = []
    for i, (seq, oid, size, cost) in enumerate(
            lrb.synthetic_trace(900, 60)):
        lines.append(f"{seq} {oid} {size} {cost}")
        if i == 100:
            lines.append("1 2 not-a-size 1.0")
        if i == 200:
            lines.append("only two")
    trace_path.write_text("\n".join(lines) + "\n")
    out = io.StringIO()
    drv = lrb.run_trace_file(str(trace_path), 1 << 16, 300, 120, 0.5, 1,
                             result_file=out,
                             extra_params={"num_iterations": 4,
                                           "verbose": -1})
    assert drv.trace_lines_skipped == 2
    assert len(drv.results) == 3             # 900 good lines / 300


# ---------------------------------------------------------------------------
# checkpoint/snapshot robustness in the training loop
# ---------------------------------------------------------------------------

def test_checkpoint_write_failure_warns_and_never_corrupts(tmp_path):
    g1 = build_booster(dict(PARAMS, num_iterations=8))
    g1.train(-1, "")
    m1 = trees_only(g1.model_to_string())
    w0 = counter("checkpoint/write_failures")
    faults.configure("checkpoint.write@1")
    g2 = build_booster(dict(PARAMS, num_iterations=8,
                            tpu_checkpoint_dir=str(tmp_path),
                            tpu_checkpoint_freq=4))
    g2.train(-1, "")
    faults.clear()
    assert trees_only(g2.model_to_string()) == m1
    assert counter("checkpoint/write_failures") - w0 == 1
    # iteration 4's write failed cleanly; iteration 8's succeeded and
    # resolves as a usable bundle
    assert ckpt.resolve_resume(str(tmp_path))["iteration"] == 8


def test_snapshots_atomic_and_pruned(tmp_path):
    base = str(tmp_path / "model.txt")
    g = build_booster(dict(PARAMS, num_iterations=10,
                           tpu_snapshot_keep=2))
    g.train(snapshot_freq=2, output_model=base)
    snaps = sorted(glob.glob(base + ".snapshot_iter_*"))
    assert [os.path.basename(p) for p in snaps] == [
        "model.txt.snapshot_iter_10", "model.txt.snapshot_iter_8"]
    # each surviving snapshot is complete, parseable model text
    for p in snaps:
        GBDT().load_model_from_string(open(p).read(), source=p)
    assert not glob.glob(base + "*.tmp*"), "torn tmp files left behind"


def test_load_model_one_line_errors():
    g = build_booster(dict(PARAMS, num_iterations=3))
    g.train(-1, "")
    good = g.model_to_string()
    with pytest.raises(LightGBMError, match="not a LightGBM model"):
        GBDT().load_model_from_string("garbage\nstuff\n", source="x.txt")
    truncated = good[: good.index("end of trees") - 40]
    with pytest.raises(LightGBMError, match="truncated model text"):
        GBDT().load_model_from_string(truncated, source="x.txt")
    broken = good.replace("left_child=", "left_child=zap ", 1)
    with pytest.raises(LightGBMError, match="malformed Tree="):
        GBDT().load_model_from_string(broken, source="x.txt")


def test_export_write_fault_does_not_crash(tmp_path):
    from lightgbm_tpu.obs.export import MetricsExporter
    faults.configure("export.write@1+")
    ex = MetricsExporter(base_path=str(tmp_path / "m"),
                         interval_s=60.0, port=-1)
    ex.start()
    ex.stop()
    faults.clear()
    assert not os.path.exists(str(tmp_path / "m.prom"))


def test_bench_regression_tolerates_new_fields():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "tools"))
    import check_bench_regression as cbr
    doc = {"metric": "m", "value": 1.0, "unit": "M row-iters/s",
           "degraded_windows": 2,
           "checkpoint": {"iteration": 40, "writes": 3}}
    notes = cbr.field_notes(doc)
    assert any("2 degraded window" in n for n in notes)
    assert any("checkpoint meta" in n for n in notes)
    # wrong-typed fields are reported, never a crash
    weird = dict(doc, degraded_windows="many", checkpoint=[1, 2])
    notes = cbr.field_notes(weird)
    assert any("not numeric" in n for n in notes)
    assert any("not an object" in n for n in notes)
    # and compare() ignores them entirely
    assert cbr.compare(doc, dict(doc)) == []
