"""Elastic multi-host training: the worker entry, the localhost
launcher, and the preemption drill.

Three layers, bottom-up:

**Worker** (``python -m lightgbm_tpu.parallel.elastic --spec s.json``):
one rank of a real ``jax.distributed`` cluster. Reads a drill spec
(synthetic workload + training params), bootstraps the cluster
(parallel/cluster.py — topology from the ``LGBM_TPU_NUM_MACHINES`` /
``LGBM_TPU_MACHINE_RANK`` / ``LGBM_TPU_COORDINATOR`` env the launcher
exports), builds its per-host shard of the dataset through the
multihost ingest (io/distributed.py construct_multihost), trains the
full GBDT engine under the no-hang DeadlineGuard, and writes a
per-rank result JSON (+ rank 0: the final model text). A peer death —
mid-collective failure or silent stall — exits with
``EXIT_PEER_LOST`` after ONE actionable line naming the dead rank;
the orchestrator (here: the drill) restarts survivors on a smaller
mesh with ``resume_from`` pointed at the checkpoint directory. A
resume spec reconstructs the ORIGINAL run's binning by injecting the
checkpoint bundle's serialized mappers
(utils/checkpoint.mappers_from_bundle) — restored tree thresholds
cannot shift, whatever the new world size.

**Launcher** (``launch_workers``): spawns W real OS processes over a
fresh localhost port with per-rank env (platform pinned to CPU, one
virtual device per process, fault spec armed on the designated victim
only) — the CI-sized stand-in for a pod scheduler.

**Drill** (``run_drill``): the elastic-resume proof. Phase A trains
uninterrupted on a 2-process mesh. Phase B reruns the identical
workload with a seed-keyed SIGKILL (utils/faults.py
``train.iter@K:kill``) on rank 1 and asserts the survivor exits
promptly with the rank-naming error. Phase C resumes from phase B's
latest checkpoint on a ONE-process mesh and trains to completion.
The verdict: phase C's final model must equal phase A's —
bit-identical under the quantized int32 histogram wire, whose
shard-invariant stochastic rounding and integer collectives make the
mesh size drop out of the math (PR 4; tests/test_multichip.py proved
it across virtual mesh sizes, this drill proves it across REAL
process boundaries plus a kill plus a world-size change). The result
dict is the MULTICHIP artifact shape tools/check_bench_regression.py
gates (``model_parity=false`` fails the artifact).

Workload data is synthesized deterministically from the spec seed on
every rank (CI-scale convenience); each rank still ONLY ingests its
own host block — production per-host files ride the same
construct_multihost path.
"""
from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ..utils import log
from . import cluster

# default drill workload: big enough that every world size buckets to
# the same score width (4096 is a pow2 bucket for worlds 1 and 2 —
# see ops/step_cache.py shard_align_unit), small enough for CI
DRILL_N = 4096
DRILL_F = 8

DRILL_PARAMS: Dict = {
    "objective": "binary",
    "metric": "auc",
    "num_leaves": 15,
    "max_bin": 63,
    "min_data_in_leaf": 5,
    "learning_rate": 0.1,
    "tree_learner": "data",
    # the quantized tier's int32 wire + shard-invariant stochastic
    # rounding are what make the final model independent of the mesh
    # size — the property the whole drill rests on
    "tpu_quantized_hist": True,
    # exercise the real double-buffered device ingest off-TPU
    "tpu_ingest": 1,
    # drain the dispatch queue every iteration so a peer death
    # surfaces at the iteration that hit it (and the fault occurrence
    # count == the iteration number)
    "tpu_dispatch_sync_interval": 1,
    "tpu_stop_check_interval": 4,
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _synth_data(spec: Dict):
    import numpy as np
    r = np.random.default_rng(int(spec.get("seed", 0)))
    n = int(spec.get("n", DRILL_N))
    f = int(spec.get("f", DRILL_F))
    X = r.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


def _write_json(path: str, payload: Dict) -> None:
    from ..utils.fileio import atomic_write
    with atomic_write(path) as fh:
        json.dump(payload, fh, indent=1)


# -- the worker ---------------------------------------------------------------


def run_worker(spec: Dict) -> Dict:
    """One rank's whole life: bootstrap -> per-host ingest -> train
    (checkpointing per the spec's params) -> result JSON. Returns the
    result dict (also written to ``spec['out'] + '.rank<r>'``)."""
    from ..config import Config

    params = dict(DRILL_PARAMS)
    params.update(spec.get("params", {}))
    if spec.get("checkpoint_dir"):
        params.setdefault("tpu_checkpoint_dir", spec["checkpoint_dir"])
        params.setdefault("tpu_checkpoint_freq", 1)
    out_dir = os.path.dirname(str(spec.get("out", "") or ""))
    if out_dir:
        # every rank's flight recorder dumps into the SHARED workdir
        # so the survivor's incident sweep (obs/incident.py) reaches
        # the victim's pre-kill bundle too
        params.setdefault("tpu_flight_dir", out_dir)
    cfg = Config().set(params)
    multi = cluster.initialize_from_config(cfg)
    t0 = time.monotonic()

    import numpy as np

    from ..io.dataset import Metadata, TpuDataset
    from ..metrics import create_metrics
    from ..models.gbdt import GBDT
    from ..objectives import create_objective
    from ..obs import registry as obs

    X, y = _synth_data(spec)
    n = X.shape[0]

    resume_from = str(spec.get("resume_from", "") or "")
    inject = None
    if resume_from:
        from ..utils import checkpoint as ckpt
        bundle = ckpt.resolve_resume(resume_from)
        inject = ckpt.mappers_from_bundle(bundle)
        if inject is not None:
            log.info("elastic resume: constructing dataset with the "
                     "checkpoint's %d bin mappers",
                     sum(1 for m in inject if not m.is_trivial))
    if inject is None and spec.get("shared_binning"):
        # the scaling bench compares MODELS across world sizes; the
        # multihost bin finder samples per-host blocks, so its bin
        # boundaries legitimately depend on the world. Pin them: every
        # rank computes mappers from the full synthetic matrix it
        # already holds — deterministic, world-independent, exactly
        # what sharing a binning artifact does in production
        from ..io.dataset import find_column_mappers
        inject = find_column_mappers(X, cfg)
        log.info("shared binning: %d mappers from the full matrix",
                 sum(1 for m in inject if m is not None))

    if multi:
        from ..io.distributed import (DistributedLoader,
                                      allgather_row_slices)
        from ..io.ingest import host_row_block
        from .learners import training_mesh
        mesh = training_mesh(cfg)
        if mesh is None:
            log.fatal("multi-process bootstrap succeeded but no >1 "
                      "device mesh is available — tree_learner must "
                      "be data/voting for multihost training")
        lo, hi, _ = host_row_block(n, mesh,
                                   int(cfg.tpu_hist_chunk or 0))
        # metadata rides the real per-host wire: each rank contributes
        # only its block's labels and the global vector assembles over
        # the allgather (exactly what per-host label files would do —
        # here it must reproduce the synthesized y bit-for-bit)
        y_global = allgather_row_slices(
            np.asarray(y[lo:hi], np.float64), lo, n)
        np.testing.assert_array_equal(
            np.asarray(y_global, np.float32), y)
        ds = DistributedLoader(cfg).construct_multihost(
            X[lo:hi], Metadata(label=y_global), n_global=n,
            row_start=lo, mesh=mesh, mappers=inject)
        block = (lo, hi)
    else:
        ds = TpuDataset(cfg).construct_from_matrix(
            X, Metadata(label=y), mappers=inject)
        block = (0, n)

    obj = create_objective(cfg.objective, cfg)
    obj.init(ds.metadata, ds.num_data)
    mets = create_metrics(["auc"], cfg, ds.metadata, ds.num_data)
    g = GBDT()
    g.init(cfg, ds, obj, mets)

    out_base = str(spec.get("out", "") or "")
    my_out = (f"{out_base}.rank{cluster.rank()}" if out_base else "")

    def survivor_exit(err: cluster.PeerLostError):
        # the one-line actionable error + machine-readable survivor
        # report, then a prompt controlled exit (jax's own shutdown
        # barrier would abort the process — see cluster.shutdown)
        log.warning("%s", err)
        # this rank's own black box first (the survivor's state AT the
        # loss), then the cross-rank incident: sweep every reachable
        # flight bundle — the victim's pre-kill dump landed in the
        # shared tpu_flight_dir before its SIGKILL — plus the final KV
        # digest snapshot into ONE document (obs/incident.py)
        incident_path = None
        try:
            from ..obs import flight as obs_flight
            from ..obs import incident as obs_incident
            obs_flight.trigger(
                "peer_lost",
                {"dead_ranks": list(err.ranks),
                 "error": str(err)[:400],
                 "iteration": int(g.current_iteration)}, force=True)
            sweep_dir = str(cfg.tpu_flight_dir or "") or (
                os.path.dirname(my_out) if my_out else "")
            if sweep_dir:
                incident_path = obs_incident.write_incident(
                    "peer_lost", sweep_dir, dead_ranks=err.ranks,
                    context={"error": str(err)[:400],
                             "iteration": int(g.current_iteration)})
        except Exception:       # noqa: BLE001 — the postmortem must
            pass                # never block the controlled exit
        if my_out:
            _write_json(my_out, {
                "rank": cluster.rank(), "world": cluster.world(),
                "peer_lost": True, "dead_ranks": err.ranks,
                "error": str(err),
                "iterations": int(g.current_iteration),
                "incident": incident_path,
                "wall_s": round(time.monotonic() - t0, 3)})
        os._exit(cluster.EXIT_PEER_LOST)

    try:
        with cluster.DeadlineGuard(what="multihost training step",
                                   on_stall=survivor_exit):
            g.train(resume_from=resume_from)
    except BaseException as e:  # noqa: BLE001 — classified below
        named = cluster.explain_collective_error(e, what="training")
        if named is not None:
            survivor_exit(named)
        raise

    g._ensure_host_trees()
    text = g.model_to_string()
    auc = None
    try:
        auc = float(dict((nm, v) for nm, v, _ in
                         g.get_eval_at(0)).get("auc"))
    except Exception:
        pass
    # DCN accounting for the scaling artifact: per-iteration psum
    # payload bytes + the measured stall estimate (both None off the
    # data-parallel path — e.g. the world-1 scaling point)
    comm_per_iter = psum_stall = None
    try:
        _, waves = g.leaves_and_waves(0)
        comm = g._comm_bytes_per_iteration(waves)
        if comm:
            comm_per_iter = int(round(sum(comm) / len(comm)))
            passes = (sum(waves)
                      + g.num_tree_per_iteration * len(waves))
            psum_stall = g.psum_stall_estimate_s(passes)
    except Exception as e:      # accounting never takes training down
        log.debug("comm accounting skipped: %s", e)
    result = {
        "rank": cluster.rank(),
        "world": cluster.world(),
        "peer_lost": False,
        "iterations": int(g.current_iteration),
        "model_sha": hashlib.sha256(text.encode()).hexdigest(),
        "train_auc": auc,
        "host_row_block": list(block),
        "ingest_rows_local": int(
            obs.counter("ingest/rows_device").value
            or obs.counter("ingest/rows_host").value),
        "wall_s": round(time.monotonic() - t0, 3),
        "wire": g.wire_encoding(),
        "psum_slots": int(getattr(getattr(g, "_grower_cfg", None),
                                  "psum_slots", 1) or 1),
        "comm_bytes_per_iter": comm_per_iter,
        "psum_stall_s": psum_stall,
        "ckpt_hidden_s": (float(obs.counter("ckpt/hidden_s").value)
                          or None),
    }
    if cluster.rank() == 0:
        if spec.get("model_out"):
            from ..utils.fileio import atomic_write
            with atomic_write(spec["model_out"]) as fh:
                fh.write(text)
        if out_base:
            _write_json(out_base, result)
    if my_out:
        _write_json(my_out, result)
    if multi:
        # deterministic end-of-run rollup: push THIS rank's final
        # digest now (the heartbeat ride-along may not have fired
        # since the last iteration), and after the barrier below
        # proves every rank published, rank 0 merges and writes the
        # cluster/* rollups into its export files — the summed
        # cluster counters equal the per-rank digests by construction
        from ..obs import clusterobs
        clusterobs.publish_now()
    # every rank's files are on disk before anyone tears down
    cluster.barrier("elastic-train-done")
    if multi and cluster.rank() == 0:
        from ..obs import clusterobs
        from ..obs import export as obs_export
        try:
            clusterobs.refresh_from_kv()
            exp = obs_export.global_exporter()
            if exp is not None:
                exp._write_once()
        except Exception as e:          # noqa: BLE001 — telemetry
            log.debug("final cluster rollup skipped: %s", e)
    cluster.shutdown()
    return result


def worker_main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="elastic multihost worker (one rank)")
    ap.add_argument("--spec", required=True,
                    help="drill spec JSON path")
    args = ap.parse_args(argv)
    with open(args.spec) as fh:
        spec = json.load(fh)
    try:
        run_worker(spec)
    except BaseException as e:  # noqa: BLE001 — classified below
        # the training loop's own survivor path handles in-train peer
        # deaths; this net catches a peer dying during ANY other
        # collective (mapper-agreement allgather, multihost ingest
        # assembly, checkpoint gather) — same one-line rank-naming
        # error, same controlled exit
        named = cluster.explain_collective_error(e, what="collective")
        if named is not None:
            log.warning("%s", named)
            out = str(spec.get("out", "") or "")
            if out:
                _write_json(f"{out}.rank{cluster.rank()}", {
                    "rank": cluster.rank(), "world": cluster.world(),
                    "peer_lost": True, "dead_ranks": named.ranks,
                    "error": str(named), "iterations": 0})
            os._exit(cluster.EXIT_PEER_LOST)
        raise
    return 0


# -- the launcher -------------------------------------------------------------


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def launch_workers(spec_path: str, world: int, *,
                   port: Optional[int] = None,
                   local_devices: int = 1,
                   fault_rank: Optional[int] = None,
                   faults: str = "",
                   log_dir: str = "") -> List[subprocess.Popen]:
    """Spawn ``world`` real worker processes over a fresh localhost
    coordinator port. Every child gets a CLEAN platform env (CPU
    backend, ``local_devices`` virtual devices — NOT the parent's
    8-device test flag) and the fault spec is armed ONLY on
    ``fault_rank`` (the drill's designated victim). The ranks are
    pinned to ``JAX_PLATFORMS=cpu``: an accelerator belongs to ONE
    process at a time, so these children never ask for it and cannot
    collide with a parent that holds it (multi-chip training on one
    host is one process over a mesh, models/gbdt.py)."""
    port = port or _free_port()
    procs = []
    for r in range(world):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{local_devices}")
        env[cluster.ENV_COORDINATOR] = f"localhost:{port}"
        env[cluster.ENV_NUM_MACHINES] = str(world)
        env[cluster.ENV_MACHINE_RANK] = str(r)
        env["PYTHONPATH"] = _repo_root() + os.pathsep + \
            env.get("PYTHONPATH", "")
        # a fault plan inherited from the parent (pytest arming its
        # own drills) must not leak into every worker
        env.pop("LGBM_TPU_FAULTS", None)
        if faults and r == fault_rank:
            env["LGBM_TPU_FAULTS"] = faults
        stdout = None
        if log_dir:
            stdout = open(os.path.join(log_dir, f"worker{r}.log"),
                          "w")
        try:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "lightgbm_tpu.parallel.elastic",
                 "--spec", spec_path],
                cwd=_repo_root(), env=env, stdout=stdout,
                stderr=subprocess.STDOUT if stdout else None))
        finally:
            if stdout is not None:
                # the child owns its inherited descriptor; holding the
                # parent's open handle would leak one fd per worker
                # per drill phase
                stdout.close()
    return procs


def wait_workers(procs: List[subprocess.Popen],
                 timeout_s: float = 600.0) -> List[int]:
    """Join every worker; returns return codes (negative = signal).
    A worker that outlives the timeout is killed and reported as
    -9."""
    deadline = time.monotonic() + timeout_s
    codes = []
    for p in procs:
        left = max(deadline - time.monotonic(), 1.0)
        try:
            codes.append(p.wait(timeout=left))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            codes.append(-9)
    return codes


def run_two_process(workdir: str, *, n: int = 1024, iterations: int = 4,
                    seed: int = 0, extra_params: Optional[Dict] = None,
                    timeout_s: float = 420.0) -> Dict:
    """The tier-1 smoke: train a small workload across 2 REAL
    processes, assert both ranks finish and agree on the model hash.
    Returns {result, rank_results}."""
    os.makedirs(workdir, exist_ok=True)
    spec = {
        "seed": seed, "n": n, "f": DRILL_F,
        "params": {**(extra_params or {}),
                   "num_iterations": iterations},
        "out": os.path.join(workdir, "result.json"),
        "model_out": os.path.join(workdir, "model.txt"),
    }
    spec_path = os.path.join(workdir, "spec.json")
    _write_json(spec_path, spec)
    procs = launch_workers(spec_path, 2, log_dir=workdir)
    codes = wait_workers(procs, timeout_s)
    if any(codes):
        tails = _worker_tails(workdir, 2)
        raise RuntimeError(f"two-process smoke failed: rc={codes}\n"
                           f"{tails}")
    ranks = [_read_json(spec["out"] + f".rank{r}") for r in range(2)]
    if ranks[0]["model_sha"] != ranks[1]["model_sha"]:
        raise RuntimeError(f"ranks disagree on the trained model: "
                           f"{ranks[0]['model_sha']} vs "
                           f"{ranks[1]['model_sha']}")
    return {"result": _read_json(spec["out"]), "rank_results": ranks}


def _read_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def _worker_tails(workdir: str, world: int, nbytes: int = 2000) -> str:
    outs = []
    for r in range(world):
        p = os.path.join(workdir, f"worker{r}.log")
        try:
            with open(p) as fh:
                data = fh.read()
            outs.append(f"--- worker{r} tail ---\n{data[-nbytes:]}")
        except OSError:
            outs.append(f"--- worker{r}: no log ---")
    return "\n".join(outs)


def run_drill(workdir: str, *, n: int = DRILL_N, iterations: int = 10,
              kill_at: int = 6, seed: int = 0,
              collective_timeout_s: float = 30.0,
              timeout_s: float = 900.0) -> Dict:
    """The full elastic-resume drill (see module docstring). Returns
    the MULTICHIP artifact dict; raises on any phase failure EXCEPT
    parity, which is reported in the dict (``model_parity``) so the
    artifact gate — not an exception — is the arbiter."""
    os.makedirs(workdir, exist_ok=True)
    base = {
        "seed": seed, "n": n, "f": DRILL_F,
        "params": {"num_iterations": iterations,
                   "tpu_collective_timeout_s": collective_timeout_s},
    }

    # phase A: uninterrupted 2-process run
    dir_a = os.path.join(workdir, "a_uninterrupted")
    os.makedirs(dir_a, exist_ok=True)
    spec_a = dict(base)
    # phase A also exercises the cluster-scope rollup path: rank 0's
    # exporter merges both ranks' KV digests into cluster/* series and
    # the artifact carries the final rollup (obs/clusterobs.py)
    spec_a["params"] = {**base["params"],
                        "tpu_metrics_export":
                            os.path.join(dir_a, "metrics")}
    spec_a.update(out=os.path.join(dir_a, "result.json"),
                  model_out=os.path.join(dir_a, "model.txt"),
                  checkpoint_dir=os.path.join(dir_a, "ckpt"))
    p_a = os.path.join(dir_a, "spec.json")
    _write_json(p_a, spec_a)
    t_a = time.monotonic()
    codes = wait_workers(launch_workers(p_a, 2, log_dir=dir_a),
                         timeout_s / 2)
    if any(codes):
        raise RuntimeError(f"drill phase A (uninterrupted) failed: "
                           f"rc={codes}\n{_worker_tails(dir_a, 2)}")
    res_a = _read_json(spec_a["out"])
    ranks_a = [_read_json(spec_a["out"] + f".rank{r}")
               for r in range(2)]
    wall_a = time.monotonic() - t_a

    # phase B: identical run, rank 1 SIGKILLed at iteration kill_at
    dir_b = os.path.join(workdir, "b_killed")
    os.makedirs(dir_b, exist_ok=True)
    spec_b = dict(base)
    spec_b.update(out=os.path.join(dir_b, "result.json"),
                  checkpoint_dir=os.path.join(dir_b, "ckpt"))
    p_b = os.path.join(dir_b, "spec.json")
    _write_json(p_b, spec_b)
    t_b = time.monotonic()
    procs = launch_workers(p_b, 2, log_dir=dir_b, fault_rank=1,
                           faults=f"train.iter@{kill_at}:kill")
    codes_b = wait_workers(procs, timeout_s / 2)
    wall_b = time.monotonic() - t_b
    # rank 1 dies by SIGKILL; rank 0 must exit EXIT_PEER_LOST, fast
    if codes_b[1] != -9:
        raise RuntimeError(f"drill phase B: victim rank 1 exited "
                           f"rc={codes_b[1]}, expected SIGKILL (-9)\n"
                           f"{_worker_tails(dir_b, 2)}")
    if codes_b[0] != cluster.EXIT_PEER_LOST:
        raise RuntimeError(f"drill phase B: survivor rank 0 exited "
                           f"rc={codes_b[0]}, expected EXIT_PEER_LOST "
                           f"({cluster.EXIT_PEER_LOST})\n"
                           f"{_worker_tails(dir_b, 2)}")
    surv = _read_json(spec_b["out"] + ".rank0")
    if not surv.get("peer_lost") or 1 not in surv.get("dead_ranks", []):
        raise RuntimeError(f"drill phase B: survivor report does not "
                           f"name rank 1: {surv}")
    # the distributed incident: the survivor assembled one on its way
    # out (every rank's flight recorder dumped into the shared dir_b);
    # re-sweep now that BOTH processes have exited — the victim's
    # pre-kill bundle can hit the disk after the survivor's sweep
    from ..obs import incident as obs_incident
    incident_path = surv.get("incident") or os.path.join(
        dir_b, "incident_peer_lost.json")
    inc_doc = None
    if os.path.exists(incident_path):
        inc_doc = obs_incident.resweep(incident_path, dir_b)
    if inc_doc is None:
        incident_path = obs_incident.write_incident(
            "drill_peer_lost", dir_b, dead_ranks=[1],
            context={"kill_iteration": kill_at})
        inc_doc = (obs_incident.load_incident(incident_path)
                   if incident_path else None)

    # phase C: resume the survivor onto a ONE-process mesh
    dir_c = os.path.join(workdir, "c_resumed")
    os.makedirs(dir_c, exist_ok=True)
    spec_c = dict(base)
    spec_c.update(out=os.path.join(dir_c, "result.json"),
                  model_out=os.path.join(dir_c, "model.txt"),
                  checkpoint_dir=os.path.join(dir_c, "ckpt"),
                  resume_from=spec_b["checkpoint_dir"])
    p_c = os.path.join(dir_c, "spec.json")
    _write_json(p_c, spec_c)
    t_c = time.monotonic()
    codes_c = wait_workers(launch_workers(p_c, 1, log_dir=dir_c),
                           timeout_s / 2)
    if any(codes_c):
        raise RuntimeError(f"drill phase C (resume) failed: "
                           f"rc={codes_c}\n{_worker_tails(dir_c, 1)}")
    res_c = _read_json(spec_c["out"])
    wall_c = time.monotonic() - t_c

    from ..utils import checkpoint as ckpt_mod
    entries = ckpt_mod.list_checkpoints(spec_b["checkpoint_dir"])
    resumed_from = entries[0][0] if entries else None

    with open(spec_a["model_out"]) as fh:
        model_a = fh.read()
    with open(spec_c["model_out"]) as fh:
        model_c = fh.read()
    parity = _strip_volatile(model_a) == _strip_volatile(model_c)

    return {
        "cluster_obs": _cluster_obs_section(
            os.path.join(dir_a, "metrics.r0.jsonl"), world=2),
        "incident": _incident_section(incident_path, inc_doc),
        "schema": "lightgbm-tpu/multichip-drill",
        "version": 1,
        "drill": "elastic_resume",
        "workload": {"n": n, "f": DRILL_F, "seed": seed,
                     "iterations": iterations,
                     "params": dict(DRILL_PARAMS)},
        "world_sizes": {"train": 2, "resume": 1},
        "kill": {"rank": 1, "iteration": kill_at,
                 "survivor_exit_code": codes_b[0],
                 "survivor_error": surv.get("error", ""),
                 "survivor_named_ranks": surv.get("dead_ranks", [])},
        "resume": {"from_iteration": resumed_from,
                   "total_iterations": res_c["iterations"],
                   "collective_timeout_s": collective_timeout_s},
        "per_host_ingest_rows": [r.get("ingest_rows_local")
                                 for r in ranks_a],
        "model_parity": parity,
        "parity_kind": "bit_identical",
        "train_auc": res_a.get("train_auc"),
        "resumed_auc": res_c.get("train_auc"),
        "wall_s": {"uninterrupted": round(wall_a, 2),
                   "killed": round(wall_b, 2),
                   "resumed": round(wall_c, 2)},
    }


def _cluster_obs_section(jsonl_path: str, world: int) -> Optional[Dict]:
    """The final cluster/* rollup out of rank 0's JSONL export, shaped
    for the MULTICHIP artifact (tools/check_bench_regression.py
    validates the shape; it never perf-gates these numbers). None when
    the export is absent/unparseable — a missing rollup is a note, not
    a drill failure."""
    last = None
    try:
        with open(jsonl_path) as fh:
            for ln in fh:
                ln = ln.strip()
                if ln:
                    last = json.loads(ln)
    except (OSError, ValueError):
        return None
    if not isinstance(last, dict):
        return None
    counters = last.get("counters") or {}
    gauges = last.get("gauges") or {}
    if not any(k.startswith("cluster/") for k in counters):
        return None
    return {
        "export": jsonl_path,
        "world": gauges.get("cluster/world"),
        "ranks_reporting": gauges.get("cluster/ranks_reporting"),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith("cluster/")},
        "per_rank_iter_wall_mean_s": {
            k.rsplit("/r", 1)[1]: v for k, v in gauges.items()
            if k.startswith("cluster/iter_wall_mean_s/r")},
        "straggler": {
            "psum_stall_max_rank":
                gauges.get("cluster/psum_stall_max_rank"),
            "slowest_iter_rank":
                gauges.get("cluster/slowest_iter_rank")},
    }


def _incident_section(path: Optional[str],
                      doc: Optional[Dict]) -> Optional[Dict]:
    """The incident bundle summarized for the MULTICHIP artifact —
    the full document stays on disk; the artifact carries what the
    gate checks (who died, whose evidence made it in)."""
    if not path or not isinstance(doc, dict):
        return None
    return {
        "path": path,
        "schema": doc.get("schema"),
        "version": doc.get("version"),
        "dead_ranks": doc.get("dead_ranks", []),
        "ranks_with_dumps": doc.get("ranks_with_dumps", []),
        "digest_ranks": sorted(int(k) for k in
                               (doc.get("digests") or {})),
    }


def _strip_volatile(model_text: str) -> str:
    """Model text minus the serialized ``parameters:`` block — the
    parity bar covers every TREE byte and the feature metadata; the
    parameters block embeds volatile run-artifact paths
    (tpu_checkpoint_dir differs between drill phases by construction,
    exactly like checkpoint.VOLATILE_KNOBS excludes them from the
    resume fingerprint)."""
    lo = model_text.find("\nparameters:")
    hi = model_text.find("end of parameters")
    if lo < 0 or hi < 0:
        return model_text
    return model_text[:lo] + model_text[hi:]


# -- elastic autoscale --------------------------------------------------------


def train_autoscale(workdir: str, *, n: int = DRILL_N, f: int = DRILL_F,
                    iterations: int = 12, window: int = 4,
                    start_world: int = 2, seed: int = 0,
                    schedule: Optional[Dict[int, int]] = None,
                    extra_params: Optional[Dict] = None) -> Dict:
    """The elastic autoscale controller: train in LRB window segments
    and consult the scale signal (cluster.poll_scale_signal — a pod
    scheduler's preemption notice or load target) at every window
    boundary. On a world change the controller relies on the
    checkpoints already on disk (the controller trains with
    tpu_checkpoint_freq=1), tears down the segment's booster, and
    resumes onto the NEW world size WITHOUT leaving the process: the
    PR-15 restore path (mappers_from_bundle injection + resume_from)
    turns the re-shard into a data-plane event instead of a job
    restart. World sizes here are the ``num_machines`` virtual-mesh
    cap (the in-process stand-in for real rank counts — a VOLATILE
    knob, utils/checkpoint.py, so the fingerprint admits the resume);
    the maneuver preserves the model bit-for-bit because the
    quantized tier's histograms are mesh-size invariant
    (tests/test_multichip.py).

    ``schedule`` maps a boundary iteration to a target world; entries
    are POSTED through cluster.post_scale_signal when that boundary is
    reached, standing in for the external scheduler — the controller
    itself only ever READS the signal.

    Returns {model_text, worlds, reshards, iterations}.
    """
    from ..config import Config
    from ..io.dataset import Metadata, TpuDataset
    from ..metrics import create_metrics
    from ..models.gbdt import GBDT
    from ..objectives import create_objective
    from ..obs import registry as obs
    from ..utils import checkpoint as ckpt

    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    X, y = _synth_data({"seed": seed, "n": n, "f": f})

    world = max(int(start_world), 1)
    worlds = [world]
    reshards = 0
    done = 0
    model_text = ""
    while done < iterations:
        if schedule and done in schedule:
            cluster.post_scale_signal(int(schedule[done]))
        target = cluster.poll_scale_signal()
        if target is not None:
            cluster.clear_scale_signal()
            if target != world:
                if done > 0:
                    reshards += 1
                    obs.counter("elastic/reshard_total").add(1)
                    # instant on the trace timeline (the restore path
                    # bumps the identity incarnation when it actually
                    # re-shards the score buffers, utils/checkpoint.py)
                    from ..obs import trace as obs_trace
                    obs_trace.instant(
                        "elastic/reshard", cat="cluster",
                        args={"from_world": world, "to_world": target,
                              "iteration": done})
                    log.info("elastic autoscale: re-sharding world "
                             "%d -> %d at iteration %d (resume from "
                             "%s)", world, target, done, ckpt_dir)
                world = target
                worlds.append(world)
        end = min(done + window, iterations)
        params = dict(DRILL_PARAMS)
        params.update(extra_params or {})
        params.update(
            num_machines=world,
            num_iterations=end,
            tpu_checkpoint_dir=ckpt_dir,
            tpu_checkpoint_freq=1)
        cfg = Config().set(params)
        inject = None
        resume = ""
        if done > 0:
            resume = ckpt_dir
            bundle = ckpt.resolve_resume(ckpt_dir)
            inject = ckpt.mappers_from_bundle(bundle)
        ds = TpuDataset(cfg).construct_from_matrix(
            X, Metadata(label=y), mappers=inject)
        obj = create_objective(cfg.objective, cfg)
        obj.init(ds.metadata, ds.num_data)
        mets = create_metrics(["auc"], cfg, ds.metadata, ds.num_data)
        g = GBDT()
        g.init(cfg, ds, obj, mets)
        g.train(resume_from=resume)
        got = int(g.current_iteration)
        g._ensure_host_trees()
        model_text = g.model_to_string()
        if got <= done:     # early stop / no progress: don't spin
            done = iterations
            break
        done = got
    return {"model_text": model_text, "worlds": worlds,
            "reshards": reshards, "iterations": done}


def run_autoscale_drill(workdir: str, *, n: int = DRILL_N,
                        iterations: int = 12, window: int = 4,
                        worlds=(2, 4, 2), seed: int = 0,
                        extra_params: Optional[Dict] = None) -> Dict:
    """The grow-then-shrink proof: one uninterrupted run at
    ``worlds[0]`` vs one autoscaled run that re-shards through every
    world in ``worlds`` at successive window boundaries — final models
    must match bit-for-bit (minus the volatile parameters block).
    Returns the ``autoscale`` section of the MULTICHIP scaling
    artifact; the artifact gate (tools/check_bench_regression.py) —
    not an exception — is the parity arbiter."""
    os.makedirs(workdir, exist_ok=True)
    schedule = {window * (i + 1): int(w)
                for i, w in enumerate(worlds[1:])}
    cluster.clear_scale_signal()
    try:
        base = train_autoscale(
            os.path.join(workdir, "baseline"), n=n,
            iterations=iterations, window=iterations,
            start_world=worlds[0], seed=seed,
            extra_params=extra_params)
        el = train_autoscale(
            os.path.join(workdir, "elastic"), n=n,
            iterations=iterations, window=window,
            start_world=worlds[0], seed=seed, schedule=schedule,
            extra_params=extra_params)
    finally:
        cluster.clear_scale_signal()
    parity = (_strip_volatile(base["model_text"])
              == _strip_volatile(el["model_text"]))
    return {
        "drill": "autoscale_grow_shrink",
        "worlds": el["worlds"],
        "window": window,
        "iterations": iterations,
        "reshard_total": el["reshards"],
        "model_parity": parity,
        "parity_kind": "bit_identical",
    }


def run_scaling_bench(workdir: str, *, world_sizes=(1, 2, 4),
                      n: int = DRILL_N, iterations: int = 8,
                      seed: int = 0,
                      extra_params: Optional[Dict] = None,
                      timeout_s: float = 900.0) -> List[Dict]:
    """The measured scaling curve: train the identical workload at
    each world size over REAL processes (launch_workers), collecting
    throughput, per-iteration DCN bytes, the measured psum stall and
    the checkpoint seconds hidden by the background writer. Model
    texts (minus the volatile parameters block — world size and
    artifact paths differ by construction) must agree across every
    point; each point carries the stripped-text sha so the artifact
    gate can arbitrate."""
    points = []
    for w in world_sizes:
        wd = os.path.join(workdir, f"w{w}")
        os.makedirs(wd, exist_ok=True)
        spec = {
            "seed": seed, "n": n, "f": DRILL_F,
            "shared_binning": True,
            "params": {**(extra_params or {}),
                       "num_iterations": iterations},
            "checkpoint_dir": os.path.join(wd, "ckpt"),
            "out": os.path.join(wd, "result.json"),
            "model_out": os.path.join(wd, "model.txt"),
        }
        spec_path = os.path.join(wd, "spec.json")
        _write_json(spec_path, spec)
        t0 = time.monotonic()
        codes = wait_workers(launch_workers(spec_path, w, log_dir=wd),
                             timeout_s)
        wall = time.monotonic() - t0
        if any(codes):
            raise RuntimeError(
                f"scaling bench world={w} failed: rc={codes}\n"
                f"{_worker_tails(wd, w)}")
        res = _read_json(spec["out"])
        with open(spec["model_out"]) as fh:
            sha = hashlib.sha256(
                _strip_volatile(fh.read()).encode()).hexdigest()
        train_wall = float(res.get("wall_s") or wall)
        points.append({
            "world": w,
            "wall_s": train_wall,
            "launch_wall_s": round(wall, 2),
            "throughput_rows_per_s": round(
                n * iterations / max(train_wall, 1e-9), 1),
            "comm_bytes_per_iter": res.get("comm_bytes_per_iter"),
            "psum_stall_s": res.get("psum_stall_s"),
            "ckpt_hidden_s": res.get("ckpt_hidden_s"),
            "wire": res.get("wire"),
            "psum_slots": res.get("psum_slots"),
            "model_sha": sha,
        })
    return points


def run_scaling_artifact(workdir: str, *, world_sizes=(1, 2, 4),
                         n: int = DRILL_N, iterations: int = 8,
                         autoscale_window: int = 4,
                         seed: int = 0,
                         extra_params: Optional[Dict] = None) -> Dict:
    """Assemble the full MULTICHIP scaling artifact
    (schema lightgbm-tpu/multichip-scaling): the measured curve over
    real process worlds plus the in-process grow-then-shrink autoscale
    drill. This is what generates ``benchmarks/MULTICHIP_rNN.json``."""
    points = run_scaling_bench(
        os.path.join(workdir, "curve"), world_sizes=world_sizes, n=n,
        iterations=iterations, seed=seed, extra_params=extra_params)
    auto = run_autoscale_drill(
        os.path.join(workdir, "autoscale"), n=n,
        iterations=max(iterations, 3 * autoscale_window),
        window=autoscale_window, seed=seed,
        extra_params=extra_params)
    shas = {p["model_sha"] for p in points}
    hidden = [p["ckpt_hidden_s"] for p in points
              if p.get("ckpt_hidden_s")]
    return {
        "schema": "lightgbm-tpu/multichip-scaling",
        "version": 1,
        "workload": {"n": n, "f": DRILL_F, "seed": seed,
                     "iterations": iterations,
                     "params": {**DRILL_PARAMS,
                                **(extra_params or {})}},
        "points": points,
        "model_parity": len(shas) == 1,
        "parity_kind": "bit_identical",
        "checkpoint": {"hidden_s": (round(max(hidden), 4)
                                    if hidden else None)},
        "autoscale": auto,
    }


if __name__ == "__main__":
    sys.exit(worker_main())
