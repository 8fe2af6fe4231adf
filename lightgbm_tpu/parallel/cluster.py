"""Cluster bootstrap and collective robustness for real multi-process
training.

The sharded learners (parallel/learners.py) were proven on a
single-process virtual mesh; this module is the missing runtime layer
that makes the SAME shard_map programs span real OS processes over
DCN — the TPU-native analog of the reference's socket linkers
(src/network/linkers_socket.cpp Construct/CheckLinker: TCP bootstrap,
rank/world handshake, ``time_out``-bounded waits that NAME the machine
that never answered).

Three responsibilities:

**Bootstrap** (``initialize_from_config``): wraps
``jax.distributed.initialize`` behind the ``tpu_num_machines`` /
``tpu_machine_rank`` / ``tpu_coordinator`` knobs (env twins
``LGBM_TPU_NUM_MACHINES`` / ``LGBM_TPU_MACHINE_RANK`` /
``LGBM_TPU_COORDINATOR`` for subprocess launchers). Connection is
retried through utils/retry.py — a coordinator that is still starting
(connect refused / UNAVAILABLE / barrier timeout) is a transient blip,
not a config error. On the CPU backend the gloo collective
implementation is selected so the drill harness runs the real
cross-process wire. After initialize, a KV **heartbeat** thread
publishes this rank's liveness into the coordination service every
``HEARTBEAT_S`` so peers can DIAGNOSE a dead rank by name (see below).

**Liveness and the no-hang guarantee**: every blocking sync point gets
a bounded deadline (``tpu_collective_timeout_s``). A dead peer must
produce ONE actionable line naming the rank — never an indefinite
hang:

- ``barrier(name)`` wraps the coordination-service barrier with the
  configured timeout and re-raises its DEADLINE_EXCEEDED as a
  ``PeerLostError`` naming the ranks that never arrived (parsed from
  the service's straggler list, cross-checked against heartbeats).
- ``explain_collective_error(exc)`` maps a raw in-collective failure
  (gloo "Connection reset by peer", NCCL aborts, coordination-service
  heartbeat errors) to a ``PeerLostError`` naming the unresponsive
  rank(s) found by ``probe_dead_ranks()`` — heartbeat-SEQUENCE
  progress across a short window, never wall-clock comparison, so
  cross-host clock skew cannot frame a healthy peer.
- ``DeadlineGuard`` covers backends whose collectives BLOCK instead of
  failing: a watchdog thread monitors ``tick()`` progress stamps; a
  stall past the deadline probes liveness, logs the one-line error,
  triggers a flight dump, and fail-fasts the process with
  ``EXIT_PEER_LOST`` (a hang is turned into a fast, named death an
  orchestrator can act on — the elastic resume path).

**SPMD placement seams**: under a multi-process mesh,
``jax.device_put`` cannot place host arrays onto non-addressable
devices. ``host_to_global`` builds a global array from a host-global
value via ``make_array_from_callback`` (every rank holds the same
value — the labels/masks/scores discipline models/gbdt.py keeps), and
``fetch`` gathers any global array back to a host numpy array
(replicated arrays read directly; sharded ones ride one all-gather
jit). Single-process callers fall straight through to the normal
paths, so nothing here costs anything on the virtual mesh.

Import of this module never touches jax (the harness arms env vars
before the first jax import); jax loads lazily inside the functions.
"""
from __future__ import annotations

import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional

from ..utils import log

ENV_COORDINATOR = "LGBM_TPU_COORDINATOR"
ENV_NUM_MACHINES = "LGBM_TPU_NUM_MACHINES"
ENV_MACHINE_RANK = "LGBM_TPU_MACHINE_RANK"

# process exit code for "peer lost, resume me elsewhere" — distinct
# from crash codes so launchers (parallel/elastic.py) can tell a
# preemption casualty from a bug
EXIT_PEER_LOST = 17

# KV namespace for rank heartbeats inside the coordination service
_HB_PREFIX = "lgbm_tpu/hb/"
HEARTBEAT_S = 0.5
# how long probe_dead_ranks waits between its two sequence snapshots:
# a live rank publishes every HEARTBEAT_S, so 2.5 intervals guarantee
# visible progress with a full cycle of slack. Progress-based (the
# seq in the key), NOT wall-stamp-based — cross-host clock skew must
# not make a healthy peer look dead.
_PROBE_WAIT_S = 2.5 * HEARTBEAT_S

# coordination-service task names look like
# /job:jax_worker/replica:0/task:3 — the task index IS the rank
_TASK_RE = re.compile(r"/job:[^/]+/replica:\d+/task:(\d+)")


class PeerLostError(RuntimeError):
    """A peer process is unresponsive/dead. ``ranks`` lists the
    suspects (empty = could not attribute — coordinator itself may be
    gone). The message is the one actionable line the no-hang
    guarantee promises."""

    def __init__(self, msg: str, ranks: List[int] = ()):  # noqa: B006
        super().__init__(msg)
        self.ranks = list(ranks)


_lock = threading.Lock()
_state: Dict = {
    "initialized": False,   # this module ran jax.distributed.initialize
    "world": 1,
    "rank": 0,
    "coordinator": "",
    "deadline_s": 60.0,
    "hb_thread": None,
    "hb_stop": None,
    "tick": None,           # (label, monotonic stamp) progress marker
}


def world() -> int:
    return _state["world"]


def rank() -> int:
    return _state["rank"]


def is_multiprocess() -> bool:
    """True when this process is one rank of a >1-process cluster."""
    return _state["world"] > 1


def deadline_s() -> float:
    return _state["deadline_s"]


def _client():
    """The coordination-service KV client, or None single-process."""
    if not is_multiprocess():
        return None
    try:
        from jax._src.distributed import global_state
        return global_state.client
    except Exception:           # pragma: no cover - jax internals moved
        return None


# KV key the autoscale controller (parallel/elastic.py) polls at LRB
# window boundaries. A pod scheduler (or the drill) posts the DESIRED
# world size here; workers see it at the next boundary and re-shard
# through the checkpoint/restore path instead of dying. Env twin for
# single-process/virtual-mesh runs where no coordination service
# exists.
_ELASTIC_PREFIX = "lgbm_tpu/elastic/"
_ELASTIC_KEY = _ELASTIC_PREFIX + "target_world"
ENV_TARGET_WORLD = "LGBM_TPU_TARGET_WORLD"


def post_scale_signal(target_world: int) -> None:
    """Publish the desired world size for elastic autoscaling. Under a
    real cluster this lands in the coordination-service KV (visible to
    every rank); single-process it sets the env twin so in-process
    virtual-mesh controllers observe the same signal."""
    client = _client()
    if client is not None:
        client.key_value_set(_ELASTIC_KEY, str(int(target_world)))
    else:
        os.environ[ENV_TARGET_WORLD] = str(int(target_world))


def poll_scale_signal() -> Optional[int]:
    """The posted target world size, or None when no signal (or an
    unparsable one) is present. Non-blocking: the KV read is a dir
    listing (the only non-blocking get the coordination client
    offers — blocking_key_value_get would stall on an absent key)."""
    client = _client()
    raw = None
    if client is not None:
        try:
            entries = client.key_value_dir_get(_ELASTIC_PREFIX)
        except Exception:
            entries = []
        for key, value in entries:
            if key == _ELASTIC_KEY or key.endswith("target_world"):
                raw = value
    if raw is None:
        raw = os.environ.get(ENV_TARGET_WORLD)
    try:
        target = int(str(raw))
    except (TypeError, ValueError):
        return None
    return target if target >= 1 else None


def clear_scale_signal() -> None:
    """Retire a consumed signal so the controller does not re-shard
    again at the next boundary."""
    client = _client()
    if client is not None:
        try:
            client.key_value_delete(_ELASTIC_KEY)
        except Exception:
            pass
    os.environ.pop(ENV_TARGET_WORLD, None)


def _resolve_topology(config) -> tuple:
    """(world, rank, coordinator) from config knobs with env twins
    (a set-and-non-empty env wins — the launcher sets per-process
    ranks that one shared config string cannot express; an EMPTY env
    value falls back to the knob instead of crashing int(''))."""
    world_n = int(os.environ.get(ENV_NUM_MACHINES)
                  or getattr(config, "tpu_num_machines", 0) or 0)
    rank_n = int(os.environ.get(ENV_MACHINE_RANK)
                 or getattr(config, "tpu_machine_rank", -1))
    coord = (os.environ.get(ENV_COORDINATOR)
             or str(getattr(config, "tpu_coordinator", "") or ""))
    return world_n, rank_n, coord


def initialize_from_config(config) -> bool:
    """Bootstrap the jax.distributed runtime when the config/env asks
    for >1 processes. Returns True when this process is (now) part of
    a multi-process cluster. Idempotent: a second call with the same
    topology is a no-op; calls after jax is already distributed adopt
    the live topology.

    MUST run before any other jax use in the process (the backend
    client binds at first device access).
    """
    world_n, rank_n, coord = _resolve_topology(config)
    _state["deadline_s"] = float(
        getattr(config, "tpu_collective_timeout_s", 60.0) or 60.0)
    import jax
    # prior-initialization probe via the distributed global state —
    # NOT jax.process_count(), which would initialize the backend and
    # freeze an uninitialized process out of its cluster
    try:
        from jax._src.distributed import global_state
        already = getattr(global_state, "client", None) is not None
    except Exception:           # pragma: no cover - jax internals moved
        already = False
    if _state["initialized"] or already:
        # already distributed (this module or an embedding application)
        _adopt_live_topology()
        return is_multiprocess()
    if world_n <= 1:
        return False
    try:
        from jax._src import xla_bridge
        backends_up = bool(getattr(xla_bridge, "_backends", None))
    except Exception:           # pragma: no cover - jax internals moved
        backends_up = False
    if backends_up:
        log.fatal(f"tpu_num_machines={world_n} but the jax backend is "
                  f"already initialized — cluster bootstrap must be "
                  f"the process's FIRST jax use (run training through "
                  f"the elastic worker, parallel/elastic.py, or call "
                  f"cluster.initialize_from_config before touching "
                  f"data)")
    if rank_n < 0 or rank_n >= world_n:
        log.fatal(f"tpu_num_machines={world_n} needs tpu_machine_rank "
                  f"in [0, {world_n}) on every process (got {rank_n}); "
                  f"set it per-process or export {ENV_MACHINE_RANK}")
    if not coord:
        log.fatal(f"tpu_num_machines={world_n} needs a coordinator "
                  f"address: set tpu_coordinator=host:port (or export "
                  f"{ENV_COORDINATOR}) — rank 0's address, like the "
                  f"reference's machine_list first entry")
    # The CPU backend's cross-process collectives ride gloo; the knob
    # must be set before backend init — and NOTHING here may touch
    # devices (even utils/device.on_tpu would initialize the backend
    # and freeze the process out of the cluster). Setting it is
    # harmless on accelerator platforms: it only shapes the CPU
    # client.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        log.warning("jax has no jax_cpu_collectives_implementation "
                    "option; CPU cross-process collectives may be "
                    "unavailable")

    from ..utils import retry

    def _connect():
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=world_n,
            process_id=rank_n,
            initialization_timeout=max(int(_state["deadline_s"]), 10))

    # a coordinator that is still binding its port surfaces as connect
    # refused / UNAVAILABLE / barrier timeout — the retry classifier
    # knows these DCN strings (utils/retry.py TRANSIENT_MARKERS)
    retry.call(_connect, what=f"jax.distributed.initialize({coord})",
               policy=retry.RetryPolicy(
                   attempts=max(int(getattr(config, "tpu_retry_attempts",
                                            4) or 4), 1),
                   base_s=0.5, max_s=5.0))
    with _lock:
        _state.update(initialized=True, world=world_n, rank=rank_n,
                      coordinator=coord)
    _set_identity(rank_n, world_n)
    from ..obs import clusterobs
    clusterobs.configure_from_config(config)
    _start_heartbeat()
    log.info("cluster up: rank %d/%d, coordinator %s, %d global / %d "
             "local device(s)", rank_n, world_n, coord,
             jax.device_count(), jax.local_device_count())
    return True


def _set_identity(rank_n: int, world_n: int) -> None:
    """Propagate the resolved topology into the process identity
    record (obs/identity.py — every metrics snapshot / trace event /
    flight bundle stamps it) and the log prefix rank tag."""
    from ..obs import identity
    identity.set_topology(rank_n, world_n)
    log.set_rank_tag(identity.log_tag())


def _adopt_live_topology() -> None:
    """Record a jax.distributed runtime someone else initialized."""
    import jax
    if jax.process_count() > 1 and _state["world"] == 1:
        with _lock:
            if _state["world"] == 1:
                _state.update(world=jax.process_count(),
                              rank=jax.process_index())
        _set_identity(_state["rank"], _state["world"])
        _start_heartbeat()


# -- heartbeats and liveness -------------------------------------------------


def _start_heartbeat() -> None:
    """Publish this rank's liveness into the coordination-service KV
    store every HEARTBEAT_S: ``lgbm_tpu/hb/<rank>/<seq> = monotonic-ish
    wall stamp``, deleting the previous seq so the directory stays one
    entry per rank. Peers read the directory to name dead ranks."""
    if _state["hb_thread"] is not None or not is_multiprocess():
        return                          # fast path; re-checked under _lock
    client = _client()
    if client is None:
        return
    stop = threading.Event()

    def beat():
        from ..obs import clusterobs
        seq = 0
        while not stop.is_set():
            try:
                client.key_value_set(
                    f"{_HB_PREFIX}{rank()}/{seq}", repr(time.time()))
                if seq:
                    client.key_value_delete(
                        f"{_HB_PREFIX}{rank()}/{seq - 1}")
            except Exception:
                # coordinator gone: nothing to publish to — the main
                # thread's own collectives will surface the failure
                return
            # metrics digest rides the same clock at a slower multiple
            # (obs/clusterobs.py): ~kilobytes every DIGEST_EVERY_BEATS
            # beats against the heartbeat's bytes every beat. A digest
            # failure is NOT liveness-fatal: keep beating.
            if (seq % clusterobs.DIGEST_EVERY_BEATS == 0
                    and clusterobs.enabled()):
                try:
                    clusterobs.publish_digest(client, rank())
                except Exception:       # noqa: BLE001 — telemetry
                    pass                # must never kill the heartbeat
            seq += 1
            stop.wait(HEARTBEAT_S)

    t = threading.Thread(target=beat, name="lgbm-cluster-heartbeat",
                         daemon=True)
    with _lock:
        # check-then-act under the lock: two boosters initializing
        # concurrently (the retrain-while-serve pattern) must not
        # start TWO heartbeat threads racing on the same KV keys
        if _state["hb_thread"] is not None:
            return
        _state.update(hb_thread=t, hb_stop=stop)
    t.start()


def _hb_snapshot(client) -> Optional[Dict[int, int]]:
    """rank -> newest heartbeat SEQUENCE from the KV directory (the
    seq lives in the key, so no cross-host clock enters); None when
    the directory read itself failed."""
    try:
        entries = client.key_value_dir_get(_HB_PREFIX)
    except Exception:
        return None
    newest: Dict[int, int] = {}
    for key, _value in entries:
        m = re.search(r"hb/(\d+)/(\d+)", key)
        if not m:
            continue
        r = int(m.group(1))
        newest[r] = max(newest.get(r, -1), int(m.group(2)))
    return newest


def probe_dead_ranks(wait_s: Optional[float] = None) -> Optional[List[int]]:
    """Ranks (this one excluded) whose heartbeat sequence makes NO
    progress across a ``wait_s`` window (default ``_PROBE_WAIT_S``,
    2.5 publish intervals) — or that never published at all. Progress
    comparison is skew-immune: a healthy peer on a badly-NTP'd host
    still advances its sequence. None = the probe itself failed
    (coordinator unreachable — rank 0's process is the prime
    suspect)."""
    client = _client()
    if client is None:
        return []
    first = _hb_snapshot(client)
    if first is None:
        return None
    time.sleep(float(wait_s) if wait_s is not None else _PROBE_WAIT_S)
    second = _hb_snapshot(client)
    if second is None:
        return None
    return [r for r in range(world())
            if r != rank() and second.get(r, -1) <= first.get(r, -1)]


def _rank_list(ranks: List[int]) -> str:
    return ", ".join(f"rank {r}" for r in ranks) or "an unknown rank"


def explain_collective_error(exc: BaseException,
                             what: str = "collective") -> Optional[PeerLostError]:
    """Map a raw in-collective failure to a PeerLostError naming the
    dead rank(s), or None when ``exc`` does not look like a peer/DCN
    failure (a genuine bug must keep its own traceback)."""
    msg = str(exc)
    # barrier timeouts list BOTH "the first task at the barrier" (an
    # alive one) and the stragglers — only the section after "timed
    # out task names" may accuse anyone; other coordination errors
    # name the dead task inline, so the whole message is fair game
    scope = msg
    marker = "timed out task names"
    if marker in msg:
        scope = msg[msg.index(marker):]
    named = [int(r) for r in _TASK_RE.findall(scope)]
    peerish = named or any(s in msg for s in (
        "Connection reset", "Connection refused", "Socket closed",
        "Gloo", "gloo", "NCCL", "heartbeat timeout", "Heartbeat",
        "UNAVAILABLE", "DEADLINE_EXCEEDED", "coordination service",
        "Coordination service", "Barrier timed out"))
    if not peerish:
        return None
    suspects = sorted(set(named))
    if not suspects and _client() is not None:
        # attribute by heartbeat progress: the probe's two-snapshot
        # window (~2.5 publish intervals) is deterministic — a dead
        # peer's sequence cannot advance, however fast the socket
        # error beat its last heartbeat; a LIVE peer behind a
        # transient network blip keeps advancing and is never accused
        probed = probe_dead_ranks()
        if probed is None:
            return PeerLostError(
                f"{what} failed and the coordinator is unreachable — "
                f"rank 0 (coordinator {_state['coordinator'] or '?'}) "
                f"is likely dead; restart the cluster and resume from "
                f"the latest checkpoint (tpu_resume_from)", [0])
        suspects = probed
    return PeerLostError(
        f"{what} failed: {_rank_list(suspects)} of {world()} "
        f"unresponsive (peer died or was preempted); surviving ranks "
        f"should exit and resume from the latest checkpoint onto the "
        f"remaining hosts (tpu_resume_from; original error: "
        f"{msg.splitlines()[0][:200]})", suspects)


def barrier(name: str, timeout_s: Optional[float] = None) -> None:
    """Cross-process sync with a bounded deadline; a peer that never
    arrives raises PeerLostError naming it (the coordination service's
    straggler list) instead of blocking forever. No-op
    single-process."""
    client = _client()
    if client is None:
        return
    t = float(timeout_s if timeout_s is not None else deadline_s())
    try:
        client.wait_at_barrier(name, int(t * 1000))
    except Exception as e:  # noqa: BLE001 — classified below
        named = explain_collective_error(e, what=f"barrier {name!r}")
        if named is not None:
            raise named from e
        raise


# -- the stall watchdog (no-hang guarantee for blocking backends) ------------


def tick(label: str = "") -> None:
    """Progress stamp for DeadlineGuard — the training loop calls this
    at every iteration choke point (models/gbdt.py train_one_iter)."""
    _state["tick"] = (label, time.monotonic())


class DeadlineGuard:
    """Watchdog turning a silent collective hang into a fast, named
    death: while active, a daemon thread checks the time since the
    last ``tick``; a stall past ``deadline_s`` probes liveness — a
    DEAD peer (or unreachable coordinator) logs ONE actionable line
    naming the rank(s), dumps the flight recorder, and exits the
    process with EXIT_PEER_LOST; a stall with every peer's heartbeat
    still advancing only WARNS and keeps waiting (a slow compile must
    never read as a cluster death).

    ``on_stall`` (tests) replaces the exit with a callback; ``probe``
    (tests) replaces the KV liveness probe. The guard never fires
    single-process unless a probe override is injected."""

    def __init__(self, deadline: Optional[float] = None,
                 what: str = "training collective",
                 on_stall: Optional[Callable] = None,
                 probe: Optional[Callable] = None,
                 poll_s: float = 0.25):
        self.deadline = float(deadline if deadline is not None
                              else deadline_s())
        self.what = what
        self.on_stall = on_stall
        self.probe = probe
        self.poll_s = poll_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.fired = False

    def __enter__(self):
        if not is_multiprocess() and self.probe is None:
            return self
        tick("guard-start")
        self._thread = threading.Thread(
            target=self._watch, name="lgbm-deadline-guard", daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        return False

    def _watch(self):
        while not self._stop.wait(self.poll_s):
            last = _state.get("tick")
            if last is None:
                continue
            stalled = time.monotonic() - last[1]
            if stalled < self.deadline:
                continue
            probe = self.probe or probe_dead_ranks
            dead = probe()
            if dead == []:
                # EVERY peer's heartbeat is still advancing: nobody is
                # dead, this is a slow step (first-compile, a long
                # eval, a busy host) — killing a healthy cluster would
                # be the false positive this guard must never produce.
                # Say so, push the baseline forward, keep watching.
                log.warning(
                    "%s stalled for %.1fs at %s but every peer is "
                    "alive (heartbeats advancing) — waiting on (slow "
                    "compile/step?)", self.what, stalled,
                    last[0] or "start")
                tick(last[0])
                continue
            self.fired = True
            if dead is None:
                who = (f"the coordinator "
                       f"({_state['coordinator'] or 'rank 0'})")
                ranks = [0]
            else:
                who = _rank_list(dead)
                ranks = dead
            err = PeerLostError(
                f"{self.what} stalled for {stalled:.1f}s (deadline "
                f"{self.deadline:.1f}s) at {last[0] or 'start'}: {who} "
                f"unresponsive — exiting so the orchestrator can "
                f"resume from the latest checkpoint (tpu_resume_from)",
                ranks)
            log.warning("%s", err)
            if self.on_stall is not None:
                self.on_stall(err)
                return
            try:
                from ..obs import flight
                flight.trigger("peer_lost", {"what": self.what,
                                             "ranks": ranks,
                                             "stalled_s": round(stalled,
                                                                2)},
                               force=True)
            except Exception:
                pass
            os._exit(EXIT_PEER_LOST)


# -- SPMD placement/gather seams ---------------------------------------------


def spans_processes(mesh) -> bool:
    """True when ``mesh`` contains devices of more than one process —
    the signal that device_put placement must give way to the global
    constructors below."""
    if mesh is None or not is_multiprocess():
        return False
    procs = {getattr(d, "process_index", 0)
             for d in mesh.devices.flat}
    return len(procs) > 1


def host_to_global(x, mesh, *spec):
    """Host-global array -> global device array under
    NamedSharding(mesh, P(*spec)). EVERY process must pass the same
    value (the SPMD host-data discipline); each builds only its
    addressable shards."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = np.asarray(x)
    sh = NamedSharding(mesh, P(*spec))
    return jax.make_array_from_callback(x.shape, sh,
                                        lambda idx: x[idx])


def local_shards_to_global(shards, global_shape, mesh, *spec):
    """Per-local-device shards -> one global array (the multihost
    ingest assembly; wraps make_array_from_single_device_arrays)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P(*spec))
    return jax.make_array_from_single_device_arrays(
        tuple(global_shape), sh, list(shards))


# per-(mesh, ndim) jitted identity-with-replication programs: jax's
# jit cache keys on function identity, so a fresh lambda per fetch()
# would retrace + recompile the all-gather on EVERY checkpoint
_gather_jits: Dict = {}


def fetch(arr):
    """Global device array -> host numpy on EVERY rank. Replicated
    arrays read directly; sharded ones pay one all-gather jit (the
    checkpoint gather — utils/checkpoint.py save under a multi-process
    mesh; compiled once per (mesh, rank-count) and reused). Single-
    process/plain arrays fall through to np.asarray."""
    import numpy as np
    if not hasattr(arr, "is_fully_addressable"):
        return np.asarray(arr)
    if arr.is_fully_addressable or getattr(arr, "is_fully_replicated",
                                           False):
        return np.asarray(arr)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = arr.sharding.mesh
    key = (mesh, arr.ndim)
    fn = _gather_jits.get(key)
    if fn is None:
        rep = NamedSharding(mesh, P(*([None] * arr.ndim)))
        fn = jax.jit(lambda x: x, out_shardings=rep)
        _gather_jits[key] = fn
    return np.asarray(fn(arr))


def shutdown() -> None:
    """Orderly teardown (successful runs only: the shutdown barrier
    aborts the process if a peer already died — casualties exit via
    os._exit on the EXIT_PEER_LOST path instead)."""
    stop = _state.get("hb_stop")
    if stop is not None:
        stop.set()
    if _state["initialized"]:
        import jax
        try:
            jax.distributed.shutdown()
        except Exception as e:
            log.warning("jax.distributed.shutdown: %s", e)
        _state.update(initialized=False, world=1, rank=0,
                      hb_thread=None, hb_stop=None)
