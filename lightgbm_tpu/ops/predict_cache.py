"""Process-wide geometry-keyed predict registry: the serving twin of
``ops/step_cache.py``.

Training got its cross-booster compiled-step registry in PR 5; this
module gives the PREDICT side the same treatment. The paper's workload
(lrb.py) retrains a fresh booster per sliding window and then *queries*
it on every request — inference latency, not training throughput, is
the million-users half of the north star. Before this module the
stacked predictor's dispatch was implicit: module-level ``jax.jit``
functions whose trace keys (array shapes + static offsets) happened to
collide across same-shaped models. That reuse was real but invisible
(no counters, no way to assert "the retrained window hit a warm
program") and fragile (any odd request batch size minted a fresh
trace).

Here the dispatch becomes a pure function of an explicit, hashable
**geometry key** — path kind (XLA scan / fused Pallas forest), the
32-bucketed per-feature table offsets (their sum is Wtot), padded
split/leaf axes, class count, tree-chunk and step counts, the row
bucket, the device kind — held in a bounded process-wide LRU:

- a retrained sliding-window model with the SAME geometry (same bucket
  widths — the 32-wide per-feature table buckets make this the common
  case) hits a warm entry: no re-trace, no recompile, and the hit is
  counted (``predict_cache/hits``);
- online micro-batches (1–4096 rows) pad to power-of-two **serve
  buckets** (``serve_bucket_rows``; floor 16, same pow2/16 taper as
  the training bucketer above 16k), so a live request stream touches a
  handful of compiled programs instead of one per distinct batch size.
  Padding is bit-exact: rows are independent in every predict kernel
  (per-row one-hot, per-row leaf match), pad rows are sliced off
  before the result leaves the device wrapper;
- forest (re)stacks are counted too (``predict_cache/stacks`` full
  host builds, ``predict_cache/extends`` incremental appends — see
  ``StackedModel.extend``), so "no full restack after retrain/continue"
  is assertable, not folklore.

Knobs (config.py): ``tpu_predict_cache`` (-1 auto = on / 0 off / 1 on)
and ``tpu_serve_bucket`` (-1 pow2 buckets / 0 exact shapes / N = round
up to a multiple of N). Counters land in the obs registry and are
exported by the PR-6 Prometheus exporter; ``stats()`` is snapshotted
into run reports and bench JSON (``meta.predict_cache``).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional

from ..obs import registry as obs
from ..obs import reqlog
from ..obs import trace

# bounded registry: one entry per distinct predict geometry; LRU evict
# keeps a model-shape sweep from pinning every dispatch wrapper forever
MAX_ENTRIES = 128

# smallest serve bucket: a 1-row online request pads to 16 rows of
# kernel work (noise) and every batch size 1..16 shares ONE compiled
# program; pow2 buckets above keep the program count logarithmic
SERVE_MIN_BUCKET = 16
# above this width, pow2/16 steps (8 buckets per octave) cap the pad
# at ~1/8 — same taper as step_cache.bucket_rows, serving-floor aside
_POW2_CAP = 1 << 14

_lock = threading.Lock()
_entries: "OrderedDict[tuple, Callable]" = OrderedDict()  # guarded-by: _lock
_mode = -1          # config.tpu_predict_cache  (-1 auto / 0 off / 1 on)
_bucket = -1        # config.tpu_serve_bucket   (-1 pow2 / 0 exact / N)


def configure(predict_cache: int = -1, serve_bucket: int = -1) -> None:
    """Install the config knobs (called from GBDT.init)."""
    global _mode, _bucket
    _mode = int(predict_cache)
    _bucket = int(serve_bucket)


def enabled() -> bool:
    """Registry bookkeeping active? (-1 auto = on. Off only disables
    the explicit registry + counters; jax's own trace cache still
    dedupes identical shapes.)"""
    return _mode != 0


def serve_bucket_rows(n: int, policy: Optional[int] = None) -> int:
    """Padded request-batch width for ``n`` rows under the serving
    bucket policy (``tpu_serve_bucket``; ``policy`` is the calling
    booster's own knob so one booster's config cannot re-shape another
    live booster's serving path).

    -1 (auto): next power of two >= max(n, SERVE_MIN_BUCKET) up to
    16384; above that pow2/16 steps (pad capped at ~1/8). Bit-exact by
    construction: predict kernels treat rows independently and the pad
    rows are sliced off on the way out.
    0: exact shapes (one trace per distinct batch size — the
    pre-registry behavior).
    N > 0: round up to a multiple of N.

    This is the serve-bucket seam of the request log: the chosen width
    is noted on the calling thread's active request context (free
    no-op otherwise), so the wide event a serving entry writes carries
    the bucket its batch dispatched at (obs/reqlog.py). Callers that
    clamp the answer (stacked_predict's row-chunk ceilings) re-note
    the clamped width — last note wins, and it is the truth."""
    b = _bucket_rows(int(n), policy)
    reqlog.note_bucket(b)
    return b


def _bucket_rows(n: int, policy: Optional[int]) -> int:
    p = (_bucket if policy is None else int(policy))
    if p == 0:
        return n
    if p > 0:
        return -(-n // p) * p
    b = max(n, SERVE_MIN_BUCKET)
    if b <= _POW2_CAP:
        return 1 << (b - 1).bit_length()
    return -(-b // (1 << ((b - 1).bit_length() - 4))) \
        * (1 << ((b - 1).bit_length() - 4))


def get(key: tuple, builder: Callable[[], Callable]) -> Callable:
    """Registry lookup: the process-wide predict dispatch for ``key``,
    building it on first encounter. A hit means a LATER model with the
    same geometry reuses the warm wrapper — and, because the key covers
    every static of the underlying jit, the warm compiled program."""
    if not enabled():
        return builder()
    with _lock:
        fn = _entries.get(key)
        if fn is not None:
            _entries.move_to_end(key)
            obs.counter("predict_cache/hits").add(1)
            trace.instant("predict_cache/hit", cat="cache")
            return fn
    obs.counter("predict_cache/misses").add(1)
    trace.instant("predict_cache/miss", cat="cache")
    fn = builder()
    with _lock:
        have = _entries.get(key)
        if have is not None:
            # lost race: functionally identical by key construction
            return have
        while len(_entries) >= MAX_ENTRIES:
            _entries.popitem(last=False)
            obs.counter("predict_cache/evictions").add(1)
        _entries[key] = fn
    return fn


def count_stack(trees: int) -> None:
    """Record one FULL host-side forest stack (StackedModel._build)."""
    obs.counter("predict_cache/stacks").add(1)
    obs.counter("predict_cache/stacked_trees").add(int(trees))
    trace.instant("predict_cache/stack", cat="cache")


def count_extend(trees: int) -> None:
    """Record one INCREMENTAL stack: only ``trees`` appended trees were
    tabled (StackedModel.extend) — the whole-ensemble rebuild the old
    ``_model_gen`` invalidation would have paid was skipped."""
    obs.counter("predict_cache/extends").add(1)
    obs.counter("predict_cache/stacked_trees").add(int(trees))
    trace.instant("predict_cache/extend", cat="cache")


def stats() -> Dict:
    """Snapshot for run reports / bench JSON (meta.predict_cache)."""
    with _lock:
        entries = len(_entries)
    return {
        "enabled": enabled(),
        "entries": entries,
        "hits": obs.counter("predict_cache/hits").value,
        "misses": obs.counter("predict_cache/misses").value,
        "evictions": obs.counter("predict_cache/evictions").value,
        "stacks": obs.counter("predict_cache/stacks").value,
        "extends": obs.counter("predict_cache/extends").value,
        "stacked_trees": obs.counter("predict_cache/stacked_trees").value,
    }


def clear() -> None:
    """Drop every cached dispatch (tests)."""
    with _lock:
        _entries.clear()
