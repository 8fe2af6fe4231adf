"""Process-wide compiled-step registry: cross-booster reuse of the
fused training step.

The paper's core workload (lrb.py) trains a FRESH booster per sliding
window, and before this module every ``GBDT`` instance re-traced and
re-compiled its fused iteration step from scratch — the tier-1 suite
was compile-bound and BENCH_r05 paid 18.8 s of compile+iter0 against
112 s of training. The fix is the standard JAX serving/training
pattern: make the step a pure function of an explicit, hashable
**geometry key** and cache the resulting ``jax.jit`` callable
process-wide.

What had to move out of the per-instance closures to get there:

- **Feature metadata** (per-feature bin counts / missing types / ...):
  traced argument threaded through the grower (ops/wave_grower.py
  ``grow(..., meta=...)``) instead of factory-time constants — two
  boosters binned on different data share one trace.
- **Objective data** (labels, weights, renew targets): the objectives
  expose a pure ``gradient_builder()`` closing only over config
  scalars; the row-aligned arrays ride an ``aux`` pytree argument
  (objectives/objective.py).
- **The row count**: rows pad up to a power-of-two bucket
  (``tpu_row_bucket``) with a validity-mask argument zeroing the pad
  rows' gradients — boosters with different N share one compiled step
  bit-exactly (the pad rows carry exact +0.0 g/h and a zero bagging
  mask, so histograms, root aggregates, the integer salt of the
  stochastic-rounding stream, and renew percentiles are untouched).
- **The bin and feature axes**: the histogram width is the max
  OBSERVED bin count and trivial columns are excluded from F, so both
  drift with the data; B pads to the next power of two
  (``bucket_bins``) and F to a multiple of 8 with trivial pad
  features — every sliding window of the paper workload shares one
  geometry instead of recompiling per window.

The registry key covers everything that shapes the trace (learner
mode, mesh device ids, WaveGrowerConfig incl. split hyperparameters,
forced splits and the resolved histogram ``route`` — pallas-tpu /
fused-xla / two-pass, so the same geometry on a different
backend compiles its own program and a checkpoint restored onto
another device kind re-resolves and re-keys instead of replaying a
foreign kernel choice — valid-set slice layout, bins dtype/shape,
objective static key, aux structure, renew spec, sample-hook statics),
so a hit is guaranteed to be a functionally identical program. Ineligible
configurations (EFB bundles, feature/voting learners, RF's averaging
step, legacy-PRNG GOSS under ``tpu_goss_hash=0`` — its in-jit sampler
draws a positional PRNG stream whose values depend on the padded
width, so bucket-padded it would not be bit-exact) simply keep the
legacy per-instance closure — correctness first, reuse where it is
sound. Hashed GOSS (the default) samples on the shard-invariant
lowbias32 hash of the global row index and rides the shared step as a
traced mask; lambdarank rides its query tables as ``_``-keyed aux
arrays — both production modes hit the registry on same-geometry
retrains.

Counters land in the obs registry (``step_cache/hits|misses|
evictions``; the ``step_cache/compile`` span: per-key first-dispatch
wall time, with jax's own compile events of that dispatch attributed
to it — timer ``step_cache/backend_compile``, counters
``step_cache/persistent_hits|persistent_misses``) and ``stats()`` is
snapshotted into run reports (``meta.step_cache``) and bench JSON.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional

from ..obs import registry as obs
from ..obs import scopes, trace
from ..utils import log, timing

# bounded registry: one entry per distinct training geometry; an LRU
# evict keeps pathological sweeps (e.g. a num_leaves grid search) from
# pinning every compiled executable forever
MAX_ENTRIES = 64

# smallest pow2 bucket the auto policy pads to: tiny test datasets
# share one step without ballooning (a 50-row set pads to 256 rows of
# zero-mask work — noise)
MIN_BUCKET = 256

_lock = threading.Lock()
_steps: "OrderedDict[tuple, Callable]" = OrderedDict()  # guarded-by: _lock
_mode = -1          # config.tpu_step_cache   (-1 auto / 0 off / 1 on)
_bucket = -1        # config.tpu_row_bucket   (-1 pow2 / 0 exact / N)


def configure(step_cache: int = -1, row_bucket: int = -1) -> None:
    """Install the config knobs (called from GBDT.init)."""
    global _mode, _bucket
    _mode = int(step_cache)
    _bucket = int(row_bucket)


def enabled() -> bool:
    """Cross-booster step reuse active? (-1 auto = on: the cache is a
    pure win on every backend — compiled steps are only shared between
    bit-identical programs.)"""
    return _mode != 0


def bucket_rows(n: int, align: int = 1, policy: Optional[int] = None) -> int:
    """Padded row-block width for ``n`` data rows under the bucketing
    policy, always a multiple of ``align`` (the learner's shard/chunk
    alignment unit). ``policy`` is the calling booster's own
    ``tpu_row_bucket`` — per-booster, so one booster's init cannot
    change another live booster's shape policy through the module
    globals (those remain only the default for config-less callers
    like the stacked predictor).

    -1 (auto): next power of two >= max(n, MIN_BUCKET) up to 16384;
    above that, pow2/16 steps — a pure pow2 pad could cost a single
    big-N booster up to 2x row work per iteration for a compile it
    amortizes only once, so the pad is capped at ~1/8 (still a
    log-bounded bucket count: 8 buckets per octave).
    0: exact shapes (only the alignment pad, the pre-cache behavior).
    N > 0: round up to a multiple of N. Note only tpu_row_bucket=0
    disables shape padding; tpu_step_cache=0 switches the TRAINING
    step back to per-booster closures but keeps predict-path
    bucketing (the pre-registry behavior).
    """
    align = max(int(align), 1)
    p = (_bucket if policy is None else int(policy))
    if p == 0:
        return _round_up(n, align)
    if p > 0:
        return _round_up(_round_up(n, p), align)
    return _round_up(pow2_bucket(n, MIN_BUCKET), align)


def shard_align_unit(n: int, D: int, kchunk: int) -> int:
    """Row-alignment unit of a D-device row-sharding learner
    (data/voting): shards chunk-align only when the data is large
    enough that the pad stays small (n >= 4*D*kchunk), else they
    align to the device count alone. The bucketed score width must be
    a multiple of this. ONE function for the grower's padding
    (models/gbdt.py _setup_grower) and the elastic-resume geometry
    (utils/checkpoint.py): resuming a checkpoint onto a DIFFERENT
    world size re-buckets the row block to the new world's unit —
    ``bucket_rows(n, shard_align_unit(n, D_new, kchunk), policy)`` IS
    the new shard width, and whether the transition is score-shape
    preserving is exactly whether old and new widths agree."""
    return D * kchunk if n >= 4 * D * kchunk else D


def pow2_bucket(x: int, floor: int) -> int:
    """THE shared shape-taper every bucketing discipline uses (score
    rows, sparse nnz planes, ingest entry planes): next power of two
    >= max(x, floor) up to 16384; above that, pow2/16 steps (8 buckets
    per octave) capping the pad at ~1/8."""
    b = max(int(x), int(floor))
    if b <= (1 << 14):
        return 1 << (b - 1).bit_length()
    return _round_up(b, 1 << ((b - 1).bit_length() - 4))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def bucket_bins(b: int, policy: Optional[int] = None) -> int:
    """Padded histogram bin-axis width for ``b`` actual global bins.

    The grower's B dimension is the max OBSERVED per-feature bin count,
    which drifts with the data (a 256-row window sample bins to 51
    distinct values, the next to 46) — without padding, every sliding
    window of the paper workload is a fresh geometry and the registry
    never hits. Padding to the next power of two (floor 16, so the
    4-bit packed tier's B<=16 bound is never crossed by padding alone)
    is sound because the split finder masks per-feature via the TRACED
    ``meta.num_bin`` (bins >= num_bin contribute zero and their
    candidates are -inf), and histogram scatters never touch columns
    no bin value reaches. tpu_row_bucket=0 (exact shapes) disables
    this too — the knob means "no shape padding anywhere". ``policy``
    is the calling booster's own tpu_row_bucket (see bucket_rows)."""
    p = (_bucket if policy is None else int(policy))
    if p == 0:
        return b
    return 1 << (max(b, 16) - 1).bit_length()


def bucket_entries(e: int, policy: Optional[int] = None) -> int:
    """Padded sparse-coordinate length (the geometry key's nnz bucket)
    for ``e`` explicit entries: the sliding-window workload's windows
    carry different nnz, and without bucketing every window's sparse
    planes would be a fresh trace shape. Same policy shape as
    ``bucket_rows``: -1 (auto) next power of two (floor 1024) with
    pow2/16 steps above 16k; 0 exact; N > 0 multiples of N. Pad
    entries carry an out-of-range feature index, which every scatter
    in the sparse histogram drops (ops/hist_wave.py)."""
    p = (_bucket if policy is None else int(policy))
    if p == 0:
        return max(int(e), 1)
    if p > 0:
        return _round_up(max(int(e), 1), p)
    return pow2_bucket(e, 1024)


def aux_signature(aux) -> tuple:
    """Hashable structure+shape+dtype fingerprint of an aux pytree
    (nested dicts and tuples of arrays / None) — part of the geometry key,
    so two boosters only share a step when their traced aux trees match."""
    if aux is None:
        return ("none",)
    if isinstance(aux, (tuple, list)):
        return tuple(aux_signature(a) for a in aux)
    if isinstance(aux, dict):
        return tuple((k, aux_signature(aux[k])) for k in sorted(aux))
    return (tuple(getattr(aux, "shape", ())),
            str(getattr(aux, "dtype", type(aux).__name__)))


def get_step(key: tuple, builder: Callable[[], Callable]) -> Callable:
    """Registry lookup: return the process-wide compiled step for
    ``key``, building (and instrumenting) it on first encounter."""
    with _lock:
        fn = _steps.get(key)
        if fn is not None:
            _steps.move_to_end(key)
            obs.counter("step_cache/hits").add(1)
            trace.instant("step_cache/hit", cat="cache")
            return fn
    obs.counter("step_cache/misses").add(1)
    trace.instant("step_cache/miss", cat="cache")
    fn = _instrument(builder(), key)
    with _lock:
        # lost race: another thread built it first — keep theirs
        # (functionally identical by key construction)
        have = _steps.get(key)
        if have is not None:
            return have
        while len(_steps) >= MAX_ENTRIES:
            _steps.popitem(last=False)
            obs.counter("step_cache/evictions").add(1)
        _steps[key] = fn
    return fn


COMPILE_SPAN = "step_cache/compile"
_listening = False
_fetch = threading.local()      # .pending: this thread's last compile
                                # request was served by the persistent cache


def _compile_span() -> Optional[trace.Span]:
    """The ``step_cache/compile`` span open on the calling thread (jit
    compiles on the thread that dispatches), else None."""
    sp = trace.current()
    while sp is not None and sp.name != COMPILE_SPAN:
        sp = sp.parent
    return sp


def _on_jax_event(event: str, **_kw) -> None:
    sp = _compile_span()
    if sp is None:
        return
    if event == "/jax/compilation_cache/cache_hits":
        obs.counter("step_cache/persistent_hits").add(1)
        sp.args["persistent_hits"] += 1
        _fetch.pending = True
    elif event == "/jax/compilation_cache/cache_misses":
        obs.counter("step_cache/persistent_misses").add(1)
        sp.args["persistent_misses"] += 1


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    if event != "/jax/core/compile/backend_compile_duration":
        return
    sp = _compile_span()
    if sp is None:
        return
    # jax brackets compile-or-fetch with this event; a fetch from the
    # persistent cache (its hit event comes first) compiled nothing
    if getattr(_fetch, "pending", False):
        _fetch.pending = False
        secs = 0.0
    obs.timer("step_cache/backend_compile").add(secs)
    sp.args["backend_compile_s"] = round(
        sp.args["backend_compile_s"] + secs, 3)


def _listen() -> None:
    """Register the two jax.monitoring listeners once. They are
    process-wide by jax's design, so each attributes an event only to
    a ``step_cache/compile`` span open on ITS thread: compiles of other
    programs in the process (a caller's reference, the autotuner, a
    predictor) are never counted."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax
    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _instrument(fn: Callable, key: tuple) -> Callable:
    """Span the first dispatch of a cached step — jit compiles
    synchronously on first call while the result stays async, so the
    span is trace + lower + compile (or its fetch from the persistent
    cache) to within dispatch noise. The span's arguments say which
    step it was (a digest of the geometry key and the key itself) and
    what the backend did for it."""
    state = {"first": True}

    def call(*args):
        if state["first"]:
            state["first"] = False
            _listen()
            geometry = repr(key)
            span_args = {
                "geometry": hashlib.sha1(geometry.encode()).hexdigest()[:12],
                "key": geometry, "backend_compile_s": 0.0,
                "persistent_hits": 0, "persistent_misses": 0}
            # the abstract signature alone, for obs.op_scopes() to lower
            # on its first ask (never a buffer: two arguments are donated)
            scopes.watch(scopes.STEP_LABEL, fn, args)
            try:
                with trace.span(COMPILE_SPAN, cat="cache",
                                args=span_args) as sp:
                    out = fn(*args)
            finally:
                # a hit event that no duration event followed must not
                # zero this thread's next real compile
                _fetch.pending = False
            timing.mark_mem_peak(COMPILE_SPAN)
            log.debug("step cache: first dispatch of step %s took %.2fs "
                      "(backend compile %.2fs, persistent cache %d hit / "
                      "%d miss)", span_args["geometry"], sp.seconds,
                      span_args["backend_compile_s"],
                      span_args["persistent_hits"],
                      span_args["persistent_misses"])
            return out
        return fn(*args)

    # jit-object surface for introspection (GBDT.lower_step)
    call.lower = getattr(fn, "lower", None)
    return call


def stats() -> Dict:
    """Snapshot for run reports / bench JSON (meta.step_cache)."""
    t = obs.timer("step_cache/compile")
    with _lock:
        entries = len(_steps)
    return {
        "enabled": enabled(),
        "entries": entries,
        "hits": obs.counter("step_cache/hits").value,
        "misses": obs.counter("step_cache/misses").value,
        "evictions": obs.counter("step_cache/evictions").value,
        "compile_s": round(t.total, 3),
        "compiles": t.count,
    }


def clear() -> None:
    """Drop every cached step (tests; frees the jit executables)."""
    with _lock:
        _steps.clear()


# ---------------------------------------------------------------------------
# The shared fused-step builder
# ---------------------------------------------------------------------------

def build_train_step(*, grower, K: int, n_score: int, n_total: int,
                     valid_slices: tuple, num_leaves: int,
                     grad_fn: Optional[Callable],
                     renew_alpha: Optional[float],
                     sample_hook: Optional[Callable],
                     mesh=None, row_sharded: bool = False) -> Callable:
    """ONE jitted function for a full boosting iteration — the SINGLE
    step implementation (gradient -> K tree builds -> renew ->
    shrinkage -> score updates -> AddBias on the stored record) behind
    BOTH routing modes:

    - **registry path** (GBDT._get_cached_step): pure in its geometry —
      every data-dependent array (bins, scores, masks, labels via
      ``aux``, feature metadata via ``meta``, the row-validity mask
      ``rvalid``) is a traced argument, so the compiled program is
      shared by every booster with the same geometry key;
    - **legacy per-booster closure** (GBDT._get_step_fn for
      cache-ineligible configurations — GOSS's legacy positional
      sampler (tpu_goss_hash=0), EFB bundles, feature/voting
      learners, tpu_step_cache=0): the
      caller passes ``rvalid=None`` (exact row shapes, no validity
      mask) and ``meta=None`` (the grower consumes its own closure
      metadata), and the jitted step stays per-instance.

    One body, two callers: the stepcache parity suite
    (tests/test_step_cache.py) locks them together by construction
    instead of by a 60-line mirror.

    ``mesh``/``row_sharded``: the device mesh a parallel tree learner
    trains over, and whether iteration state is row-sharded on it —
    the score updates' leaf-gather kernel must then run per shard
    (ops/predict.py leaf_gather); None for the serial learner.
    """
    import jax
    import jax.numpy as jnp

    from .predict import add_leaf_outputs

    pad_tail = n_total - n_score
    renew = renew_alpha is not None and grad_fn is not None
    if renew:
        from .renew import renew_leaf_outputs

    def step(bins, scores, valid_scores, mask, fmask, shrink,
             init_bias, g_in, h_in, key, rvalid, meta, aux):
        with jax.named_scope("lgbm/gradients"):
            if grad_fn is None:
                g_all, h_all = g_in, h_in
            else:
                g_all, h_all = grad_fn(scores if K > 1 else scores[0],
                                       aux["obj"])
                if K == 1:
                    g_all, h_all = g_all[None, :], h_all[None, :]
            if rvalid is not None:
                # pad rows: exact +0.0 g/h (a multiply by the zero mask
                # would produce -0.0 for negative gradients, perturbing the
                # integer bit-sum salt of the quantized stochastic-rounding
                # stream)
                g_all = jnp.where(rvalid[None, :], g_all, 0.0)
                h_all = jnp.where(rvalid[None, :], h_all, 0.0)
            if sample_hook is not None:
                # in-jit gradient-based sampling (GOSS): may amplify g/h
                # and shrink the bagging mask, all device-side. The hook
                # receives rvalid (None on the legacy route) so the hashed
                # sampler derives the REAL row count from the traced
                # validity mask instead of a closure int — the registry
                # path stays pure in its geometry.
                g_all, h_all, mask = sample_hook(g_all, h_all, mask, key,
                                                 rvalid)
        recs = []
        vs = list(valid_scores)
        for k in range(K):
            g_k, h_k = g_all[k], h_all[k]
            if pad_tail:
                z = jnp.zeros(pad_tail, jnp.float32)
                g_k = jnp.concatenate([g_k, z])
                h_k = jnp.concatenate([h_k, z])
            if meta is None:
                rec, leaf_full = grower(bins, g_k, h_k, mask, fmask)
            else:
                rec, leaf_full = grower(bins, g_k, h_k, mask, fmask,
                                        meta)
            leaf_ids = leaf_full[:n_score]
            with jax.named_scope("lgbm/leaf_values"):
                if renew:
                    # objective-driven leaf refit
                    # (serial_tree_learner.cpp:780-818) against the
                    # PRE-update scores; splitless trees stay all-zero (the
                    # reference never renews a tree it is about to discard,
                    # gbdt.cpp:393-409); bucket-pad rows carry zero weight
                    # through ``mask`` and cannot shift the percentiles
                    residual = aux["renew"]["label"] - scores[k]
                    new_out = renew_leaf_outputs(
                        leaf_ids, residual, aux["renew"].get("w"),
                        num_leaves, renew_alpha, rec.leaf_output,
                        mask[:n_score])
                    new_out = jnp.where(rec.num_leaves > 1, new_out,
                                        rec.leaf_output)
                    rec = rec._replace(leaf_output=new_out)
                # fold shrinkage (Tree::Shrinkage, gbdt.cpp:371).
                # NOTE for resume/replay authors: XLA freely re-fuses this
                # fold into the score gather-add (contraction skips the
                # intermediate rounding), so the live score state is NOT
                # reproducible by replaying the saved leaf outputs —
                # checkpoint resume (utils/checkpoint.py) therefore saves
                # the score buffers themselves instead of replaying trees.
                rec = rec._replace(
                    leaf_output=rec.leaf_output * shrink,
                    internal_value=rec.internal_value * shrink)
            # out-of-bag rows included: the partition covers ALL rows
            with jax.named_scope("lgbm/score_update"):
                scores = scores.at[k].set(add_leaf_outputs(
                    scores[k], leaf_ids, rec.leaf_output, 1.0,
                    mesh=mesh, row_sharded=row_sharded))
            with jax.named_scope("lgbm/valid_scores"):
                for vi, (voff, vn) in enumerate(valid_slices):
                    vleaf = leaf_full[voff:voff + vn]
                    vs[vi] = vs[vi].at[k].set(add_leaf_outputs(
                        vs[vi][k], vleaf, rec.leaf_output, 1.0,
                        mesh=mesh, row_sharded=row_sharded))
            # AddBias on the STORED record only (tree.h:151): the init
            # score already reached train/valid scores through
            # BoostFromAverage's AddScore, so the score updates above
            # use the un-biased outputs. For a splitless first tree
            # this also yields the reference's constant tree
            # (leaf0 = init, gbdt.cpp:378-396); biasing unused leaf
            # slots is harmless (leaf_ids never reference them).
            with jax.named_scope("lgbm/leaf_values"):
                rec = rec._replace(
                    leaf_output=rec.leaf_output + init_bias[k],
                    internal_value=rec.internal_value + init_bias[k])
            recs.append(rec)
        return scores, tuple(vs), recs

    # jit-capture: ok(grower, grad_fn, sample_hook, mesh) — the three
    # callable seams and the training mesh (static: it only selects
    # the leaf gather's shard_map). Registry-path callers pass
    # callables that close only over config scalars/statics, all
    # covered by the geometry key (obj.static_key(), _grower_cfg,
    # learner mode, mesh device ids); legacy callers jit per booster,
    # so a capture is that booster's own.
    return jax.jit(step, donate_argnums=(1, 2))
