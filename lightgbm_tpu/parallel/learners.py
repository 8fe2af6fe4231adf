"""Distributed tree learners over a JAX device mesh.

TPU-native counterparts of the reference's three parallel tree learners
(reference: src/treelearner/data_parallel_tree_learner.cpp,
feature_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp and
the socket/MPI collective layer they ride on, src/network/network.cpp).
Instead of hand-rolled Bruck/recursive-halving collectives over TCP, the
whole tree build runs as ONE ``shard_map`` program over a
``jax.sharding.Mesh`` and the three communication points lower onto XLA
collectives over ICI/DCN:

  reference                              here
  ---------------------------------     ------------------------------
  histogram ReduceScatter                ``lax.psum`` of the wave's
    (data_parallel_tree_learner.cpp:147)   [W, F, B, 3] histograms
  best-split AllReduce w/ max-gain       ``lax.all_gather`` of the
    reducer (parallel_tree_learner.h:183)  per-child SplitResult batch
                                           + per-child argmax
  top-k vote Allgather                   ``lax.psum`` of one-hot votes
    (voting_parallel_tree_learner.cpp:342) + elected-feature psum

All modes drive the round-2 wave grower (ops/wave_grower.py): a wave of
up to W leaves is split per step and ONE wave-histogram pass feeds every
mode's collective, so the communication volume per step is W leaves'
histograms instead of one — the same batching win as on-device compute.

Modes (tree_learner config, config.h tree_learner):
- data:    rows sharded across devices; wave histograms psummed; every
           device computes the same global best splits.
- feature: every device holds ALL rows (like the reference, where each
           worker has the full data, feature_parallel_tree_learner.cpp:31);
           each device builds wave histograms only for its own feature
           slice, finds local bests, and the global best per child is
           all_gather + argmax. No row movement at split time.
- voting:  data-parallel with PV-Tree communication compression: each
           device votes its local top-k features per child, the global
           top-2k by vote count are elected, and ONLY those features'
           histograms are summed (``psum`` of a [2W, 2k, B, 3] slice
           instead of the full [2W, F, B, 3]).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.hist_wave import wave_histogram
from ..ops.split import (FeatureMeta, SplitResult, best_gain_per_feature,
                         find_best_split)
from ..ops.wave_grower import WaveGrowerConfig, make_wave_grower

AXIS = "workers"

# Injectable collective overrides — the TPU-native analog of the
# reference's external-collective seam (src/network/network.cpp:41-54,
# LGBM_NetworkInitWithFunctions): tests and embedders can wrap or
# replace the histogram reduce-scatter and best-split allgather.
# An override is fn(value, default_collective) -> value and must be
# jax-traceable; it runs at trace time, once per collective site per
# compilation (collectives are compiled into the XLA program, so the
# seam observes/extends tracing rather than per-step execution).
_collective_overrides: dict = {}


def set_network_functions(reduce_scatter_fn=None,
                          allgather_fn=None) -> None:
    """Install (or with both None, clear) collective overrides."""
    _collective_overrides.clear()
    if reduce_scatter_fn is not None:
        _collective_overrides["reduce_scatter"] = reduce_scatter_fn
    if allgather_fn is not None:
        _collective_overrides["allgather"] = allgather_fn


def _psum_seam(x):
    """Histogram/scalar reduction through the injectable seam."""
    def base(v):
        return jax.lax.psum(v, AXIS)
    ov = _collective_overrides.get("reduce_scatter")
    return ov(x, base) if ov is not None else base(x)


# packed psum wire (config.tpu_psum_wire): the quantized histogram
# payload is integer-valued, so inside the 127*N wrap bound it crosses
# the collective in a narrow dtype — cast, psum, widen, all exact
_WIRE_DTYPES = {"int8": jnp.int8, "int16": jnp.int16,
                "int32": jnp.int32}


def _slot_psum(x, slots: int, psum=_psum_seam):
    """The overlap-structured histogram collective
    (config.tpu_async_psum): split a [W, F, B, C] payload along the
    feature axis into ``slots`` INDEPENDENT psums. psum is elementwise
    across shards, so the slot split is BIT-identical to the monolithic
    collective (for f32 and integer wires alike) — what it buys is
    scheduling freedom: XLA can launch slot 0's DCN reduction while
    slot 1's producer (and downstream per-slot consumers) still
    occupy the cores, instead of stalling the whole step on one fused
    collective. Payloads too small/low-rank to split fall back to the
    single psum."""
    slots = max(int(slots), 1)
    if slots == 1 or x.ndim < 2 or x.shape[1] < slots:
        return psum(x)
    F = x.shape[1]
    step = F // slots
    parts = []
    lo = 0
    for s in range(slots):
        hi = F if s == slots - 1 else lo + step
        parts.append(psum(jax.lax.slice_in_dim(x, lo, hi, axis=1)))
        lo = hi
    return jnp.concatenate(parts, axis=1)


def make_hist_reduce(cfg: WaveGrowerConfig):
    """The data-parallel wave-histogram collective, assembled from the
    config's wire + slot arms (both proven bit-identical to the plain
    ``psum`` — see _slot_psum and the tune_psum_wire bound,
    ops/autotune.py):

    - wire (quant_psum only): the deferred-dequant payload is
      integer-VALUED (int32 on the Pallas tier, integral f32 on the
      XLA oracle), so the narrowing cast to cfg.psum_wire, the integer
      psum and the widening cast back are all exact inside the 127*N
      bound;
    - slots: the feature axis splits into cfg.psum_slots independent
      collectives XLA can overlap with local compute.
    """
    wire = _WIRE_DTYPES.get(cfg.psum_wire, jnp.int32)
    narrow = bool(cfg.quant_psum) and cfg.psum_wire != "int32"
    slots = max(int(cfg.psum_slots), 1)

    def one(x):
        if narrow and x.dtype != wire:
            return _psum_seam(x.astype(wire)).astype(x.dtype)
        return _psum_seam(x)

    def hist_reduce(x):
        return _slot_psum(x, slots, psum=one)

    return hist_reduce


_meshes_logged: set = set()


def make_mesh(num_devices: Optional[int] = None) -> Mesh:
    from ..utils import log
    from ..utils.device import get_devices, get_global_devices
    if jax.process_count() > 1:
        # real multi-process cluster (parallel/cluster.py): the mesh
        # MUST span every process's devices — a psum over a subset
        # would leave the excluded ranks' programs waiting forever, so
        # per-caller device caps (num_machines) do not apply here
        devs = get_global_devices()
        if num_devices is not None and num_devices < len(devs):
            log.debug("multi-process mesh ignores the %d-device cap: "
                      "collectives must span all %d global devices",
                      num_devices, len(devs))
        n = len(devs)
    else:
        devs = get_devices()
        n = (len(devs) if num_devices is None
             else min(num_devices, len(devs)))
    kind = str(getattr(devs[0], "device_kind", None) or devs[0].platform)
    # one info line per distinct mesh per process (ingest + grower +
    # every CV fold all build the same mesh; size-1 meshes are about
    # to be discarded with a serial-fallback warning)
    emit = log.info if n > 1 and (n, kind) not in _meshes_logged \
        else log.debug
    _meshes_logged.add((n, kind))
    emit("mesh built: %d device(s) of kind %s on axis %r (%d process(es))",
         n, kind, AXIS, jax.process_count())
    return Mesh(np.asarray(devs[:n]), (AXIS,))


def training_mesh(config) -> Optional[Mesh]:
    """The >1-device mesh the configured tree learner trains over, or
    None (serial learner, or only one device available). ONE policy
    for every consumer — sharded ingest (io/ingest.py) must assemble
    bins under exactly the mesh the grower will shard_map over, or
    init pays the full-matrix reshard this path exists to avoid."""
    if getattr(config, "tree_learner", "serial") == "serial":
        return None
    want = (config.num_machines
            if getattr(config, "num_machines", 1) > 1 else None)
    mesh = make_mesh(want)
    if (want is not None and mesh.devices.size < want
            and jax.process_count() == 1):
        # fewer chips than the job was written for: it trains, on a
        # narrower mesh, and says so once (the counter every time)
        from ..obs import registry as obs
        from ..utils import log
        obs.counter("learner/mesh_short").add(1)
        if ("short", want, mesh.devices.size) not in _meshes_logged:
            _meshes_logged.add(("short", want, mesh.devices.size))
            log.warning("num_machines=%d but %d device(s) found: the "
                        "%s learner trains over %d", want,
                        mesh.devices.size, config.tree_learner,
                        mesh.devices.size)
    return mesh if mesh.devices.size > 1 else None


def sync_best_splits(res: SplitResult) -> SplitResult:
    """Cross-device argmax of per-device best-split batches — the analog
    of SyncUpGlobalBestSplit (parallel_tree_learner.h:183-207) over a
    whole wave of children at once."""
    def base(v):
        return jax.lax.all_gather(v, AXIS)
    ov = _collective_overrides.get("allgather")
    with jax.named_scope("lgbm/wave/split_sync"):
        gathered = (ov(res, base) if ov is not None
                    else base(res))               # pytree of [D, M, ...]
        best = jnp.argmax(gathered.gain, axis=0)  # [M]
        m = best.shape[0]
        return SplitResult(*[leaf[best, jnp.arange(m)]
                             for leaf in gathered])


def _slice_meta(meta: FeatureMeta, start, size: int) -> FeatureMeta:
    # scalar-sentinel fields (is_cat/bundle/offset defaults) pass through
    return FeatureMeta(*[
        a if jnp.ndim(a) == 0
        else jax.lax.dynamic_slice_in_dim(jnp.asarray(a), start, size, 0)
        for a in meta])


def _hist(cfg: WaveGrowerConfig):
    def hist_fn(bins_t, g, h, leaf_ids, wave_leaves, gh_scale=None):
        return wave_histogram(bins_t, g, h, leaf_ids, wave_leaves,
                              num_bins=cfg.num_bins, chunk=cfg.chunk,
                              use_pallas=cfg.use_pallas,
                              precision=cfg.precision,
                              gh_scale=gh_scale)
    return hist_fn


def make_data_parallel_grower(cfg: WaveGrowerConfig, meta: FeatureMeta,
                              mesh: Mesh, hist_fn=None):
    """Rows sharded over the mesh; wave histograms psummed.

    (DataParallelTreeLearner semantics; the reference reduce-scatters so
    each worker reduces a feature subset — with XLA the psum IS the
    reduce+broadcast and the compiler picks the wire algorithm.)

    The collective rides the ``hist_reduce_fn`` seam, NOT a hist_fn
    override, so the grower keeps its default seams and the FUSED
    partition+histogram Pallas kernel stays live per shard — on a real
    mesh each chip runs the same single-chip kernel on its rows and
    only the [W, F, B, 3] histograms cross ICI.

    The histogram collective itself is built by ``make_hist_reduce``
    from the config's packed-wire + slot arms (tpu_psum_wire /
    tpu_async_psum) — bit-identical to the plain psum by construction;
    scalar reductions (root aggregates) keep the plain seam.
    """
    def reduce_fn(x):
        return _psum_seam(x)

    hist_reduce_fn = make_hist_reduce(cfg)

    def max_reduce_fn(x):
        # global int8 quantization scales: every shard must quantize
        # with the same (sg, sh) or the count-proxy bounds computed on
        # the psummed histogram would be scale-inconsistent and
        # shard-divergent (and same-seed parity with serial improves)
        return jax.lax.pmax(x, AXIS)

    def row_offset_fn(n_local):
        # global row index base: shard d holds the contiguous rows
        # [d*n_local, (d+1)*n_local) of the padded global matrix, so
        # the stochastic-rounding hash draws the SAME uniform for the
        # same row as the single-chip grower (serial quantized parity)
        return jax.lax.axis_index(AXIS) * jnp.int32(n_local)

    # hist_fn (e.g. the EFB bundle-expansion seam) composes: each shard
    # histograms its own rows through it, then the expanded [W, F, B, 3]
    # rides the psum exactly like the default seam's output
    grow = make_wave_grower(cfg, meta, hist_fn=hist_fn,
                            hist_reduce_fn=hist_reduce_fn,
                            reduce_fn=reduce_fn,
                            max_reduce_fn=max_reduce_fn,
                            row_offset_fn=row_offset_fn, jit=False)
    # meta rides the shard_map as a REPLICATED argument (not a trace
    # constant) so the compiled-step registry (ops/step_cache.py) can
    # share one compiled program between boosters binned on different
    # data; legacy 5-arg callers get the factory meta passed for them
    meta_dev = FeatureMeta(*[jnp.asarray(a) for a in meta])
    meta_specs = FeatureMeta(*[P(*([None] * jnp.ndim(a)))
                               for a in meta_dev])
    sharded = jax.shard_map(
        grow, mesh=mesh,
        in_specs=(P(None, AXIS), P(AXIS), P(AXIS), P(AXIS), P(None),
                  meta_specs),
        out_specs=(P(), P(AXIS)),
        check_vma=False)
    # jit-capture: ok(sharded) — shard_map-wrapped grower: the grow
    # factory's own jit site carries the capture audit (meta rides as
    # a replicated ARGUMENT, PR 4), and this jit is factory-scoped.
    jitted = jax.jit(sharded)

    def call(bins_t, g, h, mask, fmask, meta=None):
        return jitted(bins_t, g, h, mask, fmask,
                      meta_dev if meta is None else meta)

    def lower(*args):
        # jit-object surface for introspection tests/tools: legacy
        # 5-arg callers get the factory meta appended, like call()
        return jitted.lower(*(args if len(args) == 6
                              else args + (meta_dev,)))
    call.lower = lower
    call.resolved = grow.resolved
    return call


def make_feature_parallel_grower(cfg: WaveGrowerConfig, meta: FeatureMeta,
                                 mesh: Mesh, num_features: int):
    """Every device holds all rows; feature slice per device for the
    histogram/split work (FeatureParallelTreeLearner semantics)."""
    D = mesh.devices.size
    if num_features % D != 0:
        raise ValueError("feature-parallel requires padded features")
    Fd = num_features // D
    local_hist = _hist(cfg)

    def hist_fn(bins_t, g, h, leaf_ids, wave_leaves, gh_scale=None):
        # int8 quantization composes: every device holds ALL rows, so
        # the (global-max) scales and the stochastic-rounding key are
        # identical on every device and the feature-sliced histograms
        # dequantize consistently
        i = jax.lax.axis_index(AXIS)
        local_bins = jax.lax.dynamic_slice_in_dim(bins_t, i * Fd, Fd, 0)
        return local_hist(local_bins, g, h, leaf_ids, wave_leaves,
                          gh_scale=gh_scale)

    def split_fn(hists, sg, sh, nd, fmask, can):
        i = jax.lax.axis_index(AXIS)
        meta_l = _slice_meta(meta, i * Fd, Fd)
        fmask_l = jax.lax.dynamic_slice_in_dim(fmask, i * Fd, Fd, 0)
        res = jax.vmap(
            lambda hh, a, b, c, d: find_best_split(
                hh, a, b, c, fmask_l, meta_l, cfg.hp, d)
        )(hists, sg, sh, nd, can)
        res = res._replace(
            feature=jnp.where(res.feature >= 0, res.feature + i * Fd, -1))
        return sync_best_splits(res)

    grow = make_wave_grower(cfg, meta, hist_fn=hist_fn, split_fn=split_fn,
                            jit=False)
    sharded = jax.shard_map(
        grow, mesh=mesh,
        in_specs=(P(None, None), P(None), P(None), P(None), P(None)),
        out_specs=(P(), P()),
        check_vma=False)
    # jit-capture: ok(sharded) — shard_map-wrapped grower: the grow
    # factory's own jit site carries the capture audit (meta rides as
    # a replicated ARGUMENT, PR 4), and this jit is factory-scoped.
    return jax.jit(sharded)


def make_feature_parallel_bundled_grower(cfg: WaveGrowerConfig,
                                         meta: FeatureMeta, mesh: Mesh,
                                         efb):
    """Feature-parallel over EFB BUNDLE columns: every device holds all
    rows and histograms only its slice of the bundle matrix, expands
    that slice to its members' [W, F, B, 3] columns (zeros elsewhere),
    finds its local best with the full-F split kernel (zero histograms
    can never win), and the global best is the usual
    all_gather + argmax. Closes the reference's
    FeatureParallelTreeLearner x EFB composition without requiring the
    bundle count to divide the device count (tail slices clamp and
    overlap; duplicated work, identical elections)."""
    from ..io.efb import expand_bundle_histogram
    D = mesh.devices.size
    mb, mo, nb_m, db_m, Bb, B_out, num_bundles = efb
    Bd = max(1, -(-num_bundles // D))
    mb = jnp.asarray(mb)
    mo = jnp.asarray(mo)
    nb_m = jnp.asarray(nb_m)
    db_m = jnp.asarray(db_m)
    meta_dev = FeatureMeta(*[jnp.asarray(a) for a in meta])

    def hist_fn(bins_t, g, h, leaf_ids, wave_leaves, gh_scale=None):
        i = jax.lax.axis_index(AXIS)
        start = jnp.minimum(i * Bd,
                            jnp.int32(max(num_bundles - Bd, 0)))
        local = jax.lax.dynamic_slice_in_dim(bins_t, start, Bd, 0)
        bh = wave_histogram(local, g, h, leaf_ids, wave_leaves,
                            num_bins=Bb, chunk=cfg.chunk,
                            use_pallas=cfg.use_pallas,
                            precision=cfg.precision, gh_scale=gh_scale)
        mb_loc = jnp.clip(mb - start, 0, Bd - 1)
        owned = (mb >= start) & (mb < start + Bd)
        full = expand_bundle_histogram(bh, mb_loc, mo, nb_m, db_m,
                                       B_out)
        return full * owned[None, :, None, None]

    def split_fn(hists, sg, sh, nd, fmask, can):
        res = jax.vmap(
            lambda hh, a, b, c, d: find_best_split(
                hh, a, b, c, fmask, meta_dev, cfg.hp, d)
        )(hists, sg, sh, nd, can)
        return sync_best_splits(res)

    grow = make_wave_grower(cfg, meta, hist_fn=hist_fn,
                            split_fn=split_fn, jit=False)
    sharded = jax.shard_map(
        grow, mesh=mesh,
        in_specs=(P(None, None), P(None), P(None), P(None), P(None)),
        out_specs=(P(), P()),
        check_vma=False)
    # jit-capture: ok(sharded) — shard_map-wrapped grower: the grow
    # factory's own jit site carries the capture audit (meta rides as
    # a replicated ARGUMENT, PR 4), and this jit is factory-scoped.
    return jax.jit(sharded)


def make_voting_parallel_grower(cfg: WaveGrowerConfig, meta: FeatureMeta,
                                mesh: Mesh, num_features: int,
                                top_k: int = 20, hist_fn=None):
    """Data-parallel with PV-Tree vote compression
    (VotingParallelTreeLearner, voting_parallel_tree_learner.cpp:166-360):
    per child, local top-k vote -> elect 2k global features -> psum only
    elected histograms."""
    D = mesh.devices.size
    k = max(1, min(top_k, num_features))
    k2 = min(2 * k, num_features)
    meta_dev = FeatureMeta(*[jnp.asarray(a) for a in meta])
    # local-vote gates and totals scaled to the per-device shard, like the
    # reference's local_config (voting_parallel_tree_learner.cpp:53-55)
    hp_vote = cfg.hp._replace(
        min_data_in_leaf=cfg.hp.min_data_in_leaf / D,
        min_sum_hessian_in_leaf=cfg.hp.min_sum_hessian_in_leaf / D)

    # LOCAL histograms — no psum; the election decides what is summed.
    # No hist_fn override: the default seams keep the fused
    # partition+histogram kernel live per shard (its output is exactly
    # the local wave histogram the election wants).
    def reduce_fn(x):
        return _psum_seam(x)

    def split_fn(hists, sg, sh, nd, fmask, can):
        # 1. local per-feature gains over the LOCAL histograms with the
        #    TRUE local leaf sumups (the reference votes with local
        #    smaller_leaf_splits_, voting_parallel_tree_learner.cpp:151-160)
        #    — every row lands in exactly one bin of feature 0, so the
        #    bin-sum of any one feature's local histogram IS the local
        #    leaf aggregate; gates stay num_machines-scaled (:53-55)
        sg_l = hists[:, 0, :, 0].sum(axis=-1)             # [M]
        sh_l = hists[:, 0, :, 1].sum(axis=-1)
        nd_l = hists[:, 0, :, 2].sum(axis=-1)
        local_gain = jax.vmap(
            lambda hh, a, b, c, d: best_gain_per_feature(
                hh, a, b, c, fmask, meta_dev, hp_vote, d)
        )(hists, sg_l, sh_l, nd_l, can)                   # [M, F]
        _, local_top = jax.lax.top_k(local_gain, k)       # [M, k]
        # 2. global vote: one-hot count of each device's top-k per child
        m = local_gain.shape[0]
        votes = jnp.zeros((m, num_features), jnp.float32)
        votes = votes.at[jnp.arange(m)[:, None], local_top].add(1.0)
        votes = _psum_seam(votes)
        # exact lexicographic (votes, summed-local-gain) election: rank
        # the gain sums 0..F-1 per child, then score = votes*F + rank —
        # deterministic, no saturating squash
        # gated features contribute 0 (not -inf: one device's gate must
        # not veto a feature other devices can still split)
        finite_gain = jnp.where(jnp.isfinite(local_gain), local_gain, 0.0)
        gain_sum = _psum_seam(finite_gain)
        order = jnp.argsort(gain_sum, axis=1)             # low -> high
        rank = jnp.zeros_like(order).at[
            jnp.arange(m)[:, None], order].set(
                jnp.arange(num_features, dtype=order.dtype)[None, :])
        score = votes * num_features + rank.astype(jnp.float32)
        _, elected = jax.lax.top_k(score, k2)             # [M, 2k]
        # 3. aggregate ONLY the elected features' histograms
        elected_hist = _psum_seam(
            jnp.take_along_axis(
                hists, elected[:, :, None, None], axis=1))
        meta_e = FeatureMeta(*[
            a if jnp.ndim(a) == 0 else a[elected]
            for a in meta_dev])                               # [M, 2k]
        # scalar-sentinel fields broadcast, per-slot fields map
        meta_axes = FeatureMeta(*[
            None if jnp.ndim(a) == 0 else 0 for a in meta_e])
        fmask_e = fmask[elected]
        res = jax.vmap(
            lambda hh, a, b, c, fm, me, d: find_best_split(
                hh, a, b, c, fm, me, cfg.hp, d),
            in_axes=(0, 0, 0, 0, 0, meta_axes, 0),
        )(elected_hist, sg, sh, nd, fmask_e, meta_e, can)
        return res._replace(
            feature=jnp.where(
                res.feature >= 0,
                jnp.take_along_axis(
                    elected, jnp.maximum(res.feature, 0)[:, None],
                    axis=1)[:, 0],
                -1))

    def row_offset_fn(n_local):
        # shard-invariant stochastic-rounding stream (see the
        # data-parallel learner)
        return jax.lax.axis_index(AXIS) * jnp.int32(n_local)

    grow = make_wave_grower(cfg, meta, hist_fn=hist_fn,
                            split_fn=split_fn,
                            reduce_fn=reduce_fn,
                            max_reduce_fn=lambda x: jax.lax.pmax(x, AXIS),
                            row_offset_fn=row_offset_fn, jit=False)
    sharded = jax.shard_map(
        grow, mesh=mesh,
        in_specs=(P(None, AXIS), P(AXIS), P(AXIS), P(AXIS), P(None)),
        out_specs=(P(), P(AXIS)),
        check_vma=False)
    # jit-capture: ok(sharded) — shard_map-wrapped grower: the grow
    # factory's own jit site carries the capture audit (meta rides as
    # a replicated ARGUMENT, PR 4), and this jit is factory-scoped.
    jitted = jax.jit(sharded)
    jitted.resolved = grow.resolved
    return jitted


def make_grower_for_mode(mode: str, cfg: WaveGrowerConfig,
                         meta: FeatureMeta, mesh: Optional[Mesh],
                         num_features: int, top_k: int = 20,
                         hist_fn=None, efb_feature=None):
    """Factory matching TreeLearner::CreateTreeLearner
    (src/treelearner/tree_learner.cpp:9-33) — {serial, feature, data,
    voting} on the tpu device type. ``hist_fn`` overrides the serial
    histogram seam (EFB bundle expansion, models/gbdt.py);
    ``efb_feature`` = (member_bundle, member_offset, num_bin,
    default_bin, B_bundle, B_out, num_bundles) routes feature-parallel
    over bundle columns instead."""
    if mode == "serial" or mesh is None or mesh.devices.size == 1:
        return make_wave_grower(cfg, meta, hist_fn=hist_fn)
    if mode == "data":
        return make_data_parallel_grower(cfg, meta, mesh, hist_fn=hist_fn)
    if mode == "feature":
        if efb_feature is not None:
            return make_feature_parallel_bundled_grower(
                cfg, meta, mesh, efb_feature)
        if hist_fn is not None:
            raise ValueError("feature-parallel does not compose with an "
                             "injected histogram seam (EFB bundles)")
        return make_feature_parallel_grower(cfg, meta, mesh, num_features)
    if mode == "voting":
        return make_voting_parallel_grower(cfg, meta, mesh, num_features,
                                           top_k, hist_fn=hist_fn)
    raise ValueError(f"Unknown tree_learner {mode!r}")
