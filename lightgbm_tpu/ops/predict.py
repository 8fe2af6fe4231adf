"""On-device prediction over binned data.

Counterpart of the reference's score updating and tree prediction
(reference: src/boosting/score_updater.hpp:17-123, src/io/tree.h:212-266).
Scores for train/valid sets are maintained entirely on device: a tree's
splits are replayed over the binned matrix (same order and leaf numbering
as growth, so the assignment is identical to the grower's partition), then
leaf outputs are gathered into the score vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .partition import apply_split, member_column
from .split import FeatureMeta


@jax.jit
def replay_partition(rec, bins_t, meta: FeatureMeta):
    """Assign each row of ``bins_t`` [F, N] (feature-major) to a leaf of
    the recorded tree by replaying its splits (Tree numbering: split i's
    right child = leaf i+1 — the wave grower's new-id assignment keeps
    this invariant, ops/wave_grower.py).
    """
    meta = FeatureMeta(*[jnp.asarray(x) for x in meta])
    n = bins_t.shape[1]
    num_splits = rec.split_leaf.shape[0]
    leaf_ids = jnp.zeros(n, jnp.int32)

    def body(i, leaf_ids):
        feat = rec.split_feature[i]
        enabled = rec.split_leaf[i] >= 0
        safe_feat = jnp.maximum(feat, 0)
        bin_col = member_column(bins_t, safe_feat, meta)
        return apply_split(
            leaf_ids, bin_col, rec.split_leaf[i], i + 1, rec.split_bin[i],
            rec.split_default_left[i], meta.missing_type[safe_feat],
            meta.default_bin[safe_feat], meta.num_bin[safe_feat],
            enabled=enabled, is_cat=rec.split_is_cat[i],
            cat_words=rec.split_cat_words[i])

    return jax.lax.fori_loop(0, num_splits, body, leaf_ids)


def _leaf_gather_kernel(tbl_ref, leaf_ref, out_ref, *, L):
    """out[r, c] = tbl[leaf[r, c]] (-0.0 for ids outside [0, L)).

    XLA lowers a [L]-table gather by 11M indices to a ~1.5 GB/s scalar
    loop (measured 7.7 ms per 1M rows — 14% of a whole boosting
    iteration); this kernel instead sweeps the table once with full-
    width VPU selects: L sequential compare+selects over an [8, C]
    tile, with the table in SMEM for scalar reads."""
    leaf = leaf_ref[...]                                # [8, C] i32
    def body(l, acc):
        return jnp.where(leaf == l, tbl_ref[0, l], acc)
    out_ref[...] = jax.lax.fori_loop(
        0, L, body, jnp.zeros_like(out_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def leaf_gather_pallas(table, leaf_ids, *, interpret=False):
    """table[leaf_ids] for a small table — TPU replacement for the slow
    XLA gather. leaf_ids outside [0, len(table)) yield 0.0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    L = table.shape[0]
    n = leaf_ids.shape[0]
    chunk = 16384                      # [8, chunk] f32 tiles in VMEM
    block = 8 * chunk
    pad = (-n) % block
    lv = jnp.pad(leaf_ids, (0, pad), constant_values=-1) \
        .reshape(8, -1)                # row-major [8, n_pad/8]
    tbl = table.astype(jnp.float32)[None, :]            # [1, L]
    out = pl.pallas_call(
        functools.partial(_leaf_gather_kernel, L=L),
        grid=(lv.shape[1] // chunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((8, chunk), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8, chunk), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(lv.shape, jnp.float32),
        name="leaf_gather_pallas",
        interpret=interpret,
    )(tbl, lv)
    return out.reshape(-1)[:n]


def leaf_gather(table, leaf_ids, mesh=None, row_sharded=False):
    """Dispatch: Pallas sweep on TPU, plain XLA gather elsewhere.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): inside a program that spans a device mesh — every
    booster training under a parallel tree learner — the sweep must run
    per shard. ``mesh`` is that mesh (None = a single-device program);
    ``row_sharded`` says whether ``leaf_ids`` is split over the mesh's
    row axis (data/voting learners) or replicated (feature learner).
    Row counts the mesh does not divide take the XLA gather — jax only
    shards evenly divisible axes (models/gbdt.py _place_scores)."""
    from ..utils.device import on_tpu
    if not (on_tpu() and table.shape[0] <= 4096
            and leaf_ids.shape[0] >= 8):
        return table[leaf_ids]
    if mesh is None:
        return leaf_gather_pallas(table, leaf_ids)
    if row_sharded and leaf_ids.shape[0] % mesh.devices.size:
        return table[leaf_ids]
    from jax.sharding import PartitionSpec as P
    rows = P(mesh.axis_names[0]) if row_sharded else P()
    return jax.shard_map(leaf_gather_pallas, mesh=mesh,
                         in_specs=(P(), rows), out_specs=rows,
                         check_vma=False)(table, leaf_ids)


@functools.partial(jax.jit, static_argnames=("mesh", "row_sharded"))
def add_leaf_outputs(scores, leaf_ids, leaf_output, shrinkage, mesh=None,
                     row_sharded=False):
    """score += shrinkage * leaf_output[leaf] (ScoreUpdater::AddScore).
    ``mesh``/``row_sharded``: see leaf_gather."""
    return scores + shrinkage * leaf_gather(leaf_output, leaf_ids, mesh,
                                            row_sharded)


def predict_trees_binned(records, bins_t, meta: FeatureMeta,
                         shrinkage_done=True):
    """Sum of leaf outputs over a list of TreeRecords for binned rows."""
    n = bins_t.shape[1]
    out = jnp.zeros(n, jnp.float32)
    for rec in records:
        leaf = replay_partition(rec, bins_t, meta)
        out = out + leaf_gather(rec.leaf_output, leaf)
    return out
