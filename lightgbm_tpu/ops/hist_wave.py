"""Wave histogram construction: W leaves' histograms in one data pass.

TPU-native replacement for the reference's per-leaf histogram kernels
(reference: src/io/dense_bin.hpp:72-130 CPU loops,
src/treelearner/ocl/histogram256.cl:345 OpenCL device kernels). Two key
departures from round 1's per-leaf one-hot einsum, a third for the
root and a fourth for the wave passes' compacted rows:

1. **Wave batching.** The MXU matmul that accumulates histograms has
   128 output lanes but a single leaf only needs 3 channels
   (grad, hess, count). Filling the idle lanes with OTHER leaves'
   channels makes one full-data pass produce histograms for up to
   ``W = 128 // 3 = 42`` leaves at the price of one — the per-wave
   analog of the OpenCL kernel's one-workgroup-per-feature-group
   batching.

2. **No materialized one-hot.** Round 1's ``jax.nn.one_hot`` einsum
   wrote a [N, F, B] float tensor through HBM (7 GB per pass at the
   HIGGS size — the measured 5.5 ms/pass was pure HBM traffic). The
   Pallas kernel builds the one-hot tiles in VMEM and feeds the MXU
   directly.

3. **The root pays for one leaf.** The one pass of a tree that serves
   a single leaf has no other leaves to fill lanes with; its kernel
   (``root_histogram_pallas``) splits the bin axis into two digits and
   contracts a feature's hi one-hot against its lo-selected weight
   rows: a sixth of the one-hot dot's MACs at 255 bins, the same sums
   bit for bit.

4. **A wave pass's dotted rows pay for the slots beside them.** Of
   the 120 lanes a compacted row meets in the wave's one-hot dot, the 5
   of its own slot carry its weights. No dense dot over rows of mixed
   slots does better (it spends its whole output, W x Bp x channels,
   on every row), so the fused kernel puts its staged rows in slot
   order and takes the root's split to each 128-row block against the
   slots it holds (``_flush_by_slot``): 5,120 to 12,480 MACs a row a
   feature against 32,768, the same products in another order.

Data layout is **feature-major**: ``bins_t [F, N]`` so that a feature's
bin row is a contiguous lane vector — the transposed one-hot tile
``[group*B, Ct]`` is then built by broadcast compares with no VMEM
relayout, and the accumulating matmul ``oh_t @ w`` is in canonical
[M, K] x [K, N] form for the MXU.

Output layout: ``[W, F, B, 3]`` with channel 0=sum_grad, 1=sum_hess,
2=count, matching round 1's per-leaf ``[F, B, 3]``.

The XLA implementation is the fallback (CPU tests, any-backend
correctness oracle); the Pallas kernel is used on TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def take_rows(x, idx):
    """``x[idx]`` for a short static index vector along the leading axis,
    as one row slice a slot.

    XLA's gather expander lowers a gather of a few rows out of a large
    operand (the wide cells' histogram pool and bins matrix: hundreds of
    MB and up) as slices of the WHOLE operand, each read and written
    once a call, before it gathers out of the copies; a row slice reads
    its row and nothing else. The same semantics as ``x[idx]``: a
    negative index wraps once (-1 is the last row), one out of range
    clamps."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(x, idx[k], 0,
                                                   keepdims=False)
                      for k in range(idx.shape[0])])


def _feature_row(get_row, f: int, cache: dict, packed4: bool):
    """Logical feature ``f``'s bin row as i32 lanes (shared by the wave
    and fused kernels). ``get_row(r)`` reads stored bin row ``r`` as
    i32 lanes. 4-bit tier: two features per byte row (feature 2p in
    the low nibble of row p); each byte row is widened once per kernel
    invocation via ``cache``."""
    if not packed4:
        return get_row(f)
    pr = f // 2
    if pr not in cache:
        cache[pr] = get_row(pr)
    r = cache[pr]
    return (jax.lax.shift_right_logical(r, 4) if f % 2
            else jnp.bitwise_and(r, 15))


def _bf16_split(x):
    """Split f32 into (hi, lo) with hi exactly bf16-representable and
    hi + lo == x exactly. Bit-truncation of the low 16 mantissa bits —
    NOT astype(bf16).astype(f32) (XLA's simplifier elides that convert
    round-trip as identity, silently zeroing lo) and NOT
    lax.reduce_precision (unimplemented in Pallas TPU lowering).
    Truncation instead of round-to-nearest is fine: the decomposition
    only needs hi to be exact under the MXU's bf16 input rounding."""
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    hi = jax.lax.bitcast_convert_type(xi & jnp.int32(-65536), jnp.float32)
    return hi, x - hi


def _tile_grid(n_tiles: int, n_chunks: int):
    """(grid, at) of a histogram kernel: ``at(fn)`` turns an index map
    over (feature tile, row chunk) into the grid's. One tile keeps the
    1-D grid over row chunks the kernels had before the tile axis, so
    whatever fits one resident block lowers as it always did; more
    walk (tiles, chunks), tiles outermost: a tile's accumulator stays
    in VMEM while every row chunk streams past it."""
    if n_tiles == 1:
        return (n_chunks,), lambda fn: (lambda i: fn(0, i))
    return (n_tiles, n_chunks), lambda fn: fn


def _chunk_padded_rows(bins_t, g, h, leaf_ids, chunk):
    """(bins_t, ghl) of a partition-free histogram kernel: the rows
    padded to a whole number of chunks (pad rows in leaf -1, which no
    slot counts) and the [4, N] f32 rows (grad, hess, leaf id, 0)."""
    pad = (-bins_t.shape[1]) % chunk
    if pad:
        bins_t = jnp.pad(bins_t, ((0, 0), (0, pad)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        leaf_ids = jnp.pad(leaf_ids, (0, pad), constant_values=-1)
    return bins_t, jnp.stack([
        g.astype(jnp.float32), h.astype(jnp.float32),
        leaf_ids.astype(jnp.float32), jnp.zeros_like(g, jnp.float32)],
        axis=0)


# ---------------------------------------------------------------------------
# XLA reference implementation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_bins", "chunk",
                                             "precision"))
def wave_histogram_xla(bins_t, g, h, leaf_ids, wave_leaves, *, num_bins,
                       chunk=0, precision="highest"):
    """[W, F, B, 3] histograms of the rows of each wave leaf.

    Scatter-add formulation: each (row, feature) contributes its
    (g, h, 1) to flat index ``slot*F*B + f*B + bin``. This is the
    CPU/any-backend correctness oracle — XLA lowers the scatter to a
    sequential loop, which is fast on CPU and exactly associative; the
    MXU one-hot design lives in the Pallas kernel below. (The previous
    oracle materialized the [F, N, B] one-hot through memory — hundreds
    of MB per pass.) ``chunk``/``precision`` are accepted for interface
    parity with the Pallas path; the scatter needs neither.

    Args:
      bins_t:      [F, N] integer bin matrix, feature-major (uint8/int32).
      g, h:        [N] f32 gradient/hessian (bagging mask already folded:
                   masked-out rows carry g = h = 0 and count rides on
                   leaf membership, so set their leaf_ids to -1).
      leaf_ids:    [N] int32 current leaf assignment (-1 = out of bag).
      wave_leaves: [W] int32 leaf ids whose histograms are wanted
                   (-1 slots produce a zero histogram).
    """
    F, n = bins_t.shape
    W = wave_leaves.shape[0]
    B = num_bins
    eq = (leaf_ids[None, :] == wave_leaves[:, None]) \
        & (wave_leaves >= 0)[:, None]                     # [W, N]
    found = eq.any(axis=0)
    slot = jnp.argmax(eq, axis=0).astype(jnp.int32)       # [N]
    base = jnp.where(found, slot * (F * B), W * F * B)    # OOB -> dropped
    return _scatter_hist3(bins_t, g, h, base, num_bins=B, num_slots=W)


def _scatter_hist3(bins_t, g, h, base, *, num_bins, num_slots):
    """ONE combined scatter-add of all three channels: per (row,
    feature) the flat target is ``base_row + f*B + bin`` and the
    update is the 3-vector (g, h, 1). One pass over the F*N indices
    instead of three — measured 1.5x on the CPU backend at the bench
    shape — and BIT-identical to three per-channel scatters (each
    target's per-channel add sequence is the same row order either
    way). ``base`` carries each row's wave-slot offset, with
    out-of-wave rows at the OOB-high sentinel ``num_slots*F*B`` that
    ``mode="drop"`` discards (negative sentinels would wrap
    python-style)."""
    F, n = bins_t.shape
    B = num_bins
    size = num_slots * F * B
    flat = (base[None, :] + jnp.arange(F, dtype=jnp.int32)[:, None] * B
            + bins_t.astype(jnp.int32)).ravel()           # [F*N]
    vals = jnp.stack([
        jnp.broadcast_to(g.astype(jnp.float32), (F, n)),
        jnp.broadcast_to(h.astype(jnp.float32), (F, n)),
        jnp.broadcast_to(jnp.ones((), jnp.float32), (F, n))],
        axis=-1).reshape(-1, 3)                           # [F*N, 3]
    hist = jnp.zeros((size, 3), jnp.float32).at[flat].add(
        vals, mode="drop")
    return hist.reshape(num_slots, F, B, 3)


# ---------------------------------------------------------------------------
# Fused partition + wave histogram, XLA formulation (the off-TPU hot path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_bins", "count_proxy",
                                             "dequant"))
def fused_partition_histogram_xla(bins_t, g, h, sample_mask, leaf_ids,
                                  wl, new_ids, feat, tbin, dleft,
                                  iscat, catw, small_ids, miss, defb,
                                  nb, *, num_bins, count_proxy=False,
                                  gh_scale=None, dequant=True):
    """Partition one wave + build its smaller-child histograms in one
    traced region — the XLA twin of ``fused_partition_histogram_pallas``
    for backends without the Pallas kernels (the exact tier's off-TPU
    hot path).

    What fusing buys over [apply_wave_splits -> wave_histogram_xla]:
    the leaf-membership compare ``eq`` [W, N] is computed ONCE and
    reused for (a) the partition's move mask and (b) the smaller-child
    histogram membership (the unfused pipeline re-derives membership
    from the POST-split leaf ids — a second [W, N] compare sweep plus
    an argmax), and the three histogram channels ride one combined
    scatter (``_scatter_hist3``). BIT-identical to the unfused
    pipeline: the partition applies the same ``row_goes_right``
    decisions (rows match at most one slot, so the vectorized
    destination sum equals the sequential select chain) and the
    scatter consumes the identical flat-index sequence the oracle
    builds from the post-split leaf ids.

    Per-slot split parameters ride as [W] vectors (the Pallas kernel's
    packed table, unpacked): ``wl``/``new_ids``/``small_ids`` are the
    wave's parent/right-child/smaller-child leaf ids (-1 = inactive
    slot), ``miss``/``defb``/``nb`` the split features' missing-type /
    default-bin / bin-count metadata. g/h must be pre-masked by
    ``sample_mask``; out-of-bag rows partition but never count.

    With ``count_proxy`` also returns each slot's EXACT in-bag
    moved-row count (the right-child count, from the partition mask —
    the same synthesis the Pallas fused kernel does). ``gh_scale`` +
    ``dequant`` mirror the dispatcher's quantized-tier handling: the
    scatter is exact on integer-valued f32, and dequantization (or the
    deferred quant-psum wire) happens on the way out.
    """
    from .partition import row_goes_right

    F, n = bins_t.shape
    B = num_bins
    W = wl.shape[0]
    i32 = jnp.int32
    active = wl >= 0
    safe_feat = jnp.maximum(feat, 0)
    cols = bins_t[safe_feat].astype(i32)                  # [W, N]
    right = jax.vmap(
        lambda c, tb, dl, ms, db, nbk, ic, cw: row_goes_right(
            c, tb, dl, ms, db, nbk, is_cat=ic, cat_words=cw)
    )(cols, tbin, dleft, miss, defb, nb, iscat, catw)     # [W, N]
    eq = (leaf_ids[None, :] == wl[:, None]) & active[:, None]
    moved = eq & right
    # destination via (new_id + 1): rows match at most one slot (wave
    # leaves are distinct), so the masked sum IS the select chain
    dest1 = jnp.sum(jnp.where(moved, new_ids[:, None] + 1, 0), axis=0)
    leaf_new = jnp.where(dest1 > 0, dest1 - 1, leaf_ids).astype(i32)

    # smaller-child membership from the ALREADY-COMPUTED masks: row r
    # lands in slot k's smaller child iff it was in parent k and its
    # move direction matches the smaller side — no post-split compare
    in_bag = sample_mask > 0
    small_right = small_ids == new_ids                    # [W]
    memb = (eq & (moved == small_right[:, None])
            & (small_ids >= 0)[:, None] & in_bag[None, :])
    found = memb.any(axis=0)
    slot = jnp.argmax(memb, axis=0).astype(i32)
    base = jnp.where(found, slot * (F * B), W * F * B)
    hist = _scatter_hist3(bins_t, g, h, base, num_bins=B, num_slots=W)
    if gh_scale is not None and dequant:
        hist = hist * _qscale_vec(gh_scale)
    if not count_proxy:
        return leaf_new, hist
    cnt_r = jnp.sum((moved & in_bag[None, :]).astype(jnp.float32),
                    axis=1)
    return leaf_new, hist, cnt_r


# ---------------------------------------------------------------------------
# Sparse histogram tier (CSR-native datasets, io/sparse.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_bins",
                                             "num_features"))
def wave_histogram_sparse(sp, g, h, leaf_ids, wave_leaves, *, num_bins,
                          num_features, gh_scale=None):
    """[W, F, B, 3] wave histograms by scatter over the nnz explicit
    entries — the O(nnz) tier for CSR-native datasets.

    ``sp`` = (codes, feat, row, zero_bins): per-entry bin code, INNER
    feature index and global row of every explicit entry (device
    planes from io/ingest.py SparseDeviceBinner or host coords from
    io/dataset.py), plus the per-feature bin of the implicit value 0.0.
    Sentinel (pad) entries carry ``feat >= F`` and are dropped.

    Three scatter families per channel instead of the dense one-hot
    pass over N x F:

    - explicit entries add their row's (g, h, 1) at
      ``slot*F*B + feat*B + code``  — O(nnz);
    - per-(slot, feature) explicit subtotals at ``slot*F + feat`` and
      per-slot row totals (O(nnz + N)) complete the DEFAULT bin:
      ``hist[w, f, zero_bin_f] += leaf_total_w - explicit_subtotal_wf``
      (the implicit cells of feature f in leaf w are exactly the
      leaf's rows minus its explicit entries — the EFB module uses the
      same most-frequent-bin complement, io/efb.py).

    Exactness: with integer-valued g/h (tpu_quantized_hist) and counts,
    every sum is exact, so the result is BIT-equal to the dense
    ``wave_histogram_xla`` — order-free integers make the completion
    subtraction exact. With raw f32 gradients the completion
    reassociates the default-bin sum, so final-ulp drift vs the dense
    tier is possible (the tpu_sparse=-1 auto rule therefore requires
    quantized histograms; =1 forces the tier anyway).

    ``gh_scale`` dequantizes quantized sums exactly like the dense XLA
    path (same scalar multiply on equal integer sums -> bit-equal
    f32)."""
    codes, feat, row, zb = sp
    F = num_features
    B = num_bins
    W = wave_leaves.shape[0]
    size = W * F * B
    f32 = jnp.float32
    feat = feat.astype(jnp.int32)
    codes = codes.astype(jnp.int32)
    row = row.astype(jnp.int32)

    # entry -> wave slot via its row's leaf (mirrors the dense oracle)
    lr = leaf_ids[row]                                    # [E]
    eq = (lr[None, :] == wave_leaves[:, None]) \
        & (wave_leaves >= 0)[:, None]                     # [W, E]
    found = eq.any(axis=0) & (feat < F)
    slot = jnp.argmax(eq, axis=0).astype(jnp.int32)
    flat = jnp.where(found, slot * (F * B) + feat * B + codes, size)
    flatf = jnp.where(found, slot * F + feat, W * F)

    # row -> wave slot for the per-leaf totals
    eqr = (leaf_ids[None, :] == wave_leaves[:, None]) \
        & (wave_leaves >= 0)[:, None]                     # [W, N]
    slotr = jnp.where(eqr.any(axis=0),
                      jnp.argmax(eqr, axis=0).astype(jnp.int32), W)

    # default-bin completion targets: (w, f) -> flat bin index of f's
    # zero bin in slot w
    didx = (jnp.arange(W, dtype=jnp.int32)[:, None] * (F * B)
            + jnp.arange(F, dtype=jnp.int32)[None, :] * B
            + zb.astype(jnp.int32)[None, :]).reshape(-1)  # [W*F]

    def chan(v):
        ev = v[row].astype(f32)
        he = jnp.zeros(size, f32).at[flat].add(ev, mode="drop")
        sub = jnp.zeros(W * F, f32).at[flatf].add(ev, mode="drop")
        ls = jnp.zeros(W + 1, f32).at[slotr].add(v.astype(f32))[:W]
        return he.at[didx].add((ls[:, None] - sub.reshape(W, F))
                               .reshape(-1))

    hist = jnp.stack([chan(g), chan(h),
                      chan(jnp.ones_like(g, f32))], axis=1)
    hist = hist.reshape(W, F, B, 3)
    if gh_scale is not None:
        hist = hist * _qscale_vec(gh_scale)
    return hist


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _wave_hist_kernel(wl_ref, bins_ref, ghl_ref, out_ref, *maybe_cnt,
                      F, B, W, groups, group_sz, variant,
                      exact_dot=False, int8=False, count_proxy=False,
                      packed4=False, tiled=False):
    """One grid step = one row chunk; accumulates into out_ref (VMEM).
    ``tiled``: the grid is (feature tiles, row chunks); ``F``/``groups``
    and every block are then one tile's (autotune.hist_feature_tiling).

    Every tensor keeps ROWS ON THE LANE AXIS — no relayouts anywhere:
    the weight matrix is built transposed ([channels, Ct] on sublanes)
    and the MXU dot contracts the lane axis of both operands.

    wl_ref:   [Wp, 1] f32 wave leaf ids as a column (-1 = inactive)
    bins_ref: [Fp, Ct] feature-major bins (uint8)
    ghl_ref:  [4, Ct] f32 packed rows (grad, hess, leaf_id, 0)
    out_ref:  [groups, gb_pad, 128] accumulated histograms
    maybe_cnt: with variant="hilo4", a second [groups, gb_pad, 128]
              accumulator carrying the exact count channels; then,
              ``tiled``, the [Fp, Ct] i32 scratch of the rolled loop

    ``variant`` selects the exact-tier (precision="highest") channel
    layout — bf16 hi/lo decompositions make every MXU product exact,
    and hi + lo restores ~16 mantissa bits (the reference's f32
    histogram accuracy, GPU-Performance.rst) at full bf16 MXU speed:

    - "hilo5": [g_hi | g_lo | h_hi | h_lo | count] x W, 5W <= 128 ->
      W <= 25. One dot per feature group (the original layout).
    - "hilo4": [g_hi | g_lo | h_hi | h_lo] x W, 4W <= 128 -> W <= 32,
      with the exact counts accumulated by a SECOND dot of the same
      one-hot tile against the membership rows into ``maybe_cnt`` —
      more MXU work per pass, 25% fewer full-data passes per tree
      (the pass count is what an HBM-bound geometry pays for).
    - "hilo3": [g_hi | g_lo | count] x W, 3W <= 128 -> W <= 42. The
      hess plane is FUSED with the count plane — sound ONLY when the
      hessian is identically the sample mask (constant-unit-hessian
      objectives: L2/L1/quantile/Huber without row weights), where
      sum(h) == count bin-for-bin and bit-for-bit (the caller gates
      this, models/gbdt.py).

    ``variant=None`` (precision="default") keeps the single-bf16 rows
    [g | h | count] x W (3W <= 128), grad/hess rounding to bf16.
    """
    step = pl.program_id(1 if tiled else 0)
    cnt_ref = maybe_cnt[0] if variant == "hilo4" else None
    rows_ref = maybe_cnt[-1] if tiled else None     # VMEM scratch

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        if cnt_ref is not None:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    gvec = ghl_ref[0:1, :]                              # [1, Ct]
    hvec = ghl_ref[1:2, :]
    lvec = ghl_ref[2:3, :]
    wl = wl_ref[...]                                    # [Wp, 1]
    mw = ((lvec == wl[:W]) & (wl[:W] >= 0.0)).astype(jnp.float32)
    cnt_rows = None
    if int8 and count_proxy:
        # count-proxy: 2 channels only (see fused kernel / wave_grower)
        w_rows = jnp.concatenate([mw * gvec, mw * hvec], axis=0)
    elif int8:
        # quantized mode: gvec/hvec carry integer values in [-127, 127]
        # (tpu_quantized_hist, see wave_grower); int8 x int8 -> int32
        # MXU products are exact and run at 2x the bf16 rate
        w_rows = jnp.concatenate([mw * gvec, mw * hvec, mw], axis=0)
    elif variant == "hilo5":                            # mw: [W, Ct]
        g_hi, g_lo = _bf16_split(gvec)
        h_hi, h_lo = _bf16_split(hvec)
        w_rows = jnp.concatenate(
            [mw * g_hi, mw * g_lo, mw * h_hi, mw * h_lo, mw], axis=0)
    elif variant == "hilo4":
        g_hi, g_lo = _bf16_split(gvec)
        h_hi, h_lo = _bf16_split(hvec)
        w_rows = jnp.concatenate(
            [mw * g_hi, mw * g_lo, mw * h_hi, mw * h_lo], axis=0)
        cnt_rows = mw                                   # [W, Ct]
    elif variant == "hilo3":
        # constant-unit-hessian layout: the count plane IS the hess
        # plane (sum over a bin of h == m is exactly its row count)
        g_hi, g_lo = _bf16_split(gvec)
        w_rows = jnp.concatenate([mw * g_hi, mw * g_lo, mw], axis=0)
    else:
        w_rows = jnp.concatenate([mw * gvec, mw * hvec, mw], axis=0)
    nrow = w_rows.shape[0]
    if nrow != 128:
        w_rows = jnp.pad(w_rows, ((0, 128 - nrow), (0, 0)))
    if cnt_rows is not None and cnt_rows.shape[0] != 128:
        cnt_rows = jnp.pad(cnt_rows,
                           ((0, 128 - cnt_rows.shape[0]), (0, 0)))

    ct = gvec.shape[1]
    Bp = _round_up(B, 8)       # 8-aligned per-feature stride: the
    gb = group_sz * Bp         # concat below must not shuffle sublanes
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (Bp, 1), 0)
    # bf16 operands halve the one-hot tiles' footprint; numerically
    # identical to the DEFAULT bf16 MXU pass (interpret mode keeps f32
    # for the HIGHEST-precision CPU oracle)
    if int8:
        oh_dt = jnp.int8
        w_mm = w_rows.astype(jnp.int8)
        acc_dt = jnp.int32
    else:
        oh_dt = jnp.float32 if exact_dot else jnp.bfloat16
        w_mm = w_rows if exact_dot else w_rows.astype(jnp.bfloat16)
        acc_dt = jnp.float32

    gb_pad = out_ref.shape[1]

    def group(p, row_at):
        """out_ref[p] += one_hot(bins of group p) . weight rows;
        ``row_at(sidx)`` is the [1, Ct] i32 bin row of the group's
        ``sidx``-th feature, or None past the last feature."""
        # per-feature one-hot blocks concatenated on ALIGNED sublane
        # boundaries: one compare per feature (the previous
        # which_feat/select merge was VPU-bound — 2 selects + compare
        # per element vs 1 compare here)
        blocks = []
        for sidx in range(group_sz):
            row = row_at(sidx)
            blocks.append(jnp.zeros((Bp, ct), oh_dt) if row is None
                          else (row == bin_iota).astype(oh_dt))
        oh_t = (blocks[0] if group_sz == 1
                else jnp.concatenate(blocks, axis=0))   # [gb, Ct]
        # contract the LANE axis of both operands: [gb, Ct] x [128, Ct]
        # -> [gb, 128]. DEFAULT precision = one bf16 MXU pass; one-hot
        # entries and the hi/lo rows are exactly bf16-representable, so
        # the pass is exact and hi + lo restores f32-grade sums. In
        # interpret mode (CPU tests) the XLA CPU "default" dot has
        # different split-precision numerics, so force HIGHEST there.
        acc = jax.lax.dot_general(
            oh_t, w_mm, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=(None if int8
                       else jax.lax.Precision.HIGHEST if exact_dot
                       else jax.lax.Precision.DEFAULT),
            preferred_element_type=acc_dt)              # [gb, 128]
        if gb_pad != gb:
            acc = jnp.pad(acc, ((0, gb_pad - gb), (0, 0)))
        out_ref[p, :, :] += acc
        if cnt_rows is not None:
            # hilo4: the count channels ride a SECOND dot of the SAME
            # one-hot tile against the membership rows (0/1 products
            # are exact in bf16; integer sums < 2^24 are exact in f32)
            cnt_mm = (cnt_rows if exact_dot
                      else cnt_rows.astype(jnp.bfloat16))
            acc_c = jax.lax.dot_general(
                oh_t, cnt_mm, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=(jax.lax.Precision.HIGHEST if exact_dot
                           else jax.lax.Precision.DEFAULT),
                preferred_element_type=jnp.float32)
            if gb_pad != gb:
                acc_c = jnp.pad(acc_c, ((0, gb_pad - gb), (0, 0)))
            cnt_ref[p, :, :] += acc_c

    if not tiled:
        rows_cache = {}
        for p in range(groups):
            group(p, lambda sidx: (
                _feature_row(lambda r: bins_ref[r, :].astype(jnp.int32),
                             p * group_sz + sidx, rows_cache,
                             packed4)[None, :]
                if p * group_sz + sidx < F else None))
        return
    # a tile's groups are all alike (whole groups of stored rows, no
    # ragged last one), so ONE group body is compiled and a loop walks
    # it: the same dots in the same order as the unrolled loop, at a
    # small part of its compile time (64 unrolled groups of 16,384-lane
    # one-hot tiles took Mosaic 178 s). Its rows are read by a dynamic
    # sublane index, which packed uint8 rows do not allow: the tile's
    # bin block is widened to i32 once a grid step
    rows_ref[...] = bins_ref[...].astype(jnp.int32)
    per_row = 2 if packed4 else 1

    def row_at(p, sidx):
        r = rows_ref[pl.ds(p * (group_sz // per_row) + sidx // per_row,
                           1), :]                       # [1, Ct]
        if not packed4:
            return r
        return (jax.lax.shift_right_logical(r, 4) if sidx % 2
                else jnp.bitwise_and(r, 15))

    def body(p, carry):
        group(p, functools.partial(row_at, p))
        return carry

    jax.lax.fori_loop(0, groups, body, 0)


def _exact_nchan(variant) -> int:
    """MXU weight-row channels per wave slot of an exact-tier
    (precision="highest") layout — the lane-budget denominator
    (128 // nchan = the wave-width cap the variant buys)."""
    return {"hilo5": 5, "hilo4": 4, "hilo3": 3}[variant]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "interpret",
                                    "precision", "count_proxy",
                                    "packed4", "num_features",
                                    "dequant", "variant",
                                    "feature_tile"))
def wave_histogram_pallas(bins_t, g, h, leaf_ids, wave_leaves, *, num_bins,
                          chunk=2048, interpret=False, precision="highest",
                          gh_scale=None, count_proxy=False,
                          packed4=False, num_features=None,
                          dequant=True, variant="hilo5",
                          feature_tile=None):
    """Pallas wave histogram — same contract as wave_histogram_xla.

    Grid over row chunks; per chunk the kernel builds the leaf-membership
    weight matrix and the transposed per-feature-group one-hot tiles in
    VMEM and accumulates ``one_hot_t @ w`` MXU products into a
    VMEM-resident accumulator (the per-workgroup partial-histogram design
    of ocl/histogram256.cl:345, with the partial-sum reduction done by
    grid revisiting instead of atomics).

    precision="highest" uses the bf16 hi/lo weight decomposition (exact
    products, ~f32-sum accuracy, needs wave W <= 25); "default" uses
    single bf16 weights (W <= 42, grad/hess round to bf16);
    "int8" expects PRE-QUANTIZED integer-valued g/h in [-127, 127]
    (tpu_quantized_hist) and accumulates exactly in int32 at 2x MXU
    rate (W <= 42) — ``gh_scale`` = (g_scale, h_scale) dequantizes the
    output back to f32 sums. ``dequant=False`` defers that scaling and
    returns the RAW int32 sums instead (the quantized-psum wire format:
    the data-parallel learner reduces the integer representation across
    the mesh and dequantizes after the collective, ops/wave_grower.py).

    Where the accumulators of all F features no longer fit VMEM beside
    the bin block, the grid gains an outer axis over tiles of features
    (autotune.hist_feature_tiling: a trace-time choice from the shapes);
    ``feature_tile`` (stored bin rows a tile) forces one, for tests.
    """
    F, n = bins_t.shape
    if packed4:
        if num_bins > 16:
            raise NotImplementedError("packed4 needs max_bin <= 16")
        if not (count_proxy or precision == "highest"):
            raise NotImplementedError(
                "packed4 needs the count-proxy or hi/lo exact tier")
        F = int(num_features)
    W = int(wave_leaves.shape[0])
    B = num_bins
    int8 = precision == "int8"
    if count_proxy and not int8:
        raise NotImplementedError("count_proxy requires precision='int8'")
    hilo = precision == "highest"
    variant = variant if hilo else None
    nchan = ((2 if count_proxy else 3) if int8
             else _exact_nchan(variant) if hilo else 3)
    ncol = nchan * W
    if ncol > 128:
        raise NotImplementedError(
            f"wave_size {W} needs {nchan}W <= 128 lanes")
    if int8 and 127 * (n + (-n) % chunk) >= 2 ** 31:
        raise NotImplementedError(
            "int8 histogram sums could overflow int32 beyond ~16.9M "
            "rows; disable tpu_quantized_hist")
    # tile geometry + block shapes from the shared source of truth the
    # autotuner's VMEM predicate prices (ops/autotune.py); ``geom`` is
    # ONE feature tile's, the whole matrix's where one tile holds it
    geom, n_tiles = autotune.hist_feature_tiling(
        F=F, B=B, W=W, chunk=chunk, fused=False, F_rows=bins_t.shape[0],
        bins_bytes=bins_t.dtype.itemsize, int8=int8,
        count_proxy=count_proxy, variant=variant, force=feature_tile)
    tiled = n_tiles > 1
    group_sz, gb = geom["group_sz"], geom["gb"]
    groups, gb_pad = geom["groups"], geom["gb_pad"]

    bins_t, ghl = _chunk_padded_rows(bins_t, g, h, leaf_ids, chunk)
    n_pad = bins_t.shape[1]
    wp = geom["wp"]
    wl = wave_leaves.astype(jnp.float32)[:, None]        # [W, 1]
    if wp != W:
        wl = jnp.pad(wl, ((0, wp - W), (0, 0)), constant_values=-1.0)

    kernel = functools.partial(
        _wave_hist_kernel, F=geom["F"], B=B, W=W, groups=groups,
        group_sz=group_sz, variant=variant,
        exact_dot=interpret and not int8,
        int8=int8, count_proxy=count_proxy, packed4=packed4,
        tiled=tiled)

    blk = autotune.wave_hist_block_shapes(chunk=chunk, geom=geom)
    grid, at = _tile_grid(n_tiles, n_pad // chunk)
    # every tile's accumulator is one block of the output's group axis
    hist_all = (n_tiles * groups,) + blk["hist"][1:]
    out_specs = [pl.BlockSpec(blk["hist"], at(lambda t, i: (t, 0, 0)),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct(
        hist_all, jnp.int32 if int8 else jnp.float32)]
    if variant == "hilo4":
        # second accumulator: the count-dot channels (f32, W lanes)
        out_specs.append(pl.BlockSpec(blk["hist"],
                                      at(lambda t, i: (t, 0, 0)),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct(hist_all, jnp.float32))
    groups = n_tiles * groups           # of the whole output from here
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk["wl"], at(lambda t, i: (0, 0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["bins"], at(lambda t, i: (t, i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["ghl"], at(lambda t, i: (0, i)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(out_specs[0] if len(out_specs) == 1
                   else tuple(out_specs)),
        out_shape=(out_shape[0] if len(out_shape) == 1
                   else tuple(out_shape)),
        scratch_shapes=([pltpu.VMEM(blk["bins"], jnp.int32)] if tiled
                        else []),
        # the unrolled group loop's temporaries exceed the 16 MB default
        # scoped-vmem cap; v5e has 128 MB physical VMEM
        compiler_params=autotune.tpu_compiler_params(),
        name="wave_histogram_pallas",
        interpret=interpret,
    )(wl, bins_t, ghl)
    out = outs[0] if variant == "hilo4" else outs

    # [groups, gb_pad, 128] -> [F, B, ncol] -> [W, F, B, 3]
    # (feature rows sit at the aligned Bp stride; slice back to B)
    out = out[:, :gb, :ncol].reshape(
        groups * group_sz, geom["Bp"], ncol)[:F, :B]
    if variant == "hilo5":
        out = out.reshape(F, B, 5, W)
        out = jnp.stack([out[:, :, 0] + out[:, :, 1],     # g = hi + lo
                         out[:, :, 2] + out[:, :, 3],     # h = hi + lo
                         out[:, :, 4]], axis=2)           # count
        return out.transpose(3, 0, 1, 2)
    if variant == "hilo4":
        cnt = outs[1][:, :gb, :W].reshape(
            groups * group_sz, geom["Bp"], W)[:F, :B]     # [F, B, W]
        out = out.reshape(F, B, 4, W)
        out = jnp.stack([out[:, :, 0] + out[:, :, 1],     # g = hi + lo
                         out[:, :, 2] + out[:, :, 3],     # h = hi + lo
                         cnt], axis=2)                    # count (dot 2)
        return out.transpose(3, 0, 1, 2)
    if variant == "hilo3":
        out = out.reshape(F, B, 3, W)
        # the fused hess/count plane serves both output channels:
        # h == sample mask (constant-unit-hessian gate), so the bin's
        # hess sum IS its count
        return jnp.stack([out[:, :, 0] + out[:, :, 1],    # g = hi + lo
                          out[:, :, 2],                   # h = count
                          out[:, :, 2]], axis=2).transpose(3, 0, 1, 2)
    if count_proxy:
        out = out.reshape(F, B, 2, W).transpose(3, 0, 1, 2)
        if not dequant:
            return out
        return out.astype(jnp.float32) * jnp.stack(
            [jnp.float32(gh_scale[0]), jnp.float32(gh_scale[1])])
    out = out.reshape(F, B, 3, W).transpose(3, 0, 1, 2)
    if int8:
        if not dequant:
            return out
        out = out.astype(jnp.float32) * _qscale_vec(gh_scale)
    return out


# ---------------------------------------------------------------------------
# Root histogram Pallas kernel: a two-digit split of the bin axis
# ---------------------------------------------------------------------------

def _root_weight_rows(gvec, hvec, mvec, variant):
    """The [1, n] MXU weight rows of the one leaf a root pass serves,
    in channel order: what row 0 of _wave_hist_kernel's ``w_rows``
    blocks holds, value for value. "hilo4" rides the five rows of
    "hilo5": with one leaf there are lanes to spare for its counts."""
    if variant is None:
        rows = [gvec, hvec]
    else:
        rows = list(_bf16_split(gvec))
        if variant != "hilo3":
            rows += _bf16_split(hvec)
    return [mvec * r for r in rows] + [mvec]


def _root_hist_kernel(bins_ref, ghl_ref, out_ref, hi_ref, lo_ref, *,
                      F, H, L, gf, variant, exact_dot, tiled):
    """One grid step = one row chunk (of one feature tile, ``tiled``):
    out_ref[p] += the histograms of feature group p over the chunk's
    rows, by a two-digit split of the bin axis.

    A row's bin b = hi x L + lo. For one feature, with the weight rows
    w_c of _root_weight_rows, P[(c, l), k] = w_c[k] where lo[k] == l
    (else 0) and Q[h, k] = 1 where hi[k] == h: hist[h x L + l, c] =
    sum_k Q[h, k] P[(c, l), k], the very products, in the same order
    over k, as the one-hot dot of _wave_hist_kernel, which spends
    Bp x 128 MACs on a row of a feature where this spends
    nchan x L x 128: the ``gf`` features of a group put their Q side by
    side as the 128-lane operand of ONE dot and stream their P rows
    past it; each feature's own [nchan x L, H] block of the product is
    its histogram and the wrapper drops the others. Rows stay on the
    lane axis throughout, as in every kernel of this file.

    bins_ref: [F_rows, Ct] feature-major bins (uint8)
    ghl_ref:  [4, Ct] f32 rows (grad, hess, leaf id: 0 in, -1 out, 0)
    out_ref:  [groups, gf x nchan x L, 128] accumulators
    hi_ref, lo_ref: [F_rows, Ct] i32 scratch, the block's two digits
    """
    step = pl.program_id(1 if tiled else 0)

    @pl.when(step == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    i32, f32 = jnp.int32, jnp.float32
    mvec = (ghl_ref[2:3, :] == 0.0).astype(f32)             # [1, Ct]
    ct = mvec.shape[1]
    # every weight row over the L sublanes of its lo digits, once a step
    wb = [jnp.broadcast_to(r, (L, ct)) for r in _root_weight_rows(
        ghl_ref[0:1, :], ghl_ref[1:2, :], mvec, variant)]
    x = bins_ref[...].astype(i32)
    hi_ref[...] = jax.lax.shift_right_logical(x, L.bit_length() - 1)
    lo_ref[...] = jnp.bitwise_and(x, L - 1)
    l_iota = jax.lax.broadcasted_iota(i32, (L, 1), 0)
    h_iota = jax.lax.broadcasted_iota(i32, (H, 1), 0)
    dt = f32 if exact_dot else jnp.bfloat16

    def group(p, n_live):
        """out_ref[p] += P . Q of the group's first ``n_live`` features
        (zero operands stand for the others of a ragged last group)."""
        ps, qs = [], []
        for s in range(n_live):
            r = pl.ds(p * gf + s, 1)
            hit = lo_ref[r, :] == l_iota                    # [L, Ct]
            ps += [jnp.where(hit, w, 0.0) for w in wb]
            qs.append((hi_ref[r, :] == h_iota).astype(dt))
        if n_live < gf:
            ps.append(jnp.zeros(((gf - n_live) * len(wb) * L, ct), f32))
            qs.append(jnp.zeros(((gf - n_live) * H, ct), dt))
        # the lane axis of both operands contracts, as in every dot of
        # this file; see _wave_hist_kernel on DEFAULT against HIGHEST
        out_ref[p, :, :] += jax.lax.dot_general(
            jnp.concatenate(ps, axis=0).astype(dt),         # [R, Ct]
            jnp.concatenate(qs, axis=0),                    # [128, Ct]
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=(jax.lax.Precision.HIGHEST if exact_dot
                       else jax.lax.Precision.DEFAULT),
            preferred_element_type=f32)

    # ONE body for the whole groups, walked by a loop, and one for a
    # ragged last group. Unrolled, the groups ran 7% SLOWER at 67
    # features and compiled in 21 s against 2.9 (PERF.md section 5,
    # PR 33, call A); see the tiled _wave_hist_kernel on what unrolling
    # its 16,384-lane one-hot tiles cost Mosaic
    def whole_group(p, carry):
        group(p, gf)
        return carry

    jax.lax.fori_loop(0, F // gf, whole_group, 0)
    if F % gf:
        group(F // gf, F % gf)


def root_nchan(precision, variant) -> int:
    """Weight rows of the root kernel's one leaf (_root_weight_rows)."""
    return 5 if precision == "highest" and variant != "hilo3" else 3


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk", "interpret",
                                    "precision", "variant",
                                    "feature_tile"))
def root_histogram_pallas(bins_t, g, h, leaf_ids, *, num_bins, chunk=2048,
                          interpret=False, precision="highest",
                          variant="hilo5", feature_tile=None):
    """[1, F, B, 3]: the histogram of leaf 0, a tree's root pass.

    What ``wave_histogram_pallas`` returns in slot 0 for
    ``wave_leaves = [0, -1, ...]``, sum for sum, by a dot that asks the
    MXU for ``nchan x 8 x 128`` MACs on a row of a feature where that
    one asks for ``Bp x 128`` (_root_hist_kernel): one leaf needs no
    lanes for other leaves' channels. ``leaf_ids``: 0 for the rows that
    count, -1 for out-of-bag rows (and for the chunk's pad rows). The
    bf16 tiers only (``precision`` "highest" with its ``variant``, or
    "default"), byte bins of at most 256 levels;
    autotune.root_split_applies is the grower's question.

    ``chunk`` is the caller's (the grower's, chosen by what the wave
    and fused kernels cost); the kernel walks the largest
    ``chunk / 2^k`` rows a step that its own working set fits VMEM at
    (autotune.root_hist_tiling: 16384 of 32768 or 65536 at 5 channels x
    256 bins). Its sums then add up in the halved chunk's order: equal
    to the wave kernel's at THAT chunk bit for bit, and to the one at
    the caller's chunk as closely as two chunk sizes of the wave kernel
    agree.

    The Mosaic call keeps the name ``wave_histogram_pallas``: a trace
    reader that groups a tree's histogram passes by kernel name goes on
    counting the root pass with the others.
    Where the bin block and its digit scratches of all F features no
    longer fit VMEM the grid gains an outer axis over tiles of features
    (autotune.root_hist_tiling); ``feature_tile`` (stored bin rows a
    tile) forces one, for tests.
    """
    if precision not in ("highest", "default"):
        raise NotImplementedError(
            f"the root kernel serves the bf16 tiers, not {precision!r}")
    F, n = bins_t.shape
    B = num_bins
    nchan = root_nchan(precision, variant)
    variant = variant if precision == "highest" else None
    chunk, geom, n_tiles = autotune.root_hist_tiling(
        F=F, B=B, nchan=nchan, chunk=chunk,
        bins_bytes=bins_t.dtype.itemsize, force=feature_tile)
    H, L, gf, groups = geom["H"], geom["L"], geom["gf"], geom["groups"]

    bins_t, ghl = _chunk_padded_rows(bins_t, g, h, leaf_ids, chunk)
    tiled = n_tiles > 1
    kernel = functools.partial(
        _root_hist_kernel, F=geom["F"], H=H, L=L, gf=gf, variant=variant,
        exact_dot=interpret, tiled=tiled)
    blk = autotune.root_hist_block_shapes(chunk=chunk, geom=geom)
    grid, at = _tile_grid(n_tiles, bins_t.shape[1] // chunk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk["bins"], at(lambda t, i: (t, i)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["ghl"], at(lambda t, i: (0, i)),
                         memory_space=pltpu.VMEM),
        ],
        # every tile's accumulator is one block of the output's group axis
        out_specs=pl.BlockSpec(blk["hist"], at(lambda t, i: (t, 0, 0)),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (n_tiles * groups,) + blk["hist"][1:], jnp.float32),
        scratch_shapes=[pltpu.VMEM(blk["bins"], jnp.int32)] * 2,
        compiler_params=autotune.tpu_compiler_params(),
        name="wave_histogram_pallas",
        interpret=interpret,
    )(bins_t, ghl)

    # [tiles x groups, gf x nchan x L, gf x H]: feature j of a group
    # keeps block (j, j) of its group's product, the products of two
    # different features' digits are dropped; -> [F, B, nchan]
    out = out.reshape(n_tiles * groups, gf, nchan, L, gf, H)
    out = jnp.stack([out[:, j, :, :, j] for j in range(gf)], axis=1)
    out = out.transpose(0, 1, 4, 3, 2)           # [G, gf, H, L, nchan]
    out = out.reshape(n_tiles, groups * gf, H * L, nchan)
    out = out[:, :geom["F"], :B].reshape(-1, B, nchan)[:F]
    if nchan == 5:
        out = jnp.stack([out[..., 0] + out[..., 1],       # g = hi + lo
                         out[..., 2] + out[..., 3],       # h = hi + lo
                         out[..., 4]], axis=-1)           # count
    elif variant == "hilo3":
        # the fused hess/count plane serves both (see _wave_hist_kernel)
        out = jnp.stack([out[..., 0] + out[..., 1],
                         out[..., 2], out[..., 2]], axis=-1)
    return out[None]


def _qscale_vec(gh_scale):
    """[3] channel dequantization vector (g_scale, h_scale, 1)."""
    sg, sh = gh_scale
    return jnp.stack([jnp.float32(sg), jnp.float32(sh),
                      jnp.float32(1.0)])


def wave_histogram(bins_t, g, h, leaf_ids, wave_leaves, *, num_bins,
                   chunk=0, use_pallas=None, precision="highest",
                   gh_scale=None, count_proxy=False, dequant=True,
                   variant="hilo5", route=""):
    """Dispatch: Pallas on the TPU, XLA elsewhere (force via use_pallas
    or pin an explicit ``route`` — see autotune.tune_hist_route).

    precision="int8": g/h are integer-valued (quantized) and gh_scale
    dequantizes the sums; the XLA scatter path is exact on integer
    floats as-is, so only the Pallas kernel switches dtype.
    ``dequant=False`` skips the scaling (quantized-psum wire format —
    the XLA oracle then returns integer-VALUED f32 sums, the Pallas
    kernel raw int32).
    count_proxy: the Pallas kernel returns 2 channels (g, h); the XLA
    oracle still returns 3 exact channels — proxy callers overwrite
    the count channel either way (wave_grower.bound_counts).
    variant: exact-tier channel layout (precision="highest" only; see
    _wave_hist_kernel) — the XLA oracle is layout-free, so only the
    Pallas kernel consumes it."""
    if not route:
        if use_pallas is False:
            route = "two-pass"
        else:
            route = autotune.tune_hist_route(use_pallas=use_pallas)
    if route == "pallas-tpu":
        from ..utils.device import on_tpu
        return wave_histogram_pallas(
            bins_t, g, h, leaf_ids, wave_leaves, num_bins=num_bins,
            chunk=chunk or autotune.DEFAULT_HIST_CHUNK,
            # off the chip a pinned route runs the same kernel
            # interpreted, as the fused pass does (ops/wave_grower.py)
            interpret=not on_tpu(),
            precision=precision, gh_scale=gh_scale,
            count_proxy=count_proxy, dequant=dequant, variant=variant)
    out = wave_histogram_xla(
        bins_t, g, h, leaf_ids, wave_leaves, num_bins=num_bins,
        chunk=0, precision="highest")
    if precision == "int8" and dequant:
        out = out * _qscale_vec(gh_scale)
    return out


# ---------------------------------------------------------------------------
# Fused partition + wave histogram Pallas kernel
# ---------------------------------------------------------------------------

# rows of the packed per-slot split table (int32, transposed to
# [128, TBL_ROWS] at the kernel boundary)
TBL_PARENT, TBL_NEW, TBL_FEAT, TBL_BIN, TBL_DLEFT = 0, 1, 2, 3, 4
TBL_MISS, TBL_DEFBIN, TBL_NUMBIN, TBL_SMALL, TBL_ISCAT = 5, 6, 7, 8, 9
TBL_CATW = 10           # 8 bitset words (left-set bins) follow
TBL_ROWS = 24           # padded to an int32 sublane multiple

FUSED_MAX_WAVE = 32          # 4 channels x W <= 128 MXU lanes (bf16 h)
FUSED_MAX_WAVE_HILO = 24     # 5 channels, kept a multiple of 8
FUSED_MAX_WAVE_HILO4 = 32    # 4 channels + a count dot (exact tier)
FUSED_MAX_WAVE_HILO3 = 40    # 3 channels (fused hess/count plane),
                             # 42 floor'd to a multiple of 8
FUSED_MAX_WAVE_INT8 = 42     # 3 channels (int8 gq/hq/count)
FUSED_MAX_WAVE_INT8_NC = 64  # 2 channels (count-proxy mode: the MXU dot
                             # carries only gq/hq; per-bin counts are
                             # synthesized downstream from the hessian
                             # channel and EXACT per-child counts come
                             # from the partition mask — see wave_grower)


def _fused_kernel(tbl_ref, binsf_ref, ghm_ref, leaf_ref, *rest, F, B, W,
                  groups, group_sz, variant, exact_dot=False, int8=False,
                  any_cat=True, count_proxy=False, packed4=False,
                  compact_tile=0, tiled=False, split=None):
    """One grid step: partition one row chunk by the wave's W splits,
    then accumulate the wave's smaller-child histograms — ONE data pass.

    Lane-natural layout throughout (rows on lanes): the partition runs
    in [W, Ct] orientation fed by feature-major bin ROWS (no row-major
    copy of the bins exists at all), per-slot split parameters are
    columns of the transposed table, and the weight matrix is built
    transposed for a lane-contracting MXU dot. No relayouts.

    tbl_ref:   [128, TBL_ROWS=24] i32 packed split table (row k = wave
               slot k, column j = TBL_* field j: 10 scalar fields then
               8 categorical left-set bitset words; parent -1 =
               inactive slot)
    binsf_ref: [F, Ct]  feature-major bins (uint8)
    ghm_ref:   [4, Ct]  f32 rows (grad, hess, bag_mask, 0); grad/hess
               pre-masked, the mask rides separately for the counts
    leaf_ref:  [1, Ct]  i32 leaf ids BEFORE this wave (all rows,
               out-of-bag included)
    cols_ref:  (``tiled`` only) [Wp, Ct] the bin rows of the wave's W
               split features, one row a slot
    hist_ref:  [groups, gb_pad, 128] accumulated histograms; with
               ``split`` [W, groups of gf features, 3 x L, 128], a
               slot's three planes (g, h, count) by digit, written on a
               tile's last step from the accumulators (_flush_by_slot)
    leaf_out_ref: [1, Ct] i32 leaf ids AFTER this wave
    tiles_ref: [1] i32 (SMEM) rows put through the one-hot dot, in
               units of COMPACT_TILE_UNIT; with ``split`` [2]: the
               128-row blocks dotted, then the (block, slot) pairs
    rest:      the count accumulator (count_proxy / "hilo4" without
               ``split``), then — with ``compact_tile`` — the
               compaction's scratch, then ``split``'s

    Channel layout: the exact tier (tpu_use_dp) rides one of the
    ``variant`` layouts of _wave_hist_kernel — "hilo5"
    ([g_hi | g_lo | h_hi | h_lo | count] x W, W <= 24), "hilo4" (the
    count channel moves to a second dot into the count accumulator,
    W <= 32) or "hilo3" (the fused hess/count plane for
    constant-unit-hessian objectives, W <= 40) — all with exact bf16
    products and f32-grade hi + lo reconstruction. ``variant=None``
    (precision="default"): [g_hi | g_lo | h | count] x W (W <= 32),
    hessian single bf16 (2^-9 relative rounding). Counts exact in
    every layout.

    ``compact_tile`` = T > 0: only the rows that can contribute reach
    the dot, T at a time (see "stable row compaction" below): each
    T-row sub-tile of the chunk is gathered through ONE [T, T] one-hot
    into a [C, T] staging buffer, and the turn on which the buffer
    fills dots it and gathers the rows past its end a second time, as
    the next tile's head. 0: every row of the chunk reaches the dot,
    with zero weights where it contributes nothing.
    autotune.hist_compact_tile chooses, from the dot's cost a row.

    ``split`` (autotune.fused_wave_split's geometry, where
    autotune.wave_split_applies): the staging buffer is
    WAVE_SPLIT_STAGE_ROWS wide, filled a T-row window at a time by the
    same gathers, and what is flushed is put in slot order and dotted
    by the root's two-digit split, each 128-row block against the slots
    it holds (_flush_by_slot). None: the one-hot dot of _accumulate_hist
    against every slot's lanes, over a T-row tile.

    ``tiled``: the grid is (feature tiles, row chunks) and ``binsf_ref``,
    ``hist_ref``, ``F`` and ``groups`` are ONE tile's. A wave's split
    features lie in any tile, so their bin rows come in ``cols_ref``
    (gathered once a pass outside the kernel) and every tile routes
    its rows alike, from the leaf ids of BEFORE the pass (``leaf_ref``
    is never the buffer ``leaf_out_ref`` writes); each writes the same
    new ids. Tile 0 alone counts what is counted once a pass: the
    dotted tiles and the count-proxy's moved rows.
    """
    if tiled:
        cols_ref, *rest = rest
    hist_ref, leaf_out_ref, tiles_ref, *rest = rest
    step = pl.program_id(1 if tiled else 0)
    first_tile = pl.program_id(0) == 0 if tiled else None
    T = compact_tile
    has_cnt = count_proxy or (variant == "hilo4" and not split)
    cnt_ref = rest[0] if has_cnt else None
    scratch = rest[1:] if has_cnt else rest

    def once_a_pass(fn):
        """Run ``fn`` in the first feature tile only."""
        if tiled:
            pl.when(first_tile)(fn)
        else:
            fn()

    @pl.when(step == 0)
    def _():
        if not split:             # (the split's accumulators: below)
            hist_ref[...] = jnp.zeros_like(hist_ref)
        if variant == "hilo4" and not split:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        @once_a_pass
        def _():
            tiles_ref[0] = 0
            if split:
                tiles_ref[1] = 0
            if count_proxy:
                cnt_ref[...] = jnp.zeros_like(cnt_ref)

    i32 = jnp.int32
    leaf = leaf_ref[...]                                # [1, Ct]
    ct = leaf.shape[1]

    # per-slot split parameters as [W, 1] columns
    bin_c = tbl_ref[:W, TBL_BIN:TBL_BIN + 1]
    dleft_c = tbl_ref[:W, TBL_DLEFT:TBL_DLEFT + 1]
    miss_c = tbl_ref[:W, TBL_MISS:TBL_MISS + 1]
    defb_c = tbl_ref[:W, TBL_DEFBIN:TBL_DEFBIN + 1]
    nb_c = tbl_ref[:W, TBL_NUMBIN:TBL_NUMBIN + 1]
    parent_c = tbl_ref[:W, TBL_PARENT:TBL_PARENT + 1]
    new_c = tbl_ref[:W, TBL_NEW:TBL_NEW + 1]
    small_c = tbl_ref[:W, TBL_SMALL:TBL_SMALL + 1]
    iscat_c = tbl_ref[:W, TBL_ISCAT:TBL_ISCAT + 1]

    # ---- partition (DataPartition::Split, data_partition.hpp:109) ----
    # cols[k, :] = bins of slot k's split feature, fetched as ONE MXU
    # row-gather: a [W, F] one-hot over features times the bf16 bins
    # tile. Bin values <= 255 are exactly bf16-representable and each
    # output has a single nonzero product, so the gather is exact —
    # and it replaces the previous F-deep select sweep over [W, Ct]
    # (F x W VPU ops per row) with an F-contraction matmul.
    feat_c = tbl_ref[:W, TBL_FEAT:TBL_FEAT + 1]
    if tiled:
        cols = cols_ref[...].astype(i32)[:W]                # [W, Ct]
    elif packed4:
        # 4-bit tier (dense_nbits_bin.hpp analog): two features per
        # HBM byte. Gather the PACKED byte rows (values <= 255: exact
        # bf16), then select each slot's nibble by feat & 1.
        F2 = binsf_ref.shape[0]
        feat2_c = jax.lax.shift_right_logical(feat_c, 1)
        odd_c = jnp.bitwise_and(feat_c, 1)
        f_iota2 = jax.lax.broadcasted_iota(i32, (W, F2), 1)
        feat_oh = (f_iota2 == feat2_c).astype(jnp.bfloat16)
        bins_bf = binsf_ref[...].astype(i32) \
            .astype(jnp.bfloat16)                           # [F2, Ct]
        packed_cols = jax.lax.dot_general(
            feat_oh, bins_bf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(i32)  # [W, Ct]
        cols = jnp.where(odd_c > 0,
                         jax.lax.shift_right_logical(packed_cols, 4),
                         jnp.bitwise_and(packed_cols, 15))
    elif B <= 128:
        # int8 gather: bin values <= 127 are exact int8, the one-hot
        # row-select dot runs at the MXU's 2x int8 rate and accumulates
        # exactly in int32
        f_iota = jax.lax.broadcasted_iota(i32, (W, F), 1)
        feat_oh8 = (f_iota == feat_c).astype(jnp.int8)      # [W, F]
        bins_i8 = binsf_ref[...].astype(i32) \
            .astype(jnp.int8)                               # [F, Ct]
        cols = jax.lax.dot_general(
            feat_oh8, bins_i8,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=i32)                      # [W, Ct]
    elif B <= 256:
        f_iota = jax.lax.broadcasted_iota(i32, (W, F), 1)
        feat_oh = (f_iota == feat_c).astype(jnp.bfloat16)   # [W, F]
        # (Mosaic has no u8->bf16 cast; hop through i32)
        bins_bf = binsf_ref[...].astype(i32) \
            .astype(jnp.bfloat16)                           # [F, Ct]
        cols = jax.lax.dot_general(
            feat_oh, bins_bf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(i32)  # [W, Ct]
    else:
        # bins above 256 are not exactly bf16-representable: keep the
        # exact F-deep select sweep for the wide-bin tier
        cols = jnp.zeros((W, ct), i32)
        for f in range(F):
            cols = jnp.where(feat_c == f,
                             binsf_ref[f, :].astype(i32)[None, :], cols)
    # missing semantics match ops/partition.py row_goes_right; logical
    # form, not jnp.where-on-bools (Mosaic can't lower the i8->i1
    # truncation a boolean select produces). Per-slot SENTINEL bins
    # fold the missing-type tests into the cheap [W, 1] lane: -9 never
    # matches a real bin, so each [W, Ct] compare does double duty
    na_sent = jnp.where(miss_c == 2, nb_c - 1, -9)
    def_sent = jnp.where(miss_c == 1, defb_c, -9)
    is_missing = (cols == na_sent) | (cols == def_sent)
    gt = cols > bin_c
    ndl = dleft_c == 0
    # right = is_missing ? !default_left : col > threshold, in xor form
    # (two fewer [W, Ct] ops than the and/or expansion)
    right = gt ^ (is_missing & (gt ^ ndl))
    # categorical: the bin's bit set in the slot's left bitset -> LEFT
    # (dense_bin.hpp SplitCategorical); unseen/NaN bins go right.
    # Statically skipped when the dataset has no categorical features
    # (any_cat) — the 8-way word select + bit test is ~400 VPU ops/row.
    if any_cat:
        widx = jnp.right_shift(cols, 5)
        word = jnp.zeros_like(cols)
        for wq in range(8):
            word = jnp.where(
                widx == wq,
                tbl_ref[:W, TBL_CATW + wq:TBL_CATW + wq + 1],
                word)
        cat_left = jnp.bitwise_and(
            jnp.right_shift(word, jnp.bitwise_and(cols, 31)), 1) != 0
        # logical form (no bool select — see `right` above)
        iscat_b = iscat_c > 0
        right = (iscat_b & ~cat_left) | (~iscat_b & right)
    # inactive (parent -1) slots can only match CHUNK-PADDED tail rows
    # (leaf -1; real leaf ids are never negative); their g/h/mask are
    # zero and their leaf_out is sliced off, so no >= 0 guard is needed
    moved = (leaf == parent_c) & right                      # [W, Ct]
    # destination via (new_id + 1) so inactive slots (-1 -> 0) drop out
    # of the sum and the `any` reduce is folded into one pass
    dest1 = jnp.sum(jnp.where(moved, new_c + 1, 0), axis=0,
                    keepdims=True)                          # [1, Ct]
    leaf_new = jnp.where(dest1 > 0, dest1 - 1, leaf)        # [1, Ct]
    leaf_out_ref[...] = leaf_new

    # ---- transposed wave weight rows ----
    gvec = ghm_ref[0:1, :]
    hvec = ghm_ref[1:2, :]
    mvec = ghm_ref[2:3, :]
    if count_proxy:
        # exact per-slot right-child counts from the partition mask:
        # the count CHANNEL is gone from the MXU dot, but the exact
        # in-bag row count of every new (right) child falls out of
        # `moved` for the cost of one [W, Ct] reduce — wave_grower
        # derives the left side as parent - right and synthesizes the
        # per-bin count estimates from the hessian channel. Taken from
        # the partition, BEFORE any compaction: every row counts.
        @once_a_pass
        def _():
            mvd = moved.astype(jnp.float32) * mvec          # [W, Ct]
            s = jnp.sum(mvd, axis=1, keepdims=True)         # [W, 1]
            wp_c = cnt_ref.shape[0]
            if wp_c != W:
                s = jnp.pad(s, ((0, wp_c - W), (0, 0)))
            cnt_ref[...] += jnp.broadcast_to(s, cnt_ref.shape)
    chan = _channel_rows(gvec, hvec, mvec, variant=variant, int8=int8,
                         count_proxy=count_proxy)
    acc_kw = dict(F=F, B=B, groups=groups, group_sz=group_sz,
                  variant=variant, exact_dot=exact_dot, int8=int8,
                  packed4=packed4)
    hist_cnt_ref = cnt_ref if variant == "hilo4" and not split else None
    if not T:
        # ---- masked full-chunk dot: every row of the chunk goes
        # through the one-hot dot, rows outside the wave's smaller
        # children with zero weight rows (small -1 slots only match
        # zero-weight padded tail rows) ----
        m = (leaf_new == small_c).astype(jnp.float32)       # [W, Ct]
        _accumulate_hist(
            lambda r: binsf_ref[r, :].astype(i32), chan, m, hist_ref,
            hist_cnt_ref, **acc_kw)

        @once_a_pass
        def _():
            tiles_ref[0] += ct // COMPACT_TILE_UNIT
        return

    # ---- stable row compaction ahead of the dot ----
    # The partition above visits every row; the one-hot dot is what a
    # row costs (bins x lanes MACs), and only rows that sit in one of
    # the wave's smaller children AND carry weight can change a sum.
    # Those rows are packed, in row order, into a [C, T] staging buffer
    # that lives across grid steps; whenever T of them are there, ONE
    # T-wide tile goes through the weight-row build and the one-hot
    # dot. The packing is itself an MXU gather, like `cols` above: per
    # T-row sub-tile a one-hot P[T, T] (row t -> staging position count
    # + its rank among the sub-tile's selected rows) contracts against
    # the sub-tile's bin rows and channel multiplicands. Positions past
    # the tile's end meet no 1; the turn on which the tile fills dots
    # it and gathers those rows again, through the same one-hot moved
    # down by T, as the head of the next tile: a second gather on that
    # turn only. Every value that passes through is exact in the dot's
    # input type (bins <= 255; bf16-rounded channel multiplicands, the
    # same rounding the weight rows got before; the row's 1-based slot
    # <= 64) and meets a single 1, so the gather is exact and the
    # histogram differs from the masked dot's only in the order of its
    # f32 additions.
    x_ref, sel_ref, staged_ref, cnt_smem, *split_refs = scratch
    # the staging buffer is NW windows of T rows: one, the dotted tile,
    # without ``split``
    TS = staged_ref.shape[1]
    NW = TS // T
    xdt = jnp.float32 if exact_dot else jnp.bfloat16
    dot_prec = (jax.lax.Precision.HIGHEST if exact_dot
                else jax.lax.Precision.DEFAULT)
    k1_c = jax.lax.broadcasted_iota(i32, (W, 1), 0) + 1
    slot1 = jnp.sum(jnp.where(leaf_new == small_c, k1_c, 0), axis=0,
                    keepdims=True)                          # [1, Ct]
    sel = (slot1 > 0) & ((mvec > 0.0) | (gvec != 0.0) | (hvec != 0.0))
    sel_f = sel.astype(jnp.float32)
    sel_ref[...] = sel_f
    # payload rows [_PAY_ROWS, Ct]: the channel multiplicands, then the
    # row's slot (0 = not selected), laid out by sublane selects (no
    # sublane concat of 1-row pieces)
    r_iota = jax.lax.broadcasted_iota(i32, (_PAY_ROWS, 1), 0)
    pay = jnp.zeros((_PAY_ROWS, ct), jnp.float32)
    for j, row in enumerate(chan + [slot1.astype(jnp.float32) * sel_f]):
        pay = jnp.where(r_iota == j, row, pay)
    x_ref[0:_PAY_ROWS, :] = pay.astype(xdt)
    f_rows = binsf_ref.shape[0]
    x_ref[_PAY_ROWS:_PAY_ROWS + f_rows, :] = \
        binsf_ref[...].astype(i32).astype(xdt)
    slot_row = len(chan)

    @pl.when(step == 0)
    def _():
        staged_ref[...] = jnp.zeros_like(staged_ref)
        cnt_smem[0] = 0
        if split:
            split_refs[-1][...] = jnp.zeros_like(split_refs[-1])

    # U[i, j] = 1 where i < j, one 128-lane block's worth: blk . U =
    # each row's rank among the block's selected rows before it (0/1
    # products, f32 sums <= T: exact)
    LB = 128                                 # lanes of a block
    tri = (jax.lax.broadcasted_iota(i32, (LB, LB), 0)
           < jax.lax.broadcasted_iota(i32, (LB, LB), 1)).astype(xdt)
    t_iota = jax.lax.broadcasted_iota(i32, (T, 1), 0)
    n_sub = ct // T

    def rank_of(start, live_f):
        """[1, T] i32: selected rows of the sub-tile at ``start`` before
        each row. The T // 128 lane blocks ride the sublanes of ONE dot
        against the block-sized triangle; a block's ranks then start at
        the sum of the blocks before it."""
        blks = [sel_ref[:, pl.ds(pl.multiple_of(start + b * LB, LB), LB)]
                * live_f for b in range(T // LB)]
        lhs = jnp.zeros((_PAY_ROWS, LB), jnp.float32)
        for b, blk in enumerate(blks):
            lhs = jnp.where(r_iota == b, blk, lhs)
        within = jax.lax.dot_general(
            lhs.astype(xdt), tri,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=dot_prec,
            preferred_element_type=jnp.float32)             # [16, LB]
        ranks, before = [], jnp.zeros((1, 1), jnp.float32)
        for b, blk in enumerate(blks):
            ranks.append(within[b:b + 1, :] + before)
            before = before + jnp.sum(blk, axis=1, keepdims=True)
        return jnp.concatenate(ranks, axis=1).astype(i32)

    def window(w):
        """The staging buffer's ``w``-th T-row window, as an index."""
        if NW == 1:
            return ...
        return (slice(None), pl.ds(pl.multiple_of(w * T, T), T))

    def flush():
        if split:
            blocks, pairs = _flush_by_slot(
                staged_ref, *split_refs, W=W, F=binsf_ref.shape[0],
                nch=slot_row, geom=split, exact_dot=exact_dot)

            @once_a_pass
            def _():
                tiles_ref[0] += blocks
                tiles_ref[1] += pairs
            # the windows past the first are not written again before
            # they are added to
            staged_ref[...] = jnp.zeros_like(staged_ref)
            return
        m = (staged_ref[slot_row:slot_row + 1, :]
             == k1_c.astype(jnp.float32)).astype(jnp.float32)  # [W, T]
        rows = [staged_ref[j:j + 1, :] for j in range(slot_row)]
        _accumulate_hist(
            lambda r: staged_ref[_PAY_ROWS + r, :].astype(i32), rows,
            m, hist_ref, hist_cnt_ref, **acc_kw)

        @once_a_pass
        def _():
            tiles_ref[0] += T // COMPACT_TILE_UNIT

    def sub_tile(i, c):
        # one more turn than there are sub-tiles in the LAST grid step:
        # it packs nothing and flushes what is left (zero weights past
        # `count`: the staging buffer is zero there)
        live = i < n_sub
        start = pl.multiple_of(jnp.minimum(i, n_sub - 1) * T, T)
        live_f = live.astype(jnp.float32)
        sel_t = sel_ref[:, pl.ds(start, T)] * live_f
        # the window the rows land in and what it holds already
        w = c // T if NW > 1 else 0
        cw = c - w * T if NW > 1 else c
        # staging position of each selected row, -1 where not selected
        tgt = jnp.where(sel_t > 0.0, rank_of(start, live_f) + cw,
                        -1)                                 # [1, T]

        def gather(tgt):
            perm = (t_iota == tgt).astype(xdt)              # [T, T]
            return jax.lax.dot_general(
                x_ref[:, pl.ds(start, T)], perm,
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=dot_prec,
                preferred_element_type=jnp.float32)         # [C, T]

        staged_ref[window(w)] += gather(tgt)
        c = c + jnp.sum(sel_t).astype(i32)
        full = (c >= TS) | (jnp.logical_not(live) & (c > 0))
        if NW > 1:
            # the rows past a window's end open the next one, by the
            # second gather the turn that fills a tile always made
            @pl.when((c - w * T >= T) & (w < NW - 1))
            def _():
                staged_ref[window(w + 1)] = gather(tgt - T)

        @pl.when(full)
        def _():
            flush()
            # the rows past the tile's end open the next tile (an
            # unselected row's -1 - T meets no position either)
            staged_ref[window(0)] = gather(tgt - T)

        return jnp.where(full, jnp.maximum(c - TS, 0), c)

    last = step == pl.num_programs(1 if tiled else 0) - 1
    cnt_smem[0] = jax.lax.fori_loop(
        0, n_sub + last.astype(i32), sub_tile, cnt_smem[0])
    if split:
        # a tile's sums are whole: its channel rows leave the kernel as
        # the three planes the wrapper hands on (g = hi + lo, h, count;
        # _channel_rows), three fifths of what the accumulators hold
        acc_ref, L = split_refs[-1], split["L"]

        @pl.when(last)
        def _():
            def planes(k, c):
                for p in range(split["groups"]):
                    a = acc_ref[k, p]                        # [nch x L, 128]
                    ch = [a[j * L:(j + 1) * L, :] for j in range(slot_row)]
                    hist_ref[k, p] = jnp.concatenate(
                        [ch[0] + ch[1],
                         ch[2] + ch[3] if slot_row == 5 else ch[2],
                         ch[-1]], axis=0)
                return c

            jax.lax.fori_loop(0, W, planes, 0)


# rows of the compaction payload block ahead of the bin rows in the
# staging layout: <= 5 channel multiplicands + the slot, padded to one
# packed bf16 sublane tile so the bin rows start tile-aligned
_PAY_ROWS = autotune.HIST_COMPACT_PAY_ROWS
# the unit `tiles` counts in (rows): a pass that compacts reports
# T / unit per dotted tile, one that does not reports chunk / unit per
# grid step, so rows_dotted = tiles * unit either way and stays int32-
# exact (the rows themselves would not, past 2^31 / waves rows)
COMPACT_TILE_UNIT = 128


def _channel_rows(gvec, hvec, mvec, *, variant, int8, count_proxy):
    """The [1, n] per-row multiplicands of a wave slot's MXU weight
    rows, in channel order (see _fused_kernel's channel layouts). The
    weight row of slot k, channel c is ``m[k] * rows[c]``. For "hilo4"
    the LAST row (the mask) feeds the second, count dot instead."""
    if int8 and count_proxy:
        # 2 channels x W <= 128 lanes -> waves up to 64 leaves wide,
        # cutting full-data passes per tree (the count channel's lane
        # budget bought more wave width than the counts were worth)
        return [gvec, hvec]
    if int8:
        # quantized mode (tpu_quantized_hist): gvec/hvec hold integers
        # in [-127, 127]; int8 MXU products, exact int32 sums, 2x rate
        return [gvec, hvec, mvec]
    g_hi, g_lo = _bf16_split(gvec)
    if variant == "hilo3":
        # fused hess/count plane (h == mask, see _wave_hist_kernel)
        return [g_hi, g_lo, mvec]
    if variant is None:
        return [g_hi, g_lo, hvec, mvec]
    h_hi, h_lo = _bf16_split(hvec)
    # hilo5: count is the 5th channel; hilo4: it moves to a second dot
    return [g_hi, g_lo, h_hi, h_lo, mvec]


def _accumulate_hist(get_row, chan, m, hist_ref, cnt_ref, *, F, B, groups,
                     group_sz, variant, exact_dot, int8, packed4):
    """hist_ref[p] += one_hot(bins of group p) . weight rows, over the
    ``n`` rows on the lane axis of ``m`` [W, n] (slot membership, f32
    0/1), ``chan`` (_channel_rows) and ``get_row(r)`` (stored bin row r
    as i32 lanes)."""
    n = m.shape[1]
    n_w = len(chan) - (variant == "hilo4")
    w_rows = jnp.concatenate([m * r for r in chan[:n_w]], axis=0)
    cnt_rows = m * chan[-1] if variant == "hilo4" else None
    nrow = w_rows.shape[0]
    if nrow != 128:
        w_rows = jnp.pad(w_rows, ((0, 128 - nrow), (0, 0)))
    if cnt_rows is not None and cnt_rows.shape[0] != 128:
        cnt_rows = jnp.pad(cnt_rows,
                           ((0, 128 - cnt_rows.shape[0]), (0, 0)))

    # ---- one-hot tiles + lane-contracting MXU accumulate ----
    Bp = _round_up(B, 8)       # aligned per-feature stride (see
    gb = group_sz * Bp         # _wave_hist_kernel)
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (Bp, 1), 0)
    # bf16 operands halve the one-hot tile's VMEM/register footprint;
    # numerically identical to the DEFAULT bf16 MXU pass (interpret
    # mode keeps f32 for the HIGHEST-precision CPU oracle)
    if int8:
        oh_dt = jnp.int8
        w_mm = w_rows.astype(jnp.int8)
        acc_dt = jnp.int32
    else:
        oh_dt = jnp.float32 if exact_dot else jnp.bfloat16
        w_mm = w_rows if exact_dot else w_rows.astype(jnp.bfloat16)
        acc_dt = jnp.float32
    rows_cache = {}
    for p in range(groups):
        blocks = []
        for sidx in range(group_sz):
            f = p * group_sz + sidx
            if f < F:
                row = _feature_row(get_row, f, rows_cache, packed4)
                blocks.append(
                    (row[None, :] == bin_iota).astype(oh_dt))
            else:
                blocks.append(jnp.zeros((Bp, n), oh_dt))
        oh_t = (blocks[0] if group_sz == 1
                else jnp.concatenate(blocks, axis=0))
        acc = jax.lax.dot_general(
            oh_t, w_mm, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=(None if int8
                       else jax.lax.Precision.HIGHEST if exact_dot
                       else jax.lax.Precision.DEFAULT),
            preferred_element_type=acc_dt)
        gb_pad = hist_ref.shape[1]
        if gb_pad != gb:
            acc = jnp.pad(acc, ((0, gb_pad - gb), (0, 0)))
        hist_ref[p, :, :] += acc
        if cnt_rows is not None:
            # hilo4 count dot (same one-hot tile; exact 0/1 products)
            cnt_mm = (cnt_rows if exact_dot
                      else cnt_rows.astype(jnp.bfloat16))
            acc_c = jax.lax.dot_general(
                oh_t, cnt_mm,
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=(jax.lax.Precision.HIGHEST if exact_dot
                           else jax.lax.Precision.DEFAULT),
                preferred_element_type=jnp.float32)
            if gb_pad != gb:
                acc_c = jnp.pad(acc_c, ((0, gb_pad - gb), (0, 0)))
            cnt_ref[p, :, :] += acc_c


def _flush_by_slot(staged_ref, ord_ref, key_ref, p_ref, span_ref, acc_ref,
                   *, W, F, nch, geom, exact_dot):
    """acc_ref[k, p] += the histograms of feature group p over the
    staged rows of slot k, by the root kernel's two-digit split of the
    bin axis (_root_hist_kernel) a slot at a time; returns the 128-row
    blocks and the (block, slot) pairs it dotted, as i32 scalars.

    A dense dot over rows of mixed slots spends its whole output on
    every row: W x Bp x nchan MACs a feature, whichever operand the
    slot is folded into. Only a dot whose rows share a slot addresses
    one slot's histogram, so the staged rows are first put IN SLOT
    ORDER by one more exact one-hot gather (a row's place = the staged
    rows of lower slots + its rank among its own slot's, from the
    [W, T2] membership and the 128 x 128 triangle the scan ranks by;
    stable, so a slot's sums stay in row order; skipped where one slot
    holds every staged row). A slot is then a contiguous run, and each
    128-row block holds slots first..last: for each that has a row in
    it, the block's slot-free selected weight rows P (a channel
    multiplicand where the bin's select digit is l, else 0: what
    _root_weight_rows x ``lo == l`` is to the root kernel) contract
    against the lane-digit one-hots Q of THAT slot's rows into that
    slot's accumulator, the products of two different features' digits
    dropped on the way. Q's compare folds the slot in: a row's key is
    ``digit + H x slot`` (slot 1-based, 0 = no row), slot k's one-hot
    row h is ``key == h + H x (k + 1)``. The digits are the root
    kernel's the other way round: the H lanes take the bin's LOW digit
    (bin = l x H + h), so that a plane's H lanes are H neighbouring
    bins and the wrapper's transpose to [W, F, B, 3] moves runs of H
    (the high digit on the lanes cost it 4.83 against 3.08 ms a pass at
    700 features: PERF.md section 5, PR 35, call C).

    One [R, 128] x [128, 128] dot is one weight load, and a region of
    few of them between two scalar reads leaves the MXU idle while it
    fills and drains: so the blocks' scalars (first slot, the one past
    the last, which of those between hold a row) are all read ahead of
    the dots, from one [W, blocks] compare, and the dots of a block's
    FIRST slot, which every block that holds a row has, stand in one
    straight line with its build, WAVE_SPLIT_UNROLL blocks at a time;
    only a block's further slots take a loop of their own.

    staged_ref: [C, T2] f32: ``nch`` channel multiplicands, the slot,
                then (from _PAY_ROWS) the F stored bin rows
    ord_ref:    [C, T2] f32 scratch, the same in slot order
    key_ref:    [U, C - _PAY_ROWS, 128] i32 scratch, U blocks' keys
    p_ref:      [U, groups, R, 128] scratch, U blocks' P by group
    span_ref:   [2 x T2 / 128] i32 SMEM scratch, the blocks' scalars
    acc_ref:    [W, groups, nch x L, 128] f32 scratch: row (channel, l),
                lane (feature of the group, h)
    """
    i32, f32 = jnp.int32, jnp.float32
    dt = f32 if exact_dot else jnp.bfloat16
    prec = (jax.lax.Precision.HIGHEST if exact_dot
            else jax.lax.Precision.DEFAULT)
    H, L, gf, nl = geom["H"], geom["L"], geom["gf"], geom["nl"]
    groups = geom["groups"]
    T2 = staged_ref.shape[1]
    LB = COMPACT_TILE_UNIT
    NB = T2 // LB
    U = key_ref.shape[0]
    slot_row = nch

    def dot_lanes(a, b):
        """[M, n] x [N, n] -> [M, N]: the lane axis of both contracts,
        as in every dot of this file."""
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=f32)

    srow = staged_ref[slot_row:slot_row + 1, :]              # [1, T2]
    k_iota = jax.lax.broadcasted_iota(i32, (W, 1), 0)
    k1 = (k_iota + 1).astype(f32)
    m = (srow == k1).astype(f32)                             # [W, T2]
    tot_c = jnp.sum(m, axis=1, keepdims=True)                # [W, 1]
    # rows of slots up to and including each slot / before it
    incl_c = jnp.sum(((srow > 0.0) & (srow <= k1)).astype(f32), axis=1,
                     keepdims=True)
    offs_c = incl_c - tot_c
    n_tot = jnp.sum(tot_c)
    mixed = jnp.sum((tot_c > 0.0).astype(f32)) > 1.5

    @pl.when(mixed)
    def _():
        lower = jnp.sum(jnp.where(k1 < srow, tot_c, 0.0), axis=0,
                        keepdims=True)                       # [1, T2]
        tri = (jax.lax.broadcasted_iota(i32, (LB, LB), 0)
               < jax.lax.broadcasted_iota(i32, (LB, LB), 1)).astype(dt)
        own, before = [], jnp.zeros((W, 1), f32)
        for b in range(NB):
            blk = m[:, b * LB:(b + 1) * LB]                  # [W, LB]
            within = jax.lax.dot_general(
                blk.astype(dt), tri,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=f32)
            own.append(jnp.sum(blk * (within + before), axis=0,
                               keepdims=True))
            before = before + jnp.sum(blk, axis=1, keepdims=True)
        place = jnp.where(srow > 0.0,
                          lower + jnp.concatenate(own, axis=1),
                          -1.0).astype(i32)                  # [1, T2]
        src = staged_ref[...].astype(dt)
        oc = min(autotune.WAVE_SPLIT_ORDER_COLS, T2)
        col = jax.lax.broadcasted_iota(i32, (oc, 1), 0)
        for j in range(T2 // oc):
            perm = (col + j * oc == place).astype(dt)        # [oc, T2]
            ord_ref[:, j * oc:(j + 1) * oc] = dot_lanes(src, perm)

    @pl.when(jnp.logical_not(mixed))
    def _():
        ord_ref[...] = staged_ref[...]

    # ---- the blocks' scalars, all ahead of the dots ----
    # Block b holds places [128 b, 128 b + 128): its first slot is the
    # number of slots that end at or before its start, the one past its
    # last the number that start before its end; a slot between them
    # holds a row of it where its run reaches into it. One [W, blocks]
    # compare, two f32-exact sums a block: first + 64 x stop, and the
    # slots past the first that hold a row as bits (KNOWN of them; a
    # slot further on is dotted unseen)
    KNOWN = 24
    pos0 = (jax.lax.broadcasted_iota(i32, (1, NB), 1) * LB).astype(f32)
    started = offs_c < jnp.minimum(pos0 + LB, n_tot)         # [W, NB]
    first_r = jnp.sum((incl_c <= pos0).astype(f32), axis=0, keepdims=True)
    span_r = first_r + 64.0 * jnp.sum(started.astype(f32), axis=0,
                                      keepdims=True)
    here = started & (incl_c > pos0) & (tot_c > 0.0)
    rel = k_iota - first_r.astype(i32)                       # [W, NB]
    seen = here & (rel >= 1) & (rel <= KNOWN)
    bits_r = jnp.sum(jnp.where(
        seen, jnp.left_shift(1, jnp.clip(rel - 1, 0, KNOWN - 1)), 0
    ).astype(f32), axis=0, keepdims=True)
    b_iota = jax.lax.broadcasted_iota(i32, (1, NB), 1)
    for b in range(NB):
        at_b = b_iota == b
        span_ref[2 * b] = jnp.sum(jnp.where(at_b, span_r, 0.0)).astype(i32)
        span_ref[2 * b + 1] = jnp.sum(
            jnp.where(at_b, bits_r, 0.0)).astype(i32)
    n_blocks = jnp.sum(jnp.max(here.astype(f32), axis=0,
                               keepdims=True)).astype(i32)
    n_pairs = jnp.sum(here.astype(f32)).astype(i32)

    l_iota = jax.lax.broadcasted_iota(i32, (L, 1), 0)
    h_iota = jax.lax.broadcasted_iota(i32, (H, 1), 0)
    lane_s = jax.lax.broadcasted_iota(i32, (1, gf * H), 1) // H

    def slot_dots(u, k, p, weight_rows):
        """acc_ref[k, p] += block u's group p over slot k's rows."""
        n_live = min(gf, F - p * gf)
        key = h_iota + H * (k + 1)                           # [H, 1]
        qs = [(key_ref[u, p * gf + s:p * gf + s + 1, :] == key).astype(dt)
              for s in range(n_live)]
        if n_live < gf:
            qs.append(jnp.zeros(((gf - n_live) * H, LB), dt))
        res = dot_lanes(weight_rows, jnp.concatenate(qs, axis=0))
        # feature s keeps block (s, s) of the product
        own = jnp.zeros((nl, gf * H), f32)
        for s in range(n_live):
            own = jnp.where(lane_s == s, res[s * nl:(s + 1) * nl, :], own)
        acc_ref[k, p] += own

    def first_slots(b0):
        """U blocks' builds and first slots' dots, one straight line. A
        block past the staged rows holds zero weights: its dots add
        nothing, to slot W - 1."""
        for u in range(U):
            b = b0 + u
            lanes = pl.ds(pl.multiple_of(b * LB, LB), LB)
            k = jnp.minimum(jnp.bitwise_and(span_ref[2 * b], 63), W - 1)
            # the block's rows: whole sublane tiles at a lane offset
            pay = ord_ref[0:_PAY_ROWS, lanes]                # [16, LB]
            x = ord_ref[_PAY_ROWS:, lanes].astype(i32)
            lane_d = jnp.bitwise_and(x, H - 1)
            sel_d = jax.lax.shift_right_logical(x, H.bit_length() - 1)
            key_ref[u] = lane_d + H * pay[slot_row:slot_row + 1,
                                          :].astype(i32)
            wb = [jnp.broadcast_to(pay[c:c + 1, :], (L, LB))
                  for c in range(nch)]
            for p in range(groups):
                n_live = min(gf, F - p * gf)
                ps = []
                for s in range(n_live):
                    hit = sel_d[p * gf + s:p * gf + s + 1, :] == l_iota
                    ps += [jnp.where(hit, w, 0.0) for w in wb]
                if n_live < gf:
                    ps.append(jnp.zeros(((gf - n_live) * nl, LB), f32))
                weight_rows = jnp.concatenate(ps, axis=0).astype(dt)
                p_ref[u, p] = weight_rows
                slot_dots(u, k, p, weight_rows)

    def further_slots(b0):
        for u in range(U):
            span, bits = span_ref[2 * (b0 + u)], span_ref[2 * (b0 + u) + 1]
            first = jnp.bitwise_and(span, 63)

            def slot(k, c):
                r = k - first - 1

                @pl.when((r >= KNOWN) | (jnp.bitwise_and(jnp.right_shift(
                    bits, jnp.minimum(r, KNOWN - 1)), 1) > 0))
                def _():
                    for p in range(groups):
                        slot_dots(u, k, p, p_ref[u, p])

                return c

            jax.lax.fori_loop(first + 1, jnp.right_shift(span, 6), slot, 0)

    def some_blocks(q, c):
        @pl.when((q * (U * LB)).astype(f32) < n_tot)
        def _():
            first_slots(q * U)
            further_slots(q * U)

        return c

    jax.lax.fori_loop(0, NB // U, some_blocks, 0)
    return n_blocks, n_pairs


@functools.partial(jax.jit, static_argnames=("num_bins", "chunk",
                                             "interpret", "precision",
                                             "any_cat", "count_proxy",
                                             "packed4", "num_features",
                                             "dequant", "variant",
                                             "compact", "feature_tile",
                                             "split", "stage_rows"))
def fused_partition_histogram_pallas(bins_t, g, h, sample_mask,
                                     leaf_ids, tbl, *, num_bins,
                                     chunk=2048, interpret=False,
                                     precision="highest",
                                     gh_scale=None, any_cat=True,
                                     count_proxy=False, packed4=False,
                                     num_features=None, dequant=True,
                                     variant="hilo5", compact=None,
                                     feature_tile=None, split=None,
                                     stage_rows=None):
    """Partition one wave + build its smaller-child histograms in ONE
    data pass. Returns (new_leaf_ids [N], hist [W, F, B, 3], work) —
    or, with ``count_proxy``, (new_leaf_ids, hist [W, F, B, 2],
    cnt_right [W], work). ``work`` is a [3] int32: rows scanned, rows
    put through the dot, both in units of COMPACT_TILE_UNIT, and the
    block-dots those rows met: one a unit where every slot's lanes
    share a one-hot dot, the (block, slot) pairs where the flush dots a
    slot at a time.

    Only rows that sit in one of the wave's smaller children and carry
    weight can change a sum, and the one-hot dot is what a row costs:
    where it is dear enough (autotune.hist_compact_tile, from the
    dot's MACs a row) the kernel packs those rows ahead of the dot and
    dots whole tiles of them (_fused_kernel). ``compact`` = True /
    False overrides that choice — for tests and for the measurement
    that sets its threshold, never from a parameter.

    Where the kernel compacts and the root's digit split applies
    (autotune.wave_split_applies: the bf16 tiers at 57 to 256 byte
    bins) the flush puts its staged rows in slot order and dots each
    128-row block against the slots it holds, by the root kernel's two
    digits (_flush_by_slot): a sixth to a third of the one-hot dot's
    MACs at 255 bins, the same products, added up in another order.
    ``split`` = True / False and ``stage_rows`` (rows staged ahead of a
    flush; autotune.WAVE_SPLIT_STAGE_ROWS) override the rule and the
    constant, for tests and the measurements beside them alone.

    Where one resident block cannot hold every feature's accumulator,
    bin rows and compaction payload, the pass walks tiles of features
    (autotune.hist_feature_tiling, a trace-time choice from the shapes;
    ``feature_tile`` = stored bin rows a tile forces one, for tests):
    the wave's W split columns are gathered once and handed to every
    tile, which routes and compacts its rows alike and dots its own
    features (_fused_kernel).

    tbl: [18, W] int32 packed split table (TBL_* rows: 10 scalar
    fields + 8 categorical bitset words). g/h must be pre-masked by
    sample_mask; counts use the mask channel. Only the feature-major
    bins are read — the partition selects feature rows.

    precision="int8": g/h are pre-quantized integer-valued floats
    (tpu_quantized_hist); sums accumulate exactly in int32 at 2x MXU
    rate and ``gh_scale`` dequantizes the output. ``dequant=False``
    returns the histogram in its RAW int32 representation instead —
    the quantized-psum wire format the data-parallel learner reduces
    across the mesh before dequantizing (ops/wave_grower.py).

    count_proxy (int8 only): drop the count channel from the MXU dot
    (2 channels x W <= 128 -> waves up to 64 wide, fewer full-data
    passes per tree). The returned ``cnt_right`` holds each slot's
    EXACT in-bag row count moved to the new (right) child; per-bin
    count estimates are synthesized downstream (wave_grower).

    packed4 (count-proxy or hi/lo exact tier): ``bins_t`` is
    [ceil(F/2), N] with TWO features' 4-bit bins per byte (feature 2p
    in the low nibble of row p) — half the HBM residency for
    max_bin <= 16 datasets, like the reference's Dense4bitsBin
    (dense_nbits_bin.hpp); the kernel unpacks nibbles in VMEM. The
    nibble unpack is channel-layout-independent, so the exact hi/lo
    variants compose with it. ``num_features`` gives the logical F.
    """
    F, n = bins_t.shape
    if packed4:
        if num_bins > 16:
            raise NotImplementedError("packed4 needs max_bin <= 16")
        if not (count_proxy or precision == "highest"):
            raise NotImplementedError(
                "packed4 needs the count-proxy or hi/lo exact tier")
        F = int(num_features)
    W = int(tbl.shape[1])
    B = num_bins
    int8 = precision == "int8"
    if count_proxy and not int8:
        raise NotImplementedError("count_proxy requires precision='int8'")
    hilo = precision == "highest"
    variant = variant if hilo else None
    cap = (FUSED_MAX_WAVE_INT8_NC if int8 and count_proxy
           else FUSED_MAX_WAVE_INT8 if int8
           else {"hilo5": FUSED_MAX_WAVE_HILO,
                 "hilo4": FUSED_MAX_WAVE_HILO4,
                 "hilo3": FUSED_MAX_WAVE_HILO3}[variant] if hilo
           else FUSED_MAX_WAVE)
    if W > cap:
        raise NotImplementedError(f"fused wave needs W <= {cap}")
    if int8 and 127 * (n + (-n) % chunk) >= 2 ** 31:
        raise NotImplementedError(
            "int8 histogram sums could overflow int32 beyond ~16.9M "
            "rows; disable tpu_quantized_hist")
    nchan = ((2 if count_proxy else 3) if int8
             else _exact_nchan(variant) if hilo else 4)
    # tile geometry + block shapes from the shared source of truth the
    # autotuner's VMEM predicate prices (ops/autotune.py); ``geom`` is
    # ONE feature tile's, the whole matrix's where one tile holds it
    geom, n_tiles = autotune.hist_feature_tiling(
        F=F, B=B, W=W, chunk=chunk, fused=True, F_rows=bins_t.shape[0],
        bins_bytes=bins_t.dtype.itemsize, int8=int8,
        count_proxy=count_proxy, variant=variant, force=feature_tile,
        split=split, stage_rows=stage_rows)
    tiled = n_tiles > 1
    Bp, group_sz, gb = geom["Bp"], geom["group_sz"], geom["gb"]
    groups, gb_pad = geom["groups"], geom["gb_pad"]

    pad = (-n) % chunk
    if pad:
        bins_t = jnp.pad(bins_t, ((0, 0), (0, pad)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        sample_mask = jnp.pad(sample_mask, (0, pad))
        leaf_ids = jnp.pad(leaf_ids, (0, pad), constant_values=-1)
    n_pad = n + pad

    ghm = jnp.stack([
        g.astype(jnp.float32), h.astype(jnp.float32),
        sample_mask.astype(jnp.float32),
        jnp.zeros_like(g, jnp.float32)], axis=0)          # [4, N]
    leaf2d = leaf_ids.astype(jnp.int32)[None, :]          # [1, N]
    # transposed table: row k = slot k, col j = field j
    tblT = jnp.pad(tbl.astype(jnp.int32).T,
                   ((0, 128 - W), (0, TBL_ROWS - tbl.shape[0])),
                   constant_values=-1)                     # [128, 16]

    T = autotune.hist_compact_tile(
        geom=geom, chunk=chunk, bins_bytes=bins_t.dtype.itemsize,
        int8=int8, force=compact)
    sg = autotune.fused_wave_split(
        geom=geom, compact_tile=T, int8=int8, count_proxy=count_proxy,
        variant=variant, force=split)
    if split and not sg:
        raise NotImplementedError(
            "the digit split serves the compacting bf16 tiers at 57 to "
            "256 byte bins")
    T2 = stage_rows or autotune.WAVE_SPLIT_STAGE_ROWS
    if sg and T2 % math.lcm(autotune.WAVE_SPLIT_UNROLL * COMPACT_TILE_UNIT,
                            T):
        raise ValueError(f"stage_rows {T2}: whole {T}-row windows and "
                         f"whole runs of {autotune.WAVE_SPLIT_UNROLL} "
                         "blocks")
    exact_dot = interpret and not int8
    kernel = functools.partial(
        _fused_kernel, F=geom["F"], B=B, W=W, groups=groups,
        group_sz=group_sz, variant=variant, exact_dot=exact_dot,
        int8=int8, any_cat=any_cat, count_proxy=count_proxy,
        packed4=packed4, compact_tile=T, tiled=tiled, split=sg)

    blk = autotune.fused_hist_block_shapes(chunk=chunk, geom=geom,
                                           tbl_rows=TBL_ROWS,
                                           compact_tile=T, tiled=tiled,
                                           split=sg, W=W, stage_rows=T2)
    grid, at = _tile_grid(n_tiles, n_pad // chunk)
    # every tile's accumulator is one block of the output's first axis
    hist_all = (n_tiles * blk["hist"][0],) + blk["hist"][1:]
    hist_at = (lambda t, i: (t, 0, 0, 0)) if sg else (lambda t, i: (t, 0, 0))
    operands = [tblT, bins_t, ghm, leaf2d]
    in_specs = [
        pl.BlockSpec(blk["tbl"], at(lambda t, i: (0, 0)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(blk["bins"], at(lambda t, i: (t, i)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(blk["ghm"], at(lambda t, i: (0, i)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(blk["leaf"], at(lambda t, i: (0, i)),
                     memory_space=pltpu.VMEM),
    ]
    if tiled:
        # the wave's split columns, one row a slot: a tile holds only
        # its own features' rows, so the W rows the partition reads are
        # taken here, once a pass (W x N bytes beside the F x N the
        # pass streams anyway)
        feat = jnp.maximum(tbl[TBL_FEAT].astype(jnp.int32), 0)
        if packed4:
            byte = take_rows(bins_t, feat // 2)
            cols = jnp.where((feat % 2 == 1)[:, None],
                             jnp.right_shift(byte, 4),
                             jnp.bitwise_and(byte, 15))
        else:
            cols = take_rows(bins_t, feat)                 # [W, N]
        operands.append(jnp.pad(
            cols, ((0, blk["cols"][0] - W), (0, 0))))
        in_specs.append(pl.BlockSpec(blk["cols"],
                                     at(lambda t, i: (0, i)),
                                     memory_space=pltpu.VMEM))
    out_specs = [
        pl.BlockSpec(blk["hist"], at(hist_at), memory_space=pltpu.VMEM),
        pl.BlockSpec(blk["leaf_out"], at(lambda t, i: (0, i)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(hist_all,
                             jnp.int32 if int8 else jnp.float32),
        jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        jax.ShapeDtypeStruct((2 if sg else 1,), jnp.int32),
    ]
    scratch = []
    if T:
        xdt = jnp.float32 if exact_dot else jnp.bfloat16
        scratch = [pltpu.VMEM(blk["x"], xdt),
                   pltpu.VMEM(blk["sel"], jnp.float32),
                   pltpu.VMEM(blk["staged"], jnp.float32),
                   pltpu.SMEM((1,), jnp.int32)]
    if sg:
        scratch += [pltpu.VMEM(blk["ord"], jnp.float32),
                    pltpu.VMEM(blk["key"], jnp.int32),
                    pltpu.VMEM(blk["p"], xdt),
                    pltpu.SMEM((2 * T2 // COMPACT_TILE_UNIT,), jnp.int32),
                    pltpu.VMEM(blk["acc"], jnp.float32)]
    if count_proxy:
        out_specs.append(pl.BlockSpec(blk["cnt"],
                                      at(lambda t, i: (0, 0)),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct(blk["cnt"], jnp.float32))
    elif variant == "hilo4" and not sg:
        # second histogram-shaped accumulator: the count-dot channels
        out_specs.append(pl.BlockSpec(blk["hist"],
                                      at(lambda t, i: (t, 0, 0)),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct(hist_all, jnp.float32))
    groups = n_tiles * groups           # of the whole output from here
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        compiler_params=autotune.tpu_compiler_params(),
        name="fused_partition_histogram_pallas",
        interpret=interpret,
    )(*operands)
    hist, leaf_out = outs[0], outs[1]
    # without the split a unit of dotted rows is one block-dot
    work = jnp.stack([jnp.int32(n_pad // COMPACT_TILE_UNIT), outs[2][0],
                      outs[2][-1]])
    outs = outs[:2] + outs[3:]

    def ret(*vals):
        return vals + (work,)

    if sg:
        # [tiles x W, groups, (plane, l), (s, h)] -> [W, F, bin = l x H + h,
        # plane]: the lanes' digit is the bin's LOW one, so a plane's H
        # lanes stay side by side through the transpose (_flush_by_slot)
        gf = sg["gf"]
        hist = hist.reshape(n_tiles, W, sg["groups"], 3, sg["L"], gf,
                            sg["H"])
        hist = hist.transpose(1, 0, 2, 5, 4, 6, 3)
        hist = hist.reshape(W, n_tiles, sg["groups"] * gf,
                            sg["L"] * sg["H"], 3)
        return ret(leaf_out[0, :n], hist[:, :, :geom["F_rows"], :B]
                   .reshape(W, -1, B, 3)[:, :F])

    # [groups, gb_pad, 128] -> [F, B, nchan*W] -> [W, F, B, nchan'].
    # channel rows were [c*W + k]: reshape (nchan, W) then combine
    # (feature rows sit at the aligned Bp stride; slice back to B)
    hist = hist[:, :gb, :nchan * W].reshape(
        groups * group_sz, Bp, nchan * W)[:F, :B]
    hist = hist.reshape(F, B, nchan, W)
    if count_proxy:
        hist = hist.transpose(0, 1, 3, 2)                  # [F,B,W,2]
        if dequant:
            hist = hist.astype(jnp.float32) \
                * jnp.stack([jnp.float32(gh_scale[0]),
                             jnp.float32(gh_scale[1])])
        return ret(leaf_out[0, :n], hist.transpose(2, 0, 1, 3),
                   outs[2][:W, 0])
    if int8:
        hist = hist.transpose(0, 1, 3, 2)                  # [F,B,W,3]
        if dequant:
            hist = hist.astype(jnp.float32) * _qscale_vec(gh_scale)
        return ret(leaf_out[0, :n], hist.transpose(2, 0, 1, 3))
    if variant == "hilo5":
        hist = jnp.stack([hist[:, :, 0] + hist[:, :, 1],   # g = hi+lo
                          hist[:, :, 2] + hist[:, :, 3],   # h = hi+lo
                          hist[:, :, 4]], axis=2)          # count
    elif variant == "hilo4":
        cnt = outs[2][:, :gb, :W].reshape(
            groups * group_sz, Bp, W)[:F, :B]              # [F, B, W]
        hist = jnp.stack([hist[:, :, 0] + hist[:, :, 1],   # g = hi+lo
                          hist[:, :, 2] + hist[:, :, 3],   # h = hi+lo
                          cnt], axis=2)                    # count (dot 2)
    elif variant == "hilo3":
        hist = jnp.stack([hist[:, :, 0] + hist[:, :, 1],   # g = hi+lo
                          hist[:, :, 2],                   # h = count
                          hist[:, :, 2]], axis=2)          # count
    else:
        hist = jnp.stack([hist[:, :, 0] + hist[:, :, 1],   # g = hi+lo
                          hist[:, :, 2],                   # h (bf16)
                          hist[:, :, 3]], axis=2)          # count
    return ret(leaf_out[0, :n], hist.transpose(3, 0, 1, 2))
