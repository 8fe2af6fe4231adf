"""Exclusive Feature Bundling (EFB).

TPU-native counterpart of the reference's feature bundling
(reference: src/io/dataset.cpp:66-210 FindGroups/FastFeatureBundling,
NIPS'17 LightGBM paper §4). Mutually-exclusive sparse features share one
HBM column: member k owns the bin range [offset_k, offset_k + num_bin_k)
and column value 0 means "every member at its default bin".

Where the reference bakes bundling into FeatureGroup bin storage and
per-feature OrderedBin iterators, here it is a pure storage transform
around the wave grower's seams:

- the device bins tensor holds BUNDLE columns (F_bundles x N, narrower
  than F_members x N by the bundling ratio);
- after each wave histogram pass over bundles, member histograms are
  reconstructed by a gather + the default-bin complement
  (member_default = bundle_row_total - sum of the member's other bins
  — the "most frequent bin" trick of dense_bin.hpp);
- the partition decodes a member's bin from the bundle column:
  in-range -> col - offset, out-of-range (another member active or all
  defaults) -> the member's default bin.

Everything downstream (split search, SplitResult, TreeRecord, host
trees) keeps ORIGINAL member features and bin spaces.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..utils import log


EFB_SAMPLE_CNT = 50_000


def sample_rows_for_probe(n: int):
    """Row indices find_bundles would draw from an n-row bin matrix —
    THE sampling contract shared by the streamed-ingest probes
    (io/dataset.py, io/loader.py): same rng(3), same count. Returns
    None when find_bundles would use every row."""
    if n > EFB_SAMPLE_CNT:
        return np.random.default_rng(3).choice(n, EFB_SAMPLE_CNT,
                                               replace=False)
    return None


def would_bundle(sample_bins: np.ndarray, mappers,
                 max_conflict_rate: float) -> bool:
    """Bundling decision from a pre-binned probe sample (the rows
    ``sample_rows_for_probe`` selected): True iff find_bundles on the
    full matrix would bundle anything. One definition for both
    streamed-ingest callers so the bit-identical-bundling guarantee
    cannot de-synchronize."""
    if sample_bins.shape[1] <= 1:
        return False
    db = np.array([m.default_bin for m in mappers], np.int32)
    nb = np.array([m.num_bin for m in mappers], np.int32)
    bundles = find_bundles(sample_bins, db, nb, max_conflict_rate,
                           presampled=True)
    return len(bundles) < sample_bins.shape[1]


def find_bundles(bins: np.ndarray, default_bins: np.ndarray,
                 num_bins: np.ndarray, max_conflict_rate: float,
                 sample_cnt: int = EFB_SAMPLE_CNT,
                 max_bundle_bins: int = 255,
                 presampled: bool = False) -> List[List[int]]:
    """Greedy conflict-bounded grouping (Dataset::FindGroups,
    dataset.cpp:66-159): features ordered by non-default count; each
    joins the first bundle whose accumulated conflicts stay under
    ``max_conflict_rate * n`` and whose total bin width fits.
    ``presampled``: ``bins`` already IS the rng(3) row sample (the
    streamed-ingest probe, io/dataset.py _efb_would_bundle) — skip the
    internal subsample so both callers see identical rows."""
    n, f = bins.shape
    if f <= 1:
        return [[j] for j in range(f)]
    if n > sample_cnt and not presampled:
        idx = np.random.default_rng(3).choice(n, sample_cnt,
                                              replace=False)
        sample = bins[idx]
    else:
        sample = bins
    sn = sample.shape[0]
    nondefault = sample != default_bins[None, :]      # [sn, F] bool
    counts = nondefault.sum(axis=0)
    order = np.argsort(-counts, kind="stable")
    max_conflict = int(max_conflict_rate * sn)

    bundle_masks: List[np.ndarray] = []
    bundle_rows: List[int] = []          # rows some member is non-default in
    bundle_conflicts: List[int] = []
    bundle_bins_total: List[int] = []
    bundles: List[List[int]] = []
    for j in order:
        placed = False
        fj = nondefault[:, j]
        cj = int(counts[j])
        width = int(num_bins[j])
        for bi in range(len(bundles)):
            # what needs no pass over the rows first: the bin width, and
            # the least the two masks can overlap (|A & B| >= |A| + |B|
            # - n). Dense columns fail one or the other against every
            # bundle, and the search over F columns is F^2 / 2 of these
            # (2,000 dense columns: 457 s of row passes before)
            if bundle_bins_total[bi] + width > max_bundle_bins:
                continue
            room = max_conflict - bundle_conflicts[bi]
            if cj + bundle_rows[bi] - sn > room:
                continue
            conflict = int((bundle_masks[bi] & fj).sum())
            if conflict <= room:
                bundles[bi].append(int(j))
                bundle_masks[bi] |= fj
                bundle_rows[bi] += cj - conflict
                bundle_conflicts[bi] += conflict
                bundle_bins_total[bi] += width
                placed = True
                break
        if not placed:
            bundles.append([int(j)])
            bundle_masks.append(fj.copy())
            bundle_rows.append(cj)
            bundle_conflicts.append(0)
            bundle_bins_total.append(width)
    # keep member order stable inside each bundle
    return [sorted(b) for b in bundles]


def bundle_bins(bins: np.ndarray, bundles: List[List[int]],
                default_bins: np.ndarray, num_bins: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Encode member bins into bundle columns.

    Returns (bundled [N, F_b], member_bundle [F_m], member_offset [F_m],
    max_bundle_width). Column encoding: 0 = all members at default;
    member k non-default with bin b -> offset_k + b (later members win
    the allowed conflicts, dataset.cpp:186-199 merge semantics).
    """
    n, f = bins.shape
    fb = len(bundles)
    member_bundle = np.zeros(f, np.int32)
    member_offset = np.zeros(f, np.int32)
    width = 1
    for bi, members in enumerate(bundles):
        # offset 0 is reserved for the all-default col value only when
        # a bundle has >1 member; singleton bundles stay identity-coded
        if len(members) == 1:
            j = members[0]
            member_bundle[j] = bi
            member_offset[j] = 0
            width = max(width, int(num_bins[j]))
            continue
        off = 1
        for j in members:
            member_bundle[j] = bi
            member_offset[j] = off
            off += int(num_bins[j])
        width = max(width, off)
    out = np.zeros((n, fb), bins.dtype if width <= 256 else np.int32)
    for bi, members in enumerate(bundles):
        if len(members) == 1:
            out[:, bi] = bins[:, members[0]]
            continue
        col = np.zeros(n, np.int64)
        for j in members:
            nd = bins[:, j] != default_bins[j]
            col[nd] = member_offset[j] + bins[nd, j]
        out[:, bi] = col.astype(out.dtype)
    return out, member_bundle, member_offset, width


def expand_bundle_histogram(bundle_hist, member_bundle, member_offset,
                            member_num_bin, member_default_bin, B_out):
    """[..., F_b, B_bundle, 3] bundle histograms -> member histograms
    [..., F_m, B_out, 3] (jit-traceable; see module docstring for the
    default-bin complement)."""
    import jax.numpy as jnp
    mb = jnp.asarray(member_bundle)
    mo = jnp.asarray(member_offset)
    nb = jnp.asarray(member_num_bin)
    db = jnp.asarray(member_default_bin)
    Bb = bundle_hist.shape[-2]
    bidx = jnp.arange(B_out, dtype=jnp.int32)[None, :]       # [1, B]
    src = jnp.clip(mo[:, None] + bidx, 0, Bb - 1)            # [F_m, B]
    valid = (bidx < nb[:, None]) & ~(bidx == db[:, None])
    # gather member rows out of their bundles
    per_bundle = bundle_hist[..., mb, :, :]                  # [...,F_m,Bb,3]
    member = jnp.take_along_axis(
        per_bundle, src[(None,) * (per_bundle.ndim - 3)
                        + (slice(None), slice(None), None)],
        axis=-2)                                             # [...,F_m,B,3]
    member = member * valid[(None,) * (per_bundle.ndim - 3)
                            + (slice(None), slice(None), None)]
    # default-bin complement: bundle row total - member's other bins
    tot = bundle_hist.sum(axis=-2)[..., mb, :]               # [...,F_m,3]
    rest = member.sum(axis=-2)
    comp = (tot - rest)[..., None, :]                        # [...,F_m,1,3]
    at_default = (bidx == db[:, None])[(None,) * (per_bundle.ndim - 3)
                                       + (slice(None), slice(None),
                                          None)]
    return member + comp * at_default
