"""Whole-model device prediction as one jitted scan of MXU matmuls.

The reference predicts by walking every tree per row under OpenMP
(src/boosting/gbdt_prediction.cpp:9-30, include/LightGBM/tree.h:212-266).
A pointer walk is the wrong shape for a TPU — data-dependent hops defeat
both the MXU and the vector unit. Instead the whole ensemble is lowered
to three dense contractions per tree chunk:

1.  Host-side, every feature's node thresholds become closed-right bin
    edges; raw rows are binned once (exact float64 searchsorted). Every
    node becomes a *decision table* over its feature's bins — built by
    evaluating the node's own host decision function (missing handling,
    default-left, categorical bitsets: tree.h:183-201) at one
    representative value per bin, so the device path agrees with the
    host path by construction.
2.  ``C[n, s] = OH @ W`` — an int8 one-hot matmul looks up every node
    decision for every row at the int8 MXU rate.
3.  A per-tree batched einsum against the signed ancestor matrix
    ``P[t, s, l]`` (+1 = leaf l sits in s's left subtree, -1 = right)
    counts how many ancestor decisions point at each leaf; the row's
    leaf is the one whose count equals its depth. One more einsum with
    the leaf values accumulates per-class scores.

No gathers, no per-tree dispatch: a 500-tree model predicts in one
host->device upload per row chunk and ~T/TC fused scan steps.

Serving shape (ops/predict_cache.py): the dispatch is a pure function
of an explicit geometry key held in a process-wide registry, online
micro-batches pad to power-of-two serve buckets (bit-exact — rows are
independent in every kernel here and pad rows are sliced off), and
appending trees to an already-stacked model re-stacks ONLY the new
tree chunk (``extend``): a new threshold splits an existing bin into
sub-bins on which every OLD node's decision is constant (its own
threshold is a bin edge), so old decision-table rows are copied, not
re-evaluated.

Numerical note: leaf values and per-row score accumulation run in
float32 on device (the reference accumulates in double,
gbdt_prediction.cpp). Expect ~1e-7 RELATIVE error that grows with
leaf-value magnitude and tree count; for parity-sensitive comparisons
against the reference at f64 resolution, use the host prediction path
(``use_pallas=False`` routes chunks through the same f32 kernels —
the exact-f64 path is the per-tree host traversal, models/tree.py).
"""
from __future__ import annotations

import functools
from functools import partial
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune, predict_cache
from ..io.binning import MissingType
from ..obs import registry as obs
from ..obs import reqlog
from ..utils import log, timing

# decision_type bit layout (models/tree.py, mirroring tree.h)
K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2

_ZERO_EPS = 1e-35
# per-feature table-width cap: categorical features whose bitsets cover
# more distinct categories than this fall back to the host path
MAX_FEATURE_WIDTH = 1024
TREE_CHUNK = 16    # trees per scan/grid step (TC=16 measured ~10%
                   # faster than 8 at the 500-tree bench shape; wide
                   # models drop TC until the kernel blocks fit VMEM)
# fused-kernel working-set budget (shared with the autotuner, which
# prices the SAME block shapes the kernel's BlockSpecs are built from:
# ops/autotune.py forest_block_shapes / forest_vmem_bytes)
_PALLAS_VMEM_BUDGET = autotune.PALLAS_VMEM_BUDGET_BYTES


class StackedModel:
    """Host-built stacked arrays for a list of trees + the jitted runner.

    ``serve_bucket`` is the owning booster's ``tpu_serve_bucket`` policy
    (None = the process default installed by predict_cache.configure)."""

    def __init__(self, trees: List, num_features: int, num_class: int,
                 serve_bucket: Optional[int] = None):
        self.num_class = num_class
        self.num_trees = len(trees)
        self._serve_policy = serve_bucket
        self.ok = True
        try:
            self._build(trees, num_features)
        except _FallbackError as e:
            log.warning("stacked predict unavailable (%s); "
                        "host prediction path will be used", e)
            self.ok = False

    # -- host-side build ----------------------------------------------------

    def _build(self, trees: List, num_features: int) -> None:
        F = num_features
        self._F = F
        L = max([t.num_leaves for t in trees] + [2])
        S = L - 1

        # 1. per-feature edges / category sets from every node
        self._thr_sets: List[set] = [set() for _ in range(F)]
        self._cat_sets: List[set] = [set() for _ in range(F)]
        self._zero_mt = np.zeros(F, bool)
        self._is_cat = np.zeros(F, bool)
        self._scan_nodes(trees)

        # 2. per-feature representative values + binning data
        reps = self._rebuild_tables()

        # 3. decision tables, ancestor matrix, targets, leaf values
        W, P, tgt, leaf_val = self._stack_trees(trees, reps, S, L)
        if W.nbytes > (2 << 30):
            raise _FallbackError(f"W matrix {W.nbytes >> 20} MB")
        self._W_host = W
        self._P_host = P
        self._tgt_host = tgt
        self._leaf_host = leaf_val
        self._S, self._L = S, L
        self._dev_cache: dict = {}
        self._dispatch_memo: dict = {}
        predict_cache.count_stack(len(trees))

    def _scan_nodes(self, trees: List) -> None:
        """Accumulate every node's thresholds / category bitsets into
        the per-feature sets (the union layout the decision tables are
        binned against). Raises on shapes the stacker cannot host."""
        F = self._F
        for t in trees:
            for s in range(t.num_leaves - 1):
                f = t.split_feature[s]
                if f >= F:
                    raise _FallbackError(f"node feature {f} >= {F}")
                dt = t.decision_type[s]
                if dt & K_CATEGORICAL_MASK:
                    self._is_cat[f] = True
                    ci = t.threshold_in_bin[s]
                    lo, hi = t.cat_boundaries[ci], t.cat_boundaries[ci + 1]
                    for wi in range(lo, hi):
                        w = int(t.cat_threshold[wi]) & 0xFFFFFFFF
                        base = (wi - lo) * 32
                        while w:
                            b = (w & -w).bit_length() - 1
                            self._cat_sets[f].add(base + b)
                            w &= w - 1
                else:
                    self._thr_sets[f].add(float(t.threshold[s]))
                    if (dt >> 2) & 3 == MissingType.ZERO:
                        self._zero_mt[f] = True
        if np.any(self._is_cat & (np.array(
                [len(s) for s in self._thr_sets]) > 0)):
            raise _FallbackError("feature used both numerically and "
                                 "categorically")

    def _rebuild_tables(self) -> List[np.ndarray]:
        """Per-feature representative values, bin edges, table offsets
        and the device-binning fast-path arrays, all derived from the
        accumulated threshold/category sets. Returns the rep list.

        Numerical layout: [m closed-right bins][overflow][NaN].
        Categorical layout: [known cats][other][negative/NaN]."""
        F = self._F
        self._edges: List[Optional[np.ndarray]] = [None] * F
        self._cats: List[Optional[np.ndarray]] = [None] * F
        reps: List[np.ndarray] = []
        widths = np.zeros(F, np.int64)
        for f in range(F):
            if self._is_cat[f]:
                cs = np.array(sorted(self._cat_sets[f]), np.float64)
                if cs.size > MAX_FEATURE_WIDTH:
                    raise _FallbackError(
                        f"categorical feature {f} has {cs.size} "
                        f"distinct categories (> {MAX_FEATURE_WIDTH})")
                self._cats[f] = cs
                other = (cs.max() + 1.0) if cs.size else 1.0
                rep = np.concatenate([cs, [other, -1.0]])
            else:
                thr = set(self._thr_sets[f])
                if self._zero_mt[f]:
                    # isolate the reference's zero band |x| <= 1e-35
                    # (tree.h:188) into its own bin so a representative
                    # speaks for every value it covers
                    thr |= {np.nextafter(-_ZERO_EPS, -np.inf), _ZERO_EPS}
                edges = np.asarray(sorted(thr), np.float64)
                if edges.size > MAX_FEATURE_WIDTH:
                    raise _FallbackError(
                        f"feature {f} has {edges.size} thresholds")
                self._edges[f] = edges
                over = (np.nextafter(edges[-1], np.inf)
                        if edges.size else 0.0)
                rep = np.concatenate([edges, [over, np.nan]])
            # widths bucketed to 32 (8-aligned sublane starts are a
            # Mosaic requirement; the coarser bucket makes the kernel
            # SHAPE stable across models — e.g. every max_bin=63
            # feature lands on width 64 — so the predict registry and
            # persistent compile cache serve repeat predicts instead of
            # a fresh ~40 s Mosaic compile per model). Padded slots
            # have all-zero W rows and are never addressed by a code.
            widths[f] = -(-rep.size // 32) * 32
            reps.append(rep)
        self._rep_sizes = np.array([r.size for r in reps], np.int64)
        self._offsets = np.concatenate([[0], np.cumsum(widths)])
        self._Wtot = int(self._offsets[-1])

        # device-binning fast path (numerical features only): f32 edges
        # rounded DOWN so an f32 row compares exactly like f64 against
        # the f64 threshold (x <= t  <=>  x <= largest-f32 <= t, for
        # f32-representable x)
        self._dev_bin_ok = not any(c is not None for c in self._cats)
        if self._dev_bin_ok:
            m_max = max((e.size for e in self._edges if e is not None),
                        default=0)
            E = np.full((F, max(m_max, 1)), np.inf, np.float32)
            for f in range(F):
                e = self._edges[f]
                if e is None or e.size == 0:
                    continue
                # clip into f32 range BEFORE the cast: thresholds near
                # ±DBL_MAX would otherwise overflow to ±inf with a
                # RuntimeWarning. The clipped edge keeps the compare
                # semantics: any finite f32 x <= f32max < huge-t (left
                # stays left), and the bump below handles the negative
                # side exactly like any other not-f32-representable edge
                f32i = np.finfo(np.float32)
                ef = e.clip(f32i.min, f32i.max).astype(np.float32)
                bump = ef.astype(np.float64) > e
                ef[bump] = np.nextafter(ef[bump], -np.inf)
                E[f, :e.size] = ef
            self._E_f32 = E
            self._nan_slot = np.array(
                [self._offsets[f] + self._rep_sizes[f] - 1
                 for f in range(F)],
                np.int32)
            self._off32 = self._offsets[:F].astype(np.int32)
        return reps

    def _stack_trees(self, trees: List, reps: List[np.ndarray],
                     S: int, L: int):
        """Decision tables / ancestor matrices / leaf values for
        ``trees`` against the CURRENT table layout — called with the
        full ensemble at build and with only the appended chunk on an
        incremental ``extend``."""
        T = len(trees)
        Wtot = self._Wtot
        W = np.zeros((Wtot, T, S), np.int8)
        P = np.zeros((T, S, L), np.int8)
        tgt = np.full((T, L), 1e9, np.float32)   # padded leaves: no match
        leaf_val = np.zeros((T, L), np.float32)
        for ti, t in enumerate(trees):
            nl = t.num_leaves
            leaf_val[ti, :nl] = np.asarray(t.leaf_value[:nl], np.float32)
            for s in range(nl - 1):
                f = t.split_feature[s]
                o = self._offsets[f]
                W[o:o + self._rep_sizes[f], ti, s] = _node_table(
                    t, s, reps[f])
            # DFS: signed ancestor matrix + per-leaf left-count target
            if nl == 1:
                tgt[ti, 0] = 0.0
                continue
            stack2 = [(0, [])]           # node, ancestor (node, sign) list
            while stack2:
                node, anc = stack2.pop()
                for child, sign in ((t.left_child[node], 1),
                                    (t.right_child[node], -1)):
                    a2 = anc + [(node, sign)]
                    if child < 0:
                        lf = ~child
                        # E = (#left-ancestors gone left)
                        #   - (#right-ancestors gone left) == nLeft
                        # exactly when every ancestor decision points
                        # at this leaf
                        tgt[ti, lf] = sum(1 for _, sg in a2 if sg > 0)
                        for sn, sg in a2:
                            P[ti, sn, lf] = sg
                    else:
                        stack2.append((child, a2))
        return W, P, tgt, leaf_val

    # -- incremental stacking -----------------------------------------------

    def clone_for_extend(self) -> "StackedModel":
        """A shallow copy whose ``extend()`` cannot perturb a reader
        of the original — the copy-on-write half of the serving lock's
        publish protocol (models/gbdt.py _stacked_model): a predict()
        in flight on the ORIGINAL keeps a fully consistent model while
        the training thread extends the clone and publishes it.

        Only the containers ``extend`` mutates IN PLACE are duplicated
        (threshold/category sets, the role masks, the device-stack and
        dispatch memos — whose ``clear()`` would otherwise nuke the
        original's too); the big host tables and binning arrays are
        only ever REASSIGNED by extend, so sharing them until then is
        safe."""
        import copy
        new = copy.copy(self)
        new._thr_sets = [set(s) for s in self._thr_sets]
        new._cat_sets = [set(s) for s in self._cat_sets]
        new._zero_mt = self._zero_mt.copy()
        new._is_cat = self._is_cat.copy()
        new._dev_cache = dict(self._dev_cache)
        new._dispatch_memo = dict(self._dispatch_memo)
        return new

    def extend(self, new_trees: List) -> bool:
        """Append ``new_trees``, re-stacking ONLY the new tree chunk.

        Soundness of copying the old decision-table rows instead of
        re-evaluating every old node: a new threshold splits an
        existing bin into sub-bins that each lie WHOLLY inside the old
        bin, and an old node's decision is constant across any old bin
        (its own threshold is one of the bin edges; for zero-as-missing
        nodes the ±1e-35 band is an isolated bin whose sub-bins stay
        inside the band). New categories map to the old "other" slot —
        exactly the decision every old bitset gives them. So
        ``W_new[new_slot, old_trees] = W_old[old_code(new_rep)]`` where
        ``old_code`` is the ORIGINAL binning of the new representative
        values — the same function rows are binned with at predict.

        Returns False when the extension cannot be hosted (feature-role
        conflict, width cap, byte cap) — the caller falls back to a
        full rebuild, which will surface the same fallback if it is
        structural. The model is untouched on failure."""
        new_trees = list(new_trees)
        if not self.ok:
            return False
        if not new_trees:
            return True
        # snapshot everything the trial mutates, so a mid-flight
        # fallback restores the model exactly
        saved = ([set(s) for s in self._thr_sets],
                 [set(s) for s in self._cat_sets],
                 self._zero_mt.copy(), self._is_cat.copy(),
                 self._edges, self._cats, self._rep_sizes,
                 self._offsets, self._Wtot, self._dev_bin_ok,
                 getattr(self, "_E_f32", None),
                 getattr(self, "_nan_slot", None),
                 getattr(self, "_off32", None))
        old_edges, old_cats = self._edges, self._cats
        old_offsets = self._offsets
        S_old, L_old = self._S, self._L
        T_old = self.num_trees
        try:
            self._scan_nodes(new_trees)
            reps = self._rebuild_tables()
            L = max([L_old] + [t.num_leaves for t in new_trees])
            S = L - 1
            # old tables re-laid into the new slot layout: one fancy-
            # index copy per ensemble, no node re-evaluation
            W = np.zeros((self._Wtot, T_old + len(new_trees), S),
                         np.int8)
            for f in range(self._F):
                o_new = self._offsets[f]
                n_new = int(self._rep_sizes[f])
                src = _feature_codes(reps[f], old_edges[f], old_cats[f])
                W[o_new:o_new + n_new, :T_old, :S_old] = \
                    self._W_host[old_offsets[f] + src, :, :]
            Wn, Pn, tgtn, leafn = self._stack_trees(new_trees, reps,
                                                    S, L)
            if W.nbytes > (2 << 30):
                raise _FallbackError(f"W matrix {W.nbytes >> 20} MB")
            W[:, T_old:, :] = Wn
            P = np.concatenate([
                np.pad(self._P_host,
                       ((0, 0), (0, S - S_old), (0, L - L_old))), Pn])
            tgt = np.concatenate([
                np.pad(self._tgt_host, ((0, 0), (0, L - L_old)),
                       constant_values=1e9), tgtn])
            leaf = np.concatenate([
                np.pad(self._leaf_host, ((0, 0), (0, L - L_old))),
                leafn])
        except _FallbackError as e:
            # full restore — including the f32 edge planes, which a
            # SUCCESSFUL _rebuild_tables overwrites before a later
            # check (the W byte cap) can still raise
            (self._thr_sets, self._cat_sets, self._zero_mt,
             self._is_cat, self._edges, self._cats, self._rep_sizes,
             self._offsets, self._Wtot, self._dev_bin_ok,
             self._E_f32, self._nan_slot, self._off32) = saved
            log.info("incremental stack fell back (%s); rebuilding", e)
            return False
        self._W_host, self._P_host = W, P
        self._tgt_host, self._leaf_host = tgt, leaf
        self._S, self._L = S, L
        self.num_trees = T_old + len(new_trees)
        # stale device stacks / dispatch wrappers key off the old
        # geometry — drop them (uploads re-issue lazily per tree range)
        self._dev_cache.clear()
        self._dispatch_memo.clear()
        predict_cache.count_extend(len(new_trees))
        return True

    # -- prediction ---------------------------------------------------------

    def _bin_rows(self, X: np.ndarray) -> np.ndarray:
        """[N, F] float64 -> global one-hot column codes [N, Fm] int32
        (model features only; surplus input columns are ignored)."""
        N = X.shape[0]
        Fm = len(self._offsets) - 1
        codes = np.zeros((N, Fm), np.int32)
        nanc = np.full(N, np.nan)
        for f in range(Fm):
            x = X[:, f] if f < X.shape[1] else nanc
            codes[:, f] = self._offsets[f] + _feature_codes(
                x, self._edges[f], self._cats[f])
        return codes

    def _stack_range(self, key, first: int, ntree: int, Sp: int,
                     Lp: int, tgt_dtype, TC: int):
        """Shared stacker for the scan (Sp=S, Lp=L) and Pallas
        (MXU-tile-padded) layouts: slice the tree range, pad to a TC
        multiple, and shape [steps, ...] chunk stacks."""
        hit = self._dev_cache.get(key)
        if hit is not None:
            return hit
        # bounded: a learning-curve loop (predict at 10, 20, ... trees)
        # would otherwise pin one device copy of W/P per tree range
        while len(self._dev_cache) >= 4:
            self._dev_cache.pop(next(iter(self._dev_cache)))
        TC = min(TC, max(ntree - first, 1))
        nt = ntree - first
        steps = -(-nt // TC)
        pad = steps * TC - nt
        S, L = self._S, self._L
        sl = slice(first, ntree)

        def padT(a, fill=0.0):
            a = a[sl]
            if pad:
                shape = (pad,) + a.shape[1:]
                a = np.concatenate(
                    [a, np.full(shape, fill, a.dtype)], axis=0)
            return a

        W = np.transpose(self._W_host, (1, 0, 2))[sl]       # [nt, Wtot, S]
        if pad:
            W = np.concatenate(
                [W, np.zeros((pad,) + W.shape[1:], np.int8)])
        W = np.pad(W, ((0, 0), (0, 0), (0, Sp - S)))
        W = (W.reshape(steps, TC, self._Wtot, Sp)
              .transpose(0, 2, 1, 3)
              .reshape(steps, self._Wtot, TC * Sp))
        P = np.pad(padT(self._P_host),
                   ((0, 0), (0, Sp - S), (0, Lp - L)))
        P = P.reshape(steps, TC, Sp, Lp)
        tgt = np.pad(padT(self._tgt_host, 1e9).astype(np.float64),
                     ((0, 0), (0, Lp - L)), constant_values=1e9)
        if tgt_dtype == np.int32:
            tgt = np.minimum(tgt, 2 ** 30)
        tgt = tgt.astype(tgt_dtype).reshape(steps, TC, Lp)
        leaf = np.pad(padT(self._leaf_host),
                      ((0, 0), (0, Lp - L))).reshape(steps, TC, Lp)
        cls = (np.arange(first, first + steps * TC) % self.num_class)
        clsOH = np.eye(self.num_class, dtype=np.float32)[cls].reshape(
            steps, TC, self.num_class)
        if pad:   # padded trees: no leaf ever matches, but zero the class
            clsOH[-1, TC - pad:, :] = 0.0
        out = (jnp.asarray(W), jnp.asarray(P.astype(np.int8)),
               jnp.asarray(tgt), jnp.asarray(leaf), jnp.asarray(clsOH))
        self._dev_cache[key] = out
        return out

    def _tree_chunk(self) -> int:
        """Trees per scan step (XLA path): halved for wide models so the
        intermediate C matrix stays reasonable."""
        return TREE_CHUNK if self._Wtot <= 4096 else TREE_CHUNK // 2

    def _pallas_tc(self, row_tile: int = autotune.DEFAULT_ROW_TILE
                   ) -> Optional[int]:
        """Trees per grid step for the fused forest kernel, sized from
        the kernel's ACTUAL VMEM blocks (not just Wtot): the
        double-buffered W ([Wtot, TC*Sp] int8) and P ([TC, Sp, Lp] int8)
        inputs plus the in-kernel C/one-hot temporaries all scale with
        TC and the 128-padded S/L, so a large-num_leaves model can blow
        the budget at a modest Wtot. The byte estimate is
        autotune.forest_vmem_bytes — priced from the SAME block shapes
        forest_predict_pallas builds its BlockSpecs from. Returns None
        when even TC=1 does not fit — predict() then routes to the XLA
        scan path instead of tripping a Mosaic compile error on
        device."""
        Sp = -(-self._S // 128) * 128
        Lp = -(-self._L // 128) * 128
        # K/F default for skeleton callers (tests size the guard with
        # only _S/_L/_Wtot set); both terms are minor
        K = max(getattr(self, "num_class", 1), 1)
        offs = getattr(self, "_offsets", None)
        F = max(len(offs) - 1, 0) if offs is not None else 0
        tc = TREE_CHUNK
        while tc >= 1:
            est = autotune.forest_vmem_bytes(
                F=F, Wtot=self._Wtot, TC=tc, Sp=Sp, Lp=Lp, K=K,
                row_tile=row_tile)
            if est <= _PALLAS_VMEM_BUDGET:
                return tc
            tc //= 2
        return None

    def _device_arrays(self, first: int, ntree: int):
        return self._stack_range((first, ntree), first, ntree,
                                 self._S, self._L, np.float32,
                                 self._tree_chunk())

    def _dispatch(self, key: tuple, builder):
        """Registry-backed dispatch memo: the process registry is
        consulted ONCE per (model, geometry) — so its hit/miss counts
        measure CROSS-model reuse (the retrain case), not per-chunk
        call traffic."""
        fn = self._dispatch_memo.get(key)
        if fn is None:
            # jit-capture: ok(builder) — forwarding seam: the real
            # builders are audited at their _dispatch call sites
            fn = predict_cache.get(key, builder)
            self._dispatch_memo[key] = fn
        return fn

    def _stream(self, rows, N: int, chunk: int, prep_layout, runner):
        """Host prep (slice + pad-to-bucket + layout) for each row
        chunk on the ingest prefetch worker (io/ingest.py), device
        dispatch as chunks arrive, ordered async handles returned —
        chunk k's d2h overlaps chunk k+1's prep and compute. A single
        chunk skips the worker thread entirely (online micro-batches
        must not pay a thread spawn per request)."""

        def prep(c0):
            part = rows[c0:c0 + chunk]
            nrows = part.shape[0]
            if nrows < chunk:
                # pad to the full bucket shape so every chunk reuses
                # one compiled program (padded rows produce garbage
                # scores/leaves, sliced off by the caller)
                part = np.concatenate([part, np.zeros(
                    (chunk - nrows,) + part.shape[1:], part.dtype)])
            return prep_layout(part), nrows

        if N <= chunk:
            parts = [prep(0)]
        else:
            from ..io.ingest import prefetch
            parts = prefetch(((lambda c0=c0: prep(c0))
                              for c0 in range(0, N, chunk)),
                             wait_span=None)
        return [(runner(part), nrows) for part, nrows in parts]

    def warmup(self, rows: int = 1) -> bool:
        """Run one throwaway predict over ``rows`` zero rows so the
        device stacks upload and the serve-bucket program for this
        batch shape compiles NOW, not on the first live request — the
        publish seam of a retrain-while-serve swap (lrb.py) calls this
        on the trainer thread before the new model goes live, so the
        post-swap request stream never pays the cold tail. A
        same-geometry predecessor makes this a registry hit
        (ops/predict_cache.py) and the cost is one warm dispatch."""
        if not self.ok:
            return False
        self.predict(np.zeros((max(int(rows), 1), self._F),
                              np.float64))
        return True

    def predict(self, X: np.ndarray, first: int = 0,
                ntree: Optional[int] = None,
                pred_leaf: bool = False,
                row_chunk: int = 262144,
                use_pallas: Optional[bool] = None) -> np.ndarray:
        """Raw scores [K, N] (or leaf indices [N, ntree-first] int32)."""
        ntree = self.num_trees if ntree is None else min(ntree,
                                                         self.num_trees)
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        Fm = len(self._offsets) - 1
        # device binning when rows are f32-exact and all-numerical:
        # skips the host searchsorted pass AND halves the upload.
        # Probe a small sample first so ineligible inputs (true f64
        # data) don't pay a full-matrix round-trip scan.
        dev_bin = self._dev_bin_ok and X.shape[1] >= Fm
        rows = None
        # overflow in these casts is EXPECTED for not-f32-exact data
        # (values beyond f32 range become inf, _f32_exact rejects them
        # and the host binning path runs) — don't warn about it
        with np.errstate(over="ignore"):
            if dev_bin:
                probe = X[:64, :Fm]
                dev_bin = _f32_exact(probe, probe.astype(np.float32))
            if dev_bin:
                Xf = X[:, :Fm].astype(np.float32)
                dev_bin = _f32_exact(X[:, :Fm], Xf)
                rows = Xf if dev_bin else None
        if rows is None:
            rows = self._bin_rows(X)
        N = X.shape[0]
        from ..utils.device import on_tpu
        tpu = on_tpu()
        # route by device kind: the TPU takes the fused kernel, every
        # other platform runs the XLA scan (use_pallas forces the
        # kernel either way — off the TPU it runs in interpret mode,
        # which is how the tier-1 parity suite drives it)
        forest = tpu if use_pallas is None else bool(use_pallas)
        # VMEM guard from the kernel's ACTUAL block bytes (W, P, C,
        # one-hot all scale with TC x padded S/L, not just Wtot):
        # _pallas_tc halves the tree chunk until the blocks fit and
        # returns None for models that cannot fit at all — those use
        # the XLA scan path instead of crashing the fused kernel.
        tc = self._pallas_tc() if forest else None
        row_tile = autotune.DEFAULT_ROW_TILE
        if forest and tc is None:
            # the default row tile can miss the VMEM budget where a
            # smaller one fits (row_tile-scaled blocks dominating at
            # large Wtot/Sp) — try the smaller candidate tiles before
            # surrendering to the XLA scan path
            for rt in (1024, 512):
                tc = self._pallas_tc(rt)
                if tc is not None:
                    row_tile = rt
                    break
        if forest and tc is None:
            # never a silent stand-in: the XLA scan answers the same
            # question, but a caller who asked the accelerator for the
            # fused kernel must be able to see (and a smoke run to
            # assert) that it did not get it
            obs.counter("predict/forest_surrenders").add(1)
            (log.warning if tpu else log.info)(
                "fused forest kernel does not fit VMEM at any tile "
                "(Wtot=%d, S=%d, L=%d): predicting through the XLA "
                "scan instead", self._Wtot, self._S, self._L)
        forest = forest and tc is not None
        offs = tuple(int(o) for o in self._offsets)
        m_max = self._E_f32.shape[1] if dev_bin else 0
        device = autotune.device_kind()
        if forest and not pred_leaf:
            # fused forest kernel, dispatched per ROW CHUNK: every
            # chunk's [chunk, K] f32 result is queued asynchronously,
            # so the per-chunk downloads overlap the remaining chunks'
            # compute instead of serializing after the math. f32 on
            # the wire (f64 only at this API boundary,
            # predictor.hpp-style) halves the download.
            interp = not tpu
            row_tile, tc = self._tuned_tiles(first, ntree, row_tile,
                                             tc, interp)
            dev = self._device_arrays_pallas(first, ntree, tc)
            fchunk = 1 << 18
            # online batches pad to a pow2 serve bucket so request
            # sizes 1..bucket share ONE trace (the kernel pads rows to
            # a row_tile multiple internally either way — bucketing
            # only stabilizes the jit key)
            chunk = (fchunk if N > fchunk else min(
                fchunk, predict_cache.serve_bucket_rows(
                    N, self._serve_policy)))
            # the request context records the width ACTUALLY
            # dispatched — the clamp above can shrink the raw
            # serve-bucket answer for huge batches (obs/reqlog.py)
            reqlog.note_bucket(chunk)
            _, TCr, Sp, Lp = dev[1].shape
            key = ("pallas", device, offs, Sp, Lp, self.num_class,
                   TCr, dev[0].shape[0], row_tile, dev_bin, m_max,
                   chunk, interp)

            # the registered dispatch is PURE in the key: the model's
            # device stacks (and edge tables) arrive as arguments, so
            # a registry hit from a retrained same-geometry model runs
            # the warm program on ITS arrays
            def build():
                if dev_bin:
                    def run(part, dv, aux):
                        return forest_predict_from_x(
                            jnp.asarray(part), *aux, *dv,
                            offsets=offs, row_tile=row_tile,
                            interpret=interp)
                else:
                    def run(part, dv, aux):
                        return forest_predict_pallas(
                            jnp.asarray(part), *dv, offsets=offs,
                            row_tile=row_tile, interpret=interp)
                return run

            aux = ()
            if dev_bin:     # upload the edge tables once, not per chunk
                aux = (jnp.asarray(self._E_f32),
                       jnp.asarray(self._off32),
                       jnp.asarray(self._nan_slot))
            fn = self._dispatch(key, build)
            if not interp:
                obs.counter("predict/forest_kernel_calls").add(1)
            # host half of the double buffer (io/ingest.py prefetch):
            # the worker slices/pads/transposes chunk k+1 while the
            # device chews on chunk k
            layout = ((lambda p: p) if dev_bin
                      else (lambda p: np.ascontiguousarray(p.T)))
            handles = self._stream(rows, N, chunk, layout,
                                   lambda part: fn(part, dev, aux))
            acc = np.concatenate(
                [np.asarray(h)[:nr] for h, nr in handles], axis=0)
            return acc.T.astype(np.float64)
        dev = self._device_arrays(first, ntree)
        # pad rows to a power-of-two serve bucket so repeated odd-sized
        # calls (an online request stream) reuse one compiled kernel
        # per bucket instead of recompiling per batch size — bit-exact,
        # rows are independent and the pad is sliced off below. Policy
        # knob: tpu_serve_bucket (ops/predict_cache.py).
        bucket = min(row_chunk, predict_cache.serve_bucket_rows(
            N, self._serve_policy))
        # record the clamped width the batch actually rides (the raw
        # serve-bucket answer noted inside serve_bucket_rows can
        # exceed row_chunk for huge batches)
        reqlog.note_bucket(bucket)
        TC = dev[1].shape[1]
        key = ("scan", device, offs, self._S, self._L, self.num_class,
               TC, dev[0].shape[0], bool(pred_leaf), dev_bin, m_max,
               bucket)
        Wtot = self._Wtot

        # pure in the key (see the pallas path note): stacks/edge
        # tables are arguments, not closure state
        def build():
            if dev_bin:
                def run(chunk, dv, aux):
                    return _run_chunk_from_x(
                        jnp.asarray(chunk), *aux, *dv, Wtot, pred_leaf)
            else:
                def run(chunk, dv, aux):
                    return _run_chunk(jnp.asarray(chunk), *dv,
                                      Wtot, pred_leaf)
            return run

        aux = ()
        if dev_bin:     # upload the edge tables once, not per chunk
            aux = (jnp.asarray(self._E_f32), jnp.asarray(self._off32),
                   jnp.asarray(self._nan_slot))
        # jit-capture: ok(Wtot) — determined by offs (the per-feature
        # table offsets sum to Wtot), which IS in the key
        fn = self._dispatch(key, build)
        handles = self._stream(rows, N, bucket, lambda p: p,
                               lambda p: fn(p, dev, aux))
        if pred_leaf:
            out = np.concatenate(
                [np.asarray(h)[:nr] for h, nr in handles], axis=0)
            return out[:, :ntree - first]
        return np.concatenate(
            [np.asarray(h)[:nr] for h, nr in handles],
            axis=0).T.astype(np.float64)

    def _device_arrays_pallas(self, first: int, ntree: int, tc: int):
        """Kernel-shaped stacks: per-tree axes padded to MXU tiles
        (S -> Sp multiple of 128 so per-tree lane slices of C are
        aligned; L -> Lp for the second dot's output lanes)."""
        Sp = -(-self._S // 128) * 128
        Lp = -(-self._L // 128) * 128
        return self._stack_range(("pallas", first, ntree, tc), first,
                                 ntree, Sp, Lp, np.int32, tc)

    def _tuned_tiles(self, first: int, ntree: int, rt_default: int,
                     tc_default: int, interp: bool):
        """(row_tile, tc) for the fused forest kernel — autotuned on
        first encounter of this model-shape key (ops/autotune.py),
        cached on disk thereafter. The key is the kernel's SHAPE — the
        exact table width Wtot (already a sum of 32-bucketed
        per-feature widths, so retrained models of one pipeline
        usually land on the same value), padded S/L, classes, device
        kind — not the tree count: timing scales uniformly in the step
        count, so the ranking measured on the first model of a shape
        serves all of them. A cached choice is applied only when it is
        in THIS model's freshly computed candidate set, so an entry
        from a near-miss shape can never install a tc that does not
        fit. Off-TPU and with tpu_autotune=off the measured default
        tile is used untouched."""
        t = autotune.tuner()
        if interp or t.mode == "off":
            return rt_default, tc_default
        tiles = ((512, 1024, 2048, 4096, 8192)
                 if t.mode == "exhaustive" else (1024, 2048, 4096))
        cands = []
        for rt in tiles:
            tc = self._pallas_tc(rt)
            if tc is not None:
                cands.append({"row_tile": rt, "tc": tc})
        if not cands:
            return rt_default, tc_default
        Sp = -(-self._S // 128) * 128
        Lp = -(-self._L // 128) * 128
        key = {"Wtot": self._Wtot, "Sp": Sp, "Lp": Lp,
               "K": self.num_class, "F": len(self._offsets) - 1,
               "device": autotune.device_kind(),
               # candidate fingerprint (Autotuner.best contract): the
               # feasible (row_tile, tc) set varies with the tuning
               # mode and model geometry, and on/exhaustive runs must
               # not thrash or shadow each other's entries
               "tiles": [[c["row_tile"], c["tc"]] for c in cands]}
        offs = tuple(int(o) for o in self._offsets)
        # a multiple of every tile, several steps above the largest
        # one: a max(tiles)-row dispatch would amortize fixed per-
        # dispatch overhead over ONE grid step for the biggest tile
        # but several for the small ones, biasing the ranking toward
        # big tiles relative to the real 2^18-row predict chunks
        n_meas = min(8 * max(tiles), 1 << 18)
        codes = jnp.zeros((len(offs) - 1, n_meas), jnp.int32)

        def measure(cand):
            dev = self._device_arrays_pallas(first, ntree, cand["tc"])
            return timing.measure(
                lambda: forest_predict_pallas(
                    codes, *dev, offsets=offs,
                    row_tile=cand["row_tile"], interpret=False))

        choice = t.best(
            "forest", key, cands, measure,
            default={"row_tile": rt_default, "tc": tc_default})
        rt, tc = int(choice["row_tile"]), int(choice["tc"])
        # losing candidates' device stacks would otherwise sit in the
        # (bounded) _dev_cache; keep only the winner's
        for k in [k for k in self._dev_cache
                  if k[0] == "pallas" and k[3] != tc]:
            self._dev_cache.pop(k, None)
        return rt, tc


class _FallbackError(Exception):
    pass


def _feature_codes(x: np.ndarray, edges: Optional[np.ndarray],
                   cats: Optional[np.ndarray]) -> np.ndarray:
    """Values -> LOCAL bin codes for one feature under the table
    layout of _rebuild_tables. Shared between row binning (_bin_rows)
    and the incremental-extend slot remap, so the two cannot drift.

    Numerical: [closed-right bins][overflow][NaN].
    Categorical: [known cats][other][negative/NaN]."""
    N = x.shape[0]
    if cats is not None:
        nan = np.isnan(x)
        neg = ~nan & (x < 0)
        cat = np.trunc(np.where(nan | neg, 0, x))
        if cats.size:
            pos = np.clip(np.searchsorted(cats, cat), 0, cats.size - 1)
            known = cats[pos] == cat
        else:
            # empty bitset (all categories go right): every value maps
            # to the "other" slot
            pos = np.zeros(N, np.int64)
            known = np.zeros(N, bool)
        b = np.where(known, pos, cats.size)          # other
        return np.where(nan | neg, cats.size + 1, b)  # neg/NaN slot
    edges = edges if edges is not None else np.zeros(0, np.float64)
    nan = np.isnan(x)
    b = np.searchsorted(edges, np.where(nan, 0.0, x), side="left")
    return np.where(nan, edges.size + 1, b)


def _node_table(tree, s: int, reps: np.ndarray) -> np.ndarray:
    """Evaluate node s's decision (go-left=1) at each representative
    value — vectorized mirror of tree.h:183-201 / Tree._decision."""
    dt = tree.decision_type[s]
    if dt & K_CATEGORICAL_MASK:
        nan = np.isnan(reps)
        ok = ~nan & (reps >= 0)
        cat = np.trunc(np.where(ok, reps, 0)).astype(np.int64)
        ci = tree.threshold_in_bin[s]
        lo, hi = tree.cat_boundaries[ci], tree.cat_boundaries[ci + 1]
        words = np.asarray(tree.cat_threshold[lo:hi], np.uint32)
        wi = cat // 32
        in_r = ok & (wi < (hi - lo))
        bit = np.zeros(reps.size, bool)
        if in_r.any():
            bit[in_r] = ((words[wi[in_r]]
                          >> (cat[in_r] % 32).astype(np.uint32)) & 1) != 0
        return bit.astype(np.int8)
    mt = (dt >> 2) & 3
    def_left = bool(dt & K_DEFAULT_LEFT_MASK)
    nan = np.isnan(reps)
    fz = np.where(nan & (mt != MissingType.NAN), 0.0, reps)
    miss = (((mt == MissingType.ZERO)
             & (fz >= -_ZERO_EPS) & (fz <= _ZERO_EPS))
            | ((mt == MissingType.NAN) & nan))
    with np.errstate(invalid="ignore"):
        go_left = np.where(miss, def_left, fz <= tree.threshold[s])
    return go_left.astype(np.int8)


@jax.jit
def _codes_from_x(x, E, off32, nan_slot):
    """f32 rows -> feature-major global one-hot codes on device."""
    bins = jnp.sum(x[:, :, None] > E[None], axis=2).astype(jnp.int32)
    codes = jnp.where(jnp.isnan(x), nan_slot[None], off32[None] + bins)
    return codes.T


@functools.partial(jax.jit, static_argnames=("offsets", "row_tile",
                                             "interpret"))
def forest_predict_from_x(x, E, off32, nan_slot, W, P, tgt, leaf, cls,
                          *, offsets,
                          row_tile=autotune.DEFAULT_ROW_TILE,
                          interpret=False):
    """Device binning + forest kernel in ONE dispatch."""
    codes_t = _codes_from_x(x, E, off32, nan_slot)
    return forest_predict_pallas(codes_t, W, P, tgt, leaf, cls,
                                 offsets=offsets, row_tile=row_tile,
                                 interpret=interpret)


def _f32_exact(X64: np.ndarray, X32: np.ndarray) -> bool:
    """True when every finite value round-trips f64 -> f32 -> f64."""
    with np.errstate(invalid="ignore"):
        same = (X32.astype(np.float64) == X64) | np.isnan(X64)
    return bool(same.all())


@partial(jax.jit, static_argnums=(9, 10))
def _run_chunk_from_x(x, E, off32, nan_slot, W, P, tgt, leaf, clsOH,
                      Wtot: int, pred_leaf: bool):
    """f32 rows -> codes on device (edges pre-rounded so the f32
    compare reproduces the host's f64 searchsorted exactly), then the
    shared kernel. The codes computation is shared with the Pallas
    path (_codes_from_x) so the binning semantics cannot diverge."""
    codes = _codes_from_x(x, E, off32, nan_slot).T
    return _kernel(codes, W, P, tgt, leaf, clsOH, Wtot, pred_leaf)


@partial(jax.jit, static_argnums=(6, 7))
def _run_chunk(codes, W, P, tgt, leaf, clsOH, Wtot: int,
               pred_leaf: bool):
    """codes [n, F] int32 -> scores [n, K] f32 (or leaf idx [n, T])."""
    return _kernel(codes, W, P, tgt, leaf, clsOH, Wtot, pred_leaf)


def _kernel(codes, W, P, tgt, leaf, clsOH, Wtot: int, pred_leaf: bool):
    n = codes.shape[0]
    from ..utils.device import on_tpu
    # int8 / bf16 feed the MXU's fast paths; the CPU backend's dot
    # lacks those mixed kernels, so it runs f32 (values are exact
    # small ints either way)
    lut_t = jnp.int8 if on_tpu() else jnp.float32
    acc_t = jnp.int32 if on_tpu() else jnp.float32
    mm_t = jnp.bfloat16 if on_tpu() else jnp.float32
    # one-hot row build: one scatter, no [n, F, Wtot] intermediate
    OH = jnp.zeros((n, Wtot), lut_t)
    OH = OH.at[jnp.arange(n)[:, None], codes].set(lut_t(1))

    def step(acc, xs):
        Wc, Pc, tgtc, leafc, clsc = xs
        TC, S, L = Pc.shape
        # node decisions: int8 MXU lookup, C in {0, 1}
        C = jax.lax.dot_general(
            OH, Wc.astype(lut_t), (((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)
        C = C.reshape(n, TC, S).astype(mm_t)
        # signed ancestor-agreement count per leaf (exact ints < 256)
        E = jnp.einsum("nts,tsl->ntl", C, Pc.astype(mm_t),
                       preferred_element_type=jnp.float32)
        match = (E == tgtc[None]).astype(jnp.float32)
        if pred_leaf:
            li = jnp.argmax(match, axis=2).astype(jnp.int32)
            return acc, li
        # HIGHEST: default matmul precision truncates f32 operands to
        # bf16 (on CPU XLA too, shape-dependent) — leaf values and the
        # class scatter must stay exact f32
        val = jnp.einsum("ntl,tl->nt", match, leafc,
                         precision=jax.lax.Precision.HIGHEST)
        acc = acc + jnp.matmul(val, clsc,
                               precision=jax.lax.Precision.HIGHEST)
        return acc, None

    acc0 = jnp.zeros((n, clsOH.shape[-1]), jnp.float32)
    acc, ys = jax.lax.scan(step, acc0, (W, P, tgt, leaf, clsOH))
    if pred_leaf:
        return jnp.moveaxis(ys, 0, 1).reshape(n, -1)
    return acc


# --- fused forest kernel ---------------------------------------------------
#
# The XLA scan above materializes the node-decision matrix C and the
# ancestor-agreement counts E in HBM between its three contractions;
# at 500 trees x 1M rows that traffic alone costs more than the math.
# The Pallas kernel keeps the whole chain in VMEM: build the one-hot
# tile from codes, run both int8 MXU dots, fuse the match compare and
# leaf-value reduction, and emit ONLY the [N, K] score accumulator.
# One dispatch for the entire forest.

def _forest_kernel(codes_ref, W_ref, P_ref, tgt_ref, leaf_ref, cls_ref,
                   acc_ref, *, F, Wtot, offs, TC, Sp, Lp, K, nt):
    i32 = jnp.int32
    step = pl.program_id(1)

    # Grid is (rows, steps) steps-inner: each [nt, K] accumulator block
    # is visited in CONSECUTIVE iterations (a Pallas requirement for
    # read-modify-write output blocks; a steps-outer order interleaves
    # visits and loses partial sums). The W/P re-fetch per row tile is
    # ~4 MB x steps — cheap at a 2048-row tile.
    # One-hot tile [Wtot, nt] int8, rebuilt per iteration:
    # nt*Wtot compares — noise next to the dots.
    blocks = []
    for f in range(F):
        w = offs[f + 1] - offs[f]
        row = codes_ref[f, :].astype(i32) - offs[f]
        iota = jax.lax.broadcasted_iota(i32, (w, 1), 0)
        blocks.append((row[None, :] == iota).astype(jnp.int8))
    oh = jnp.concatenate(blocks, axis=0)                 # [Wtot, nt]

    # dot 1: every node decision for every row, int8 MXU
    C = jax.lax.dot_general(
        oh, W_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=i32)                      # [nt, TC*Sp]
    C8 = C.astype(jnp.int8)                              # values {0,1}

    # dot 2 per tree + fused match/value reduction
    vals = []
    for t in range(TC):
        Ct = C8[:, t * Sp:(t + 1) * Sp]
        E = jax.lax.dot_general(
            Ct, P_ref[0, t], (((1,), (0,)), ((), ())),
            preferred_element_type=i32)                  # [nt, Lp]
        match = (E == tgt_ref[0, t][None, :]).astype(jnp.float32)
        vals.append(jnp.sum(match * leaf_ref[0, t][None, :],
                            axis=1, keepdims=True))      # [nt, 1]
    val = jnp.concatenate(vals, axis=1)                  # [nt, TC]
    contrib = jax.lax.dot_general(
        val, cls_ref[0], (((1,), (0,)), ((), ())),
        # f32 MXU default truncates operands to bf16 — keep the class
        # scatter exact (tiny dot, cost is nil)
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [nt, K]

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("offsets", "row_tile",
                                             "interpret"))
def forest_predict_pallas(codes_t, W, P, tgt, leaf, cls, *, offsets,
                          row_tile=autotune.DEFAULT_ROW_TILE,
                          interpret=False):
    """codes_t [F, N] int32 -> scores [N, K] f32, one fused dispatch.

    BlockSpecs come from autotune.forest_block_shapes — the same tuples
    _pallas_tc's VMEM estimate prices, so guard and kernel cannot
    drift."""
    F, N = codes_t.shape
    steps, Wtot, TCSp = W.shape
    _, TC, Sp, Lp = P.shape
    K = cls.shape[-1]
    pad = (-N) % row_tile
    if pad:
        # padded rows get code 0 -> garbage scores, sliced off below
        codes_t = jnp.pad(codes_t, ((0, 0), (0, pad)))
    n_pad = N + pad
    kernel = functools.partial(
        _forest_kernel, F=F, Wtot=Wtot, offs=tuple(offsets), TC=TC,
        Sp=Sp, Lp=Lp, K=K, nt=row_tile)
    blk = autotune.forest_block_shapes(F=F, Wtot=Wtot, TC=TC, Sp=Sp,
                                       Lp=Lp, K=K, row_tile=row_tile)
    acc = pl.pallas_call(
        kernel,
        grid=(n_pad // row_tile, steps),
        in_specs=[
            pl.BlockSpec(blk["codes"], lambda r, t: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["W"], lambda r, t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["P"], lambda r, t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["tgt"], lambda r, t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["leaf"], lambda r, t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk["cls"], lambda r, t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(blk["acc"], lambda r, t: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, K), jnp.float32),
        compiler_params=autotune.tpu_compiler_params(),
        name="forest_predict_pallas",
        interpret=interpret,
    )(codes_t, W, P, tgt, leaf, cls)
    return acc[:N]
