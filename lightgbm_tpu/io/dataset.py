"""Binned dataset container.

TPU-native counterpart of the reference Dataset/Metadata/FeatureGroup
(reference: include/LightGBM/dataset.h:36-622, src/io/dataset.cpp:212,
src/io/metadata.cpp). The reference stores per-group CPU bin arrays with
4/8/16/32-bit widths; here the binned matrix is ONE dense device tensor
``[N, F] uint8/int32`` resident in HBM (the GPU learner already did the
dense-only device layout, gpu_tree_learner.cpp:325-357 — we follow that
design and keep every non-trivial feature dense).

Host-side responsibilities: sampling, BinMapper construction
(Dataset::Construct / DatasetLoader::ConstructBinMappersFromTextData),
trivial-feature exclusion, metadata (labels/weights/queries/init scores).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..config import Config
from ..ops.split import FeatureMeta
from ..utils import log
from .binning import BinMapper, BinType


class Metadata:
    """Labels / weights / queries / init scores (dataset.h:36-249)."""

    def __init__(self, label=None, weight=None, group=None, init_score=None):
        self.label = (None if label is None
                      else np.asarray(label, np.float32).reshape(-1))
        self.weights = (None if weight is None
                        else np.asarray(weight, np.float32).reshape(-1))
        self.init_score = (None if init_score is None
                           else np.asarray(init_score, np.float64))
        self.query_boundaries = None
        if group is not None:
            group = np.asarray(group, np.int64).reshape(-1)
            self.query_boundaries = np.concatenate(
                [[0], np.cumsum(group)]).astype(np.int64)
        self._query_weights = None

    def check_or_partition(self, num_data: int) -> None:
        if self.label is not None and len(self.label) != num_data:
            log.fatal(f"Length of label ({len(self.label)}) is not same "
                      f"as number of data ({num_data})")
        if self.weights is not None and len(self.weights) != num_data:
            log.fatal("Length of weights differs from number of data")
        if (self.query_boundaries is not None
                and self.query_boundaries[-1] != num_data):
            log.fatal("Sum of query counts differs from number of data")

    @property
    def num_queries(self):
        if self.query_boundaries is None:
            return 0
        return len(self.query_boundaries) - 1


# columns of the bin search's row sample gathered at a time
_SAMPLE_COLS = 64


def find_column_mappers(X: np.ndarray, config: Config,
                        categorical=(), total_rows: Optional[int] = None,
                        columns: Optional[Sequence[int]] = None,
                        presampled: bool = False
                        ) -> List[Optional[BinMapper]]:
    """Sample rows and find a BinMapper per column (trivial ones
    included) — the shared bin-construction loop of
    DatasetLoader::ConstructBinMappersFromTextData
    (src/io/dataset_loader.cpp:196-235, 388-433).

    ``total_rows`` is the GLOBAL row count when ``X`` is one shard of a
    distributed load: the per-shard sample budget and the
    min_data_in_leaf filter scale by the shard/global ratio, and every
    shard must use the SAME total or their bin boundaries diverge.
    ``columns`` restricts the search to a subset (the distributed
    owner-rule workload split, dataset_loader.cpp:434-466); unowned
    entries come back as None. ``presampled``: ``X`` already IS the
    sample of a ``total_rows``-row dataset (two-round loading) — skip
    re-sampling, scale only the min_data filter."""
    X = np.asarray(X)
    n, nf = X.shape
    cfg = config
    total = n if total_rows is None else max(int(total_rows), 1)
    idx = None                  # sampled rows; None: every row of X
    if not presampled:
        budget = cfg.bin_construct_sample_cnt
        if total > n > 0:
            budget = max(budget * n // total, 1)   # this shard's share
        sample_cnt = min(budget, n)
        rng = np.random.default_rng(cfg.data_random_seed)
        if sample_cnt < n:
            idx = np.sort(rng.choice(n, sample_cnt, replace=False))
    snum = n if idx is None else len(idx)
    filter_cnt = 0
    if cfg.min_data_in_leaf > 0 and total > 0:
        # dataset_loader.cpp: filter scaled by sample/total ratio
        filter_cnt = max(int(cfg.min_data_in_leaf * snum / total), 1)
    cats = set(categorical)
    wanted = set(range(nf)) if columns is None else set(columns)
    mappers: List[Optional[BinMapper]] = []
    block, j0 = None, 0
    for j in range(nf):
        if j not in wanted:
            mappers.append(None)
            continue
        if idx is None:
            col = X[:, j].astype(np.float64)
        else:
            # the sampled rows, _SAMPLE_COLS columns at a time: the
            # whole sample at once is a second matrix (3.2 GB of fresh
            # pages at 200,000 x 2,000) for the sake of one column
            if block is None or not j0 <= j < j0 + _SAMPLE_COLS:
                j0 = j - j % _SAMPLE_COLS
                block = X[idx, j0:j0 + _SAMPLE_COLS]
            col = block[:, j - j0].astype(np.float64)
        # reference samples only non-zero values; zeros are implied
        nonzero = col[(np.abs(col) > 1e-35) | np.isnan(col)]
        m = BinMapper()
        bt = (BinType.CATEGORICAL if j in cats else BinType.NUMERICAL)
        m.find_bin(nonzero, snum, cfg.max_bin, cfg.min_data_in_bin,
                   filter_cnt, bt, cfg.use_missing, cfg.zero_as_missing)
        mappers.append(m)
    return mappers


class TpuDataset:
    """Constructed, binned training matrix + metadata."""

    def __init__(self, config: Config):
        self.config = config
        self.num_data = 0
        self.num_total_features = 0
        self.mappers: List[BinMapper] = []          # per used (inner) feature
        self.used_feature_map: np.ndarray = np.array([], np.int32)
        self.real_to_inner: dict = {}
        self.bins: Optional[np.ndarray] = None      # [N, F_used]
        # device-resident feature-major bins (io/ingest.py streamed
        # ingest): [F_used, N + bins_t_dev_pad] uint8/int32 jax array;
        # exactly one of bins / bins_t_dev is set after construction.
        # When the configured tree learner row-shards (data/voting over
        # a >1-device mesh) the array is assembled ROW-SHARDED under a
        # NamedSharding and bins_t_dev_pad holds the zero-bin columns
        # appended so every shard is the same width (consumers treat
        # them exactly like the grower's own row padding).
        self.bins_t_dev = None
        self.bins_t_dev_pad = 0
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_bin_global = 1
        self._reference: Optional["TpuDataset"] = None
        # EFB state (io/efb.py); None = unbundled
        self.bundles = None
        self.bundled_bins: Optional[np.ndarray] = None
        self.member_bundle: Optional[np.ndarray] = None
        self.member_offset: Optional[np.ndarray] = None
        self.bundle_width = 0
        # CSR-native state (io/sparse.py): set by the sparse
        # construction route. ``sparse_coords`` holds the
        # zero-suppressed (code, inner feature, row) planes — numpy on
        # the host path, jax arrays when the sparse device ingest
        # assembled them — retained only when the sparse histogram
        # tier may consume them (sparse.want_coords).
        self.sparse_nnz = 0
        self.sparse_density: Optional[float] = None
        self.sparse_coords = None
        self.sparse_zero_bins: Optional[np.ndarray] = None

    # -- construction -------------------------------------------------------

    def construct_from_matrix(self, X: np.ndarray, metadata: Metadata,
                              categorical: Sequence[int] = (),
                              reference: Optional["TpuDataset"] = None,
                              feature_names: Optional[List[str]] = None,
                              mappers: Optional[List[BinMapper]] = None,
                              ring=None):
        """Build bin mappers (or reuse reference's) and bin the matrix.

        Mirrors DatasetLoader::ConstructFromSampleData
        (src/io/dataset_loader.cpp:499) + Dataset::CreateValid
        (src/io/dataset.cpp:368). ``mappers`` (one per REAL column,
        trivial ones included) injects externally-agreed bin boundaries —
        the distributed loader's synced mappers
        (dataset_loader.cpp:434-466 Allgather of serialized BinMappers).
        """
        # span trace starts HERE when configured (obs/trace.py): ingest
        # runs before any booster exists, and its worker-thread spans
        # must land in the same buffer the training spans will
        from ..obs import trace
        trace.ensure_from_config(self.config)
        from .sparse import SparseMatrix
        if isinstance(X, SparseMatrix):
            return self._construct_from_sparse(
                X, metadata, categorical=categorical,
                reference=reference, feature_names=feature_names,
                mappers=mappers)
        X = np.asarray(X)
        if X.dtype not in (np.float32, np.float64):
            X = X.astype(np.float64)
        n, nf = X.shape
        self.num_data = n
        self.num_total_features = nf
        self.metadata = metadata
        self.metadata.check_or_partition(n)
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(nf)])

        from ..utils import timing
        if reference is not None:
            # valid set: reuse the train set's mappers (CreateValid)
            self._reference = reference
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.real_to_inner = reference.real_to_inner
            self.max_bin_global = reference.max_bin_global
            self.feature_names = reference.feature_names
            self.num_total_features = reference.num_total_features
        elif mappers is not None:
            self._set_mappers(mappers)
        else:
            with timing.phase("binning/find_bins", mem_peak=True):
                self._construct_mappers(X, set(categorical))
        with timing.phase("binning/bin_matrix", mem_peak=True) as ph:
            self._bin_matrix(X, efb_possible=(mappers is None
                                              and reference is None),
                             ring=ring)
            if self.bins_t_dev is not None:
                # device phase: sync at phase exit so queued kernel
                # time lands here, not in a later unrelated phase
                ph.watch(self.bins_t_dev)
        if mappers is None and self.bins is not None:
            # distributed shards skip EFB: bundling is data-dependent
            # (find_bundles over LOCAL bins) and would diverge across
            # ranks; parallel learners run unbundled anyway. The device
            # ingest path pre-probed EFB on the reference's own sample
            # (_efb_would_bundle) and only runs when nothing bundles.
            with timing.phase("binning/efb"):
                self._apply_efb()
        return self

    def _construct_from_sparse(self, sm, metadata: Metadata,
                               categorical: Sequence[int] = (),
                               reference: Optional["TpuDataset"] = None,
                               feature_names: Optional[List[str]] = None,
                               mappers: Optional[List[BinMapper]] = None
                               ) -> "TpuDataset":
        """CSR-native construction (io/sparse.py): the host never
        materializes the [N, F] float64 matrix. Mappers sample straight
        from CSR (bit-identical to the densified path's), binning is
        O(nnz) — device-side through the streamed sparse ingest
        (io/ingest.py SparseDeviceBinner) or a host scatter into the
        bin-storage tier — and datasets where EFB actually bundles
        build the host bin matrix (uint8, not float64) so the bundling
        decision and bundled matrix stay bit-identical to the
        densified path. Above ``sparse_threshold`` density the input
        takes the explicit dense fallback (the one place the densify
        cliff warning still fires on this path)."""
        from ..obs import registry as obs
        from ..utils import timing
        from . import sparse as sp
        cfg = self.config
        n, nf = sm.shape
        if not sp.route_sparse(cfg, sm):
            obs.counter("sparse/route_dense").add(1)
            log.info("sparse input density %.4f is above the CSR route "
                     "gate (1 - sparse_threshold = %g): densifying",
                     sm.density, 1.0 - cfg.sparse_threshold)
            return self.construct_from_matrix(
                sm.to_dense(warn=True), metadata,
                categorical=categorical, reference=reference,
                feature_names=feature_names, mappers=mappers)
        obs.counter("sparse/route_sparse").add(1)
        obs.counter("sparse/nnz_rows").add(sm.nnz)
        obs.gauge("sparse/density").set(sm.density)
        self.num_data = n
        self.num_total_features = nf
        self.metadata = metadata
        self.metadata.check_or_partition(n)
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(nf)])
        if reference is not None:
            self._reference = reference
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.real_to_inner = reference.real_to_inner
            self.max_bin_global = reference.max_bin_global
            self.feature_names = reference.feature_names
            self.num_total_features = reference.num_total_features
        elif mappers is not None:
            self._set_mappers(mappers)
        else:
            with timing.phase("binning/find_bins", mem_peak=True):
                self._set_mappers(sp.find_column_mappers_sparse(
                    sm, cfg, set(categorical)))
        self.sparse_nnz = sm.nnz
        self.sparse_density = sm.density
        if self.mappers:
            self.sparse_zero_bins = sp.zero_bins(self.mappers)
        # coords feed the sparse histogram tier — train sets only (the
        # grower histograms training rows; valid rows ride as weight-0
        # passengers of the dense matrix either way)
        keep_coords = (sp.want_coords(cfg, sm.density)
                       and reference is None)
        efb_possible = mappers is None and reference is None
        with timing.phase("binning/bin_matrix", mem_peak=True) as ph:
            self._bin_sparse(sm, keep_coords, efb_possible)
            if self.bins_t_dev is not None:
                ph.watch(self.bins_t_dev)
        if mappers is None and self.bins is not None:
            with timing.phase("binning/efb"):
                self._apply_efb()
            if self.bundles is not None:
                # the sparse tier never composes with EFB bundles
                # (models/gbdt.py) — binned coordinates of UNBUNDLED
                # member features would be the wrong layout anyway
                self.sparse_coords = None
        return self

    def _bin_sparse(self, sm, keep_coords: bool,
                    efb_possible: bool) -> None:
        """Bin a CSR matrix: streamed sparse device ingest when enabled
        and reproducible, else an O(nnz) host scatter into the
        bin-storage tier. Either way the dense float64 [N, F] never
        exists."""
        from ..obs import registry as obs
        from . import sparse as sp
        self.bins_t_dev = None
        self.bins_t_dev_pad = 0
        self.bins = None
        n = sm.shape[0]
        if self._sparse_device_ok(sm, efb_possible):
            from .ingest import IngestUnsupported, SparseDeviceBinner
            try:
                binner = SparseDeviceBinner(
                    self.mappers, self.used_feature_map, self.config)
            except IngestUnsupported as e:
                log.warning("sparse device ingest unavailable (%s); "
                            "host scatter", e)
            else:
                self.bins_t_dev, coords = binner.bin_matrix_sparse(
                    sm, want_coords=keep_coords)
                if keep_coords:
                    self.sparse_coords = coords
                log.info("sparse device ingest: %d rows x %d features "
                         "binned on device from nnz=%d (density %.4f) "
                         "in %d-row chunks", n, self.num_features,
                         sm.nnz, sm.density, binner.chunk_rows)
                return
        # host path: one O(nnz) entry binning serves both the bin
        # matrix scatter and (when wanted) the retained coordinates
        dtype = self.bin_dtype()
        if not self.mappers:
            self.bins = np.zeros((n, 1), dtype)
            return
        codes, feat, rows = sp.bin_entries(sm, self.mappers,
                                           self.used_feature_map)
        bins = np.empty((n, len(self.mappers)), dtype)
        bins[:] = self.sparse_zero_bins.astype(dtype)[None, :]
        bins[rows, feat] = codes.astype(dtype)
        self.bins = bins
        if keep_coords:
            self.sparse_coords = (codes, feat, rows)
        obs.counter("ingest/rows_host").add(n)

    def _sparse_device_ok(self, sm, efb_possible: bool) -> bool:
        """Gate for the streamed sparse device path — the sparse twin
        of ``_device_ingest_ok``: config-enabled, usable reproducible
        mappers, no EFB interaction, and no row-sharding mesh (the
        sparse route has no sharded ingest yet; sharded learners get
        the host bins placed under the mesh at booster init)."""
        from .ingest import ingest_enabled, ingest_mesh, mappers_supported
        if not ingest_enabled(self.config):
            return False
        if not self.mappers:
            return False
        if not mappers_supported(self.mappers):
            return False
        ref = self._reference
        if ref is not None and ref.bundles is not None:
            return False
        if ref is None and ingest_mesh(self.config) is not None:
            return False
        if efb_possible and self._efb_would_bundle_sparse(sm):
            log.info("EFB bundles this sparse data; using the host "
                     "scatter so bundling stays bit-identical (set "
                     "enable_bundle=false for device sparse ingest)")
            return False
        return True

    def _efb_would_bundle_sparse(self, sm) -> bool:
        """``_efb_would_bundle`` for CSR input: bin the SAME rng(3) row
        sample find_bundles would draw (O(nnz of the sample)) and ask
        ``would_bundle`` directly — identical verdict to the densified
        path's, binning is row-wise."""
        cfg = self.config
        if not cfg.enable_bundle or self.num_features <= 1:
            return False
        from .efb import sample_rows_for_probe, would_bundle
        from .sparse import host_bins_from_sparse
        idx = sample_rows_for_probe(sm.shape[0])
        sample = sm if idx is None else sm.take_rows(idx)
        return would_bundle(
            host_bins_from_sparse(sample, self.mappers,
                                  self.used_feature_map,
                                  self.bin_dtype()),
            self.mappers, cfg.max_conflict_rate)

    def _construct_mappers(self, X: np.ndarray, categorical: set) -> None:
        self._set_mappers(find_column_mappers(X, self.config, categorical))

    def _set_mappers(self, all_mappers: List[BinMapper]) -> None:
        """Install per-REAL-column mappers: trivial-feature exclusion +
        index maps (shared by local bin finding and distributed-agreed
        injection)."""
        used = [j for j, m in enumerate(all_mappers) if not m.is_trivial]
        if not used:
            log.warning("There are no meaningful features, as all feature "
                        "values are constant.")
        self.mappers = [all_mappers[j] for j in used]
        self.used_feature_map = np.asarray(used, np.int32)
        self.real_to_inner = {r: i for i, r in enumerate(used)}
        self.max_bin_global = max(
            (m.num_bin for m in self.mappers), default=1)

    def _bin_matrix(self, X: np.ndarray, efb_possible: bool = False,
                    ring=None) -> None:
        """Bin the whole matrix: streamed device ingest (io/ingest.py)
        when enabled and reproducible, else the host binner. Train sets
        of a row-sharding learner assemble the bins directly under the
        mesh's NamedSharding (no single-device staging). ``ring``
        (io/ingest.py ChunkRing) lets a windowed retrain loop reuse the
        previous construction's device-resident chunk buffers."""
        self.bins_t_dev = None
        self.bins_t_dev_pad = 0
        if self._device_ingest_ok(X, efb_possible):
            from .ingest import (DeviceBinner, IngestUnsupported,
                                 ingest_mesh)
            try:
                binner = DeviceBinner(self.mappers, self.used_feature_map,
                                      self.config, X.dtype)
            except IngestUnsupported as e:
                log.warning("device ingest unavailable (%s); host binner", e)
            else:
                # valid sets ride as passenger columns of the grower
                # matrix (models/gbdt.py) — only the train set's rows
                # are worth sharding at ingest time
                mesh = (ingest_mesh(self.config)
                        if self._reference is None else None)
                if mesh is not None:
                    self.bins_t_dev = binner.bin_matrix_sharded(X, mesh)
                    self.bins_t_dev_pad = (self.bins_t_dev.shape[1]
                                           - self.num_data)
                    self.bins = None
                    log.info("sharded device ingest: %d rows binned "
                             "across %d device(s) in %d-row chunks",
                             self.num_data, mesh.devices.size,
                             binner.chunk_rows)
                    return
                self.bins_t_dev = binner.bin_matrix(X, ring=ring)
                self.bins = None
                log.info("streamed device ingest: %d rows binned on "
                         "device in %d-row chunks%s", self.num_data,
                         binner.chunk_rows,
                         " (chunk ring)" if ring is not None else "")
                return
        self.bins = self.bin_rows(X)

    def _device_ingest_ok(self, X: np.ndarray, efb_possible: bool) -> bool:
        """Gate for the streamed device path: config-enabled, usable
        features, exact-comparison dtype, no EFB interaction (a valid
        set of a bundled train set must produce bundled host bins; a
        fresh set that WOULD bundle takes the host path so the bundling
        decision and bundled matrix stay bit-identical)."""
        from .ingest import ingest_enabled, mappers_supported
        if not ingest_enabled(self.config):
            return False
        if not self.mappers:
            return False
        if X.dtype not in (np.float32, np.float64):
            return False
        if not mappers_supported(self.mappers):
            return False
        ref = self._reference
        if ref is not None and ref.bundles is not None:
            return False
        if efb_possible and self._efb_would_bundle(X):
            log.info("EFB bundles this data; using the host binner so "
                     "bundling stays bit-identical (set "
                     "enable_bundle=false to stream ingest instead)")
            return False
        return True

    def _efb_would_bundle(self, X: np.ndarray) -> bool:
        """Replicate find_bundles' own sampled decision (io/efb.py
        would_bundle) without a full host bin matrix: bin the SAME
        rng(3) row sample it would draw and ask it directly. Identical
        verdict to the host path by construction — binning is
        row-wise."""
        cfg = self.config
        if not cfg.enable_bundle or self.num_features <= 1:
            return False
        from .efb import sample_rows_for_probe, would_bundle
        idx = sample_rows_for_probe(X.shape[0])
        sample = X if idx is None else X[idx]
        return would_bundle(self.bin_rows(np.asarray(sample)),
                            self.mappers, cfg.max_conflict_rate)

    def host_bins(self) -> Optional[np.ndarray]:
        """The [N, F] host bin matrix in the host storage tier
        (bin_dtype). Device-ingested sets download TRANSIENTLY — the
        result is returned, not stored, so the one-of-bins/bins_t_dev
        invariant (and the device-resident fast path) stays intact."""
        if self.bins is None and self.bins_t_dev is not None:
            log.info("materializing device-binned matrix on host "
                     "(%d rows)", self.num_data)
            return np.ascontiguousarray(
                np.asarray(self.bins_t_dev)[:, :self.num_data].T).astype(
                self.bin_dtype(), copy=False)
        return self.bins

    def bin_rows(self, X: np.ndarray) -> np.ndarray:
        """Bin a block of rows (post-drop feature layout) with this
        dataset's mappers — numerical columns through the threaded C++
        bulk binner, the rest per-column. Used for the whole matrix and
        for two_round's streaming chunks (io/loader.py)."""
        n = X.shape[0]
        f = len(self.mappers)
        dtype = self.bin_dtype()
        bins = np.zeros((n, max(f, 1)), dtype)
        done = self._bin_matrix_native(X, bins, dtype)
        for i, real in enumerate(self.used_feature_map):
            if i in done:
                continue
            bins[:, i] = self.mappers[i].value_to_bin(X[:, real]).astype(dtype)
        from ..obs import registry as obs
        obs.counter("ingest/rows_host").add(n)
        return bins

    def bin_dtype(self):
        """Tiered bin storage width (the reference's Dense{8,16,32}Bin,
        src/io/dense_bin.hpp:43): uint8 up to 256 bins, uint16 to
        65536, int32 beyond. The device tensor upcasts >8-bit tiers to
        int32 at upload (models/gbdt.py) — the tiers size host RAM and
        the binary cache."""
        if self.max_bin_global <= 256:
            return np.uint8
        if self.max_bin_global <= 65536:
            return np.uint16
        return np.int32

    def _bin_matrix_native(self, X, bins, dtype) -> set:
        """Bulk-bin the numerical uint8 columns through the threaded C++
        binner (native/fast_parser.cpp lgbm_tpu_bin_columns) — numpy's
        per-column searchsorted is ~45 s for the 11M x 28 HIGGS shape,
        the native path ~1 s. Returns the set of inner features done
        (categoricals and >256-bin tiers stay on value_to_bin)."""
        if dtype is not np.uint8 or not self.mappers:
            return set()
        from .binning import BinType, MissingType
        from .native import bin_columns_native
        idx, cols, bl, rl, nb = [], [], [], [], []
        for i, real in enumerate(self.used_feature_map):
            m = self.mappers[i]
            if m.bin_type != BinType.NUMERICAL:
                continue
            r = m.num_bin - 1
            nanb = -1
            if m.missing_type == MissingType.NAN:
                r -= 1
                nanb = m.num_bin - 1
            idx.append(i)
            cols.append(real)
            bl.append(np.asarray(m.bin_upper_bound[:r], np.float64))
            rl.append(r)
            nb.append(nanb)
        if not idx:
            return set()
        out = bin_columns_native(
            X, np.asarray(cols, np.int32), bl,
            np.asarray(rl, np.int32), np.asarray(nb, np.int32))
        if out is None:
            return set()
        for k, i in enumerate(idx):
            bins[:, i] = out[:, k]
        return set(idx)

    def _apply_efb(self) -> None:
        """Exclusive feature bundling (Dataset::FindGroups +
        FastFeatureBundling, dataset.cpp:66-210) — see io/efb.py."""
        from .efb import bundle_bins, find_bundles
        cfg = self.config
        if self._reference is not None:
            ref = self._reference
            if ref.bundles is None:
                return
            self.bundles = ref.bundles
            db = np.array([m.default_bin for m in self.mappers], np.int32)
            nb = np.array([m.num_bin for m in self.mappers], np.int32)
            self.bundled_bins, self.member_bundle, self.member_offset, \
                self.bundle_width = bundle_bins(
                    self.bins, ref.bundles, db, nb)
            return
        if not cfg.enable_bundle or self.num_features <= 1:
            return
        db = np.array([m.default_bin for m in self.mappers], np.int32)
        nb = np.array([m.num_bin for m in self.mappers], np.int32)
        bundles = find_bundles(self.bins, db, nb, cfg.max_conflict_rate)
        if len(bundles) >= self.num_features:
            return                       # nothing bundled
        self.bundles = bundles
        self.bundled_bins, self.member_bundle, self.member_offset, \
            self.bundle_width = bundle_bins(self.bins, bundles, db, nb)
        log.info("EFB bundled %d features into %d columns",
                 self.num_features, len(bundles))

    # -- views --------------------------------------------------------------

    @property
    def num_features(self) -> int:
        return len(self.mappers)

    def feature_meta(self) -> FeatureMeta:
        if not self.mappers:
            # all features trivial: one dummy single-bin feature matching
            # the [N, 1] zero bin matrix — never splittable, so the tree
            # stays the constant prior (gbdt.cpp:378-396)
            return FeatureMeta(
                num_bin=np.ones(1, np.int32),
                missing_type=np.zeros(1, np.int32),
                default_bin=np.zeros(1, np.int32),
                monotone=np.zeros(1, np.int32),
                penalty=np.ones(1, np.float32),
                is_cat=np.zeros(1, np.int32))
        mono = None
        if self.config.monotone_constraints:
            mono = [0] * self.num_features
            for i, real in enumerate(self.used_feature_map):
                if real < len(self.config.monotone_constraints):
                    mono[i] = self.config.monotone_constraints[real]
        contri = None
        if self.config.feature_contri:
            contri = [1.0] * self.num_features
            for i, real in enumerate(self.used_feature_map):
                if real < len(self.config.feature_contri):
                    contri[i] = self.config.feature_contri[real]
        meta = FeatureMeta.from_mappers(self.mappers, mono, contri)
        if self.bundles is not None:
            meta = meta._replace(bundle=self.member_bundle,
                                 offset=self.member_offset)
        return meta

    def feature_infos(self) -> List[str]:
        """Per REAL feature; 'none' for unused (model header parity)."""
        infos = []
        for real in range(self.num_total_features):
            inner = self.real_to_inner.get(real)
            infos.append("none" if inner is None
                         else self.mappers[inner].feature_info())
        return infos

    def create_valid(self, X, metadata: Metadata) -> "TpuDataset":
        from .sparse import SparseMatrix
        if not isinstance(X, SparseMatrix):
            X = np.asarray(X)
        v = TpuDataset(self.config)
        v.construct_from_matrix(X, metadata, reference=self)
        # CreateValid's contract (dataset.cpp:368): the valid set BINS
        # with the train set's mappers, never re-derives them — the
        # streamed ingest path rides the same guarantee
        assert v.mappers is self.mappers, \
            "create_valid must never re-derive bin mappers"
        return v

    # -- binary cache (SaveBinaryFile parity, dataset.cpp:542) --------------

    # v2 writes nibble-packed dict bins; the version lives in the token
    # so a pre-v2 reader REJECTS new files instead of loading a dict it
    # cannot use. v1 files (plain array bins) are still readable.
    BINARY_TOKEN = b"______LightGBM_TPU_Binary_File_Tokenv2____\n"
    BINARY_TOKEN_V1 = b"______LightGBM_TPU_Binary_File_Token______\n"

    def _pack_nibble_columns(self, bins: Optional[np.ndarray] = None):
        """4-bit storage tier (the reference's Dense4bitsBin,
        src/io/dense_nbits_bin.hpp:37-58): columns with <= 16 bins are
        nibble-packed two-rows-per-byte in the binary cache. (No
        compute-path tier is needed here: 16-bin features already pack
        8 per 128-row MXU tile in the wave kernel, so packing would
        only inflate the matmul.) Returns (bins_or_packed, packed_cols).
        """
        if bins is None:
            bins = self.bins
        if bins is None or bins.dtype != np.uint8 \
                or not self.mappers:
            return bins, []
        packed_cols = [i for i, m in enumerate(self.mappers)
                       if m.num_bin <= 16]
        if not packed_cols:
            return bins, []
        out = {"shape": bins.shape}
        n = bins.shape[0]
        half = (n + 1) // 2
        for i in packed_cols:
            col = bins[:, i]
            lo = col[0::2]
            hi = np.zeros(half, np.uint8)
            hi[:n // 2] = col[1::2]
            out[i] = (lo | (hi << 4)).astype(np.uint8)
        packed_set = set(packed_cols)
        keep = [i for i in range(bins.shape[1])
                if i not in packed_set]
        out["rest"] = bins[:, keep]
        out["keep"] = keep
        return out, packed_cols

    @staticmethod
    def _unpack_nibble_columns(bins, packed_cols):
        if not packed_cols:
            return bins
        n, f = bins["shape"]
        full = np.zeros((n, f), np.uint8)
        full[:, bins["keep"]] = bins["rest"]
        for i in packed_cols:
            b = bins[i]
            full[0::2, i] = b[: (n + 1) // 2] & 0x0F
            full[1::2, i] = (b[: n // 2] >> 4) & 0x0F
        return full

    def save_binary(self, filename: str) -> None:
        import pickle
        # device-ingested sets download transiently (host_bins keeps
        # the device-resident layout authoritative)
        bins_repr, packed_cols = self._pack_nibble_columns(
            self.host_bins())
        with open(filename, "wb") as fh:
            fh.write(self.BINARY_TOKEN)
            pickle.dump({
                "num_data": self.num_data,
                "num_total_features": self.num_total_features,
                "mappers": [m.to_dict() for m in self.mappers],
                "used_feature_map": self.used_feature_map,
                "bins": bins_repr,
                "packed_cols": packed_cols,
                "label": self.metadata.label,
                "weights": self.metadata.weights,
                "query_boundaries": self.metadata.query_boundaries,
                "init_score": self.metadata.init_score,
                "feature_names": self.feature_names,
            }, fh, protocol=4)
        log.info("Saved binary dataset to %s", filename)

    @classmethod
    def is_binary_file(cls, filename: str) -> bool:
        try:
            with open(filename, "rb") as fh:
                tok = fh.read(len(cls.BINARY_TOKEN))
                return tok in (cls.BINARY_TOKEN, cls.BINARY_TOKEN_V1)
        except OSError:
            return False

    @classmethod
    def load_binary(cls, filename: str, config: Config) -> "TpuDataset":
        import pickle
        with open(filename, "rb") as fh:
            tok = fh.read(len(cls.BINARY_TOKEN))
            if tok not in (cls.BINARY_TOKEN, cls.BINARY_TOKEN_V1):
                log.fatal(f"{filename} is not a lightgbm_tpu binary file")
            d = pickle.load(fh)
        ds = cls(config)
        ds.num_data = d["num_data"]
        ds.num_total_features = d["num_total_features"]
        ds.mappers = [BinMapper.from_dict(m) for m in d["mappers"]]
        ds.used_feature_map = d["used_feature_map"]
        ds.real_to_inner = {r: i for i, r in enumerate(ds.used_feature_map)}
        ds.bins = cls._unpack_nibble_columns(
            d["bins"], d.get("packed_cols", []))
        ds.metadata = Metadata(d["label"], d["weights"], None, d["init_score"])
        ds.metadata.query_boundaries = d["query_boundaries"]
        ds.feature_names = d["feature_names"]
        ds.max_bin_global = max((m.num_bin for m in ds.mappers), default=1)
        return ds
