"""Dataset loading from text / binary files.

TPU-native counterpart of the reference DatasetLoader
(reference: src/io/dataset_loader.cpp:161-1111 LoadFromFile /
ConstructBinMappersFromTextData; column resolution
dataset_loader.cpp:53-159; sidecar files src/io/metadata.cpp:324-431).

Responsibilities: resolve label/weight/group/ignore/categorical columns
(by index or ``name:`` prefix against the header), parse the text file
(io/parser.py), split metadata columns out of the feature matrix, load
``.weight`` / ``.query`` / ``.init`` sidecar files, and construct the
binned TpuDataset. Binary files (save_binary) short-circuit straight to
TpuDataset.load_binary like dataset_loader.cpp:252-257.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..config import Config
from ..obs import registry as obs
from ..utils import log
from .dataset import Metadata, TpuDataset
from .file_io import open_file
from .parser import parse_file


def _parse_column_spec(spec: str, names: List[str], what: str) -> int:
    """'name:foo' or integer index -> index; -1 when unset
    (dataset_loader.cpp:53-112)."""
    spec = spec.strip()
    if not spec:
        return -1
    if spec.startswith("name:"):
        name = spec[5:]
        if name not in names:
            log.fatal(f"Could not find {what} column {name!r} in data file "
                      "(set header=true?)")
        return names.index(name)
    try:
        return int(spec)
    except ValueError:
        log.fatal(f"Bad {what} column spec {spec!r}; use an index or "
                  "'name:column_name'")


def _parse_multi_column_spec(spec: str, names: List[str],
                             what: str) -> Set[int]:
    """Comma-separated indices or 'name:a,b,c' (dataset_loader.cpp:113-159)."""
    spec = spec.strip()
    if not spec:
        return set()
    out: Set[int] = set()
    if spec.startswith("name:"):
        for name in spec[5:].split(","):
            name = name.strip()
            if not name:
                continue
            if name not in names:
                log.fatal(f"Could not find {what} column {name!r} in data "
                          "file (set header=true?)")
            out.add(names.index(name))
        return out
    for tok in spec.split(","):
        tok = tok.strip()
        if tok:
            out.add(int(tok))
    return out


def _read_float_file(path: str) -> Optional[np.ndarray]:
    """One float per line (metadata.cpp LoadWeights/LoadQueryBoundaries)."""
    if not os.path.isfile(path):
        return None
    vals = []
    with open(path) as fh:
        for ln in fh:
            ln = ln.strip()
            if ln and not ln.startswith("#"):
                vals.append([float(x) for x in ln.replace(",", " ").split()])
    if not vals:
        return None
    arr = np.asarray(vals, np.float64)
    return arr[:, 0] if arr.shape[1] == 1 else arr


class DatasetLoader:
    """LoadFromFile / column bookkeeping (dataset_loader.cpp:24-52)."""

    def __init__(self, config: Config,
                 predict_fun=None):
        self.config = config

    # -- text -> TpuDataset --------------------------------------------------

    def load_from_file(self, filename: str,
                       reference: Optional[TpuDataset] = None) -> TpuDataset:
        """LoadFromFile (dataset_loader.cpp:161-257). ``reference`` set
        = validation data binned with the train mappers (CreateValid)."""
        cfg = self.config
        if TpuDataset.is_binary_file(filename):
            log.info("Loading binary dataset %s", filename)
            return TpuDataset.load_binary(filename, cfg)
        bin_cache = filename + ".bin"
        if (cfg.enable_load_from_binary_file and reference is None
                and TpuDataset.is_binary_file(bin_cache)):
            log.info("Loading dataset from binary cache %s", bin_cache)
            return TpuDataset.load_binary(bin_cache, cfg)

        if cfg.two_round or cfg.tpu_out_of_core == 1:
            ds = self._load_two_round(filename, reference)
        else:
            X, meta, names, categorical = self._parse_with_metadata(
                filename)
            ds = TpuDataset(cfg)
            ds.construct_from_matrix(
                X, meta, categorical=categorical, reference=reference,
                feature_names=names or None)
        log.info("Finished loading %s: %d rows, %d used features",
                 filename, ds.num_data, ds.num_features)
        if cfg.save_binary and reference is None:
            ds.save_binary(bin_cache)
        return ds

    # -- two-round (memory-light) loading ------------------------------------

    def _data_lines(self, filename: str):
        """Yield data lines: header/comments/blanks skipped
        (TextReader parity, utils/text_reader.h)."""
        header_pending = self.config.header
        with open_file(filename) as fh:
            for ln in fh:
                t = ln.strip()
                if not t or t.startswith("#"):
                    continue
                if header_pending:
                    header_pending = False
                    continue
                yield ln.rstrip("\r\n")

    def _load_two_round(self, filename: str,
                        reference: Optional[TpuDataset] = None,
                        chunk_rows: int = 0) -> TpuDataset:
        """two_round=true (or tpu_out_of_core=1): the reference's
        memory-light path (dataset_loader.cpp LoadFromFile with
        two_round — SampleTextDataFromFile then a second streaming
        pass, :196-235/:657-704). Pass 1 counts rows and parses only a
        sampled subset to build the bin mappers; pass 2 re-streams the
        file in ``chunk_rows`` blocks (tpu_ooc_block_rows; 0 = 256k),
        binning each block straight into the uint8 matrix — the full
        float matrix never exists. With device ingest on, each block
        feeds the double-buffered device binner and even the host bin
        matrix disappears: peak RSS is bounded by the block size, not
        N (tpu_out_of_core=0 pins the host-bins fallback)."""
        cfg = self.config
        if chunk_rows <= 0:
            chunk_rows = int(cfg.tpu_ooc_block_rows) or (1 << 18)
        from .dataset import find_column_mappers
        from .parser import (_first_data_lines, detect_format,
                             parse_delimited, parse_libsvm)
        first, head = _first_data_lines(filename, 2, cfg.header, True)
        fmt = detect_format(first)
        delim = "\t" if fmt == "tsv" else ","
        full_names = ([t.strip() for t in head.split(delim)]
                      if cfg.header and head else [])
        label_all = _parse_column_spec(
            cfg.label_column, full_names,
            "label") if cfg.label_column else 0
        if label_all < 0:
            label_all = 0

        def parse_lines(lines, ncol_hint=0):
            if fmt == "libsvm":
                return parse_libsvm(lines, label_all, ncol_hint)
            return parse_delimited(lines, delim, label_all)

        # pass 1 (ONE scan): count rows, reservoir-sample the bin-
        # construction lines, and for libsvm track the true column
        # count across the WHOLE file (features absent from the sample
        # must still get bin slots — trivial, but present)
        cap = max(int(cfg.bin_construct_sample_cnt), 1)
        rng = np.random.default_rng(cfg.data_random_seed)
        reservoir: List[str] = []
        n = 0
        libsvm_maxidx = -1
        for ln in self._data_lines(filename):
            if fmt == "libsvm":
                # indices ascend in well-formed libsvm rows: the last
                # pair carries the row's max feature index
                tail = ln.rstrip().rsplit(None, 1)
                if len(tail) == 2 and ":" in tail[1]:
                    try:
                        libsvm_maxidx = max(
                            libsvm_maxidx,
                            int(tail[1].split(":", 1)[0]))
                    except ValueError:
                        pass
            if n < cap:
                reservoir.append(ln)
            else:
                j = int(rng.integers(0, n + 1))
                if j < cap:
                    reservoir[j] = ln
            n += 1
        if n == 0:
            log.fatal(f"Data file {filename} is empty")
        sparsed = parse_lines(reservoir,
                              libsvm_maxidx + 1 if fmt == "libsvm"
                              else 0)
        ncol = max(sparsed.num_columns,
                   libsvm_maxidx + 1 if fmt == "libsvm" else 0)
        # rows missing trailing delimited columns bin as missing (the
        # one-round parser's semantics); absent libsvm pairs are 0
        pad_value = 0.0 if fmt == "libsvm" else np.nan

        feat_names = list(full_names)
        if feat_names and sparsed.label is not None \
                and len(feat_names) > ncol:
            feat_names.pop(max(label_all, 0))
        (weight_idx, group_idx, keep_cols, categorical,
         feat_names) = self._resolve_columns(feat_names, ncol)

        ds = TpuDataset(cfg)
        ds.num_data = n
        ds.num_total_features = len(keep_cols)
        ds.feature_names = (feat_names if feat_names else
                            [f"Column_{i}"
                             for i in range(len(keep_cols))])
        if reference is not None:
            ds._reference = reference
            ds.mappers = reference.mappers
            ds.used_feature_map = reference.used_feature_map
            ds.real_to_inner = reference.real_to_inner
            ds.max_bin_global = reference.max_bin_global
            ds.feature_names = reference.feature_names
            ds.num_total_features = reference.num_total_features
        else:
            Xs = sparsed.values
            if Xs.shape[1] < ncol:
                Xs = np.pad(Xs, ((0, 0), (0, ncol - Xs.shape[1])),
                            constant_values=pad_value)
            ds._set_mappers(find_column_mappers(
                Xs[:, keep_cols], cfg, categorical,
                total_rows=n, presampled=True))

        # pass 2: stream + bin. With device ingest enabled the chunks
        # feed the jitted device binner (io/ingest.py) and the [F, N]
        # matrix assembles directly on device — parsing the next text
        # block is the host half of the double buffer, so transfer and
        # binning overlap the tokenizer. Host path otherwise.
        f_used = max(len(ds.mappers), 1)
        dtype = np.uint8 if ds.max_bin_global <= 256 else np.int32
        from .ingest import (DeviceBinner, IngestUnsupported,
                             ingest_enabled, ingest_mesh)
        stream = None
        efb_live = (reference is None and cfg.enable_bundle
                    and ds.num_features > 1)
        if (cfg.tpu_out_of_core != 0 and ingest_enabled(cfg)
                and ds.mappers
                and (reference is None or reference.bundles is None)):
            try:
                binner = DeviceBinner(ds.mappers, ds.used_feature_map,
                                      cfg, np.float64)
            except IngestUnsupported as e:
                log.warning("two_round device ingest unavailable (%s); "
                            "host binner", e)
            else:
                # valid sets ride as passenger columns of the grower
                # matrix (models/gbdt.py) — only the train set's rows
                # are worth sharding at ingest time
                mesh = ingest_mesh(cfg) if reference is None else None
                import jax
                if (mesh is not None and efb_live
                        and jax.process_count() > 1):
                    # an engaged EFB probe would need the global array
                    # materialized on one host, which a multi-process
                    # mesh cannot provide — host binner keeps the
                    # bundling decision bit-identical
                    log.debug("two_round: EFB probe + multi-process "
                              "mesh; host binner")
                elif mesh is not None:
                    stream = binner.start_sharded_stream(mesh, n)
                else:
                    stream = binner.start_stream()
        bins = (None if stream is not None
                else np.zeros((n, f_used), dtype))
        # EFB probe sample: the same rng(3) rows find_bundles would
        # draw, collected RAW while streaming and host-binned at the
        # end, so the bundling decision is bit-identical to the host
        # path's (io/dataset.py _efb_would_bundle has the in-memory
        # analog)
        efb_sorted = None
        efb_rows: List[np.ndarray] = []
        if stream is not None and efb_live:
            from .efb import sample_rows_for_probe
            idx = sample_rows_for_probe(n)
            efb_sorted = np.arange(n) if idx is None else np.sort(idx)
        label = np.zeros(n, np.float32)
        weight = np.zeros(n, np.float32) if weight_idx >= 0 else None
        group_col = np.zeros(n, np.float64) if group_idx >= 0 else None
        row = 0
        buf: List[str] = []

        def flush(buf):
            nonlocal row
            if not buf:
                return
            obs.counter("ooc/blocks").add(1)
            obs.counter("ooc/disk_bytes").add(
                sum(len(s) + 1 for s in buf))
            p = parse_lines(buf, ncol)
            Xc = p.values
            if Xc.shape[1] < ncol:
                # delimited rows missing trailing columns -> missing
                # (NaN, matching the one-round parser); absent libsvm
                # pairs -> 0 (libsvm sparse semantics)
                Xc = np.pad(Xc, ((0, 0), (0, ncol - Xc.shape[1])),
                            constant_values=pad_value)
            elif Xc.shape[1] > ncol:
                if fmt == "libsvm":
                    # pass-1 sized columns from each row's LAST pair;
                    # exceeding it means some row has non-ascending
                    # feature indices — truncating would silently drop
                    # features, so fail loudly instead
                    log.fatal(
                        f"two_round: libsvm row block has "
                        f"{Xc.shape[1]} columns, expected {ncol}; "
                        "feature indices are not ascending within a "
                        "row. Sort indices or load with "
                        "two_round=false")
                log.warning("two_round: row block has %d columns, "
                            "expected %d; extra columns ignored",
                            Xc.shape[1], ncol)
                Xc = Xc[:, :ncol]
            k = Xc.shape[0]
            if p.label is not None:
                label[row:row + k] = p.label
            if weight is not None:
                weight[row:row + k] = Xc[:, weight_idx]
            if group_col is not None:
                group_col[row:row + k] = Xc[:, group_idx]
            Xf = Xc[:, keep_cols]
            if stream is not None:
                if efb_sorted is not None:
                    lo = np.searchsorted(efb_sorted, row)
                    hi = np.searchsorted(efb_sorted, row + k)
                    if hi > lo:
                        efb_rows.append(Xf[efb_sorted[lo:hi] - row])
                stream.feed(Xf)
            else:
                bins[row:row + k] = ds.bin_rows(Xf)
            obs.counter("loader/two_round_blocks").add(1)
            obs.counter("loader/two_round_rows").add(k)
            row += k

        for ln in self._data_lines(filename):
            buf.append(ln)
            if len(buf) >= chunk_rows:
                flush(buf)
                buf = []
        flush(buf)
        if stream is None:
            ds.bins = bins
        else:
            dev = stream.finish()
            bundled = False
            if efb_sorted is not None and efb_rows:
                from .efb import would_bundle
                bundled = would_bundle(
                    ds.bin_rows(np.concatenate(efb_rows)),
                    ds.mappers, cfg.max_conflict_rate)
            if bundled:
                # EFB engages on this data: materialize the host
                # layout so _apply_efb bundles the same full matrix
                # the host path would have built
                log.info("two_round: EFB bundles this data; "
                         "materializing device bins on host")
                ds.bins = np.ascontiguousarray(
                    np.asarray(dev)[:, :n].T).astype(dtype, copy=False)
            else:
                ds.bins_t_dev = dev
                ds.bins_t_dev_pad = dev.shape[1] - n
                log.info("two_round: streamed device ingest "
                         "(%d rows%s)", n,
                         f", {ds.bins_t_dev_pad} pad"
                         if ds.bins_t_dev_pad else "")
        ds.metadata = self._assemble_metadata(
            filename, label if sparsed.label is not None else None,
            weight, group_col)
        ds.metadata.check_or_partition(n)
        if ds.bins is not None:
            ds._apply_efb()  # handles both fresh and reference bundles
        try:
            import resource
            obs.gauge("ooc/rss_peak_mb").set(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 1024.0)
        except ImportError:        # non-POSIX host
            pass
        log.info("two_round load: %d rows binned in %d-row blocks",
                 n, chunk_rows)
        return ds

    def _parse_with_metadata(self, filename: str
                             ) -> Tuple[np.ndarray, Metadata, List[str],
                                        List[int]]:
        cfg = self.config
        # resolve the label against the raw header line (full column
        # set, label included) without parsing the whole file twice
        full_names: List[str] = []
        if cfg.header:
            with open_file(filename) as fh:
                head = fh.readline()
            from .parser import detect_format
            delim = {"csv": ",", "tsv": "\t"}.get(
                detect_format([head]), "\t")
            full_names = [t.strip() for t in head.rstrip("\r\n")
                          .split(delim)]
        label_all = _parse_column_spec(
            cfg.label_column, full_names,
            "label") if cfg.label_column else 0
        if label_all < 0:
            label_all = 0
        parsed, header_names = parse_file(filename, header=cfg.header,
                                          label_idx=label_all)
        X = parsed.values
        label = parsed.label

        (weight_idx, group_idx, keep_cols, categorical,
         feat_names) = self._resolve_columns(list(header_names),
                                             X.shape[1])
        weight = X[:, weight_idx].astype(np.float32) if weight_idx >= 0 \
            else None
        group_col = X[:, group_idx] if group_idx >= 0 else None
        if len(keep_cols) != X.shape[1]:
            X = X[:, keep_cols]

        meta = self._assemble_metadata(filename, label, weight, group_col)
        return X, meta, feat_names, categorical

    def _resolve_columns(self, feat_names: List[str], ncol: int):
        """weight/group/ignore/categorical column resolution. Indices
        do NOT count the label column (docs/Parameters: "index starts
        from 0 ... doesn't count the label column"); names resolve
        against the post-label layout. Returns
        (weight_idx, group_idx, keep_cols, categorical, kept_names)
        with ``categorical`` remapped to the kept layout."""
        cfg = self.config
        weight_idx = _parse_column_spec(
            cfg.weight_column, feat_names,
            "weight") if cfg.weight_column else -1
        group_idx = _parse_column_spec(
            cfg.group_column, feat_names,
            "group") if cfg.group_column else -1
        ignore = _parse_multi_column_spec(cfg.ignore_column, feat_names,
                                          "ignore")
        categorical = _parse_multi_column_spec(
            cfg.categorical_feature, feat_names, "categorical")
        drop = sorted({i for i in (weight_idx, group_idx) if i >= 0}
                      | {i for i in ignore if 0 <= i < ncol})
        keep_cols = [i for i in range(ncol) if i not in drop]
        remap = {old: new for new, old in enumerate(keep_cols)}
        categorical = sorted({remap[c] for c in categorical
                              if c in remap})
        if feat_names:
            feat_names = [feat_names[i] for i in keep_cols
                          if i < len(feat_names)]
        return weight_idx, group_idx, keep_cols, categorical, feat_names

    def _assemble_metadata(self, filename: str, label, weight,
                           group_col) -> Metadata:
        """Metadata from in-file columns + sidecar files
        (metadata.cpp:324-431): <file>.weight, <file>.query, init scores
        from config or <file>.init."""
        cfg = self.config
        if weight is None:
            w = _read_float_file(filename + ".weight")
            if w is not None:
                weight = np.asarray(w, np.float32).reshape(-1)
                log.info("Loading weights from %s.weight", filename)
        group = None
        if group_col is not None:
            # query-id column -> boundaries via run-length counts
            ids = np.asarray(group_col)
            change = np.nonzero(np.diff(ids))[0] + 1
            bounds = np.concatenate([[0], change, [len(ids)]])
            group = np.diff(bounds)
        else:
            q = _read_float_file(filename + ".query")
            if q is None:
                q = _read_float_file(filename + ".query.weight")
            if q is not None:
                group = np.asarray(q, np.int64).reshape(-1)
                log.info("Loading query boundaries from %s.query", filename)
        init_score = None
        init_path = cfg.initscore_filename or (filename + ".init")
        isc = _read_float_file(init_path)
        if isc is not None:
            init_score = np.asarray(isc, np.float64)
            if init_score.ndim == 2:       # [N, K] column-major flatten
                init_score = init_score.T.reshape(-1)
            log.info("Loading initial scores from %s", init_path)
        return Metadata(label=label, weight=weight, group=group,
                        init_score=init_score)

    # -- prediction-side text load ------------------------------------------

    def load_predict_matrix(self, filename: str, num_features: int
                            ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Parse a file for prediction: the label column may be absent
        when rows carry exactly num_features columns (Predictor path,
        parser.cpp:25-62 via infer_label_idx)."""
        cfg = self.config
        parsed, _ = parse_file(filename, header=cfg.header, label_idx=0,
                               num_features_hint=num_features)
        X = parsed.values
        if X.shape[1] < num_features:
            X = np.pad(X, ((0, 0), (0, num_features - X.shape[1])),
                       constant_values=np.nan)
        elif X.shape[1] > num_features:
            X = X[:, :num_features]
        return X, parsed.label
