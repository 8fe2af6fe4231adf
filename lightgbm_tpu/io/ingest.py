"""Streamed TPU-side ingest: device binning with a double-buffered
host->device chunk pipeline.

The host binner (io/binning.py + the threaded C++ bulk binner) maps
values to bins one full column scan at a time while the TPU idles; at
HIGGS scale that is ~29 s of binning against ~112 s of training. This
module moves the value->bin mapping onto the device, mirroring the
reference's streamed two-round ingest design
(DatasetLoader::ConstructFromSampleData, src/io/dataset_loader.cpp:499:
bin boundaries from a bounded ``bin_construct_sample_cnt`` sample, then
a streaming pass that bins rows as they arrive):

- bin boundaries still come from the bounded row sample
  (io/dataset.py find_column_mappers — unchanged semantics);
- the value->bin map runs on device as a jitted chunked kernel: the
  count of each feature's ``bin_upper_bound`` below a value, by
  compares where a feature has at most ``_COUNT_MAX_BOUNDS`` bounds
  and by a branchless lower-bound search past that, plus the
  missing/zero-bin/categorical rules of ``BinMapper.value_to_bin``,
  BIT-EXACT against the host path (see "exactness" below);
- raw row chunks stream host->device double-buffered: a worker thread
  prepares chunk k+1 (column select where the columns are not the
  matrix's own, the tail's pad; the values cross as the bits they
  are) while chunk k's async ``device_put`` + kernel dispatch are in
  flight, so transfer overlaps compute and the full host uint8 matrix
  + transpose + bulk upload disappear from the critical path;
- the feature-major ``[F, N]`` ``bins_t`` matrix is assembled directly
  on device (one concatenate over chunk outputs), which is exactly the
  layout the wave grower consumes (models/gbdt.py);
- when the configured tree learner row-shards (``tree_learner`` data /
  voting over a >1-device mesh), ``bin_matrix_sharded`` round-robins
  the chunk pipeline ACROSS the mesh and assembles the matrix directly
  under the grower's ``NamedSharding`` — each device receives and bins
  only its own contiguous row block, so no single-device staging copy
  of the dataset ever exists (Design.md §7). ONE prefetch worker feeds
  either pipeline, the sharded one's D device queues too: the main
  thread uploads and dispatches, and on four chips 1, 2 and 4 workers
  take the same time (PERF.md, PR 36).

Exactness
---------
jax runs with x64 disabled, so comparing values against the float64
``bin_upper_bound`` cannot use device floats directly. Instead every
comparison is done in the *sortable-integer* order of IEEE-754: a
float maps to an unsigned key (sign bit flipped for positives, all
bits flipped for negatives) whose integer order equals the float
order. Keys are computed ON DEVICE from the raw bits in both cases:

- float32 input: each float64 bound is rounded DOWN to float32 first.
  For any f32 value x and f64 bound b, ``b < x  <=>  floor32(b) < x``
  (the largest f32 <= b preserves the strict predicate over f32
  operands), so the f32 key count reproduces the f64
  ``searchsorted(..., side="left")`` exactly.
- float64 input: each value crosses as its two raw uint32 words (a
  zero-copy view, 8 B a value); the device keys the pair into a high
  and a low plane and compares lexicographically,
  ``(bh < xh) | ((bh == xh) & (bl < xl))`` — exact total order, no
  rounding anywhere.

``-0.0`` is normalized to ``+0.0`` on both sides before key
extraction: numpy's searchsorted treats them as equal while the key
order would not, and the zero-as-one-bin boundaries sit at
±kZeroThreshold right next to that crossing.

NaN follows ``value_to_bin``: mapped as 0.0, then overridden to the
last bin for MissingType.NAN features (the device reads NaN from the
bits: an exponent of all ones and a mantissa that is not zero).
Categorical columns are truncated to int on host (few columns, cheap)
and matched against the category table on device.
"""
from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import threading
from typing import List, Optional, Sequence

import numpy as np

from ..analysis import lockorder
from ..obs import registry as obs
from ..obs import trace
from ..utils import log, timing
from .binning import BinMapper, BinType, MissingType

# both routes find a value's bin by COUNTING the bounds below it (a
# compare and an add a bound on the vector unit, the rows on the lanes;
# three compares with float64's two key planes) where a feature has at
# most this many bounds, and by a gather search above it: the search's
# eight dependent gathers a value are what a chip is slowest at
# (PERF.md, PR 36)
_COUNT_MAX_BOUNDS = 256
_TARGET_CHUNK_BYTES = 64 << 20      # ~64 MB of raw values per chunk
_MIN_CHUNK_ROWS = 1 << 14
_MAX_CHUNK_ROWS = 1 << 21


class IngestUnsupported(Exception):
    """Raised at DeviceBinner construction when the mapper set has a
    shape the device kernel cannot reproduce bit-exactly (callers fall
    back to the host binner)."""


def ingest_enabled(config) -> bool:
    """Config gate: tpu_ingest=1 forces the device path on any backend
    (tests), 0 disables, -1 (default) auto-enables on a real TPU."""
    t = getattr(config, "tpu_ingest", -1)
    if t == 0:
        return False
    if t >= 1:
        return True
    from ..utils.device import on_tpu
    return on_tpu()


def ingest_mesh(config):
    """The device mesh sharded ingest should target, or None for the
    single-device pipeline: the configured tree learner must row-shard
    (data/voting) over more than one device. Uses the SAME mesh
    construction as the learners (parallel/learners.py make_mesh), so
    the [F, N] bins land exactly where the shard_mapped grower will
    read them — no single-device staging, no per-iteration reshard."""
    if getattr(config, "tree_learner", "serial") not in ("data",
                                                         "voting"):
        return None
    from ..parallel.learners import training_mesh
    return training_mesh(config)


def shard_width(n: int, D: int, hist_chunk: int = 0) -> int:
    """Per-device row-shard width S for ``n`` global rows over ``D``
    mesh devices: device (mesh position) gd owns global rows
    [gd*S, (gd+1)*S). Each shard aligns to the grower's row chunk so
    _setup_grower ADOPTS this padding instead of re-padding +
    resharding the whole mesh-resident matrix: the pinned
    tpu_hist_chunk when set, else the LARGEST power-of-two unit
    u <= MAX_HIST_CHUNK (the autotune candidate ceiling, exhaustive
    tier included) with n >= 4*D*u — the grower only chunk-aligns when
    n >= 4*D*kchunk, so every kchunk it can align to satisfies
    kchunk <= u and (both powers of two) divides S; pad stays <= S/4
    by the same bound. ONE function for the single-process sharded
    path, the multi-process per-host path, and the loader's host
    row-block slicing (io/distributed.py) — their geometries cannot
    drift."""
    S = max(-(-int(n) // int(D)), 1)
    from ..ops.autotune import MAX_HIST_CHUNK
    if hist_chunk > 0:
        u = hist_chunk if n >= 4 * D * hist_chunk else 1
    else:
        u = 1
        while u * 2 <= MAX_HIST_CHUNK and n >= 4 * D * (u * 2):
            u *= 2
    if u > 1:
        S = -(-S // u) * u
    return S


def host_row_block(n_global: int, mesh, hist_chunk: int = 0) -> tuple:
    """(row_start, row_stop, S) — the contiguous GLOBAL row range this
    process must hold so its addressable devices' shard blocks are
    coverable by bin_matrix_multihost (row_stop clamps to n_global)."""
    import jax
    positions = list(mesh.devices.reshape(-1))
    S = shard_width(n_global, len(positions), hist_chunk)
    proc = jax.process_index()
    owned = [gd for gd, dev in enumerate(positions)
             if dev.process_index == proc]
    if not owned:
        return 0, 0, S
    lo = min(owned) * S
    hi = min((max(owned) + 1) * S, int(n_global))
    return min(lo, int(n_global)), hi, S


def mappers_supported(mappers: Sequence[BinMapper]) -> bool:
    """True when every mapper is reproducible on device: categorical
    tables must fit int32 (host matching runs at int64)."""
    for m in mappers:
        if m.bin_type == BinType.CATEGORICAL:
            if any(abs(int(c)) >= 2 ** 31 for c in m.bin_2_categorical):
                return False
    return True


def auto_chunk_rows(config, n_features: int, itemsize: int) -> int:
    """Rows per pipeline chunk: the config knob, or a power of two
    sized so one chunk's raw values are ~64 MB on the wire."""
    knob = int(getattr(config, "tpu_ingest_chunk_rows", 0) or 0)
    if knob > 0:
        return knob
    per_row = max(n_features * itemsize, 1)
    c = max(_TARGET_CHUNK_BYTES // per_row, 1)
    c = 1 << int(np.floor(np.log2(c)))
    return int(min(max(c, _MIN_CHUNK_ROWS), _MAX_CHUNK_ROWS))


class PrefetchError(RuntimeError):
    """A prefetch thunk failed after retries; the message carries the
    chunk index so a dead pipeline names WHERE it died. The original
    failure rides ``__cause__``."""


def prefetch(thunks, depth: int = 2, what: str = "chunk",
             policy=None,
             wait_span: Optional[str] = "ingest/prefetch_wait"):
    """Evaluate an iterator of zero-arg callables on ONE worker thread
    with a bounded lookahead, yielding results in order — the host
    half of the double buffer: while the device chews on chunk k, the
    worker slices/keys chunk k+1. One thread is deliberate: host prep
    is memory-bandwidth bound and the results must stay ordered.

    Spans: the worker's spans name the span open here when their thunk
    was queued as their ``cause`` (obs/trace.carry), and the consumer's
    wait for the worker is a ``wait_span`` span — its timer against the
    worker's own (``ingest/prep_chunk``) says which side sets the pace.
    A caller that is not the ingest passes ``wait_span=None`` and emits
    none: the timer is the ingest's alone.

    Fault tolerance: each thunk runs under the bounded-backoff retry
    policy (utils/retry.py; ``policy`` — e.g. the DeviceBinner's
    ``tpu_retry_attempts``-sized one — or the module default when
    None, so transient failures recover in place on the worker). A
    persistent failure surfaces as a ``PrefetchError`` naming the
    failed chunk's index, every queued lookahead future is cancelled,
    and the worker shuts down cleanly — the pipeline never
    half-drains past a dead chunk."""
    from ..utils import retry
    it = iter(thunks)
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ingest-prefetch") as ex:
        q: collections.deque = collections.deque()  # (index, future)
        submitted = 0

        def submit() -> bool:
            nonlocal submitted
            try:
                thunk = next(it)
            except StopIteration:
                return False
            idx = submitted
            submitted += 1
            q.append((idx, ex.submit(
                trace.carry(retry.call), thunk, what=f"{what} {idx}",
                policy=policy)))
            return True

        try:
            for _ in range(max(depth, 1)):
                if not submit():
                    break
            while q:
                idx, fut = q.popleft()
                submit()
                try:
                    with (trace.span(wait_span, cat="ingest") if wait_span
                          else contextlib.nullcontext()):
                        res = fut.result()
                    yield res
                except Exception as e:  # noqa: BLE001 — annotate+stop
                    raise PrefetchError(
                        f"{what} {idx} failed after retries "
                        f"({type(e).__name__}: {e}); pipeline "
                        f"cancelled") from e
        finally:
            for _, f in q:
                f.cancel()


# -- device-resident chunk ring ----------------------------------------------

# upload-region bucket floor: small windows re-pad to at most this many
# rows, so the splice shapes (and their compiled programs) stay few
_RING_UPLOAD_FLOOR = 256


def ring_upload_rows(k: int, prev_valid: int, chunk_rows: int) -> int:
    """Rows the ring path actually uploads for a chunk carrying ``k``
    live rows over a slot whose previous occupant had ``prev_valid``:
    the next power of two covering BOTH (stale rows of a larger
    previous window must be overwritten with pad constants), floored
    at ``_RING_UPLOAD_FLOOR`` and capped at the full chunk."""
    u = max(k, prev_valid, 1)
    b = _RING_UPLOAD_FLOOR
    while b < u:
        b *= 2
    return min(b, chunk_rows)


class ChunkRing:
    """Bounded ring of device-RESIDENT raw ingest chunks, reused across
    dataset constructions of the same chunk geometry — the lrb.py
    sliding-window loop's training matrix.

    The streamed ingest pipeline pads every chunk to the binner's fixed
    ``chunk_rows`` on the HOST so all chunks share one compiled kernel;
    for a sample-sized window that means most of the transfer is pad
    bytes, re-uploaded every window. With a ring, each chunk slot keeps
    its last assembled device transfer tuple resident; the next window
    uploads only the bucketed live-row region (``ring_upload_rows``)
    and the resident tail — whose rows are pad constants by the
    invariant below — is spliced back on device. The raw value/key
    words are MAPPER-INDEPENDENT, so a fresh window's fresh bin
    mappers bin the resident rows exactly as a full re-upload would:
    training results are bit-identical.

    Invariant: every resident array's rows at index >= its recorded
    ``valid`` row count hold the host binner's pad constants (zeros;
    -1 for the categorical plane). Maintained because each upload
    region covers ``max(k, prev_valid)`` rows and carries those same
    constants beyond row ``k``.

    Slots are keyed by chunk index and guarded by the binner's chunk
    geometry key — a dataset with a different chunk shape simply
    misses. Thread-safe: the lrb trainer thread ingests while the main
    thread may be building the next window's ring-less eval batches.
    """

    def __init__(self, capacity: int = 8):
        self._lock = lockorder.named_lock("ingest.chunk_ring._lock")
        self._cap = max(int(capacity), 1)
        # guarded-by: _lock
        self._slots: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()

    @property
    def capacity(self) -> int:
        return self._cap

    def get(self, slot: int, geom_key) -> tuple:
        """-> (resident arrays tuple or None, valid_rows)."""
        with self._lock:
            ent = self._slots.get(slot)
            if ent is None or ent[0] != geom_key:
                return None, 0
            self._slots.move_to_end(slot)
            return ent[1], ent[2]

    def put(self, slot: int, geom_key, arrays, valid: int) -> None:
        with self._lock:
            self._slots[slot] = (geom_key, arrays, int(valid))
            self._slots.move_to_end(slot)
            while len(self._slots) > self._cap:
                self._slots.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._slots.clear()


# -- sortable-integer float keys --------------------------------------------

def _keys64_host(v: np.ndarray):
    """float64 [..] -> (hi, lo) uint32 key planes, integer order ==
    float order (NaN-free input)."""
    b = np.ascontiguousarray(v, np.float64).view(np.uint64)
    neg = (b >> np.uint64(63)).astype(bool)
    mask = np.where(neg, np.uint64(0xFFFFFFFFFFFFFFFF),
                    np.uint64(0x8000000000000000))
    u = b ^ mask
    return ((u >> np.uint64(32)).astype(np.uint32),
            u.astype(np.uint32))


def _keys64_dev(w):
    """Raw float64 words uint32 [C, 2Fn] (little-endian: word 1 is the
    high word) -> (hi, lo) key planes and the NaN mask, each [Fn, C],
    on the device: ``_keys64_host`` of ``where(isnan, 0, v) + 0.0``,
    bit for bit. The word pair goes to a major axis after the
    transpose, never to a trailing axis of 2 (which pads to 128
    lanes)."""
    import jax.numpy as jnp
    C, F2 = w.shape
    wt = w.T.reshape(F2 // 2, 2, C)
    lo, hi = wt[:, 0], wt[:, 1]
    mag = hi & jnp.uint32(0x7FFFFFFF)
    # NaN: an exponent of all ones and a mantissa that is not zero
    nanm = (mag > jnp.uint32(0x7FF00000)) | (
        (mag == jnp.uint32(0x7FF00000)) & (lo != 0))
    zero = nanm | ((mag == 0) & (lo == 0))     # NaN, -0.0 -> +0.0
    hi = jnp.where(zero, jnp.uint32(0), hi)
    lo = jnp.where(zero, jnp.uint32(0), lo)
    neg = (hi >> jnp.uint32(31)).astype(bool)
    return (hi ^ jnp.where(neg, jnp.uint32(0xFFFFFFFF),
                           jnp.uint32(0x80000000)),
            lo ^ jnp.where(neg, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)),
            nanm)


def _key32_host(v: np.ndarray) -> np.ndarray:
    """float32 [..] -> uint32 key (NaN-free input)."""
    b = np.ascontiguousarray(v, np.float32).view(np.uint32)
    neg = (b >> np.uint32(31)).astype(bool)
    mask = np.where(neg, np.uint32(0xFFFFFFFF), np.uint32(0x80000000))
    return b ^ mask


def _floor32(b64: np.ndarray) -> np.ndarray:
    """Largest float32 <= each float64 entry (rounds DOWN, so the
    strict `bound < x` predicate is preserved for float32 x)."""
    f = b64.astype(np.float32)
    over = f.astype(np.float64) > b64
    down = np.nextafter(f, np.float32(-np.inf))
    return np.where(over, down, f).astype(np.float32)


def _cat_iv_host(col: np.ndarray) -> np.ndarray:
    """Host half of the categorical map: truncate toward zero to int32
    with NaN/out-of-range -> -1 (never a category; negatives were
    NaN-ified at find_bin time, bin.cpp:304)."""
    col = np.asarray(col, np.float64)
    with np.errstate(invalid="ignore"):
        bad = np.isnan(col) | (np.abs(col) >= 2.0 ** 31)
        safe = np.where(bad, -1.0, col)
    return safe.astype(np.int64).astype(np.int32)


# -- the device binner -------------------------------------------------------

class DeviceBinner:
    """Jitted chunked value->bin kernel for one mapper set.

    Built once per dataset; ``bin_matrix`` (whole in-memory matrix,
    threaded prefetch) and ``start_stream`` (two-round loader feed)
    share the same compiled chunk function. ``x_dtype`` selects the
    exact-comparison scheme (see module docstring)."""

    def __init__(self, mappers: List[BinMapper],
                 used_feature_map: np.ndarray, config,
                 x_dtype) -> None:
        import jax.numpy as jnp
        if not mappers:
            raise IngestUnsupported("no usable features")
        if not mappers_supported(mappers):
            raise IngestUnsupported("categorical table exceeds int32")
        x_dtype = np.dtype(x_dtype)
        if x_dtype not in (np.float32, np.float64):
            raise IngestUnsupported(f"dtype {x_dtype} not supported")
        self.mappers = mappers
        self.f32_input = x_dtype == np.float32
        used = np.asarray(used_feature_map, np.int64)
        self.num_inner = [i for i, m in enumerate(mappers)
                          if m.bin_type == BinType.NUMERICAL]
        self.cat_inner = [i for i, m in enumerate(mappers)
                         if m.bin_type != BinType.NUMERICAL]
        self.num_cols = used[self.num_inner]       # real/source columns
        self.cat_cols = used[self.cat_inner]
        # every source column numerical, used and in order: a chunk is
        # then the matrix's own rows, sent as they lie
        self._num_cols_identity = bool(
            len(self.num_cols)
            and np.array_equal(self.num_cols,
                               np.arange(len(self.num_cols))))
        max_bin_global = max(m.num_bin for m in mappers)
        self.out_dtype = np.uint8 if max_bin_global <= 256 else np.int32
        self.chunk_rows = auto_chunk_rows(config, len(mappers),
                                          x_dtype.itemsize)
        # explicit Pallas row chunk, when the operator pinned one —
        # lets sharded ingest align shards to the exact chunk the
        # grower will use instead of the 32k candidate superset
        self.hist_chunk = int(getattr(config, "tpu_hist_chunk", 0) or 0)
        # transient-failure policy for this pipeline's prep + transfer
        # seams: attempts come from the tpu_retry_attempts knob
        from ..utils import retry
        self.retry_policy = retry.RetryPolicy(
            attempts=int(getattr(config, "tpu_retry_attempts", 4) or 4))

        # numerical tables: per-feature search range r, NaN bin, and the
        # bound keys padded to a power of two with the max key (never
        # `< x`, so padding never counts)
        rs, nan_bins, bounds = [], [], []
        for i in self.num_inner:
            m = mappers[i]
            r = m.num_bin - 1
            nb = -1
            if m.missing_type == MissingType.NAN:
                r -= 1
                nb = m.num_bin - 1
            rs.append(r)
            nan_bins.append(nb)
            bounds.append(np.asarray(m.bin_upper_bound[:r], np.float64)
                          + 0.0)                     # -0.0 -> +0.0
        max_r = max(rs, default=0)
        Bp = 1 << max(int(np.ceil(np.log2(max_r + 1))), 0)
        self._Bp = Bp
        Fn = len(self.num_inner)
        if self.f32_input:
            bk = np.full((Fn, Bp), np.uint32(0xFFFFFFFF), np.uint32)
            for k, bu in enumerate(bounds):
                bk[k, :len(bu)] = _key32_host(_floor32(bu))
            self._bhi = jnp.asarray(bk)
            self._blo = None
        else:
            bh = np.full((Fn, Bp), np.uint32(0xFFFFFFFF), np.uint32)
            bl = np.full((Fn, Bp), np.uint32(0xFFFFFFFF), np.uint32)
            for k, bu in enumerate(bounds):
                h, lo = _keys64_host(bu)
                bh[k, :len(bu)] = h
                bl[k, :len(bu)] = lo
            self._bhi = jnp.asarray(bh)
            self._blo = jnp.asarray(bl)
        self._nan_bin = jnp.asarray(np.asarray(nan_bins, np.int32))
        # numerical features binned by the count, not the search
        self.counts = bool(Fn) and Bp <= _COUNT_MAX_BOUNDS

        # categorical tables (kept per-feature: lengths differ)
        self._cats = [jnp.asarray(np.asarray(m.bin_2_categorical,
                                             np.int64).astype(np.int32))
                      for m in (mappers[i] for i in self.cat_inner)]
        self._cat_nbin = [mappers[i].num_bin for i in self.cat_inner]

        # static output permutation: chunk kernel emits [numerical;
        # categorical] row blocks, take() restores mapper order
        order = np.asarray(self.num_inner + self.cat_inner, np.int64)
        self._inv_perm = jnp.asarray(np.argsort(order).astype(np.int32))
        self._chunk_fn = self._build_chunk_fn()

    # -- kernel --------------------------------------------------------------

    def _build_chunk_fn(self):
        import jax
        import jax.numpy as jnp

        Bp = self._Bp
        bhi, blo = self._bhi, self._blo
        nan_bin = self._nan_bin
        cats, cat_nbin = self._cats, self._cat_nbin
        inv_perm = self._inv_perm
        out_dtype = self.out_dtype
        f32_input = self.f32_input
        Fn = len(self.num_inner)

        def gather(b, idx):                  # b [F,Bp], idx [C,F] -> [C,F]
            return jax.vmap(lambda col, i: col[i],
                            in_axes=(0, 1), out_axes=1)(b, idx)

        def lower_bound(xh, xl):
            """Branchless count of bounds < x per (row, feature):
            uniform binary search, Bp a power of two, pad = max key."""
            pos = jnp.zeros(xh.shape, jnp.int32)
            step = Bp
            while step > 1:
                step //= 2
                idx = pos + (step - 1)
                gh = gather(bhi, idx)
                go = gh < xh
                if xl is not None:
                    gl = gather(blo, idx)
                    go = go | ((gh == xh) & (gl < xl))
                pos = jnp.where(go, pos + step, pos)
            return pos

        def count_below(xh, xl):
            """The same count by compares, in the key planes' order
            (``xl`` None: the float32 route's one plane): bounds are
            sorted and their pad is the max key in both planes, which
            is below nothing. The rows lie on the lanes ([Fn, C] in and
            out), a bound is a column broadcast along them, eight
            bounds a turn of the loop so the count is carried through
            memory Bp / 8 times."""
            turn = min(Bp, 8)

            def body(i, acc):
                for j in range(turn):
                    col = jax.lax.dynamic_slice_in_dim(
                        bhi, i * turn + j, 1, axis=1)      # [Fn, 1]
                    below = col < xh
                    if xl is not None:
                        lcol = jax.lax.dynamic_slice_in_dim(
                            blo, i * turn + j, 1, axis=1)
                        below = below | ((col == xh) & (lcol < xl))
                    acc = acc + below.astype(jnp.int32)
                return acc
            return jax.lax.fori_loop(
                0, Bp // turn, body, jnp.zeros(xh.shape, jnp.int32))

        def key32_dev(x):
            b = jax.lax.bitcast_convert_type(x, jnp.uint32)
            neg = (b >> jnp.uint32(31)).astype(bool)
            mask = jnp.where(neg, jnp.uint32(0xFFFFFFFF),
                             jnp.uint32(0x80000000))
            return b ^ mask

        counts = self.counts

        def chunk(x, cat_iv):
            """One chunk -> [F, C] bins. x: the raw numerical values
            [C, Fn] as float32, or float64's words as uint32 [C, 2Fn]."""
            parts = []
            if Fn:
                if f32_input:
                    nanm = jnp.isnan(x)
                    v = jnp.where(nanm, jnp.float32(0.0), x) \
                        + jnp.float32(0.0)           # -0.0 -> +0.0
                    xk = key32_dev(v)
                    pos = (count_below(xk.T, None).T if counts
                           else lower_bound(xk, None))
                    out_num = jnp.where(nanm & (nan_bin[None, :] >= 0),
                                        nan_bin[None, :], pos).T
                else:
                    xh, xl, nanm = _keys64_dev(x)
                    pos = (count_below(xh, xl) if counts
                           else lower_bound(xh.T, xl.T).T)
                    out_num = jnp.where(nanm & (nan_bin[:, None] >= 0),
                                        nan_bin[:, None], pos)
                parts.append(out_num)
            for k, cvals in enumerate(cats):
                iv = cat_iv[:, k]
                default = jnp.int32(cat_nbin[k] - 1)
                if cvals.shape[0]:
                    eq = iv[:, None] == cvals[None, :]
                    hit = jnp.argmax(eq, axis=1).astype(jnp.int32)
                    out_c = jnp.where(eq.any(axis=1), hit, default)
                else:
                    out_c = jnp.full(iv.shape, default, jnp.int32)
                parts.append(out_c[None, :])
            allout = (parts[0] if len(parts) == 1
                      else jnp.concatenate(parts, axis=0))
            return jnp.take(allout, inv_perm, axis=0).astype(out_dtype)

        # jit-capture: ok(Fn, f32_input, out_dtype, nan_bin, cats,
        # cat_nbin, inv_perm, key32_dev, lower_bound, count_below,
        # counts) — per-binner jit: the captured mapper tables ARE the
        # kernel's constants, derived from THIS dataset's bin mappers and
        # cached on the binner instance (one binner per dataset,
        # asserted by create_valid's mapper-reuse contract).
        return jax.jit(chunk)

    # -- host-side chunk prep ------------------------------------------------

    def _prep_chunk(self, X: np.ndarray, pad_to: Optional[int] = None):
        """Slice one chunk on the host (worker-thread half of the
        double buffer). Returns the transfer tuple, tail-padded to the
        fixed chunk shape so every chunk reuses one compiled kernel —
        or to ``pad_to`` rows (the ring path, which splices the
        remaining pad tail from the device-resident slot instead of
        re-uploading it)."""
        from ..utils import faults
        if faults.active():
            faults.check("ingest.prep", context=f"{X.shape[0]} rows")
        with trace.span("ingest/prep_chunk", cat="ingest",
                        args={"rows": int(X.shape[0])}):
            return self._prep_chunk_inner(X, pad_to)

    def _prep_chunk_inner(self, X: np.ndarray,
                          pad_to: Optional[int] = None):
        C = pad_to if pad_to is not None else self.chunk_rows
        k = X.shape[0]
        pad = C - k
        if self._num_cols_identity and X.shape[1] == len(self.num_cols):
            Xn = X                       # a view: no host copy at all
        elif len(self.num_cols):
            Xn = X[:, self.num_cols]
        else:
            Xn = np.zeros((k, 0), X.dtype)
        if self.f32_input:
            x = np.ascontiguousarray(Xn, np.float32)
        else:
            # the float64 words as they lie, keyed on the device
            x = np.ascontiguousarray(Xn, np.float64).view(np.uint32)
        if pad:
            x = np.pad(x, ((0, pad), (0, 0)))    # zeros: +0.0
        if len(self.cat_cols):
            cat_iv = _cat_iv_host(X[:, self.cat_cols])
            if pad:
                cat_iv = np.pad(cat_iv, ((0, pad), (0, 0)),
                                constant_values=-1)
        else:
            cat_iv = np.zeros((C, 0), np.int32)
        return (x, cat_iv), k

    def _submit(self, prepped, device=None, assemble=None):
        """Main-thread half: async transfer + kernel dispatch. Returns
        the [F, k] device block (tail chunks sliced to their true
        rows). ``device`` pins the transfer AND the kernel to one mesh
        device (sharded ingest); None = the default device.
        ``assemble`` (the ring path) maps the transferred arrays to
        the full-chunk tuple the kernel consumes — ONE copy of the
        transfer protocol (fault point, retry, span, h2d ledger)
        serves both paths."""
        import jax
        arrs, k = prepped
        nbytes = sum(int(a.nbytes) for a in arrs)
        from ..utils import faults, retry

        def put():
            # the ingest.device_put fault point: an injected transient
            # fault retries with bounded backoff (the recovery drills);
            # a real device_put failure on an attached chip — out of
            # HBM — is not transient and raises (utils/retry.py)
            if faults.active():
                faults.check("ingest.device_put",
                             context=f"{nbytes} bytes")
            return jax.device_put(arrs, device)

        span_args = {"rows": int(k), "bytes": nbytes}
        if assemble is not None:
            span_args["ring"] = True
        with trace.span("ingest/chunk", cat="ingest", args=span_args):
            with timing.phase("binning/device_xfer"):
                arrs = retry.call(
                    put, what="ingest device_put",
                    policy=self.retry_policy)
            obs.counter("ingest/h2d_bytes").add(nbytes)
            obs.counter("ingest/h2d_chunks").add(1)
            obs.counter("ingest/rows_device").add(k)
            if self.f32_input:
                # rows that crossed the wire as the float32 they were
                obs.counter("ingest/f32_rows").add(k)
            if self.counts:
                # rows whose numerical features the count binned
                obs.counter("ingest/rows_counted").add(k)
            if assemble is not None:
                arrs = assemble(arrs)
            out = self._chunk_fn(*arrs)
        if k < self.chunk_rows:
            out = out[:, :k]
        return out

    # -- drivers -------------------------------------------------------------

    def bin_matrix(self, X: np.ndarray,
                   ring: Optional[ChunkRing] = None):
        """Whole in-memory matrix -> [F, N] device bins with the
        double-buffered pipeline (worker preps chunk k+1 while chunk
        k's transfer + kernel are in flight). With a ``ring``, chunk
        slots reuse the device-resident buffers of the previous
        same-geometry construction and only the bucketed live-row
        region crosses the wire (see ChunkRing)."""
        import jax.numpy as jnp
        n = X.shape[0]
        C = self.chunk_rows
        if ring is not None:
            if -(-n // C) <= ring.capacity:
                return self._bin_matrix_ringed(X, ring)
            # a matrix wider than the ring would evict every slot
            # before its next-window reuse: every get would miss while
            # every put still pins a full resident chunk — pure
            # overhead, so take the plain path instead
            log.debug("chunk ring bypassed: %d chunks exceed ring "
                      "capacity %d", -(-n // C), ring.capacity)
        starts = list(range(0, n, C))

        def thunk(r0):
            return lambda: self._prep_chunk(X[r0:min(r0 + C, n)])

        outs = [self._submit(p)
                for p in prefetch((thunk(r0) for r0 in starts),
                                  what="ingest chunk",
                                  policy=self.retry_policy)]
        bins_t = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)
        log.debug("device ingest: %d rows x %d features in %d chunk(s) "
                  "of %d rows", n, len(self.mappers), len(outs), C)
        return bins_t

    # -- ring path ------------------------------------------------------------

    def _geom_key(self) -> tuple:
        """Chunk geometry the ring's resident buffers are only valid
        for: the raw-value planes depend on the source columns, dtype
        scheme and fixed chunk rows — NOT on the bin mappers, which is
        exactly why a fresh window's fresh mappers can bin resident
        rows bit-identically."""
        return (self.chunk_rows, self.f32_input,
                tuple(int(c) for c in self.num_cols),
                tuple(int(c) for c in self.cat_cols))

    def _ring_tail(self, idx: int, rows: int, like) -> "object":
        """Device-created pad tail for a cold slot: the host binner's
        pad constants (zeros; -1 for the categorical plane), never
        crossing the wire."""
        import jax.numpy as jnp
        fill = -1 if idx == 1 else 0
        return jnp.full((rows,) + tuple(like.shape[1:]), fill,
                        like.dtype)

    def _ring_assemble(self, up, resident, U: int):
        """Splice the uploaded [U, ...] row blocks onto each resident
        slot's pad tail -> full chunk_rows arrays (on device)."""
        import jax.numpy as jnp
        C = self.chunk_rows
        if U >= C:                       # full-width uploads pass through
            return tuple(up)
        return tuple(
            jnp.concatenate([a, resident[i][U:] if resident is not None
                             else self._ring_tail(i, C - U, a)], axis=0)
            for i, a in enumerate(up))

    def _bin_matrix_ringed(self, X: np.ndarray, ring: ChunkRing):
        import jax.numpy as jnp
        n = X.shape[0]
        C = self.chunk_rows
        geom = self._geom_key()
        plans = []                      # (slot, live rows, U, resident)
        for slot, r0 in enumerate(range(0, n, C)):
            k = min(C, n - r0)
            resident, valid = ring.get(slot, geom)
            plans.append((slot, r0, k,
                          ring_upload_rows(k, valid, C), resident))

        def thunk(p):
            slot, r0, k, U, _ = p
            return lambda: (p, self._prep_chunk(X[r0:r0 + k], pad_to=U))

        outs = []
        saved = 0
        for p, prepped in prefetch((thunk(p) for p in plans),
                                   what="ingest ring chunk",
                                   policy=self.retry_policy):
            slot, _, k, U, resident = p

            def assemble(up, slot=slot, resident=resident, U=U, k=k):
                full = self._ring_assemble(up, resident, U)
                if U < C:
                    # full-width uploads are NOT stored: pinning a
                    # whole chunk buys nothing (the next partial
                    # window's cold path makes its pad tail on device)
                    # and would force that window to re-cover the full
                    # previous valid extent
                    ring.put(slot, geom, full, valid=k)
                return full

            outs.append(self._submit(prepped, assemble=assemble))
            # bounded-cardinality: two literal names (hit/miss)
            obs.counter("ingest/ring_hits"
                        if resident is not None
                        else "ingest/ring_misses").add(1)
            # bytes the full-pad path would have shipped for the rows
            # the ring kept resident (or created on device)
            up, _k = prepped
            if U < C:
                saved += sum((C - U) * int(a.nbytes) // U for a in up)
        if saved:
            obs.counter("ingest/ring_saved_bytes").add(saved)
        bins_t = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)
        log.debug("device ingest (ring): %d rows x %d features in %d "
                  "chunk(s) of %d rows", n, len(self.mappers),
                  len(outs), C)
        return bins_t

    def bin_matrix_sharded(self, X: np.ndarray, mesh):
        """Whole in-memory matrix -> ROW-SHARDED [F, N_pad] device bins
        under ``NamedSharding(mesh, P(None, AXIS))``, assembled with NO
        single-device staging: device d owns the contiguous global row
        block [d*S, (d+1)*S) (S = ceil(N/D); tail rows of the last
        shard are zero bins, the same values row padding would write),
        its chunks stream host->device pinned to d, and the chunk
        submission round-robins ACROSS devices so every chip's transfer
        + bin kernel overlap the next chip's host prep. ONE prefetch
        worker preps for all D queues and the main thread submits: a
        worker a queue takes the same time on four chips (PERF.md,
        PR 36). Bit-exact with ``bin_matrix``: the identical compiled
        chunk kernel maps the identical row slices — only the
        destination device differs.

        Returns a jax.Array whose trailing ``N_pad - N`` columns are
        padding (the caller records the true row count)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.learners import AXIS

        devs = list(mesh.devices.reshape(-1))
        D = len(devs)
        n = X.shape[0]
        C = self.chunk_rows
        S = shard_width(n, D, self.hist_chunk)

        # interleaved (device, row-slice) submission order: chunk k of
        # every shard before chunk k+1 of any — the round-robin that
        # keeps all D transfer queues busy while ONE prefetch worker
        # preps ahead in the same order
        tasks = []   # (device index, start row, rows)
        max_chunks = -(-S // C)
        for k in range(max_chunks):
            for d in range(D):
                r0 = d * S + k * C
                r1 = min(d * S + S, n, r0 + C)
                if r0 < min(d * S + S, n):
                    tasks.append((d, r0, r1 - r0))

        def thunk(t):
            d, r0, rows = t
            return lambda: (d, self._prep_chunk(X[r0:r0 + rows]))

        per_dev = [[] for _ in range(D)]
        for prepped in prefetch((thunk(t) for t in tasks),
                                what="sharded ingest chunk",
                                policy=self.retry_policy):
            d, p = prepped
            per_dev[d].append(self._submit(p, device=devs[d]))

        shards = []
        for d in range(D):
            rows_d = max(min(S, n - d * S), 0)
            parts = per_dev[d]
            if rows_d < S:
                # zero-bin tail (row padding): committed to device d so
                # the assembled shard never leaves it
                parts.append(jax.device_put(
                    jnp.zeros((len(self.mappers), S - rows_d),
                              self.out_dtype), devs[d]))
            shards.append(parts[0] if len(parts) == 1
                          else jnp.concatenate(parts, axis=1))
        sharding = NamedSharding(mesh, P(None, AXIS))
        bins_t = jax.make_array_from_single_device_arrays(
            (len(self.mappers), D * S), sharding, shards)
        log.debug("sharded device ingest: %d rows x %d features over "
                  "%d device(s) (%d-row shards, %d-row chunks)",
                  n, len(self.mappers), D, S, C)
        return bins_t

    def bin_matrix_multihost(self, X_local: np.ndarray, mesh,
                             n_global: int, row_start: int):
        """Per-host half of a MULTI-PROCESS sharded ingest: this host
        streams only its own contiguous row block through the
        double-buffered pipeline onto its ADDRESSABLE devices, and the
        global [F, N_pad] bin matrix assembles across processes via
        ``make_array_from_single_device_arrays`` — no rank ever holds
        (or transfers) another rank's rows. The device->row-block map
        is IDENTICAL to ``bin_matrix_sharded``'s (global mesh position
        gd owns global rows [gd*S, (gd+1)*S)), so a single-process
        mesh and a multi-process mesh of the same size produce the
        same global layout bit-for-bit.

        ``X_local`` holds global rows [row_start, row_start + len)
        and must cover every block owned by this process's devices
        (parallel/elastic.py's loader slices exactly that).
        """
        import jax
        from ..parallel import cluster
        from ..parallel.learners import AXIS

        positions = list(mesh.devices.reshape(-1))
        D = len(positions)
        n = int(n_global)
        C = self.chunk_rows
        S = shard_width(n, D, self.hist_chunk)

        proc = jax.process_index()
        local = [(gd, dev) for gd, dev in enumerate(positions)
                 if dev.process_index == proc]
        n_local = X_local.shape[0]
        for gd, _ in local:
            lo = gd * S
            hi = min(lo + S, n)
            if lo < hi and not (row_start <= lo
                                and hi <= row_start + n_local):
                raise ValueError(
                    f"multihost ingest: rank's rows [{row_start}, "
                    f"{row_start + n_local}) do not cover device "
                    f"{gd}'s block [{lo}, {hi}) — slice per-host data "
                    f"with elastic.host_row_block so host and device "
                    f"blocks line up")

        # interleaved (device, chunk) submission across the LOCAL
        # devices — the same round-robin overlap as the single-process
        # sharded path, per host
        tasks = []     # (local index, global row start, rows)
        max_chunks = -(-S // C)
        for k in range(max_chunks):
            for li, (gd, _) in enumerate(local):
                r0 = gd * S + k * C
                r1 = min(gd * S + S, n, r0 + C)
                if r0 < min(gd * S + S, n):
                    tasks.append((li, r0, r1 - r0))

        def thunk(t):
            li, r0, rows = t
            lo = r0 - row_start
            return lambda: (li, self._prep_chunk(
                X_local[lo:lo + rows]))

        per_dev = [[] for _ in local]
        for prepped in prefetch((thunk(t) for t in tasks),
                                what="multihost ingest chunk",
                                policy=self.retry_policy):
            li, p = prepped
            per_dev[li].append(self._submit(p, device=local[li][1]))

        import jax.numpy as jnp
        shards = []
        for li, (gd, dev) in enumerate(local):
            rows_d = max(min(S, n - gd * S), 0)
            parts = per_dev[li]
            if rows_d < S:
                parts.append(jax.device_put(
                    jnp.zeros((len(self.mappers), S - rows_d),
                              self.out_dtype), dev))
            shards.append(parts[0] if len(parts) == 1
                          else jnp.concatenate(parts, axis=1))
        bins_t = cluster.local_shards_to_global(
            shards, (len(self.mappers), D * S), mesh, None, AXIS)
        obs.counter("ingest/rows_local_host").add(
            sum(min(S, max(n - gd * S, 0)) for gd, _ in local))
        log.info("multihost device ingest: rank %d/%d binned %d of %d "
                 "global rows onto %d local device(s) (%d-row shards)",
                 cluster.rank(), cluster.world(),
                 sum(min(S, max(n - gd * S, 0)) for gd, _ in local),
                 n, len(local), S)
        return bins_t

    def start_stream(self) -> "IngestStream":
        return IngestStream(self)

    def start_sharded_stream(self, mesh, n_global: int
                             ) -> "ShardedIngestStream":
        return ShardedIngestStream(self, mesh, n_global)


# -- CSR-native sparse ingest -------------------------------------------------

# sparse chunks carry a VARIABLE entry count: pad each plane set to a
# power-of-two bucket so the compiled chunk kernel is shared across
# chunks/windows (the step-cache shape-bucketing discipline); sentinel
# entries carry feature index F and are dropped by the device scatter
_SPARSE_ENTRY_FLOOR = 2048


def sparse_entry_bucket(e: int) -> int:
    """Padded entry-plane length for ``e`` explicit entries — the
    shared pow2 shape-taper, floored so tiny chunks share one compiled
    kernel."""
    from ..ops.step_cache import pow2_bucket
    return pow2_bucket(e, _SPARSE_ENTRY_FLOOR)


class SparseDeviceBinner(DeviceBinner):
    """Device-side binning of CSR chunks riding the same
    double-buffered prefetch pipeline as the dense ``DeviceBinner``.

    The host half (worker thread) slices a row-chunk of the CSR matrix
    and keys its explicit VALUES on the host (``_keys64_host``'s
    sortable-integer f64 hi/lo planes, the order ``_keys64_dev`` makes
    on the device for the dense route) — with
    the entry COLUMN/ROW indices as two more planes on the transfer
    thunk. The device half runs the dense kernel's branchless
    lower-bound search PER ENTRY (bounds row gathered by each entry's
    feature) and
    scatters the resulting bin codes over a zero-bin-filled ``[F, C]``
    block — the dense feature-major chunk layout, assembled without any
    host [N, F] matrix at any width. Bit-exact vs the host
    ``value_to_bin`` by the same argument as the dense kernel: the key
    comparisons are identical, and implicit cells take the
    host-computed ``zero_bins`` constants.

    Categorical entries are coded on the host in the prep thunk (few,
    cheap — the dense path already host-truncates categoricals).

    ``bin_matrix_sparse`` optionally also returns the zero-suppressed
    (code, feature, row) coordinate planes — device-resident, already
    binned — which feed the sparse histogram tier
    (ops/hist_wave.py ``wave_histogram_sparse``)."""

    def __init__(self, mappers: List[BinMapper],
                 used_feature_map: np.ndarray, config) -> None:
        super().__init__(mappers, used_feature_map, config, np.float64)
        import jax.numpy as jnp
        from .sparse import zero_bins
        self._zb_dev = jnp.asarray(zero_bins(mappers))
        # real column -> (inner feature, numerical-bounds row) lookups,
        # built lazily at the matrix width (entries on TRIVIAL columns
        # must be dropped, and those columns sit outside used_feature_map)
        self._lut_nf = -1
        self._lut_inner = None
        self._lut_numpos = None
        self._inner_is_cat = np.zeros(len(mappers), bool)
        self._inner_is_cat[self.cat_inner] = True
        self._sparse_fn = self._build_sparse_chunk_fn()

    def _lut(self, nf: int):
        if self._lut_nf != nf:
            used = np.asarray(
                [int(c) for c in np.concatenate(
                    [self.num_cols, self.cat_cols])] or [], np.int64)
            inner_of = np.concatenate(
                [self.num_inner, self.cat_inner]).astype(np.int64) \
                if len(used) else np.zeros(0, np.int64)
            real2inner = np.full(nf, -1, np.int64)
            real2inner[used] = inner_of
            inner2numpos = np.full(len(self.mappers), 0, np.int64)
            inner2numpos[self.num_inner] = np.arange(
                len(self.num_inner))
            self._lut_nf = nf
            self._lut_inner = real2inner
            self._lut_numpos = inner2numpos
        return self._lut_inner, self._lut_numpos

    # -- device kernel -------------------------------------------------------

    def _build_sparse_chunk_fn(self):
        import jax
        import jax.numpy as jnp

        Bp = self._Bp
        bhi, blo = self._bhi, self._blo
        nan_bin = self._nan_bin
        zb = self._zb_dev
        out_dtype = self.out_dtype
        C = self.chunk_rows
        F = len(self.mappers)
        Fn = len(self.num_inner)

        def lower_bound_entries(xh, xl, fb):
            """Count of bounds < x per entry, bounds row gathered by
            the entry's feature — the dense kernel's uniform binary
            search, per entry instead of per (row, feature)."""
            pos = jnp.zeros(xh.shape, jnp.int32)
            step = Bp
            while step > 1:
                step //= 2
                idx = pos + (step - 1)
                gh = bhi[fb, idx]
                go = gh < xh
                gl = blo[fb, idx]
                go = go | ((gh == xh) & (gl < xl))
                pos = jnp.where(go, pos + step, pos)
            return pos

        def chunk(r0, xa, xb, nan, nb, ni, nr, ci, cr, cc):
            """One CSR chunk -> ([F, C] bins, per-entry coords).

            xa/xb: f64 hi/lo key planes of the numerical entry values;
            nan: host NaN mask; nb: bounds-row index; ni/nr: inner
            feature + local row per numerical entry; ci/cr/cc: inner
            feature / local row / host-coded bin per categorical
            entry. Sentinel (pad) entries carry feature F — out of
            bounds for every scatter, dropped by mode="drop"."""
            out = jnp.broadcast_to(
                zb.astype(out_dtype)[:, None], (F, C))
            if Fn and xa.shape[0]:
                pos = lower_bound_entries(xa, xb, nb)
                code_n = jnp.where(nan & (nan_bin[nb] >= 0),
                                   nan_bin[nb], pos)
            else:
                code_n = jnp.zeros((0,), jnp.int32)
            out = out.at[ni, nr].set(code_n.astype(out_dtype),
                                     mode="drop")
            if cc.shape[0]:
                out = out.at[ci, cr].set(cc.astype(out_dtype),
                                         mode="drop")
            codes = jnp.concatenate([code_n, cc]).astype(jnp.int32)
            feat = jnp.concatenate([ni, ci])
            rows = jnp.concatenate([nr, cr]) + r0
            return out, codes, feat, rows

        # jit-capture: ok(C, zb, nan_bin, out_dtype,
        # lower_bound_entries) — per-binner jit (see the dense
        # DeviceBinner note above): zero-bin/nan tables are this
        # dataset's mapper constants, cached on the binner instance.
        return jax.jit(chunk)

    # -- host-side chunk prep ------------------------------------------------

    def _prep_sparse_chunk(self, sm, r0: int, r1: int):
        from ..utils import faults
        if faults.active():
            faults.check("ingest.prep", context=f"{r1 - r0} rows")
        with trace.span("ingest/prep_chunk", cat="ingest",
                        args={"rows": int(r1 - r0), "sparse": True}):
            return self._prep_sparse_chunk_inner(sm, r0, r1)

    def _prep_sparse_chunk_inner(self, sm, r0: int, r1: int):
        sub = sm.row_slice(r0, r1)
        real2inner, inner2numpos = self._lut(sm.shape[1])
        inner = real2inner[sub.cols]
        lrows = sub.rows().astype(np.int32)
        F = len(self.mappers)
        kept = inner >= 0
        is_cat = np.zeros(len(inner), bool)
        is_cat[kept] = self._inner_is_cat[inner[kept]]
        numm = kept & ~is_cat
        catm = kept & is_cat

        # numerical planes: keyed values + indices (NaN -> key of +0.0
        # with the mask riding separately, -0.0 normalized — the dense
        # route's device keys' exact recipe)
        v = sub.data[numm]
        nanm = np.isnan(v)
        v = np.where(nanm, 0.0, v) + 0.0
        xa, xb = _keys64_host(v)
        nb = inner2numpos[inner[numm]].astype(np.int32)
        ni = inner[numm].astype(np.int32)
        nr = lrows[numm]
        if len(self.num_inner):
            En = sparse_entry_bucket(len(v))
            pad = En - len(v)
            if pad:
                xa = np.pad(xa, (0, pad))
                xb = np.pad(xb, (0, pad))
                nanm = np.pad(nanm, (0, pad))
                nb = np.pad(nb, (0, pad))
                ni = np.pad(ni, (0, pad), constant_values=F)
                nr = np.pad(nr, (0, pad))

        # categorical planes: host-coded (few columns, cheap — the
        # dense path host-truncates categoricals the same way)
        if len(self.cat_inner):
            cis, crs, ccs = [], [], []
            for i in self.cat_inner:
                m2 = catm & (inner == i)
                if not m2.any():
                    continue
                ccs.append(np.asarray(
                    self.mappers[i].value_to_bin(sub.data[m2]),
                    np.int32))
                cis.append(np.full(int(m2.sum()), i, np.int32))
                crs.append(lrows[m2])
            ci = (np.concatenate(cis) if cis else np.zeros(0, np.int32))
            cr = (np.concatenate(crs) if crs else np.zeros(0, np.int32))
            cc = (np.concatenate(ccs) if ccs else np.zeros(0, np.int32))
            Ec = sparse_entry_bucket(len(cc))
            pad = Ec - len(cc)
            ci = np.pad(ci, (0, pad), constant_values=F)
            cr = np.pad(cr, (0, pad))
            cc = np.pad(cc, (0, pad))
        else:
            ci = cr = cc = np.zeros(0, np.int32)
        return (r0, (xa, xb, nanm, nb, ni, nr, ci, cr, cc),
                r1 - r0)

    # -- driver --------------------------------------------------------------

    def _submit_sparse(self, prepped):
        import jax
        import jax.numpy as jnp
        r0, arrs, k = prepped
        nbytes = sum(int(a.nbytes) for a in arrs)
        from ..utils import faults, retry

        def put():
            if faults.active():
                faults.check("ingest.device_put",
                             context=f"{nbytes} bytes")
            return jax.device_put(arrs)

        with trace.span("ingest/chunk", cat="ingest",
                        args={"rows": int(k), "bytes": nbytes,
                              "sparse": True}):
            with timing.phase("binning/device_xfer"):
                arrs = retry.call(put, what="sparse ingest device_put",
                                  policy=self.retry_policy)
            obs.counter("ingest/h2d_bytes").add(nbytes)
            obs.counter("ingest/h2d_chunks").add(1)
            obs.counter("ingest/rows_device").add(k)
            out, codes, feat, rows = self._sparse_fn(jnp.int32(r0),
                                                     *arrs)
        if k < self.chunk_rows:
            out = out[:, :k]
        return out, (codes, feat, rows)

    def bin_matrix_sparse(self, sm, want_coords: bool = False):
        """CSR matrix -> ([F, N] device bins, coords or None) with the
        double-buffered pipeline: the worker keys chunk k+1's entry
        planes while chunk k's transfer + kernel are in flight.
        ``coords`` = (codes, feat, rows) device planes over every
        chunk's entries — sentinel (pad) entries carry feature F, which
        every downstream scatter drops."""
        import jax.numpy as jnp
        n = sm.shape[0]
        C = self.chunk_rows
        starts = list(range(0, n, C))

        def thunk(r0):
            return lambda: self._prep_sparse_chunk(
                sm, r0, min(r0 + C, n))

        outs, codes, feats, rows = [], [], [], []
        for p in prefetch((thunk(r0) for r0 in starts),
                          what="sparse ingest chunk",
                          policy=self.retry_policy):
            block, co = self._submit_sparse(p)
            outs.append(block)
            if want_coords:
                codes.append(co[0])
                feats.append(co[1])
                rows.append(co[2])
        bins_t = outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)
        coords = None
        if want_coords:
            coords = (jnp.concatenate(codes), jnp.concatenate(feats),
                      jnp.concatenate(rows))
        log.debug("sparse device ingest: %d rows x %d features "
                  "(nnz=%d) in %d chunk(s) of %d rows", n,
                  len(self.mappers), sm.nnz, len(outs), C)
        return bins_t, coords


class IngestStream:
    """Feed-driven variant for streaming loaders (two-round text
    loading): rows arrive in parser-sized blocks, are repacked to the
    binner's chunk granularity and dispatched asynchronously — the
    caller's parsing of the next block IS the host half of the double
    buffer."""

    def __init__(self, binner: DeviceBinner):
        self._b = binner
        self._pend: List[np.ndarray] = []
        self._pend_rows = 0
        self._outs: List = []
        self._rows = 0

    def feed(self, X: np.ndarray) -> None:
        C = self._b.chunk_rows
        self._pend.append(np.asarray(X))
        self._pend_rows += X.shape[0]
        self._rows += X.shape[0]
        while self._pend_rows >= C:
            block = (self._pend[0] if len(self._pend) == 1
                     else np.concatenate(self._pend, axis=0))
            self._outs.append(self._b._submit(
                self._b._prep_chunk(block[:C])))
            rest = block[C:]
            self._pend = [rest] if rest.shape[0] else []
            self._pend_rows = rest.shape[0]

    def finish(self):
        """-> [F, N] device bins over every fed row."""
        import jax.numpy as jnp
        if self._pend_rows:
            block = (self._pend[0] if len(self._pend) == 1
                     else np.concatenate(self._pend, axis=0))
            self._outs.append(self._b._submit(self._b._prep_chunk(block)))
            self._pend, self._pend_rows = [], 0
        if not self._outs:
            return jnp.zeros((len(self._b.mappers), 0),
                             self._b.out_dtype)
        return (self._outs[0] if len(self._outs) == 1
                else jnp.concatenate(self._outs, axis=1))


class ShardedIngestStream:
    """Feed-driven variant of ``bin_matrix_sharded`` /
    ``bin_matrix_multihost`` for the out-of-core two-round loader:
    global rows arrive IN FILE ORDER in parser-sized blocks, and mesh
    position gd owns the contiguous global row block [gd*S, (gd+1)*S)
    exactly as the in-memory sharded drivers lay it out — so the stream
    slices at shard/chunk boundaries and dispatches each completed
    chunk pinned to the owning device while the caller parses the next
    text block. On a multi-process mesh every rank parses the whole
    file but TRANSFERS only the rows its addressable devices own;
    ``finish()`` assembles the global [F, N_pad] array with the same
    cross-process assembly as ``bin_matrix_multihost``. Bit-exact vs
    the in-memory drivers: identical compiled chunk kernel, identical
    row->device map (chunk k of shard gd covers global rows
    [gd*S + k*C, min(gd*S + (k+1)*C, (gd+1)*S, n)))."""

    def __init__(self, binner: DeviceBinner, mesh, n_global: int):
        import jax
        self._b = binner
        self._mesh = mesh
        self._n = int(n_global)
        self._positions = list(mesh.devices.reshape(-1))
        self._S = shard_width(self._n, len(self._positions),
                              binner.hist_chunk)
        proc = jax.process_index()
        self._local = {gd: dev
                       for gd, dev in enumerate(self._positions)
                       if dev.process_index == proc}
        self._multiproc = any(d.process_index != proc
                              for d in self._positions)
        self._cursor = 0                # global row index of _pend[0]
        self._pend: List[np.ndarray] = []
        self._pend_rows = 0
        self._outs = {gd: [] for gd in self._local}
        self._rows_local = 0

    def _boundary(self):
        """(owning shard, next dispatch boundary) for the cursor: the
        end of the current chunk, clipped to the shard end and n."""
        S, C, n = self._S, self._b.chunk_rows, self._n
        gd = self._cursor // S
        off = self._cursor - gd * S
        return gd, min(gd * S + (off // C + 1) * C, (gd + 1) * S, n)

    def feed(self, X: np.ndarray) -> None:
        self._pend.append(np.asarray(X))
        self._pend_rows += X.shape[0]
        while self._pend_rows and self._cursor < self._n:
            gd, bnd = self._boundary()
            need = bnd - self._cursor
            if self._pend_rows < need:
                break
            self._emit(gd, need)

    def _emit(self, gd: int, rows: int) -> None:
        block = (self._pend[0] if len(self._pend) == 1
                 else np.concatenate(self._pend, axis=0))
        take, rest = block[:rows], block[rows:]
        self._pend = [rest] if rest.shape[0] else []
        self._pend_rows = int(rest.shape[0])
        self._cursor += rows
        if gd in self._local:
            self._outs[gd].append(self._b._submit(
                self._b._prep_chunk(take), device=self._local[gd]))
            self._rows_local += rows

    def finish(self):
        """-> row-sharded [F, N_pad] device bins over every fed row
        (trailing ``N_pad - n`` columns are zero-bin padding)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.learners import AXIS
        while self._pend_rows and self._cursor < self._n:
            gd, bnd = self._boundary()
            self._emit(gd, min(bnd - self._cursor,
                                   self._pend_rows))
        n, S = self._n, self._S
        D = len(self._positions)
        F = len(self._b.mappers)
        shards = []
        for gd, dev in self._local.items():
            rows_d = max(min(S, n - gd * S), 0)
            parts = self._outs[gd]
            if rows_d < S:
                # zero-bin tail (row padding): committed to device gd
                # so the assembled shard never leaves it
                parts.append(jax.device_put(
                    jnp.zeros((F, S - rows_d), self._b.out_dtype),
                    dev))
            shards.append(parts[0] if len(parts) == 1
                          else jnp.concatenate(parts, axis=1))
        if self._multiproc:
            from ..parallel import cluster
            obs.counter("ingest/rows_local_host").add(self._rows_local)
            return cluster.local_shards_to_global(
                shards, (F, D * S), self._mesh, None, AXIS)
        sharding = NamedSharding(self._mesh, P(None, AXIS))
        return jax.make_array_from_single_device_arrays(
            (F, D * S), sharding, shards)
