"""Kernel autotuner + shared VMEM geometry (ops/autotune.py).

Covers the tuning-cache lifecycle (round-trip, version invalidation,
stale candidate sets), the VMEM-budget predicate that gates candidate
tiles, the single-source-of-truth property (the forest kernel's
BlockSpecs and _pallas_tc's byte estimate derive from the same shape
function), and tuned-vs-default numerical parity on both Pallas hot
paths.
"""
import json

import numpy as np
import pytest

from conftest import TEST_PARAMS, fit_gbdt, make_binary

from lightgbm_tpu.ops import autotune
from lightgbm_tpu.ops.autotune import (Autotuner, TuningCache,
                                       TUNING_CACHE_VERSION)


def _counting_measure(times):
    """measure() stub: returns scripted seconds, counts invocations."""
    calls = []

    def measure(cand):
        calls.append(cand)
        return times[json.dumps(cand, sort_keys=True)]

    return measure, calls


CANDS = [{"chunk": 4096}, {"chunk": 8192}, {"chunk": 16384}]
TIMES = {json.dumps(c, sort_keys=True): t
         for c, t in zip(CANDS, (3e-3, 1e-3, 2e-3))}
KEY = {"F": 28, "B": 64, "tier": "int8", "device": "test"}


def _failed() -> int:
    from lightgbm_tpu.obs import registry as obs
    return obs.counter("autotune/candidates_failed").value


class TestTuningCache:
    def test_roundtrip_no_retiming(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        measure, calls = _counting_measure(TIMES)
        t = Autotuner("on", path)
        choice = t.best("fused_hist", KEY, CANDS, measure)
        assert choice == {"chunk": 8192}          # fastest candidate
        assert len(calls) == len(CANDS)
        # a FRESH tuner (new process analog) serves the persisted
        # winner without timing anything
        measure2, calls2 = _counting_measure(TIMES)
        t2 = Autotuner("on", path)
        assert t2.best("fused_hist", KEY, CANDS, measure2) == choice
        assert calls2 == []
        # the file records the winner and the per-candidate timings
        with open(path) as fh:
            d = json.load(fh)
        assert d["version"] == TUNING_CACHE_VERSION
        (entry,) = d["entries"].values()
        assert entry["choice"] == {"chunk": 8192}
        assert len(entry["timings_ms"]) == len(CANDS)

    def test_version_mismatch_invalidates(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        key = TuningCache.key_string("fused_hist", KEY)
        with open(path, "w") as fh:
            json.dump({"version": TUNING_CACHE_VERSION + 999,
                       "entries": {key: {"choice": {"chunk": 4096}}}},
                      fh)
        measure, calls = _counting_measure(TIMES)
        choice = Autotuner("on", path).best("fused_hist", KEY, CANDS,
                                            measure)
        # the stale-version entry was ignored: re-timed, new winner
        assert choice == {"chunk": 8192}
        assert len(calls) == len(CANDS)

    def test_corrupt_cache_file_is_ignored(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        with open(path, "w") as fh:
            fh.write("{not json")
        measure, calls = _counting_measure(TIMES)
        assert Autotuner("on", path).best(
            "fused_hist", KEY, CANDS, measure) == {"chunk": 8192}
        assert len(calls) == len(CANDS)

    def test_stale_candidate_set_retunes(self, tmp_path):
        path = str(tmp_path / "tuning.json")
        t = Autotuner("on", path)
        key = TuningCache.key_string("fused_hist", KEY)
        # cached choice no longer in the candidate set (e.g. a
        # tightened VMEM budget dropped it) -> re-tune
        t.cache.put(key, {"choice": {"chunk": 65536}, "timings_ms": {}})
        measure, calls = _counting_measure(TIMES)
        assert t.best("fused_hist", KEY, CANDS, measure) == \
            {"chunk": 8192}
        assert len(calls) == len(CANDS)

    def test_mode_off_returns_default_without_timing(self, tmp_path):
        measure, calls = _counting_measure(TIMES)
        t = Autotuner("off", str(tmp_path / "t.json"))
        assert t.best("fused_hist", KEY, CANDS, measure,
                      default={"chunk": 16384}) == {"chunk": 16384}
        assert calls == []

    def test_failing_candidates_are_skipped(self, tmp_path):
        def measure(cand):
            if cand["chunk"] == 8192:
                raise RuntimeError("Mosaic rejected this tiling")
            return TIMES[json.dumps(cand, sort_keys=True)]

        t = Autotuner("on", str(tmp_path / "t.json"))
        failed0 = _failed()
        # 8192 (the true fastest) fails -> next best wins, not a crash
        assert t.best("fused_hist", KEY, CANDS, measure) == \
            {"chunk": 16384}
        assert _failed() - failed0 == 1      # ... and it is counted

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_every_candidate_failing_is_fatal_on_tpu_only(
            self, tmp_path, monkeypatch, backend):
        """A rejected candidate is counted on every backend; on a TPU
        backend it is warned about with the compiler's text and a key
        whose EVERY candidate failed raises instead of quietly serving
        a default nobody could compile. Off-TPU (injected timers) the
        default is still served. The backend is steered here, through
        the routing seam the tuner itself asks — no option exists for
        it."""
        from lightgbm_tpu.utils import device, log
        monkeypatch.setattr(device, "on_tpu", lambda: backend == "tpu")
        lines = []
        log.set_callback(lines.append)
        try:
            t = Autotuner("on", str(tmp_path / "t.json"))
            failed0 = _failed()

            def measure(cand):
                raise RuntimeError("Mosaic says: scoped vmem exceeded")

            if backend == "tpu":
                with pytest.raises(log.LightGBMError,
                                   match="every candidate"):
                    t.best("fused_hist", KEY, CANDS, measure,
                           default={"chunk": 16384})
                assert sum("scoped vmem exceeded" in ln
                           and "Warning" in ln for ln in lines) \
                    == len(CANDS)
            else:
                assert t.best("fused_hist", KEY, CANDS, measure,
                              default={"chunk": 16384}) \
                    == {"chunk": 16384}
            assert _failed() - failed0 == len(CANDS)
        finally:
            log.set_callback(None)

    def test_tune_hist_chunk_rejection_is_fatal_on_tpu(
            self, tmp_path, monkeypatch):
        """The same rule through the seam the trainer calls
        (tune_hist_chunk with an injected ``_measure``)."""
        from lightgbm_tpu.utils import device, log
        monkeypatch.setattr(device, "on_tpu", lambda: True)
        monkeypatch.setattr(autotune, "device_kind",
                            lambda: "TPU v5 lite")
        autotune.configure("on", str(tmp_path / "t.json"))
        try:
            failed0 = _failed()

            def measure(cand):
                raise RuntimeError("Mosaic rejected this tiling")

            with pytest.raises(log.LightGBMError,
                               match="every candidate"):
                autotune.tune_hist_chunk(
                    fused=True, F=28, B=64, W=64, precision="int8",
                    count_proxy=True, n_rows=1 << 20, _measure=measure)
            assert _failed() - failed0 == 4   # 4096 .. 32768
        finally:
            autotune.configure("on", None)


class TestVmemPredicate:
    def test_hist_candidates_respect_budget(self):
        # a bench-shaped problem admits large chunks...
        small = autotune.hist_chunk_candidates(
            F=28, B=64, W=64, fused=True, int8=True, count_proxy=True)
        assert {"chunk": 16384} in small
        # ...a wide/deep-bin problem no longer sheds the big chunks:
        # where one resident block is over the budget the kernel walks
        # feature tiles (tests/test_wide_features.py), and the candidate
        # carries the tile it was priced with
        # (200 features: since the fused kernel's flush dots a slot at
        # a time, PR 35, its stage, ordered tile and per-slot
        # accumulators are priced too, and 256 features no longer fit
        # one block at any offered chunk)
        wide = autotune.hist_chunk_candidates(
            F=200, B=256, W=24, fused=True)
        assert [c["chunk"] for c in wide] == [32768, 16384, 8192, 4096]
        geom = autotune.hist_geometry(F=200, B=256, W=24)
        for c in wide:
            one_block = autotune.fits_vmem(autotune.hist_vmem_bytes(
                chunk=c["chunk"], geom=geom, W=24, fused=True))
            assert one_block == ("tile" not in c)
            if not one_block:
                assert autotune.fits_vmem(autotune.hist_vmem_bytes(
                    chunk=c["chunk"], W=24, fused=True, tiled=True,
                    geom=autotune.hist_geometry(F=c["tile"], B=256,
                                                W=24)))
        assert "tile" in wide[0] and "tile" not in wide[-1]
        # a shape whose VMEM accumulator alone exceeds the budget used
        # to have no feasible chunk at all; under tiles every chunk is
        assert all("tile" in c for c in autotune.hist_chunk_candidates(
            F=4096, B=256, W=24, fused=True))

    def test_int8_overflow_guard_filters_chunks(self):
        # n just under the int32 histogram guard: padding a 16M-row
        # dataset up to a big chunk multiple must not cross 2^31/127
        n = 2 ** 31 // 127 - 1000
        cands = autotune.hist_chunk_candidates(
            F=28, B=64, W=64, fused=True, int8=True, count_proxy=True,
            n_rows=n)
        for c in cands:
            assert 127 * (n + (-n) % c["chunk"]) < 2 ** 31

    def test_forest_guard_derives_from_shared_shapes(self):
        """_pallas_tc's byte estimate IS autotune.forest_vmem_bytes —
        priced from the same forest_block_shapes the kernel's
        BlockSpecs are built from (no independent hand-maintained byte
        formula)."""
        from lightgbm_tpu.ops.stacked_predict import (StackedModel,
                                                      _PALLAS_VMEM_BUDGET)
        assert _PALLAS_VMEM_BUDGET == autotune.PALLAS_VMEM_BUDGET_BYTES

        sm = StackedModel.__new__(StackedModel)
        sm._S, sm._L, sm._Wtot = 1023, 1024, 8192
        tc = sm._pallas_tc()
        assert tc is not None
        est = autotune.forest_vmem_bytes(
            F=0, Wtot=8192, TC=tc, Sp=1024, Lp=1024, K=1, row_tile=2048)
        assert est <= autotune.PALLAS_VMEM_BUDGET_BYTES
        # the next power of two does NOT fit — tc is the guard's answer
        assert autotune.forest_vmem_bytes(
            F=0, Wtot=8192, TC=tc * 2, Sp=1024, Lp=1024, K=1,
            row_tile=2048) > autotune.PALLAS_VMEM_BUDGET_BYTES
        # block shapes match what forest_predict_pallas hands BlockSpec
        blk = autotune.forest_block_shapes(
            F=28, Wtot=8192, TC=tc, Sp=1024, Lp=1024, K=1,
            row_tile=2048)
        assert blk["codes"] == (28, 2048)
        assert blk["W"] == (1, 8192, tc * 1024)
        assert blk["P"] == (1, tc, 1024, 1024)
        assert blk["acc"] == (2048, 1)

    def test_hist_kernel_uses_shared_geometry(self):
        """The wave kernels' accumulator shape comes from
        autotune.hist_geometry — the same numbers hist_vmem_bytes
        prices."""
        import jax.numpy as jnp
        from lightgbm_tpu.ops.hist_wave import wave_histogram_pallas
        g = autotune.hist_geometry(F=5, B=64, W=8)
        assert g["Bp"] == 64 and g["group_sz"] == 2
        assert g["groups"] == 3 and g["gb_pad"] == 128
        rng = np.random.default_rng(0)
        bins = jnp.asarray(rng.integers(0, 64, (5, 512)).astype(np.uint8))
        out = wave_histogram_pallas(
            bins, jnp.ones(512), jnp.ones(512),
            jnp.zeros(512, jnp.int32),
            jnp.zeros(1, jnp.int32), num_bins=64, chunk=256,
            interpret=True)
        assert out.shape == (1, 5, 64, 3)


class TestDefaultsOffTpu:
    def test_tune_hist_chunk_returns_tier_default_on_cpu(self, tmp_path,
                                                         monkeypatch):
        # conftest pins the cpu backend: no timing may happen, and the
        # measured per-tier defaults come back untouched
        autotune.configure("on", str(tmp_path / "t.json"))
        try:
            assert autotune.tune_hist_chunk(
                fused=True, F=28, B=64, W=24) == \
                autotune.DEFAULT_HIST_CHUNK
            assert autotune.tune_hist_chunk(
                fused=True, F=28, B=64, W=64, precision="int8",
                count_proxy=True) == autotune.DEFAULT_HIST_CHUNK_INT8
            assert not (tmp_path / "t.json").exists()
        finally:
            autotune.configure("on", None)

    @staticmethod
    def _on_platform(monkeypatch, name):
        """Steer the one question every route decision asks
        (device.on_tpu reads get_devices()[0].platform) to a platform
        this host does not have."""
        from types import SimpleNamespace

        from lightgbm_tpu.utils import device
        monkeypatch.setattr(
            device, "get_devices",
            lambda: [SimpleNamespace(platform=name, device_kind=name)])

    def test_tune_hist_route_capability_ladder(self, monkeypatch):
        """Two questions: Mosaic or not, fused-eligible or not. Any
        platform but the TPU — a jax GPU included — takes the plain XLA
        routes."""
        assert autotune.HIST_ROUTES == ("pallas-tpu", "fused-xla",
                                        "two-pass")
        self._on_platform(monkeypatch, "tpu")
        assert autotune.tune_hist_route() == "pallas-tpu"
        assert autotune.tune_hist_route(
            fused_eligible=False) == "pallas-tpu"
        for other in ("cpu", "gpu"):
            self._on_platform(monkeypatch, other)
            assert autotune.tune_hist_route() == "fused-xla"
            assert autotune.tune_hist_route(
                fused_eligible=False) == "two-pass"
        # the config override beats the device, both directions
        assert autotune.tune_hist_route(use_pallas=True) == "pallas-tpu"
        self._on_platform(monkeypatch, "tpu")
        assert autotune.tune_hist_route(use_pallas=False) == "fused-xla"
        assert autotune.tune_hist_route(
            use_pallas=False, fused_eligible=False) == "two-pass"

    @pytest.mark.parametrize("platform", ["cpu", "gpu"])
    def test_cpu_backend_without_timer_keeps_default(
            self, tmp_path, monkeypatch, platform):
        """Off the TPU — on whatever platform jax has — the wave arm
        times nothing and writes nothing without an injected timer."""
        self._on_platform(monkeypatch, platform)
        autotune.configure("on", str(tmp_path / "t.json"))
        try:
            assert autotune.tune_hist_chunk(
                fused=False, F=8, B=64, W=8) == \
                autotune.DEFAULT_HIST_CHUNK
            assert not (tmp_path / "t.json").exists()
        finally:
            autotune.configure("on", None)

    def test_config_knob_validation(self):
        from lightgbm_tpu.config import Config
        cfg = Config().set({"tpu_autotune": "bogus"})
        assert cfg.tpu_autotune == "on"
        cfg = Config().set({"tpu_autotune": "exhaustive",
                            "tpu_tuning_cache": "/tmp/x.json"})
        assert cfg.tpu_autotune == "exhaustive"
        assert cfg.tpu_tuning_cache == "/tmp/x.json"

    def test_overlap_knob_validation(self):
        """(PR16) the three overlap knobs are tri-state -1/0/1 and
        clamp anything else back to auto."""
        from lightgbm_tpu.config import Config
        cfg = Config()
        assert (cfg.tpu_psum_wire, cfg.tpu_async_psum,
                cfg.tpu_ckpt_async) == (-1, -1, -1)
        cfg = Config().set({"tpu_psum_wire": 0, "tpu_async_psum": 1,
                            "tpu_ckpt_async": 0})
        assert (cfg.tpu_psum_wire, cfg.tpu_async_psum,
                cfg.tpu_ckpt_async) == (0, 1, 0)
        cfg = Config().set({"tpu_psum_wire": 7, "tpu_async_psum": -3,
                            "tpu_ckpt_async": "2"})
        assert (cfg.tpu_psum_wire, cfg.tpu_async_psum,
                cfg.tpu_ckpt_async) == (-1, -1, -1)


class TestPsumWire:
    """(PR16) the packed-wire and async-psum tuner arms: pure bound
    checks / analytic defaults off-TPU, so fully deterministic here."""

    def test_wire_bound_selects_narrowest_safe(self):
        # 127*N < 2^7 only for N=1; 127*N < 2^15 up to N=258
        assert autotune.tune_psum_wire(n_rows_global=1) == "int8"
        assert autotune.tune_psum_wire(n_rows_global=200) == "int16"
        assert autotune.tune_psum_wire(n_rows_global=258) == "int16"
        assert autotune.tune_psum_wire(n_rows_global=259) == "int32"
        assert autotune.tune_psum_wire(n_rows_global=4096) == "int32"

    def test_wire_requested_zero_is_legacy(self):
        assert autotune.tune_psum_wire(
            n_rows_global=1, requested=0) == "int32"

    def test_wire_force_narrow_refuses_on_wrap_bound(self):
        """tpu_psum_wire=1 cannot override the overflow proof: the
        refusal falls back to int32 and says why."""
        from lightgbm_tpu.utils import log as tpulog
        lines = []
        tpulog.add_sink(lines.append)
        try:
            got = autotune.tune_psum_wire(n_rows_global=4096,
                                          requested=1)
        finally:
            tpulog.remove_sink(lines.append)
        assert got == "int32"
        assert any("wrap bound" in ln for ln in lines)

    def _mesh(self, n):
        from lightgbm_tpu.parallel.learners import make_mesh
        from lightgbm_tpu.utils.device import get_devices
        return make_mesh(min(n, len(get_devices())))

    def test_async_arm_decisions(self):
        mesh2 = self._mesh(2)
        kw = dict(mesh=mesh2, W=8, F=4, B=64, channels=3)
        # requested sync / async win outright
        assert autotune.tune_hist_psum_async(requested=0, **kw) == 1
        assert autotune.tune_hist_psum_async(requested=1, **kw) == 2
        # auto: analytic default (async) off-TPU on a real mesh
        assert autotune.tune_hist_psum_async(requested=-1, **kw) == 2
        # single feature column: nothing to split
        assert autotune.tune_hist_psum_async(
            mesh=mesh2, W=8, F=1, B=64, channels=3, requested=1) == 1

    def test_async_arm_single_device_mesh_stays_sync(self):
        mesh1 = self._mesh(1)
        assert autotune.tune_hist_psum_async(
            mesh=mesh1, W=8, F=4, B=64, channels=3, requested=-1) == 1


class TestTunedParity:
    """A tuned tile choice may never change results beyond documented
    tolerance: the histogram kernels accumulate per-chunk partial sums
    (f32 reassociation across chunk sizes -> tolerance; int8 tier is
    exact int32), and the forest kernel's per-row scores are
    independent of the row blocking (bit-for-bit)."""

    def _hist_args(self, n=1536, F=6, B=64, W=8, seed=3):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        bins = jnp.asarray(rng.integers(0, B, (F, n)).astype(np.uint8))
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        h = jnp.asarray(np.abs(rng.normal(size=n)).astype(np.float32))
        leaf = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
        wl = jnp.asarray(np.array([0, 1] + [-1] * (W - 2), np.int32))
        return bins, g, h, leaf, wl

    def test_wave_hist_chunk_parity(self):
        from lightgbm_tpu.ops.hist_wave import (wave_histogram_pallas,
                                                wave_histogram_xla)
        args = self._hist_args()
        ref = np.asarray(wave_histogram_xla(*args, num_bins=64))
        for chunk in (256, 512, 1536):
            out = np.asarray(wave_histogram_pallas(
                *args, num_bins=64, chunk=chunk, interpret=True))
            np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-5)

    def test_wave_hist_chunk_parity_int8_exact(self):
        import jax.numpy as jnp
        from lightgbm_tpu.ops.hist_wave import (wave_histogram_pallas,
                                                wave_histogram_xla)
        bins, _, _, leaf, wl = self._hist_args()
        rng = np.random.default_rng(9)
        n = bins.shape[1]
        gq = jnp.asarray(rng.integers(-127, 128, n).astype(np.float32))
        hq = jnp.asarray(rng.integers(0, 128, n).astype(np.float32))
        ref = np.asarray(wave_histogram_xla(
            bins, gq, hq, leaf, wl, num_bins=64))
        outs = [np.asarray(wave_histogram_pallas(
            bins, gq, hq, leaf, wl, num_bins=64, chunk=c,
            interpret=True, precision="int8", gh_scale=(1.0, 1.0)))
            for c in (256, 768)]
        # int32 accumulation: bit-for-bit across tile choices AND
        # exactly equal to the oracle's integer-float sums
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], ref)

    def test_fused_chunk_parity(self):
        import jax.numpy as jnp
        from lightgbm_tpu.ops.hist_wave import (
            fused_partition_histogram_pallas)
        bins, g, h, leaf, _ = self._hist_args(W=4)
        n = bins.shape[1]
        mask = jnp.ones(n, jnp.float32)
        W = 4
        tbl = np.zeros((18, W), np.int32)
        tbl[0] = [0, 1, -1, -1]          # parents
        tbl[1] = [2, 3, -1, -1]          # new ids
        tbl[2] = [0, 1, 0, 0]            # features
        tbl[3] = [31, 40, 0, 0]          # bins
        tbl[7] = 64                      # num_bin
        tbl[8] = [2, 3, -1, -1]          # smaller child
        tbl_d = jnp.asarray(tbl)
        outs = []
        for chunk in (256, 768):
            leaf_o, hist, _ = fused_partition_histogram_pallas(
                bins, g, h, mask, leaf, tbl_d, num_bins=64,
                chunk=chunk, interpret=True)
            outs.append((np.asarray(leaf_o), np.asarray(hist)))
        # the partition is integer logic: identical at any tile
        np.testing.assert_array_equal(outs[0][0], outs[1][0])
        np.testing.assert_allclose(outs[0][1], outs[1][1],
                                   atol=1e-4, rtol=1e-5)

    def test_forest_row_tile_parity_bit_for_bit(self):
        from lightgbm_tpu.ops.stacked_predict import (
            forest_predict_pallas)
        import jax.numpy as jnp
        X, y = make_binary(n=1000, f=6, seed=21)
        g = fit_gbdt(X, y, dict(TEST_PARAMS, objective="binary"),
                     num_round=10)
        g._ensure_host_trees()
        from lightgbm_tpu.ops.stacked_predict import StackedModel
        sm = StackedModel(g.models, g.max_feature_idx + 1, 1)
        assert sm.ok
        tc = sm._pallas_tc()
        dev = sm._device_arrays_pallas(0, sm.num_trees, tc)
        Xt = np.random.default_rng(4).normal(size=(700, 6))
        codes = jnp.asarray(np.ascontiguousarray(sm._bin_rows(Xt).T))
        offs = tuple(int(o) for o in sm._offsets)
        outs = [np.asarray(forest_predict_pallas(
            codes, *dev, offsets=offs, row_tile=rt, interpret=True))
            for rt in (256, 512, 1024)]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])


def test_measure_median_with_sync():
    """timing.measure: median-of-k wall seconds, device-synced."""
    import jax.numpy as jnp

    from lightgbm_tpu.utils import timing

    def fn():
        return jnp.arange(1024.0).sum()

    t = timing.measure(fn, repeats=3, warmup=1)
    assert 0.0 < t < 10.0


class _FakeJaxConfig:
    """Stands in for ``jax.config`` while ensure_compile_cache runs:
    records what the one setter would write without touching the real
    process-wide config (no test sets the cache directory for real)."""

    def __init__(self, real, cache_dir=None):
        self._real = real
        self.jax_compilation_cache_dir = cache_dir
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value
        if key == "jax_compilation_cache_dir":
            self.jax_compilation_cache_dir = value

    def __getattr__(self, name):
        return getattr(self._real, name)


@pytest.fixture
def cache_rule(monkeypatch):
    """(autotune module, fake-config factory) with ensure_compile_cache's
    once-guard reset (earlier tests' GBDT.init tripped it)."""
    import jax

    from lightgbm_tpu.ops import autotune as at
    monkeypatch.setattr(at, "_compile_cache_done", False)

    def install(cache_dir=None):
        fake = _FakeJaxConfig(jax.config, cache_dir)
        monkeypatch.setattr(jax, "config", fake)
        return fake

    return at, install


def test_compile_cache_cpu_backend_leaves_config_alone(cache_rule):
    """The persistent compile cache auto-wires only for accelerator
    backends; on the CPU test backend the jax config comes through
    untouched (mode 0 likewise), and the decision is not latched — a
    later booster may still opt in with tpu_compile_cache=1."""
    at, install = cache_rule
    cfg = install()
    at.ensure_compile_cache()
    at.ensure_compile_cache(mode=0)
    assert cfg.updates == {}
    assert at._compile_cache_done is False
    at.ensure_compile_cache(mode=1)
    assert cfg.jax_compilation_cache_dir == at.default_cache_dir()


def test_compile_cache_operator_placement_wins(cache_rule, monkeypatch,
                                               tmp_path):
    """A cache directory the operator placed — JAX_COMPILATION_CACHE_DIR,
    which jax reads into this very config value — is used as is and
    NOTHING else is set, even on a TPU backend; the tuning cache then
    sits inside it."""
    from lightgbm_tpu.utils import device
    at, install = cache_rule
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    placed = str(tmp_path / "operator_cache")
    cfg = install(placed)
    at.ensure_compile_cache()
    assert cfg.updates == {}
    assert cfg.jax_compilation_cache_dir == placed
    assert at.default_tuning_cache_path().startswith(placed + "/")


def test_compile_cache_unset_goes_to_the_fixed_checkout_path(
        cache_rule, monkeypatch):
    """Unset, on a TPU backend: ONE fixed directory inside the checkout
    (git-ignored) — never a temp name, pid or time, because the path is
    part of the cache key — with the tuning cache inside it."""
    import os

    from lightgbm_tpu.utils import device
    at, install = cache_rule
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    cfg = install()
    at.ensure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".lgbm_tpu_cache")
    assert cfg.jax_compilation_cache_dir == want
    assert at.default_cache_dir() == want
    assert os.path.dirname(at.default_tuning_cache_path()) == want
    with open(os.path.join(repo, ".gitignore")) as fh:
        assert ".lgbm_tpu_cache/" in fh.read().split()
