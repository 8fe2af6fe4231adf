"""The six readers of the data-parallel cell on a made-up trace with two
device planes and collective events, the wire's required bytes by hand, and
``datagen_f32`` against ``datagen``.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_dp_readers.py -q
"""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import tracereduce
import work_dp

KERNELS = json.loads((HERE.parent / "traffic" / "train_dp.json")
                     .read_text())["kernels"]
MS = 1e6                                            # ns


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", HERE.parent / "readers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def registry(monkeypatch):
    """A registry of this test's own in the process-wide one's place."""
    from lightgbm_tpu.obs import registry as obs
    fresh = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "default_registry", lambda: fresh)
    return fresh


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def _trace(collectives=True):
    """Two chips, a 100 ms window, two iterations: a kernel each, then the
    sum. Chip 0 reaches each sum 4 ms before chip 1 and waits in it."""
    def plane(i, kernel_ms, sum_ms):
        ev, at = [], 0.0
        for it in range(2):
            at = 50.0 * it
            ev.append(_ev("%fused_partition_histogram_pallas.3 = f32[] "
                          "custom-call()", at, kernel_ms))
            if collectives:
                ev.append(_ev(f"%all-reduce.{it} = f32[24,72,256,3] "
                              "all-reduce()", at + kernel_ms, sum_ms))
                ev.append(_ev("%all-gather.7 = s32[4] all-gather()",
                              at + kernel_ms + sum_ms, 1.0))
        return NS(name=f"/device:TPU:{i}",
                  lines=[NS(name="XLA Ops", events=ev),
                         NS(name="Steps", events=[_ev("ignored", 0, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench/window", 0.0, 100.0)])])
    return NS(planes=[plane(0, 36.0, 6.0), plane(1, 40.0, 2.0), host])


def _facts(collectives=True):
    tree = {"num_leaves": 255}
    return {"trace": tracereduce.reduce(_trace(collectives), KERNELS),
            "done": 2, "first_window_tree": 3, "trees": [tree] * 5,
            "features": 67, "bins": 255, "chips": 4, "rows": 1 << 20,
            "device_kind": "TPU v5 lite", "bin_s": 52.5}


def test_collective_readers_read_the_mean_chip():
    f = _facts()
    assert f["trace"]["planes"] == 2
    # (6 + 1) and (2 + 1) ms a sum, two sums a chip: the mean chip 10 ms
    assert f["trace"]["kernel_s"]["collective"] == pytest.approx(0.010)
    assert _reader("collective.ms_per_iter")(f) == pytest.approx(5.0)
    busy = f["trace"]["busy_s"]
    assert busy == pytest.approx(0.086)
    assert _reader("collective.share_pct")(f) == pytest.approx(100 * 0.010 / busy)
    need = 2 * 255 * 67 * 255 * 3 * 4 * 0.75           # two trees, by hand
    assert work_dp.window_wire_bytes(f) == pytest.approx(need)
    roof = _reader("collective.wire_roofline")(f)
    assert roof == pytest.approx(100 * (need / 200e9) / 0.010)
    assert 0 < roof < 100


def test_collective_readers_give_none_where_the_trace_holds_none():
    f = _facts(collectives=False)
    assert "collective" not in f["trace"]["kernel_s"]
    for name in ("collective.ms_per_iter", "collective.share_pct",
                 "collective.wire_roofline"):
        assert _reader(name)(f) is None, name
    one_chip = dict(_facts(), chips=1)
    assert _reader("collective.wire_roofline")(one_chip) is None


@pytest.mark.parametrize("leaves, chips, want", [
    (255, 4, 255 * 67 * 255 * 12 * 3 / 4),
    (255, 2, 255 * 67 * 255 * 12 / 2),
    (1, 4, 67 * 255 * 12 * 3 / 4),        # a stump still sums its root
], ids=["255_leaves_4_chips", "2_chips", "stump"])
def test_a_trees_wire_bytes_by_hand(leaves, chips, want):
    assert work_dp.tree_wire_bytes(leaves, 67, 255, chips) == want
    assert work_dp.link_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(SystemExit):
        work_dp.link_bytes_per_s("TPU v9")


def test_counter_readers(registry):
    """None on a program without the counters (this PR's parent), the
    ratios by hand with them."""
    f = _facts()
    for name in ("collective.mib_per_iter", "shard.dotted_rows_skew",
                 "ingest.us_per_row"):
        assert _reader(name)(f) is None, name
    registry.counter("hist/trees_counted").add(10)
    registry.counter("hist/rows_dotted").add(4000)
    assert _reader("collective.mib_per_iter")(f) is None
    assert _reader("shard.dotted_rows_skew")(f) is None
    registry.counter("comm/psum_bytes").add(10 * 3 * 2 ** 20)
    registry.counter("hist/rows_dotted_max_shard").add(1100)
    registry.counter("ingest/f32_rows").add(21_000_000)
    assert _reader("collective.mib_per_iter")(f) == pytest.approx(3.0)
    assert _reader("shard.dotted_rows_skew")(f) == pytest.approx(1.1)
    assert _reader("ingest.us_per_row")(f) == pytest.approx(2.5)
    assert _reader("shard.dotted_rows_skew")(dict(f, chips=1)) is None


@pytest.mark.parametrize("seed, threads", [(4000000007, 3)])
def test_datagen_f32_makes_datagens_levels_and_labels(seed, threads):
    import datagen
    import datagen_f32
    spec = {"rows": (1 << 20) + 4096, "features": 8, "levels": 255,
            "uniform_columns": 7, "skew_scale": 80.0, "label_noise": 0.5}
    X64, L64, y64 = datagen.make(spec, seed, 2)
    X32, L32, y32 = datagen_f32.make(dict(spec, dtype="float32"), seed, threads)
    assert X32.dtype == np.float32 and X32.flags.c_contiguous
    np.testing.assert_array_equal(L32, L64)
    np.testing.assert_array_equal(y32, y64)
    np.testing.assert_array_equal(X32, X64)          # every level is exact
    with pytest.raises(ValueError):
        datagen_f32.make(dict(spec, dtype="float64"), seed)
