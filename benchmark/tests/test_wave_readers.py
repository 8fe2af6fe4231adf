"""The reader of what a wave pass's dot spends on a row
(``readers/kernel.wave_macs_per_row.py``), against the program's registry.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


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


def test_wave_macs_per_row_is_none_without_the_counter_or_the_gauge(registry):
    """This PR's parent feeds ``hist/rows_dotted`` alone and sets no gauge;
    a booster that has not reached a stop check has counted nothing; a
    grower off the kernels' route sets the gauge to 0: None each time, and
    nothing raised."""
    read = _reader("kernel.wave_macs_per_row")
    assert read({}) is None
    registry.counter("hist/rows_dotted").add(4096)
    assert read({}) is None                       # the parent: no third count
    registry.counter("hist/blocks_dotted").add(78)
    assert read({}) is None                       # no gauge
    registry.gauge("hist/wave_macs").set(0.0)
    assert read({}) is None                       # not the grower's to price
    registry.gauge("hist/wave_macs").set(5120.0)
    assert read({}) == pytest.approx(78 * 128 * 5120 / 4096)


@pytest.mark.parametrize("blocks, pairs, macs, want", [
    (16, 39, 5120.0, 12480.0),      # a full stage of 24 live slots
    (16, 16, 5120.0, 5120.0),       # one live slot: the root's dot
    (16, 16, 32768.0, 32768.0),     # the one-hot dot: one block-dot a unit
], ids=["24_slots", "one_slot", "one_hot_dot"])
def test_wave_macs_per_row_reads_pairs_over_blocks(registry, blocks, pairs,
                                                   macs, want):
    read = _reader("kernel.wave_macs_per_row")
    registry.counter("hist/rows_dotted").add(blocks * 128)
    registry.counter("hist/blocks_dotted").add(pairs)
    registry.gauge("hist/wave_macs").set(macs)
    assert read({}) == pytest.approx(want)
