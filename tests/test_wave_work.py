"""The wave passes' work counters, from the tree's record to the benchmark's
reader: ``TreeRecord.wave_work`` ([3]: rows scanned, rows put through the
dot, both in units of ``hist_wave.COMPACT_TILE_UNIT``, and the block-dots
those rows met; the kernel and the grower fill it, ``tests/test_wave_ops.py``
and ``tests/test_wave_split.py``) rides the stop check's ONE download and
feeds ``hist/rows_scanned``, ``hist/rows_dotted``, ``hist/blocks_dotted`` and
``hist/trees_counted``; ``benchmark/readers/kernel.dotted_rows_ratio.py`` lays
the rows against the rows the grown trees require, and
``kernel.wave_macs_per_row.py`` the block-dots against the rows."""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import fit_gbdt, make_binary
from lightgbm_tpu.obs import registry as obs
from lightgbm_tpu.ops.hist_wave import COMPACT_TILE_UNIT

ROOT = Path(__file__).resolve().parent.parent
ROWS = 1200


def _counters():
    c = obs.default_registry().counter_items()
    return {k: c.get(f"hist/{k}", 0)
            for k in ("rows_scanned", "rows_dotted", "blocks_dotted",
                      "trees_counted")}


@pytest.fixture(scope="module")
def trained():
    """Four iterations with the stop check held off, so the tests make it."""
    X, y = make_binary(n=ROWS, f=6, seed=3)
    return fit_gbdt(X, y, {"objective": "binary", "num_leaves": 7,
                           "tpu_stop_check_interval": 100}, num_round=4)


def _with_work(g, work):
    g._clean_groups = 0
    g.records = [r._replace(wave_work=jnp.asarray(w, jnp.int32))
                 for r, w in zip(g.records, work)]


def test_stop_check_feeds_the_counters_once_a_tree(trained):
    work = [(30, 9, 21), (30, 11, 11), (40, 7, 16), (20, 20, 48)]
    for r in trained.records:
        assert r.wave_work.shape == (3,)
    _with_work(trained, work)
    before = _counters()
    assert trained._check_stop() is False
    assert trained._check_stop() is False      # nothing new: nothing added
    got = {k: v - before[k] for k, v in _counters().items()}
    assert got == {"rows_scanned": 120 * COMPACT_TILE_UNIT,
                   "rows_dotted": 47 * COMPACT_TILE_UNIT,
                   "blocks_dotted": 96,         # block-dots, not rows
                   "trees_counted": 4}


def test_units_past_int32_rows_add_up_on_the_host(trained):
    """A tree's rows can pass 2^31 (15 waves over 150 M rows); its units of
    128 rows cannot, and the host adds them as Python ints."""
    big = 2**31 // COMPACT_TILE_UNIT * 3 // 2
    _with_work(trained, [(big, big, big)] * 4)
    before = _counters()
    trained._check_stop()
    assert (_counters()["rows_scanned"] - before["rows_scanned"]
            == 4 * big * COMPACT_TILE_UNIT > 2**32)


def test_stop_check_download_was_compiled_in_setup():
    """The booster compiles the stop check's stacked download when it is
    built, for the tail an interval leaves; the first check, an interval
    into training, then compiles nothing (the benchmark's window counts
    compiles)."""
    X, y = make_binary(n=600, f=5, seed=4)
    g = fit_gbdt(X, y, {"objective": "binary", "num_leaves": 5,
                        "tpu_stop_check_interval": 3}, num_round=2)
    seen = []

    def on(event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        assert g.train_one_iter() is False     # the third: the check reads
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert g._clean_groups == 3 and not seen, seen


def _reader():
    sys.path.insert(0, str(ROOT / "benchmark"))
    try:
        spec = importlib.util.spec_from_file_location(
            "dotted_rows_ratio",
            ROOT / "benchmark" / "readers" / "kernel.dotted_rows_ratio.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        import modeltext
        import work
    finally:
        sys.path.remove(str(ROOT / "benchmark"))
    return mod, modeltext, work


def test_reader_lays_dotted_rows_against_required_rows(trained, monkeypatch):
    mod, modeltext, work = _reader()
    trees = modeltext.parse_trees(trained.model_to_string())
    facts = {"trees": trees, "rows": ROWS, "features": 6, "bins": 255}
    need = [work.row_reads(t, ROWS) for t in trees]
    fresh = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "default_registry", lambda: fresh)
    assert mod.read(facts) is None             # the parent: no such counter
    # three of the four trees verified so far; their wave passes dotted
    # exactly their smaller children's rows, and one part tile each
    fresh.counter("hist/trees_counted").add(3)
    fresh.counter("hist/rows_dotted").add(sum(need[:3]) - 3 * ROWS + 3 * 64)
    assert mod.read(facts) == pytest.approx(1.0 + 192 / sum(need[:3]))
    # a program that dots every row in every pass reads passes + 1 over it
    fresh.counter("hist/rows_dotted").add(-fresh.counter_items()[
        "hist/rows_dotted"] + 3 * 2 * ROWS)
    assert mod.read(facts) == pytest.approx(9 * ROWS / sum(need[:3]))
    # more trees counted than the model holds: nothing to lay them against
    fresh.counter("hist/trees_counted").add(5)
    assert mod.read(facts) is None
