"""The five readers of device time by the program's own scopes
(``readers/step.{gradients,hist_xla,split_find,bookkeep}_ms_per_iter.py``,
``readers/step.unscoped_pct.py``, through ``scopesplit.py``): on a made-up
window and a made-up op-scope table in the program's place.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import scopesplit

READERS = ("step.gradients_ms_per_iter", "step.hist_xla_ms_per_iter",
           "step.split_find_ms_per_iter", "step.bookkeep_ms_per_iter",
           "step.unscoped_pct")

# {op name: (scope the program's table gives it, self seconds)}; an op the
# table does not know has the scope "unknown"
WINDOW = {
    "fusion.53": ("lgbm/gradients/rank_pairs", 0.010),
    "select_multiply_fusion": ("lgbm/gradients", 0.002),
    "wave_histogram_pallas.1": ("lgbm/root_hist", 0.080),
    "fused_partition_histogram_pallas.8": ("lgbm/wave/hist", 1.000),
    "maximum_maximum_fusion.1": ("lgbm/root_hist", 0.004),
    "fusion.347": ("lgbm/wave/hist", 0.136),
    "copy.119": ("lgbm/wave/hist", 0.056),
    "all-reduce.21": ("lgbm/wave/hist_psum", 0.003),
    "all-reduce.3": ("lgbm/root_hist/psum", 0.001),
    "fusion.351": ("lgbm/wave/split_find", 0.036),
    "all-gather.2": ("lgbm/wave/split_sync", 0.002),
    "copy-done.86": ("lgbm/wave/bookkeep", 0.020),
    "leaf_gather_pallas.1": ("lgbm/score_update", 0.008),
    "fusion.9": ("lgbm/leaf_values", 0.001),
    "copy.7": (None, 0.004),
    "add.1": ("unknown", 0.001),
}
DONE = 2


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "r", HERE.parent / "readers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _facts(window=WINDOW, done=DONE):
    ops = {n: s for n, (_, s) in window.items()}
    return {"done": done, "trace": {
        "op_seconds": ops, "busy_s": sum(ops.values()),
        "kernel_s": {"hist": 1.080}}}


@pytest.fixture
def program(monkeypatch):
    """The program's ``obs.op_scopes`` answering with WINDOW's table."""
    from lightgbm_tpu import obs
    table = {n: s for n, (s, _) in WINDOW.items() if s != "unknown"}
    monkeypatch.setattr(obs, "op_scopes", lambda: dict(table))
    return obs


def test_the_five_split_the_window_round_the_kernels(program, capsys):
    facts = _facts()
    got = {r: _reader(r)(facts) for r in READERS}
    ms = 1e3 / DONE
    assert got["step.gradients_ms_per_iter"] == pytest.approx(0.012 * ms)
    # the kernels' own time and the collectives' scopes are not hist_xla
    assert got["step.hist_xla_ms_per_iter"] == pytest.approx(
        (0.004 + 0.136 + 0.056) * ms)
    assert got["step.split_find_ms_per_iter"] == pytest.approx(0.038 * ms)
    assert got["step.bookkeep_ms_per_iter"] == pytest.approx(0.020 * ms)
    busy = facts["trace"]["busy_s"]
    assert got["step.unscoped_pct"] == pytest.approx(100 * 0.005 / busy)
    # the split is made and printed once, and its parts add up to busy
    err = capsys.readouterr().err
    assert err.count("device time by program scope") == 1
    assert "copy.7 2" in err and "add.1 (unknown) 0.5" in err
    sp = facts["scope_split"]
    assert sp["kernels"] == pytest.approx(1.080)
    assert sp["kernels"] + sum(sp["by_scope"].values()) == pytest.approx(busy)


def test_path_components_decide_the_part():
    """``hist_psum`` is not ``hist``; ``rank_pairs`` is ``gradients``;
    the pool's loop and the score update are none of the four."""
    part = scopesplit.part_of
    assert part("lgbm/wave/hist_psum") is None
    assert part("lgbm/root_hist/psum") is None
    assert part("lgbm/wave/hist") == part("lgbm/root_hist") == "hist_xla"
    assert part("lgbm/gradients/rank_pairs") == "gradients"
    assert part("lgbm/wave/split_sync") == "split_find"
    assert part("lgbm/wave/loop") is part("lgbm/score_update") is None
    assert part(None) is None


def test_a_program_without_the_table_reads_none(monkeypatch):
    """The parent of the PR that brought the readers: no ``op_scopes``."""
    from lightgbm_tpu import obs
    monkeypatch.delattr(obs, "op_scopes")
    facts = _facts()
    assert [_reader(r)(facts) for r in READERS] == [None] * len(READERS)


@pytest.mark.parametrize("facts", [
    _facts(window={}), _facts(done=0),
    {"done": 2, "trace": {"op_seconds": {"fusion.1": 1.0}, "busy_s": 0.0,
                          "kernel_s": {}}},
])
def test_nothing_to_read_reads_none(program, facts):
    assert [_reader(r)(facts) for r in READERS] == [None] * len(READERS)
