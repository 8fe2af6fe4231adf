"""The required-work function, by hand on a three-leaf tree, and the model-text
reader that feeds it."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import modeltext
import work

# root (1000 rows) -> leaf 0 (300) | node 1 (700); node 1 -> leaf 1 (450) | leaf 2 (250)
TEXT = """tree
version=v2
num_class=1

Tree=0
num_leaves=3
num_cat=0
split_feature=4 1
split_gain=10.5 3.25
threshold=11.5 30.5
decision_type=2 2
left_child=-1 -2
right_child=1 -3
leaf_value=0.1 -0.2 0.3
leaf_count=300 450 250
internal_value=0 0.05
internal_count=1000 700
shrinkage=0.1


end of trees
"""


def test_three_leaf_tree_by_hand():
    (tree,) = modeltext.parse_trees(TEXT)
    assert tree["num_leaves"] == 3
    # split 0: children 300 | 700 -> 300; split 1: 450 | 250 -> 250
    assert modeltext.smaller_child_rows(tree) == 550
    assert work.row_reads(tree, 1000) == 1550
    need = work.required([tree], rows=1000, features=67, bins=63)
    # 63 bins -> 6 bits a feature: 67 * 6 / 8 = 50.25 B of bins + 8 B of g, h
    assert need["row_reads"] == 1550
    assert need["bytes"] == pytest.approx(1550 * 58.25)
    assert need["adds"] == pytest.approx(1550 * 2 * 67)
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    secs, bound = work.least_seconds(need, peaks)
    assert bound == "bytes"
    assert secs == pytest.approx(1550 * 58.25 / 819e9)


def test_a_stump_needs_the_root_pass_only():
    stump = {"num_leaves": 1}
    assert work.row_reads(stump, 1000) == 1000


def test_unknown_device_kind_is_an_error():
    import run
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_window_least_seconds_takes_the_trees_grown_in_the_window():
    (tree,) = modeltext.parse_trees(TEXT)
    facts = {"first_window_tree": 1, "done": 1, "trees": [tree, tree, tree],
             "rows": 1000, "features": 67, "bins": 63,
             "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}}
    assert work.window_least_seconds(facts) == pytest.approx(1550 * 58.25 / 819e9)
    assert work.window_least_seconds({**facts, "done": 0}) is None
