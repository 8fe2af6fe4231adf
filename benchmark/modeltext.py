"""Reads trees out of a LightGBM v2 model text (what ``model_to_string``
returns): the program's answer, as the comparison and the work function see it.
Only numerical splits with no missing handling are understood; anything else
is an error, not a guess."""
from __future__ import annotations

import numpy as np

_INT = ("split_feature", "decision_type", "left_child", "right_child",
        "leaf_count", "internal_count")
_FLT = ("split_gain", "threshold", "leaf_value", "internal_value")


def parse_trees(text: str) -> list[dict]:
    trees = []
    for part in text.split("\nTree=")[1:]:
        body = part.split("\n\n")[0]
        kv = dict(line.split("=", 1) for line in body.splitlines()[1:]
                  if "=" in line)
        t = {"num_leaves": int(kv["num_leaves"]),
             "shrinkage": float(kv.get("shrinkage", 1.0))}
        if int(kv.get("num_cat", 0)):
            raise ValueError("categorical splits are not understood here")
        for k in _INT:
            t[k] = np.array(kv.get(k, "").split(), np.int64)
        for k in _FLT:
            t[k] = np.array(kv.get(k, "").split(), np.float64)
        if t["num_leaves"] > 1 and np.any((t["decision_type"] & 1) != 0):
            raise ValueError("categorical decision in a numerical model")
        trees.append(t)
    return trees


def smaller_child_rows(tree: dict) -> int:
    """Rows in the smaller child of every split, summed: what LightGBM's
    histogram subtraction has to read after the root."""
    if tree["num_leaves"] < 2:
        return 0
    def count(c):
        return tree["leaf_count"][~c] if c < 0 else tree["internal_count"][c]
    return int(sum(min(count(l), count(r)) for l, r in
                   zip(tree["left_child"], tree["right_child"])))
