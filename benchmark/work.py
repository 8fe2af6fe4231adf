"""The work one boosting iteration REQUIRES, from the cell's shapes and the
trees that were grown, never from how the program grew them.

Per tree the root reads every row once and each split reads the rows of its
smaller child (LightGBM's histogram subtraction; counts come from the model
text). A row read costs its real features' bins at ceil(log2 B) bits plus 8
bytes of gradient and hessian, and 2 adds per feature. Full-data passes the
program makes beyond that do not count, as recomputed operations do not in a
model's MFU."""
from __future__ import annotations

import math

import modeltext


def row_reads(tree: dict, rows: int) -> int:
    return rows + modeltext.smaller_child_rows(tree)


def required(trees: list[dict], rows: int, features: int, bins: int) -> dict:
    reads = sum(row_reads(t, rows) for t in trees)
    bits = max(1, math.ceil(math.log2(bins)))
    return {"row_reads": reads,
            "bytes": reads * (features * bits / 8.0 + 8.0),
            "adds": reads * 2.0 * features}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_adds = work["adds"] / peaks["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_adds else (by_adds, "adds")


def window_least_seconds(facts: dict) -> float | None:
    """Least time for the trees grown inside the (traced) window; None when
    there are none."""
    first = facts["first_window_tree"]
    trees = facts["trees"][first:first + facts["done"]]
    if not trees:
        return None
    need = required(trees, facts["rows"], facts["features"], facts["bins"])
    return least_seconds(need, facts["peaks"])[0]
