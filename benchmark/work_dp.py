"""The bytes a data-parallel tree REQUIRES on the wire, from the cell's shapes
and the trees that were grown, never from what the program sends.

LightGBM's data-parallel algorithm (``data_parallel_tree_learner.cpp``) sums,
for each tree, one ``[F, B, 3]`` float32 histogram a leaf: the root's and each
split's SMALLER child's (the sibling comes by subtraction on every machine).
It reduce-SCATTERS them: each of the ``D`` machines ends with the sum of its
own ``1/D`` of the features, so ``(D - 1) / D`` of each histogram leaves a
chip, once. A program that all-reduces whole waves of 24 slots hands its wire
three times that and more; the share of the roofline says so."""
from __future__ import annotations

import json
from pathlib import Path

CHANNELS, ENTRY_BYTES = 3, 4          # gradient, hessian, count; float32


def tree_wire_bytes(leaves: int, features: int, bins: int, chips: int) -> float:
    """Bytes that have to leave ONE chip for a tree of ``leaves`` leaves."""
    hist = features * bins * CHANNELS * ENTRY_BYTES
    return leaves * hist * (chips - 1) / chips


def window_wire_bytes(facts: dict) -> float | None:
    first = facts["first_window_tree"]
    trees = facts["trees"][first:first + facts["done"]]
    chips = int(facts.get("chips", 1))
    if not trees or chips < 2:
        return None
    return sum(tree_wire_bytes(int(t["num_leaves"]), facts["features"],
                               facts["bins"], chips) for t in trees)


def link_bytes_per_s(device_kind: str) -> float:
    table = json.loads(Path(__file__).with_name("peaks_ici.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"no link peak for device kind {device_kind!r} in "
                         "peaks_ici.json")
    return float(table["devices"][device_kind]["ici_bytes_per_s"])


def window_wire_least_seconds(facts: dict) -> float | None:
    """Least time a chip's link needs for the trees grown in the window."""
    need = window_wire_bytes(facts)
    if need is None:
        return None
    return need / link_bytes_per_s(facts["device_kind"])
