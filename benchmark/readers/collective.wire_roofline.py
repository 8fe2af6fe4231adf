"""The least time a chip's link needs for the bytes the ALGORITHM requires on
the wire (``work_dp.py``: a reduce-scatter of one histogram a leaf) over the
time the collective group took. A program that all-reduces whole 24-slot waves
reads a third at most however good its wire; waiting for the slowest shard
reads lower still. None, never 0, where the trace holds no collective."""
import work_dp


def read(facts):
    t = facts["trace"]["kernel_s"].get("collective")
    least = work_dp.window_wire_least_seconds(facts)
    if not t or least is None:
        return None
    return 100.0 * least / t
