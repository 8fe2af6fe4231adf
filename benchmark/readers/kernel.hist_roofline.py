"""The histogram kernels' share of their roofline: the least time for the
required work (``work.py``; bound by bytes at these shapes) over the kernels'
summed device time."""
import work


def read(facts):
    least = work.window_least_seconds(facts)
    if least is None or "hist" not in facts["trace"]["kernel_s"]:
        return None
    return 100.0 * least / facts["trace"]["kernel_s"]["hist"]
