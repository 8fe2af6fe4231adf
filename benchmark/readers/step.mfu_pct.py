"""The whole iteration's share of the chip's peak: the least time the chip
could take for the work the traced trees require (``work.py``) over the traced
window's length."""
import work


def read(facts):
    least = work.window_least_seconds(facts)
    if least is None or not facts["trace"]["window_s"]:
        return None
    return 100.0 * least / facts["trace"]["window_s"]
