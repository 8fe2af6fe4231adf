"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest chip."""


def read(facts):
    return facts["peak_bytes"] / 2**30 if facts["peak_bytes"] else None
