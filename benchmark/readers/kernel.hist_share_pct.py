"""Device time of the histogram kernels (root wave + fused partition and
histogram) over device-busy time."""


def read(facts):
    tr = facts["trace"]
    if "hist" not in tr["kernel_s"] or not tr["busy_s"]:
        return None
    return 100.0 * tr["kernel_s"]["hist"] / tr["busy_s"]
