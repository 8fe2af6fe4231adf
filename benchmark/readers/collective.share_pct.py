"""The collective group's device time over the busy time (both the mean over
the chips' planes). None where the trace holds no collective."""


def read(facts):
    tr = facts["trace"]
    t = tr["kernel_s"].get("collective")
    if not t or not tr["busy_s"]:
        return None
    return 100.0 * t / tr["busy_s"]
