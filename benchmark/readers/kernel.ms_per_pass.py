"""Device time of one full-data pass: the ``hist`` group's device time in the
traced window (``tracereduce``'s ``kernel_s``) over its launches there."""
import progtrace


def read(facts):
    red = progtrace.of(facts)
    n = red and red["group_launches"].get("hist")
    if not n or "hist" not in facts["trace"]["kernel_s"]:
        return None
    return 1e3 * facts["trace"]["kernel_s"]["hist"] / n
