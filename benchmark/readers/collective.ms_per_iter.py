"""Device time of the collective operations (the traffic file's ``collective``
group: all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all;
self time, the mean over the chips' planes) inside the traced iterations over
their number. It holds the wire AND the wait for the slowest shard: a chip
that reaches the sum first sits in it. None where the trace holds none."""


def read(facts):
    t = facts["trace"]["kernel_s"].get("collective")
    if not t or not facts["done"]:
        return None
    return 1e3 * t / facts["done"]
