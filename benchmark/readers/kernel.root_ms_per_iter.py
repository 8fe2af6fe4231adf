"""Device time of a tree's root pass an iteration: the traced window's self
time (``tracereduce``'s ``op_seconds``) of the device operations whose name
holds ``wave_histogram_pallas``, the name under which the root's Mosaic call
reaches the compiler (``ops/hist_wave.py``), over the iterations traced. The
wave passes run under ``fused_partition_histogram_pallas`` and are not in it:
the number is the root's ONLY where the fused kernel serves every wave pass,
as in both cells. The one-hot wave kernel reaches the compiler under the same
name, so where a W > 1 caller runs it (the feature and voting learners, a
bundled EFB booster) its wave passes would be booked here too; no cell does.
None where the trace holds no such operation."""
ROOT_KERNEL = "wave_histogram_pallas"


def read(facts):
    hit = [s for name, s in facts["trace"].get("op_seconds", {}).items()
           if ROOT_KERNEL in name.lower()]
    if not hit or not facts["done"]:
        return None
    return 1e3 * sum(hit) / facts["done"]
