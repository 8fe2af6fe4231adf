"""Microseconds of ``Dataset`` construction (the program's ``binning/*`` phase
clocks: find bins, bin the matrix, upload) a row of the GLOBAL matrix that
went through the float32 route (counter ``ingest/f32_rows``). None where no
row did, or the program has no such counter."""


def read(facts):
    from lightgbm_tpu.obs import registry as obs
    rows = dict(obs.default_registry().counter_items()).get("ingest/f32_rows", 0)
    if not rows or not facts.get("bin_s"):
        return None
    return 1e6 * facts["bin_s"] / rows
