"""Feature tiles a histogram pass walks: the program's gauge
``hist/feature_tiles`` (``ops/wave_grower.py``, set where the grower is built, as the
kernels resolve it at trace time: ``autotune.hist_feature_tiling`` prices
the accumulators, the bin block and the compaction's payload a feature against
``PALLAS_VMEM_BUDGET_BYTES``). 1 is a pass whose every feature fits one
resident block, which lowers the kernels as they were before the tile axis.
None where the program has no such gauge."""
import progtrace


def read(facts):
    return progtrace.registry_gauge("hist/feature_tiles")
