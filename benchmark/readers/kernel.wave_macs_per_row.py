"""MACs a row put through a wave pass's dot met, a feature: the program's
counter ``hist/blocks_dotted`` (``models/gbdt.py``: the 128-row block-dots of
the fused kernel's flush, fed at the stop check beside ``hist/rows_dotted``,
from a third scalar a tree on the same download) x 128 rows x the gauge
``hist/wave_macs`` (``ops/wave_grower.py``, set where the grower is built: MACs
one block-dot spends on a row of a feature) over ``hist/rows_dotted``.

Where the flush dots one-hot rows against every slot's lanes a row meets one
block-dot of 256 x 128 MACs at 255 bins: 32,768. Where it puts the staged rows
in slot order and dots each 128-row block against the slots it holds, by the
root kernel's two digits, a block-dot is 5 x 8 x 128 = 5,120 and a row meets
as many as its block holds slots: 5,120 with one live slot, 12,480 at most
with 24 (39 pairs to 16 blocks). The ordering gather's MACs (the staged rows'
``C x 2,048`` a row, whatever the features: ~2,900 a feature at 67) are not in
it. ``work.py`` requires 2 adds. None where the program has no such counter
or gauge, or has dotted nothing yet."""
import progtrace

BLOCK_ROWS = 128


def read(facts):
    from lightgbm_tpu.obs import registry as obs
    counters = dict(obs.default_registry().counter_items())
    macs = progtrace.registry_gauge("hist/wave_macs")
    rows = counters.get("hist/rows_dotted", 0)
    if "hist/blocks_dotted" not in counters or not macs or not rows:
        return None
    return counters["hist/blocks_dotted"] * BLOCK_ROWS * macs / rows
