"""MACs the root pass's dot spends on one row of one feature, as the kernel
was built: the program's gauge ``hist/root_macs`` (``ops/wave_grower.py``, set
where the grower is built). 32,768 where the wave kernel's one-hot dot serves
the root at 255 bins (256 bin rows x 128 lanes, 5 of which carry anything);
5,120 under the root kernel's two-digit split of the bin axis (5 channels x 8
low digits, streamed past the 128 lanes of four features' high digits).
``work.py`` requires 2 adds. None where the program has no such gauge."""
import progtrace


def read(facts):
    return progtrace.registry_gauge("hist/root_macs")
