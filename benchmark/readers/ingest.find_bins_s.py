"""Seconds of ``Dataset`` construction outside the bin kernel: the per-feature
bin search and the bundler's conflict search, the program's timers
``binning/find_bins`` and ``binning/efb`` (``io/dataset.py``). Both grow with
the number of columns; ``ingest.bin_s`` holds them and ``binning/bin_matrix``
together. None where the program recorded neither."""
import progtrace


def read(facts):
    found = [t for t in (progtrace.registry_timer("binning/find_bins"),
                         progtrace.registry_timer("binning/efb"))
             if t is not None]
    return sum(found) if found else None
