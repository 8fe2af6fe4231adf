"""The fullest chip's ``peak_bytes_in_use`` when ``binning/bin_matrix`` ended:
the program's gauge ``mem/peak_bytes@binning/bin_matrix`` (``utils/timing.py``
``phase(mem_peak=True)``). The high-water mark is monotone: where this equals
``device.peak_hbm_gib`` the process's peak is ingest's."""
import progtrace


def read(facts):
    peak = progtrace.registry_gauge("mem/peak_bytes@binning/bin_matrix")
    return peak / 2**30 if peak else None
