"""Find bins + bin the matrix + upload: the program's own phase clocks
(``utils/timing.py``, ``binning/*``, device-synced at phase exit)."""


def read(facts):
    return facts["bin_s"]
