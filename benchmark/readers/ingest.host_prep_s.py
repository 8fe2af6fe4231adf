"""Seconds the ingest worker thread spent keying chunks on the host: the
program's timer ``ingest/prep_chunk`` (``io/ingest.py`` ``_prep_chunk``), read
from its registry in this process. The span occurs in ``Dataset`` construction
only, so the total is set-up's."""
import progtrace


def read(facts):
    return progtrace.registry_timer("ingest/prep_chunk")
