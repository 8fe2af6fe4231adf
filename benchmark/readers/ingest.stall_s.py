"""Seconds the main thread waited for the ingest worker: the program's timer
``ingest/prefetch_wait`` (``io/ingest.py`` ``prefetch``, round
``fut.result()``). Near ``ingest.host_prep_s`` the worker's keying sets the
pace of ``Dataset`` construction; near 0 the upload and the bin kernel do."""
import progtrace


def read(facts):
    return progtrace.registry_timer("ingest/prefetch_wait")
