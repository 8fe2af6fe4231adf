"""Pair slots the lambdarank layout evaluates an iteration over the real
pairs of the data (``sum n_q^2`` over the queries): the program's gauges
``rank/pair_slots`` and ``rank/pairs_real`` (``objectives/objective.py``, set
where the objective builds its query tables). 1.0 is a layout with no
padding; one ``[nq, qmax, qmax]`` block over this cell's queries reads ~18;
the layout by query length stays at or under 4. None where the program has no
such gauges (a program that pads every query to the longest one sets none)."""
import progtrace


def read(facts):
    slots = progtrace.registry_gauge("rank/pair_slots")
    real = progtrace.registry_gauge("rank/pairs_real")
    if not slots or not real:
        return None
    return slots / real
