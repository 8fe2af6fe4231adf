#!/usr/bin/env python3
"""``calibrate.py`` for a ranking cell (kind ``rank_loop``):

    python3 benchmark/calibrate_rank.py --workload yahoo_ltr.train_rank --seeds 11,12,13 \
        --seconds 1 --out chiprun_out/cal_rank.jsonl \
        [--plant tail_pairs_left_out --plant-seeds 11,12,13]

The tool IS ``calibrate.py`` (one process, one whole run of the harness a
seed, the control and the emulated faults read beside the program); this file
only lets its ``--plant`` find the ranking faults of ``plants_rank.py`` under
the names it looks them up by, and prints, a line of its own before each
seed's, what only a ranking reference reads: the fault ``tail`` (every query's
pairs beyond its first 64 documents left out) and ``own`` (the judgement with
each step's gradient computed at the reference's own chain instead of at the
program's scores: the gap between the two gradients and what it moves)."""
from __future__ import annotations

import json
import sys

import calibrate
import plants
import plants_rank
import reference_rank

if __name__ == "__main__":
    plants.ALL.update(plants_rank.ALL)
    compare = reference_rank.compare

    def with_tail(levels, grades, lengths, trees, prog_scores, params, seed,
                  **kw):
        out = compare(levels, grades, lengths, trees, prog_scores, params,
                      seed, **kw)
        for k in ("tail", "own"):
            if k in out:
                print(json.dumps({"seed": seed, k: out[k]}), flush=True)
        return out
    reference_rank.compare = with_tail
    sys.exit(calibrate.main())
