"""Traffic kind ``rank_loop``: ``train_loop``'s loop over a RANKING data set.
One client, closed loop, ``Booster.update()`` back to back for the window with
at most ``in_flight`` iterations queued; the traffic file's parameters are
``train_loop``'s.

The loop IS ``train_loop.run``: this file loads a copy of that module for
itself and puts the three things a ranking job changes in its collaborators'
places, so the window, the fences, the guards and every ``facts`` key the
readers read are the same code:

* the data: ``datagen_rank.make`` (``datagen.make``'s columns, graded labels,
  query lengths; the configuration's ``data`` block says which);
* the system, entered where users enter it: ``Dataset(X, label=grades,
  group=lengths)`` -> ``Booster`` -> ``update()``. ``make_system`` of THIS
  module is the one seam: tests and ``calibrate_rank.py`` put a fault of
  ``plants_rank.py`` there and see ``correct`` come out false;
* the judge: ``reference_rank.compare`` (the plain lambdarank gradient, then
  what ``reference.compare`` does with it).

What travels in ``train_loop``'s ``y`` argument is ``fields``: the keyword
arguments of ``Dataset`` that describe the rows, ``{"label": grades, "group":
lengths}``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import datagen_rank
import reference_rank

_spec = importlib.util.spec_from_file_location(
    "bench_rank_loop_train_loop", Path(__file__).with_name("train_loop.py"))
_loop = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_loop)


class System(_loop.System):
    """The program, as this traffic drives it."""

    def __init__(self, params: dict, X, fields: dict, spans):
        import lightgbm_tpu as lgb
        with spans.span("setup/dataset"):
            self.ds = lgb.Dataset(X, **fields, params=dict(params))
            self.ds.construct()
        with spans.span("setup/booster_init"):
            self.bst = lgb.Booster(dict(params), self.ds)


def make_system(params, X, fields, spans):
    return System(params, X, fields, spans)


def _make(spec, seed, threads):
    X, levels, grades, lengths = datagen_rank.make(spec, seed, threads)
    return X, levels, {"label": grades, "group": lengths}


def _compare(levels, fields, trees, prog_scores, params, seed, **kw):
    return reference_rank.compare(levels, fields["label"], fields["group"],
                                  trees, prog_scores, params, seed, **kw)


_loop.datagen = SimpleNamespace(make=_make)
_loop.reference = SimpleNamespace(compare=_compare)
_loop.make_system = lambda *a: make_system(*a)     # this module's, as it stands at the call


def run(ctx) -> dict:
    return _loop.run(ctx)
