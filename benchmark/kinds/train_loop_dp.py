"""Traffic kind ``train_loop_dp``: ``train_loop``'s loop over a float32 matrix
that a DATA-PARALLEL learner shards over the cell's chips. One client, closed
loop, ``Booster.update()`` back to back for the window with at most
``in_flight`` iterations queued; the traffic file's parameters are
``train_loop``'s, with a ``collective`` group of device operations beside
``hist``.

The loop IS ``train_loop.run`` (this file loads a copy of that module for
itself, as ``rank_loop.py`` does): the window, the fences, the guards and
every ``facts`` key the readers read are the same code. What differs:

* the data: ``datagen_f32.make`` (``datagen.make``'s levels and labels, the
  matrix float32);
* a question BEFORE any data is made: does a float32 matrix stay float32
  through ``Dataset`` (``lightgbm_tpu.basic.keeps_float32``)? A program that
  cannot say exits 5 with one line, within seconds: it would bin tens of
  millions of float64 rows into the run's time limit;
* three guards, limit 0 each: ``guard.serial_fallback`` (the booster did not
  resolve to ``tree_learner=data`` over the cell's chips, or the program
  counted a fall back to the serial learner), ``guard.float64_route`` (not
  every row went through the float32 ingest), ``guard.unsharded_bins`` (the
  bin matrix is not one equal shard on each of the chips);
* ``System.scores`` hands the reference a HOST copy: the scores live sharded
  over the chips, the reference computes on one.

**Adding a multi-chip cell.** ``"chips": 4`` in the ``workloads`` entry (the
harness's gate then asks for four; ``memory_peak_bytes`` is the FULLEST
chip's); ``num_machines`` and ``tree_learner`` in the configuration's
``params``; ``data.rows`` is the GLOBAL count and so is ``facts["rows"]``
(``hist/rows_dotted`` is summed over the shards by the program). The kind sets
``facts["chips"]``: ``work_dp.py`` and ``shard.dotted_rows_skew`` read it, and
the accepted ``work.window_least_seconds`` does NOT, so ``step.mfu_pct`` and
``kernel.hist_roofline`` set the CLUSTER's work against one chip's peaks here.
``tracereduce.reduce`` averages busy time, the window, every operation's self
time and so every ``kernel_s`` group over the device planes: a reader of a
trace number reads a mean chip. The fault that belongs to the mechanism (one
chip's histogram left out of the sum) is ``plants_dp.py``'s, the limits are
read by ``calibrate_dp.py``.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

_spec = importlib.util.spec_from_file_location(
    "bench_train_loop_dp_train_loop", Path(__file__).with_name("train_loop.py"))
_loop = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_loop)

_seen: dict = {}          # what the last System showed, for the guards


def _counter(name: str) -> int:
    from lightgbm_tpu.obs import registry as obs
    return int(dict(obs.default_registry().counter_items()).get(name, 0))


class System(_loop.System):
    """The program, as this traffic drives it."""

    def __init__(self, params: dict, X, y, spans, weight=None, ds=None):
        """``weight`` and ``ds`` (a constructed ``Dataset`` to build the
        booster on) are ``plants_dp.py``'s and ``calibrate_dp.py``'s."""
        import lightgbm_tpu as lgb
        f32_before = _counter("ingest/f32_rows")
        with spans.span("setup/dataset"):
            self.ds = ds or lgb.Dataset(X, label=y, weight=weight,
                                        params=dict(params))
            self.ds.construct()
            if ds is not None and weight is not None:
                ds.set_weight(weight)
        with spans.span("setup/booster_init"):
            self.bst = lgb.Booster(dict(params), self.ds)
        _seen.clear()
        _seen["f32_rows"] = (len(y) if ds is not None else
                             _counter("ingest/f32_rows") - f32_before)

    def scores(self):
        import numpy as np
        return np.asarray(super().scores())

    def report(self) -> dict:
        rep = super().report()
        _seen["report"] = rep
        return rep


def make_system(params, X, y, spans):
    return System(params, X, y, spans)


def _make(spec, seed, threads):
    import datagen_f32
    return datagen_f32.make(spec, seed, threads)


_loop.datagen = SimpleNamespace(make=_make)
_loop.make_system = lambda *a: make_system(*a)     # this module's, as it stands at the call


def ask_float32() -> None:
    """The program's own word that a float32 matrix stays float32."""
    try:
        from lightgbm_tpu.basic import keeps_float32
        import numpy as np
        ok = bool(keeps_float32(np.zeros((2, 2), np.float32)))
    except (ImportError, AttributeError):
        ok = False
    if not ok:
        print("[bench] this program cannot say that a float32 matrix stays "
              "float32 through Dataset (no lightgbm_tpu.basic.keeps_float32): "
              "the cell needs the float32 ingest route", file=sys.stderr,
              flush=True)
        sys.stdout.flush()
        raise SystemExit(5)


def guards(rows: int, chips: int) -> dict:
    rep = _seen.get("report", {})
    shards = rep.get("bins_shards", [])
    width = rep.get("bins_shape", (0, 0))[1]
    sharded = (len(shards) == chips
               and len({dev for dev, _ in shards}) == chips
               and all(shape[1] * chips == width and shape[1] * chips >= rows
                       for _, shape in shards))
    return {
        "guard.serial_fallback": float(
            rep.get("learner_mode") != "data"
            or rep.get("num_devices") != chips
            or _counter("learner/serial_fallbacks") > 0),
        "guard.float64_route": float(_seen.get("f32_rows", 0) != rows),
        "guard.unsharded_bins": float(not sharded),
    }


def run(ctx) -> dict:
    import jax
    ask_float32()
    chips = int(ctx.config["params"]["num_machines"])
    res = _loop.run(ctx)
    res["numbers"].update(guards(res["facts"]["rows"], chips))
    res["facts"]["chips"] = chips
    res["facts"]["device_kind"] = jax.devices()[0].device_kind
    return res
