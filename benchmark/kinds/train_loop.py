"""Traffic kind ``train_loop``: one client, closed loop, ``Booster.update()``
back to back for the window with at most ``in_flight`` iterations queued.

Parameters (the traffic file): ``in_flight``, ``check_iters`` (set-up drives
the booster through this many first iterations, whose trees and scores the
reference then follows), ``sampled_nodes`` (nodes per tree, beside the root,
whose whole histogram the reference recomputes), ``trace_seconds`` (how much of
the window a ``--trace 1`` run traces and measures), ``datagen_threads``.

The system under test is entered where users enter it: ``Dataset`` ->
``Booster`` -> ``update()``. ``make_system`` is the one seam: tests put a
broken system there and see ``correct`` come out false.
"""
from __future__ import annotations

import gc
import sys
import time
from collections import deque

import numpy as np

import datagen
import modeltext
import reference


class System:
    """The program, as this traffic drives it."""

    def __init__(self, params: dict, X, y, spans):
        import lightgbm_tpu as lgb
        with spans.span("setup/dataset"):
            self.ds = lgb.Dataset(X, label=y, params=dict(params))
            self.ds.construct()
        with spans.span("setup/booster_init"):
            self.bst = lgb.Booster(dict(params), self.ds)

    def update(self) -> bool:
        return self.bst.update()

    def scores(self):
        """A fresh device array [n] of the training scores as they stand."""
        return self.bst._gbdt.train_scores()[0]

    def iterations(self) -> int:
        return self.bst.current_iteration()

    def model_text(self) -> str:
        return self.bst.model_to_string()

    def report(self) -> dict:
        return self.bst.device_report()

    def stop_check_interval(self) -> int:
        return int(self.bst._gbdt._stop_check_interval)

    def close(self) -> None:
        self.bst = self.ds = None
        gc.collect()


def make_system(params, X, y, spans):
    return System(params, X, y, spans)


def _program_counters() -> dict:
    from lightgbm_tpu.obs import registry as obs
    from lightgbm_tpu.ops import step_cache
    from lightgbm_tpu.utils import timing
    c = dict(obs.default_registry().counter_items())
    return {"candidates_failed": c.get("autotune/candidates_failed", 0),
            "retries": c.get("retry/retries", 0),
            "step_compiles": step_cache.stats()["compiles"],
            "bin_s": timing.seconds("binning/"),
            "autotune_s": timing.seconds("autotune")}


class CompileWatch:
    """Counts backend compiles through jax's own monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    cfg, tr, spans = ctx.config, ctx.traffic, ctx.spans
    params, data = dict(cfg["params"]), dict(cfg["data"])
    data.update(ctx.overrides.get("data", {}))
    params.update(ctx.overrides.get("params", {}))
    rows, feats = int(data["rows"]), int(data["features"])
    K = int(tr["check_iters"])
    compiles = CompileWatch()

    def peak_bytes() -> int:
        if not ctx.on_chip:
            return 0
        return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())

    with spans.span("setup/datagen"):
        X, levels, y = datagen.make(data, ctx.seed, int(tr["datagen_threads"]))
    system = make_system(params, X, y, spans)
    del X
    peak_at = {"construct": peak_bytes()}
    fence_fn = jax.jit(lambda a: a + 1)
    one = jnp.zeros((), jnp.int32)

    def fence():
        return fence_fn(one)

    # the first steps: the reference follows these; the same booster goes on
    # into the window
    prog_scores = []
    with spans.span("setup/first_update"):
        system.update()
    prog_scores.append(system.scores())
    peak_at["first_update"] = peak_bytes()
    for _ in range(K - 1):
        with spans.span("setup/warm_update"):
            system.update()
        prog_scores.append(system.scores())
    with spans.span("setup/warm_drain"):
        # the program's periodic stop check stacks this many scalars and reads
        # them back; its first one would otherwise compile inside the window
        np.asarray(jnp.stack([jnp.zeros((), jnp.int32)]
                             * system.stop_check_interval()))
        fence().block_until_ready()
    before = _program_counters()
    compiles_before = compiles.n
    it_before = system.iterations()
    check_every = system.stop_check_interval()

    traced = ctx.trace
    seconds = min(ctx.seconds, float(tr["trace_seconds"])) if traced else ctx.seconds
    if traced:
        ctx.start_trace()
    # ---- the window -------------------------------------------------------
    fences: deque = deque()
    issued = stopped = 0
    with spans.span("window"):
        t0 = time.monotonic()
        setup_s = t0 - ctx.t_process
        while True:
            if len(fences) >= int(tr["in_flight"]):
                with spans.span("fence_wait"):
                    fences.popleft().block_until_ready()
            if time.monotonic() - t0 >= seconds:
                break
            reads = (it_before + issued + 1) % check_every == 0
            with spans.span("update_issue+stop_check" if reads else "update_issue"):
                stopped += bool(system.update())
            issued += 1
            fences.append(fence())
        with spans.span("fence_wait"):
            while fences:
                fences.popleft().block_until_ready()
        t1 = time.monotonic()
    if traced:
        ctx.stop_trace()
    window_s = t1 - t0
    compiles_in_window = compiles.n - compiles_before
    after = _program_counters()
    done = system.iterations() - it_before
    peak = peak_bytes()

    print(f"[bench] window: issued {issued} done {done} in {window_s:.3f}s = "
          f"{rows * done / window_s:.6g} row-iters/s; setup {setup_s:.2f}s; "
          f"peak {peak / 2**30:.3f} GiB; setup spans "
          + ", ".join(f"{k.split('/')[1]}={spans.total(k):.2f}s" for k in (
              "setup/datagen", "setup/dataset", "setup/booster_init",
              "setup/first_update", "setup/warm_update", "setup/warm_drain"))
          + f"; program clocks bin={after['bin_s']:.2f}s autotune="
          f"{after['autotune_s']:.2f}s; peak after construct "
          f"{peak_at['construct'] / 2**30:.3f} GiB, after the first update "
          f"{peak_at['first_update'] / 2**30:.3f} GiB", file=sys.stderr, flush=True)
    rep = system.report()
    print("[bench] program resolved: " + ", ".join(
        f"{k}={rep.get(k)}" for k in ("platform", "route", "fused_pallas",
                                      "interpret", "precision", "exact_variant", "wave_size",
                                      "chunk", "num_bins", "bins_shape", "device_ingest", "learner_mode")
        if k in rep), file=sys.stderr, flush=True)
    text = system.model_text()
    system.close()
    trees = modeltext.parse_trees(text)
    guards = {
        "guard.trees_missing": float(max(0, it_before + issued - len(trees))),
        "guard.stopped_early": float(stopped),
        "guard.autotune_failed": float(after["candidates_failed"]),
        "guard.retries": float(after["retries"]),
        "guard.compiles_in_window": float(
            compiles_in_window
            + (after["step_compiles"] - before["step_compiles"])),
        # off the chip (a test, the rehearsal) the route is the CPU's by design
        # and nothing is measured; run.py's gate keeps such a run from a result
        "guard.not_mosaic_route": float(ctx.on_chip and not (
            rep.get("platform") == "tpu" and rep.get("route") == "pallas-tpu"
            and rep.get("fused_pallas") and not rep.get("interpret"))),
    }

    with spans.span("reference"):
        cmp_params = {**params, "levels": data["levels"]}
        ref = reference.compare(levels, y, trees[:K], prog_scores[:K],
                                cmp_params, ctx.seed,
                                n_sampled=int(tr["sampled_nodes"]),
                                lower=ctx.lower)
    print(f"[bench] reference: {spans.total('reference'):.2f}s", file=sys.stderr,
          flush=True)
    for d in ref["detail"]:
        print(f"[bench] tree {d['tree']} worst leaf: " + ", ".join(
            f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in d["worst_leaf"].items()), file=sys.stderr, flush=True)
    numbers = {**ref["numbers"], **guards}
    facts = {
        "rows": rows, "features": feats, "bins": int(data["levels"]),
        "issued": issued, "done": done, "window_s": window_s,
        "setup_s": setup_s, "t_window": (t0, t1), "trees": trees,
        "first_window_tree": it_before, "spans": spans,
        "bin_s": after["bin_s"], "autotune_s": after["autotune_s"],
        "first_step_s": spans.total("setup/first_update"),
        "peak_bytes": peak, "reference": ref,
    }
    return {
        "attempted": issued, "failed": max(0, issued - done) + stopped,
        "end_to_end": {"train_row_iters_per_s": rows * done / window_s,
                       "setup_s": setup_s},
        "numbers": numbers, "limits": cfg["limits"], "facts": facts,
        "memory_peak_bytes": peak,
    }
