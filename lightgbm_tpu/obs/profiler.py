"""jax profiler integration: the ``ProfileWindow`` iteration bracket.

Brackets training iterations with ``jax.profiler.start_trace`` /
``stop_trace`` (config ``tpu_profile_dir``). ``tpu_profile_iters = 0``
traces the whole boosting loop (the pre-existing engine.train
behavior); ``N > 0`` traces exactly N iterations starting at iteration
2, skipping the compile-dominated first iteration so the capture shows
steady-state device work. The window only opens and closes the
profiler session: every span of the program (obs/trace.span,
utils/timing.phase) is a ``TraceAnnotation`` inside whatever session
is open, this one or anyone else's, so the engine's span names appear
in the capture as ``lgbm/<name>`` with nothing to switch on.

Resilient by design: a jax without the profiler, or a backend where
tracing fails, logs a warning and training proceeds untraced.
"""
from __future__ import annotations

from ..utils import log


def profiler_available() -> bool:
    try:
        import jax
        return (hasattr(jax.profiler, "start_trace")
                and hasattr(jax.profiler, "stop_trace"))
    except Exception:                   # noqa: BLE001 — absence == off
        return False


class ProfileWindow:
    """start/stop_trace bracket over a configurable iteration window.

    Drivers call ``iter_begin(it)`` / ``iter_end(it)`` with 1-based
    iteration numbers and ``close()`` after the loop (idempotent; also
    the safety net for early stops while the trace is open).
    """

    def __init__(self, trace_dir: str = "", iters: int = 0):
        self.trace_dir = trace_dir or ""
        self.iters = max(int(iters or 0), 0)
        self._active = False
        self._done = False
        if self.trace_dir and not profiler_available():
            log.warning("tpu_profile_dir=%s set but jax.profiler is "
                        "unavailable; tracing disabled", self.trace_dir)
            self.trace_dir = ""

    @property
    def enabled(self) -> bool:
        return bool(self.trace_dir)

    def _start_at(self) -> int:
        # whole-run trace starts at iteration 1; a bounded window skips
        # the compile-dominated first iteration
        return 1 if self.iters == 0 else 2

    def iter_begin(self, it: int) -> None:
        if (not self.enabled or self._active or self._done
                or it < self._start_at()):
            return
        try:
            import jax
            jax.profiler.start_trace(self.trace_dir)
        except Exception as e:          # noqa: BLE001 — tracing is an
            # observability aid; a failing profiler must not stop training
            log.warning("jax.profiler.start_trace(%s) failed: %s",
                        self.trace_dir, e)
            self.trace_dir = ""
            return
        self._active = True
        log.info("profiler trace started (dir=%s, window=%s)",
                 self.trace_dir,
                 "whole run" if self.iters == 0
                 else f"{self.iters} iterations from iteration "
                      f"{self._start_at()}")

    def iter_end(self, it: int) -> None:
        if (not self._active or self.iters == 0
                or it < self._start_at() + self.iters - 1):
            return
        self._stop()

    def close(self) -> None:
        if self._active:
            self._stop()

    def _stop(self) -> None:
        try:
            import jax
            jax.profiler.stop_trace()
            log.info("profiler trace written to %s", self.trace_dir)
        except Exception as e:          # noqa: BLE001
            log.warning("jax.profiler.stop_trace failed: %s", e)
        self._active = False
        self._done = True
