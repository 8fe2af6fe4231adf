"""Bounded retry with exponential backoff + jitter for transient
failures.

One policy for the seams that talk to something that can blip — the
``jax.distributed`` coordination service at bootstrap
(parallel/cluster.py), the fleet scoring daemon's HTTP socket
(serve/client.py), and the fault-injection drills on the ingest and
lrb window-train seams (utils/faults.py): retry with exponential
backoff and deterministic jitter, give up after a bounded number of
attempts, and count every decision in the obs registry
(``retry/attempts``, ``retry/retries``, ``retry/giveups``) so a live
run's flakiness is visible in the Prometheus export instead of buried
in logs.

Classification is conservative: only errors that *say* they are
transient are retried — a genuine bug fails fast on attempt 1. What
that means for a chip ATTACHED to this process (the only kind there
is: jax runs the device in-process, no RPC sits between them), marker
by marker:

- ``RESOURCE_EXHAUSTED`` is NOT transient: from a local device it
  means out of HBM (or a kernel over its VMEM) and is as true on the
  fourth attempt as on the first — retrying only delays the report.
  ``chip_smoke.py`` asserts ``retry/retries == 0``.
- ``ABORTED`` / ``Connection reset`` / ``Socket closed`` described a
  remote device runtime; a local one does not produce them, and a
  coordination-service channel that drops says ``UNAVAILABLE``.
- ``DEADLINE_EXCEEDED`` / ``UNAVAILABLE`` and the bootstrap phrases
  below come from the coordination service's grpc channel — peers
  still arriving, a coordinator still binding its port: worth backoff.
- stdlib ``ConnectionError`` / ``TimeoutError`` and the two
  http.client phrases are the fleet client's socket blips.
- ``InjectedFault(transient=True)`` is the drills' way to exercise
  this path on purpose.

Stdlib + obs only; importing this module never touches jax.
"""
from __future__ import annotations

import random
import time
from typing import Callable, Optional

from . import log
from .faults import InjectedFault

# substrings of transient failures (grpc/absl status names surface
# verbatim in XlaRuntimeError messages) — see the module docstring for
# why each is here and why RESOURCE_EXHAUSTED is not
TRANSIENT_MARKERS = (
    # jax.distributed / DCN bootstrap blips (parallel/cluster.py): a
    # coordinator that is still binding its port, restarting after a
    # preemption, or mid-handshake surfaces these — worth backoff, not
    # an attempt-1 giveup. Kept SPECIFIC (full service/phrase strings),
    # so a genuine config error ("connection" in some unrelated text)
    # still fails fast.
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "Connection refused",               # coordinator not listening yet
    "failed to connect to all addresses",   # grpc channel not up
    "Barrier timed out",                # peers still arriving
    "heartbeat timeout",                # coordination-service blip
    "Heartbeat timeout",
    "coordination service",             # service restarting
    "Coordination service",
    # fleet scoring-daemon client blips (serve/client.py): a daemon
    # mid model-swap or mid-restart drops the socket with these exact
    # stdlib phrases (http.client.RemoteDisconnected / socket.timeout
    # surfaced through urllib). Scoring requests are idempotent, so a
    # bounded retry is always safe.
    "Remote end closed connection",     # daemon dropped mid-response
    "Read timed out",                   # response overdue, socket alive
)


def is_transient(exc: BaseException) -> bool:
    """True when ``exc`` is worth retrying (see module docstring)."""
    if isinstance(exc, InjectedFault):
        return bool(exc.transient)
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return True
    msg = str(exc)
    return any(m in msg for m in TRANSIENT_MARKERS)


class RetryPolicy:
    """Backoff shape: ``attempts`` total tries, delay
    ``base_s * 2**k`` capped at ``max_s``, plus up to ``jitter`` of
    that delay from a seeded RNG (deterministic for a given seed —
    drills reproduce; production leaves seed=None for wall-clock
    entropy)."""

    def __init__(self, attempts: int = 4, base_s: float = 0.05,
                 max_s: float = 2.0, jitter: float = 0.5,
                 seed: Optional[int] = None,
                 sleep: Callable[[float], None] = time.sleep):
        self.attempts = max(int(attempts), 1)
        self.base_s = max(float(base_s), 0.0)
        self.max_s = max(float(max_s), self.base_s)
        self.jitter = max(float(jitter), 0.0)
        self._rng = random.Random(seed)
        self._sleep = sleep

    def delay_s(self, retry_index: int) -> float:
        """Backoff before retry ``retry_index`` (0-based)."""
        d = min(self.base_s * (2.0 ** retry_index), self.max_s)
        return d * (1.0 + self.jitter * self._rng.random())

    def sleep(self, retry_index: int) -> float:
        d = self.delay_s(retry_index)
        if d > 0:
            self._sleep(d)
        return d


DEFAULT_POLICY = RetryPolicy()


def call(fn: Callable, *, what: str = "operation",
         policy: Optional[RetryPolicy] = None,
         classify: Callable[[BaseException], bool] = is_transient):
    """Run ``fn()``; retry transient failures per ``policy``. The final
    transient failure (or any non-transient one) re-raises unchanged —
    callers see the real error, plus a ``gave up`` log line carrying
    ``what`` and the attempt count."""
    from ..obs import registry as obs
    p = policy or DEFAULT_POLICY
    for attempt in range(1, p.attempts + 1):
        obs.counter("retry/attempts").add(1)
        try:
            return fn()
        except BaseException as e:      # noqa: BLE001 — classified below
            if not classify(e):
                raise
            if attempt >= p.attempts:
                obs.counter("retry/giveups").add(1)
                log.warning("%s: gave up after %d attempts (%s: %s)",
                            what, attempt, type(e).__name__, e)
                raise
            obs.counter("retry/retries").add(1)
            d = p.sleep(attempt - 1)
            log.warning("%s: transient failure (attempt %d/%d, retrying "
                        "in %.2fs): %s", what, attempt, p.attempts, d, e)
