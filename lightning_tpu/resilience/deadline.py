"""Dispatch deadlines and supervised-loop restart backoff.

Two failure shapes the breakers can't see: a dispatch that HANGS (a
wedged device runtime — the call never returns, so
there is no exception to count) and a flush loop that DIES (an escaped
exception kills the asyncio task; every later submit queues forever).
This module covers both:

* ``guard(aw, family, seam)`` bounds an awaitable by the family's
  configured deadline; a blown deadline is metered
  (``clntpu_deadline_exceeded_total{family,seam}``), emitted on the
  events bus, and surfaces as ``DeadlineExceeded`` — which the caller's
  existing failure handling (breaker + host fallback + future
  resolution) then treats like any other dispatch error.  NOTE: the
  underlying thread (asyncio.to_thread work) cannot be cancelled — the
  guard un-wedges the CALLER; the worker leaks until it returns.

* ``deadline_for(family)`` is the thread-side knob for blocking waits
  (the replay dispatch loop's prepared-bucket queue.get).

* ``RestartBackoff`` paces supervised-loop restarts (GossipIngest /
  RouteService flush loops): exponential from ``base`` to ``cap``,
  reset on a healthy iteration.  Restarts are metered per loop
  (``clntpu_loop_restarts_total{loop}``).

Deadlines default OFF (a cold XLA compile legitimately takes minutes;
a default that kills it would break first-run daemons).  Configure::

    LIGHTNING_TPU_DEADLINE_S            default for every family (0 = off)
    LIGHTNING_TPU_DEADLINE_VERIFY_S     per-family override
    LIGHTNING_TPU_DEADLINE_ROUTE_S
    LIGHTNING_TPU_DEADLINE_MCF_S
    LIGHTNING_TPU_DEADLINE_INGEST_S

(No sign deadline: hsmd's batched sign is a synchronous call on the
caller's thread — nothing could act on a blown deadline there.  Its
hang coverage is the caller's own event-loop supervision.)
"""
from __future__ import annotations

import asyncio
import logging
import os

from ..obs import families as _f
from ..utils import events

log = logging.getLogger("lightning_tpu.resilience.deadline")


class DeadlineExceeded(RuntimeError):
    pass


def deadline_for(family: str) -> float | None:
    """Configured dispatch deadline in seconds, or None (disabled)."""
    raw = os.environ.get(f"LIGHTNING_TPU_DEADLINE_{family.upper()}_S")
    if raw is None:
        raw = os.environ.get("LIGHTNING_TPU_DEADLINE_S")
    if raw is None:
        return None
    try:
        val = float(raw)
    except ValueError:
        return None
    return val if val > 0 else None


def note_exceeded(family: str, seam: str, deadline_s: float) -> None:
    """Meter + emit a blown deadline (thread-side callers that manage
    their own timeout, e.g. the replay dispatch loop's queue.get)."""
    _f.DEADLINE_EXCEEDED.labels(family, seam).inc()
    events.emit("deadline_exceeded", {"family": family, "seam": seam,
                                      "deadline_s": deadline_s})
    log.warning("%s:%s dispatch deadline (%.3fs) exceeded",
                family, seam, deadline_s)


async def guard(aw, family: str, seam: str):
    """Await ``aw`` under the family's deadline (pass-through when none
    is configured)."""
    dl = deadline_for(family)
    if dl is None:
        return await aw
    try:
        return await asyncio.wait_for(aw, dl)
    except asyncio.TimeoutError:
        note_exceeded(family, seam, dl)
        raise DeadlineExceeded(
            f"{family}:{seam} dispatch exceeded {dl:g}s deadline") from None


class RestartBackoff:
    """Exponential restart pacing for a supervised loop."""

    def __init__(self, base: float = 0.05, cap: float = 5.0):
        self.base = base
        self.cap = cap
        self._next = base

    def next(self) -> float:
        delay = self._next
        self._next = min(self.cap, self._next * 2.0)
        return delay

    def reset(self) -> None:
        self._next = self.base


def note_restart(loop: str, error: BaseException, delay: float) -> None:
    """Meter + emit one supervised-loop restart."""
    _f.LOOP_RESTARTS.labels(loop).inc()
    events.emit("loop_restart", {"loop": loop, "error": repr(error),
                                 "restart_delay_s": round(delay, 3)})
    log.exception("%s loop error; restarting in %.2fs", loop, delay,
                  exc_info=error)
