"""The open-loop schedule: requests are due at fixed instants whatever
the system does, and every latency is counted from the due time.

The arithmetic is tools/loadtime.py's (a stalled generator sends at
once until the schedule is level again, so the offered average holds);
what differs is the clock a latency starts from: the instant the
request was DUE, so a stall is charged to every request it delayed.
"""
from __future__ import annotations

import asyncio
import time
from typing import AsyncIterator, Iterator

now = time.monotonic


def due_times(start: float, interval_s: float, seconds: float
              ) -> Iterator[tuple[int, float]]:
    """(index, due) for every request due in [start, start+seconds)."""
    if interval_s <= 0:
        raise ValueError("interval must be positive")
    i = 0
    while i * interval_s < seconds:
        yield i, start + i * interval_s
        i += 1


def count_due(interval_s: float, seconds: float) -> int:
    return sum(1 for _ in due_times(0.0, interval_s, seconds))


OVERRUN_S = 1.0     # how late the generator may get to a request


async def paced(start: float, interval_s: float, seconds: float,
                spin_s: float = 0.0, overrun_s: float = OVERRUN_S
                ) -> AsyncIterator[tuple[int, float, float]]:
    """Yield (index, due, late_s) at each due time.  ``late_s`` is how
    long after its due time the generator got to the request: the
    generator's own lateness, reported beside every open-loop metric
    so a starved generator is not read as a fast server.  A timer
    wake-up is a millisecond late; a driver with nothing else on its
    loop passes ``spin_s`` to sleep short of the due time and spin
    the rest.  The window ends whatever the system does: a request
    the generator has not reached ``overrun_s`` after the window closed
    is never sent, and the driver counts it as failed (count_due says
    how many were due).  A cell far below capacity passes a longer
    ``overrun_s``: after a stall of the machine near the window's end
    it then still serves every due request, late, and each is charged
    its full wait."""
    end = start + seconds + overrun_s
    for i, due in due_times(start, interval_s, seconds):
        # always yield once: a late generator must not starve the loop
        await asyncio.sleep(max(0.0, due - spin_s - now()))
        while now() < due:
            pass
        if now() >= end:
            return
        yield i, due, max(0.0, now() - due)
