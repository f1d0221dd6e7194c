"""Open-loop arrivals: block k is due at t0 + k / rate whatever the
system does, and a block's latency runs from the time it was DUE, so
the wait that a stall imposes on later blocks is counted.  The clock
and the sleep are arguments, so the accounting is tested on a fake
clock.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Fixed interval, no jitter: an orderer under steady load cuts
    full blocks at an even pace."""

    rate: float      # blocks per second
    t0: float        # the clock's reading at which block 0 is due

    def due(self, k: int) -> float:
        return self.t0 + k / self.rate

    def count_within(self, seconds: float) -> int:
        """Blocks due in [t0, t0 + seconds)."""
        n = int(seconds * self.rate)
        while self.due(n) < self.t0 + seconds:
            n += 1
        while n > 0 and self.due(n - 1) >= self.t0 + seconds:
            n -= 1
        return n


def feed(schedule: Schedule, n: int, make, hand_in, clock, sleep) -> list:
    """Hand in blocks 0..n-1 at their due times.  `make(k)` builds block
    k BEFORE its due time (the copy is not the system's work);
    `hand_in(k, block, due, handed)` passes it on.  Returns how late
    each hand-in was, in seconds: a starved generator must not be read
    as a fast server."""
    late = []
    for k in range(n):
        block = make(k)
        due = schedule.due(k)
        while True:
            now = clock()
            if now >= due:
                break
            sleep(due - now)
        handed = clock()
        late.append(handed - due)
        hand_in(k, block, due, handed)
    return late


def latency(due: float, done: float) -> float:
    """From the time the block was due, not from when it was taken."""
    return done - due
