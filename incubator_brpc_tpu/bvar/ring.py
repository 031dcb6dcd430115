"""The two clocks a stage is stamped on, and the ring rows of stamps stay in.

A timeline row is a tuple of ``time.monotonic_ns()`` stamps written by
the threads that do the work. Where one thread both begins and ends a
stage it reads its own CPU clock beside the wall clock (``clocks``): the
stage's wall time less its CPU time is the time that thread was off the
processor, waiting for the interpreter (bvar/lock_probe.py is the number
for that wait), for the runtime, or parked.
``Ring`` keeps the last rows the sampler fed, as numbers, for a reader
that lines them up with a device trace (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import threading
from time import monotonic_ns, thread_time_ns

import numpy as np


# One dispatch, link step and fused call in this many carries the CPU
# clock on its stamps. The read is a system call: 5.8 us alone on the
# chip's hosts, 20-40 inside a server of many threads, where five a
# dispatch cost 1.4-1.7% of the calls/s at 256 B, five a link step 2.6% of
# a 1 MiB echo's goodput and eight a fused call 2.8% of the partitioned
# cell's (PERF.md, PR 35). The CPU recorders' means lose nothing but
# samples: theirs is a quarter of the wall recorders' count.
CPU_CLOCK_EVERY = 4


def clocks(cpu: bool = True) -> tuple:
    """``(time.monotonic_ns(), time.thread_time_ns())``, for a stamp at
    which the calling thread begins or ends a stage it executes;
    ``cpu=False``, for a unit of work that is not the one in
    ``CPU_CLOCK_EVERY``, leaves the second clock unread: -1, a stamp never
    taken."""
    return monotonic_ns(), thread_time_ns() if cpu else -1


class Ring:
    """The last ``rows`` rows of ``len(names)`` int64 numbers, oldest
    overwritten. Preallocated; written in bulk off the hot path."""

    def __init__(self, names, rows: int):
        self.names = tuple(names)
        self._table = np.zeros((rows, len(self.names)), dtype=np.int64)
        self._written = 0  # rows ever written
        self._lock = threading.Lock()

    def extend(self, table: np.ndarray) -> None:
        """Append the rows of ``table``, ``(n, len(names))``, in order."""
        size = len(self._table)
        with self._lock:
            at = (self._written + max(0, len(table) - size)) % size
            self._written += len(table)
            table = table[-size:]
            head = min(len(table), size - at)
            self._table[at : at + head] = table[:head]
            self._table[: len(table) - head] = table[head:]

    def read(self) -> tuple:
        """``(names, rows)``: a copy of the rows that were written and not
        yet overwritten, oldest first."""
        size = len(self._table)
        with self._lock:
            if self._written <= size:
                return self.names, self._table[: self._written].copy()
            at = self._written % size
            return self.names, np.concatenate(
                (self._table[at:], self._table[:at])
            )
