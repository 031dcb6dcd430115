"""IntRecorder + LatencyRecorder (reference src/bvar/latency_recorder.h).

LatencyRecorder is the compound bvar behind every per-method /status row:
average latency (IntRecorder window), percentile latencies (Percentile
window), max latency (Maxer window), qps (PerSecond of a count Adder).
"""

from __future__ import annotations

import threading
import weakref
from collections import deque
from typing import Optional

import numpy as np

from incubator_brpc_tpu.bvar.variable import Variable
from incubator_brpc_tpu.bvar.reducer import Adder, Maxer
from incubator_brpc_tpu.bvar.ring import Ring
from incubator_brpc_tpu.bvar.window import PerSecond, Window, sample_every_second
from incubator_brpc_tpu.bvar.percentile import Percentile


class IntRecorder(Variable):
    """Average of recorded ints; (sum, num) packed per-thread in the
    reference (int_recorder.h) — here a per-thread pair via Adder agents."""

    def __init__(self, name: Optional[str] = None):
        self._sum = Adder()
        self._num = Adder()
        super().__init__(name)

    def __lshift__(self, value: int) -> "IntRecorder":
        self._sum << value
        self._num << 1
        return self

    def average(self) -> float:
        n = self._num.get_value()
        return (self._sum.get_value() / n) if n else 0.0

    def sum(self) -> int:
        return self._sum.get_value()

    def get_value(self):
        return self.average()


class LatencyRecorder(Variable):
    """latency/qps/percentile compound (reference latency_recorder.h:40-107).

    ``<< latency_us`` records one call. Exposes (when named):
    {name}_latency, {name}_max_latency, {name}_qps, {name}_count,
    {name}_latency_{50,90,99,999}.
    """

    def __init__(self, name: Optional[str] = None, window_size: int = 10):
        self._latency = IntRecorder()
        self._max = Maxer(identity=0)
        self._count = Adder()
        self._percentile = Percentile()
        self._qps_window = PerSecond(self._count, window_size)
        self._lock = threading.Lock()
        super().__init__(name)

    def __lshift__(self, latency_us: float) -> "LatencyRecorder":
        self._latency << latency_us
        self._max << latency_us
        self._count << 1
        self._percentile.add(latency_us)
        return self

    def record_batch(
        self, count: int, total: float, max_value: float, samples
    ) -> None:
        """Aggregate feed for high-volume batch consumers (the native
        telemetry drain): ``count`` calls totalling ``total`` µs with
        max ``max_value``, plus ``samples`` — a bounded representative
        subset for the percentile reservoir. count/sum/max/qps stay
        EXACT; quantiles see the subset, which the reservoir (already a
        random subsample past its capacity) absorbs without bias worth
        the 100k-calls/s it saves."""
        if count <= 0:
            return
        self._latency._sum << total
        self._latency._num << count
        self._max << max_value
        self._count << count
        add = self._percentile.add
        for v in samples:
            add(v)

    # --- accessors mirrored from the reference API ---
    def latency(self) -> float:
        return self._latency.average()

    def max_latency(self) -> float:
        v = self._max.get_value()
        return 0 if v == float("-inf") else v

    def count(self) -> int:
        return self._count.get_value()

    def latency_sum(self) -> float:
        """Total of every recorded latency — a summary's ``_sum`` sample."""
        return self._latency.sum()

    def qps(self) -> float:
        return self._qps_window.get_value()

    def latency_percentile(self, ratio: float) -> float:
        return self._percentile.get_number(ratio)

    def get_value(self):
        return {
            "latency": self.latency(),
            "max_latency": self.max_latency(),
            "qps": self.qps(),
            "count": self.count(),
            "latency_50": self.latency_percentile(0.5),
            "latency_90": self.latency_percentile(0.9),
            "latency_99": self.latency_percentile(0.99),
            "latency_999": self.latency_percentile(0.999),
        }

    def describe(self) -> str:
        v = self.get_value()
        return (
            f"count={v['count']} qps={v['qps']:.0f} latency={v['latency']:.1f}us "
            f"p50={v['latency_50']:.1f} p99={v['latency_99']:.1f} max={v['max_latency']:.1f}"
        )


class RecorderFeed:
    """Rows of numbers on their way to a row of LatencyRecorders: the
    write path is one ``rows.append(tuple)``, and the 1 Hz sampler thread
    does the arithmetic and feeds each recorder through ``record_batch``
    — count, sum and max exact, the percentile reservoir given one row of
    16, the recorders up to a second behind. For timelines stamped on a
    hot path: ten ``<<`` a call, on the caller's thread, cost 5% of the
    calls/s of a 256-byte device echo (PERF.md, PR 25).

    A row holds whole numbers: absolute stamps (ns) and counts, by the
    positions ``stamps`` names. ``columns`` is the table of what is fed:
    ``(recorder, scale, span)`` each. ``span`` is one position, whose value
    is fed as it stands (a count; left out, the column's own index), a
    ``(begin, end)`` pair of positions, fed as their difference, or a
    tuple of such pairs, fed as the differences' sum. A value is
    multiplied by ``scale`` on its way in (1e-3 for ns into a us
    recorder). A number under 0 (``MISSING``) is a stamp never taken or a
    count never seen: the row feeds that column nothing. 0 is a reading
    like any other (a new thread's CPU clock).

    With ``ring_rows`` the rows the sampler has fed stay, the last
    ``ring_rows`` of them, for ``timeline()``; ``name`` lists the feed in
    ``feeds()``. ``worker`` and ``call`` declare which ``(begin, end)``
    pairs a reader of the timeline may take as spans: a thread of the
    program inside a stage it executes, both stamps its own; a call, step
    or write from entry to exit (docs/OBSERVABILITY.md has the tables)."""

    MISSING = -1

    def __init__(
        self, columns, stamps=None, name=None, ring_rows=0, worker=(), call=()
    ):
        columns = tuple(columns)
        self.stamps = tuple(
            stamps if stamps is not None else range(len(columns))
        )
        at = {stamp: i for i, stamp in enumerate(self.stamps)}
        self.columns = []  # (recorder, scale, begin positions, end positions)
        for i, (recorder, scale, *span) in enumerate(columns):
            span = span[0] if span else self.stamps[i]
            if not isinstance(span, tuple):
                begins, ends = [at[span]], None  # a value as it stands
            else:
                pairs = span if isinstance(span[0], tuple) else (span,)
                begins = [at[b] for b, _ in pairs]
                ends = [at[e] for _, e in pairs]
            self.columns.append((recorder, scale, begins, ends))
        self.name, self.worker, self.call = name, tuple(worker), tuple(call)
        # bounded, so a starved sampler drops the oldest rows, not memory
        self.rows: deque = deque(maxlen=1 << 16)
        self.ring = Ring(self.stamps, ring_rows) if ring_rows else None
        self._feeding = threading.Lock()  # one flush at a time: never the writer
        if name is not None:
            _feeds[name] = self
        sample_every_second(self)

    def _values(self, table: np.ndarray):
        """``(recorder, scale, values, fed)`` a column: what each row of
        ``table`` gives it, and which rows give it anything."""
        for recorder, scale, begins, ends in self.columns:
            if ends is None:
                values = table[:, begins[0]]
                fed = values >= 0
            else:
                begin, end = table[:, begins], table[:, ends]
                values = (end - begin).sum(axis=1)
                fed = ((begin >= 0) & (end >= 0)).all(axis=1)
            yield recorder, scale, values, fed

    def read(self, row) -> list:
        """What one row gives each column, unscaled, in the table's order;
        ``None`` where it gives nothing."""
        table = np.array([row], dtype=np.int64)
        return [
            int(values[0]) if fed[0] else None
            for _recorder, _scale, values, fed in self._values(table)
        ]

    def flush(self) -> None:
        """Feed every row that waits now (tests; a reader that wants the
        last rows counted), then keep them in the ring."""
        with self._feeding:
            rows = []
            try:
                while True:
                    rows.append(self.rows.popleft())
            except IndexError:
                pass
            if not rows:
                return
            table = np.array(rows, dtype=np.int64)
            for recorder, scale, values, fed in self._values(table):
                if not fed.all():
                    values = values[fed]
                if len(values):
                    recorder.record_batch(
                        len(values), int(values.sum()) * scale,
                        int(values.max()) * scale,
                        (values[::16] * scale).tolist(),
                    )
            self._fed(table)
            if self.ring is not None:
                self.ring.extend(table)

    _take_sample = flush  # what the sampler thread calls

    def _fed(self, table: np.ndarray) -> None:
        """The rows a flush has just fed, for a feed that counts beside
        its recorders (bvar/lock_probe.py's adders)."""


    def timeline(self):
        """``(stamps, rows)``: what waits is fed, then the ring's rows as
        an int64 array, oldest first, a column a position of ``stamps``.
        ``None`` for a feed that keeps no rows."""
        if self.ring is None:
            return None
        self.flush()
        return self.ring.read()


# feeds that keep their rows, by name, while their owner lives
_feeds: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def feeds() -> dict:
    """``{name: RecorderFeed}`` of the live feeds that were given a name."""
    return dict(_feeds)
