"""From per-call records to the end-to-end metrics. Every number is taken
over all the calls and all the time of the window: a rate counts the
correct calls that completed inside it over its whole length, a
percentile is over all of those calls."""

from __future__ import annotations

import numpy as np

from benchmark.generator import DUE_NS, END_NS, OK, SEND_NS, SIZE, STATUS

# a 99th percentile is reported only with ten samples beyond it
P99_MIN_CALLS = 1000


def in_window(table: np.ndarray, t_open: int, seconds: float) -> np.ndarray:
    """Correct calls that completed between the opening and the close."""
    close = t_open + int(seconds * 1e9)
    keep = (table[:, STATUS] == OK) & (table[:, END_NS] <= close)
    return table[keep]


def latencies_us(done: np.ndarray) -> np.ndarray:
    """Client-side call times, from when each call was due."""
    return (done[:, END_NS] - done[:, DUE_NS]) / 1e3


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: a value that was observed."""
    ordered = np.sort(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(ordered))))
    return float(ordered[rank - 1])


def end_to_end(table: np.ndarray, t_open: int, seconds: float) -> dict:
    done = in_window(table, t_open, seconds)
    out = {}
    if len(done) == 0:
        return out
    lat = latencies_us(done)
    out["call_rate"] = len(done) / seconds
    out["goodput"] = float(done[:, SIZE].sum()) / seconds / 1e9
    out["latency_p50_us"] = percentile(lat, 50)
    if len(done) >= P99_MIN_CALLS:
        out["latency_p99_us"] = percentile(lat, 99)
    return out


def per_second(table: np.ndarray, t_open: int, seconds: float) -> list:
    """Correct completions in each whole second of the window."""
    done = in_window(table, t_open, seconds)
    slot = (done[:, END_NS] - t_open) // 1_000_000_000
    return np.bincount(slot, minlength=int(seconds))[: int(seconds)].tolist()


def longest_silence_s(table: np.ndarray, t_open: int, seconds: float) -> float:
    """The longest stretch of the window in which no call completed: a
    stall of the served path shows here before it shows in a median."""
    close = t_open + int(seconds * 1e9)
    ends = np.sort(table[:, END_NS])
    ends = ends[(ends >= t_open) & (ends <= close)]
    marks = np.concatenate(([t_open], ends, [close]))
    return float(np.diff(marks).max() / 1e9)


def lateness_us(table: np.ndarray) -> float:
    """Open loops: how late the generator sent the median call."""
    if len(table) == 0:
        return 0.0
    return float(np.median(table[:, SEND_NS] - table[:, DUE_NS]) / 1e3)
