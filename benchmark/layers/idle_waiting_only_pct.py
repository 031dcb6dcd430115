"""Device: the share of the busiest chip's idle time with no worker span open
and at least one call span open: work is inside the layer and no thread of
the layer is on it (a hand-over not yet taken, the lock held elsewhere).
From the program's own timelines (``benchmark/timeline.py``); the three
``idle_*_pct`` add up to 100. ``None`` on a program that keeps no rows."""
from benchmark import timeline


def read(run):
    shares = timeline.idle_shares(run)
    return None if shares is None else shares["waiting_only"]
