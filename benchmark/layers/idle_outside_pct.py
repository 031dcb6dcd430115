"""Device: the share of the busiest chip's idle time with neither a worker nor
a call span open: the time is outside every layer that has spans (host
plane, client, generator). From the program's own timelines
(``benchmark/timeline.py``); the three ``idle_*_pct`` add up to 100.
``None`` on a program that keeps no rows."""
from benchmark import timeline


def read(run):
    shares = timeline.idle_shares(run)
    return None if shares is None else shares["outside"]
