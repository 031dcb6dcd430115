"""The busiest chip's idle time, classed by the program's own spans.

The program keeps, for a bounded time, the rows of stamps its always-on
recorders are fed from (``incubator_brpc_tpu.bvar.feeds()``: a
``RecorderFeed`` with a ring a feed; ``docs/OBSERVABILITY.md`` has each
feed's table). The stamps are ``time.monotonic_ns()``, the clock of the
generator's records and, through ``xplane.SYNC_MARK``, of the device
trace, and the process that runs ``run.py`` holds the chip and the
server, so the rings are read here, in-process, once the window is over.
Each feed declares two kinds of span:

- **worker spans**: a thread of the program is inside a stage it
  executes, begin and end stamped by that thread (a dispatch's stack and
  launch, a watcher's wait and readback, the link's launch, readback and
  pump, a fused call's stages, a stream's handler batch);
- **call spans**: a call, step, parked ``send``, ``write`` or fused call
  from entry to exit.

Every instant in which the busiest chip ran no operation is then one of
three: a worker span is open (``worker_open``); none is, but a call span
is: work is inside the layer and no thread of the layer is on it
(``waiting_only``); neither (``outside``: the host plane, the client, the
generator). The three add up to the idle time.

What a reader may take from this file:

- ``idle_shares(run)``: ``{"worker_open", "waiting_only", "outside"}`` in
  per cent of the busiest chip's idle time, or ``None`` where the program
  keeps no rows (a parent from before PR 35), no feed has a row in the
  window or the trace has no device with work. Computed once a run and
  kept on ``run``; the first call also prints the longest gaps
  (``describe_gaps``) on a line of their own each.
- ``spans(t_open, t_close)``: ``{"worker": [...], "call": [...]}``, each
  entry ``(label, starts, ends)`` clipped to the window, label
  ``<feed>:<begin>-><end>``; ``None`` on a program without feeds.
- ``classify(gaps, worker, call)`` and ``describe_gaps(gaps, worker, call,
  passes, t_open)``: the arithmetic, on plain arrays (the tests' way in).
"""

from __future__ import annotations

import numpy as np

from benchmark import xplane

LONGEST = 7  # gaps named one by one
NAMED = 4  # spans named in a gap, by the time each covers of it


def spans(t_open: int, t_close: int):
    """The program's worker and call spans that touch the window."""
    from incubator_brpc_tpu import bvar

    if not hasattr(bvar, "feeds"):
        return None
    out = {"worker": [], "call": []}
    for name, feed in sorted(bvar.feeds().items()):
        timeline = feed.timeline()
        if timeline is None:
            continue
        stamps, rows = timeline
        at = {stamp: i for i, stamp in enumerate(stamps)}
        for kind in out:
            for begin, end in getattr(feed, kind):
                start, stop = rows[:, at[begin]], rows[:, at[end]]
                keep = (start >= 0) & (stop > start) & (stop > t_open) & (start < t_close)
                if keep.any():
                    out[kind].append((
                        f"{name}:{begin}->{end}",
                        np.maximum(start[keep], t_open),
                        np.minimum(stop[keep], t_close),
                    ))
    return out


def _union(entries):
    if not entries:
        return xplane.union([], [])
    return xplane.union(
        np.concatenate([s for _l, s, _e in entries]),
        np.concatenate([e for _l, _s, e in entries]),
    )


def classify(gaps, worker, call) -> dict:
    """Nanoseconds of ``gaps`` (``(starts, ends)``) under each of the three
    classes, from lists of ``(label, starts, ends)``."""
    g_start, g_end = gaps
    in_worker = xplane.covered(*_union(worker), g_start, g_end)
    in_any = xplane.covered(*_union(worker + call), g_start, g_end)
    idle = g_end - g_start
    return {
        "worker_open": int(in_worker.sum()),
        "waiting_only": int((in_any - in_worker).sum()),
        "outside": int((idle - in_any).sum()),
    }


def describe_gaps(gaps, worker, call, passes, t_open: int) -> list:
    """One line a gap for the ``LONGEST`` gaps: how long, when, the spans
    open in it by how much of it each covers, and the sampler passes
    (``(begins, ends)``) that fell inside."""
    g_start, g_end = gaps
    lines = []
    unions = [(label, xplane.union(s, e)) for label, s, e in worker + call]
    for i in np.argsort(g_end - g_start)[::-1][:LONGEST]:
        lo, hi = g_start[i : i + 1], g_end[i : i + 1]
        inside = sorted(
            ((int(xplane.covered(*u, lo, hi)[0]), label) for label, u in unions),
            reverse=True,
        )
        named = [f"{label} {ns / 1e9:.6f} s" for ns, label in inside[:NAMED] if ns]
        p_begin, p_end = passes
        hit = (p_end > lo[0]) & (p_begin < hi[0])
        sampler = (
            "; sampler pass " + ", ".join(
                f"{(e - b) / 1e9:.6f} s at +{(b - t_open) / 1e9:.3f}"
                for b, e in zip(p_begin[hit], p_end[hit]))
            if hit.any() else "; no sampler pass inside"
        )
        lines.append(
            f"idle gap {(hi[0] - lo[0]) / 1e9:.6f} s at +{(lo[0] - t_open) / 1e9:.3f} s:"
            f" open {', '.join(named) if named else 'no span of the program'}"
            + sampler
        )
    return lines


def _sampler_passes():
    from incubator_brpc_tpu import bvar

    names, rows = bvar.sampler_passes()
    return rows[:, names.index("begin")], rows[:, names.index("end")]


def idle_shares(run):
    """Per cent of the busiest chip's idle time under each class; ``None``
    where there is nothing to read. Kept on ``run`` after the first call."""
    if hasattr(run, "idle_shares"):
        return run.idle_shares
    run.idle_shares = None
    found = spans(run.t_open, run.t_close) if run.devices else None
    if not found or not (found["worker"] or found["call"]):
        return None
    busiest = max(
        (xplane.busy(d["ops"], run.t_open, run.t_close) for d in run.devices.values()),
        key=lambda busy_gaps: busy_gaps[0],
    )[1]
    ns = classify(busiest, found["worker"], found["call"])
    idle = sum(ns.values())
    if not idle:
        return None
    for line in describe_gaps(
            busiest, found["worker"], found["call"], _sampler_passes(), run.t_open):
        print(line, flush=True)
    run.idle_shares = {kind: 100.0 * part / idle for kind, part in ns.items()}
    return run.idle_shares
