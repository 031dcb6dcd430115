"""The interpreter lock and the processors, beside the busiest chip's gaps.

``benchmark/timeline.py`` classes the chip's idle time by the program's
spans and names its seven longest gaps by the spans open in each. The
program's lock probe (``incubator_brpc_tpu/bvar/lock_probe.py``: a thread
that asks for the interpreter lock every ~10 ms and stamps when it was
due, when the machine woke it and when the interpreter let it run, on
``time.monotonic_ns()``, the timeline's clock) declares no span, so it
classes nothing; this file sets its ticks beside the same seven gaps and
prints **one more line a gap**: the ticks that fell inside, the longest
wait for the lock and the longest lateness of the machine among them, and
one verdict:

- ``lock held throughout``: every tick inside waited over the probe's
  ``BUSY_NS``: some thread had the interpreter all through the gap;
- ``lock free``: none did: every thread of the process was in native code
  or parked, so look in the runtime;
- ``lock held at k of n ticks``: both;
- ``machine late``: a tick was woken later than half the gap is long: the
  host, not the program;
- ``no tick inside``: the gap is shorter than the probe's period;

with the line of any stall the probe named inside (``lock_probe.stalls()``).
And one line for the window: the processors by thread name, from the
probe's once-a-second readings nearest the window's edges
(``lock_probe.readings()``).

What a reader may take from this file: ``report(run)``, which prints those
lines once a traced run and returns them (kept on ``run.lock_report``;
``None`` on a program without the probe, without the native library, or
with no device plane: the parent's tree prints none); ``describe_gaps`` and
``describe_threads``, the arithmetic on plain arrays (the tests' way in).
"""

from __future__ import annotations

import numpy as np

from benchmark import xplane

LONGEST = 7  # gaps named one by one, as timeline.LONGEST
NAMED = 10  # thread names a window's line spells out


def describe_gaps(gaps, ticks, stalls, busy_ns: int, t_open: int) -> list:
    """One line a gap for the ``LONGEST`` gaps of ``gaps`` (``(starts,
    ends)``), from ``ticks`` (``(due, woken, running)`` arrays) and
    ``stalls`` (``[(woken_ns, wait_ns, text)]``)."""
    g_start, g_end = gaps
    due, woken, running = ticks
    lines = []
    for i in np.argsort(g_end - g_start)[::-1][:LONGEST]:
        lo, hi = int(g_start[i]), int(g_end[i])
        inside = (running >= lo) & (due <= hi)
        wait, late = (running - woken)[inside], (woken - due)[inside]
        waited = int((wait > busy_ns).sum())
        if not inside.any():
            verdict = "no tick inside"
        elif late.max() >= (hi - lo) / 2:
            verdict = "machine late"
        elif waited == len(wait):
            verdict = "lock held throughout"
        elif not waited:
            verdict = ("lock free (every thread of the process in native code "
                       "or parked: look in the runtime)")
        else:
            verdict = f"lock held at {waited} of {len(wait)} ticks"
        numbers = (
            f" {len(wait)} ticks, longest wait {wait.max() / 1e3:.1f} us, "
            f"longest lateness {late.max() / 1e3:.1f} us:" if inside.any() else ""
        )
        named = "".join(
            f"; {text}" for at, lasted, text in stalls
            if at + lasted >= lo and at <= hi
        )
        lines.append(
            f"lock in gap {(hi - lo) / 1e9:.6f} s at +{(lo - t_open) / 1e9:.3f} s:"
            f"{numbers} {verdict}{named}"
        )
    return lines


def describe_threads(readings, t_open: int, t_close: int):
    """One line: processors each thread name kept busy between the two of
    ``readings`` (``[(monotonic_ns, {name: (cpu_ns, runq_ns, tasks)})]``)
    nearest the window's edges, and of its runnable time the share it
    waited for a processor; ``None`` with fewer than two readings."""
    if len(readings) < 2:
        return None
    first = min(readings, key=lambda r: abs(r[0] - t_open))
    last = min(readings, key=lambda r: abs(r[0] - t_close))
    seconds = (last[0] - first[0]) / 1e9
    if seconds <= 0:
        return None
    rows = []
    for name, (cpu_ns, runq_ns, _tasks) in last[1].items():
        cpu_0, runq_0, _n = first[1].get(name, (0, 0, 0))
        cpu, runq = cpu_ns - cpu_0, runq_ns - runq_0
        if cpu > 0:  # under 0: tasks of that name have ended since
            rows.append((cpu / 1e9 / seconds, 100.0 * runq / (cpu + runq), name))
    rows.sort(reverse=True)
    named = ", ".join(
        f"{name} {cores:.3f} (runq {runq:.1f}%)" for cores, runq, name in rows[:NAMED]
    )
    rest = rows[NAMED:]
    if rest:
        named += f", {len(rest)} more names {sum(r[0] for r in rest):.3f}"
    return (
        f"processors by thread over {seconds:.2f} s: {named}; "
        f"in all {sum(r[0] for r in rows):.3f}"
    )


def _probe():
    """``(the program's probe module, its feed)``, or ``None`` on a program
    without the probe or a process in which it never started."""
    try:
        from incubator_brpc_tpu import bvar
        from incubator_brpc_tpu.bvar import lock_probe
    except ImportError:
        return None
    feed = bvar.feeds().get("interpreter_lock")
    return None if feed is None else (lock_probe, feed)


def report(run):
    """Print the gap lines and the window's line, once a run; the lines, or
    ``None`` where there is nothing to read. Kept on ``run``."""
    if hasattr(run, "lock_report"):
        return run.lock_report
    run.lock_report = None
    found = _probe() if getattr(run, "devices", None) else None
    if found is None:
        return None
    probe, feed = found
    stamps, rows = feed.timeline()
    if not len(rows):
        return None
    ticks = tuple(rows[:, stamps.index(s)] for s in ("due", "woken", "running"))
    busiest = max(
        (xplane.busy(d["ops"], run.t_open, run.t_close) for d in run.devices.values()),
        key=lambda busy_gaps: busy_gaps[0],
    )[1]
    stalls = [(s["woken_ns"], s["wait_ns"], s["text"]) for s in probe.stalls()]
    lines = describe_gaps(busiest, ticks, stalls, probe.BUSY_NS, run.t_open)
    threads = describe_threads(probe.readings(), run.t_open, run.t_close)
    if threads is not None:
        lines.append(threads)
    for line in lines:
        print(line, flush=True)
    run.lock_report = lines
    return lines
