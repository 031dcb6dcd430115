"""The program's own stage recorders and dispatch adders, as the gains over
the window that ``run.counters`` holds (``spans.counters`` snapshots every
``device_transport*`` and ``device_link*`` bvar at the window's edges). A
program that lacks a recorder, as one from before PR 25 does, reads as
``None``."""

from __future__ import annotations

import re

# the stages of a DeviceEndpoint call, in order: their means add up to the
# time inside ``call_bytes``
DEVICE_STAGES = (
    "copy", "credit_wait", "queue_wait", "stack", "launch",
    "cq_wait", "ready", "readback", "wake",
)

_RTT = re.compile(r"^device_link_(\d+)_step_rtt_us$")


def mean(run, name: str):
    """Mean of a latency recorder over the window: gain of its sum over
    gain of its count."""
    gain = run.counters.get(name)
    if not isinstance(gain, dict) or not gain.get("count"):
        return None
    return gain["sum"] / gain["count"]


def device_stage(run, stage: str):
    return mean(run, f"device_transport_{stage}_us")


def link_recorder(run, suffix: str):
    """``device_link_<n>_<suffix>`` of the link that delivered most steps
    in the window (as ``layers/link_step_rtt_us.py`` picks it)."""
    steps = {
        m.group(1): gain["count"]
        for name, gain in run.counters.items()
        if (m := _RTT.match(name)) and gain["count"]
    }
    if not steps:
        return None
    busiest = max(steps, key=steps.get)
    return mean(run, f"device_link_{busiest}_{suffix}")


def ratio(run, numerator: str, denominator: str):
    """Gain of one adder over the gain of another."""
    n, d = run.counters.get(numerator), run.counters.get(denominator)
    if n is None or not d:
        return None
    return n / d
