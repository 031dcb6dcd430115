"""The benchmark's own spans and counter snapshots, taken around the calls
into each layer. Spans inside the program are a later PR's; a traced run
wraps the server's handler here, an untraced run leaves it bare."""

from __future__ import annotations

import time


class HandlerSpans:
    """``(t_in_ns, t_out_ns)`` of every handler call, on the clock the
    generator stamps its calls with (``CLOCK_MONOTONIC`` is one clock for
    every process of the machine)."""

    def __init__(self):
        self.rows = []

    def wrap(self, handler):
        rows = self.rows

        def timed(cntl, request):
            t0 = time.monotonic_ns()
            try:
                return handler(cntl, request)
            finally:
                rows.append((t0, time.monotonic_ns()))

        return timed


COUNTER_PREFIXES = ("device_link", "device_transport")


def counters() -> dict:
    """The program's bvars by exposed name: a plain number for an adder,
    ``{"count", "sum"}`` for a latency recorder."""
    from incubator_brpc_tpu.bvar import LatencyRecorder, expose_registry

    out = {}
    for prefix in COUNTER_PREFIXES:
        for name, var in expose_registry.snapshot(prefix):
            if isinstance(var, LatencyRecorder):
                out[name] = {"count": var.count(), "sum": var.latency_sum()}
            else:
                value = var.get_value()
                if isinstance(value, (int, float)):
                    out[name] = value
    return out


def delta(before: dict, after: dict) -> dict:
    """What each counter gained between two snapshots."""
    out = {}
    for name, b in after.items():
        a = before.get(name)
        if isinstance(b, dict):
            a = a or {"count": 0, "sum": 0}
            out[name] = {k: b[k] - a[k] for k in b}
        else:
            out[name] = b - (a or 0)
    return out
