"""From the profiler's ``.xplane.pb`` to device numbers: which operations
ran on each chip and when, how long the chip was busy, and what the host
was doing in each gap. Read with nothing but JAX
(``jax.profiler.ProfileData``). Checked on the recorded trace in
``tests/data`` by ``tests/test_xplane.py``.

Times in a trace count from the trace's own start. A traced run drops one
host annotation, ``SYNC_MARK``, at a moment it also reads from
``time.monotonic_ns``; the difference puts device events on the clock the
generator and the handler spans use.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

SYNC_MARK = "benchmark_sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_UNSAFE = re.compile(r"[^A-Za-z0-9_.-]+")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%?(\S+) = (\(.*?\)|\S+) ")

HANDLER = "handler_inside_device_path"
CLIENT_WAIT = "client_waiting_no_handler_running"
NO_CALL = "no_call_in_flight"


def find_trace(log_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def safe_name(name: str) -> str:
    """An operation's name without spaces: for an HLO line, the
    instruction and the shape it yields (``fusion_u32_u32_1048576``)."""
    hlo = _HLO.match(_LAYOUT.sub("", name))
    if hlo:
        name = f"{hlo.group(1)}_{hlo.group(2)}"
    return re.sub("_+", "_", _UNSAFE.sub("_", name)).strip("_")[:120]


class Events:
    """The events of one line: names with starts and ends in ns."""

    def __init__(self, names, start, end):
        order = np.argsort(start, kind="stable")
        self.names = [names[i] for i in order]
        self.start = np.asarray(start, np.int64)[order]
        self.end = np.asarray(end, np.int64)[order]

    def __len__(self):
        return len(self.names)

    def clip(self, lo: int, hi: int) -> "Events":
        keep = np.nonzero((self.end > lo) & (self.start < hi))[0]
        return Events(
            [self.names[i] for i in keep],
            np.maximum(self.start[keep], lo),
            np.minimum(self.end[keep], hi),
        )

    def seconds_by_name(self) -> dict:
        out = {}
        for name, ns in zip(self.names, self.end - self.start):
            out[name] = out.get(name, 0.0) + ns / 1e9
        return out


def with_ops(modules: Events, ops: Events) -> Events:
    """The program executions in which an operation ran. A scalar staged
    with ``jnp.uint32(int)`` is a program execution of its own
    (``jit_convert_element_type``) with nothing on the operations' line;
    the step is what is left."""
    if len(ops) == 0:
        return Events([], [], [])
    nxt = np.searchsorted(ops.start, modules.start, side="left")
    inside = ops.start[np.minimum(nxt, len(ops) - 1)] < modules.end
    keep = np.nonzero((nxt < len(ops)) & inside)[0]
    return Events([modules.names[i] for i in keep],
                  modules.start[keep], modules.end[keep])


class Trace:
    """``devices[plane] = {"ops", "modules", "steps"}`` (operations,
    program executions, and those of them in which an operation ran) and
    the trace-clock time of the sync mark."""

    def __init__(self, devices: dict, sync_ns):
        self.devices = devices
        self.sync_ns = sync_ns

    def shifted(self, offset_ns: int) -> "Trace":
        return Trace(
            {
                plane: {
                    k: Events(e.names, e.start + offset_ns, e.end + offset_ns)
                    for k, e in lines.items()
                }
                for plane, lines in self.devices.items()
            },
            None if self.sync_ns is None else self.sync_ns + offset_ns,
        )


def _events(line) -> Events:
    names, start, end = [], [], []
    for e in line.events:
        names.append(e.name)
        start.append(int(e.start_ns))
        end.append(int(e.start_ns + e.duration_ns))
    return Events(names, start, end)


def read_trace(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, sync = {}, None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                ops = _events(lines[OPS_LINE])
                modules = (
                    _events(lines[MODULES_LINE])
                    if MODULES_LINE in lines else Events([], [], [])
                )
                devices[plane.name] = {
                    "ops": ops, "modules": modules,
                    "steps": with_ops(modules, ops),
                }
        elif plane.name.startswith("/host:") and sync is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_MARK:
                        sync = int(e.start_ns)
                        break
                if sync is not None:
                    break
    return Trace(devices, sync)


def union(start, end):
    """Disjoint sorted intervals covering the same instants."""
    start, end = np.asarray(start, np.int64), np.asarray(end, np.int64)
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.concatenate(([True], start[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return start[first], reach[last]


def covered(u_start, u_end, lo, hi):
    """How much of each ``[lo, hi)`` a union of disjoint intervals covers."""
    lo, hi = np.asarray(lo, np.int64), np.asarray(hi, np.int64)
    if len(u_start) == 0:
        return np.zeros(len(lo), np.int64)
    total = np.concatenate(([0], np.cumsum(u_end - u_start)))

    def upto(t):  # covered length in (-inf, t)
        i = np.searchsorted(u_start, t, side="right")
        last = np.maximum(i - 1, 0)  # the interval that starts at or before t
        inside = np.maximum(np.minimum(t, u_end[last]) - u_start[last], 0)
        return np.where(i > 0, total[last] + inside, 0)

    return upto(hi) - upto(lo)


def busy(ops: Events, lo: int, hi: int):
    """Seconds in which an operation ran between ``lo`` and ``hi``, and
    the gaps between them as ``(starts, ends)``."""
    clipped = ops.clip(lo, hi)
    u_start, u_end = union(clipped.start, clipped.end)
    busy_ns = int((u_end - u_start).sum())
    gap_start = np.concatenate(([lo], u_end))
    gap_end = np.concatenate((u_start, [hi]))
    keep = gap_end > gap_start
    return busy_ns / 1e9, (gap_start[keep], gap_end[keep])


def label_gaps(gaps, handler_spans, client_spans, top: int = 7) -> list:
    """The idle time by what the host was doing, then the longest single
    gaps named by what filled most of each: at most ``3 + top`` entries
    of ``[name, seconds]``. A handler runs inside a client's call, so
    client time outside every handler is the call minus the handler."""
    g_start, g_end = gaps
    if len(g_start) == 0:
        return []
    h = union(*handler_spans)
    c = union(*client_spans)
    in_handler = covered(*h, g_start, g_end)
    in_call = np.maximum(covered(*c, g_start, g_end), in_handler)
    parts = {
        HANDLER: in_handler,
        CLIENT_WAIT: in_call - in_handler,
        NO_CALL: (g_end - g_start) - in_call,
    }
    out = [["total:" + name, float(ns.sum()) / 1e9] for name, ns in parts.items()]
    names = list(parts)
    stacked = np.stack([parts[n] for n in names])
    for i in np.argsort(g_end - g_start)[::-1][:top]:
        out.append(
            ["one_gap:" + names[int(stacked[:, i].argmax())],
             float(g_end[i] - g_start[i]) / 1e9]
        )
    return out


def step_time(devices: dict, lo: int, hi: int) -> tuple:
    """``(executions, device ns)`` of the step programs between ``lo`` and
    ``hi``, over all the devices."""
    executions = total_ns = 0
    for lines in devices.values():
        steps = lines["steps"].clip(lo, hi)
        executions += len(steps)
        total_ns += int((steps.end - steps.start).sum())
    return executions, total_ns


def top_ops(ops_by_device: list, top: int = 10) -> list:
    """``[name, seconds]`` of the operations that took most device time,
    summed over the devices."""
    total = {}
    for ops in ops_by_device:
        for name, s in ops.seconds_by_name().items():
            total[safe_name(name)] = total.get(safe_name(name), 0.0) + s
    return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:top]]
