"""The interpreter lock's probe: a thread that does nothing but ask for it.

Every layer's stage recorder prices its stage, and none the lock the stage
waits on (PERF.md §6, PR 27-49). One daemon thread a process, started
with the sampler thread (a process that measures itself measures its
lock), ticks every ``PERIOD_NS`` with a seeded jitter of half a period
either way, so that it beats with no 5 ms switch interval and no 1 Hz
pass. A tick is one call of ``tb_sleep_until_ns`` through ``native.LIB``,
the handle that lets the lock go: the library sleeps to the tick's
absolute due time on ``CLOCK_MONOTONIC`` and stamps ``woken`` before it
returns, that is before ``ctypes`` queues for the lock; back in Python the
thread stamps ``running`` and appends ``(due, woken, running)`` to a
``RecorderFeed``. All three are on ``time.monotonic_ns()``'s clock, the
clock of every other feed, of the benchmark's records and of the device
trace.

- ``woken - due`` is **the machine's lateness**: the kernel woke the
  thread that late (a host that took the processor, a process stopped).
- ``running - woken`` is **the wait for the interpreter**: what any thread
  of this process that came back from native code at that instant would
  have waited.

The sampler feeds ``device_transport_lock_wait_us`` and
``device_transport_machine_late_us`` from the rows and counts the ticks:
``device_transport_lock_probes``; ``..._lock_busy``, those that waited over
``BUSY_NS`` (the lock was in another thread's hand when asked);
``..._lock_forced``, those that waited the interpreter's switch interval
out (the holder gave the lock up only because it was made to);
``..._lock_stall_us``, the sum of the waits over ``STALL_NS``. The feed is
``interpreter_lock`` in ``bvar.feeds()`` and keeps its rows, but declares
no ``worker`` and no ``call`` span: a probe's wait is no stage of a layer.

Once a second the probe reads the processors by thread
(bvar/processors.py) and keeps the readings as long as the ring keeps
ticks (``readings()``). On a tick that waited over ``STALL_NS`` it reads
them again and names, in one line of the log and in ``stalls()``, the
thread of the interpreter whose on-processor time grew most between the
two readings, with that thread's current frame: the silence of an untraced
run leaves the thread and the line in that run's own output. Without the
native library there is no probe.
"""

from __future__ import annotations

import logging
import random
import sys
import threading
from collections import deque
from time import monotonic_ns
from typing import Optional

from incubator_brpc_tpu import native
from incubator_brpc_tpu.bvar import processors
from incubator_brpc_tpu.bvar.recorder import LatencyRecorder, RecorderFeed
from incubator_brpc_tpu.bvar.reducer import Adder

logger = logging.getLogger(__name__)

# 100 ticks a second, ~1,950 a 20 s window (a reading a second takes a slot
# or two). Six same-seed pairs against a copy whose probe never starts read
# +0.15% of echo_256b_c16's calls/s and -0.26% of the native cell's at the
# median, three and four pairs of six the probe's way: nothing to see at
# 10 ms, so the period was not lengthened (PERF.md, PR 51)
PERIOD_NS = 10_000_000
# A wait over this found the lock in another thread's hand. An idle process
# on the chip's host waits 4.3 us at the median, 16.7 at the 99th per cent
# and 0.96 ms once in a thousand ticks; beside sixteen threads that pass
# the lock around, 1.9 ms at the median (PERF.md, PR 51): three times the
# idle 99th per cent
BUSY_NS = 50_000
STALL_NS = 100_000_000  # a wait that is a silence: named, logged, summed
RING_ROWS = 1 << 15  # 5.4 minutes of ticks
READ_EVERY_NS = 1_000_000_000  # the processors by thread, between stalls
READINGS_KEPT = RING_ROWS * PERIOD_NS // READ_EVERY_NS + 1
STALLS_KEPT = 64
FRAMES_NAMED = 4  # of the holder's stack, from the innermost out
LOG_EVERY_NS = 10_000_000_000  # a stall's line in the log, at most


class _Ticks(RecorderFeed):
    """The probe's rows: two recorders fed as any feed's, and four adders
    that count what no mean shows."""

    def __init__(self):
        self.probes = Adder(name="device_transport_lock_probes")
        self.busy = Adder(name="device_transport_lock_busy")
        self.forced = Adder(name="device_transport_lock_forced")
        self.stall_us = Adder(name="device_transport_lock_stall_us")
        super().__init__(
            [
                (LatencyRecorder(name="device_transport_lock_wait_us"),
                 1e-3, ("woken", "running")),
                (LatencyRecorder(name="device_transport_machine_late_us"),
                 1e-3, ("due", "woken")),
            ],
            stamps=("due", "woken", "running"),
            name="interpreter_lock",
            ring_rows=RING_ROWS,
        )

    def _fed(self, table) -> None:
        wait = table[:, 2] - table[:, 1]
        self.probes << len(wait)
        self.busy << int((wait > BUSY_NS).sum())
        self.forced << int((wait >= sys.getswitchinterval() * 1e9).sum())
        self.stall_us << int(wait[wait > STALL_NS].sum()) // 1000


class LockProbe:
    """The probe's thread, its feed, and what it keeps for a reader: the
    readings of the processors by thread and the stalls it named."""

    def __init__(self):
        self.feed = _Ticks()
        self._last: Optional[processors.Reading] = None
        self._readings: deque = deque(maxlen=READINGS_KEPT)  # readings() serves it
        self._stalls: deque = deque(maxlen=STALLS_KEPT)  # stalls() serves it
        self._logged_ns = -LOG_EVERY_NS
        self._unlogged = 0
        self._thread = threading.Thread(
            target=self._run, name="bvar_lock_probe", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        sleep_until = native.LIB.tb_sleep_until_ns
        append = self.feed.rows.append
        jitter, half = random.Random(51).randrange, PERIOD_NS // 2
        slot = read_at = 0  # the next tick's slot; the next reading's time
        while True:
            slot += PERIOD_NS
            now = monotonic_ns()
            if slot - half < now:
                # a stall or a reading took the slots it lasted: never a
                # burst, and never a due time that has passed, which would
                # read as the machine's lateness
                slot = now + PERIOD_NS
            due = slot + jitter(-half, half)
            woken = sleep_until(due)
            running = monotonic_ns()
            append((due, woken, running))
            if running - woken > STALL_NS:
                self._stalled(due, woken, running)
            elif running >= read_at:
                self.remember()
                read_at = running + READ_EVERY_NS

    def remember(self) -> Optional[processors.Reading]:
        """Read the processors by thread and keep the reading."""
        reading = processors.TABLE.read()
        if reading is not None:
            self._last = reading
            self._readings.append((reading.at, reading.by_name()))
        return reading

    def _stalled(self, due: int, woken: int, running: int) -> None:
        """Name the holder of the stall that just ended: the frames first,
        before any thread moves on, then the processors again."""
        try:
            frames = sys._current_frames()
            before = self._last
            stall = _name_holder(
                before, self.remember(), frames, self._thread.native_id)
            stall.update(woken_ns=woken, wait_ns=running - woken, late_ns=woken - due)
            stall["text"] = _stall_text(stall)
            self._stalls.append(stall)
            if running - self._logged_ns < LOG_EVERY_NS:
                self._unlogged += 1
                return
            more = f" ({self._unlogged} more since the last line)" if self._unlogged else ""
            self._logged_ns, self._unlogged = running, 0
            logger.warning("%s%s", stall["text"], more)
        except Exception:  # the probe outlives whatever a reading meets
            logger.exception("interpreter lock probe: a stall could not be named")


def _name_holder(before, after, frames: dict, own_tid: int) -> dict:
    """Who held the lock, from two readings of the processors around the
    stall: the thread of the interpreter (only such a thread can hold its
    lock) whose on-processor time grew most, and how much every task of
    the process ran."""
    stall = {
        "holder": None, "holder_tid": None, "holder_cpu_ns": None,
        "process_cpu_ns": None, "between_ns": None, "frames": None,
    }
    if before is None or after is None:
        return stall
    grew = {
        tid: task.cpu_ns - (before.tasks[tid].cpu_ns if tid in before.tasks else 0)
        for tid, task in after.tasks.items() if tid != own_tid
    }
    stall["between_ns"] = after.at - before.at
    stall["process_cpu_ns"] = sum(grew.values())
    known = [tid for tid in grew if after.tasks[tid].known]
    if not known:
        return stall
    tid = max(known, key=grew.get)
    stall.update(holder=after.tasks[tid].name, holder_tid=tid, holder_cpu_ns=grew[tid])
    ident = next(
        (t.ident for t in threading.enumerate() if t.native_id == tid), None
    )
    frame, stack = frames.get(ident), []
    while frame is not None and len(stack) < FRAMES_NAMED:
        stack.append((frame.f_code.co_filename, frame.f_code.co_name, frame.f_lineno))
        frame = frame.f_back
    stall["frames"] = stack or None
    return stall


def _ms(ns) -> str:
    return f"{ns / 1e6:.1f} ms"


def _stall_text(stall: dict) -> str:
    """``interpreter lock stall: waited <ms> from +<s> s (machine late
    <ms>); <who>``, where who is ``thread '<name>' (tid <n>) was on a
    processor <ms> of the <ms> between readings, now at <file>:<line> in
    <function> < <its caller's> ...``, or that no thread of the interpreter was on a processor
    for a tenth of the wait, with what the process's tasks ran: a holder
    that slept with the lock and a process off the processor look alike
    from inside (a machine that was late waking the probe itself shows in
    ``machine late``)."""
    head = (
        f"interpreter lock stall: waited {_ms(stall['wait_ns'])} from "
        f"+{stall['woken_ns'] / 1e9:.3f} s (machine late {_ms(stall['late_ns'])}); "
    )
    if stall["between_ns"] is None:
        return head + "no two readings of the processors to name a holder from"
    between = f"of the {_ms(stall['between_ns'])} between readings"
    if stall["holder"] is not None and stall["holder_cpu_ns"] >= stall["wait_ns"] // 10:
        where = (
            ", now at " + " < ".join(
                f"{file}:{line} in {function}" for file, function, line in stall["frames"])
            if stall["frames"] else ", no frame of its own now"
        )
        return head + (
            f"thread {stall['holder']!r} (tid {stall['holder_tid']}) was on a "
            f"processor {_ms(stall['holder_cpu_ns'])} {between}{where}"
        )
    return head + (
        f"no thread of the interpreter was on a processor for a tenth of it: "
        f"the holder slept with the lock, or the process was off the "
        f"processor; its tasks ran {_ms(stall['process_cpu_ns'])} {between}"
    )


_probe: Optional[LockProbe] = None


def start() -> None:
    """Start the process's probe (the sampler thread's first registration
    calls this once); nothing without the native library."""
    global _probe
    if _probe is None and native.LIB is not None:
        _probe = LockProbe()
        _probe.start()


def stalls() -> list:
    """The stalls the probe named, oldest first, at most ``STALLS_KEPT``:
    dicts of ``woken_ns`` (``time.monotonic_ns()``), ``wait_ns``,
    ``late_ns``, ``holder`` (a thread's name or ``None``), ``holder_tid``,
    ``holder_cpu_ns``, ``process_cpu_ns``, ``between_ns``, ``frames``
    (``[(file, function, line)]``, the innermost first, or ``None``) and
    ``text``, the log's line."""
    return [] if _probe is None else list(_probe._stalls)


def readings() -> list:
    """``(monotonic_ns, {name: (cpu_ns, runq_ns, tasks)})`` of the probe's
    once-a-second readings of the processors by thread, oldest first."""
    return [] if _probe is None else list(_probe._readings)
