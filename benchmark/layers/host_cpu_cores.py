"""Server process: processors the chip-holding process kept busy over the
window. Gain of the program's ``device_transport_process_cpu_us``
(``time.process_time_ns``: every thread of the process, the runtime's own
included; in an ``in_process`` cell the callers too) over the window's
length. At or over 1.0 the interpreter lock can be saturated; well under it,
threads wait for something else than each other's bytecode. ``None`` on a
program from before PR 35."""


def read(run):
    gain = run.counters.get("device_transport_process_cpu_us")
    if not isinstance(gain, (int, float)) or not run.window_s:
        return None
    return gain / 1e6 / run.window_s
