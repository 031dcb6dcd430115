"""Server process (bvar/lock_probe.py): the machine's lateness. Mean over
the window of the program's ``device_transport_machine_late_us``: the
probe's absolute due time on ``CLOCK_MONOTONIC`` to the instant the kernel
woke it, both read in native code with no lock of the program in the way:
a host that took the processor, a process stopped. ``None`` on a program
without the probe."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_machine_late_us")
