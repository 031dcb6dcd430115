"""Link (transport/device_link.py): the CPU time (``time.thread_time_ns``) of
``link_pump_us``'s stage: feeding a delivered train's bytes into the
sockets' messengers, on the in-order deliverer's thread. Mean of the busiest
link's ``device_link_<n>_pump_cpu_us`` recorder over the window; a program
from before PR 35 has none and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "pump_cpu_us")
