"""Link (transport/device_link.py): the CPU time (``time.thread_time_ns``) of
``link_readback_us``'s stage: ``_rows_to_host`` of a delivered train, on the
in-order deliverer's thread. Mean of the busiest link's
``device_link_<n>_readback_cpu_us`` recorder over the window; a program from
before PR 35 has none and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "readback_cpu_us")
