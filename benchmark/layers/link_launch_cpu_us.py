"""Link (transport/device_link.py): the CPU time (``time.thread_time_ns``) of
``link_launch_us``'s stage: slots filled until ``_make_slots`` and the step
call have returned, on the drive's thread. Mean of the busiest link's
``device_link_<n>_launch_cpu_us`` recorder over the window; a program from
before PR 35 has none and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "launch_cpu_us")
