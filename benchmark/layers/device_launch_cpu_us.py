"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the CPU time (``time.thread_time_ns``) of
``device_launch_us``'s stage: rows stacked until the program call, which
stages the host rows itself, has returned, on the drain or ``-tx`` thread,
once a dispatch and credited to each of its calls. Mean of the program's
``device_transport_launch_cpu_us`` recorder over the window; the wall mean
less this is time that thread was off the processor. A program from before
PR 35 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "launch_cpu")
