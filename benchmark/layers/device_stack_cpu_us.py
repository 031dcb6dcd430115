"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the CPU time (``time.thread_time_ns``) of
``device_stack_us``'s stage: batch taken until its rows are stacked into one
array, on the drain or ``-tx`` thread, once a dispatch and credited to each
of its calls as the wall stage is. Mean of the program's
``device_transport_stack_cpu_us`` recorder over the window; the wall mean
less this is time that thread was off the processor. A program from before
PR 35 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "stack_cpu")
