"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the CPU time (``time.thread_time_ns``) of
``device_readback_us``'s stage: ``block_until_ready`` returned until
``device_get`` has, on the completion watcher. Mean of the program's
``device_transport_readback_cpu_us`` recorder over the window; the wall mean
less this is time that thread was off the processor. A program from before
PR 35 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "readback_cpu")
