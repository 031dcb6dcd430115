"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): readback done until the caller runs again (parse,
settle, credit released, the butex wake), per call. Mean of the program's
``device_transport_wake_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "wake")
