"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): time in ``_acquire_credit`` per call (the window
of in-flight calls was full). Mean of the program's
``device_transport_credit_wait_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "credit_wait")
