"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): enqueued until taken into a batch, per call (holds
the spawn of the drain or ``-tx`` thread). Mean of the program's
``device_transport_queue_wait_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "queue_wait")
