"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): rows stacked until ``device_put`` and the program
call have returned, per call. Mean of the program's
``device_transport_launch_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "launch")
