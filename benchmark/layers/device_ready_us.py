"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the watcher inside ``block_until_ready``, per
call. Mean of the program's ``device_transport_ready_us`` recorder over the
window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "ready")
