"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): ``watch()`` until a completion-watcher thread
starts the job, per call (the job queued behind the watcher pool). Mean of
the program's ``device_transport_cq_wait_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "cq_wait")
