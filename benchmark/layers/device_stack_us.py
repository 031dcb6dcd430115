"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): batch taken until its rows are stacked into one
array (``np.zeros`` and the row copies), per call. Mean of the program's
``device_transport_stack_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "stack")
