"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): ``device_get`` and ``np.asarray`` of the response,
per call. Mean of the program's ``device_transport_readback_us`` recorder
over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "readback")
