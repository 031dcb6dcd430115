"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the byte adapter's host copies per call — bytes to
words, words into the zeroed bucket, ``tobytes()[:n]`` coming out. Mean of
the program's ``device_transport_copy_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.device_stage(run, "copy")
