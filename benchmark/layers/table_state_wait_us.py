"""Host to HBM crossing and completion (transport/device.py): a dispatch
ready to launch until the service's state was in its hand, i.e. the wait
for the dispatch before it to hand the table on; a part of
``device_launch_us``. Mean of the program's
``device_transport_state_wait_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_state_wait_us")
