"""Link (transport/device_link.py): slots filled until ``_make_slots``
(device placement) and the step call have returned, per step. Mean of the
busiest link's ``device_link_<n>_launch_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "launch_us")
