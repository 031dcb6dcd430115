"""Link (transport/device_link.py): the staging gather of queued bytes into
one slot row (twice a step: both sides). Mean of the busiest link's
``device_link_<n>_flush_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "flush_us")
