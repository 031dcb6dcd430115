"""Link (transport/device_link.py): ``watch()`` until ``block_until_ready``
returned on a watcher thread, per step. Mean of the busiest link's
``device_link_<n>_ready_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "ready_us")
