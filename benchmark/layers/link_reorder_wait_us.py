"""Link (transport/device_link.py): a step ready until its in-order delivery
begins, per step. Mean of the busiest link's
``device_link_<n>_reorder_wait_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "reorder_wait_us")
