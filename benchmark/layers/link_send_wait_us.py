"""Link (transport/device_link.py): a ``DeviceLink.send`` parked because
the side's backlog was over the budget (``window * slot_bytes``), 0 where
it was admitted at once. Mean of the busiest link's
``device_link_<n>_send_wait_us`` over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "send_wait_us")
