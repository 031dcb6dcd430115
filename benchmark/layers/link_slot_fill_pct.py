"""Link (transport/device_link.py): how full the slots travel — payload
bytes packed (``device_link_bytes``) over the payload capacity of every slot
side filled (``device_link_capacity_bytes``)."""
from benchmark import stages


def read(run):
    share = stages.ratio(run, "device_link_bytes", "device_link_capacity_bytes")
    return None if share is None else 100.0 * share
