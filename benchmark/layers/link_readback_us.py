"""Link (transport/device_link.py): ``_rows_to_host`` of a delivered step.
Mean of the busiest link's ``device_link_<n>_readback_us`` recorder over the
window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "readback_us")
