"""Link (transport/device_link.py): feeding a delivered step's bytes into
the sockets' messengers, per step. Mean of the busiest link's
``device_link_<n>_pump_us`` recorder over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "pump_us")
