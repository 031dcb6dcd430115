"""Link (transport/device_link.py): between consecutive step dispatches of
one drive, while work stayed queued: the host time between steps. Mean of
the busiest link's ``device_link_<n>_dispatch_interval_us`` recorder over
the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "dispatch_interval_us")
