"""Link, the lane (transport/device_link.py): body and header paired until the array was queued for the
stream's consumer (or parked behind an earlier message of its stream). Mean of the busiest
link's ``device_link_<n>_lane_deliver_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_deliver_us")
