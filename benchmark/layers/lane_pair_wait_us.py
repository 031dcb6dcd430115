"""Link, the lane (transport/device_link.py): the wait of whichever came first, the body seen ready or
its header frame cut off the byte stream, for the other. Mean of the busiest
link's ``device_link_<n>_lane_pair_wait_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_pair_wait_us")
