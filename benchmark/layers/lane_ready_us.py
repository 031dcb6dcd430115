"""Link, the lane (transport/device_link.py): the program call returned until a completion watcher saw the
receiver's shard ready. Mean of the busiest
link's ``device_link_<n>_lane_ready_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_ready_us")
