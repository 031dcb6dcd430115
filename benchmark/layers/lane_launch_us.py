"""Link, the lane (transport/device_link.py): the message taken by ``lane_send`` until the program call
has returned, on the writer's thread: one call into the runtime, no host copy. Mean of the busiest
link's ``device_link_<n>_lane_launch_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_launch_us")
