"""Link, the lane (transport/device_link.py): a lane program end to end, the sum of its four stages: the
message taken by ``lane_send`` until its array was queued for the stream's
consumer. Mean of the busiest
link's ``device_link_<n>_lane_step_us`` recorder over the window, a row a
paired lane program; ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "lane_step_us")
