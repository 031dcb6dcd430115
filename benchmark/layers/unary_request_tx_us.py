"""Link, a unary call with a device attachment (rpc/channel.py):
``call_method`` entered until the request's ``lane_send`` returned, on the
caller's thread: the call id, the socket, the frame packed as the array's
tag and the lane program's launch (``lane_launch_us`` is a part of it).
Mean of the busiest link's ``device_link_<n>_unary_request_tx_us`` recorder
over the window, a row a call; ``None`` on a program without it."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "unary_request_tx_us")
