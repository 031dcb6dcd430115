"""Link, the lane under unary calls: the message taken by ``lane_send``
until the program call has returned, on the sender's thread (the caller's
for a request, the handler's worker for an answer).
The requests' and the answers' programs alike, two a call. Mean of
``device_link_<n>_lane_launch_us`` over the window on the link with most unary
device calls in it (such a window need hold no train, so
``layers/lane_launch_us.py``, which goes by trains, finds no link); ``None`` on
a program without the unary recorders."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "lane_launch_us")
