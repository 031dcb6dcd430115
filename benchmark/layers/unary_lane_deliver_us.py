"""Link, the lane under unary calls: its turn come until the receiving
socket was handed it: the tag cut as a frame, an answer matched to its call
and the call ended, a request handed to a worker.
The requests' and the answers' programs alike, two a call. Mean of
``device_link_<n>_lane_deliver_us`` over the window on the link with most unary
device calls in it (such a window need hold no train, so
``layers/lane_deliver_us.py``, which goes by trains, finds no link); ``None`` on
a program without the unary recorders."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "lane_deliver_us")
