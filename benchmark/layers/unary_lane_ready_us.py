"""Link, the lane under unary calls: the program call returned until a
completion watcher saw the body ready on the receiver's device.
The requests' and the answers' programs alike, two a call. Mean of
``device_link_<n>_lane_ready_us`` over the window on the link with most unary
device calls in it (such a window need hold no train, so
``layers/lane_ready_us.py``, which goes by trains, finds no link); ``None`` on
a program without the unary recorders."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "lane_ready_us")
