"""Link, the lane under unary calls: the body seen ready until its tag was
in hand on the host and its turn in the lane's order had come.
The requests' and the answers' programs alike, two a call. Mean of
``device_link_<n>_lane_pair_wait_us`` over the window on the link with most unary
device calls in it (such a window need hold no train, so
``layers/lane_pair_wait_us.py``, which goes by trains, finds no link); ``None`` on
a program without the unary recorders."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "lane_pair_wait_us")
