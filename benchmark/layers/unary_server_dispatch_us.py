"""Host plane, a unary call with a device attachment (rpc/server.py): the
lane's hand-over of the request (its tag cut as the frame it is) until the
handler was entered on a worker thread: the worker pool's hand-over,
``process_request``'s checks, admission. Mean of the busiest link's
``device_link_<n>_unary_server_dispatch_us`` recorder over the window, a
row a call; ``None`` on a program without it."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "unary_server_dispatch_us")
