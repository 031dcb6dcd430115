"""Link, a unary call with a device attachment (rpc/server.py): the handler
returned until the answer's ``lane_send`` returned, on the handler's worker
thread: ``_finish``, the answer's frame packed as its array's tag and the
lane program's launch. Mean of the busiest link's
``device_link_<n>_unary_reply_tx_us`` recorder over the window, a row a
call; ``None`` on a program without it."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "unary_reply_tx_us")
