"""Host plane, a unary call with a device attachment (rpc/channel.py): the
lane's hand-over of the answer until the caller runs again (``call_method``
about to return; an asynchronous call: ``done`` about to be handed to a
worker): the answer matched to its call on the lane's deliverer, the call
ended, the caller's thread woken and given the interpreter. Mean of the
busiest link's ``device_link_<n>_unary_client_wake_us`` recorder over the
window, a row a call; ``None`` on a program without it."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "unary_client_wake_us")
