"""Host plane: a unary call with a device attachment as ``call_method`` sees
it, entered to returned: the frame of the four ``unary_*`` stages, the
lane's two flights and the handler. Mean of the busiest link's
``device_link_<n>_unary_call_us`` recorder over the window, a row a call
that ended well; ``None`` on a program without it."""
from benchmark import stages_unary


def read(run):
    return stages_unary.link_recorder(run, "unary_call_us")
