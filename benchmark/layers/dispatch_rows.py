"""DeviceEndpoint micro-batching: calls stacked into one dispatch, from the
program's own adders (``device_transport_dispatch_rows`` over
``device_transport_dispatches``); ``calls_per_dispatch`` is the same
quantity counted from outside."""
from benchmark import stages


def read(run):
    return stages.ratio(
        run, "device_transport_dispatch_rows", "device_transport_dispatches")
