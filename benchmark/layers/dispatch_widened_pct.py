"""DeviceEndpoint micro-batching: the share of the calls dispatched that
rode a bucket wider than their own, because the batch they left in held a
call of a wider one (``device_transport_dispatch_widened_rows`` over
``device_transport_dispatch_rows``). 0 where every call is of one bucket;
a program from before PR 28 has no such adder and reads ``None``."""
from benchmark import stages


def read(run):
    widened = stages.ratio(
        run, "device_transport_dispatch_widened_rows",
        "device_transport_dispatch_rows")
    return None if widened is None else 100.0 * widened
