"""Host to HBM crossing and completion: the share of the dispatches whose
operand was the request's own memory, a call alone that fills its row
handed to the one-row program without a copy
(``device_transport_dispatch_borrowed`` over
``device_transport_dispatches``). A program from before PR 53 has no such
adder and reads ``None``."""
from benchmark import stages


def read(run):
    borrowed = stages.ratio(
        run, "device_transport_dispatch_borrowed", "device_transport_dispatches")
    return None if borrowed is None else 100.0 * borrowed
