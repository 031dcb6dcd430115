"""Host to HBM crossing and completion: the share of the words the step
programs were handed that no call wrote and the dispatch zeroed, the tails
of rows shorter than their bucket and pad rows whole
(``device_transport_dispatch_zeroed_words`` over
``device_transport_dispatch_words``). 0 where every call fills its row and
leaves alone; a program from before PR 53, which zeroed a whole array a
dispatch and another a call, has no such adder and reads ``None``."""
from benchmark import stages


def read(run):
    zeroed = stages.ratio(
        run, "device_transport_dispatch_zeroed_words",
        "device_transport_dispatch_words")
    return None if zeroed is None else 100.0 * zeroed
