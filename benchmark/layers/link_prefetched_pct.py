"""Link: the share of dispatched exchange programs whose host copies were
asked for at the dispatch (``device_link_prefetched_steps`` over
``device_link_steps``). 100 where every train's readback finds its
transfers landed or landing; a program from before PR 36 has no such adder
and reads ``None``."""
from benchmark import stages


def read(run):
    asked = stages.ratio(run, "device_link_prefetched_steps", "device_link_steps")
    return None if asked is None else 100.0 * asked
