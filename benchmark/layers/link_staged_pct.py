"""Link: the share of dispatched exchange programs launched from one host
buffer (``device_link_staged_steps`` over ``device_link_steps``): both
sides' slots filled into one ``(2, k, width)`` array and handed to the
program call, whose ``in_shardings`` place each device's half. 100 on the
``ppermute`` geometry, 0 on the host swap, which dispatches no program; a
program from before PR 38 has no such adder and reads ``None``."""
from benchmark import stages


def read(run):
    staged = stages.ratio(run, "device_link_staged_steps", "device_link_steps")
    return None if staged is None else 100.0 * staged
