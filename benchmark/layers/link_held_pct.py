"""Link: the share of dispatched exchange programs whose dispatch was held
for credit at all (``device_link_held_steps`` over ``device_link_steps``).
0 where no train ever found less credit than its backlog wanted while
slots were out; a program from before PR 32 has no such adder and reads
``None``."""
from benchmark import stages


def read(run):
    held = stages.ratio(run, "device_link_held_steps", "device_link_steps")
    return None if held is None else 100.0 * held
