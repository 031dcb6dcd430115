"""Link: slots a side that one dispatched exchange program carried
(``device_link_slots`` over ``device_link_steps``): how long the trains
run. 1 where every step is a train of one; a program from before PR 30
has no such adder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.ratio(run, "device_link_slots", "device_link_steps")
