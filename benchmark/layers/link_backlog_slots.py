"""Link (transport/device_link.py): slots the fuller side's backlog would
fill when a train is dispatched, the first of the two numbers a train's
length is taken from (the other is the free credit). Mean of the busiest
link's ``device_link_<n>_backlog_slots_at_dispatch`` over the window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "backlog_slots_at_dispatch")
