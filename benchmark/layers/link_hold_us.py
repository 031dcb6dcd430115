"""Link (transport/device_link.py): how long the drive kept a train back
for the credit of the train its backlog wanted (the first look that found
the free credit short of it, slots still in flight, to the dispatch), 0 for
a train that went at once: a mean over every train. Mean of the busiest
link's ``device_link_<n>_hold_us`` over the window; a program from before
PR 32 never holds, has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "hold_us")
