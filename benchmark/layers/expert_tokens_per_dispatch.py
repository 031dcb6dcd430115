"""Device program (models/expert_shard.py): tokens served by one dispatch,
from the program's own adders (``device_transport_expert_tokens`` over
``device_transport_dispatches``). ``None`` on a program without the
adder."""
from benchmark import stages


def read(run):
    return stages.ratio(
        run, "device_transport_expert_tokens", "device_transport_dispatches")
