"""Device program (models/expert_shard.py): distinct layers the rows of one
dispatch named, from the program's own adders
(``device_transport_expert_layers`` over ``device_transport_dispatches``).
A dispatch's cost is its layers, not its rows: each is 705 MB to read.
``None`` on a program without the adder."""
from benchmark import stages


def read(run):
    return stages.ratio(
        run, "device_transport_expert_layers", "device_transport_dispatches")
