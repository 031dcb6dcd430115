"""Expert exchange (models/expert_exchange.py): one layer call as the source
rank sees it, entered to the combined micro-batch ready on its chip: gather,
the three sub-calls in flight together, combine. Mean of the program's
``device_transport_expert_exchange_call_us`` recorder over the window, a row
a layer call that ended well; ``None`` on a program without it."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_expert_exchange_call_us")
