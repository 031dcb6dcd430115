"""Expert exchange (models/expert_exchange.py): the first sub-call sent until
the last rank's answer is in hand: three lane programs out, the ranks' steps,
three lane programs back (a request and an answer that wait at a lane
together share one). Mean of ``device_transport_expert_exchange_fanout_us``
over the window; ``None`` on a program without it."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_transport_expert_exchange_fanout_us")
