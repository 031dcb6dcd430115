"""Host plane (transport/sock.py, rpc/channel.py, rpc/server.py): the
median client call time minus the median time inside the handler."""
import numpy as np

from benchmark import reduce


def read(run):
    if len(run.done) == 0 or len(run.handler) == 0:
        return None
    inside = np.median(run.handler[:, 1] - run.handler[:, 0]) / 1e3
    return float(np.median(reduce.latencies_us(run.done)) - inside)
