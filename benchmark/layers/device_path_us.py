"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the median time inside the wrapped
``server_handler`` call."""
import numpy as np


def read(run):
    if len(run.handler) == 0:
        return None
    return float(np.median(run.handler[:, 1] - run.handler[:, 0]) / 1e3)
