"""Host to HBM crossing and completion (transport/device.py,
runtime/device_butex.py): the inside view checked against the outside one.
The share of the mean time inside the wrapped handler (``run.handler``) that
the nine stage recorders' means do not cover."""
from benchmark import stages


def read(run):
    means = [stages.device_stage(run, s) for s in stages.DEVICE_STAGES]
    if len(run.handler) == 0 or None in means:
        return None
    outside = float((run.handler[:, 1] - run.handler[:, 0]).mean() / 1e3)
    return 100.0 * (outside - sum(means)) / outside
