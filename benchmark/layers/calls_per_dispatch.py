"""DeviceEndpoint micro-batching: correct calls completed in the window
for each execution of a (batch, bucket) step program in it."""
from benchmark import xplane


def read(run):
    executions, _ = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not executions or len(run.done) == 0:
        return None
    return len(run.done) / executions
