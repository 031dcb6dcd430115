"""Device program (models/tensor_echo.py, ops/framing.py): device time of
one execution of the fused step, the mean over the window's executions."""
from benchmark import xplane


def read(run):
    executions, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not executions:
        return None
    return total_ns / executions / 1e3
