"""Device program (models/expert_shard.py, ops/framing.py): device time of
one execution of the expert shard's step (parse, verify, the experts'
products on the weights where they lie, respond), the mean over the
window's executions. Milliseconds: a layer's eight experts are 705 MB to
read. ``None`` on a program without the shard's counters."""
from benchmark import roofline_expert, xplane


def read(run):
    if run.counters.get(roofline_expert.TOKENS) is None:
        return None
    executions, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not executions:
        return None
    return total_ns / executions / 1e3
