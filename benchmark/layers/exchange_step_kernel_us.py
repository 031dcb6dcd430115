"""Device program (models/expert_shard.py ``dispatch_tensor``): device time
of one execution of a rank's step for a tensor operand (``jit_step_tensor``:
parse, sort the pairs by expert, gather, the grouped Pallas product on the
weights where they lie, add back, pack), the mean over the window's
executions on the ranks' chips. ``None`` on a trace without the program."""
from benchmark import roofline_exchange


def read(run):
    executions, total_ns = roofline_exchange.step_time(run)
    if not executions:
        return None
    return total_ns / executions / 1e3
