"""Combo (rpc/combo.py ``_fused_dispatch``): device time of one execution
of the fused program (``jit_combo_fused``: the method's kernel on each
shard's row and the all-gather), the mean over the window's executions on
every shard device. ``None`` where no such program ran (a CPU rehearsal, a
program from before PR 33)."""

PROGRAM = "combo_fused"


def read(run):
    executions = total_ns = 0
    for lines in run.devices.values():
        steps = lines["steps"].clip(run.t_open, run.t_close)
        for name, start, end in zip(steps.names, steps.start, steps.end):
            if PROGRAM in name:
                executions += 1
                total_ns += int(end - start)
    if not executions:
        return None
    return total_ns / executions / 1e3
