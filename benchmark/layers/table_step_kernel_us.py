"""Device program (models/record_table.py, ops/framing.py): device time of
one execution of the table's step (parse, verify, gather, apply the
updates where the table lies, respond), the mean over the window's
executions. Under 50 us the step moves rows; in milliseconds it moves the
table. ``None`` on a program without the table's counters."""
from benchmark import xplane


def read(run):
    if run.counters.get("device_transport_table_reads") is None:
        return None
    executions, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not executions:
        return None
    return total_ns / executions / 1e3
