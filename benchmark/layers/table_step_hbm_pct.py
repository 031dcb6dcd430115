"""Device program: the share of the HBM roofline the table's step reaches.
The least bytes of what the window's steps served
(``roofline_table.table_step_bytes`` over the gains of the program's
``device_transport_table_reads``, ``..._table_updates`` and
``device_transport_dispatch_pad_rows``) over the chip's peak HBM rate,
against the device time of the steps. A gather of at most 16 rows is bound
by latency, so this reads far under 1%; what it guards is the other
direction. ``None`` on a program without the counters."""
from benchmark import roofline_table, xplane


def read(run):
    reads = run.counters.get("device_transport_table_reads")
    updates = run.counters.get("device_transport_table_updates")
    rows = run.counters.get("device_transport_dispatch_pad_rows")
    _, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if reads is None or updates is None or not rows or not total_ns or not run.peaks:
        return None
    least_s = (roofline_table.table_step_bytes(reads, updates, rows)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (total_ns / 1e9)
