"""Device program (models/kv_page_pool.py): device time of one execution of
the pool's write (``jit_kv_page_write``: up to four blocks into their pages,
the pool donated), the mean over the window's executions. Tens of
microseconds while it moves blocks; milliseconds if it ever moved the pool.
``None`` on a program without the pool's counter or a trace without the
program."""
from benchmark import roofline_lane


def read(run):
    if run.counters.get("device_transport_kv_pages_written") is None:
        return None
    executions, ns = roofline_lane.program_time(
        run.devices, run.t_open, run.t_close, roofline_lane.PAGE_WRITE_PROGRAM)
    if not executions:
        return None
    return ns / executions / 1e3
