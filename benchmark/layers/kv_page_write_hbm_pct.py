"""Device program: the share of the HBM roofline the pool's write reaches.
Bytes: every page written (``device_transport_kv_pages_written``) is a
block read and a page written, twice the configuration's ``block_bytes``
(``benchmark/roofline_lane.py`` holds the count). Time: the device time of
the executions of ``jit_kv_page_write`` inside the window. ``None`` on a
program without the counter or a trace without the program."""
from benchmark import roofline_lane


def read(run):
    pages = run.counters.get("device_transport_kv_pages_written")
    page_bytes = run.cell.config.get("block_bytes")
    peak = (run.peaks or {}).get("hbm_bytes_per_s")
    _, ns = roofline_lane.program_time(
        run.devices, run.t_open, run.t_close, roofline_lane.PAGE_WRITE_PROGRAM)
    if not pages or not page_bytes or not peak or not ns:
        return None
    least_s = roofline_lane.page_write_least_seconds(pages, page_bytes, peak)
    return 100.0 * least_s / (ns / 1e9)
