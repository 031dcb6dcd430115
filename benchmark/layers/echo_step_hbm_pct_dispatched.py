"""Device program: the share of the HBM roofline the fused step reaches,
counted from what was dispatched. The program's adders say how many rows of
how many words the window's executions ran, padding included
(``roofline.echo_step_bytes`` summed over them), so this holds for a mix of
sizes, where ``echo_step_hbm_pct`` has to take each call as a row of its own
bucket."""
from benchmark import roofline, xplane


def read(run):
    words = run.counters.get("device_transport_dispatch_words")
    rows = run.counters.get("device_transport_dispatch_pad_rows")
    _, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not words or not rows or not total_ns or not run.peaks:
        return None
    least_bytes = 4 * (2 * words + roofline.FRAME_HEADER_WORDS * rows)
    return 100.0 * least_bytes / run.peaks["hbm_bytes_per_s"] / (total_ns / 1e9)
