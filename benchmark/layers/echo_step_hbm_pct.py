"""Device program: the share of the HBM roofline the fused step reaches.
The least bytes its shapes need (``roofline.echo_step_bytes``) over the
chip's peak HBM rate, against the device time of its executions. Only for
a mix of one size: a batch of b calls runs as one program over the next
power of two of rows, and the trace does not say how many rows an
execution had, so the count takes each call as a row of its own — never
more bytes than the program moved."""
from benchmark import roofline, xplane


def read(run):
    sizes = set(run.traffic["sizes"])
    _, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if len(sizes) != 1 or not total_ns or len(run.done) == 0:
        return None
    bucket = roofline.bucket_words(sizes.pop())
    least_s = (roofline.echo_step_bytes(bucket) * len(run.done)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (total_ns / 1e9)
