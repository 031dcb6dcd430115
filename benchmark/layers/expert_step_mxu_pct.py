"""Device program: the share of the chip's bf16 peak the expert shard's
step reaches, by useful operations only (``roofline_expert.step_flops``
over the gain of the program's ``device_transport_expert_pairs``), against
the device time of the steps. Low by the traffic's nature (~8 tokens an
expert a row); it rises with tokens an expert, which grouping a batch by
layer would give. ``None`` on a program without the counter."""
from benchmark import roofline_expert, xplane


def read(run):
    pairs = run.counters.get(roofline_expert.PAIRS)
    _, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not pairs or not total_ns or not run.peaks:
        return None
    least_s = roofline_expert.step_flops(pairs) / run.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (total_ns / 1e9)
