"""Device program: the share of the HBM roofline the expert shard's step
reaches. The least bytes of what the window's steps served
(``roofline_expert.step_bytes`` over the gains of the program's
``device_transport_expert_weight_sets`` and ``..._expert_tokens``) over the
chip's peak HBM rate, against the device time of the steps. The step is
HBM-bound at this traffic's tokens an expert. ``None`` on a program
without the counters."""
from benchmark import roofline_expert, xplane


def read(run):
    sets = run.counters.get(roofline_expert.WEIGHT_SETS)
    tokens = run.counters.get(roofline_expert.TOKENS)
    _, total_ns = xplane.step_time(run.devices, run.t_open, run.t_close)
    if not sets or tokens is None or not total_ns or not run.peaks:
        return None
    least_s = roofline_expert.step_bytes(sets, tokens) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (total_ns / 1e9)
