"""Device program: the share of the HBM roofline the ranks' tensor step
reaches. The least bytes of what the window's steps served
(``roofline_exchange.step_bytes`` over the gains of
``device_transport_expert_weight_sets`` and ``..._expert_tokens``, the three
ranks together) over the chip's peak HBM rate, against the device time of
``jit_step_tensor`` on the ranks' chips. ``None`` on a program without the
counters or a trace without the program."""
from benchmark import roofline_exchange


def read(run):
    sets = run.counters.get(roofline_exchange.WEIGHT_SETS)
    tokens = run.counters.get(roofline_exchange.TOKENS)
    seconds = roofline_exchange.step_time(run)[1] / 1e9
    if not sets or tokens is None or not seconds or not run.peaks:
        return None
    least_s = roofline_exchange.step_bytes(sets, tokens) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
