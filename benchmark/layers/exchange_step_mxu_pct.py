"""Device program: the share of the chip's bf16 peak the ranks' tensor step
reaches, by useful operations only (``roofline_exchange.step_flops`` over the
gain of ``device_transport_expert_pairs``), against the device time of
``jit_step_tensor`` on the ranks' chips. At ~256 pairs an expert the step
stands at the balance of the two rooflines. ``None`` on a program without
the counter or a trace without the program."""
from benchmark import roofline_exchange


def read(run):
    pairs = run.counters.get(roofline_exchange.PAIRS)
    seconds = roofline_exchange.step_time(run)[1] / 1e9
    if not pairs or not seconds or not run.peaks:
        return None
    least_s = roofline_exchange.step_flops(pairs) / run.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / seconds
