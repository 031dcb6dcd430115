"""Expert exchange: the share of the HBM roofline the source's gather and
combine reach together. Least bytes: ``roofline_exchange.source_bytes`` over
the gain of ``device_transport_expert_exchange_tokens_sent``, the window's
layer calls and the configuration's ``micro_batch_tokens``; time: the device
time of ``jit_expert_exchange_gather`` and ``jit_expert_exchange_combine`` on
the source's chip. ``None`` on a program without the counters or a trace
without the programs."""
from benchmark import roofline_exchange, roofline_lane


def read(run):
    sent = run.counters.get(roofline_exchange.TOKENS_SENT)
    calls = (run.counters.get(roofline_exchange.CALLS) or {}).get("count")
    tokens = run.cell.config.get("micro_batch_tokens")
    total_ns = sum(
        roofline_lane.program_time(
            run.devices, run.t_open, run.t_close, program,
            roofline_exchange.SOURCE_PLANE)[1]
        for program in (roofline_exchange.GATHER_PROGRAM,
                        roofline_exchange.COMBINE_PROGRAM))
    if not sent or not calls or not tokens or not total_ns or not run.peaks:
        return None
    least_s = (roofline_exchange.source_bytes(sent, calls, tokens)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (total_ns / 1e9)
