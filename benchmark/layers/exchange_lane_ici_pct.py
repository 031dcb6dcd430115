"""Link, the lanes: the share of the source chip's interconnect peak the
three links' lane programs reach while they run. Bytes:
``device_link_lane_bytes`` over the window, the bodies the three lanes
carried, both directions: each enters or leaves the source's chip once. Time:
the device time of the executions of ``jit_device_link_lane`` on the source's
chip inside the window. Peak: ``ici_bits_per_s_per_chip`` of ``peaks.json``,
the chip's whole interconnect (``lane_step_ici_pct``'s count for a chip that
talks to three). ``None`` on a program without the lane or a trace without
the program."""
from benchmark import roofline_lane


def read(run):
    carried = run.counters.get("device_link_lane_bytes")
    peak = (run.peaks or {}).get("ici_bits_per_s_per_chip")
    _, ns = roofline_lane.program_time(
        run.devices, run.t_open, run.t_close, roofline_lane.LANE_PROGRAM,
        "/device:TPU:0")
    if not carried or not peak or not ns:
        return None
    return 100.0 * roofline_lane.lane_least_seconds(carried, peak) / (ns / 1e9)
