"""Link, the lane: the share of the chip's interconnect peak the lane's
program reaches while it runs. Bytes: ``device_link_lane_bytes`` over the
window, what the sending chip sent. Time: the device time of the
executions of ``jit_device_link_lane`` on the sending chip (the
configuration's ``prefill_device``) inside the window. Peak:
``ici_bits_per_s_per_chip`` of ``peaks.json``, the chip's whole
interconnect (``benchmark/roofline_lane.py`` holds the count). ``None`` on
a program without the lane or a trace without the program."""
from benchmark import roofline_lane


def read(run):
    sent = run.counters.get("device_link_lane_bytes")
    peak = (run.peaks or {}).get("ici_bits_per_s_per_chip")
    plane = f"/device:TPU:{run.cell.config.get('prefill_device', 0)}"
    _, ns = roofline_lane.program_time(
        run.devices, run.t_open, run.t_close, roofline_lane.LANE_PROGRAM, plane)
    if not sent or not peak or not ns:
        return None
    return 100.0 * roofline_lane.lane_least_seconds(sent, peak) / (ns / 1e9)
