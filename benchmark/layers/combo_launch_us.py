"""Combo (rpc/combo.py): the call of the fused program until it returned, under
the launch order. Mean of ``device_link_combo_launch_us`` over the window; a
program from before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_launch_us")
