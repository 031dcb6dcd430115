"""Combo (rpc/combo.py): the wait for the process's launch order
(``parallel/collective.py``), near 0 for a caller that finds it free. Mean
of ``device_link_combo_launch_wait_us`` over the window; a program from
before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_launch_wait_us")
