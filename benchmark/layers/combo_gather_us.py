"""Combo (rpc/combo.py): the program call returned to both gathered arrays on
the host (``np.asarray``): the device's work and the read-back. Mean of
``device_link_combo_gather_us`` over the window; a program from before PR 33
has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_gather_us")
