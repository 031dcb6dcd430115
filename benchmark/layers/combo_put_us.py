"""Combo (rpc/combo.py): two ``device_put`` a partition and the assembly of the
two sharded arrays. Mean of ``device_link_combo_put_us`` over the window; a
program from before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_put_us")
