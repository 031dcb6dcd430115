"""Combo (rpc/combo.py): LB feedback, ``dm.unpack`` a partition and the
mergers' joins. Mean of ``device_link_combo_merge_us`` over the window; a
program from before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_merge_us")
