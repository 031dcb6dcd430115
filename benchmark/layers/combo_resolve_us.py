"""Combo (rpc/combo.py): a partition's LB pick and fingerprint check, all
partitions (``_maybe_fused_device_call`` entered to the devices known). Mean
of ``device_link_combo_resolve_us`` over the window; a program from before
PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_resolve_us")
