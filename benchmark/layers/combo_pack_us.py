"""Combo (rpc/combo.py): ``dm.pack`` (a zeroed row and a copy) a partition and
the ``np.stack`` of the rows. Mean of ``device_link_combo_pack_us`` over the
window; a program from before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_pack_us")
