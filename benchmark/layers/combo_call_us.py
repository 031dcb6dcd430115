"""Combo (rpc/combo.py): a fused call as ``call_method`` sees it, from its
entry to just before ``done``: the CallMapper's cuts, the seven stages and
the adders. Mean of ``device_link_combo_call_us`` over the window; a program
from before PR 33 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_combo_call_us")
