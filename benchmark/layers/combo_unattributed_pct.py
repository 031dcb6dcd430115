"""Combo (rpc/combo.py): the inside view checked against itself. The share
of the mean fused call (``device_link_combo_call_us``) that the seven stage
recorders' means do not cover: the CallMapper's own time and what lies
between stamps. ``None`` unless all eight recorders have rows."""
from benchmark import stages

STAGES = ("resolve", "pack", "put", "launch_wait", "launch", "gather", "merge")


def read(run):
    call = stages.mean(run, "device_link_combo_call_us")
    means = [stages.mean(run, f"device_link_combo_{s}_us") for s in STAGES]
    if not call or None in means:
        return None
    return 100.0 * (call - sum(means)) / call
