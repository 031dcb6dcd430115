"""Combo (rpc/combo.py): the share of the window's fused calls whose answer
was one join of the gathered rows (``device_link_combo_joined`` over
``device_link_combo_fused``): 100 where every merger of every call is the
default ``ResponseMerger``, 0 where a user's merger ran. ``None`` on a
program without the adder or a window without a fused call."""
from benchmark import stages


def read(run):
    share = stages.ratio(run, "device_link_combo_joined", "device_link_combo_fused")
    return None if share is None else 100.0 * share
