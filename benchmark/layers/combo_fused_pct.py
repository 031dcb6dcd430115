"""Combo (rpc/combo.py): the share of the window's combo calls that rode
the one ``shard_map`` dispatch (``device_link_combo_fused`` over it,
``…_mc_lowered`` and ``…_host_fanout``; a call counts in exactly one).
``None`` on a program without the adders or a window without a call."""


def read(run):
    lowered = [
        run.counters.get(f"device_link_combo_{how}")
        for how in ("fused", "mc_lowered", "host_fanout")
    ]
    if None in lowered or not sum(lowered):
        return None
    return 100.0 * lowered[0] / sum(lowered)
