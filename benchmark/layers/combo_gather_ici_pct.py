"""Combo (rpc/combo.py ``_fused_dispatch``): the share of the chip's
interconnect peak the fused program's all-gather reaches while it runs.
Bytes: the least one shard's chip receives, the other partitions' rows,
``(partitions - 1) * row_bytes`` a call, times the window's fused calls
(``device_link_combo_fused``). Time: the device time of the operations
named ``all-gather*`` inside the window, the mean over the chips with
such work. Peak: ``ici_bits_per_s_per_chip`` of ``peaks.json`` over 8, the
chip's whole interconnect: the share reads low and cannot pass 100."""
from benchmark import xplane

OP = "all-gather"


def gather_bytes(calls: int, partitions: int, row_bytes: int) -> int:
    """The least one chip receives for ``calls`` fused calls."""
    return calls * (partitions - 1) * row_bytes


def gather_seconds(devices: dict, lo: int, hi: int):
    """Mean over the chips of the device time of the ``all-gather*``
    operations between ``lo`` and ``hi``; ``None`` where no chip ran one."""
    per_chip = []
    for lines in devices.values():
        ops = lines["ops"].clip(lo, hi)
        ns = sum(
            int(end - start)
            for name, start, end in zip(ops.names, ops.start, ops.end)
            if xplane.safe_name(name).startswith(OP)
        )
        if ns:
            per_chip.append(ns / 1e9)
    return sum(per_chip) / len(per_chip) if per_chip else None


def read(run):
    calls = run.counters.get("device_link_combo_fused")
    partitions = run.cell.config.get("partitions")
    row_bytes = run.cell.config.get("row_bytes")
    peak = (run.peaks or {}).get("ici_bits_per_s_per_chip")
    seconds = gather_seconds(run.devices, run.t_open, run.t_close)
    if not calls or not partitions or not row_bytes or not peak or not seconds:
        return None
    return 100.0 * gather_bytes(calls, partitions, row_bytes) / seconds / (peak / 8)
