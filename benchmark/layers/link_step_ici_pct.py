"""Link: the share of the chip's interconnect peak the ``ppermute`` step
reaches while it runs. Bytes: the slots a side the window's exchange
programs carried (``device_link_slots``) times the configuration's
``link_slot_words`` times 4: what one chip sent at the least, slot headers
left out. Time: the device time of the operations named
``collective-permute*`` inside the window, the mean over the chips with
work. Peak: ``ici_bits_per_s_per_chip`` of ``peaks.json`` over 8, the
chip's whole interconnect, of which the links to one neighbour are a
part: the share reads low and cannot pass 100."""
from benchmark import xplane

OP = "collective-permute"


def step_bytes(slots: int, slot_words: int) -> int:
    """The least one chip sends for ``slots`` slots a side."""
    return slots * slot_words * 4


def permute_seconds(devices: dict, lo: int, hi: int):
    """Mean over the chips of the device time of the ``collective-permute*``
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
    slots = run.counters.get("device_link_slots")
    words = run.cell.config.get("channel_options", {}).get("link_slot_words")
    peak = (run.peaks or {}).get("ici_bits_per_s_per_chip")
    seconds = permute_seconds(run.devices, run.t_open, run.t_close)
    if not slots or not words or not peak or not seconds:
        return None
    return 100.0 * step_bytes(slots, words) / seconds / (peak / 8)
