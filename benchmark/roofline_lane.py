"""What the tensor lane and the page pool have to move, from what they
served. Kept with the benchmark so that no PR that claims a gain can change
the count.

The lane: every byte of ``device_link_lane_bytes`` leaves the sending chip
once, over its interconnect; nothing else has to (the header crosses the
byte stream). The chip's whole 1,600 Gbit/s is the peak, of which the links
to one neighbour are a part, so the share reads low and cannot pass 100.

The pool's write: a block is read once where the lane landed it and written
once into its page; the page ids and the slice updates' bookkeeping are
what a share under 100% shows.

Device time is that of the program's own executions, told from every other
program by its name in the trace's step line (``jit_device_link_lane``,
``jit_kv_page_write``)."""

LANE_PROGRAM = "device_link_lane"
PAGE_WRITE_PROGRAM = "kv_page_write"


def program_time(devices: dict, lo: int, hi: int, program: str, plane=None):
    """``(executions, device ns)`` of the executions of the programs whose
    name holds ``program`` between ``lo`` and ``hi``: on the device plane
    ``plane``, or summed over every plane where none is given."""
    executions = total_ns = 0
    for name, lines in devices.items():
        if plane is not None and name != plane:
            continue
        steps = lines["steps"].clip(lo, hi)
        for step, start, end in zip(steps.names, steps.start, steps.end):
            if program in step:
                executions += 1
                total_ns += int(end - start)
    return executions, total_ns


def lane_least_seconds(lane_bytes: int, ici_bits_per_s: float) -> float:
    """The least time the sending chip's interconnect takes for the lane's
    bytes."""
    return lane_bytes / (ici_bits_per_s / 8)


def page_write_least_seconds(pages: int, page_bytes: int, hbm_bytes_per_s: float) -> float:
    """The least time the pool's chip takes to read ``pages`` blocks and
    write them into their pages."""
    return 2 * pages * page_bytes / hbm_bytes_per_s
