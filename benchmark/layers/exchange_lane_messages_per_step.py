"""Link, the lanes: messages one dispatched lane program carried, over the
three links (``device_link_lane_messages`` over ``device_link_lane_steps``):
``lane_messages_per_step`` in the cell that reports ``call_rate`` and no
``goodput``. 1 where every operand and every answer crosses alone; a request
and an answer of one shape that wait at a link's launch together share a
program and raise it. ``None`` on a program without the lane."""
from benchmark import stages


def read(run):
    return stages.ratio(run, "device_link_lane_messages", "device_link_lane_steps")
