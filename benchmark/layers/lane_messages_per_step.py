"""Link, the lane: messages one dispatched lane program carried
(``device_link_lane_messages`` over ``device_link_lane_steps``). 1 while a
program carries one message; consecutive messages of one shape riding one
program, as slots ride a train, would raise it. ``None`` on a program
without the lane."""
from benchmark import stages


def read(run):
    return stages.ratio(run, "device_link_lane_messages", "device_link_lane_steps")
