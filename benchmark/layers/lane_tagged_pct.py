"""Link, the lane: the share of dispatched lane programs that carried their
message's tag beside its body (``device_link_lane_tagged_steps`` over
``device_link_lane_steps``): the message crossed whole in one program, and
no header of it rode a train of the byte stream. 100 once every device
message crosses so; a program whose lane pairs a body with a header off the
byte stream has no such adder and reads ``None``, as does a window with no
lane program."""
from benchmark import stages


def read(run):
    tagged = stages.ratio(
        run, "device_link_lane_tagged_steps", "device_link_lane_steps")
    return None if tagged is None else 100.0 * tagged
