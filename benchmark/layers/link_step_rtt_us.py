"""Link (transport/device_link.py): the mean of the link's own
``device_link_<id>_step_rtt_us`` recorder over the window (dispatch of an
exchange step to its in-order delivery)."""
import re

_NAME = re.compile(r"^device_link_\d+_step_rtt_us$")


def read(run):
    gains = [v for k, v in run.counters.items() if _NAME.match(k) and v["count"]]
    if not gains:
        return None
    busiest = max(gains, key=lambda v: v["count"])
    return busiest["sum"] / busiest["count"]
