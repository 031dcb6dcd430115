"""The recorders of a unary call that carried a device attachment, as the
gains over the window that ``run.counters`` holds. Such a call leaves a row
on each side of its link (``device_link_<n>_unary_*``) and two on the link's
lane (``device_link_<n>_lane_*``), and may leave none on the byte stream, so
the busiest link is the one with most such calls in the window
(``stages.link_recorder`` goes by delivered trains). A program without the
recorders, as one from before PR 44 is, reads as ``None``."""

from __future__ import annotations

import re

from benchmark import stages

_CALL = re.compile(r"^device_link_(\d+)_unary_call_us$")
# what covers a call beside its handler: the four stages of the call path
# and, once each way, the lane's flight from the launch to the hand-over
CALL_STAGES = (
    "unary_request_tx_us", "unary_server_dispatch_us", "unary_reply_tx_us",
    "unary_client_wake_us",
)
FLIGHT_STAGES = ("lane_ready_us", "lane_pair_wait_us")


def link_recorder(run, suffix: str):
    """Mean of ``device_link_<n>_<suffix>`` over the window, ``n`` the link
    with most unary device calls in it."""
    calls = {
        m.group(1): gain["count"]
        for name, gain in run.counters.items()
        if (m := _CALL.match(name)) and isinstance(gain, dict) and gain["count"]
    }
    if not calls:
        return None
    return stages.mean(run, f"device_link_{max(calls, key=calls.get)}_{suffix}")
