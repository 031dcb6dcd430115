"""Host plane: of the window's unary attachments that were device arrays,
the share whose request crossed by the lane as it lay
(``device_link_unary_lane_requests`` over it and
``device_link_unary_bytes_fallbacks``, the attachments, either way, that
went as host bytes because the socket under the call has no second
device). 100 in a cell whose link joins two chips. ``None`` on a program
without the adders or a window without such a call."""


def read(run):
    lane = run.counters.get("device_link_unary_lane_requests")
    fallbacks = run.counters.get("device_link_unary_bytes_fallbacks")
    if lane is None or fallbacks is None or not lane + fallbacks:
        return None
    return 100.0 * lane / (lane + fallbacks)
