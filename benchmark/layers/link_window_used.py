"""Link (transport/device_link.py): undrained steps at each dispatch, the
new one included — how much of ``link_window`` is ever in flight. Mean of
the busiest link's ``device_link_<n>_inflight_at_dispatch`` over the
window."""
from benchmark import stages


def read(run):
    return stages.link_recorder(run, "inflight_at_dispatch")
