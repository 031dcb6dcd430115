"""Stream (rpc/stream.py): how much of the configuration's ``max_buf_size``
a write finds unconsumed ahead of it when it is admitted. Mean of
``device_link_stream_unconsumed_at_write`` over the window, against the
configuration's ``stream.max_buf_size``; admission asks for less than the
window, so it cannot pass 100."""
from benchmark import stages


def read(run):
    window = run.cell.config.get("stream", {}).get("max_buf_size")
    ahead = stages.mean(run, "device_link_stream_unconsumed_at_write")
    if ahead is None or not window:
        return None
    return 100.0 * ahead / window
