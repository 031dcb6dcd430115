"""Stream (rpc/stream.py): a write admitted → the feedback frame that
covers its last byte applied on the writer's side: the data's way across
the link, the sink's handler, and the feedback's way back. Mean of
``device_link_stream_feedback_lag_us`` over the window."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_stream_feedback_lag_us")
