"""Stream (rpc/stream.py): a data frame cut off the link (``_on_frame``) →
the consumer's handler entered for that message: the ordered queue's
hand-over. Mean of ``device_link_stream_deliver_us`` over the window."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_stream_deliver_us")
