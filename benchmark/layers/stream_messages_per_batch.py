"""Stream (rpc/stream.py): messages one ``on_received_messages`` call is
handed (``device_link_stream_messages`` over ``device_link_stream_batches``):
1 where the consumer keeps up with the link, up to ``messages_in_batch``
where messages queue before it."""
from benchmark import stages


def read(run):
    return stages.ratio(
        run, "device_link_stream_messages", "device_link_stream_batches")
