"""Stream (rpc/stream.py): the share of the bytes handed to handlers that
were device arrays (``device_link_stream_device_bytes`` over it and
``device_link_stream_bytes``, which counts host bytes alone). ``None`` on a
program without the adder or a window that handed nothing over."""


def read(run):
    device = run.counters.get("device_link_stream_device_bytes")
    host = run.counters.get("device_link_stream_bytes")
    if device is None or host is None or not device + host:
        return None
    return 100.0 * device / (device + host)
