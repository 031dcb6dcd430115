"""Stream (rpc/stream.py): an admitted ``Stream.write``'s time parked on the
``max_buf_size`` window, 0 where it was admitted at once. Mean of
``device_link_stream_write_wait_us`` over the window; a program from before
PR 31 has no such recorder and reads ``None``."""
from benchmark import stages


def read(run):
    return stages.mean(run, "device_link_stream_write_wait_us")
