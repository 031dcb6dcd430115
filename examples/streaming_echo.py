#!/usr/bin/env python
"""streaming_echo — bidirectional stream with credit-window flow control
(reference example/streaming_echo_c++): the client opens a stream on an
RPC, pushes messages, the server echoes them back on its half. Then the
sink form (upstream's server.cpp consumes and writes nothing back): one
transfer of --total_bytes in --message_bytes messages to a sink that
acknowledges the whole with one receipt, GB/s printed, as
link_performance.py does for its configuration. This is the shape of the
benchmark's link_stream_sink_ici deployment. Last, the same write loop with
the messages device arrays (``Stream.write`` of a ``jax.Array``): over a
device link between two devices the sink's handler is handed a
``jax.Array`` on its own device and no block touches host memory; over
host sockets, or a link on one shared device, it is handed the array's
bytes. Nothing chooses but what the socket under the stream is (the shape
of the benchmark's kv_block_stream_ici deployment).

Run (self-contained: starts its own servers):
    python examples/streaming_echo.py                     # host sockets
    python examples/streaming_echo.py --transport tpu     # device link
    python examples/streaming_echo.py --transport tpu \
        --message_bytes 1048576 --total_bytes 33554432    # the cell's sizes
"""

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, ".")

from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel,
    Server,
    StreamHandler,
    StreamOptions,
    stream_accept,
    stream_create,
)


def sink_transfer(args) -> None:
    import __graft_entry__ as ge

    facts = ge.stream_leg(
        os.urandom(args.total_bytes), args.message_bytes,
        transport=args.transport,
    )
    over = f" over {facts['geometry']} {facts['devices']}" if "geometry" in facts else ""
    print(
        f"[sink] transport={args.transport}{over}: {args.total_bytes} B in "
        f"{facts['messages']} messages of {args.message_bytes} B, bytes and "
        f"boundaries kept, at most {facts['ahead']} B ahead of the sink, "
        f"{args.total_bytes / facts['seconds'] / 1e9:.4f} GB/s"
    )


def device_transfer(args) -> None:
    """``--total_bytes`` as device arrays of ``--message_bytes``, made on
    this side's device before the clock starts, to a sink that checks each
    against its source and says what it was handed."""
    import jax
    import numpy as np

    from incubator_brpc_tpu.rpc import ChannelOptions
    from incubator_brpc_tpu.utils.status import ErrorCode

    words = max(1, args.message_bytes // 4)
    count = max(1, args.total_bytes // (4 * words))
    handed, done = [], threading.Event()

    class Sink(StreamHandler):
        def on_received_messages(self, stream, messages):
            handed.extend(messages)  # jax.Arrays or bytes, in order
            if len(handed) >= count:
                done.set()

    def open_stream(cntl, request):
        stream_accept(cntl, StreamOptions(handler=Sink()))
        return b""

    server = Server()
    server.add_service("StreamService", {"Open": open_stream})
    assert server.start(0)
    ch = Channel()
    options = ChannelOptions(timeout_ms=60000)
    if args.transport == "tpu":
        options = ChannelOptions(transport="tpu", timeout_ms=60000)
    assert ch.init(f"127.0.0.1:{server.port}", options=options)
    s = stream_create(StreamOptions(max_buf_size=max(1 << 20, 16 * words)))
    cntl = ch.call_method("StreamService", "Open", b"", request_stream=s)
    assert cntl.ok(), cntl.error_text
    assert s.wait_connected(60)
    source = [np.full(words, i, np.uint32) for i in range(count)]
    blocks = jax.block_until_ready(
        [jax.device_put(block, jax.devices()[0]) for block in source])
    # a deployment warms the lane for its shapes (DeviceLink.warm_lane);
    # here the first message compiles its program, inside the clock
    t0 = time.monotonic()
    for block in blocks:
        while (rc := s.write(block, timeout=10)) != 0:
            assert rc in (ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED), rc
    assert done.wait(120), "the sink did not get every message"
    seconds = time.monotonic() - t0
    s.close()
    server.stop()
    arrays = [m for m in handed if isinstance(m, jax.Array)]
    for got, want in zip(handed, source):
        words_got = np.asarray(got) if isinstance(got, jax.Array) else (
            np.frombuffer(got, np.uint32))
        assert np.array_equal(words_got, want), "a block changed on the way"
    where = (
        f"{len(arrays)} jax.Arrays on {sorted(arrays[0].devices())[0]}" if arrays
        else f"{len(handed)} bytes messages (no second device under this socket)"
    )
    print(
        f"[device] transport={args.transport}: {count} device arrays of "
        f"{4 * words} B written from {jax.devices()[0]}, the sink was handed "
        f"{where}, {count * 4 * words / seconds / 1e9:.4f} GB/s"
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--transport", choices=("tpu", "tcp"), default="tcp",
                   help="the sink transfer's transport: device link or host sockets")
    p.add_argument("--message_bytes", type=int, default=64 << 10,
                   help="bytes a Stream.write of the sink transfer")
    p.add_argument("--total_bytes", type=int, default=4 << 20,
                   help="bytes the sink transfer moves")
    args = p.parse_args(argv)

    server = Server()
    server_streams = {}

    class ServerSide(StreamHandler):
        def on_received_messages(self, stream, messages):
            for m in messages:
                stream.write(b"echo:" + m)  # push back on our half

        def on_closed(self, stream):
            print("[server] stream closed")

    def open_stream(cntl, request):
        s = stream_accept(cntl, StreamOptions(handler=ServerSide()))
        server_streams[s.id] = s
        return b"stream accepted"

    server.add_service("StreamService", {"Open": open_stream})
    assert server.start(0)

    got, done = [], threading.Event()

    class ClientSide(StreamHandler):
        def on_received_messages(self, stream, messages):
            got.extend(messages)
            if len(got) >= 5:
                done.set()

    ch = Channel()
    assert ch.init(f"127.0.0.1:{server.port}")
    s = stream_create(StreamOptions(handler=ClientSide(), max_buf_size=1 << 20))
    cntl = ch.call_method("StreamService", "Open", b"", request_stream=s)
    assert cntl.ok(), cntl.error_text
    assert s.wait_connected(5)

    for i in range(5):
        assert s.write(b"msg-%d" % i) == 0
        time.sleep(0.02)
    assert done.wait(5)
    print("[client] received:", got)
    s.close()
    server.stop()
    sink_transfer(args)
    device_transfer(args)


if __name__ == "__main__":
    main()
