#!/usr/bin/env python
"""streaming_echo — bidirectional stream with credit-window flow control
(reference example/streaming_echo_c++): the client opens a stream on an
RPC, pushes messages, the server echoes them back on its half. Then the
sink form (upstream's server.cpp consumes and writes nothing back): one
transfer of --total_bytes in --message_bytes messages to a sink that
acknowledges the whole with one receipt, GB/s printed, as
link_performance.py does for its configuration. This is the shape of the
benchmark's link_stream_sink_ici deployment.

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


if __name__ == "__main__":
    main()
