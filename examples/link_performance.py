#!/usr/bin/env python
"""link_performance — the RDMA parity harness retargeted at device links
(reference example/rdma_performance/client.cpp:30-40: echo with a tunable
attachment size, qps + latency printout, a --use flag flipping the
transport). BASELINE config #5's shape.

Run (self-contained: starts its own server):
    python examples/link_performance.py                      # device links
    python examples/link_performance.py --transport tcp      # host sockets
    python examples/link_performance.py --attachment-kb 32 --threads 4
    python examples/link_performance.py --attachment-in device  # use_rdma=true

``--attachment-in device`` is upstream's ``use_rdma`` switch as it was
meant: the attachment lies in memory the transport owns, here the client
device's HBM. The program has no switch for it: ``call_method`` is handed a
``jax.Array`` instead of bytes, and over a link between two devices the
array crosses the link's lane as it lies, the handler reads a ``jax.Array``
on its own device and the caller gets one back (docs/DEVICE_PLANE.md, "A
unary call carries a tensor"). Over ``--transport tcp``, or where both ends
share one device, the same call sends the array's bytes.
"""

import argparse
import sys
import threading
import time

sys.path.insert(0, ".")

from incubator_brpc_tpu.bvar import LatencyRecorder  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel,
    ChannelOptions,
    Controller,
    Server,
    ServerOptions,
)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--transport", choices=("tpu", "tcp"), default="tpu",
                   help="the use_rdma flip: device links vs host sockets")
    p.add_argument("--attachment-kb", type=int, default=4,
                   help="echoed attachment size in KiB (attachment_size)")
    p.add_argument("--attachment-in", choices=("host", "device"), default="host",
                   help="where the attachment lies: host bytes, or a "
                        "jax.Array on the client's device (use_rdma=true)")
    p.add_argument("--threads", type=int, default=2, help="caller threads")
    p.add_argument("--seconds", type=float, default=3.0, help="test_seconds")
    args = p.parse_args(argv)

    def echo(cntl, req):
        cntl.response_attachment = cntl.request_attachment  # echo_attachment
        return req

    server = Server(ServerOptions(usercode_inline=True))
    server.add_service("perf", {"echo": echo})
    assert server.start(0)

    ch = Channel()
    assert ch.init(
        f"127.0.0.1:{server.port}",
        options=ChannelOptions(
            transport=args.transport,
            timeout_ms=120000,
            link_slot_words=64 * 1024,
        ),
    )
    attachment = b"a" * (args.attachment_kb << 10)
    warm = ch.call_method(
        "perf", "echo", b"warm", attachment=attachment,
        cntl=Controller(timeout_ms=120000),
    )
    assert warm.ok(), warm.error_text
    nbytes_a_call = 2 * len(attachment)  # echoed both ways
    if args.attachment_in == "device":
        import jax
        import numpy as np

        # the link exists now: its client side's device is where the
        # caller's tensors lie (any device over tcp: the bytes are sent)
        sock = ch._device_sock
        device = sock.link.devices[sock.side] if sock is not None else jax.devices()[0]
        attachment = jax.device_put(np.frombuffer(attachment, np.uint32), device)
        warm = ch.call_method(
            "perf", "echo", b"warm", attachment=attachment,
            cntl=Controller(timeout_ms=120000),
        )
        assert warm.ok(), warm.error_text
        print(f"attachment {attachment.dtype}{list(attachment.shape)} on "
              f"{device}; the answer came back as "
              f"{type(warm.response_attachment).__name__}")

    latency = LatencyRecorder(name=None)
    stop_at = time.monotonic() + args.seconds
    totals = {"calls": 0, "bytes": 0, "fail": 0}
    lock = threading.Lock()

    def worker():
        calls = fail = nbytes = 0
        while time.monotonic() < stop_at:
            t0 = time.perf_counter()
            c = ch.call_method(
                "perf", "echo", b"ping", attachment=attachment,
                cntl=Controller(timeout_ms=120000),
            )
            if c.ok():
                calls += 1
                nbytes += nbytes_a_call
                latency << (time.perf_counter() - t0) * 1e6
            else:
                fail += 1
        with lock:
            totals["calls"] += calls
            totals["bytes"] += nbytes
            totals["fail"] += fail

    threads = [threading.Thread(target=worker) for _ in range(args.threads)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    print(
        f"transport={args.transport} attachment={args.attachment_kb}KiB "
        f"in={args.attachment_in} "
        f"threads={args.threads}: {totals['calls'] / wall:.0f} qps, "
        f"{totals['bytes'] / wall / 1e9:.3f} GB/s, "
        f"p50={latency.latency_percentile(0.5):.0f}us "
        f"p99={latency.latency_percentile(0.99):.0f}us "
        f"fail={totals['fail']}"
    )
    server.stop()


if __name__ == "__main__":
    main()
