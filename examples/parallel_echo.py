#!/usr/bin/env python
"""parallel_echo — scatter/gather over a ParallelChannel (reference
example/parallel_echo_c++): one call fans out to N sub-channels, responses
merge in channel order. With enough mesh devices, the second half shows
the ICI collective lowering (BASELINE config #3): the same call over
device links to distinct devices fuses into ONE shard_map all-gather
dispatch — byte-identical to the host fan-out.

Run: python examples/parallel_echo.py
"""

import sys

sys.path.insert(0, ".")

from incubator_brpc_tpu.bvar import expose_registry  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    CallMapper,
    Channel,
    ChannelOptions,
    Controller,
    ParallelChannel,
    Server,
    ServerOptions,
    SubCall,
    device_method,
)

ROW = 2  # bytes of the request a replica answers, in the device half


class RowMapper(CallMapper):
    """combo_channel.md's UseFieldAsSubRequest over bytes: sub-request i is
    row i of the request (the last may be shorter or empty)."""

    def map(self, channel_index, nchannels, service, method, request):
        return SubCall(request=request[channel_index * ROW:(channel_index + 1) * ROW])


def lowerings() -> dict:
    """How many combo calls took each lowering (docs/OBSERVABILITY.md)."""
    return {
        name.rsplit("_combo_", 1)[1]: var.get_value()
        for name, var in expose_registry.snapshot("device_link_combo_")
        if name.endswith(("_fused", "_mc_lowered", "_host_fanout"))
    }


def main() -> None:
    servers = []
    for i in range(3):
        s = Server()
        s.add_service(
            "EchoService", {"Echo": (lambda c, req, _i=i: b"[replica%d]%s" % (_i, req))}
        )
        assert s.start(0)
        servers.append(s)

    pc = ParallelChannel()  # default fail_limit: succeeds unless ALL fail
    for s in servers:
        ch = Channel()
        assert ch.init(f"127.0.0.1:{s.port}")
        pc.add_channel(ch)

    cntl = pc.call_method("EchoService", "Echo", b"fanout")
    assert cntl.ok(), cntl.error_text
    print(f"merged response: {cntl.response_payload!r}")
    for s in servers:
        s.stop()

    # -- the collective lowering (SURVEY §2.5; needs a 4+ device mesh) ----
    import jax

    if len(jax.devices()) < 4:
        print("(single device: the fused-collective half needs a 4+ mesh)")
        return

    def add_one(data, n):  # the device kernel every partition serves
        import jax.numpy as jnp

        return data + jnp.uint8(1), n

    dservers = []
    for i in range(3):
        s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
        s.add_service("dsvc", {"inc": device_method(add_one, width=256)})
        assert s.start(0)
        dservers.append(s)
    fused = ParallelChannel()
    for s in dservers:
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{s.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=60000),
        )
        fused.add_channel(ch, call_mapper=RowMapper())
    before = lowerings()
    # the first call makes the three links' handshakes, which compile the
    # links' programs: the call's own deadline bounds them (the default 500
    # ms does not hold on a loaded host, and the call then falls back to
    # the host fan-out)
    cntl = fused.call_method(
        "dsvc", "inc", b"\x01\x02\x03\x04\x05",
        cntl=Controller(timeout_ms=60000),
    )
    assert cntl.ok(), cntl.error_text
    assert cntl.response_payload == b"\x02\x03\x04\x05\x06"
    took = [how for how, n in lowerings().items() if n > before[how]]
    print(
        f"lowering={took} merged={cntl.response_payload!r}  "
        "(fused: one shard_map all-gather dispatch, not 3 RPCs)"
    )
    for s in dservers:
        s.stop()


if __name__ == "__main__":
    main()
