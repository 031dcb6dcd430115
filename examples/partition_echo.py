#!/usr/bin/env python
"""partition_echo — sharded service behind one naming entry (reference
example/partition_echo_c++ + dynamic_partition_echo_c++): servers publish
"N/M" partition tags; a PartitionChannel fans a call across all partitions;
a DynamicPartitionChannel weights traffic across coexisting schemes.
Run: python examples/partition_echo.py
"""

import sys

sys.path.insert(0, ".")

from incubator_brpc_tpu.bvar import expose_registry  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    CallMapper,
    DynamicPartitionChannel,
    PartitionChannel,
    Server,
    SubCall,
)

ROW = 8  # bytes of the request a shard answers, in the device half


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


def shard_server(i: int) -> Server:
    s = Server()
    s.add_service(
        "EchoService", {"Echo": (lambda c, req, _i=i: b"[shard%d]%s" % (_i, req))}
    )
    assert s.start(0)
    return s


def main() -> None:
    shards = [shard_server(i) for i in range(3)]
    url = "list://" + ",".join(
        f"127.0.0.1:{s.port} {i}/3" for i, s in enumerate(shards)
    )

    pc = PartitionChannel()
    assert pc.init(url, partition_count=3)
    cntl = pc.call_method("EchoService", "Echo", b"sharded")
    assert cntl.ok(), cntl.error_text
    print("partitioned response:", cntl.response_payload)
    pc.stop()

    # dynamic: a /3 scheme and a /1 scheme coexist mid-repartition
    extra = shard_server(99)
    url2 = url + f",127.0.0.1:{extra.port} 0/1"
    dpc = DynamicPartitionChannel()
    assert dpc.init(url2)
    seen = set()
    for _ in range(12):
        c = dpc.call_method("EchoService", "Echo", b"x")
        assert c.ok(), c.error_text
        seen.add(c.response_payload)
    print("dynamic schemes answered:", sorted(seen))
    dpc.stop()
    for s in shards + [extra]:
        s.stop()

    # -- the same partitioned call over DEVICE LINKS (needs a 4+ mesh):
    # each shard binds its own mesh device; the client holds a star of
    # links through the DeviceLinkMap (the SocketMap analog; SURVEY §2.5's
    # sharded parameter-server shape) ---------------------------------------
    import jax

    if len(jax.devices()) < 4:
        print("(single device: the device-fabric half needs a 4+ mesh)")
        return
    from incubator_brpc_tpu.rpc import ChannelOptions, ServerOptions, device_method

    def echo_kernel(data, n):  # the device kernel every shard serves
        return data, n

    dshards = []
    for i in range(3):
        s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
        s.add_service(
            "PartitionEcho", {"Echo": device_method(echo_kernel, width=256)}
        )
        assert s.start(0)
        dshards.append(s)
    durl = "list://" + ",".join(
        f"127.0.0.1:{s.port} {i}/3" for i, s in enumerate(dshards)
    )
    dpc2 = PartitionChannel()
    assert dpc2.init(
        durl,
        partition_count=3,
        options=ChannelOptions(transport="tpu", timeout_ms=60000),
        call_mapper=RowMapper(),
    )
    from incubator_brpc_tpu.rpc import Controller

    # sub-calls inherit the PARENT controller's budget: give the first
    # call room for 3 link handshakes + the first jitted step's compile
    before = lowerings()
    cntl = dpc2.call_method(
        "PartitionEcho", "Echo", b"row-zero" b"row--one" b"two",
        cntl=Controller(timeout_ms=60000),
    )
    assert cntl.ok(), cntl.error_text
    assert cntl.response_payload == b"row-zerorow--onetwo"
    took = [how for how, n in lowerings().items() if n > before[how]]
    peers = sorted(
        str(sub[0]._device_sock.link.devices[1]) for sub in dpc2._subs
    )
    print(f"device-fabric response: {cntl.response_payload!r} (lowering: {took})")
    print(f"star fabric peers: {peers}")
    dpc2.stop()
    for s in dshards:
        s.stop()


if __name__ == "__main__":
    main()
