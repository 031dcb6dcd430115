"""Two-party device transport tests (reference shape:
test/brpc_rdma_unittest.cpp — handshake, data path, flow control, teardown
— run loopback on the virtual device mesh, SURVEY §4's prescription)."""

import contextlib
import threading
import time

import pytest

from incubator_brpc_tpu.rpc import Channel, ChannelOptions, Server
from incubator_brpc_tpu.utils.status import ErrorCode


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


@pytest.fixture
def echo_server():
    server = Server()

    def echo(cntl, req):
        cntl.response_attachment = cntl.request_attachment
        return req

    server.add_service("EchoService", {"Echo": echo})
    assert server.start(0)
    yield server
    server.stop()
    server.join(timeout=5)


def _tpu_channel(server, **opts) -> Channel:
    ch = Channel()
    assert ch.init(
        f"127.0.0.1:{server.port}",
        options=ChannelOptions(transport="tpu", timeout_ms=30000, **opts),
    )
    return ch


class TestDeviceEcho:
    def test_echo_roundtrip_crosses_two_devices(self, echo_server):
        import jax

        ch = _tpu_channel(echo_server)
        cntl = ch.call_method("EchoService", "Echo", b"over the device plane")
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"over the device plane"
        ds = ch._device_sock
        assert ds is not None
        if len(jax.devices()) > 1:
            # the two halves really sit on different mesh devices
            assert ds.link.devices[0] != ds.link.devices[1]
            assert ds.link._mesh is not None  # shard_map/ppermute path

    def test_attachment_and_meta_survive(self, echo_server):
        ch = _tpu_channel(echo_server)
        cntl = ch.call_method(
            "EchoService", "Echo", b"payload", attachment=b"piggyback"
        )
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"payload"
        assert cntl.response_attachment == b"piggyback"

    def test_payload_larger_than_slot_spans_steps(self, echo_server):
        # slot_words=256 -> 1 KiB slots; a 64 KiB frame needs ~64 steps of
        # byte-stream chunking each way
        ch = _tpu_channel(echo_server, link_slot_words=256, link_window=4)
        big = bytes(range(256)) * 256
        cntl = ch.call_method("EchoService", "Echo", big)
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == big

    def test_many_sequential_calls_share_one_link(self, echo_server):
        ch = _tpu_channel(echo_server)
        first = None
        for i in range(20):
            cntl = ch.call_method("EchoService", "Echo", f"msg-{i}".encode())
            assert cntl.ok(), cntl.error_text
            assert cntl.response_payload == f"msg-{i}".encode()
            if first is None:
                first = ch._device_sock
        assert ch._device_sock is first  # one handshake, one link

    def test_handshake_used_host_socket(self, echo_server):
        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"x").ok()
        # the bootstrap TCP socket exists in the client map independently
        # of the device link
        host = ch._socket_map.get_or_create(ch._single_server)
        assert host is not ch._device_sock


class TestContentionAndFlowControl:
    def test_contended_writers(self, echo_server):
        ch = _tpu_channel(echo_server, link_slot_words=512, link_window=2)
        errs = []

        def worker(i):
            for j in range(10):
                body = (f"t{i}-{j}-".encode()) + bytes((i * 31 + j) % 256 for _ in range(3000))
                c = ch.call_method("EchoService", "Echo", body)
                if c.failed() or c.response_payload != body:
                    errs.append((i, j, c.error_code, c.error_text))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs[:3]

    def test_window_bounds_inflight_steps(self, echo_server):
        ch = _tpu_channel(echo_server, link_slot_words=256, link_window=2)
        big = b"w" * 50000
        cntl = ch.call_method("EchoService", "Echo", big)
        assert cntl.ok(), cntl.error_text
        link = ch._device_sock.link
        # the credit window held dispatched-but-undrained steps at <= window
        assert link.inflight_steps <= link.window

    def test_writer_stalls_then_resumes_on_backlog(self, echo_server):
        # direct link-level test: a tiny window and slot make the byte
        # budget small; a burst of sends must block (not error) and all
        # bytes must still arrive in order
        ch = _tpu_channel(echo_server, link_slot_words=64, link_window=1)
        assert ch.call_method("EchoService", "Echo", b"warm").ok()
        link = ch._device_sock.link
        blob = b"AB" * 4000  # far past the 1-slot byte budget

        rc = link.send(0, blob)  # blocks internally while draining
        assert rc == 0

        # server side got the byte stream appended to its read buffer; the
        # messenger will reject it as garbage eventually, but the transport
        # delivered every byte in order first — assert via the socket's
        # buffer growth before the parse error fails the link
        assert _wait(lambda: link._closed or link._out_nbytes[0] == 0)


class TestTeardown:
    def test_server_stop_fails_client_link(self, echo_server):
        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"x").ok()
        ds = ch._device_sock
        echo_server.stop()
        assert _wait(lambda: ds.state != 0)  # CONNECTED == 0
        # subsequent calls fail fast or re-handshake-fail, never hang
        c = ch.call_method("EchoService", "Echo", b"y")
        assert c.failed()

    def test_link_failure_reports_not_hangs(self, echo_server):
        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"x").ok()
        ch._device_sock.link.fail("injected")
        c = ch.call_method("EchoService", "Echo", b"y")
        # the failed link is detected and re-handshaken (fresh link), or
        # the call fails visibly — either way no hang
        assert c.ok() or c.error_code != 0

    def test_reconnect_after_link_failure(self, echo_server):
        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"x").ok()
        old = ch._device_sock
        old.link.fail("injected")
        assert _wait(lambda: old.state != 0)
        c = ch.call_method("EchoService", "Echo", b"again")
        assert c.ok(), c.error_text
        assert ch._device_sock is not old  # fresh handshake, fresh link


class _CountingSink:
    """Messenger stand-in that drains the socket read buffer and counts."""

    def __init__(self):
        self.nbytes = 0
        self.chunks = []

    def process(self, sock):
        n = len(sock._read_buf)
        if n:
            self.chunks.append(sock._read_buf.to_bytes(n))
            sock._read_buf.popn(n)
            self.nbytes += n


class TestHostLoopbackFastPath:
    """Shared-device geometry: the exchange is a host swap — no device
    dispatch, no readback (VERDICT r3 item 1's on-chip fast path)."""

    def _make_link(self, **kw):
        import jax

        from incubator_brpc_tpu.transport.device_link import (
            DeviceLink,
            DeviceSocket,
        )

        dev = jax.devices()[0]
        link = DeviceLink([dev, dev], **kw)
        sinks = (_CountingSink(), _CountingSink())
        socks = (
            DeviceSocket(link, side=0, messenger=sinks[0]),
            DeviceSocket(link, side=1, messenger=sinks[1]),
        )
        return link, socks, sinks

    def test_same_device_defaults_to_host_swap(self):
        link, socks, sinks = self._make_link(slot_words=1024)
        assert link._step is None  # no jitted step compiled at all
        payload = bytes(range(256)) * 16
        assert link.send(0, payload) == 0
        assert _wait(lambda: sinks[1].nbytes == len(payload))
        assert b"".join(sinks[1].chunks) == payload
        # and the reverse direction
        assert link.send(1, b"pong" * 100) == 0
        assert _wait(lambda: sinks[0].nbytes == 400)

    def test_forced_device_loop_still_works(self):
        link, socks, sinks = self._make_link(
            slot_words=1024, host_loopback=False
        )
        assert link._step is not None  # the jitted on-device swap
        payload = b"device-loop" * 50
        assert link.send(0, payload) == 0
        assert _wait(lambda: sinks[1].nbytes == len(payload), timeout=30)
        assert b"".join(sinks[1].chunks) == payload

    def test_fast_and_device_paths_deliver_identical_streams(self):
        payload = bytes((i * 7 + 3) % 256 for i in range(50000))
        outs = []
        for forced in (None, False):
            link, socks, sinks = self._make_link(
                slot_words=256, window=2, host_loopback=forced
            )
            assert link.send(0, payload) == 0
            assert _wait(lambda: sinks[1].nbytes == len(payload), timeout=60)
            outs.append(b"".join(sinks[1].chunks))
        assert outs[0] == outs[1] == payload

    def test_loopback_throughput_sane(self, monkeypatch):
        # the fast path must move bytes at memcpy-class rates — a
        # regression to per-step device round trips is what this guards,
        # and that is an event: no call into the runtime on the way of
        # 64 MiB. The rate is reported beside it and not judged (a floor of
        # 0.2 GB/s read 0.07-0.12 under six workers and 1.85-1.97 alone)
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(
            slot_words=256 * 1024, window=8
        )
        assert link.geometry == "host-swap"
        dl._quiesce_links(timeout=5.0)  # earlier tests' links are idle
        before = {
            a: getattr(dl, a).get_value()
            for a in ("link_steps", "link_slots", "link_staged", "link_prefetched")
        }
        crossed = []
        for name in ("device_put", "device_get", "block_until_ready"):
            inner = getattr(jax, name)
            monkeypatch.setattr(
                jax, name,
                lambda *a, _inner=inner, _name=name, **kw: (
                    crossed.append(_name), _inner(*a, **kw))[1],
            )
        chunk = b"t" * (1 << 20)
        total = 64 << 20
        t0 = time.perf_counter()
        for _ in range(total // len(chunk)):
            assert link.send(0, chunk, timeout=30) == 0
        assert _wait(lambda: sinks[1].nbytes == total, timeout=60)
        gbps = total / (time.perf_counter() - t0) / 1e9
        print(f"loopback link moved {gbps:.3f} GB/s")
        assert _wait(lambda: link.inflight_steps == 0)
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        # one 1 MiB slot a step, swapped on the host: no program launched,
        # no host copy asked for, nothing put on or read from a device
        assert gained == {
            "link_steps": 64, "link_slots": 64, "link_staged": 0,
            "link_prefetched": 0,
        }
        assert crossed == []


class TestWireAckWindow:
    """ack_mode='wire': the credit window gates on the cumulative-delivered
    count carried in received slot headers (word 3) — the only signal a
    multi-controller host has (the RDMA piggybacked imm-data acks +
    accumulated-ack/SendImm catch-up, rdma_endpoint.h:117-123,176-195)."""

    def _make_link(self, **kw):
        import jax

        from incubator_brpc_tpu.transport.device_link import (
            DeviceLink,
            DeviceSocket,
        )

        devs = jax.devices()
        pair = devs[:2] if len(devs) >= 2 else [devs[0], devs[0]]
        link = DeviceLink(pair, ack_mode="wire", **kw)
        sinks = (_CountingSink(), _CountingSink())
        DeviceSocket(link, side=0, messenger=sinks[0])
        DeviceSocket(link, side=1, messenger=sinks[1])
        return link, sinks

    def test_stream_drains_under_wire_acks(self):
        link, sinks = self._make_link(slot_words=256, window=4)
        payload = bytes((i * 13 + 5) % 256 for i in range(100_000))
        assert link.send(0, payload, timeout=60) == 0
        assert _wait(lambda: sinks[1].nbytes == len(payload), timeout=60)
        assert b"".join(sinks[1].chunks) == payload
        # the window held: seq never ran more than window + 1 catch-up
        # step ahead of the acks the wire carried
        assert link._seq - link._peer_ack <= link.window + 1

    def test_window_one_still_makes_progress(self):
        # the degenerate window: every data step needs an ack catch-up
        # step — throughput halves, progress must NOT stop
        link, sinks = self._make_link(slot_words=128, window=1)
        payload = b"w1" * 3000
        assert link.send(0, payload, timeout=60) == 0
        assert _wait(lambda: sinks[1].nbytes == len(payload), timeout=60)
        assert b"".join(sinks[1].chunks) == payload

    def test_bidirectional_wire_acks(self):
        link, sinks = self._make_link(slot_words=256, window=2)
        a = bytes(range(256)) * 40
        b = bytes(reversed(range(256))) * 40
        assert link.send(0, a, timeout=60) == 0
        assert link.send(1, b, timeout=60) == 0
        assert _wait(lambda: sinks[1].nbytes == len(a), timeout=60)
        assert _wait(lambda: sinks[0].nbytes == len(b), timeout=60)
        assert b"".join(sinks[1].chunks) == a
        assert b"".join(sinks[0].chunks) == b

    def test_rpc_over_wire_ack_link(self, echo_server):
        from incubator_brpc_tpu.rpc import Controller

        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{echo_server.port}",
            options=ChannelOptions(
                transport="tpu",
                timeout_ms=60000,
                link_ack_mode="wire",
                link_slot_words=256,
                link_window=2,
            ),
        )
        big = bytes(range(256)) * 64
        cntl = ch.call_method(
            "EchoService", "Echo", big, cntl=Controller(timeout_ms=60000)
        )
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == big
        assert ch._device_sock.link.ack_mode == "wire"


class TestNPartyFabric:
    """The SocketMap-analog link manager: N peers, one link per peer device,
    partitioned RPC over the device plane (VERDICT r3 item 3)."""

    def _start_partition_servers(self, n=4):
        from incubator_brpc_tpu.rpc import Server, ServerOptions

        servers = []
        for i in range(n):
            # each partition's server binds its own mesh device (1..n);
            # the client side of every link is device 0 — a star fabric
            s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
            s.add_service(
                "part", {"get": (lambda cntl, req, _i=i: f"p{_i}:".encode() + req)}
            )
            assert s.start(0)
            servers.append(s)
        return servers

    def test_partition_channel_over_device_links(self):
        import jax

        from incubator_brpc_tpu.rpc.combo import PartitionChannel

        if len(jax.devices()) < 5:
            pytest.skip("needs a 5+ device mesh")
        servers = self._start_partition_servers(4)
        try:
            url = "list://" + ",".join(
                f"127.0.0.1:{s.port} {i}/4" for i, s in enumerate(servers)
            )
            pc = PartitionChannel()
            assert pc.init(
                url,
                partition_count=4,
                options=ChannelOptions(transport="tpu", timeout_ms=60000),
            )
            from incubator_brpc_tpu.rpc import Controller

            cntl = pc.call_method(
                "part", "get", b"X", cntl=Controller(timeout_ms=60000)
            )
            assert cntl.ok(), cntl.error_text
            # default merger concatenates in channel (partition) order
            assert cntl.response_payload == b"p0:Xp1:Xp2:Xp3:X"
            # every sub-channel rides a device link, each to a DIFFERENT
            # server device, all sharing the client device — a 5-party star
            links = [sub[0]._device_sock.link for sub in pc._subs]
            assert all(link._mesh is not None for link in links)
            client_devs = {str(link.devices[0]) for link in links}
            server_devs = [str(link.devices[1]) for link in links]
            assert len(client_devs) == 1
            assert len(set(server_devs)) == 4
            assert client_devs.isdisjoint(server_devs)
            pc.stop()
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)

    def test_link_map_dedupes_links_across_channels(self):
        from incubator_brpc_tpu.rpc import Server, ServerOptions

        srv = Server(ServerOptions(device_index=1))
        srv.add_service("EchoService", {"Echo": lambda cntl, req: req})
        assert srv.start(0)
        try:
            ch1 = _tpu_channel(srv)
            ch2 = _tpu_channel(srv)
            assert ch1.call_method("EchoService", "Echo", b"a").ok()
            assert ch2.call_method("EchoService", "Echo", b"b").ok()
            # one handshake, one link: both channels share the map entry
            assert ch1._device_sock is ch2._device_sock
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_lb_target_with_tpu_transport(self):
        from incubator_brpc_tpu.rpc import Server, ServerOptions

        s1 = Server(ServerOptions(device_index=1))
        s2 = Server(ServerOptions(device_index=2))
        for i, s in enumerate((s1, s2)):
            s.add_service("svc", {"who": (lambda cntl, req, _i=i: f"s{_i}".encode())})
            assert s.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{s1.port},127.0.0.1:{s2.port}",
                "rr",
                options=ChannelOptions(transport="tpu", timeout_ms=60000),
            )
            seen = set()
            for _ in range(6):
                cntl = ch.call_method("svc", "who", b"")
                assert cntl.ok(), cntl.error_text
                seen.add(cntl.response_payload)
            assert seen == {b"s0", b"s1"}  # rr rotated across both peers
        finally:
            s1.stop()
            s2.stop()


class TestCollectiveLowering:
    """ParallelChannel/PartitionChannel fused to ONE shard_map dispatch
    when every sub-channel rides a device link to a distinct mesh device
    and the method is a registered device kernel (VERDICT r3 item 2;
    SURVEY §2.5 all-gather lowering; BASELINE configs #3/#4)."""

    @staticmethod
    def _kernel(data, n):
        # a real transform (not echo) so a wrong shard order / stale cache
        # shows up in the bytes: add the byte's index, wrap mod 256
        import jax.numpy as jnp

        idx = jnp.arange(data.shape[0], dtype=jnp.uint8)
        return data + idx, n

    def _servers(self, n=4):
        from incubator_brpc_tpu.rpc import Server, ServerOptions, device_method

        servers = []
        for i in range(n):
            s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
            s.add_service("dsvc", {"xform": device_method(self._kernel, width=512)})
            assert s.start(0)
            servers.append(s)
        return servers

    def _make_pc(self, servers, fuse, mapper=None):
        from incubator_brpc_tpu.rpc.combo import ParallelChannel

        pc = ParallelChannel(fuse_device_calls=fuse)
        for s in servers:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{s.port}",
                options=ChannelOptions(transport="tpu", timeout_ms=60000),
            )
            pc.add_channel(ch, call_mapper=mapper)
        return pc

    def test_fused_and_host_fanout_produce_identical_merges(self):
        import jax

        if len(jax.devices()) < 5:
            pytest.skip("needs a 5+ device mesh")

        class PerIndexMapper:
            def map(self, i, nchan, service, method, request):
                from incubator_brpc_tpu.rpc.combo import SubCall

                return SubCall(request=bytes([i * 10]) * (i + 3))

        servers = self._servers(4)
        try:
            mapper = PerIndexMapper()
            fused_pc = self._make_pc(servers, fuse=True, mapper=mapper)
            host_pc = self._make_pc(servers, fuse=False, mapper=mapper)
            from incubator_brpc_tpu.rpc import Controller

            f = fused_pc.call_method(
                "dsvc", "xform", b"ignored", cntl=Controller(timeout_ms=60000)
            )
            h = host_pc.call_method(
                "dsvc", "xform", b"ignored", cntl=Controller(timeout_ms=60000)
            )
            assert f.ok(), f.error_text
            assert h.ok(), h.error_text
            assert getattr(f, "collective_fused", False) is True
            assert getattr(h, "collective_fused", False) is False
            assert f.response_payload == h.response_payload
            assert len(f.response_payload) == 3 + 4 + 5 + 6
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)

    def test_fused_falls_back_for_plain_methods(self):
        import jax

        if len(jax.devices()) < 3:
            pytest.skip("needs a 3+ device mesh")
        from incubator_brpc_tpu.rpc import Server, ServerOptions

        servers = []
        for i in range(2):
            s = Server(ServerOptions(device_index=i + 1))
            s.add_service("plain", {"echo": lambda cntl, req: req})
            assert s.start(0)
            servers.append(s)
        try:
            pc = self._make_pc(servers, fuse=True)
            from incubator_brpc_tpu.rpc import Controller

            cntl = pc.call_method(
                "plain", "echo", b"hp", cntl=Controller(timeout_ms=60000)
            )
            assert cntl.ok(), cntl.error_text
            assert getattr(cntl, "collective_fused", False) is False
            assert cntl.response_payload == b"hphp"  # host fan-out concat
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)


class TestFabricFailurePaths:
    def test_fused_falls_back_when_one_link_is_dead(self):
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs a 4+ device mesh")
        from incubator_brpc_tpu.rpc import (
            Controller,
            Server,
            ServerOptions,
            device_method,
        )
        from incubator_brpc_tpu.rpc.combo import ParallelChannel

        def k(data, n):
            return data, n

        servers = []
        for i in range(3):
            s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
            s.add_service("fsvc", {"m": device_method(k, width=64)})
            assert s.start(0)
            servers.append(s)
        try:
            pc = ParallelChannel(fail_limit=1)  # any sub failing fails the call
            for s in servers:
                ch = Channel()
                assert ch.init(
                    f"127.0.0.1:{s.port}",
                    options=ChannelOptions(transport="tpu", timeout_ms=60000),
                )
                pc.add_channel(ch)
            c = pc.call_method("fsvc", "m", b"ok", cntl=Controller(timeout_ms=60000))
            assert c.ok() and getattr(c, "collective_fused", False)
            # kill one member: the fused preconditions must fail CLEANLY
            # and the host fan-out arbitrate (no hang, no partial fuse).
            # Server stop closes its link half GRACEFULLY (F_CLOSE rides
            # the link); wait for the client side to observe it
            dead_ds = pc._subs[1][0]._device_sock
            servers[1].stop()
            servers[1].join(timeout=5)
            assert _wait(lambda: dead_ds.state != 0, timeout=10)
            c2 = pc.call_method("fsvc", "m", b"after", cntl=Controller(timeout_ms=5000))
            assert getattr(c2, "collective_fused", False) is False
            # fail_limit=1 with a dead member: the call reports failure
            assert c2.failed()
        finally:
            for s in servers:
                s.stop()

    def test_link_map_isolates_credentials(self, echo_server):
        from incubator_brpc_tpu.transport.device_link import device_link_map

        class FakeAuth:
            def generate_credential(self) -> str:
                return "cred"  # credentials are str by contract

            def verify_credential(self, cred, sock) -> bool:
                return True

        from incubator_brpc_tpu.utils.endpoint import EndPoint

        target = EndPoint(ip="127.0.0.1", port=echo_server.port)
        plain = device_link_map.get_or_create(target, timeout_ms=30000)
        authed = device_link_map.get_or_create(
            target, timeout_ms=30000, auth=FakeAuth()
        )
        # different credentials must NEVER share a link (socket_map.h:35
        # keys by auth identity for the same reason)
        assert plain is not authed
        assert plain.link is not authed.link


class TestStepFailureInjection:
    def test_dispatch_failure_mid_traffic_fails_link_cleanly(self, echo_server):
        # inject a step that blows up on the Nth dispatch: the link must
        # fail (not wedge), in-flight callers must get errors, and the
        # next call must re-handshake onto a FRESH link
        from incubator_brpc_tpu.rpc import Controller

        ch = _tpu_channel(echo_server)
        assert ch.call_method(
            "EchoService", "Echo", b"warm", cntl=Controller(timeout_ms=30000)
        ).ok()
        link = ch._device_sock.link
        orig_step = link._step
        calls = {"n": 0}

        def failing_step(slots):
            calls["n"] += 1
            if calls["n"] >= 2:
                raise RuntimeError("injected device fault")
            return orig_step(slots)

        link._step = failing_step
        # this call's request or response step hits the injected fault
        c = ch.call_method(
            "EchoService", "Echo", b"boom", cntl=Controller(timeout_ms=10000)
        )
        # either the failure landed mid-call (error) or after (link dead)
        assert c.failed() or link._closed
        assert _wait(lambda: link._closed, timeout=10)
        # recovery: the map re-handshakes a fresh link and traffic resumes
        c2 = ch.call_method(
            "EchoService", "Echo", b"again", cntl=Controller(timeout_ms=30000)
        )
        assert c2.ok(), c2.error_text
        assert ch._device_sock.link is not link


class TestZeroCopyDelivery:
    def test_received_blocks_reference_step_output_memory(self, echo_server):
        # The receive path must wrap the link step's output buffer as an
        # external IOBuf block (HBM-backed IOBuf: rdma block_pool.h:20-66 /
        # iobuf.cpp:258-306) — no host-side payload copy before the parse
        # boundary. Asserted by address identity: the fed block's view must
        # point INTO the delivered row's own buffer.
        import numpy as np

        from incubator_brpc_tpu.iobuf import IOBuf
        from incubator_brpc_tpu.transport import device_link as dl

        ch = _tpu_channel(echo_server, link_slot_words=4096)
        assert ch.call_method("EchoService", "Echo", b"warm").ok()

        ext_addrs = []  # addresses handed to append_external (zero-copy wraps)
        row_spans = []  # [start, end) of delivered rows' buffers

        orig_ext = IOBuf.append_external

        def ext_spy(iobuf_self, obj, release_cb=None):
            a = np.frombuffer(memoryview(obj), dtype=np.uint8)
            ext_addrs.append((a.ctypes.data, a.nbytes))
            return orig_ext(iobuf_self, obj, release_cb)

        orig_rows = dl.DeviceLink._rows_to_host

        def rows_spy(link_self, arrays):
            rows = orig_rows(link_self, arrays)
            for row in rows:
                if row is not None:
                    b = row.view(np.uint8)
                    row_spans.append((b.ctypes.data, b.ctypes.data + b.nbytes))
            return rows

        IOBuf.append_external = ext_spy
        dl.DeviceLink._rows_to_host = rows_spy
        try:
            big = b"q" * 12000  # > 4096: external-block delivery path
            cntl = ch.call_method("EchoService", "Echo", big)
            assert cntl.ok(), cntl.error_text
            assert cntl.response_payload == big
        finally:
            IOBuf.append_external = orig_ext
            dl.DeviceLink._rows_to_host = orig_rows
        # at least one received chunk was wrapped IN PLACE inside a
        # delivered row's own buffer — no host copy before the parse
        aliased = [
            (a, n)
            for a, n in ext_addrs
            for lo, hi in row_spans
            if lo <= a and a + n <= hi
        ]
        assert aliased, f"no external block aliased a delivered row: {ext_addrs[:3]} vs {row_spans[:3]}"

    def test_iobuf_write_queues_block_views(self, echo_server):
        # DeviceSocket.write(IOBuf) must not flatten to bytes: the link
        # gathers from the IOBuf's own block views
        from incubator_brpc_tpu.iobuf import IOBuf

        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"warm").ok()
        link = ch._device_sock.link
        buf = IOBuf()
        payload = b"Z" * 9000
        buf.append_external(payload)
        # inject directly: the queue entries must be views, with the IOBuf
        # itself as the keepalive
        rc = link.send(0, buf)
        assert rc == 0
        # drained by the driver shortly; the send accounting was by view
        import time as _t

        deadline = _t.monotonic() + 5
        while link._out_nbytes[0] and _t.monotonic() < deadline:
            _t.sleep(0.01)
        assert link._out_nbytes[0] == 0


class TestDynamicPartitionFused:
    def test_dynamic_scheme_fuses_too(self):
        """DynamicPartitionChannel picks a scheme, whose ParallelChannel
        applies the same collective lowering when its partitions are
        device-method servers."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs a 4+ device mesh")
        from incubator_brpc_tpu.rpc import (
            Controller,
            Server,
            ServerOptions,
            device_method,
        )
        from incubator_brpc_tpu.rpc.combo import DynamicPartitionChannel

        def bump(data, n):
            import jax.numpy as jnp

            return data + jnp.uint8(2), n

        servers = []
        for i in range(3):
            s = Server(ServerOptions(device_index=i + 1, usercode_inline=True))
            s.add_service("dd", {"k": device_method(bump, width=128)})
            assert s.start(0)
            servers.append(s)
        try:
            url = "list://" + ",".join(
                f"127.0.0.1:{s.port} {i}/3" for i, s in enumerate(servers)
            )
            from incubator_brpc_tpu.rpc import ChannelOptions as CO

            dpc = DynamicPartitionChannel()
            assert dpc.init(
                url, options=CO(transport="tpu", timeout_ms=60000)
            )
            deadline = time.monotonic() + 10
            while not dpc._schemes and time.monotonic() < deadline:
                time.sleep(0.05)
            c = dpc.call_method(
                "dd", "k", b"\x07", cntl=Controller(timeout_ms=60000)
            )
            assert c.ok(), c.error_text
            assert c.response_payload == b"\x09" * 3
            assert getattr(c, "collective_fused", False) is True
            dpc.stop()
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)


def _link_recorders(link):
    link._step_feed.flush()  # the sampler thread would, within the second
    return {
        name: getattr(link, "_m_" + name)
        for name in (
            "rtt", "flush", "launch", "ready", "reorder_wait", "readback",
            "pump", "dispatch_interval", "inflight", "hold",
        )
    }


def _stages_sum(m):
    """The five stages that add up to ``step_rtt``, summed."""
    return sum(
        m[name].latency_sum()
        for name in ("launch", "ready", "reorder_wait", "readback", "pump")
    )


class TestStepStageRecorders:
    """The link's step split the way the endpoint's call is: launch, ready,
    reorder_wait, readback and pump add up to step_rtt."""

    def _stream(self, link, nbytes=48 * 1024):
        sinks = [_CountingSink(), _CountingSink()]
        from incubator_brpc_tpu.transport.device_link import DeviceSocket

        for side in (0, 1):
            DeviceSocket(link, side, messenger=sinks[side])
        assert link.send(0, bytes(range(256)) * (nbytes // 256)) == 0
        assert _wait(lambda: sinks[1].nbytes == nbytes, timeout=30.0)
        assert _wait(lambda: link.inflight_steps == 0)
        return sinks

    @pytest.mark.parametrize("geometry", ["host-swap", "device-swap", "ppermute"])
    def test_stages_add_up_to_the_step_round_trip(self, geometry):
        import jax

        from incubator_brpc_tpu.transport.device_link import DeviceLink

        devs = jax.devices()
        if geometry == "ppermute":
            if len(devs) < 2:
                pytest.skip("needs two devices")
            link = DeviceLink(devs[:2], slot_words=1024, window=4)
        else:
            link = DeviceLink(
                [devs[0], devs[0]], slot_words=1024, window=4,
                host_loopback=(geometry == "host-swap"),
            )
        assert link.geometry == geometry
        self._stream(link)
        m = _link_recorders(link)
        steps = m["rtt"].count()
        # 48 KiB through 4 KiB slots: 12 slots, a step each on the host
        # swap, trains of up to the window's 4 where a program is dispatched
        assert steps >= (12 if geometry == "host-swap" else 3)
        for name in (
            "launch", "ready", "reorder_wait", "readback", "pump", "inflight", "hold",
        ):
            assert m[name].count() == steps, name
        # one send over an idle link: no train was held (TestSlotTrains has
        # one that was: the hold lies before the dispatch, outside step_rtt)
        assert m["hold"].max_latency() == 0
        assert m["flush"].count() == 2 * steps  # both sides' trains, every step
        assert _stages_sum(m) == pytest.approx(m["rtt"].latency_sum(), rel=1e-6)
        # one send, one drive: every step but the first has an interval
        assert m["dispatch_interval"].count() == steps - 1
        assert m["dispatch_interval"].latency_sum() > 0
        # slots in flight at each dispatch, the new train's included: 1..window
        assert steps <= m["inflight"].latency_sum() <= 4 * steps
        assert m["inflight"].max_latency() <= 4

    def test_capacity_counts_every_slot_side_filled(self):
        import jax

        from incubator_brpc_tpu.transport.device_link import (
            DeviceLink,
            link_bytes,
            link_capacity,
        )

        dev = jax.devices()[0]
        link = DeviceLink([dev, dev], slot_words=1024, window=4)
        before = (link_capacity.get_value(), link_bytes.get_value())
        self._stream(link)
        steps = _link_recorders(link)["rtt"].count()
        assert link_capacity.get_value() - before[0] == 2 * steps * 4096
        assert link_bytes.get_value() - before[1] == 48 * 1024

    def test_pump_no_longer_holds_the_readback(self):
        import jax

        from incubator_brpc_tpu.transport.device_link import DeviceLink

        dev = jax.devices()[0]
        link = DeviceLink([dev, dev], slot_words=1024, window=2, host_loopback=False)
        inner = link._rows_to_host

        def slow(arrays):
            time.sleep(0.02)
            return inner(arrays)

        link._rows_to_host = slow
        self._stream(link, nbytes=8 * 1024)
        link._step_feed.flush()
        assert link._m_readback.latency() >= 20_000
        assert link._m_pump.max_latency() < 20_000

    def test_new_recorders_retire_with_the_link(self):
        import jax

        from incubator_brpc_tpu.bvar import expose_registry
        from incubator_brpc_tpu.transport.device_link import DeviceLink

        dev = jax.devices()[0]
        link = DeviceLink([dev, dev], slot_words=1024)
        pfx = f"device_link_{link.link_id}_"
        names = {name[len(pfx):] for name, _ in expose_registry.snapshot(pfx)}
        assert names == {
            "step_rtt_us", "flush_us", "launch_us", "ready_us",
            "reorder_wait_us", "readback_us", "pump_us",
            "dispatch_interval_us", "inflight_at_dispatch",
            "backlog_slots_at_dispatch", "send_wait_us",
            "hold_us", "out_bytes_second", "in_bytes_second",
            # PR 35: the CPU clock of the stages one thread begins and ends
            "launch_cpu_us", "readback_cpu_us", "pump_cpu_us",
        }
        link.fail("retire")
        assert not list(expose_registry.snapshot(pfx))


def _trains(slots, window):
    """The train lengths one ``send()`` of ``slots`` slots' worth of bytes
    leaves behind when the other side is idle: each the largest power of
    two within the backlog and the free credit, and a train's credit comes
    back whole, at its delivery. One send over an idle link never meets the
    hold (PR 32): with a window that is a power of two, whenever slots are
    out here the free credit is either none, which waits as it always did,
    or covers the train the rest of the backlog wants."""
    out, free = [], window
    while slots:
        if free == 0:
            free = window  # the drive waited: everything out was delivered
        k = 1 << (min(slots, free).bit_length() - 1)
        out.append(k)
        slots -= k
        free -= k
    return out


class _FrameSink(_CountingSink):
    """Counts like the sink above and re-cuts the byte stream into the
    length-prefixed frames ``_framed_stream`` wrote."""

    def frames(self):
        data, out = b"".join(self.chunks), []
        while data:
            n = int.from_bytes(data[:4], "little")
            out.append(data[4 : 4 + n])
            data = data[4 + n :]
        return out


def _framed_stream(seed, nbytes):
    """Seeded frames of uneven sizes, length-prefixed, ``nbytes`` in all."""
    import numpy as np

    rng = np.random.default_rng(seed)
    frames, left = [], nbytes
    while left:
        n = min(left - 4, int(rng.integers(1, 1500))) if left > 4 else 0
        if left - 4 - n < 4:  # no room for another prefix: take the rest
            n = left - 4
        frames.append(rng.bytes(n))
        left -= 4 + n
    stream = b"".join(len(f).to_bytes(4, "little") + f for f in frames)
    assert len(stream) == nbytes
    return frames, stream


class TestSlotTrains:
    """One exchange program carries a train of slots a side: as many as
    the backlog fills and the credit admits, a power of two."""

    SLOT_WORDS = 256  # 1 KiB slots

    def _make_link(self, geometry="ppermute", **kw):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        devs = jax.devices()
        if geometry == "ppermute":
            if len(devs) < 2:
                pytest.skip("needs two devices")
            link = dl.DeviceLink(devs[:2], slot_words=self.SLOT_WORDS, **kw)
        else:
            link = dl.DeviceLink(
                [devs[0], devs[0]], slot_words=self.SLOT_WORDS,
                host_loopback=False, **kw,
            )
        assert link.geometry == geometry
        sinks = (_FrameSink(), _FrameSink())
        socks = [
            dl.DeviceSocket(link, side=i, messenger=sinks[i]) for i in (0, 1)
        ]
        return link, socks, sinks

    @staticmethod
    def _queue_then_drive(link, queue):
        """Run ``queue()`` (sends, closes) with the drive held off, then
        start it: the backlog it meets is everything queued."""
        with link._lock:
            assert not link._driving
            link._driving = True
        queue()
        with link._lock:
            link._driving = False
        link._kick()

    @pytest.mark.parametrize(
        "slots,window",
        [(1, 8), (2, 8), (3, 8), (4, 8), (8, 8), (17, 8), (22, 8), (8, 4), (5, 1)],
    )
    def test_backlog_and_credit_set_the_train(self, slots, window):
        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(window=window)
        dl._quiesce_links(timeout=5.0)  # earlier tests' links are idle
        before = (dl.link_steps.get_value(), dl.link_slots.get_value())
        # the last slot part full: frames cross slot and train boundaries
        frames, stream = _framed_stream(slots, slots * 1024 - 100)
        assert link.send(0, stream, timeout=60) == 0
        assert _wait(lambda: sinks[1].nbytes == len(stream), timeout=60)
        assert _wait(lambda: link.inflight_steps == 0)
        assert sinks[1].frames() == frames
        expect = _trains(slots, window)
        assert dl.link_steps.get_value() - before[0] == len(expect)
        assert dl.link_slots.get_value() - before[1] == slots == link._seq
        m = _link_recorders(link)
        assert m["rtt"].count() == len(expect)
        # slots in flight at each dispatch, the new train's included
        assert sum(expect) <= m["inflight"].latency_sum() <= window * len(expect)
        assert m["inflight"].max_latency() <= window

    def test_one_mebibyte_echo_takes_a_fraction_of_the_steps(self, echo_server):
        # 1 MiB + header is 17 slots of the default 64 KiB each way: 34
        # steps a call at one slot a step, trains of 8, 8, 1 now
        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"warm").ok()
        link = ch._device_sock.link
        assert (link.slot_words, link.window) == (16384, 8)
        assert _wait(lambda: link.inflight_steps == 0)
        steps, slots = _link_recorders(link)["rtt"].count(), link._seq
        big = bytes(range(256)) * 4096
        cntl = ch.call_method("EchoService", "Echo", b"", attachment=big)
        assert cntl.ok(), cntl.error_text
        assert cntl.response_attachment == big
        assert _wait(lambda: link.inflight_steps == 0)
        assert link._seq - slots == 34
        assert _link_recorders(link)["rtt"].count() - steps <= 12

    @pytest.mark.parametrize("ack_mode", ["local", "wire"])
    def test_window_bounds_slots_in_flight_both_ways(self, ack_mode):
        link, socks, sinks = self._make_link(window=4, ack_mode=ack_mode)
        peak = []
        take = link._take_seq_locked

        def spy(k=1, *saw):
            out = take(k, *saw)
            peak.append((k, link._inflight, link._seq - link._peer_ack))
            return out

        link._take_seq_locked = spy
        a, b = _framed_stream(1, 40_000), _framed_stream(2, 30_000)
        self._queue_then_drive(
            link,
            lambda: (link.send(0, a[1], timeout=60), link.send(1, b[1], timeout=60)),
        )
        assert _wait(lambda: sinks[1].nbytes == 40_000, timeout=60)
        assert _wait(lambda: sinks[0].nbytes == 30_000, timeout=60)
        assert sinks[1].frames() == a[0] and sinks[0].frames() == b[0]
        assert max(k for k, _, _ in peak) == 4  # trains ran at the window
        if ack_mode == "local":
            assert max(inflight for _, inflight, _ in peak) <= 4
        else:
            # one over for the catch-up step that carries the acks
            assert max(ahead for _, _, ahead in peak) <= 4 + 1
            assert link._seq - link._peer_ack <= 4 + 1

    def test_close_rides_the_slot_that_ends_its_stream(self):
        from incubator_brpc_tpu.transport.sock import CONNECTED

        link, socks, sinks = self._make_link(window=8)
        a, b = _framed_stream(3, 1500), _framed_stream(4, 4 * 1024)

        def queue():
            assert link.send(0, a[1]) == 0  # two slots, then the close
            link.close(0)
            assert link.send(1, b[1]) == 0  # five slots: a train of four
        self._queue_then_drive(link, queue)
        assert _wait(lambda: socks[1].state != CONNECTED, timeout=30)
        assert socks[1].error_code == ErrorCode.ECLOSE
        assert sinks[1].frames() == a[0]  # every byte before the close
        assert _wait(lambda: sinks[0].nbytes == len(b[1]), timeout=30)
        assert sinks[0].frames() == b[0]
        # the close came back: both ends down, nothing in flight or driving
        assert _wait(lambda: socks[0].state != CONNECTED, timeout=30)
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        assert socks[0].write(b"late") == ErrorCode.EFAILEDSOCKET

    def test_dispatch_failure_mid_train_leaves_no_sender_parked(self):
        link, socks, sinks = self._make_link(window=8)
        step, calls = link._step, []

        def failing(slots):
            calls.append(slots.shape[1])
            if len(calls) == 2:
                raise RuntimeError("injected device fault")
            return step(slots)

        link._step = failing
        rcs = []

        def sender():
            # 40 slots' worth in sends of 10: past the window's byte budget
            # after the first, so later ones park until credit or failure
            for _ in range(4):
                rcs.append(link.send(0, b"z" * (10 * 1024), timeout=30))

        t = threading.Thread(target=sender)
        t.start()
        t.join(timeout=20)
        assert not t.is_alive()
        assert _wait(lambda: link._closed, timeout=10)
        assert calls[0] == 8 and len(calls) == 2  # the second train failed
        assert ErrorCode.EFAILEDSOCKET in rcs
        # CONNECTED == 0: fail() took both sockets down with the link
        assert _wait(lambda: all(s.state != 0 for s in socks))
        assert _wait(lambda: not link._driving)

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_no_train_length_compiles_after_the_handshake(self, geometry):
        import jax

        compiles = []

        def listener(name, *_a, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            link, socks, sinks = self._make_link(geometry, window=8)
            built = len(compiles)
            assert built >= 4  # one program a train length: 1, 2, 4, 8
            seen = set()
            take = link._take_seq_locked
            link._take_seq_locked = lambda k=1, *saw: (seen.add(k), take(k, *saw))[1]
            total = 0
            for slots in (1, 2, 4, 8, 15):
                total += slots * 1024
                assert link.send(0, b"c" * (slots * 1024), timeout=60) == 0
                assert _wait(lambda: sinks[1].nbytes == total, timeout=60)
            assert seen == {1, 2, 4, 8}
            assert len(compiles) == built
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)

    def test_host_swap_keeps_one_slot_a_step(self):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        dev = jax.devices()[0]
        link = dl.DeviceLink([dev, dev], slot_words=self.SLOT_WORDS, window=8)
        assert link.geometry == "host-swap"
        sink = _FrameSink()
        dl.DeviceSocket(link, side=0, messenger=_FrameSink())
        dl.DeviceSocket(link, side=1, messenger=sink)
        frames, stream = _framed_stream(7, 8 * 1024)
        assert link.send(0, stream) == 0
        assert _wait(lambda: sink.nbytes == len(stream))
        assert sink.frames() == frames
        assert _link_recorders(link)["rtt"].count() == link._seq == 8

    # -- PR 32: a dispatch waits for the credit the train it wants needs ----

    @staticmethod
    def _gate_deliveries(link):
        """Completions wait on the returned event before ``_on_step_done``
        runs: trains stay in flight until the test lets them land."""
        gate, inner = threading.Event(), link._on_step_done

        def gated(*a, **k):
            assert gate.wait(timeout=30)
            inner(*a, **k)

        link._on_step_done = gated
        return gate

    @staticmethod
    def _spy_trains(link):
        """Every train cut: (k, the train its backlog wanted, slots in
        flight before it)."""
        cuts, take = [], link._take_seq_locked

        def spy(k=1, backlog=None):
            wanted = max(1, min(backlog, link.window))
            cuts.append((k, 1 << (wanted.bit_length() - 1), link._inflight))
            return take(k, backlog)

        link._take_seq_locked = spy
        return cuts

    @pytest.mark.parametrize(
        "inflight,backlog,window,held,then",
        [
            (4, 17, 8, True, [8, 8, 1]),  # half the window is out: wait for it
            (4, 9, 8, True, [8, 1]),
            (2, 8, 4, True, [4, 4]),
            (4, 8, 8, True, [8]),
            (4, 1, 8, False, [1]),  # the credit covers what is queued: go
            (4, 2, 8, False, [2]),
            (4, 3, 8, False, [2, 1]),
            (4, 6, 8, False, [4, 2]),
            (1, 5, 8, False, [4, 1]),  # credit 7 admits the 4 it wants
            (2, 1, 4, False, [1]),
        ],
    )
    def test_a_dispatch_waits_for_the_credit_of_the_train_it_wants(
        self, inflight, backlog, window, held, then
    ):
        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(window=window)
        gate, cuts = self._gate_deliveries(link), self._spy_trains(link)
        held_before = dl.link_held.get_value()
        first_frames, first = _framed_stream(99, inflight * 1024)
        assert link.send(0, first) == 0
        assert _wait(lambda: link._seq == inflight and not link._driving)
        frames, stream = _framed_stream(backlog, backlog * 1024 - 100)
        assert link.send(0, stream) == 0
        if held:
            # nothing is cut while the first train is out, however long
            time.sleep(0.3)
            assert link._seq == inflight and link._driving
        else:
            # cut at once, the first train still undelivered
            assert _wait(lambda: link._seq >= inflight + then[0])
        assert sinks[1].nbytes == 0
        gate.set()
        assert _wait(lambda: sinks[1].nbytes == len(first) + len(stream), timeout=30)
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        assert sinks[1].frames() == first_frames + frames
        assert [k for k, _, _ in cuts] == [inflight] + then
        m = _link_recorders(link)
        assert dl.link_held.get_value() - held_before == (1 if held else 0)
        assert m["hold"].count() == len(cuts)
        if held:
            # the one held train waited out the gate; the hold lies before
            # its dispatch, so the five stages still add up to step_rtt
            assert m["hold"].latency_sum() == m["hold"].max_latency() >= 250_000
            assert _stages_sum(m) == pytest.approx(m["rtt"].latency_sum(), rel=1e-6)
        else:
            assert m["hold"].max_latency() == 0

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_every_train_is_the_wanted_one_or_alone_in_flight(self, geometry):
        link, socks, sinks = self._make_link(geometry, window=8)
        cuts = self._spy_trains(link)
        frames, stream = _framed_stream(11, 300 * 1024 - 77)
        sends = [stream[i : i + 3000] for i in range(0, len(stream), 3000)]

        def sender():
            for chunk in sends:
                assert link.send(0, chunk, timeout=60) == 0

        t = threading.Thread(target=sender)
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        assert _wait(lambda: sinks[1].nbytes == len(stream), timeout=60)
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        assert sinks[1].frames() == frames
        assert sum(k for k, _, _ in cuts) == link._seq >= 300
        for k, wanted, out in cuts:
            assert k == wanted or out == 0, (k, wanted, out)
        assert max(k for k, _, _ in cuts) == 8  # a standing backlog rode the window

    @pytest.mark.parametrize("window", [1, 2, 4, 8])
    @pytest.mark.parametrize("ack_mode", ["local", "wire"])
    def test_two_way_residues_drain_without_a_timeout(self, ack_mode, window):
        from incubator_brpc_tpu.runtime.butex import ETIMEDOUT, Butex

        link, socks, sinks = self._make_link(window=window, ack_mode=ack_mode)
        waits = []

        class SpyButex(Butex):
            def wait(self, *a, **k):
                waits.append(super().wait(*a, **k))
                return waits[-1]

        link._wbutex = SpyButex(0)  # nothing waits on a link just made
        a, b = _framed_stream(5, 37 * 1024 + 13), _framed_stream(6, 21 * 1024 - 5)

        def sender(side, stream):
            # sends of 2.5 slots: every one leaves an odd residue behind
            for i in range(0, len(stream), 2560):
                assert link.send(side, stream[i : i + 2560], timeout=60) == 0

        threads = [
            threading.Thread(target=sender, args=(0, a[1])),
            threading.Thread(target=sender, args=(1, b[1])),
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert _wait(lambda: sinks[1].nbytes == len(a[1]), timeout=60)
        assert _wait(lambda: sinks[0].nbytes == len(b[1]), timeout=60)
        assert sinks[1].frames() == a[0] and sinks[0].frames() == b[0]
        # what _quiesce_links waits for, of this link alone: the drive left
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        assert ETIMEDOUT not in waits, (len(waits), time.monotonic() - t0)

    def test_fail_while_the_drive_holds_frees_drive_and_senders(self):
        link, socks, sinks = self._make_link(window=8)
        gate = self._gate_deliveries(link)
        try:
            assert link.send(0, b"f" * 4096) == 0
            assert _wait(lambda: link._seq == 4 and not link._driving)
            assert link.send(0, b"b" * (17 * 1024)) == 0  # over the send budget
            rcs = []
            t = threading.Thread(
                target=lambda: rcs.append(link.send(0, b"p" * 1024, timeout=30))
            )
            t.start()
            assert _wait(lambda: link._wbutex.has_waiters() and link._held_since_ns)
            assert link._seq == 4 and link._driving and t.is_alive()
            link.fail("injected while held")
            t.join(timeout=5)
            assert not t.is_alive() and rcs == [ErrorCode.EFAILEDSOCKET]
            assert _wait(lambda: not link._driving, timeout=5)
            assert link._seq == 4  # nothing was cut after the failure
        finally:
            gate.set()
        assert _wait(lambda: all(s.state != 0 for s in socks))

    def test_host_swap_never_holds(self):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        dev = jax.devices()[0]
        link = dl.DeviceLink([dev, dev], slot_words=self.SLOT_WORDS, window=8)
        assert link.geometry == "host-swap"
        sink = _FrameSink()
        dl.DeviceSocket(link, side=0, messenger=_FrameSink())
        dl.DeviceSocket(link, side=1, messenger=sink)
        held_before = dl.link_held.get_value()
        frames, stream = _framed_stream(9, 100 * 1024 + 3)
        for i in range(0, len(stream), 2560):
            assert link.send(0, stream[i : i + 2560], timeout=30) == 0
        assert _wait(lambda: sink.nbytes == len(stream))
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        assert sink.frames() == frames
        m = _link_recorders(link)
        assert m["hold"].count() == m["rtt"].count() == link._seq
        assert m["hold"].max_latency() == 0
        assert dl.link_held.get_value() == held_before


class TestHostCopyRequests:
    """PR 36: a train's host copies are asked for when the train is
    dispatched, not when the deliverer needs them."""

    _make_link = TestSlotTrains._make_link
    _gate_deliveries = staticmethod(TestSlotTrains._gate_deliveries)
    SLOT_WORDS = TestSlotTrains.SLOT_WORDS

    @staticmethod
    def _spy_requests(link):
        """Every ``_request_host`` of the link: the step output it was given
        and the single-device arrays the runtime was asked to copy under it."""
        from jax._src.array import ArrayImpl

        asked, inner = [], ArrayImpl._copy_single_device_array_to_host_async
        requests, request = [], link._request_host

        def copy_spy(arr):
            asked.append(id(arr))
            return inner(arr)

        def request_spy(out):
            asked.clear()
            ArrayImpl._copy_single_device_array_to_host_async = copy_spy
            try:
                request(out)
            finally:
                ArrayImpl._copy_single_device_array_to_host_async = inner
            requests.append((out, list(asked)))

        link._request_host = request_spy
        return requests

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_every_shard_is_asked_for_once_before_the_watcher_returns(self, geometry):
        link, socks, sinks = self._make_link(geometry, window=8)
        done, inner = [], link._on_step_done
        link._on_step_done = lambda seq, arrays, *a, **k: (
            done.append(arrays), inner(seq, arrays, *a, **k)
        )[1]
        gate, requests = self._gate_deliveries(link), self._spy_requests(link)
        frames, stream = _framed_stream(36, 8 * 1024)
        assert link.send(0, stream) == 0
        # the train is out and its watcher parked before _on_step_done: the
        # request was made on the drive's thread, at the dispatch
        assert _wait(lambda: link._seq == 8 and not link._driving)
        assert len(requests) == 1 and not done
        gate.set()
        assert _wait(lambda: sinks[1].nbytes == len(stream), timeout=30)
        assert sinks[1].frames() == frames
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        (out, asked), = requests
        assert done == [out]  # the array the deliverer reads is the one asked for
        if geometry == "ppermute":
            shards = out.addressable_shards
            assert len(shards) == 2
            assert sorted(asked) == sorted(id(s.data) for s in shards)
        else:
            assert asked == [id(out)]

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap", "host-swap"])
    def test_prefetched_steps_follow_steps_both_ways(self, geometry):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        if geometry == "host-swap":
            dev = jax.devices()[0]
            link = dl.DeviceLink([dev, dev], slot_words=self.SLOT_WORDS, window=8)
            sinks = (_FrameSink(), _FrameSink())
            for i in (0, 1):
                dl.DeviceSocket(link, side=i, messenger=sinks[i])
        else:
            link, socks, sinks = self._make_link(geometry, window=8)
        assert link.geometry == geometry
        dl._quiesce_links(timeout=5.0)  # earlier tests' links are idle
        before = (dl.link_steps.get_value(), dl.link_prefetched.get_value())
        a, b = _framed_stream(7, 45 * 1024 + 5), _framed_stream(8, 19 * 1024 - 3)
        threads = [
            threading.Thread(target=lambda s=s, d=d: link.send(s, d[1], timeout=60))
            for s, d in ((0, a), (1, b))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert _wait(lambda: sinks[1].nbytes == len(a[1]), timeout=60)
        assert _wait(lambda: sinks[0].nbytes == len(b[1]), timeout=60)
        assert sinks[1].frames() == a[0] and sinks[0].frames() == b[0]
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        steps = dl.link_steps.get_value() - before[0]
        assert steps >= 6
        # a program dispatched is a request made; the host swap dispatches none
        assert dl.link_prefetched.get_value() - before[1] == (
            0 if geometry == "host-swap" else steps
        )

    def test_one_mebibyte_echo_at_the_defaults_prefetches_every_train(self, echo_server):
        from incubator_brpc_tpu.transport import device_link as dl

        ch = _tpu_channel(echo_server)
        assert ch.call_method("EchoService", "Echo", b"warm").ok()
        link = ch._device_sock.link
        assert (link.slot_words, link.window, link.geometry) == (16384, 8, "ppermute")
        dl._quiesce_links(timeout=5.0)
        before = (dl.link_steps.get_value(), dl.link_prefetched.get_value())
        big = bytes(range(256)) * 4096
        cntl = ch.call_method("EchoService", "Echo", b"", attachment=big)
        assert cntl.ok(), cntl.error_text
        assert cntl.response_attachment == big
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
        steps = dl.link_steps.get_value() - before[0]
        assert 6 <= steps <= 12
        assert dl.link_prefetched.get_value() - before[1] == steps

    @pytest.mark.parametrize(
        "slots,window",
        [(1, 8), (2, 8), (3, 8), (4, 8), (8, 8), (17, 8), (22, 8), (8, 4), (5, 1)],
    )
    def test_the_byte_stream_is_the_one_a_link_without_the_request_carries(
        self, slots, window
    ):
        frames, stream = _framed_stream(slots, slots * 1024 - 100)
        got = []
        for request in (True, False):
            link, socks, sinks = self._make_link(window=window)
            if not request:
                link._request_host = lambda out: None  # the parent's link
            cuts = TestSlotTrains._spy_trains(link)
            assert link.send(0, stream, timeout=60) == 0
            assert _wait(lambda: sinks[1].nbytes == len(stream), timeout=60)
            assert _wait(lambda: link.inflight_steps == 0 and not link._driving)
            got.append(([k for k, _, _ in cuts], b"".join(sinks[1].chunks)))
        assert got[0] == got[1] == (_trains(slots, window), stream)
        assert sinks[1].frames() == frames

    def test_a_request_that_raises_is_a_dispatch_that_failed(self):
        link, socks, sinks = self._make_link(window=8)
        request, calls = link._request_host, []

        def failing(out):
            calls.append(out.shape[1])
            if len(calls) == 2:
                raise RuntimeError("injected transfer fault")
            request(out)

        link._request_host = failing
        rcs = []

        def sender():
            for _ in range(4):
                rcs.append(link.send(0, b"z" * (10 * 1024), timeout=30))

        t = threading.Thread(target=sender)
        t.start()
        t.join(timeout=20)
        assert not t.is_alive()  # no sender left parked
        assert _wait(lambda: link._closed, timeout=10)
        assert calls[0] == 8 and len(calls) == 2  # the second train's request
        assert ErrorCode.EFAILEDSOCKET in rcs
        assert _wait(lambda: all(s.state != 0 for s in socks))
        assert _wait(lambda: not link._driving)

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_the_handshake_warms_request_and_readback_at_every_length(
        self, geometry, monkeypatch
    ):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        warmed = []
        request, rows_to_host = dl.DeviceLink._request_host, dl.DeviceLink._rows_to_host
        monkeypatch.setattr(
            dl.DeviceLink, "_request_host",
            staticmethod(lambda out: (warmed.append(("request", out.shape[1])), request(out))[1]),
        )
        monkeypatch.setattr(
            dl.DeviceLink, "_rows_to_host",
            lambda self, arrays: (
                warmed.append(("read", arrays.shape[1])), rows_to_host(self, arrays)
            )[1],
        )
        compiles = []

        def listener(name, *_a, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            link, socks, sinks = self._make_link(geometry, window=8)
            assert warmed == [
                (what, k) for k in (1, 2, 4, 8) for what in ("request", "read")
            ]
            built = len(compiles)
            frames, stream = _framed_stream(3, 15 * 1024)
            assert link.send(0, stream, timeout=60) == 0
            assert _wait(lambda: sinks[1].nbytes == len(stream), timeout=60)
            assert sinks[1].frames() == frames
            # 8, 4, 2, 1: each asked for once and read once, none compiled
            live = warmed[8:]
            assert sorted(live) == sorted(
                (what, k) for k in (8, 4, 2, 1) for what in ("request", "read")
            )
            assert len(compiles) == built
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)


@contextlib.contextmanager
def _backend_compiles():
    """The backend compilations of the process while the block runs."""
    import jax

    compiles = []

    def listener(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


class _DirtyNumpy:
    """numpy as the link sees it, except that ``empty`` hands back memory
    full of a pattern: a word the fill leaves unwritten arrives as it."""

    PATTERN = 0xA5A5A5A5

    def __getattr__(self, name):
        import numpy as np

        return getattr(np, name)

    def empty(self, shape, dtype):
        import numpy as np

        return np.full(shape, self.PATTERN, dtype=dtype)


class TestTrainStagedOnce:
    """PR 38: a train is staged once. Both sides' slots are filled into one
    ``(2, k, width)`` host buffer and that buffer is what the exchange
    program is called with; on the host swap its halves are what the
    deliverer is handed."""

    SLOT_WORDS = TestSlotTrains.SLOT_WORDS
    GEOMETRIES = ["host-swap", "device-swap", "ppermute"]
    _queue_then_drive = staticmethod(TestSlotTrains._queue_then_drive)

    def _make_link(self, geometry, **kw):
        import jax

        from incubator_brpc_tpu.transport import device_link as dl

        if geometry != "host-swap":
            return TestSlotTrains._make_link(self, geometry, **kw)
        dev = jax.devices()[0]
        link = dl.DeviceLink([dev, dev], slot_words=self.SLOT_WORDS, **kw)
        assert link.geometry == geometry
        sinks = (_FrameSink(), _FrameSink())
        socks = [dl.DeviceSocket(link, side=i, messenger=sinks[i]) for i in (0, 1)]
        return link, socks, sinks

    @staticmethod
    def _uneven(k):
        """Side 0 fills ``k`` slots but for 100 bytes, side 1 half a slot
        less than half as many: both sides loaded, neither evenly."""
        a = _framed_stream(40 + k, k * 1024 - 100)
        b = _framed_stream(50 + k, max(1, k // 2) * 1024 - 512)
        return a, b

    def _exchange(self, link, sinks, a, b):
        had = (sinks[0].nbytes, sinks[1].nbytes)
        self._queue_then_drive(
            link, lambda: (link.send(0, a[1]), link.send(1, b[1]))
        )
        assert _wait(lambda: sinks[1].nbytes == had[1] + len(a[1]), timeout=60)
        assert _wait(lambda: sinks[0].nbytes == had[0] + len(b[1]), timeout=60)
        assert _wait(lambda: link.inflight_steps == 0 and not link._driving)

    @pytest.mark.parametrize("k", [1, 2, 4, 8])
    @pytest.mark.parametrize("geometry", GEOMETRIES)
    def test_both_sides_arrive_in_order_with_unwritten_words_zeroed(
        self, geometry, k, monkeypatch
    ):
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(geometry, window=8)
        monkeypatch.setattr(dl, "np", _DirtyNumpy())
        cuts = TestSlotTrains._spy_trains(link)
        arrived, deliver = [], link._deliver
        link._deliver = lambda rows: (
            arrived.append([np.array(r) for r in rows]), deliver(rows)
        )[1]
        a, b = self._uneven(k)
        self._exchange(link, sinks, a, b)
        # bytes out equal bytes in, in order, both ways
        assert b"".join(sinks[1].chunks) == a[1] and sinks[1].frames() == a[0]
        assert b"".join(sinks[0].chunks) == b[1] and sinks[0].frames() == b[0]
        # one train of k slots a side; the host swap keeps one slot a step
        assert [c[0] for c in cuts] == ([1] * k if geometry == "host-swap" else [k])
        base = dl.LINK_HEADER_WORDS * 4
        slots = [row for rows in arrived for side in rows for row in side]
        assert len(slots) == 2 * k
        short = 0
        for row in slots:
            assert int(row[0]) == dl.LINK_MAGIC
            assert not row[6 : dl.LINK_HEADER_WORDS].any()  # reserved words
            used = int(row[1])
            short += used < self.SLOT_WORDS * 4
            if geometry != "host-swap":
                # the whole row crossed the wire: nothing of the heap with it
                assert not row.view(np.uint8)[base + used :].any()
        # side 0's last slot, side 1's last and every empty slot behind it
        assert short == 1 + (k - max(1, k // 2) + 1)

    @pytest.mark.parametrize("k", [1, 2, 4, 8])
    @pytest.mark.parametrize("geometry", GEOMETRIES)
    def test_a_train_is_one_host_buffer_and_one_call_into_the_runtime(
        self, geometry, k, monkeypatch
    ):
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(geometry, window=8)
        driving = threading.local()
        placed, drive = [], link._drive

        def spied_drive():
            driving.on = True
            try:
                drive()
            finally:
                driving.on = False

        link._drive = spied_drive
        for name in ("device_put", "make_array_from_single_device_arrays"):
            inner = getattr(jax, name)
            monkeypatch.setattr(
                jax, name,
                lambda *a, _inner=inner, _name=name, **kw: (
                    placed.append(_name) if getattr(driving, "on", False) else None,
                    _inner(*a, **kw),
                )[1],
            )
        handed = []
        if geometry == "host-swap":
            done = link._on_step_done
            link._on_step_done = lambda seq, arrays, *r: (
                handed.append(arrays[1]), done(seq, arrays, *r)
            )[1]
        else:
            step = link._step
            link._step = lambda both: (handed.append(both), step(both))[1]
        dl._quiesce_links(timeout=5.0)  # earlier tests' links are idle
        before = (dl.link_steps.get_value(), dl.link_staged.get_value())
        a, b = self._uneven(k)
        self._exchange(link, sinks, a, b)
        assert sinks[1].frames() == a[0] and sinks[0].frames() == b[0]
        steps = dl.link_steps.get_value() - before[0]
        # no staging call from the drive: the program call places the buffer
        assert placed == []
        if geometry == "host-swap":
            # no program, nothing staged: the deliverer is handed the two
            # halves of the one buffer, the peer's first
            assert steps == k and dl.link_staged.get_value() == before[1]
            for rows in handed:
                assert rows[0].base is rows[1].base
                assert rows[0].base.shape == (2, 1, link._width)
                assert np.shares_memory(rows[0], rows[0].base[1])
                assert np.shares_memory(rows[1], rows[1].base[0])
            return
        assert steps == 1 and dl.link_staged.get_value() - before[1] == 1
        (both,) = handed
        assert type(both) is np.ndarray and both.dtype == np.uint32
        assert both.shape == (2, k, link._width)

    @pytest.mark.parametrize("window", [8, 4])
    @pytest.mark.parametrize("geometry", ["device-swap", "ppermute"])
    def test_the_handshake_warms_the_host_buffer_call_at_every_length(
        self, geometry, window
    ):
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        warmed = []
        with _backend_compiles() as compiles:
            link, socks, sinks = self._make_link(geometry, window=window)
            built = len(compiles)
            lengths = [k for k in (1, 2, 4, 8) if k <= window]
            assert built >= len(lengths)  # one program a train length
            # _warm_step calls what _drive calls, with what _drive hands it
            step = link._step
            link._step = lambda both: (warmed.append(both), step(both))[1]
            link._warm_step()
            assert [(type(w), w.shape) for w in warmed] == [
                (np.ndarray, (2, k, link._width)) for k in lengths
            ]
            assert len(compiles) == built
            cuts = TestSlotTrains._spy_trains(link)
            before = dl.link_staged.get_value()
            for k in lengths:
                self._exchange(link, sinks, *self._uneven(k))
            assert [c[0] for c in cuts] == lengths
            assert dl.link_staged.get_value() - before >= len(lengths)
            assert len(compiles) == built  # no train of live traffic compiled

    def test_the_step_takes_a_committed_array_of_its_sharding_as_it_is(self):
        """What ``MultiControllerLink`` hands the inherited step: a global
        array already laid out over the link's two devices."""
        import jax
        import numpy as np

        link, socks, sinks = self._make_link("ppermute", window=8)
        rows = np.arange(2 * 4 * link._width, dtype=np.uint32).reshape(2, 4, -1)
        shards = [jax.device_put(rows[i][None], link.devices[i]) for i in (0, 1)]
        placed = jax.make_array_from_single_device_arrays(
            rows.shape, link._sharding, shards
        )
        out = link._step(placed)
        assert out.sharding == link._sharding
        assert np.array_equal(np.asarray(out), rows[::-1])
        # and the same rows as a host buffer give the same exchange
        assert np.array_equal(np.asarray(link._step(rows)), rows[::-1])


class TestLane:
    """The link's second program (PR 39). A committed array on the sender's
    device lands on the receiver's by one ``ppermute`` and is handed over
    as a device array, its tag's words beside it from the same program
    (PR 40); the byte stream beside it is as it was. The program is an
    exchange (PR 45): a launch takes the head message of each direction."""

    SLOT_WORDS = TestSlotTrains.SLOT_WORDS
    _make_link = TestSlotTrains._make_link

    LANE = {
        "lane_step_us", "lane_launch_us", "lane_ready_us", "lane_pair_wait_us",
        "lane_deliver_us", "lane_launch_cpu_us",
    }

    @staticmethod
    def _names(link):
        from incubator_brpc_tpu.bvar import expose_registry

        pfx = f"device_link_{link.link_id}_"
        return {name[len(pfx):] for name, _ in expose_registry.snapshot(pfx)}

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_only_a_link_between_two_devices_has_the_lane_and_its_names(self, geometry):
        import numpy as np

        from incubator_brpc_tpu import bvar

        link, socks, sinks = self._make_link(geometry)
        feed = f"device_link_{link.link_id}_lane_steps"
        if geometry == "ppermute":
            assert link.has_lane and socks[0].lane is link
            assert self.LANE <= self._names(link) and feed in bvar.feeds()
            assert bvar.feeds()[feed].worker == (
                ("taken", "launched"), ("paired", "queued"))
        else:
            assert not link.has_lane and socks[0].lane is None
            assert not self.LANE & self._names(link) and feed not in bvar.feeds()
            with pytest.raises(ValueError):
                link.warm_lane(0, (8,), np.uint32)
        link.fail("retire")
        assert not self._names(link)  # the lane's recorders retire with the rest

    @staticmethod
    def _receive(sock):
        """Keeps what the lane hands ``sock``: ``[(tag bytes, body)]``."""
        got = []
        sock.lane_receiver = lambda _sock, tag, body: got.append(
            (tag.tobytes(), body))
        return got

    @staticmethod
    def _padded(tag: bytes) -> bytes:
        from incubator_brpc_tpu.transport.device_link import LANE_TAG_BYTES

        return tag.ljust(LANE_TAG_BYTES, b"\0")

    @pytest.mark.parametrize("side", [0, 1])
    def test_an_array_crosses_either_way_beside_the_byte_stream(self, side):
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link("ppermute", window=8)
        data = np.random.default_rng(side).integers(
            0, 2**32, size=(3, 500), dtype=np.uint32)
        block = jax.device_put(data, link.devices[side])
        assert link.lane_accepts(side, block)
        assert not link.lane_accepts(1 - side, block)  # not that side's device
        assert not link.lane_accepts(side, data)  # host memory
        counted = ("link_bytes", "lane_bytes", "lane_steps", "lane_messages",
                   "lane_tagged")
        before = {a: getattr(dl, a).get_value() for a in counted}
        got = self._receive(socks[1 - side])
        frames, stream = _framed_stream(39, 20 * 1024)
        assert link.send(side, stream) == 0
        tags = [b"tag %d of side %d" % (i, side) for i in range(3)]
        for tag in tags:
            assert link.lane_send(side, block, tag) == 0
        assert _wait(lambda: len(got) == 3, timeout=30)
        assert [tag for tag, _ in got] == [self._padded(tag) for tag in tags]
        for _tag, landed in got:
            assert isinstance(landed, jax.Array)
            assert landed.devices() == {link.devices[1 - side]}
            assert (landed.shape, landed.dtype) == (data.shape, data.dtype)
            assert np.array_equal(np.asarray(landed), data)
        assert _wait(lambda: sinks[1 - side].nbytes == len(stream), timeout=30)
        assert sinks[1 - side].frames() == frames
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        # the bodies' nbytes and nothing else: the tags are counted nowhere
        assert gained == {
            "link_bytes": len(stream), "lane_bytes": 3 * data.nbytes,
            "lane_steps": 3, "lane_messages": 3, "lane_tagged": 3}
        assert _wait(lambda: link._lane_inflight == 0)
        # a tag too long is refused and takes no place in the lane's order
        seqs = list(link._lane_seq)
        assert link.lane_send(
            side, block, b"x" * (dl.LANE_TAG_BYTES + 1)) == ErrorCode.EINVAL
        assert link._lane_seq == seqs and link._lane_inflight == 0

    @staticmethod
    def _first_one_late(link, hook):
        """The completion ``hook`` of what was taken first (seq 0, a train's
        or a lane message's) waits for the returned event before it runs:
        the watchers of what was taken after it finish before its own."""
        release, inner = threading.Event(), getattr(link, hook)

        def late(first, *rest):
            head = first[0] if isinstance(first, list) else first  # a program's messages
            if getattr(head, "seq", head) == 0:
                assert release.wait(30)
            return inner(first, *rest)

        setattr(link, hook, late)
        return release

    def _three_out_the_first_one_late(self, carrier):
        """Three programs of one carrier out, side 0 to side 1, the first
        one's completion held: ``(link, the carrier's in-order hand-over,
        release, handed, sent)``; ``handed()`` is what side 1 was handed so
        far, ``sent`` what it is handed in the end."""
        import jax
        import numpy as np

        link, socks, sinks = self._make_link("ppermute", window=8)
        if carrier == "lane":
            block = jax.device_put(np.arange(64, dtype=np.uint32), link.devices[0])
            link.warm_lane(0, block.shape, block.dtype)
            got = self._receive(socks[1])
            release = self._first_one_late(link, "_lane_landed")
            tags = [b"message %d" % i for i in range(3)]
            for tag in tags:
                assert link.lane_send(0, block, tag) == 0
            return (link, link._lanes[1], release,
                    lambda: [tag for tag, _ in got], [self._padded(t) for t in tags])
        release = self._first_one_late(link, "_on_step_done")
        frames = [bytes([i]) * 500 for i in range(3)]
        for i, frame in enumerate(frames):  # a train of one slot each
            assert link.send(0, len(frame).to_bytes(4, "little") + frame) == 0
            assert _wait(lambda: link._seq == i + 1 and not link._driving, timeout=30)
        return link, link._trains, release, sinks[1].frames, frames

    @pytest.mark.parametrize("carrier", ["trains", "lane"])
    def test_a_carrier_hands_over_in_the_order_taken_when_watchers_finish_in_reverse(
        self, carrier
    ):
        """Completion watchers finish out of order: the first program's is
        kept before its hook until the two after it have landed, which wait
        for its turn. The one loop serves both carriers."""
        link, order, release, handed, sent = self._three_out_the_first_one_late(carrier)
        assert _wait(lambda: set(order.waiting) == {1, 2}, timeout=30)
        assert handed() == [] and order.next == 0
        release.set()
        assert _wait(lambda: len(handed()) == 3, timeout=30)
        assert handed() == sent
        assert not order.waiting and order.next == 3
        assert [lane.next for lane in link._lanes] == [0, 3 * (carrier == "lane")]
        assert _wait(lambda: not link.busy)

    @pytest.mark.parametrize("carrier", ["trains", "lane"])
    def test_a_link_failed_between_a_landing_and_its_turn_hands_nothing_over(
        self, carrier
    ):
        """What landed and waits for its turn is dropped by ``fail()``, and
        what lands on a failed link is not kept: neither carrier hands the
        sockets anything more, and nothing stays counted in flight."""
        from incubator_brpc_tpu.transport import device_link as dl

        link, order, release, handed, _sent = self._three_out_the_first_one_late(carrier)
        assert _wait(lambda: set(order.waiting) == {1, 2}, timeout=30)
        link.fail("injected link failure")
        assert order.waiting is None
        release.set()  # the first one lands on the failed link
        assert _wait(lambda: not link.busy, timeout=30)
        assert handed() == [] and order.next == 0 and order.waiting is None
        assert link.inflight_steps == 0 and link._lane_inflight == 0
        started = time.monotonic()
        dl._quiesce_links(timeout=5.0)
        assert time.monotonic() - started < 1.0

    def test_the_tag_handed_over_is_what_was_read_back_from_the_receivers_shard(self):
        """Not the sender's Python object: a double that alters the shard's
        host copy alters what the socket is handed."""
        import jax
        import numpy as np

        link, socks, sinks = self._make_link("ppermute")
        block = jax.device_put(np.arange(64, dtype=np.uint32), link.devices[0])
        link.warm_lane(0, block.shape, block.dtype)
        got = self._receive(socks[1])
        seen, read_back = [], link._tag_to_host

        def altered(landed):
            assert landed.devices() == {link.devices[1]}
            words = read_back(landed).copy()
            seen.append(words.tobytes())
            words[0] ^= 0xFFFFFFFF
            return words

        link._tag_to_host = altered
        assert link.lane_send(0, block, b"as the sender gave it") == 0
        assert _wait(lambda: len(got) == 1, timeout=30)
        assert seen == [self._padded(b"as the sender gave it")]
        handed = np.frombuffer(got[0][0], np.uint32)
        sent = np.frombuffer(seen[0], np.uint32)
        assert handed[0] == sent[0] ^ 0xFFFFFFFF
        assert np.array_equal(handed[1:], sent[1:])

    def test_every_message_hands_the_program_a_host_buffer_of_its_own(self):
        """The tags operand is made anew for each message, equal tags in a
        row too: the runtime may still be reading the one before. Each is
        handed over as sent."""
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link("ppermute")
        block = jax.device_put(np.arange(64, dtype=np.uint32), link.devices[0])
        link.warm_lane(0, block.shape, block.dtype)
        key = ((64,), "uint32")
        program, placeholders, shards = link._lane_programs[key]
        operands = []

        def seen(halves, tags):
            operands.append(tags)
            return program(halves, tags)

        link._lane_programs[key] = (seen, placeholders, shards)
        got = self._receive(socks[1])
        sent = [b"one"] * 3 + [b"two", b"one"]
        for tag in sent:
            assert link.lane_send(0, block, tag) == 0
        assert _wait(lambda: len(got) == len(sent), timeout=30)
        assert [tag for tag, _ in got] == [self._padded(tag) for tag in sent]
        assert all(
            type(tags) is np.ndarray and tags.shape == (2, dl.LANE_TAG_WORDS)
            for tags in operands)
        assert len({id(tags) for tags in operands}) == len(sent)
        for tags, tag in zip(operands, sent):  # the receiver's row stays zero
            assert tags[0].tobytes() == self._padded(tag) and not tags[1].any()

    def test_the_program_keeps_its_name_and_every_recorder_has_a_row_a_program(self):
        import jax
        import numpy as np

        from incubator_brpc_tpu import bvar
        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link("ppermute")
        block = jax.device_put(np.arange(64, dtype=np.uint32), link.devices[0])
        link.warm_lane(0, block.shape, block.dtype)
        program, placeholders, _shards = link._lane_programs[((64,), "uint32")]
        lowered = program.lower(
            link._lane_operand([block, placeholders[1]]),
            np.zeros((2, dl.LANE_TAG_WORDS), np.uint32))
        # benchmark/roofline_lane.py finds the program's executions by it
        assert "jit_device_link_lane" in lowered.as_text()
        got = self._receive(socks[1])
        before = {a: getattr(dl, a).get_value() for a in ("lane_steps", "lane_tagged")}
        programs = 2 * bvar.CPU_CLOCK_EVERY
        for i in range(programs):
            assert link.lane_send(0, block, b"%d" % i) == 0
        assert _wait(lambda: len(got) == programs, timeout=30)
        link._lane_feed.flush()
        rows = {
            what: recorder.count()
            for (recorder, *_), (what, *_) in zip(link._lane_feed.columns, dl.LANE_COLUMNS)
        }
        cpu = rows.pop("launch_cpu_us")  # one program in CPU_CLOCK_EVERY
        assert set(rows) == {"step_us", "launch_us", "ready_us", "pair_wait_us",
                             "deliver_us"}
        assert set(rows.values()) == {programs} and cpu == 2
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        assert gained == {"lane_steps": programs, "lane_tagged": programs}
        stamps, kept = link._lane_feed.timeline()
        at = {s: i for i, s in enumerate(stamps)}
        for row in kept:
            order = [row[at[s]] for s in
                     ("taken", "launched", "ready", "paired", "queued")]
            assert order == sorted(order) and row[at["nbytes"]] == block.nbytes
        assert sorted(kept[:, at["seq"]]) == list(range(programs))

    def test_a_lane_dispatch_that_raises_fails_the_link_and_leaves_nothing_in_flight(self):
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link("ppermute")
        block = jax.device_put(np.arange(64, dtype=np.uint32), link.devices[0])
        link.warm_lane(0, block.shape, block.dtype)
        key = ((64,), "uint32")
        _program, placeholders, shards = link._lane_programs[key]

        def raising(*_operands):
            raise RuntimeError("injected lane fault")

        link._lane_programs[key] = (raising, placeholders, shards)
        got = self._receive(socks[1])
        assert link.lane_send(0, block, b"tag") == ErrorCode.EFAILEDSOCKET
        assert link._closed and link._lane_inflight == 0
        assert not any(lane.waiting for lane in link._lanes)
        # CONNECTED == 0: the sockets went down with the link
        assert all(s.state != 0 for s in socks) and not got
        # a dead link takes nothing
        assert link.lane_send(0, block, b"tag") == ErrorCode.EFAILEDSOCKET
        assert link._lane_seq == [0, 1]
        started = time.monotonic()
        dl._quiesce_links(timeout=5.0)
        assert time.monotonic() - started < 1.0

    # -- the exchange (PR 45): a launch takes the head of each direction ------

    @staticmethod
    def _sent_with_the_order_held(link, sends):
        """``lane_send(*send)`` for each of ``sends`` on a thread of its
        own, started in that order while the test holds the process's launch
        order, each on its direction's queue before the next starts: what
        the first launch finds waiting is all of them. Their codes."""
        from incubator_brpc_tpu.parallel.collective import launch_order

        codes, threads = [None] * len(sends), []

        def run(i, send):
            codes[i] = link.lane_send(*send)

        with launch_order:
            for i, send in enumerate(sends):
                threads.append(threading.Thread(target=run, args=(i, send), daemon=True))
                threads[-1].start()
                assert _wait(lambda: sum(map(len, link._lane_out)) == i + 1, timeout=30)
        for thread in threads:
            thread.join(30)
            assert not thread.is_alive()
        return codes

    # (side, shape, dtype) a send, in the order queued; the programs they ride
    EXCHANGES = {
        "a_request_and_an_answer_ride_one_program": (
            [(0, (64,), "uint32"), (1, (64,), "uint32")], 1),
        "led_by_the_other_side": ([(1, (64,), "uint32"), (0, (64,), "uint32")], 1),
        "rows_of_more_than_one_dimension": (
            [(0, (3, 50), "uint32"), (1, (3, 50), "uint32")], 1),
        "different_shapes_ride_alone": ([(0, (64,), "uint32"), (1, (32,), "uint32")], 2),
        "different_dtypes_ride_alone": ([(0, (64,), "uint32"), (1, (64,), "float32")], 2),
        "one_direction_only_rides_alone": ([(0, (64,), "uint32"), (0, (64,), "uint32")], 2),
        "two_each_way_ride_two": ([(0, (64,), "uint32")] * 2 + [(1, (64,), "uint32")] * 2, 2),
        "three_against_one_ride_three": (
            [(1, (64,), "uint32")] * 3 + [(0, (64,), "uint32")], 3),
    }

    @pytest.mark.parametrize("case", list(EXCHANGES))
    def test_a_launch_takes_the_head_of_each_direction(self, case):
        """With the order held by the test: what waits each way of one
        shape and dtype crosses in one program, each side handed its own
        tags and bodies in the order sent; anything else rides alone. A
        row a message, a program's two rows sharing ``taken`` and
        ``launched``."""
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        spec, programs = self.EXCHANGES[case]
        link, socks, sinks = self._make_link("ppermute")
        rng = np.random.default_rng(45)
        sends, sent = [], ([], [])
        for i, (side, shape, dtype) in enumerate(spec):
            link.warm_lane(side, shape, dtype)
            data = rng.integers(0, 2**31, size=shape).astype(dtype)
            tag = b"message %d from side %d" % (i, side)
            sends.append((side, jax.device_put(data, link.devices[side]), tag))
            sent[1 - side].append((self._padded(tag), data))
        got = [self._receive(sock) for sock in socks]
        counted = ("lane_steps", "lane_messages", "lane_tagged", "lane_bytes")
        before = {a: getattr(dl, a).get_value() for a in counted}
        assert self._sent_with_the_order_held(link, sends) == [0] * len(sends)
        assert _wait(lambda: [len(g) for g in got] == [len(x) for x in sent], timeout=30)
        for side in (0, 1):
            assert [tag for tag, _ in got[side]] == [tag for tag, _ in sent[side]]
            for (_tag, landed), (_sent_tag, data) in zip(got[side], sent[side]):
                assert isinstance(landed, jax.Array)
                assert landed.devices() == {link.devices[side]}
                assert (landed.shape, landed.dtype) == (data.shape, data.dtype)
                assert np.array_equal(np.asarray(landed), data)
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        assert gained == {
            "lane_steps": programs, "lane_messages": len(sends),
            "lane_tagged": programs,
            "lane_bytes": sum(array.nbytes for _side, array, _tag in sends)}
        assert _wait(lambda: not link.busy)
        assert not any(link._lane_out) and not link._lane_launching
        link._lane_feed.flush()
        stamps, kept = link._lane_feed.timeline()
        at = {s: i for i, s in enumerate(stamps)}
        assert len(kept) == len(sends)  # a row a message
        launches = {(row[at["taken"]], row[at["launched"]]) for row in kept}
        assert len(launches) == programs
        for row in kept:
            order = [row[at[s]] for s in ("taken", "launched", "ready", "paired", "queued")]
            assert order == sorted(order)

    def test_eight_threads_four_a_direction_keep_each_directions_order(self):
        """Senders of both directions meet at the launch as they come: every
        message is handed over once, on its own side, in the order
        ``lane_send`` took it, and no sender is left parked."""
        import jax
        import numpy as np

        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link("ppermute")
        link.warm_lane(0, (64,), np.uint32)
        got = [self._receive(sock) for sock in socks]
        handed, hand_over = ([], []), link._hand_over_message

        def seen(seq, step):
            handed[step.to].append(seq)
            return hand_over(seq, step)

        link._hand_over_message = seen
        for lane in link._lanes:
            lane._hand_over = seen
        each, codes = 6, []
        before = {a: getattr(dl, a).get_value() for a in ("lane_steps", "lane_messages")}

        def sender(side, thread):
            for i in range(each):
                block = jax.device_put(
                    np.full(64, 1000 * thread + i, np.uint32), link.devices[side])
                codes.append(link.lane_send(side, block, b"%d %d" % (thread, i)))

        threads = [
            threading.Thread(target=sender, args=(t % 2, t), daemon=True) for t in range(8)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(60)
            assert not thread.is_alive()
        assert codes == [0] * (8 * each)
        assert _wait(lambda: [len(g) for g in got] == [4 * each] * 2, timeout=60)
        assert handed == (list(range(4 * each)), list(range(4 * each)))
        for side in (0, 1):
            last = {}
            for tag, body in got[side]:
                thread, i = map(int, tag.rstrip(b"\0").split())
                assert thread % 2 == 1 - side and i == last.get(thread, -1) + 1
                last[thread] = i
                assert body.devices() == {link.devices[side]}
                assert np.asarray(body)[0] == 1000 * thread + i
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        assert gained["lane_messages"] == 8 * each
        assert 4 * each <= gained["lane_steps"] <= 8 * each
        assert _wait(lambda: not link.busy)
        assert not any(link._lane_out) and not link._lane_launching

    def test_a_program_that_raises_fails_both_its_messages_and_whoever_waits(self):
        import jax
        import numpy as np

        link, socks, sinks = self._make_link("ppermute")
        link.warm_lane(0, (64,), np.uint32)
        key = ((64,), "uint32")
        _program, placeholders, shards = link._lane_programs[key]

        def raising(*_operands):
            raise RuntimeError("injected lane fault")

        link._lane_programs[key] = (raising, placeholders, shards)
        got = [self._receive(sock) for sock in socks]
        blocks = [jax.device_put(np.arange(64, dtype=np.uint32), d) for d in link.devices]
        # the first launch carries the heads; the third message waits behind it
        codes = self._sent_with_the_order_held(link, [
            (0, blocks[0], b"request"), (1, blocks[1], b"answer"),
            (0, blocks[0], b"the next request")])
        assert codes == [ErrorCode.EFAILEDSOCKET] * 3
        assert link._closed and link._lane_inflight == 0 and not any(link._lane_out)
        assert all(s.state != 0 for s in socks) and got == [[], []]
        assert link.lane_send(1, blocks[1], b"tag") == ErrorCode.EFAILEDSOCKET

    def test_warm_lane_leaves_nothing_to_compile_paired_or_alone(self):
        import jax
        import numpy as np

        with _backend_compiles() as compiles:
            link, socks, sinks = self._make_link("ppermute")
            link.warm_lane(0, (64,), np.uint32)
            built, programs = len(compiles), dict(link._lane_programs)
            assert list(programs) == [((64,), "uint32")]  # one program, either way
            link.warm_lane(1, (64,), np.uint32)
            got = [self._receive(sock) for sock in socks]
            blocks = [
                jax.device_put(np.arange(64, dtype=np.uint32), d) for d in link.devices]
            for side in (0, 1):
                assert link.lane_send(side, blocks[side], b"alone") == 0
            assert self._sent_with_the_order_held(
                link, [(0, blocks[0], b"paired"), (1, blocks[1], b"paired")]) == [0, 0]
            assert _wait(lambda: [len(g) for g in got] == [2, 2], timeout=30)
            assert len(compiles) == built and link._lane_programs == programs

    @pytest.mark.parametrize("geometry", ["ppermute", "device-swap"])
    def test_a_train_whose_dispatch_raises_gives_its_slots_back(self, geometry):
        """ROADMAP D10's small fault: the slots a failed dispatch took stayed
        on ``_inflight``, so the idle check at exit waited out its whole
        timeout on a dead link."""
        from incubator_brpc_tpu.transport import device_link as dl

        link, socks, sinks = self._make_link(geometry, window=8)

        def failing(slots):
            raise RuntimeError("injected device fault")

        link._step = failing
        assert link.send(0, b"z" * (5 * 1024)) == 0  # a train of four slots
        assert _wait(lambda: link._closed, timeout=10)
        assert _wait(lambda: not link._driving)
        assert link.inflight_steps == 0 and not link._trains.waiting
        started = time.monotonic()
        dl._quiesce_links(timeout=5.0)
        assert time.monotonic() - started < 1.0
