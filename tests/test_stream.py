"""Streaming RPC tests (reference test/brpc_streaming_rpc_unittest.cpp:
handshake, ordered delivery, credit-window flow control, close)."""

import threading
import time

import pytest

from incubator_brpc_tpu.rpc import (
    Channel,
    Server,
    StreamHandler,
    StreamOptions,
    stream_accept,
    stream_create,
)
from incubator_brpc_tpu.rpc import stream as stream_mod
from incubator_brpc_tpu.utils.status import ErrorCode


class Recorder(StreamHandler):
    def __init__(self, delay=0.0):
        self.messages = []
        self.closed = threading.Event()
        self.failed = threading.Event()
        self.delay = delay

    def on_received_messages(self, stream, messages):
        if self.delay:
            time.sleep(self.delay)
        self.messages.extend(messages)

    def on_closed(self, stream):
        self.closed.set()

    def on_failed(self, stream, code, reason):
        self.failed.set()
        self.closed.set()


@pytest.fixture
def echo_server():
    server = Server()
    accepted = {}

    def open_stream(cntl, request):
        opts = StreamOptions(handler=accepted.get("handler") or Recorder())
        s = stream_accept(cntl, opts)
        assert s is not None
        accepted["stream"] = s
        return b"accepted"

    def plain(cntl, request):
        return request

    server.add_service("test", {"open_stream": open_stream, "plain": plain})
    assert server.start(0)
    yield server, accepted
    server.stop()
    server.join(timeout=5)


def _connect(server, accepted, handler=None, client_opts=None):
    ch = Channel()
    assert ch.init(f"127.0.0.1:{server.port}")
    accepted["handler"] = handler
    s = stream_create(client_opts or StreamOptions(handler=Recorder()))
    cntl = ch.call_method("test", "open_stream", b"", request_stream=s)
    assert cntl.ok(), cntl.error_text
    assert s.wait_connected(timeout=5)
    return ch, s


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


class TestHandshake:
    def test_accept_connects_both_sides(self, echo_server):
        server, accepted = echo_server
        _, s = _connect(server, accepted, handler=Recorder())
        srv_stream = accepted["stream"]
        assert s.state == stream_mod.CONNECTED
        assert srv_stream.state == stream_mod.CONNECTED
        assert s.remote_id == srv_stream.id
        assert srv_stream.remote_id == s.id
        s.close()

    def test_unaccepted_stream_fails(self, echo_server):
        server, accepted = echo_server
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        s = stream_create(StreamOptions(handler=Recorder()))
        # "plain" never calls stream_accept → response meta has no stream id
        cntl = ch.call_method("test", "plain", b"x", request_stream=s)
        assert cntl.ok()
        assert _wait(lambda: s.state == stream_mod.CLOSED)
        assert s.write(b"data") == ErrorCode.EINVAL

    def test_failed_rpc_kills_stream(self, echo_server):
        server, accepted = echo_server
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        s = stream_create(StreamOptions(handler=Recorder()))
        cntl = ch.call_method("test", "nosuch", b"", request_stream=s)
        assert cntl.failed()
        assert _wait(lambda: s.state == stream_mod.CLOSED)


class TestDataPath:
    def test_ordered_delivery_client_to_server(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(server, accepted, handler=rec)
        msgs = [f"msg-{i}".encode() for i in range(50)]
        for m in msgs:
            assert s.write(m) == 0
        assert _wait(lambda: len(rec.messages) == 50)
        assert rec.messages == msgs
        s.close()

    def test_bidirectional(self, echo_server):
        server, accepted = echo_server
        client_rec = Recorder()
        _, s = _connect(
            server,
            accepted,
            handler=Recorder(),
            client_opts=StreamOptions(handler=client_rec),
        )
        srv_stream = accepted["stream"]
        assert srv_stream.write(b"from-server") == 0
        assert _wait(lambda: client_rec.messages == [b"from-server"])
        s.close()

    def test_large_messages(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(server, accepted, handler=rec)
        big = bytes(range(256)) * 4096  # 1 MiB
        assert s.write(big, timeout=10) == 0
        assert _wait(lambda: rec.messages == [big])
        s.close()


class TestFlowControl:
    def test_window_blocks_writer_and_feedback_resumes(self, echo_server):
        """The core credit-window property (stream.cpp:263-300): a slow
        consumer stalls the writer at max_buf_size; its feedback un-stalls."""
        server, accepted = echo_server
        rec = Recorder(delay=0.15)  # slow consumer
        _, s = _connect(server, accepted, handler=rec)
        s.options.max_buf_size = 4096
        chunk = b"x" * 2048

        # two chunks fill the window; the third must hit EAGAIN immediately
        assert s.write(chunk) == 0
        assert s.write(chunk) == 0
        assert s.write(chunk, timeout=0) == ErrorCode.EAGAIN
        assert s.unconsumed_bytes == 4096

        # blocking write parks until the consumer's feedback lifts the window
        t0 = time.monotonic()
        assert s.write(chunk, timeout=10) == 0
        waited = time.monotonic() - t0
        assert waited > 0.05  # it actually blocked on the butex
        assert _wait(lambda: len(rec.messages) == 3)
        s.close()

    def test_unlimited_window_never_blocks(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(
            server, accepted, handler=rec,
        )
        s.options.max_buf_size = 0
        for _ in range(20):
            assert s.write(b"y" * 1024, timeout=0) == 0
        assert _wait(lambda: len(rec.messages) == 20)
        s.close()


class TestClose:
    def test_close_notifies_peer_after_data(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(server, accepted, handler=rec)
        s.write(b"last-words")
        s.close()
        assert rec.closed.wait(timeout=5)
        assert rec.messages == [b"last-words"]  # data seen before close
        assert s.state == stream_mod.CLOSED
        assert s.write(b"after") == ErrorCode.EINVAL

    def test_registry_cleanup(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(server, accepted, handler=rec)
        sid, srv_sid = s.id, accepted["stream"].id
        assert stream_mod.get_stream(sid) is not None
        s.close()
        assert rec.closed.wait(timeout=5)
        assert stream_mod.get_stream(sid) is None
        assert _wait(lambda: stream_mod.get_stream(srv_sid) is None)

    def test_socket_failure_fails_stream(self, echo_server):
        server, accepted = echo_server
        rec = Recorder()
        ch, s = _connect(server, accepted, handler=Recorder())
        # fail the client's underlying socket out from under the stream
        client_rec = Recorder()
        s2 = stream_create(StreamOptions(handler=client_rec))
        cntl = ch.call_method("test", "open_stream", b"", request_stream=s2)
        assert cntl.ok()
        assert s2.wait_connected(timeout=5)
        s2._sock.set_failed(ErrorCode.EFAILEDSOCKET, "injected")
        assert client_rec.failed.wait(timeout=5)
        assert s2.write(b"z") == ErrorCode.EINVAL


class TestOversizedMessage:
    def test_message_larger_than_window_still_goes_out(self, echo_server):
        # A single message bigger than max_buf_size must be admitted on an
        # idle stream (one in-flight message may overshoot the window;
        # reference AppendIfNotFull stream.cpp:263). Before the fix this
        # parked the writer forever.
        server, accepted = echo_server
        rec = Recorder()
        _, s = _connect(
            server,
            accepted,
            handler=rec,
            client_opts=StreamOptions(handler=Recorder(), max_buf_size=64 * 1024),
        )
        big = bytes(256 * 1024)  # 4x the window
        assert s.write(big, timeout=5) == 0
        assert _wait(lambda: len(rec.messages) == 1)
        assert rec.messages[0] == big
        # and the window still functions afterwards: feedback caught up
        assert _wait(lambda: s.unconsumed_bytes == 0)
        s.close()


class TestStreamOverDeviceLink:
    """Streaming RPC with transport='tpu': the handshake piggybacks on an
    RPC over the device link and stream frames ride the link's byte
    stream — the 'bidirectional tensor stream over ICI' row of SURVEY
    §2.5 running on the real device plane."""

    def test_stream_rides_the_device_link(self, echo_server):
        from incubator_brpc_tpu.rpc import ChannelOptions
        from incubator_brpc_tpu.transport.device_link import DeviceSocket

        server, accepted = echo_server
        rec = Recorder()
        accepted["handler"] = rec
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=60000),
        )
        s = stream_create(StreamOptions(handler=Recorder()))
        cntl = ch.call_method("test", "open_stream", b"", request_stream=s)
        assert cntl.ok(), cntl.error_text
        assert s.wait_connected(timeout=10)
        # the RPC (and therefore the stream frames) rode a DeviceSocket
        assert isinstance(ch._device_sock, DeviceSocket)
        blob = bytes(range(256)) * 64
        for i in range(20):
            assert s.write(b"%03d:" % i + blob, timeout=30) == 0
        assert _wait(lambda: len(rec.messages) == 20, timeout=30)
        assert rec.messages[0][:4] == b"000:"
        assert rec.messages[19][:4] == b"019:"
        assert all(m[4:] == blob for m in rec.messages)
        s.close()
        assert rec.closed.wait(10)


class TestRawMessages:
    """StreamOptions(raw_messages=True): handlers receive zero-copy IOBuf
    objects (the reference hands butil::IOBufs, stream.h) — and the
    contract holds on parse paths that materialized bytes (the wrap
    fallback in Stream._consume)."""

    def test_raw_handler_gets_iobufs_with_correct_content(self):
        import threading

        from incubator_brpc_tpu.iobuf import IOBuf
        from incubator_brpc_tpu.rpc import (
            Channel,
            Server,
            ServerOptions,
            StreamHandler,
            StreamOptions,
            stream_accept,
            stream_create,
        )

        got = []
        done = threading.Event()

        class RawSink(StreamHandler):
            def on_received_messages(self, s, msgs):
                got.extend(msgs)
                if sum(len(m) for m in got) >= 3 * 65536:
                    done.set()

        def open_stream(cntl, req):
            stream_accept(
                cntl, StreamOptions(handler=RawSink(), raw_messages=True)
            )
            return b""

        srv = Server(ServerOptions(usercode_inline=True))
        srv.add_service("raw", {"open": open_stream})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            s = stream_create(StreamOptions())
            c = ch.call_method("raw", "open", b"", request_stream=s)
            assert c.ok(), c.error_text
            assert s.wait_connected(5)
            msgs = [bytes([i]) * 65536 for i in range(3)]
            for m in msgs:
                assert s.write(m, timeout=10) == 0
            assert done.wait(10), "raw messages not delivered"
            # every delivered message is an IOBuf whose bytes round-trip
            assert all(not isinstance(m, (bytes, bytearray)) for m in got)
            assert [m.to_bytes() for m in got] == msgs or b"".join(
                m.to_bytes() for m in got
            ) == b"".join(msgs)
            s.close()
        finally:
            srv.stop()
            srv.join(timeout=10)

    def test_bytes_are_wrapped_for_raw_handlers(self):
        """Parse paths that produce bytes (pure-python fallback) still
        honor the IOBuf contract via the _consume wrap."""
        from incubator_brpc_tpu.rpc.stream import (
            FT_DATA,
            Stream,
            StreamHandler,
            StreamOptions,
        )

        got = []

        class RawSink(StreamHandler):
            def on_received_messages(self, s, msgs):
                got.extend(msgs)

        s = Stream(999001, StreamOptions(handler=RawSink(), raw_messages=True),
                   is_client=False)
        # what _on_frame queues: kind, payload, its arrival stamp
        s._rq.execute(
            (FT_DATA, b"plain-bytes-payload", __import__("time").monotonic_ns())
        )
        deadline = __import__("time").monotonic() + 5
        while not got and __import__("time").monotonic() < deadline:
            __import__("time").sleep(0.01)
        assert got, "message not consumed"
        assert not isinstance(got[0], (bytes, bytearray))
        assert got[0].to_bytes() == b"plain-bytes-payload"
