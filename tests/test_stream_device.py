"""A stream whose messages are device arrays (``Stream.write`` of a
``jax.Array`` over ``Channel(transport="tpu")``; the link's lane,
``transport/device_link.py``) on the CPU's forced host devices, through the
public API: content, order and boundaries against what was written, alone
and mixed with bytes messages; the window; what crosses as bytes and what
does not; where the arrays land; what a socket without a lane does; and
failure on both halves. Every test runs under a time limit of its own."""

import threading
import time

import numpy as np
import pytest
from test_stream_link_deployment import limited  # a test's own time limit

from incubator_brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Server,
    ServerOptions,
    StreamHandler,
    StreamOptions,
    stream_accept,
    stream_create,
)
from incubator_brpc_tpu.rpc import stream as stream_mod
from incubator_brpc_tpu.transport import device_link as dl
from incubator_brpc_tpu.utils.status import ErrorCode

WORDS = 1024  # a block of 4 KiB


def nothing_waits(link) -> bool:
    """No landed message waits for its turn in either direction of the lane."""
    return not any(lane.waiting for lane in link._lanes)


def wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


class Sink(StreamHandler):
    """Keeps what it is handed; ``hold`` keeps the handler inside its first
    call until ``release`` is set."""

    def __init__(self, hold=False):
        self.got, self.batches = [], []
        self.failed, self.closed = threading.Event(), threading.Event()
        self.entered, self.release = threading.Event(), threading.Event()
        if not hold:
            self.release.set()

    def on_received_messages(self, stream, messages):
        self.entered.set()
        self.release.wait(60)
        self.batches.append(len(messages))
        self.got.extend(messages)

    def on_closed(self, stream):
        self.closed.set()

    def on_failed(self, stream, code, reason):
        self.failed.set()


class Pair:
    """A server with a sink, a channel and one connected stream."""

    def __init__(self, sink=None, max_buf_size=8192, transport="tpu",
                 server_device=None, **link):
        self.sink = sink or Sink()
        self.client = Sink()

        def open_stream(cntl, request):
            stream_accept(cntl, StreamOptions(handler=self.sink))
            return b""

        self.server = Server(ServerOptions(device_index=server_device))
        self.server.add_service("S", {"Open": open_stream})
        assert self.server.start(0)
        options = {"timeout_ms": 30000}
        if transport == "tpu":
            options.update(transport="tpu", link_slot_words=1024, link_window=4,
                           **link)
        self.channel = Channel()
        assert self.channel.init(f"127.0.0.1:{self.server.port}",
                                 options=ChannelOptions(**options))
        self.stream = stream_create(StreamOptions(
            handler=self.client, max_buf_size=max_buf_size))
        cntl = self.channel.call_method("S", "Open", b"", request_stream=self.stream)
        assert cntl.ok(), cntl.error_text
        assert self.stream.wait_connected(10)

    @property
    def link(self):
        return self.channel._device_sock.link

    def block(self, seed: int, words: int = WORDS):
        import jax

        data = np.random.default_rng(seed).integers(
            0, 2**32, size=words, dtype=np.uint32)
        return jax.device_put(data, self.link.devices[0]), data

    def close(self):
        self.sink.release.set()
        self.stream.close()
        self.server.stop()
        self.server.join(timeout=5)


@pytest.fixture
def pair():
    made = []

    def make(*args, **kwargs):
        made.append(Pair(*args, **kwargs))
        return made[-1]

    yield make
    for p in made:
        p.close()


def same(got, want) -> bool:
    """A handed message against what was written: bytes as bytes, an
    array by its words."""
    if isinstance(want, bytes):
        return isinstance(got, bytes) and got == want
    return not isinstance(got, bytes) and np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mix", ["arrays", "mixed"])
@limited(120)
def test_a_stream_equals_what_was_written(pair, mix):
    """Content, order and boundaries, message for message; the arrays are
    ``jax.Array``s on the receiver's device; the payload never enters the
    byte stream's count."""
    import jax

    p = pair()
    rng = np.random.default_rng(7)
    before = {a: getattr(dl, a).get_value()
              for a in ("link_bytes", "lane_bytes", "lane_steps", "lane_messages")}
    written, arrays = [], 0
    for i in range(24):
        if mix == "mixed" and i % 3 == 0:
            message = rng.bytes(int(rng.integers(1, 300)))
            written.append(message)
        else:
            message, data = p.block(100 + i)
            written.append(data)
            arrays += 1
        assert p.stream.write(message, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == len(written))
    assert all(same(g, w) for g, w in zip(p.sink.got, written))
    receiver = p.link.devices[1]
    assert receiver != p.link.devices[0]
    for got, want in zip(p.sink.got, written):
        if not isinstance(want, bytes):
            assert isinstance(got, jax.Array)
            assert got.devices() == {receiver}
            assert (got.shape, got.dtype) == ((WORDS,), np.uint32)
    assert wait(lambda: p.stream.unconsumed_bytes == 0)
    gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
    assert gained["lane_bytes"] == arrays * WORDS * 4
    assert gained["lane_steps"] == gained["lane_messages"] == arrays
    # headers, feedback and the bytes messages: far under the arrays' bytes
    assert gained["link_bytes"] < 24 * 400 < gained["lane_bytes"] // 4


@limited(120)
def test_the_streams_own_counters_tell_device_bytes_from_host_bytes(pair):
    p = pair()
    names = ("messages", "bytes", "device_messages", "device_bytes")
    before = {n: getattr(stream_mod.LINK_VARS, n).get_value() for n in names}
    for i in range(4):
        assert p.stream.write(p.block(i)[0], timeout=30) == 0
    assert p.stream.write(b"x" * 100, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == 5)
    gained = {n: getattr(stream_mod.LINK_VARS, n).get_value() - v
              for n, v in before.items()}
    assert gained == {"messages": 5, "bytes": 100, "device_messages": 4,
                      "device_bytes": 4 * WORDS * 4}
    # a host socket's streams have no such adders
    assert stream_mod.HOST_VARS.device_messages is None


@limited(120)
def test_the_window_counts_an_arrays_nbytes_and_is_never_overrun(pair):
    """Blocks of half the window (over a quarter of it): while the sink's
    handler is held the writer gets as far ahead as the window lets it and
    no further, in bytes of arrays as in bytes of bytes."""
    p = pair(sink=Sink(hold=True), max_buf_size=8192)
    block_bytes, limit = WORDS * 4, 8192 + WORDS * 4 - 1
    admitted = 0
    while p.stream.write(p.block(admitted)[0], timeout=0) == 0:
        admitted += 1
        assert p.stream.unconsumed_bytes == admitted * block_bytes <= limit
        assert admitted < 10, "the window never closed"
    assert admitted == 2 and p.sink.entered.wait(10)
    assert p.stream.write(p.block(9)[0], timeout=0.05) == ErrorCode.EAGAIN
    parked = {}
    writer = threading.Thread(
        target=lambda: parked.update(rc=p.stream.write(p.block(3)[0], timeout=30)))
    writer.start()
    time.sleep(0.1)
    assert writer.is_alive()  # parked on the window
    p.sink.release.set()
    writer.join(30)
    assert parked == {"rc": 0}
    assert p.stream.unconsumed_bytes <= limit
    assert wait(lambda: len(p.sink.got) == 3)
    assert wait(lambda: p.stream.unconsumed_bytes == 0)


@limited(120)
def test_an_array_is_refused_unless_it_lies_on_this_sides_device(pair):
    import jax
    import jax.numpy as jnp

    p = pair()
    elsewhere = jax.device_put(np.zeros(8, np.uint32), p.link.devices[1])
    assert p.stream.write(elsewhere, timeout=1) == ErrorCode.EINVAL
    scalar = jax.device_put(jnp.uint32(3), p.link.devices[0])
    assert p.stream.write(scalar, timeout=1) == ErrorCode.EINVAL
    with pytest.raises(TypeError):
        p.stream.write("text", timeout=1)
    assert p.stream.unconsumed_bytes == 0
    # and the stream is as good as before
    block, data = p.block(1)
    assert p.stream.write(block, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == 1) and same(p.sink.got[0], data)


@pytest.mark.parametrize("socket", ["host", "host-swap"])
@limited(120)
def test_a_socket_without_a_lane_delivers_the_arrays_bytes(pair, socket):
    """A host socket, and a link whose two ends share one device: the
    array's bytes go as a bytes message, in order with the others."""
    import jax

    if socket == "host":
        p = pair(transport="host")
        assert p.channel._device_sock is None
    else:
        p = pair(server_device=0)
        assert p.link.geometry == "host-swap" and not p.link.has_lane
    data = np.arange(WORDS, dtype=np.uint32) * 3
    block = jax.device_put(data, jax.devices()[0])
    before = dl.lane_steps.get_value()
    for message in (b"before", block, b"after"):
        assert p.stream.write(message, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == 3)
    assert p.sink.got == [b"before", data.tobytes(), b"after"]
    assert dl.lane_steps.get_value() == before


@limited(120)
def test_a_link_that_refuses_arrays_answers_einval(pair):
    """The multi-controller link has no lane yet: ``lane_accepts`` says no
    whatever the array, and ``write`` answers EINVAL without admitting."""
    from incubator_brpc_tpu.transport.mc_link import MultiControllerLink

    assert MultiControllerLink.carries_arrays is False
    p = pair()
    p.link.carries_arrays = False  # what an mc link's class says
    p.link._lane_feed = None
    assert p.channel._device_sock.lane is p.link and not p.link.has_lane
    assert p.stream.write(p.block(1)[0], timeout=1) == ErrorCode.EINVAL
    assert p.stream.unconsumed_bytes == 0
    assert p.stream.write(b"bytes still cross", timeout=30) == 0
    assert wait(lambda: p.sink.got == [b"bytes still cross"])


@limited(120)
def test_no_lane_program_compiles_after_its_warm(pair):
    import jax

    p = pair()
    p.link.warm_lane(0, (WORDS,), np.uint32)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: name.endswith("backend_compile_duration")
        and compiles.append(name))
    blocks = [p.block(i) for i in range(6)]
    del compiles[:]  # device_put above is no program of the lane's
    for block, _data in blocks:
        assert p.stream.write(block, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == 6)
    assert compiles == []
    with pytest.raises(ValueError):
        Pair(server_device=0).link.warm_lane(0, (WORDS,), np.uint32)


@limited(120)
def test_a_lane_program_that_raises_fails_both_halves(pair):
    p = pair()
    block, data = p.block(1)
    assert p.stream.write(block, timeout=30) == 0
    assert wait(lambda: len(p.sink.got) == 1)
    link = p.link

    def raising(*_operands):
        raise RuntimeError("injected lane fault")

    key = ((WORDS,), "uint32")
    _program, placeholders, shards = link._lane_programs[key]
    link._lane_programs[key] = (raising, placeholders, shards)
    t0 = time.monotonic()
    rc = p.stream.write(p.block(2)[0], timeout=30)
    assert rc not in (0, ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED)
    assert p.client.failed.wait(5) and p.sink.failed.wait(5)
    assert time.monotonic() - t0 < 5
    assert link._closed and link._lane_inflight == 0 and nothing_waits(link)
    assert p.stream.write(p.block(3)[0], timeout=1) == ErrorCode.EINVAL
    assert len(p.sink.got) == 1  # nothing of the message crossed


@limited(120)
def test_a_link_failed_mid_transfer_wakes_the_writer_and_ends_both_halves(pair):
    """Device messages in flight and a writer parked on the window: the
    link's failure ends the stream on both ends with ``on_failed`` inside
    five seconds and leaves nothing held back for the other carrier."""
    p = pair(sink=Sink(hold=True), max_buf_size=8192)
    for i in range(2):
        assert p.stream.write(p.block(i)[0], timeout=30) == 0
    assert p.sink.entered.wait(10)
    parked = {}
    writer = threading.Thread(
        target=lambda: parked.update(rc=p.stream.write(p.block(5)[0], timeout=60)))
    writer.start()
    time.sleep(0.1)
    assert writer.is_alive()
    served = [s for s in stream_mod.open_streams() if not s.is_client
              and s._sock is p.link.socks[1]]
    t0 = time.monotonic()
    p.link.fail("injected link failure")
    writer.join(5)
    assert not writer.is_alive() and parked["rc"] == ErrorCode.EINVAL
    assert p.client.failed.wait(5) and p.sink.failed.wait(5)
    assert time.monotonic() - t0 < 5
    assert nothing_waits(p.link)
    assert all(not any(s._held or ()) for s in served)
    assert wait(lambda: p.link._lane_inflight == 0)


def hold(obj, name):
    """Keeps ``obj.name`` from running until the event returned is set."""
    gate, inner = threading.Event(), getattr(obj, name)

    def held(*args, **kwargs):
        gate.wait(60)
        return inner(*args, **kwargs)

    setattr(obj, name, held)
    return gate


def served_stream(p):
    (served,) = [s for s in stream_mod.open_streams()
                 if not s.is_client and s._sock is p.link.socks[1]]
    return served


def lane_handed(p, messages: int) -> bool:
    """The lane has handed side 1's socket so many messages."""
    return wait(lambda: p.link._lanes[1].next == messages)


def write_all(p, messages):
    for message in messages:
        assert p.stream.write(message, timeout=30) == 0


def order_bytes_then_arrays_with_the_byte_stream_held_back(p):
    """The lane is the faster: the arrays are handed to the stream before
    the bytes message written ahead of them and wait for it."""
    blocks = [p.block(i) for i in range(3)]
    byte_stream = hold(p.link.socks[1], "_feed")
    write_all(p, [b"opening", *(block for block, _ in blocks)])
    assert lane_handed(p, 3)
    time.sleep(0.05)
    assert p.sink.got == [] and len(served_stream(p)._held[1]) == 3
    byte_stream.set()
    return [b"opening", *(data for _, data in blocks)]


def order_arrays_then_bytes_with_the_lanes_watcher_held_back(p):
    """The byte stream is the faster: the bytes message is cut before the
    arrays written ahead of it have landed and waits for them."""
    blocks = [p.block(i) for i in range(3)]
    p.link.warm_lane(0, (WORDS,), np.uint32)  # its first use reads a tag too
    lane = hold(p.link, "_tag_to_host")
    write_all(p, [*(block for block, _ in blocks), b"after"])
    served = served_stream(p)
    assert wait(lambda: served._held is not None and len(served._held[0]) == 1)
    time.sleep(0.05)
    assert p.sink.got == []
    lane.set()
    return [*(data for _, data in blocks), b"after"]


def order_bytes_and_arrays_alternating_from_one_writer(p):
    written = []
    for i in range(20):
        if i % 2:
            block, data = p.block(i)
            written.append(data)
        else:
            block = data = b"message %d" % i
            written.append(data)
        write_all(p, [block])
    return written


def order_close_after_arrays_not_yet_landed(p):
    """``close()`` names the arrays sent before it: the far consumer sees
    every one of them and then the close."""
    seen_at_close = []
    p.sink.on_closed = lambda stream: (
        seen_at_close.append(len(p.sink.got)), p.sink.closed.set())
    blocks = [p.block(i) for i in range(3)]
    p.link.warm_lane(0, (WORDS,), np.uint32)  # its first use reads a tag too
    lane = hold(p.link, "_tag_to_host")
    write_all(p, [block for block, _ in blocks])
    p.stream.close()
    served = served_stream(p)
    assert wait(lambda: served._held is not None and len(served._held[0]) == 1)
    time.sleep(0.05)
    assert p.sink.got == [] and not p.sink.closed.is_set()
    lane.set()
    assert p.sink.closed.wait(10) and seen_at_close == [3]
    return [data for _, data in blocks]


def order_two_writers_on_one_stream(p):
    """Every message once, each writer's own order kept."""
    import jax

    per_writer, errors = 24, []

    def writer(who):
        try:
            for i in range(per_writer):
                if (i + who) % 3 == 0:
                    message = bytes([who, i])
                else:
                    message = jax.device_put(
                        np.full(WORDS, who << 16 | i, np.uint32), p.link.devices[0])
                while (rc := p.stream.write(message, timeout=10)) != 0:
                    assert rc in (ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED), rc
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=writer, args=(who,)) for who in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert wait(lambda: len(p.sink.got) == 2 * per_writer)
    seen = {1: [], 2: []}
    for got in p.sink.got:
        if isinstance(got, bytes):
            seen[got[0]].append((got[1], "bytes"))
        else:
            word = int(np.asarray(got)[0])
            assert np.all(np.asarray(got) == word)
            seen[word >> 16].append((word & 0xFFFF, "array"))
    for who in (1, 2):
        assert seen[who] == [
            (i, "bytes" if (i + who) % 3 == 0 else "array") for i in range(per_writer)]
    return None


def order_a_bytes_write_refused_between_two_arrays(p):
    """``EOVERCROWDED`` rolls a bytes write back; it leaves no count that
    the array after it would wait on."""
    sock, refused = p.channel._device_sock, []
    inner = sock.write

    def refusing_once(data, **kwargs):
        if not refused:
            refused.append(data)
            return ErrorCode.EOVERCROWDED
        return inner(data, **kwargs)

    (first, first_data), (second, second_data) = p.block(1), p.block(2)
    write_all(p, [first])
    sock.write = refusing_once
    assert p.stream.write(b"refused", timeout=30) == ErrorCode.EOVERCROWDED
    assert len(refused) == 1
    write_all(p, [second, b"accepted"])
    return [first_data, second_data, b"accepted"]


def order_an_array_the_lane_refuses_between_two_arrays(p):
    """A tag over the lane's bound: ``EINVAL`` with nothing taken; the
    window is rolled back as for a refused bytes write, the stream lives
    and the messages after it name no count of it."""
    (first, first_data), (second, second_data) = p.block(1), p.block(2)
    write_all(p, [first, b"between"])
    bound, dl.LANE_TAG_BYTES = dl.LANE_TAG_BYTES, 8
    try:
        assert p.stream.write(p.block(3)[0], timeout=30) == ErrorCode.EINVAL
    finally:
        dl.LANE_TAG_BYTES = bound
    assert wait(lambda: p.stream.unconsumed_bytes == 0)
    write_all(p, [second, b"after"])
    return [first_data, b"between", second_data, b"after"]


def order_a_tag_that_names_a_closed_stream(p):
    """Dropped, as a data frame for an unknown stream is: the socket, the
    lane and the socket's other streams go on."""
    from incubator_brpc_tpu.protocol.tbus_std import FLAG_STREAM, Meta, pack_frame

    block, data = p.block(1)
    write_all(p, [block])
    assert wait(lambda: len(p.sink.got) == 1)
    served = served_stream(p)
    gone = served.id
    served.close()
    assert p.client.closed.wait(10)
    assert stream_mod.get_stream(gone) is None
    tag = pack_frame(
        Meta(stream_id=gone, extra={"ft": "data", "frames_before": 0}), b"", 0,
        flags=FLAG_STREAM)
    assert p.link.lane_send(0, p.block(2)[0], tag) == 0
    assert lane_handed(p, 2)
    assert len(p.sink.got) == 1 and not p.link._closed
    assert p.link.socks[1].state == 0  # CONNECTED
    # another stream over the same socket is handed its messages
    p.stream = stream_create(StreamOptions(handler=p.client, max_buf_size=1 << 20))
    cntl = p.channel.call_method("S", "Open", b"", request_stream=p.stream)
    assert cntl.ok() and p.stream.wait_connected(10)
    block, again = p.block(3)
    write_all(p, [b"next", block])
    return [data, b"next", again]


def order_a_tag_with_a_word_flipped_on_the_way(p):
    """The tag is cut by the frame parser: its checksum does not hold, the
    socket fails and both halves hear it."""
    inner = p.link._tag_to_host

    def flipped(landed):
        words = inner(landed).copy()
        words[12] ^= 1 << 7  # a word of the frame's meta
        return words

    p.link._tag_to_host = flipped
    write_all(p, [p.block(1)[0]])
    assert p.client.failed.wait(5) and p.sink.failed.wait(5)
    assert p.link._closed and p.sink.got == []
    assert p.stream.write(b"after", timeout=1) == ErrorCode.EINVAL
    return None


ORDER_CASES = {
    "bytes-then-arrays-byte-stream-held": (
        order_bytes_then_arrays_with_the_byte_stream_held_back),
    "arrays-then-bytes-watcher-held": (
        order_arrays_then_bytes_with_the_lanes_watcher_held_back),
    "alternating": order_bytes_and_arrays_alternating_from_one_writer,
    "close-after-arrays-not-landed": order_close_after_arrays_not_yet_landed,
    "two-writers": order_two_writers_on_one_stream,
    "bytes-refused-between-arrays": order_a_bytes_write_refused_between_two_arrays,
    "array-refused-between-arrays": order_an_array_the_lane_refuses_between_two_arrays,
    "tag-names-a-closed-stream": order_a_tag_that_names_a_closed_stream,
    "tag-word-flipped": order_a_tag_with_a_word_flipped_on_the_way,
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
@limited(120)
def test_the_handler_sees_the_order_written_across_the_two_carriers(pair, case):
    """Two FIFO carriers feed one stream, the byte stream and the lane,
    and either may be the faster: the handler is handed the messages in
    the order written, one a write, and nothing is left held back. A case
    returns what the sink must hold in the end (None: it has judged)."""
    p = pair(max_buf_size=1 << 20)
    want = ORDER_CASES[case](p)
    if want is not None:
        assert wait(lambda: len(p.sink.got) == len(want))
        assert all(same(g, w) for g, w in zip(p.sink.got, want))
    assert nothing_waits(p.link) and wait(lambda: p.link._lane_inflight == 0)
    for s in stream_mod.open_streams():
        assert not any(s._held or ())


@limited(180)
def test_many_writers_and_streams_share_one_lane_in_order():
    """More writer threads than this test has cores to itself, a shortened
    switch interval, three streams over one link, bytes and arrays mixed:
    every stream's handler is handed exactly what its writer wrote, in
    order, and the lane ends with nothing waiting for its turn."""
    import sys

    import jax

    streams, per_stream = 3, 40
    sinks = [Sink() for _ in range(streams)]
    accepted = iter(sinks)

    def open_stream(cntl, request):
        stream_accept(cntl, StreamOptions(handler=next(accepted)))
        return b""

    server = Server()
    server.add_service("S", {"Open": open_stream})
    assert server.start(0)
    channel = Channel()
    assert channel.init(
        f"127.0.0.1:{server.port}",
        options=ChannelOptions(transport="tpu", timeout_ms=30000,
                               link_slot_words=1024, link_window=4))
    opened = []
    for _ in range(streams):
        s = stream_create(StreamOptions(max_buf_size=16384))
        cntl = channel.call_method("S", "Open", b"", request_stream=s)
        assert cntl.ok(), cntl.error_text
        assert s.wait_connected(10)
        opened.append(s)
    link = channel._device_sock.link
    written = [[] for _ in range(streams)]
    errors = []

    def writer(index):
        rng = np.random.default_rng(index)
        try:
            for i in range(per_stream):
                if i % 4 == 1:
                    message = rng.bytes(64)
                    written[index].append(message)
                else:
                    data = rng.integers(0, 2**32, size=WORDS, dtype=np.uint32)
                    message = jax.device_put(data, link.devices[0])
                    written[index].append(data)
                while (rc := opened[index].write(message, timeout=10)) != 0:
                    assert rc in (ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED), rc
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer, args=(i,)) for i in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        assert wait(lambda: all(len(s.got) == per_stream for s in sinks), timeout=60)
    finally:
        sys.setswitchinterval(interval)
    for sink, wrote in zip(sinks, written):
        assert all(same(g, w) for g, w in zip(sink.got, wrote))
    assert nothing_waits(link) and wait(lambda: link._lane_inflight == 0)
    for s in opened:
        s.close()
    server.stop()
    server.join(timeout=5)
