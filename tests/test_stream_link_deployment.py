"""The streaming deployment (``benchmark/deployments/link_stream.py``:
StreamingRPC over ``Channel(transport="tpu")`` to a sink) on the CPU's
forced host devices at a small size: a transfer against the plain
reference ``stream_sink`` for bytes, boundaries, order and the receipt's
counts over a ``ppermute`` link; the window under a sink that holds its
handler; the three must-fail controls; and the recorders PR 31 gave
``rpc/stream.py`` and ``transport/device_link.py``. Every test runs under
a time limit of its own (``limited``): a hung stream fails its test, it
does not hold the suite."""

import copy
import functools
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, manifest  # noqa: E402

CONFIG = manifest.load_json("configs", "link_stream_sink_ici.json")
TRAFFIC = {
    "sizes": [65536], "message_bytes": 4096, "carrier": "attachment",
    "service": "StreamService", "method": "Open",
}
STREAM_NAMES = (
    "write_wait_us", "unconsumed_at_write", "feedback_lag_us", "deliver_us",
    "consume_us", "messages", "batches", "bytes", "feedback_frames",
    "write_retries",
)


def limited(seconds: float):
    """Run the test on a thread of its own and fail it when it outlives
    ``seconds`` (the thread is a daemon: a hung one dies with the worker)."""

    def wrap(test):
        @functools.wraps(test)
        def run(*args, **kwargs):
            box = {}

            def body():
                try:
                    test(*args, **kwargs)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    box["error"] = e

            thread = threading.Thread(target=body, daemon=True, name=test.__name__)
            thread.start()
            thread.join(seconds)
            if thread.is_alive():
                pytest.fail(f"{test.__name__} outlived its {seconds} s")
            if "error" in box:
                raise box["error"]

        return run

    return wrap


def small_config(**stream) -> dict:
    """The configuration as its file states it, but for the sizes: 4 KiB
    slots, a window of 4, and an 8 KiB stream window that 64 KiB turns
    over eight times."""
    config = copy.deepcopy(CONFIG)
    config["channel_options"].update(link_slot_words=1024, link_window=4)
    config["stream"].update(max_buf_size=8192, **stream)
    return config


def deploy(control=None, config=None, traffic=TRAFFIC):
    module = manifest.load_module("deployments", "link_stream.py")
    deployment = module.Deployment(config or small_config(), control, None)
    deployment.warm(traffic)
    return module, deployment


def payload(seed: int, size: int = 65536) -> bytes:
    return generator.make_pool(
        {"sizes": [size], "pool_per_size": 1}, seed, 0)[size][0]


def counts(prefix: str) -> dict:
    """Every exposed ``<prefix>*`` bvar as one number: a recorder's count,
    an adder's value. Waiting rows are fed first."""
    from incubator_brpc_tpu.bvar import LatencyRecorder, expose_registry
    from incubator_brpc_tpu.rpc import stream as stream_mod

    stream_mod.HOST_VARS.flush()
    stream_mod.LINK_VARS.flush()
    out = {}
    for name, var in expose_registry.snapshot(prefix):
        if isinstance(var, LatencyRecorder):
            out[name] = var.count()
        elif isinstance(var.get_value(), (int, float)):
            out[name] = var.get_value()
    return out


def gains(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


@limited(120)
def test_a_transfer_against_the_reference():
    from incubator_brpc_tpu.rpc import Controller

    reference = manifest.load_module("references", "stream_sink.py")
    _, deployment = deploy()
    try:
        data = payload(31)
        result = deployment.channel().call_method(
            "StreamService", "Open", b"ping", attachment=data,
            cntl=Controller(timeout_ms=60000))
        assert not result.failed(), result.error_text
        # bytes, in order, once: what the generator compares
        got = (result.response_payload, result.response_attachment)
        assert got == reference.expected(b"ping", data)
        # boundaries and order, one for one: sixteen IOBufs of 4 KiB
        held = result._sink.held
        assert not isinstance(held[0], (bytes, bytearray))  # raw_messages
        assert [m.to_bytes() for m in held] == reference.messages(data, 4096)
        assert len(held) == 16
        # the receipt named what was sent, over a ppermute link on two devices
        checks = {name: (value, ok) for name, value, _limit, ok in deployment.holds()}
        assert all(ok for _value, ok in checks.values()), checks
        assert checks["link_geometry"][0] == "ppermute"
        assert checks["link_distinct_devices"][0] == 2
        assert set(checks) >= {
            "stream_messages_with_other_boundaries",
            "stream_window_overrun_bytes", "stream_receipts_with_other_counts",
        }
    finally:
        deployment.close()


@limited(180)
def test_the_cells_own_sizes_ride_trains_of_the_window():
    """The configuration and the traffic as their files state them (64 KiB
    slots, a window of 8, 32 MiB in 1 MiB messages under a 2 MiB window):
    the flow control the chip will see, none of its speed. PR 31's run of
    this read 4.07 slots a step; with the drive holding for the credit of
    the train its backlog wants (PR 32) my runs read 5.82-5.92, and 5.67 is
    a message gone as 8, 8 and its 100-byte tail alone."""
    from incubator_brpc_tpu.rpc import Controller
    from incubator_brpc_tpu.transport import device_link as dl

    traffic = manifest.load_json("traffic", "stream_32m_in_1m_c1.json")
    _, deployment = deploy(config=copy.deepcopy(CONFIG), traffic=traffic)
    try:
        assert deployment.message_bytes == 1 << 20
        data = payload(32, traffic["sizes"][0])
        before = {a: getattr(dl, a).get_value()
                  for a in ("link_steps", "link_slots", "link_held")}
        result = deployment.channel().call_method(
            "StreamService", "Open", b"ping", attachment=data,
            cntl=Controller(timeout_ms=120000))
        assert not result.failed(), result.error_text
        assert result.response_attachment == data
        link = deployment.link
        assert (link.slot_words, link.window) == (16384, 8)
        gained = {a: getattr(dl, a).get_value() - v for a, v in before.items()}
        # 512 full slots, the call, feedback, the receipt, and a tail for
        # each message the next one was not queued behind in time
        assert 512 < gained["link_slots"] <= 32 * 17 + 40
        assert gained["link_slots"] / gained["link_steps"] > 5.0
        assert gained["link_held"] > 0
        checks = {name: (value, ok) for name, value, _limit, ok in deployment.holds()}
        assert all(ok for _value, ok in checks.values()), checks
        for name in ("stream_messages_with_other_boundaries",
                     "stream_window_overrun_bytes",
                     "stream_receipts_with_other_counts"):
            assert checks[name][0] == 0, name
    finally:
        deployment.close()


@limited(60)
def test_the_reference_cuts_where_a_writer_cuts():
    reference = manifest.load_module("references", "stream_sink.py")
    assert reference.messages(b"abcdefghij", 4) == [b"abcd", b"efgh", b"ij"]
    assert reference.messages(b"abcd", 4) == [b"abcd"]
    assert reference.messages(b"", 4) == []
    with pytest.raises(ValueError):
        reference.messages(b"abcd", 0)
    with open(os.path.join(manifest.HERE, "references", "stream_sink.py")) as f:
        assert "import" not in f.read().replace("imports nothing", "")


@limited(120)
def test_the_window_under_a_sink_that_holds_its_handler():
    """While the sink's handler is held the writer gets as far ahead as
    the window lets it and no further; a ``write(timeout=0)`` past it says
    ``EAGAIN``; a write that parks is admitted once the handler returns and
    its feedback has crossed the link."""
    from incubator_brpc_tpu.rpc import (
        Channel, ChannelOptions, Server, StreamHandler, StreamOptions,
        stream_accept, stream_create,
    )
    from incubator_brpc_tpu.utils.status import ErrorCode

    release, entered, got = threading.Event(), threading.Event(), []

    class HeldSink(StreamHandler):
        def on_received_messages(self, stream, messages):
            entered.set()
            release.wait(60)
            got.extend(messages)

    def open_stream(cntl, request):
        stream_accept(cntl, StreamOptions(handler=HeldSink()))
        return b""

    server = Server()
    server.add_service("StreamService", {"Open": open_stream})
    assert server.start(0)
    try:
        channel = Channel()
        options = dict(small_config()["channel_options"])
        assert channel.init(f"127.0.0.1:{server.port}",
                            options=ChannelOptions(**options))
        before = counts("device_link_stream")
        stream = stream_create(StreamOptions(max_buf_size=8192))
        cntl = channel.call_method("StreamService", "Open", b"", request_stream=stream)
        assert cntl.ok(), cntl.error_text
        assert stream.wait_connected(10)
        message, limit = b"m" * 4096, 8192 + 4096 - 1
        admitted = 0
        while stream.write(message, timeout=0) == 0:
            admitted += 1
            assert stream.unconsumed_bytes <= limit  # the deployment's check
            assert admitted < 10, "the window never closed"
        assert admitted == 2 and entered.wait(10)
        assert stream.write(message, timeout=0) == ErrorCode.EAGAIN
        assert stream.write(message, timeout=0.05) == ErrorCode.EAGAIN
        parked = {}
        writer = threading.Thread(
            target=lambda: parked.update(rc=stream.write(message, timeout=30)))
        writer.start()
        time.sleep(0.1)
        assert writer.is_alive()  # parked on the window
        release.set()
        writer.join(30)
        assert parked == {"rc": 0}
        assert stream.unconsumed_bytes <= limit
        deadline = time.monotonic() + 10
        while len(got) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert got == [message] * 3
        gained = gains(before, counts("device_link_stream"))
        assert gained["device_link_stream_write_retries"] == 3
        assert gained["device_link_stream_write_wait_us"] == 3  # admitted writes
        from incubator_brpc_tpu.rpc import stream as stream_mod

        waited = stream_mod.LINK_VARS.writes.columns[0][0]
        assert waited.max_latency() >= 90_000  # the parked one, in us
        stream.close()
    finally:
        release.set()
        server.stop()
        server.join(timeout=10)


@limited(120)
def test_the_deployment_retries_a_refused_write_and_never_overruns(monkeypatch):
    """A slow sink and a write that gives up after a millisecond: the
    deployment makes the write again, as upstream's client does after
    ``StreamWait``, and the overrun check still reads 0."""
    module, deployment = deploy()
    slow = module._Sink.on_received_messages

    def slowed(self, stream, messages):
        time.sleep(0.01)
        slow(self, stream, messages)

    monkeypatch.setattr(module._Sink, "on_received_messages", slowed)
    monkeypatch.setattr(module, "WRITE_WAIT_S", 0.001)
    try:
        before = counts("device_link_stream")
        send = generator.channel_caller(
            deployment.channel(), TRAFFIC,
            manifest.load_module("references", "stream_sink.py"))
        _end, status = send(payload(32))
        assert status == generator.OK
        gained = gains(before, counts("device_link_stream"))
        assert gained["device_link_stream_write_retries"] >= 1
        assert gained["device_link_stream_write_wait_us"] == 17  # and the receipt
        held = {name: (value, ok) for name, value, _l, ok in deployment.holds()}
        assert held["stream_window_overrun_bytes"] == (0, True)
        assert all(ok for _value, ok in held.values()), held
    finally:
        deployment.close()


@pytest.mark.parametrize("control", ["flip_bit", "stale", "reorder"])
@limited(120)
def test_a_control_comes_out_mismatched(control):
    module, deployment = deploy(control)
    assert control in module.CONTROLS
    try:
        send = generator.channel_caller(
            deployment.channel(), TRAFFIC,
            manifest.load_module("references", "stream_sink.py"))
        statuses = [send(payload(seed))[1] for seed in (41, 42)]
        # the first transfer has no earlier one to be stale with
        want = [generator.OK if control == "stale" else generator.MISMATCH,
                generator.MISMATCH]
        assert statuses == want
        held = {name: ok for name, _v, _l, ok in deployment.holds()}
        # a spoiled record is no longer the reference's messages either
        assert held["stream_messages_with_other_boundaries"] is False
        assert held["stream_window_overrun_bytes"] is True
    finally:
        deployment.close()


@pytest.mark.parametrize("transport", ["tpu", "host"])
@limited(120)
def test_every_stream_recorder_gains_under_its_sockets_prefix(transport, monkeypatch):
    """Over the link a stream counts under ``device_link_stream_*``, over a
    host socket under ``stream_*``, and never under the other."""
    config = small_config()
    if transport == "host":
        config["channel_options"] = {"timeout_ms": 60000}
    mine, other = (
        ("device_link_stream_", "stream_") if transport == "tpu"
        else ("stream_", "device_link_stream_")
    )
    module, deployment = deploy(config=config)
    monkeypatch.setattr(module, "WRITE_WAIT_S", 0.0005)  # so a write is refused
    slow = module._Sink.on_received_messages

    def slowed(self, stream, messages):
        time.sleep(0.005)
        slow(self, stream, messages)

    monkeypatch.setattr(module._Sink, "on_received_messages", slowed)
    try:
        before = {p: counts(p) for p in (mine, other)}
        send = generator.channel_caller(
            deployment.channel(), TRAFFIC,
            manifest.load_module("references", "stream_sink.py"))
        assert send(payload(33))[1] == generator.OK
        # the receipt's own batch is counted once the client's handler,
        # which ended the transfer, has returned
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            gained = gains(before[mine], counts(mine))
            # (the sink's write of it once that write has returned, the
            # feedback for it once the client's half has sent it)
            if gained[mine + "messages"] == gained[mine + "write_wait_us"] == gained[
                    mine + "deliver_us"] == 17 and (
                    gained[mine + "consume_us"] == gained[mine + "batches"]
                    == gained[mine + "feedback_frames"]):
                break
            time.sleep(0.01)
        for name in STREAM_NAMES:
            assert gained[mine + name] > 0, name
        # sixteen data messages to the sink and its one receipt back
        assert gained[mine + "messages"] == 17
        assert gained[mine + "bytes"] == 65536 + 16
        assert gained[mine + "write_wait_us"] == 17
        assert gained[mine + "deliver_us"] == 17
        assert gained[mine + "consume_us"] == gained[mine + "batches"]
        assert gained[mine + "feedback_frames"] == gained[mine + "batches"]
        untouched = gains(before[other], counts(other))
        assert not any(untouched[other + name] for name in STREAM_NAMES)
        if transport == "tpu":
            link = deployment.link
            link._step_feed.flush()
            link._send_feed.flush()
            assert link._m_backlog.count() == link._m_rtt.count() > 0
            assert 1 <= link._m_backlog.max_latency()
            assert link._m_send_wait.count() >= 17 + 16  # data, feedback, the call
    finally:
        deployment.close()


@limited(120)
def test_rpcz_notes_the_stream_ids_on_the_call_that_carried_them(tuned_flags):
    from incubator_brpc_tpu.builtin import rpcz

    tuned_flags("enable_rpcz", True)
    _, deployment = deploy()
    try:
        send = generator.channel_caller(
            deployment.channel(), TRAFFIC,
            manifest.load_module("references", "stream_sink.py"))
        assert send(payload(34))[1] == generator.OK
        notes = [
            text for span in rpcz.span_store.recent(200)
            if span.method == "Open" for _at, text in span.annotations
        ]
        assert any("connected to remote stream" in n for n in notes), notes
        assert any("accepted for remote stream" in n for n in notes), notes
    finally:
        deployment.close()


class _Drain:
    """Messenger stand-in: empties the socket's read buffer."""

    def process(self, sock):
        sock._read_buf.popn(len(sock._read_buf))


@limited(120)
def test_send_wait_gains_when_the_backlog_is_over_budget():
    import jax

    from incubator_brpc_tpu.transport.device_link import DeviceLink, DeviceSocket

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    link = DeviceLink(devices[:2], slot_words=1024, window=2)  # budget 8 KiB
    assert link.geometry == "ppermute"
    for side in (0, 1):
        DeviceSocket(link, side, messenger=_Drain())
    inner = link._rows_to_host

    def slow(arrays):
        time.sleep(0.005)  # so the first send's backlog outlives the second call
        return inner(arrays)

    link._rows_to_host = slow
    try:
        assert link.send(0, b"a" * 65536) == 0  # admitted at once: empty backlog
        link._send_feed.flush()
        assert (link._m_send_wait.count(), link._m_send_wait.latency_sum()) == (1, 0)
        assert link.send(0, b"b" * 4096, timeout=30) == 0  # parks: 56 KiB over 8
        link._send_feed.flush()
        assert link._m_send_wait.count() == 2
        assert link._m_send_wait.latency_sum() >= 5_000  # us: a readback at least
        deadline = time.monotonic() + 10
        while link.inflight_steps and time.monotonic() < deadline:
            time.sleep(0.01)
        link._step_feed.flush()
        # what the train's length was taken from: at the first dispatch the
        # backlog is the 16 slots of 64 KiB and the whole window is free
        assert link._m_backlog.max_latency() == 16
        assert link._m_backlog.count() == link._m_rtt.count()
    finally:
        link.fail("test over")
