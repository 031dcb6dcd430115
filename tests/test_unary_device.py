"""A unary call whose attachment is a device array
(``Channel.call_method(attachment=<jax.Array>)`` over
``Channel(transport="tpu")``; the link's lane, ``transport/device_link.py``)
on the CPU's forced host devices, through the public API: content against
``benchmark/references/tensor_echo_identity.py`` and where the arrays lie;
several callers on one connection with answers out of order; bytes one way
and an array the other; a frame too long for the lane's tag; what fails a
call and what is left behind; what a socket without a lane does, which is
what a stream does there; what is refused; what a retry sends; the
recorders. Every test runs under a time limit of its own."""

import threading
import time

import numpy as np
import pytest
from test_stream_device import Sink, nothing_waits, wait
from test_stream_link_deployment import limited  # a test's own time limit

from benchmark import manifest
from incubator_brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Controller,
    ParallelChannel,
    Server,
    ServerOptions,
    StreamOptions,
    stream_accept,
    stream_create,
)
from incubator_brpc_tpu.transport import device_link as dl
from incubator_brpc_tpu.utils.status import ErrorCode

REFERENCE = manifest.load_module("references", "tensor_echo_identity.py")
WORDS = 1024  # a tensor of 4 KiB
SEED = 2**31 + 12345


class Echo:
    """A server whose ``Echo`` answers what ``answer(cntl, request)``
    says (default: the request's attachment) and keeps what it saw, and a
    channel to it."""

    def __init__(self, answer=None, transport="tpu", server_device=None,
                 max_retry=0):
        self.saw = []
        self.answer = lambda cntl, request: cntl.request_attachment
        self.lock = threading.Lock()

        def echo(cntl, request):
            with self.lock:
                self.saw.append(cntl.request_attachment)
            cntl.response_attachment = self.answer(cntl, request)
            return request

        def open_stream(cntl, request):
            stream_accept(cntl, StreamOptions(handler=self.sink))
            return b""

        self.sink = Sink()
        self.server = Server(ServerOptions(device_index=server_device))
        self.server.add_service("E", {"Echo": echo, "Open": open_stream})
        assert self.server.start(0)
        options = {"timeout_ms": 30000, "max_retry": max_retry}
        if transport == "tpu":
            options.update(transport="tpu", link_slot_words=1024, link_window=4)
        self.channel = Channel()
        assert self.channel.init(f"127.0.0.1:{self.server.port}",
                                 options=ChannelOptions(**options))
        assert self.call(attachment=b"warm").ok()  # the handshake
        self.answer = answer or self.answer

    def call(self, request=b"ping", **kwargs):
        return self.channel.call_method("E", "Echo", request, **kwargs)

    @property
    def link(self):
        return self.channel._device_sock.link

    def tensor(self, caller: int, call: int, words: int = WORDS, device=0):
        """``(the call's tensor on the client's device, its words)``."""
        import jax

        data = REFERENCE.content(SEED, caller, call, words)
        return jax.device_put(data, self.link.devices[device]), data

    def close(self):
        self.server.stop()
        self.server.join(timeout=5)


@pytest.fixture
def echo():
    made = []

    def make(*args, **kwargs):
        made.append(Echo(*args, **kwargs))
        return made[-1]

    yield make
    for e in made:
        e.close()


def lane_counts() -> dict:
    return {a: getattr(dl, a).get_value() for a in (
        "lane_messages", "lane_bytes", "link_bytes", "unary_lane_requests",
        "unary_lane_replies", "unary_lane_bytes", "unary_bytes_fallbacks")}


def gained(before: dict) -> dict:
    return {a: v - before[a] for a, v in lane_counts().items()}


def nothing_left(link) -> bool:
    """No lane message is undelivered or unowned: none in flight, none
    landed and waiting for its turn, no frame or body held for its other
    half on either socket."""
    return (
        wait(lambda: link._lane_inflight == 0) and nothing_waits(link)
        and all(not any(s._unary_order.held) and s._unary_body is None
                for s in link.socks if s is not None)
    )


def test_the_jax_twin_computes_the_reference_words():
    import jax

    for caller, call in ((0, 0), (3, 17), (1, 2**20)):
        key = REFERENCE.tensor_key(SEED, caller, call)
        twin = jax.jit(lambda k: REFERENCE.device_words(k, 4096))(key)
        assert np.array_equal(np.asarray(twin), REFERENCE.content(SEED, caller, call, 4096))
    a, b = REFERENCE.content(SEED, 0, 1, 64), REFERENCE.content(SEED, 1, 0, 64)
    assert not np.array_equal(a, b)
    assert REFERENCE.expected(b"ping", a) == (b"ping", a)


@limited(120)
@pytest.mark.parametrize("form", ["sync", "done"])
def test_an_array_goes_out_and_comes_back_on_the_right_devices(echo, form):
    import jax

    e = echo()
    client, server = e.link.devices
    assert client != server and e.link.geometry == "ppermute"
    before = lane_counts()
    for call in range(3):
        tensor, data = e.tensor(0, call)
        if form == "sync":
            cntl = e.call(attachment=tensor)
        else:
            ended, box = threading.Event(), []
            e.call(attachment=tensor, done=lambda c: (box.append(c), ended.set()))
            assert ended.wait(30)
            (cntl,) = box
        assert cntl.ok(), cntl.error_text
        want = REFERENCE.expected(b"ping", data)
        answer = cntl.response_attachment
        assert isinstance(answer, jax.Array) and answer.devices() == {client}
        assert (answer.shape, answer.dtype) == (data.shape, data.dtype)
        assert cntl.response_payload == want[0]
        assert np.array_equal(np.asarray(answer), want[1])
        seen = e.saw[-1]
        assert isinstance(seen, jax.Array) and seen.devices() == {server}
        assert np.array_equal(np.asarray(seen), data)
    got = gained(before)
    # both ways by the lane, and of the tensors not a byte on the byte stream
    assert got["unary_lane_requests"] == got["unary_lane_replies"] == 3
    assert got["lane_messages"] == 6
    assert got["lane_bytes"] == got["unary_lane_bytes"] == 6 * data.nbytes
    assert got["link_bytes"] == 0 and got["unary_bytes_fallbacks"] == 0
    assert nothing_left(e.link)


@limited(180)
def test_four_callers_on_one_connection_get_their_own_answers_out_of_order(echo):
    """The handler keeps every second call of a caller for a while, so
    answers leave in another order than the requests came; each caller
    still gets the tensor it sent, word for word."""
    order = []

    def answer(cntl, request):
        caller, call = request[0], request[1]
        if call % 2 == 0:
            time.sleep(0.05 + 0.01 * caller)
        order.append((caller, call))
        return cntl.request_attachment

    e = echo(answer=answer)
    e.link.warm_lane(0, (WORDS,), np.uint32)
    e.link.warm_lane(1, (WORDS,), np.uint32)
    calls, errors = 8, []

    def caller(who):
        try:
            for call in range(calls):
                tensor, data = e.tensor(who, call)
                cntl = e.call(bytes([who, call]), attachment=tensor)
                assert cntl.ok(), cntl.error_text
                assert cntl.response_payload == bytes([who, call])
                assert np.array_equal(np.asarray(cntl.response_attachment), data)
        except BaseException as err:  # noqa: BLE001 — reported below
            errors.append(repr(err))

    threads = [threading.Thread(target=caller, args=(who,)) for who in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    assert len(order) == 4 * calls
    sent_order = sorted(order, key=lambda cc: (cc[1], cc[0]))
    assert order != sent_order  # answers did leave out of the order sent
    assert nothing_left(e.link)


@limited(120)
def test_requests_and_answers_of_overlapping_calls_share_lane_programs(echo):
    """Four callers on one connection: a request of one call and an answer
    of another that wait at the launch together cross in one program (the
    test holds the launch order once, so that they do), and every caller
    still gets the tensor it sent."""
    from incubator_brpc_tpu.parallel.collective import launch_order

    kept = threading.Event()

    def answer(cntl, request):
        if (request[0], request[1]) == (0, 0):  # caller 0's first answer waits
            assert kept.wait(60)
        return cntl.request_attachment

    e = echo(answer=answer)
    for side in (0, 1):
        e.link.warm_lane(side, (WORDS,), np.uint32)
    calls, errors = 6, []
    before = (dl.lane_messages.get_value(), dl.lane_steps.get_value())

    def caller(who):
        try:
            for call in range(calls):
                tensor, data = e.tensor(who, call)
                cntl = e.call(bytes([who, call]), attachment=tensor)
                assert cntl.ok(), cntl.error_text
                assert cntl.response_payload == bytes([who, call])
                answered = cntl.response_attachment
                assert answered.devices() == {e.link.devices[0]}
                assert np.array_equal(np.asarray(answered), data)
        except BaseException as err:  # noqa: BLE001 — reported below
            errors.append(repr(err))

    threads = [threading.Thread(target=caller, args=(who,)) for who in range(4)]
    threads[0].start()
    assert wait(lambda: len(e.saw) == 2)  # the handshake's call and caller 0's first
    out = e.link._lane_out
    with launch_order:
        for t in threads[1:]:
            t.start()
        assert wait(lambda: len(out[0]) == 3)  # three requests wait for the launch
        kept.set()
        assert wait(lambda: len(out[1]) == 1)  # and caller 0's answer beside them
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    messages = dl.lane_messages.get_value() - before[0]
    programs = dl.lane_steps.get_value() - before[1]
    assert messages == 2 * 4 * calls and programs < messages
    assert nothing_left(e.link) and not any(out) and not e.link._lane_launching


@limited(120)
@pytest.mark.parametrize("request_is", ["array", "bytes"])
def test_bytes_one_way_and_an_array_the_other(echo, request_is):
    import jax

    e = echo()
    tensor, data = e.tensor(0, 0)
    if request_is == "array":  # answered with bytes
        e.answer = lambda cntl, request: b"only bytes back"
        cntl = e.call(attachment=tensor)
        assert cntl.ok() and cntl.response_attachment == b"only bytes back"
        assert isinstance(e.saw[-1], jax.Array)
    else:  # bytes answered with an array on the server's device
        back = jax.device_put(data, e.link.devices[1])
        e.answer = lambda cntl, request: back
        cntl = e.call(attachment=b"only bytes out")
        assert cntl.ok(), cntl.error_text
        assert e.saw[-1] == b"only bytes out"
        answer = cntl.response_attachment
        assert isinstance(answer, jax.Array)
        assert answer.devices() == {e.link.devices[0]}
        assert np.array_equal(np.asarray(answer), data)
    assert nothing_left(e.link)


@limited(120)
@pytest.mark.parametrize("long", ["request", "answer", "both"])
def test_a_frame_too_long_for_the_tag_rides_the_byte_stream_and_meets_its_body(
    echo, long
):
    """The payload makes the frame longer than ``LANE_TAG_BYTES``: it
    crosses the byte stream, its tensor the lane, and the far socket's
    order stage brings them together, for several callers at once."""
    e = echo()
    request = b"q" * (400 if long in ("request", "both") else 4)
    errors = []

    def caller(who):
        try:
            for call in range(4):
                tensor, data = e.tensor(who, call)
                cntl = e.call(request + bytes([who]), attachment=tensor)
                assert cntl.ok(), cntl.error_text
                assert np.array_equal(np.asarray(cntl.response_attachment), data)
                assert cntl.response_payload.endswith(bytes([who]))
        except BaseException as err:  # noqa: BLE001 — reported below
            errors.append(repr(err))

    if long != "request":
        inner = e.server._methods.get("E.Echo")
        handler = inner.handler
        inner.handler = lambda cntl, req: handler(cntl, req) and b"a" * 400 + req[-1:]
    before = lane_counts()
    threads = [threading.Thread(target=caller, args=(who,)) for who in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    got = gained(before)
    assert got["lane_messages"] == 24 and got["lane_bytes"] == 24 * WORDS * 4
    assert got["link_bytes"] > 12 * 400  # the long frames, and only those
    assert got["link_bytes"] < 24 * 600
    assert nothing_left(e.link)


@limited(120)
def test_a_slow_handler_holds_no_later_message_of_its_side(echo):
    """The handler runs on a worker, not on the lane's in-order deliverer:
    while one call's handler sleeps, a later call goes through."""
    gate = threading.Event()

    def answer(cntl, request):
        if request == b"slow":
            assert gate.wait(30)
        return cntl.request_attachment

    e = echo(answer=answer)
    slow, _ = e.tensor(0, 0)
    ended, box = threading.Event(), []
    e.call(b"slow", attachment=slow, done=lambda c: (box.append(c), ended.set()))
    assert wait(lambda: len(e.saw) == 2)  # the warm call's and the slow one's
    fast, data = e.tensor(0, 1)
    cntl = e.call(b"fast", attachment=fast)
    assert cntl.ok() and np.array_equal(np.asarray(cntl.response_attachment), data)
    assert not ended.is_set()
    gate.set()
    assert ended.wait(30) and box[0].ok()
    assert nothing_left(e.link)


@limited(120)
@pytest.mark.parametrize("how", ["handler_fails", "handler_raises", "timeout",
                                 "dead_link"])
def test_a_failed_call_fails_and_leaves_nothing_on_the_lane(echo, how):
    gate = threading.Event()

    def answer(cntl, request):
        if how == "handler_fails":
            cntl.set_failed(ErrorCode.EINTERNAL, "no")
        elif how == "handler_raises":
            raise RuntimeError("no")
        else:
            gate.wait(30)
        return cntl.request_attachment

    e = echo(answer=answer)
    link = e.link
    tensor, data = e.tensor(0, 0)
    if how == "dead_link":
        timer = threading.Timer(0.3, lambda: link.fail("injected link failure"))
        timer.start()
    cntl = e.call(attachment=tensor, cntl=Controller(
        timeout_ms=500 if how == "timeout" else 20000, max_retry=0))
    assert cntl.failed()
    assert cntl.error_code == {
        "handler_fails": ErrorCode.EINTERNAL, "handler_raises": ErrorCode.EINTERNAL,
        "timeout": ErrorCode.ERPCTIMEDOUT, "dead_link": ErrorCode.EFAILEDSOCKET,
    }[how]
    assert cntl.response_attachment == b""
    gate.set()
    assert wait(lambda: nothing_left(link))
    # the caller's tensor is the caller's still, and whole
    assert np.array_equal(np.asarray(tensor), data)
    if how != "dead_link":  # the link lives: the next call goes through
        e.answer = lambda cntl, request: cntl.request_attachment
        again = e.call(attachment=tensor)
        assert again.ok(), again.error_text
        assert np.array_equal(np.asarray(again.response_attachment), data)


@limited(120)
@pytest.mark.parametrize("socket", ["host-swap link", "host socket"])
def test_without_a_lane_the_call_sends_what_a_stream_sends_there(echo, socket):
    """One shared device (the host swap) or a TCP socket: no second device
    to land on, so the array's bytes go as host bytes, out and back, by
    the rule a stream's write follows (``array_carrier``)."""
    import jax

    if socket == "host-swap link":
        e = echo(server_device=0)
        assert e.link.geometry == "host-swap" and e.channel._device_sock.lane is None
        device = e.link.devices[0]
    else:
        e = echo(transport="tcp")
        device = jax.devices()[0]
    data = REFERENCE.content(SEED, 0, 0, WORDS)
    tensor = jax.device_put(data, device)
    e.answer = lambda cntl, request: tensor  # an array back too
    before = lane_counts()
    cntl = e.call(attachment=tensor)
    assert cntl.ok(), cntl.error_text
    assert e.saw[-1] == data.tobytes() == cntl.response_attachment
    got = gained(before)
    assert got["lane_messages"] == 0 and got["unary_bytes_fallbacks"] == 2
    # and a stream over the same socket is handed the same bytes
    stream = stream_create(StreamOptions(max_buf_size=1 << 20))
    opened = e.channel.call_method("E", "Open", b"", request_stream=stream)
    assert opened.ok() and stream.wait_connected(10)
    assert stream.write(tensor, timeout=10) == 0
    assert wait(lambda: len(e.sink.got) == 1)
    assert bytes(e.sink.got[0]) == e.saw[-1]
    stream.close()


@limited(120)
@pytest.mark.parametrize("what", ["other_device", "host_memory", "no_dimension",
                                  "no_element", "deleted", "no_lane_yet"])
def test_what_the_lane_does_not_accept_is_refused_and_nothing_is_sent(echo, what):
    import jax

    e = echo()
    link, sock = e.link, e.channel._device_sock
    data = np.arange(WORDS, dtype=np.uint32)
    if what == "other_device":
        bad = jax.device_put(data, link.devices[1])
    elif what == "host_memory":
        bad = data
    elif what == "no_dimension":
        bad = jax.device_put(np.uint32(7), link.devices[0])
    elif what == "no_element":
        bad = jax.device_put(data[:0], link.devices[0])
    else:
        bad = jax.device_put(data, link.devices[0])
        if what == "deleted":
            bad.delete()
        else:  # what a MultiControllerLink is: a ppermute link without the lane
            link._lane_feed = None
            assert sock.lane is link and not link.has_lane
    before, seqs = lane_counts(), list(link._lane_seq)
    cntl = e.call(attachment=bad)
    assert cntl.failed()
    assert cntl.error_code == (
        ErrorCode.EREQUEST if what == "host_memory" else ErrorCode.EINVAL)
    assert len(e.saw) == 1  # the warm call's: the handler never ran
    assert not any(gained(before).values()) and link._lane_seq == seqs
    # the stream's write refuses the same array by the same rule
    if what != "host_memory":
        assert dl.array_carrier(sock, bad) == (None, None)
    # and the server's side: an answer that cannot cross fails the call
    if what in ("other_device", "host_memory"):
        wrong = data if what == "host_memory" else jax.device_put(data, link.devices[0])
        e.answer = lambda cntl, request: wrong
        cntl = e.call(attachment=b"bytes out")
        assert cntl.failed() and cntl.error_code == ErrorCode.EINTERNAL
    assert nothing_left(link)


@limited(120)
def test_a_retry_sends_the_same_array_and_never_a_donated_one(echo):
    """The controller holds the array for the call's retries. The first
    attempt is answered ``ELOGOFF`` (retriable): the retry sends the array
    again. Where the caller has donated it meanwhile, the retry is refused
    with ``EINVAL`` and dispatches nothing."""
    attempts, donate = [], []

    def answer(cntl, request):
        attempts.append(request)
        if len(attempts) % 2 == 1:
            for array in donate:
                array.delete()  # what a program the caller donated it to does
            cntl.set_failed(ErrorCode.ELOGOFF, "try again")
        return cntl.request_attachment

    e = echo(answer=answer, max_retry=2)
    tensor, data = e.tensor(0, 0)
    before = lane_counts()
    cntl = e.call(attachment=tensor)
    assert cntl.ok() and cntl.retried_count == 1, cntl.error_text
    assert np.array_equal(np.asarray(cntl.response_attachment), data)
    assert gained(before)["unary_lane_requests"] == 2
    tensor, data = e.tensor(0, 1)
    donate.append(tensor)
    before, seqs = lane_counts(), list(e.link._lane_seq)
    cntl = e.call(attachment=tensor)
    assert cntl.failed() and cntl.error_code == ErrorCode.EINVAL
    assert cntl.retried_count == 1 and len(attempts) == 3
    assert gained(before)["unary_lane_requests"] == 1  # the first attempt's
    assert e.link._lane_seq == [seqs[0], seqs[1] + 1]
    assert nothing_left(e.link)


@limited(120)
def test_a_combo_channel_still_takes_host_bytes_only(echo):
    e = echo()
    combo = ParallelChannel()
    combo.add_channel(e.channel)
    tensor, _ = e.tensor(0, 0)
    cntl = combo.call_method("E", "Echo", b"ping", attachment=tensor)
    assert cntl.failed() and cntl.error_code == ErrorCode.EINVAL
    assert "host bytes" in cntl.error_text
    assert combo.call_method("E", "Echo", b"ping", attachment=b"bytes").ok()


@limited(120)
def test_a_call_leaves_a_row_on_each_side_and_the_adders_count(echo):
    from incubator_brpc_tpu import bvar
    from incubator_brpc_tpu.bvar import expose_registry

    e = echo()
    link = e.link
    pfx = f"device_link_{link.link_id}_unary_"
    names = {"request_tx_us", "client_wake_us", "call_us", "server_dispatch_us",
             "reply_tx_us"}
    assert {n[len(pfx):] for n, _ in expose_registry.snapshot(pfx)} == names
    feeds = bvar.feeds()
    assert feeds[pfx + "calls"] is link.unary_calls
    assert feeds[pfx + "serves"] is link.unary_serves
    assert link.unary_calls.stamps == dl.UNARY_CALL_STAMPS
    assert link.unary_serves.stamps == dl.UNARY_SERVE_STAMPS
    tensor, _ = e.tensor(0, 0)
    t0 = time.monotonic_ns()
    for _ in range(5):
        assert e.call(attachment=tensor).ok()
    assert e.call(attachment=b"bytes leave no row").ok()
    t1 = time.monotonic_ns()
    _stamps, calls = link.unary_calls.timeline()
    _stamps, serves = link.unary_serves.timeline()
    assert len(calls) == len(serves) == 5
    for row in calls:  # entered <= request_sent <= answer_handed <= returned
        assert t0 <= row[0] <= row[1] <= row[2] <= row[3] <= t1
    for call, serve in zip(calls, serves):
        assert call[1] <= serve[0] <= serve[1] <= serve[2] <= serve[3] <= call[2]
    recorders = dict(expose_registry.snapshot(pfx))
    assert all(recorders[pfx + n].count() == 5 for n in names)
    stages = sum(recorders[pfx + n].latency() for n in names - {"call_us"})
    assert 0 < stages <= recorders[pfx + "call_us"].latency()
    link.fail("retire")
    assert not expose_registry.snapshot(pfx)  # they retire with the link's rest


# -- the deployment (benchmark/deployments/link_echo_hbm.py) at rehearsal size ----

CONFIG = manifest.load_json("configs", "link_performance_ici_hbm.json")
TRAFFIC = {
    "sizes": [4096], "carrier": "attachment", "service": "EchoService",
    "method": "Echo", "callers": 2, "warm_calls_per_caller": 1,
    "warm_seconds": 0.0,
}


def deploy(control=None):
    import copy

    config = copy.deepcopy(CONFIG)
    config["channel_options"].update(link_slot_words=1024, link_window=4)
    module = manifest.load_module("deployments", "link_echo_hbm.py")
    deployment = module.Deployment(config, control, None)
    deployment.warm(TRAFFIC)
    return module, deployment


def two_callers(deployment, calls: int = 3) -> list:
    """Both callers' statuses, each caller a thread as the harness's are."""
    from benchmark import generator

    send = generator.channel_caller(deployment.channel(), TRAFFIC, REFERENCE)
    statuses, threads = [], []
    for _ in range(2):
        threads.append(threading.Thread(
            target=lambda: statuses.extend(send(b"not sent")[1] for _ in range(calls))))
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(statuses) == 2 * calls
    return statuses


def held(deployment) -> dict:
    return {name.split("_of_")[0]: ok for name, _v, _l, ok in deployment.holds()}


@limited(180)
def test_the_deployment_echoes_tensors_the_reference_knows():
    import jax

    from benchmark import generator

    module, deployment = deploy()
    try:
        assert module.CONTROLS == ("flip_bit", "stale", "swap", "host_bytes")
        assert two_callers(deployment) == [generator.OK] * 6
        client, server = deployment.link.devices
        assert client != server
        for caller in deployment._callers:
            # the next call's tensor waits on the client's chip, and the
            # answers kept for the host are the reference's words
            assert caller.calls == caller.tensor_of == 3
            assert isinstance(caller.tensor, jax.Array)
            assert caller.tensor.devices() == {client}
            for call, answer in caller.recent:
                assert answer.devices() == {client}
                assert np.array_equal(np.asarray(answer), REFERENCE.content(
                    0, caller.index, call, 1024))
        checks = held(deployment)
        assert all(checks.values()), checks
        assert {"tensors_not_equal_to_their_call", "tensors_not_equal_on_the_host",
                "attachments_not_device_arrays", "lane_bytes_short",
                "payload_bytes_on_the_byte_stream", "link_geometry"} <= set(checks)
    finally:
        deployment.close()


@pytest.mark.parametrize("control", ["flip_bit", "stale", "swap", "host_bytes"])
@limited(180)
def test_a_control_of_the_deployment_comes_out_not_correct(control):
    from benchmark import generator

    module, deployment = deploy(control)
    try:
        statuses = two_callers(deployment)
        checks = held(deployment)
        if control == "host_bytes":
            # the tensors are right; they crossed as host bytes, which
            # breaks the lane's guarantees and nothing else
            assert statuses == [generator.OK] * 6
            assert checks["tensors_not_equal_to_their_call"] is True
            assert checks["attachments_not_device_arrays"] is False
            assert checks["lane_bytes_short"] is False
            assert checks["payload_bytes_on_the_byte_stream"] is False
        else:
            assert generator.MISMATCH in statuses
            assert generator.RPC_FAILED not in statuses
            assert checks["tensors_not_equal_to_their_call"] is False
            assert checks["attachments_not_device_arrays"] is True
            assert checks["lane_bytes_short"] is True
    finally:
        deployment.close()


def test_a_program_without_the_capability_is_refused_before_its_first_call(monkeypatch):
    module = manifest.load_module("deployments", "link_echo_hbm.py")
    monkeypatch.delattr(dl, "array_carrier")
    with pytest.raises(RuntimeError, match="host bytes only"):
        module.Deployment(CONFIG, None, None)


def test_the_cell_rehearses_on_the_cpu():
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "link_echo_ici_hbm_1m_c4", "--seed", str(2**31 + 44),
         "--seconds", "1", "--trace", "0", "--rehearse-on-cpu"],
        cwd=root, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["compilations_in_window"] == 0
    assert not any("NOT HELD" in line for line in lines)
    assert any(line.startswith("CHECK attachments_not_device_arrays: 0 ")
               for line in lines)
