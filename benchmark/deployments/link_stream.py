"""StreamingRPC over ``Channel(transport="tpu")`` to a sink: upstream's
``example/streaming_echo_c++`` (``client.cpp``: ``StreamCreate``, the call
that carries the stream, a loop of ``StreamWrite``, ``StreamClose``;
``server.cpp``: ``StreamAccept`` with a handler that consumes and writes
nothing back) over the link ``link_echo.py`` builds. The link's checks and
its single-controller set-up are that file's.

One *transfer* is one call of the harness: ``stream_create``, the ``Open``
call with the transfer's byte and message counts and the stream on it,
``wait_connected``, the attachment written as consecutive messages of
``message_bytes`` (the traffic file's key; a write that returns ``EAGAIN``
or ``EOVERCROWDED`` is made again), then the sink's receipt on its half of
the stream, then ``close``. The sink keeps the IOBufs its handler was
handed; what it holds is compared with the reference after the clock has
stopped. Only public API of ``incubator_brpc_tpu.rpc`` is on the path.

A transfer is never fewer than four messages: where ``message_bytes`` is
over a quarter of the smallest size (the CPU rehearsal cuts sizes to 4 KiB)
it is cut to that quarter, so boundaries and order are there to check.
"""

from __future__ import annotations

import struct
import threading
import time

from benchmark import manifest

_link = manifest.load_module("deployments", "link_echo.py")

COUNTS = struct.Struct("<QQ")  # a transfer's bytes and messages
WRITE_WAIT_S = 10.0  # one write parked on the window before it says EAGAIN


def _flip_bit(held: list) -> list:
    """Control: one bit of the first message the sink holds flips."""
    first = bytearray(held[0])
    first[len(first) // 2] ^= 1
    return [bytes(first)] + held[1:]


_LAST = {}  # the stale control's memory: total bytes -> the record before


def _stale(held: list) -> list:
    """Control: the sink answers with the previous transfer's record where
    the lengths agree."""
    size = sum(len(m) for m in held)
    out = _LAST.get(size, held)
    _LAST[size] = held
    return out


def _reorder(held: list) -> list:
    """Control: the sink's record swaps its first two messages."""
    if len(held) < 2:
        raise RuntimeError("reorder needs a transfer of two messages")
    return [held[1], held[0]] + held[2:]


_SPOIL = {"flip_bit": _flip_bit, "stale": _stale, "reorder": _reorder}
CONTROLS = tuple(_SPOIL)


class _Sink:
    """The server's half of one transfer: keeps what its handler is handed
    and, once it holds the byte count the ``Open`` request named, writes
    one receipt of what it counted on its half of the stream."""

    def __init__(self, want_bytes: int):
        self.want_bytes = want_bytes
        self.held = []  # IOBufs, in the order the handler got them
        self.nbytes = 0
        self.receipt_sent = False

    def on_received_messages(self, stream, messages) -> None:
        self.held.extend(messages)
        self.nbytes += sum(len(m) for m in messages)
        if self.nbytes >= self.want_bytes and not self.receipt_sent:
            self.receipt_sent = True
            stream.write(COUNTS.pack(self.nbytes, len(self.held)))

    def on_closed(self, stream) -> None:
        pass

    def on_failed(self, stream, error_code, reason) -> None:
        pass


class _Receipt:
    """The client's handler: the sink's one message back."""

    def __init__(self):
        self.counts = None
        self.arrived = threading.Event()

    def on_received_messages(self, stream, messages) -> None:
        self.counts = COUNTS.unpack(bytes(messages[0]))
        self.arrived.set()

    def on_closed(self, stream) -> None:
        self.arrived.set()

    def on_failed(self, stream, error_code, reason) -> None:
        self.arrived.set()


class _Transfer:
    """What the generator reads of one transfer: ``failed()``, the request
    as ``response_payload`` and, as ``response_attachment``, the sink's
    record joined when it is first read (the clock has stopped by then);
    the record's boundaries are compared on the same occasion."""

    def __init__(self, deployment, request: bytes, attachment: bytes, sink,
                 error=None):
        self._deployment, self._attachment = deployment, attachment
        self._sink, self._error = sink, error
        self._record = None
        self.response_payload = request

    def failed(self) -> bool:
        return self._error is not None

    @property
    def error_text(self) -> str:
        return self._error or ""

    @property
    def response_attachment(self) -> bytes:
        if self._record is None:
            self._record = self._deployment.read_record(
                self._sink, self._attachment)
        return self._record


class _StreamChannel:
    """``call_method`` of the harness is one transfer."""

    def __init__(self, deployment):
        self._deployment = deployment

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        timeout_s = (cntl.timeout_ms if cntl is not None else 60000) / 1e3
        return self._deployment.transfer(
            service, method, request, attachment, timeout_s)


class Deployment(_link.Deployment):
    def __init__(self, config: dict, control, spans):
        from incubator_brpc_tpu.rpc import Server, StreamOptions, stream_accept

        self._stream = dict(config["stream"])
        self._window = int(self._stream["max_buf_size"])
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        self._spoil = _SPOIL.get(control)
        self._sinks = {}  # the client's stream id -> the transfer's sink
        self._lock = threading.Lock()
        self._other_boundaries = 0
        self._other_counts = 0
        self._overrun = 0
        self.message_bytes = None  # warm() reads it from the traffic
        raw = bool(self._stream["raw_messages"])

        def open_stream(cntl, request):
            want_bytes, _messages = COUNTS.unpack(request)
            sink = _Sink(want_bytes)
            accepted = stream_accept(
                cntl, StreamOptions(handler=sink, raw_messages=raw))
            if accepted is None:
                cntl.set_failed(22, "the request carries no stream")
                return b""
            self._sinks[accepted.remote_id] = sink
            return b""

        handler = open_stream if spans is None else spans.wrap(open_stream)
        self.server = Server()
        self.server.add_service("StreamService", {"Open": handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self._options = dict(config["channel_options"])
        self._want = config["link"]
        self._channel = None

    def warm(self, traffic: dict) -> None:
        """The link's exchange programs compile in the handshake, which the
        first ``channel()`` makes; the callers' untimed transfers do the
        rest. A stream has no shape of its own."""
        quarter = max(1, min(traffic["sizes"]) // 4)
        self.message_bytes = min(int(traffic["message_bytes"]), quarter)
        super().channel()

    def channel(self):
        return _StreamChannel(self)

    def transfer(self, service, method, request, attachment, timeout_s):
        """One transfer on the caller's thread; the clock is the caller's."""
        from incubator_brpc_tpu.rpc import Controller, StreamOptions, stream_create
        from incubator_brpc_tpu.utils.status import ErrorCode

        size, each = len(attachment), self.message_bytes
        cuts = range(0, size, each)
        deadline = time.monotonic() + timeout_s
        receipt = _Receipt()
        stream = stream_create(StreamOptions(
            handler=receipt, max_buf_size=self._window,
            messages_in_batch=int(self._stream["messages_in_batch"])))

        def failed(why: str):
            stream.close()
            self._sinks.pop(stream.id, None)
            return _Transfer(self, request, attachment, None, why)

        cntl = super().channel().call_method(
            service, method, COUNTS.pack(size, len(cuts)),
            request_stream=stream, cntl=Controller(timeout_ms=timeout_s * 1e3))
        if cntl.failed():
            return failed(f"Open failed: {cntl.error_text}")
        if not stream.wait_connected(timeout_s):
            return failed("the stream did not connect")
        sink = self._sinks.pop(stream.id)
        ahead_limit = self._window + each - 1
        overrun = 0
        for at in cuts:
            message = attachment[at:at + each]
            while True:
                rc = stream.write(message, timeout=WRITE_WAIT_S)
                if rc == 0:
                    break
                if (rc not in (ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED)
                        or time.monotonic() > deadline):
                    return failed(f"write gave {rc}")
            overrun = max(overrun, stream.unconsumed_bytes - ahead_limit)
        arrived = receipt.arrived.wait(max(0.0, deadline - time.monotonic()))
        stream.close()
        if not arrived or receipt.counts is None:
            return failed("no receipt")
        with self._lock:
            self._overrun = max(self._overrun, overrun)
            self._other_counts += receipt.counts != (size, len(cuts))
        return _Transfer(self, request, attachment, sink)

    def read_record(self, sink, attachment: bytes) -> bytes:
        """After the clock: the sink's messages as bytes, spoiled where a
        control asks, their boundaries against the reference's cuts, and
        the join the generator compares."""
        held = [m if isinstance(m, bytes) else m.to_bytes() for m in sink.held]
        if self._spoil is not None:
            held = self._spoil(held)
        want = self._reference.messages(attachment, self.message_bytes)
        other = abs(len(held) - len(want)) + sum(
            got != cut for got, cut in zip(held, want))
        if other:
            with self._lock:
                self._other_boundaries += other
        return b"".join(held)

    def holds(self) -> list:
        return super().holds() + [
            ("stream_messages_with_other_boundaries", self._other_boundaries,
             0, self._other_boundaries == 0),
            ("stream_window_overrun_bytes", self._overrun,
             f"0 over max_buf_size {self._window} + message_bytes "
             f"{self.message_bytes} - 1", self._overrun == 0),
            ("stream_receipts_with_other_counts", self._other_counts, 0,
             self._other_counts == 0),
        ]
