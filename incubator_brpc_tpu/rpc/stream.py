"""Streaming RPC — ordered, flow-controlled byte-message streams riding an
established connection (reference src/brpc/stream.{h,cpp}, stream_impl.h,
policy/streaming_rpc_protocol.cpp).

Kept design points (and where they live in the reference):
- The handshake piggybacks on a normal RPC (``request_stream`` in RpcMeta):
  the client creates a half-open stream whose id travels in the request
  meta; the server accepts inside the handler and returns its own id in
  the response meta (stream.cpp StreamCreate/StreamAccept; SURVEY §3.4).
- Data path: every received message is pushed into a per-stream
  ExecutionQueue so one consumer fiber handles messages in order
  (stream.cpp:86 _fake_socket + execution_queue consumer).
- Flow control: the writer may have at most ``max_buf_size`` bytes
  unconsumed by the remote; past that, ``write`` parks on a butex until a
  feedback frame lifts ``_remote_consumed``
  (Stream::AppendIfNotFull stream.cpp:263-300, SetRemoteConsumed :287).
- Close is a frame like any other; the consumer sees it in order, fires
  ``on_closed``, and the registry entry dies (versioned ids are not needed:
  ids are never reused).
- A message may be a device array. Over a ``DeviceSocket`` whose link
  runs between two devices, ``write`` of a ``jax.Array`` hands the array
  to the link's lane (``transport/device_link.py``) with its **tag**: the
  data frame that would head it, empty body and all, as the lane's opaque
  words. Both cross chip to chip in one program, nothing of the message
  rides the byte stream, and the handler is handed a ``jax.Array`` on its
  own device. The window counts its ``nbytes``. Over any other socket the
  array's bytes go as a bytes message.
- Two FIFO carriers then feed one stream, the byte stream (bytes
  messages, close) and the lane (device messages), and either may be the
  faster. Each message names how many messages of the *other* carrier its
  writer had sent on the stream before it (``arrays_before`` in a frame's
  meta, absent while the stream has sent no array; ``frames_before`` in a
  tag), and the reader releases it to the ordered consumer when that many
  have been released: the handler sees the messages in the order written.
  A stream that never carried an array never enters that stage.

Always-on recorders (docs/OBSERVABILITY.md, "Streams"): a stream counts
under ``device_link_stream_*`` once it rides a ``DeviceSocket`` and under
``stream_*`` on a host socket. The hot paths stamp and append one row;
bvar's 1 Hz sampler feeds the recorders (``RecorderFeed``).

Deviation: the reference routes writes through a fake Socket so the
wait-free write queue is shared (STREAM_FAKE_FD, socket.h:193); here stream
frames are packed directly onto the real Socket's MPSC write queue — same
single-drainer property, one less indirection.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Union

from incubator_brpc_tpu import protocol as proto_pkg
from incubator_brpc_tpu.bvar import Adder, LatencyRecorder, RecorderFeed
from incubator_brpc_tpu.iobuf import IOBuf
from incubator_brpc_tpu.protocol.tbus_std import (
    FLAG_STREAM,
    Meta,
    ParsedFrame,
    pack_frame,
    pack_frame_iobuf,
)
from incubator_brpc_tpu.runtime.butex import Butex, ETIMEDOUT
from incubator_brpc_tpu.runtime.execution_queue import ExecutionQueue, TaskIterator
from incubator_brpc_tpu.transport.device_link import (
    BYTE_STREAM as _BYTE_STREAM,
    LANE as _LANE,
    CarrierOrder,
    array_carrier,
)
from incubator_brpc_tpu.utils.status import ErrorCode

if TYPE_CHECKING:
    import jax

logger = logging.getLogger(__name__)

# what a message is on the host; anything else ``write`` is given has to
# be a device array
_HOST_MESSAGE = (bytes, bytearray, memoryview, IOBuf)
# what a handler is handed, and what ``write`` takes
Message = Union[bytes, IOBuf, "jax.Array"]

# frame kinds inside meta.extra["ft"] (reference StreamFrameMeta.frame_type:
# FRAME_TYPE_DATA / FEEDBACK / CLOSE / RST, streaming_rpc_meta.proto)
FT_DATA = "data"
FT_FEEDBACK = "fb"
FT_CLOSE = "close"
FT_RST = "rst"
# the consumer queue's own kind, never on the wire: a data frame that came
# over the link's lane as a tag, beside its body, a device array
_FT_DEVICE = "device"

IDLE = 0
CONNECTING = 1
CONNECTED = 2
CLOSED = 3


# rows of each feed that stay for a timeline's reader: 30 s of a stream of
# 1 MiB messages many times over
_RING_ROWS = 1 << 13


class _StreamVars:
    """One namespace of stream recorders and adders. Rows wait in the
    feeds for the sampler thread; ``flush`` feeds them now (tests)."""

    def __init__(self, prefix: str, lane: bool = False):
        def recorder(what: str) -> LatencyRecorder:
            return LatencyRecorder(name=f"{prefix}_{what}")

        def adder(what: str) -> Adder:
            return Adder(name=f"{prefix}_{what}")

        # Rows hold stamps (time.monotonic_ns()); the sampler cuts the
        # times. writer: an admitted write entered and was admitted (the
        # same stamp twice when it never parked on the window: 0), and the
        # bytes it found unconsumed ahead of it
        self.writes = RecorderFeed(
            (
                (recorder("write_wait_us"), 1e-3, ("enter", "admitted")),
                (recorder("unconsumed_at_write"), 1, "ahead"),
            ),
            stamps=("enter", "admitted", "ahead"),
            name=f"{prefix}_writes", ring_rows=_RING_ROWS,
            call=(("enter", "admitted"),),
        )
        # a write admitted -> the feedback frame covering its last byte applied
        self.feedbacks = RecorderFeed(
            ((recorder("feedback_lag_us"), 1e-3, ("admitted", "applied")),),
            stamps=("admitted", "applied"),
            name=f"{prefix}_feedbacks", ring_rows=_RING_ROWS,
            call=(("admitted", "applied"),),
        )
        # reader: _on_frame, or the lane's hand-over -> the handler entered
        # for that message (holds a message's wait for the other carrier)
        self.delivers = RecorderFeed(
            ((recorder("deliver_us"), 1e-3, ("frame", "handler")),),
            stamps=("frame", "handler"),
            name=f"{prefix}_delivers", ring_rows=_RING_ROWS,
            call=(("frame", "handler"),),
        )
        # time inside on_received_messages, a batch, on the consumer fiber
        self.consumes = RecorderFeed(
            ((recorder("consume_us"), 1e-3, ("handler", "returned")),),
            stamps=("handler", "returned"),
            name=f"{prefix}_consumes", ring_rows=_RING_ROWS,
            worker=(("handler", "returned"),),
        )
        self.messages = adder("messages")  # handed to a handler
        self.batches = adder("batches")  # on_received_messages calls
        self.bytes = adder("bytes")  # of those messages that were host bytes
        # of those messages, the device arrays, and their nbytes: only a
        # DeviceSocket's lane delivers one
        self.device_messages = adder("device_messages") if lane else None
        self.device_bytes = adder("device_bytes") if lane else None
        self.feedback_frames = adder("feedback_frames")  # sent
        self.write_retries = adder("write_retries")  # EAGAIN/EOVERCROWDED returned

    def flush(self) -> None:
        for feed in (self.writes, self.feedbacks, self.delivers, self.consumes):
            feed.flush()


HOST_VARS = _StreamVars("stream")
LINK_VARS = _StreamVars("device_link_stream", lane=True)


class StreamOptions:
    """Reference StreamOptions (stream.h:40-78)."""

    def __init__(
        self,
        handler: Optional["StreamHandler"] = None,
        max_buf_size: int = 2 * 1024 * 1024,
        messages_in_batch: int = 128,
        raw_messages: bool = False,
    ):
        self.handler = handler
        self.max_buf_size = max_buf_size  # 0 = unlimited (no flow control)
        self.messages_in_batch = messages_in_batch
        # True: on_received_messages gets zero-copy IOBuf objects (the
        # reference's contract — stream.h hands butil::IOBuf*s); False
        # (default) keeps this API's bytes convenience, materialized at
        # consumption on the ordered consumer fiber
        self.raw_messages = raw_messages


class StreamHandler:
    """User callbacks (reference StreamInputHandler, stream.h:29-38).
    Subclass and override; all run on the stream's ordered consumer fiber."""

    def on_received_messages(self, stream: "Stream", messages: List[Message]) -> None:
        """A batch of messages in the order they were written. Each is
        ``bytes`` (an ``IOBuf`` under ``raw_messages``) or, where the
        writer wrote a device array and the link under the stream has a
        lane, a ``jax.Array`` of the written shape and dtype on this
        side's device. A stream may mix the two; order and boundaries hold
        across both. The writer's window reopens by the batch's bytes
        (an array's ``nbytes``) when this returns."""
        pass

    def on_closed(self, stream: "Stream") -> None:
        pass

    def on_failed(self, stream: "Stream", error_code: int, reason: str) -> None:
        """Transport died under the stream (no CLOSE will follow)."""
        self.on_closed(stream)


class Stream:
    """One direction-pair endpoint. Not built directly — use
    ``stream_create`` (client) / ``stream_accept`` (server handler)."""

    def __init__(self, stream_id: int, options: StreamOptions, is_client: bool):
        self.id = stream_id
        self.options = options
        self.is_client = is_client
        self.state = CONNECTING if is_client else IDLE
        self.error_code = 0
        self.error_text = ""
        self.remote_id: int = 0
        self._sock = None
        self._lock = threading.Lock()
        # writer-side window (stream.cpp:263-300)
        self._produced = 0  # bytes written to the wire
        self._remote_consumed = 0  # last feedback
        self._wbutex = Butex(0)
        # admitted writes no feedback covers yet: [end offset, admitted ns];
        # bounded, so a peer that never feeds back costs samples, not memory
        self._unacked: deque = deque(maxlen=1024)
        self._vars = HOST_VARS  # LINK_VARS once connected over a device link
        # reader side
        self._consumed = 0  # bytes this side has handled
        self._last_feedback = 0  # _consumed value last told to the peer
        self._rq: ExecutionQueue = ExecutionQueue(
            self._consume, max_batch=options.messages_in_batch
        )
        self._close_sent = False
        self._connected_event = threading.Event()
        # writer side of the order across the two carriers: messages this
        # stream has sent for certain, [data frames, device messages], and
        # the lock a carrier that keeps its sends one at a time
        self._wrote = [0, 0]
        self._send_locks = (threading.Lock(), threading.Lock())
        # reader side: a message goes on to the consumer once the messages
        # of the other carrier that were written before it have gone
        self._order = CarrierOrder(self._rq.execute)

    # -- connection plumbing (module-level handshake hooks call these) ------

    def _connect(self, sock, remote_id: int) -> None:
        with self._lock:
            if self.state == CLOSED:
                return
            self._sock = sock
            self.remote_id = remote_id
            self.state = CONNECTED
            if hasattr(sock, "link"):  # a DeviceSocket
                self._vars = LINK_VARS
        sock.on_failed.append(self._on_socket_failed)
        self._connected_event.set()

    def wait_connected(self, timeout: Optional[float] = None) -> bool:
        """Client: block until the handshake response arrived (the reference
        blocks the first StreamWrite instead; explicit is clearer)."""
        return self._connected_event.wait(timeout)

    # -- writer side --------------------------------------------------------

    def write(self, data: Message, timeout: Optional[float] = None) -> int:
        """Send one message. 0 on success; EAGAIN if the window is full and
        ``timeout`` expired (timeout=0 → immediate EAGAIN, None → block
        forever); EOVERCROWDED if the socket backlog refused the frame
        (transient — retry); EINVAL once closed/failed.

        ``data`` is ``bytes``, an ``IOBuf`` or a ``jax.Array``. What
        happens to an array is chosen from what the socket under the
        stream is, and nothing configures it:

        - a ``DeviceSocket`` whose link runs between two devices: the
          array has to lie whole on the device this side of the link
          drives, with at least one dimension and one element (else
          EINVAL). It is admitted against ``max_buf_size`` by its
          ``nbytes`` and goes whole to the link's lane; the far handler is
          handed a ``jax.Array`` of that shape and dtype on its own
          device, in its place among the stream's other messages. The
          stream keeps the array until the lane's program has it; **the
          writer may not write into, donate or delete it until the message
          was consumed** (``unconsumed_bytes`` has fallen past it): the
          program reads it where it lies.
        - a multi-controller link (``transport/mc_link.py``) has no lane
          yet: EINVAL.
        - a host socket, or a link on one shared device (the host swap):
          there is no second device to land on; the array's bytes are
          sent as a bytes message and the far handler is handed bytes."""
        array = None
        if not isinstance(data, _HOST_MESSAGE):
            array, data = self._device_message(data)
            if data is None:
                return ErrorCode.EINVAL
        n = len(data) if array is None else array.nbytes
        carrier = _BYTE_STREAM if array is None else _LANE
        limit = self.options.max_buf_size
        deadline = None if timeout is None else time.monotonic() + timeout
        t_enter = time.monotonic_ns()
        parked = False
        while True:
            with self._lock:
                if self.state != CONNECTED:
                    return ErrorCode.EINVAL
                # Admit while the current gap is below the limit — one
                # in-flight message may overshoot the window, so a message
                # larger than max_buf_size still goes out on an idle stream
                # (AppendIfNotFull stream.cpp:263 checks the same way).
                ahead = self._produced - self._remote_consumed
                if not limit or ahead < limit:
                    self._produced += n
                    sock, rid = self._sock, self.remote_id
                    admitted = [self._produced, time.monotonic_ns()]
                    self._unacked.append(admitted)
                    break
            parked = True
            if timeout == 0:
                self._vars.write_retries << 1
                return ErrorCode.EAGAIN
            remaining = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._vars.write_retries << 1
                    return ErrorCode.EAGAIN
            seq = self._wbutex.load()
            with self._lock:
                blocked = (
                    self.state == CONNECTED
                    and limit
                    and (self._produced - self._remote_consumed) >= limit
                )
            if blocked and self._wbutex.wait(seq, timeout=remaining) == ETIMEDOUT:
                self._vars.write_retries << 1
                return ErrorCode.EAGAIN
        self._vars.writes.rows.append(
            (t_enter, admitted[1] if parked else t_enter, ahead)
        )
        meta = Meta(stream_id=rid, extra={"ft": FT_DATA, "from": self.id})
        # One send at a time a carrier, and the message counted before the
        # next is sent: a count is then a place in the carrier's order, and
        # what a later message of the other carrier names has all been
        # sent. Counted once the carrier has it and not before: a refused
        # write leaves nothing to wait for.
        with self._send_locks[carrier]:
            before = self._wrote[1 - carrier]  # of the other carrier
            if array is not None:
                meta.extra["frames_before"] = before
                rc = sock.lane.lane_send(
                    sock.side, array, pack_frame(meta, b"", 0, flags=FLAG_STREAM)
                )
            else:
                if before:  # a stream that sent no array: today's frame
                    meta.extra["arrays_before"] = before
                # IOBuf pack: no body/frame concat copies on the data hot
                # path. drain_inline: this thread is blocking-capable (it
                # just passed the credit window), so it drives the
                # kernel-buffer drain itself — no KeepWrite fiber + reactor
                # wakeup relay per buffer-full cycle. The drain gets the
                # REMAINING budget (the window wait above may have consumed
                # most of ``timeout``), and its expiry only falls back to
                # the KeepWrite fiber — the frame is still sent.
                drain_budget = None
                if deadline is not None:
                    drain_budget = max(0.0, deadline - time.monotonic())
                rc = sock.write(
                    pack_frame_iobuf(meta, data, 0, flags=FLAG_STREAM),
                    timeout=drain_budget,
                    drain_inline=True,
                )
            if rc == 0:
                self._wrote[carrier] += 1  # this lock is its one writer's
                return 0
        refused = array is not None and rc == ErrorCode.EINVAL  # its tag too long
        if rc == ErrorCode.EOVERCROWDED or refused:
            # transient socket backpressure (socket.cpp:1537), or a message
            # the lane took nothing of: surface it, don't kill the stream;
            # the rollback reopens the window so any writer parked on it
            # must be woken (no feedback will do it)
            with self._lock:
                self._produced -= n
                self._forget_write_locked(admitted, n)
            self._wbutex.add(1)
            self._wbutex.wake_all()
            if not refused:
                self._vars.write_retries << 1
            return rc
        self._fail(rc, "stream data write failed")
        return rc

    def _device_message(self, array) -> tuple:
        """What ``write`` sends for a message that is no host bytes:
        ``(array, b"")`` for the lane, ``(None, its bytes)`` where the
        socket has no second device, ``(None, None)`` where it is refused:
        ``array_carrier``'s rule, which a unary call's attachment follows
        too."""
        sock = self._sock
        if sock is None:
            array_carrier(None, array)  # still a TypeError for what is no array
            return None, None
        return array_carrier(sock, array)

    def _set_remote_consumed(self, consumed: int) -> None:
        """Feedback arrived (SetRemoteConsumed stream.cpp:287): lift the
        window and wake blocked writers."""
        with self._lock:
            if consumed <= self._remote_consumed:
                return
            self._remote_consumed = consumed
            now = time.monotonic_ns()
            unacked, lags = self._unacked, self._vars.feedbacks.rows
            while unacked and unacked[0][0] <= consumed:
                lags.append((unacked.popleft()[1], now))
        self._wbutex.add(1)
        self._wbutex.wake_all()

    def _forget_write_locked(self, admitted: list, n: int) -> None:
        """A rolled-back write leaves the feedback-lag ledger: later
        writes' end offsets fall by its ``n`` bytes, as ``_produced`` did."""
        kept, after = [], False
        for entry in self._unacked:
            if entry is admitted:
                after = True
                continue
            if after:
                entry[0] -= n
            kept.append(entry)
        self._unacked = deque(kept, maxlen=self._unacked.maxlen)

    # -- reader side --------------------------------------------------------

    def _on_frame(self, frame: ParsedFrame) -> None:
        extra = frame.meta.extra
        ft = extra.get("ft", FT_DATA)
        if ft == FT_FEEDBACK:
            self._set_remote_consumed(int(extra.get("consumed", 0)))
            return
        # the native parse path leaves stream payloads as zero-copy IOBuf
        # cuts; the consumer materializes only when the handler wants bytes
        data = frame.payload_iobuf
        task = (ft, frame.payload if data is None else data, time.monotonic_ns())
        self._order.arrive(
            _BYTE_STREAM, task, int(extra.get("arrays_before", 0)), ft == FT_DATA
        )

    def _on_device_message(self, extra: dict, body) -> None:
        """The lane handed over a device message of this stream: its tag's
        meta and its array on this side's device. This moment is its
        ``arrived`` stamp: ``deliver_us`` holds its wait for the byte
        stream."""
        task = (_FT_DEVICE, body, time.monotonic_ns())
        self._order.arrive(_LANE, task, int(extra.get("frames_before", 0)))

    def _consume(self, it: TaskIterator) -> None:
        """Ordered consumer fiber (stream.cpp:86): batch data messages to the
        handler, then feed consumption back to the writer."""
        handler = self.options.handler
        batch: List[Message] = []
        arrived: List[int] = []  # when each message reached this stream
        closed = False
        raw = self.options.raw_messages
        nbytes = device_messages = device_bytes = 0
        for ft, payload, t_frame in it:
            if ft == FT_DATA:
                if not raw and not isinstance(payload, (bytes, bytearray)):
                    payload = payload.to_bytes()  # IOBuf -> bytes contract
                elif raw and isinstance(payload, (bytes, bytearray)):
                    # parse paths that materialized bytes (pure-python
                    # fallback, native-plane dispatch) still honor the raw
                    # IOBuf contract: wrap, don't surprise the handler
                    wrapped = IOBuf()
                    wrapped.append(bytes(payload))
                    payload = wrapped
                nbytes += len(payload)
                batch.append(payload)
                arrived.append(t_frame)
            elif ft == _FT_DEVICE:
                device_messages += 1
                device_bytes += payload.nbytes
                batch.append(payload)  # the array, as the lane landed it
                arrived.append(t_frame)
            elif ft in (FT_CLOSE, FT_RST):
                closed = True
        if batch:
            self._consumed += nbytes + device_bytes
            v = self._vars
            t_in = time.monotonic_ns()
            if handler is not None:
                try:
                    handler.on_received_messages(self, batch)
                except Exception:
                    logger.exception("stream %d handler raised", self.id)
            v.consumes.rows.append((t_in, time.monotonic_ns()))
            v.delivers.rows.extend((t, t_in) for t in arrived)
            v.messages << len(batch)
            v.batches << 1
            v.bytes << nbytes
            if device_messages:
                v.device_messages << device_messages
                v.device_bytes << device_bytes
            self._send_feedback()
        if closed or it.is_queue_stopped():
            self._finish_close(notify=closed)

    def _send_feedback(self) -> None:
        with self._lock:
            if self.state != CONNECTED or self._consumed == self._last_feedback:
                return
            self._last_feedback = self._consumed
            sock, rid, consumed = self._sock, self.remote_id, self._consumed
        meta = Meta(stream_id=rid, extra={"ft": FT_FEEDBACK, "consumed": consumed})
        sock.write(pack_frame(meta, b"", 0, flags=FLAG_STREAM))
        self._vars.feedback_frames << 1

    # -- close / failure ----------------------------------------------------

    def close(self) -> None:
        """Send CLOSE; the peer's consumer sees it in order after all data
        (StreamClose stream.cpp)."""
        with self._lock:
            if self.state != CONNECTED or self._close_sent:
                self.state = CLOSED
                self._connected_event.set()
                _registry_remove(self.id)
                return
            self._close_sent = True
            sock, rid, arrays = self._sock, self.remote_id, self._wrote[_LANE]
        meta = Meta(stream_id=rid, stream_close=True, extra={"ft": FT_CLOSE})
        if arrays:  # the far consumer closes after the arrays still on the lane
            meta.extra["arrays_before"] = arrays
        sock.write(pack_frame(meta, b"", 0, flags=FLAG_STREAM))
        # the local side is closed immediately; the consumer queue keeps
        # draining whatever the peer already sent
        self._finish_close(notify=False)

    def _finish_close(self, notify: bool) -> None:
        with self._lock:
            was_closed = self.state == CLOSED
            self.state = CLOSED
        # a closed or failed stream has left the registry: what a held
        # message waits for can no longer reach it
        self._order.clear()
        self._connected_event.set()
        self._wbutex.add(1)
        self._wbutex.wake_all()
        self._unhook_socket()
        _registry_remove(self.id)
        if notify and not was_closed and self.options.handler is not None:
            try:
                self.options.handler.on_closed(self)
            except Exception:
                logger.exception("stream %d on_closed raised", self.id)

    def rst(self, code: int = ErrorCode.ECLOSE, reason: str = "stream reset") -> None:
        """Force-terminate the stream NOW: tell the peer with an RST frame
        (so its writer stops instead of filling a dead window) and fail
        the local side.  The lame-duck drain uses this at grace expiry —
        a stream that outlives the drain dies cleanly here rather than
        dirtily under the final ``stop()``'s socket teardown."""
        with self._lock:
            sock, rid = self._sock, self.remote_id
            alive = self.state == CONNECTED
        if alive and sock is not None and rid:
            meta = Meta(stream_id=rid, extra={"ft": FT_RST})
            try:
                sock.write(pack_frame(meta, b"", 0, flags=FLAG_STREAM))
            except Exception:
                logger.exception("stream %d RST write failed", self.id)
        self._fail(code, reason)

    def _on_socket_failed(self, sock) -> None:
        self._fail(sock.error_code, sock.error_text or "transport failed")

    def _unhook_socket(self) -> None:
        """Drop our on_failed hook so closed streams don't accumulate on a
        long-lived connection, and release a pooled/short connection the
        channel deferred to us (the stream pinned it past EndRPC)."""
        sock = self._sock
        if sock is not None:
            try:
                sock.on_failed.remove(self._on_socket_failed)
            except ValueError:
                pass
            dispose = sock.context.pop("_stream_dispose", None)
            if dispose is not None:
                try:
                    dispose()
                except Exception:
                    logger.exception("stream connection disposal raised")

    def _fail(self, code: int, reason: str) -> None:
        with self._lock:
            if self.state == CLOSED:
                return
            self.state = CLOSED
            self.error_code = code
            self.error_text = reason
        # a closed or failed stream has left the registry: what a held
        # message waits for can no longer reach it
        self._order.clear()
        self._connected_event.set()
        self._wbutex.add(1)
        self._wbutex.wake_all()
        self._unhook_socket()
        _registry_remove(self.id)
        if self.options.handler is not None:
            try:
                self.options.handler.on_failed(self, code, reason)
            except Exception:
                logger.exception("stream %d on_failed raised", self.id)

    @property
    def _held(self) -> tuple:
        """A carrier, the messages that wait for the other one."""
        return self._order.held

    @property
    def unconsumed_bytes(self) -> int:
        with self._lock:
            return self._produced - self._remote_consumed

    def __repr__(self) -> str:
        st = {IDLE: "idle", CONNECTING: "connecting", CONNECTED: "up", CLOSED: "closed"}
        return f"<Stream id={self.id} remote={self.remote_id} {st[self.state]}>"


# -- registry + module API ---------------------------------------------------

_streams: Dict[int, Stream] = {}
_streams_lock = threading.Lock()
_next_id = itertools.count(1)


def _registry_remove(sid: int) -> None:
    with _streams_lock:
        _streams.pop(sid, None)


def get_stream(sid: int) -> Optional[Stream]:
    with _streams_lock:
        return _streams.get(sid)


def open_streams(socks=None) -> List[Stream]:
    """Live (CONNECTED) streams — all of them, or only those riding one
    of the given sockets.  ``Server.enter_lame_duck`` drains the streams
    bound to ITS connections alongside ``nprocessing`` and the active
    collective sessions: a long-lived stream is in-flight work even when
    no RPC handler is running."""
    with _streams_lock:
        items = list(_streams.values())
    live = [s for s in items if s.state == CONNECTED]
    if socks is None:
        return live
    sockset = set(socks)
    return [s for s in live if s._sock in sockset]


def stream_create(options: Optional[StreamOptions] = None) -> Stream:
    """Client side (StreamCreate stream.h:81): make the half-open stream,
    then pass it to ``Channel.call_method(..., request_stream=stream)`` —
    the id rides the request meta and the stream connects when the
    response returns."""
    s = Stream(next(_next_id), options or StreamOptions(), is_client=True)
    with _streams_lock:
        _streams[s.id] = s
    return s


def stream_accept(cntl, options: Optional[StreamOptions] = None) -> Optional[Stream]:
    """Server side (StreamAccept stream.h:96), called inside a handler whose
    request meta carries a stream id. Returns the accepted stream (already
    CONNECTED — the server knows the socket now), or None if the request
    carries no stream."""
    remote_id = getattr(cntl.request_meta, "stream_id", 0) if cntl.request_meta else 0
    sock = getattr(cntl, "_sock", None)
    if not remote_id or sock is None:
        return None
    s = Stream(next(_next_id), options or StreamOptions(), is_client=False)
    with _streams_lock:
        _streams[s.id] = s
    s._connect(sock, remote_id)
    cntl._accepted_stream_id = s.id  # echoed in the response meta
    span = getattr(cntl, "_span", None)
    if span is not None:  # /rpcz: the call that carried the handshake
        span.annotate(f"stream {s.id} accepted for remote stream {remote_id}")
    return s


def _stream_of(sock, frame: ParsedFrame) -> Optional[Stream]:
    """The open stream a frame names, or None after answering the frame."""
    s = get_stream(frame.meta.stream_id)
    if s is None:
        # peer doesn't know we're gone yet: answer data with RST so its
        # writer stops (frames carry the sender's id for exactly this)
        sender = frame.meta.extra.get("from", 0)
        if frame.meta.extra.get("ft", FT_DATA) == FT_DATA and sender:
            meta = Meta(stream_id=sender, extra={"ft": FT_RST})
            sock.write(pack_frame(meta, b"", 0, flags=FLAG_STREAM))
    return s


def process_stream(sock, frame: ParsedFrame) -> None:
    """tbus_std Protocol.process_stream hook: route a FLAG_STREAM frame to
    its stream by meta.stream_id (ParseStreamingMessage →
    Stream::OnReceived, SURVEY §3.4). A frame the messenger cut from a
    lane message's tag (``InputMessenger.process_device_message``) heads a
    device message: its attachment is the message's body, a device array
    on this side's device. One that names no open stream is dropped as a
    frame off the byte stream is."""
    s = _stream_of(sock, frame)
    if s is None:
        return
    body = frame.attachment
    if isinstance(body, (bytes, bytearray)):
        s._on_frame(frame)
    else:
        s._on_device_message(frame.meta.extra, body)


proto_pkg.TBUS_STD.process_stream = process_stream
