"""InputMessenger — bytes → protocol messages (reference
src/brpc/input_messenger.cpp).

Kept semantics:
- resumable cut loop over the socket's read IOBuf: try the socket's
  remembered protocol first, then every registered parser
  (CutInputMessage + _preferred_index, input_messenger.cpp:60-129);
- a parser that raises ParseError means "not mine — try others"; all
  parsers rejecting means wire garbage → socket failed with EREQUEST;
- of N cut messages, the first N-1 are dispatched to fresh fibers and the
  LAST is processed inline in this fiber (locality optimization,
  input_messenger.cpp:143-164).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Tuple

from incubator_brpc_tpu.protocol.registry import (
    MAX_HEADER_PEEK,
    Protocol,
    protocol_registry,
)
from incubator_brpc_tpu.protocol.tbus_std import FatalParseError, ParseError
from incubator_brpc_tpu.runtime.worker_pool import global_worker_pool
from incubator_brpc_tpu.utils.flags import get_flag
from incubator_brpc_tpu.utils.status import ErrorCode

logger = logging.getLogger(__name__)

_HEADER_PEEK = 64  # covers every registered protocol's fixed header
# variable-length headers (HTTP) may need a deeper look before they can
# size the frame; bounded so a hostile peer can't make us copy the world
_MAX_HEADER_PEEK = MAX_HEADER_PEEK


class InputMessenger:
    # sockets probe this before passing defer_tail: protocol clients
    # (memcache/resp) and test sinks duck-type `process(sock)` without it
    supports_defer_tail = True

    def __init__(self, protocols: Optional[List[Protocol]] = None):
        self._protocols = protocols  # None -> live registry order

    def _ordered(self, sock) -> List[Protocol]:
        protos = (
            self._protocols
            if self._protocols is not None
            else protocol_registry.ordered()
        )
        protos = [
            p for p in protos if p.enabled_for is None or p.enabled_for(sock)
        ]
        pref = sock.preferred_protocol
        if pref is not None and pref in protos and protos[0] is not pref:
            protos = [pref] + [p for p in protos if p is not pref]
        return protos

    def process(self, sock, defer_tail: bool = False):
        """Cut and dispatch every complete message in sock._read_buf.

        ``defer_tail=True`` (the reactor's ProcessEvent path): the last
        plain message is NOT processed here — it is returned as
        ``(proto, frame)`` for the caller to run AFTER releasing the
        socket's read state. The reference gets this for free from M:N
        bthreads (the tail runs in-place but a new event starts a new
        ProcessEvent); without it, a handler that blocks — e.g. issuing a
        nested RPC back over the SAME connection — holds the reader and
        later requests on that connection are never cut: self-call
        deadlock (examples/cascade_echo.py is the regression test)."""
        cut: List[Tuple[Protocol, object]] = []
        buf = sock._read_buf
        max_body = int(get_flag("max_body_size"))
        retry_others = False
        while True:
            pref = sock.preferred_protocol
            # stateful protocols (parse_conn) can frame messages smaller
            # than any fixed header (a 2-byte RTMP continuation chunk),
            # and may hold already-cut messages in connection state that
            # must drain even when the byte buffer is empty: always ask
            has_conn_state = pref is not None and pref.parse_conn
            if not has_conn_state and len(buf) < 8:
                break
            # native fast path: once the connection's protocol is known and
            # it can cut directly off the read chain, skip the peek/copy
            # machinery entirely (the steady state for binary connections).
            # A ParseError here falls through ONCE to the full protocol scan
            # (the reference's TRY_OTHERS), which terminates the connection
            # itself if nothing matches.
            if pref is not None and pref.parse_conn is not None and not retry_others:
                # stateful per-connection cut (RTMP): the protocol owns the
                # connection's bytes once preferred; consumed-without-frame
                # means handshake progress
                try:
                    frame, consumed = pref.parse_conn(sock, buf)
                except FatalParseError as e:
                    self._dispatch(sock, cut)  # never defer on a dying conn
                    sock.set_failed(ErrorCode.EREQUEST, f"corrupt frame: {e}")
                    return None
                except ParseError as e:
                    self._dispatch(sock, cut)
                    sock.set_failed(ErrorCode.EREQUEST, f"unparsable: {e}")
                    return None
                if frame is not None:
                    cut.append((pref, frame))
                    continue
                if consumed:
                    continue
                break  # incomplete: wait for more bytes
            if pref is not None and pref.parse_iobuf is not None and not retry_others:
                try:
                    frame, consumed = pref.parse_iobuf(
                        buf, max_total=max_body + _MAX_HEADER_PEEK
                    )
                except FatalParseError as e:
                    # bytes already consumed: the stream cannot re-sync
                    self._dispatch(sock, cut)
                    sock.set_failed(ErrorCode.EREQUEST, f"corrupt frame: {e}")
                    return None
                except ParseError:
                    retry_others = True
                    continue
                if frame is not None:
                    cut.append((pref, frame))
                    continue
                break  # incomplete: wait for more bytes
            retry_others = False
            header = buf.to_bytes(_HEADER_PEEK)
            matched = None
            total = None
            for proto in self._ordered(sock):
                if proto.parse_header is None:
                    # header-blind protocol: full-parse fallback (copies the
                    # pending buffer — protocols should provide parse_header)
                    try:
                        frame, consumed = proto.parse(buf.to_bytes())
                    except ParseError:
                        continue
                    if frame is None:
                        matched, total = proto, None  # needs more bytes
                        break
                    buf.popn(consumed)
                    sock.preferred_protocol = proto
                    cut.append((proto, frame))
                    matched, total = proto, -1  # -1: already consumed
                    break
                try:
                    total = proto.parse_header(header)
                    if total is None and len(buf) > len(header):
                        # header block longer than the fast peek: re-peek
                        # deeper before concluding "incomplete"
                        deeper = buf.to_bytes(min(len(buf), _MAX_HEADER_PEEK))
                        if len(deeper) > len(header):
                            total = proto.parse_header(deeper)
                except FatalParseError as e:
                    # the protocol MATCHED but the frame is unacceptable
                    # (oversized chunked upload, unsupported coding): fail
                    # with the protocol's own diagnostic instead of the
                    # generic try-others "unparsable bytes"
                    self._dispatch(sock, cut)
                    sock.set_failed(
                        ErrorCode.EREQUEST, f"{proto.name}: {e}"
                    )
                    return None
                except ParseError:
                    continue
                matched = proto
                break
            if matched is None:
                self._dispatch(sock, cut)
                sock.set_failed(ErrorCode.EREQUEST, "unparsable bytes on the wire")
                return None
            if total == -1:
                continue  # fallback path already cut one frame
            sock.preferred_protocol = matched
            if total is None:
                if matched.parse_conn is not None:
                    # a stateful protocol signalled takeover (e.g. an HTTP
                    # chunked request whose size is unknowable up front):
                    # loop so parse_conn sees the already-buffered bytes —
                    # a plain break could stall forever if the client has
                    # sent everything and is waiting on us
                    continue
                break  # header itself incomplete
            # flag bounds the *body*; allow any registered header on top
            if total > max_body + _MAX_HEADER_PEEK:
                self._dispatch(sock, cut)
                sock.set_failed(
                    ErrorCode.EREQUEST, f"frame of {total} B exceeds max_body_size"
                )
                return None
            if len(buf) < total:
                break
            raw = buf.to_bytes(total)
            buf.popn(total)
            try:
                frame, consumed = matched.parse(raw)
            except ParseError as e:
                self._dispatch(sock, cut)
                sock.set_failed(ErrorCode.EREQUEST, f"corrupt frame: {e}")
                return None
            if frame is None or consumed != total:
                self._dispatch(sock, cut)
                sock.set_failed(ErrorCode.EREQUEST, "parser/header length mismatch")
                return None
            cut.append((matched, frame))
        return self._dispatch(sock, cut, defer_tail=defer_tail)

    def _dispatch(self, sock, cut, defer_tail: bool = False):
        if not cut:
            return None
        # arrival stamp for deadline propagation: a request's remaining
        # budget (meta timeout_ms) is measured from when its frame was cut
        # off the wire, so time spent queued behind the worker pool or
        # earlier frames of this burst counts against it (the server sheds
        # expired-mid-queue work with EDEADLINE). One clock read per burst.
        now = time.monotonic()
        for _proto, frame in cut:
            try:
                frame.arrival_ts = now
            except AttributeError:
                pass  # __slots__ frame (HTTP): no binary deadline to carry
        # Two classes of frame must be handled inline, in wire order, on
        # this (single-per-socket) reader fiber:
        # - stream frames: their per-stream ExecutionQueue push must happen
        #   in order (the reference routes streaming messages during the
        #   parse phase for the same reason, SURVEY §3.4);
        # - frames whose protocol has no correlation ids (HTTP): responses
        #   must be written in request order.
        # Everything else gets the N-1-fibers + last-inline treatment.
        rest = []
        # a DeviceSocket keeps a unary frame that names a body on its
        # link's lane until the lane has handed that body over; it is
        # asked here, in wire order, as its order stage needs
        hold = getattr(sock, "hold_for_body", None)
        for proto, frame in cut:
            if hold is not None and hold(proto, frame):
                continue
            pre = getattr(frame, "pre_dispatch", None)
            if pre is not None:
                # ordering hooks (HTTP response-order gates) run at
                # dispatch time, in wire order — never at cut time, where
                # earlier frames of the same burst would observe them
                pre(sock)
            if getattr(frame, "force_worker", False):
                # e.g. a progressive-upload handler: it blocks reading a
                # body THIS fiber feeds — running it inline would deadlock,
                # and it must spawn IN WIRE ORDER (a later inline frame may
                # park on its completion gate; spawning late would wedge
                # the reader fiber behind a handler that never started)
                global_worker_pool().spawn(self._process_one, sock, proto, frame)
                continue
            inline = getattr(frame, "process_inline", False) or (
                getattr(frame, "is_stream", False)
                and proto.process_stream is not None
            )
            if inline:
                self._process_one(sock, proto, frame)
            else:
                rest.append((proto, frame))
        if not rest:
            return None
        pool = global_worker_pool()
        for proto, frame in rest[:-1]:
            pool.spawn(self._process_one, sock, proto, frame)
        proto, frame = rest[-1]
        if defer_tail:
            # caller runs it after releasing the socket's read state, so a
            # handler that blocks cannot wedge this connection's reads
            return (proto, frame)
        self._process_one(sock, proto, frame)  # last message inline
        return None

    def process_device_message(self, sock, tag, body) -> None:
        """A device message as a link's lane hands it over
        (``DeviceSocket._lane_deliver``): its tag's words and its body, a
        device array on this side's device. The tag is the tbus_std frame
        that would head the body on the byte stream, cut by the parser
        that cuts those, so magic and checksum hold for it too; one that
        does not parse fails the socket. The frame goes where a frame off
        the byte stream goes, the body its attachment: a stream's data
        frame to its stream and an answer to its waiting call, both here
        on the lane's in-order deliverer (neither blocks); a request to a
        worker, where the server's handlers run, so that a slow handler
        holds no later message of this side."""
        from incubator_brpc_tpu import protocol as proto_pkg
        from incubator_brpc_tpu.protocol.tbus_std import try_parse_frame

        handed_ns = time.monotonic_ns()
        try:
            frame, _ = try_parse_frame(tag.tobytes())
            if frame is None:
                raise ParseError("not a whole frame")
        except (ParseError, ValueError) as e:
            sock.set_failed(ErrorCode.EREQUEST, f"a device message's tag: {e}")
            return
        extra = frame.meta.extra
        if extra.get("unary_body"):
            # the body of a unary frame too long for a tag: paired with
            # that frame, which rides the byte stream, by the socket
            sock.hold_body(body, int(extra.get("frames_before", 0)))
            return
        frame.attachment = body
        frame.handed_ns = handed_ns
        frame.arrival_ts = handed_ns / 1e9
        proto = proto_pkg.TBUS_STD
        if frame.is_stream or frame.is_response:
            self._process_one(sock, proto, frame)
        else:
            global_worker_pool().spawn(self._process_one, sock, proto, frame)

    @staticmethod
    def _process_one(sock, proto: Protocol, frame) -> None:
        try:
            if (
                getattr(frame, "is_stream", False)
                and proto.process_stream is not None
            ):
                proto.process_stream(sock, frame)
            elif sock.user_message_handler is not None:
                sock.user_message_handler(sock, frame, proto)
            elif getattr(frame, "is_response", False):
                if proto.process_response is not None:
                    proto.process_response(sock, frame)
            elif proto.process_request is not None:
                proto.process_request(sock, frame)
            else:
                logger.warning(
                    "no handler for %s message on %r", proto.name, sock
                )
        except Exception:
            logger.exception("message handler failed on %r", sock)
