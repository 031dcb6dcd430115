"""Two-party device transport — the ``transport=tpu`` data plane.

The reference template is the RDMA endpoint pair (rdma/rdma_endpoint.h:
42-213): two Sockets handshake over their TCP connection ("RDMA" magic +
cookie, socket.cpp:1692-1704), then move the SAME wire frames through
queue-pair send/recv rings in registered memory with a credit window
(rdma_endpoint.h:105-123,176-195), completions feeding the normal input
path (rdma_completion_queue.cpp:152). This module is that design
re-thought for XLA devices:

- **The QP is a 2-device mesh axis.** A connection binds one device per
  party; the data primitive is one jitted *link step* that exchanges both
  parties' outbound slots in a single ``shard_map``/``ppermute`` over
  ``Mesh([dev_a, dev_b], ("link",))`` — a full-duplex DMA across ICI (on
  the test CPU mesh, across virtual devices; with both parties on one
  chip, the exchange degenerates to an on-device row swap). One dispatch
  moves both directions; in a multi-controller deployment the same jitted
  step is dispatched SPMD by each host, which is exactly how the design
  scales off one process.
- **Slots are the rings.** Each step carries a *train* of fixed-geometry
  uint32 slots per direction (negotiated ``slot_words`` each): as many
  as the fuller side's backlog fills and the credit window has free, a
  power of two (the RDMA endpoint posting the work requests its window
  admits, not one and wait). A backlog that would fill a longer train
  than the free credit admits waits for the slots in flight to land
  rather than going as a short train beside them. The link is a BYTE STREAM: queued host
  frames (tbus_std bytes — the same frames TCP carries, as RDMA carries
  baidu_std bytes) are packed head-to-tail into slots and re-cut by the
  receiver's normal InputMessenger loop. XLA's functional model replaces
  ring *reuse* with fresh step outputs, so the credit window bounds
  un-drained in-flight slots instead of ring slots.
- **Handshake rides the host socket.** The client sends a cookie +
  device/geometry proposal as an ordinary RPC on the already-connected
  TCP socket (the reference's magic+cookie over TCP); the server builds
  its half and answers with its device. Control stays on TCP, data moves
  on the device plane — the RDMA split exactly.
- **Completions are DeviceCompletionButex events.** Step outputs are
  watched; a per-link reorder buffer delivers them in sequence into each
  side's ``DeviceSocket`` read buffer and messenger (the CQ feeding
  InputMessenger, rdma_completion_queue.cpp:152).
- **Flow control**: writers park on a butex once the outbound backlog
  passes the window's byte budget (EOVERCROWDED past a hard cap); slot
  headers carry cumulative seq/ack words like the RDMA endpoint's
  piggybacked imm-data acks (rdma_endpoint.h:176-195).
- **The lane carries tensors, whole.** Beside the byte stream a
  ``ppermute`` link has a second program: a committed device array on the
  sender's device lands on the receiver's device by one ``ppermute``
  over the link's own mesh, and is handed to the receiver **as a device
  array** (``lane_send``): no host copy on either side. The message's
  **tag**, a few opaque ``uint32`` words its sender gives with it, crosses
  in the same program beside the body and is read back from the receiver's
  shard; the lane hands ``(tag, body)`` to the receiving ``DeviceSocket``
  in the order ``lane_send`` took the messages. The program is an
  exchange, as the trains' step is: a launch takes the head message of
  each direction, so a request and an answer that wait together cross in
  one program, and a message alone crosses with a placeholder in the other
  half. Nothing of a device message rides the byte stream
  (docs/DEVICE_PLANE.md, "The lane").
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from incubator_brpc_tpu.bvar import (
    CPU_CLOCK_EVERY,
    Adder,
    LatencyRecorder,
    PerSecond,
    RecorderFeed,
    clocks,
)
from incubator_brpc_tpu.protocol.tbus_std import Meta, pack_frame, pack_frame_iobuf
from incubator_brpc_tpu.runtime.butex import Butex, ETIMEDOUT
from incubator_brpc_tpu.runtime.device_butex import DeviceCompletionButex
from incubator_brpc_tpu.runtime.worker_pool import global_worker_pool
from incubator_brpc_tpu.utils.endpoint import EndPoint
from incubator_brpc_tpu.utils.status import ErrorCode

logger = logging.getLogger(__name__)

LINK_MAGIC = 0x5450554C  # "TPUL"
LINK_HEADER_WORDS = 8
# header words: 0 magic, 1 used_bytes, 2 seq, 3 ack_lo, 4 flags,
# 5 ack_hi (the delivered count is 64-bit on the wire: a wrapped 32-bit
# ack would wedge the wire-mode credit window), 6-7 reserved
F_DATA = 1
F_CLOSE = 2

HANDSHAKE_SERVICE = "_tpu_transport"
HANDSHAKE_METHOD = "handshake"

link_steps = Adder(name="device_link_steps")  # exchange programs dispatched
# slots a side those programs carried: over device_link_steps it is how
# long the trains run
link_slots = Adder(name="device_link_slots")
# of those programs, the ones whose dispatch waited for the credit the
# train its backlog wanted needs
link_held = Adder(name="device_link_held_steps")
# of those programs, the ones whose host copies were asked for at dispatch
link_prefetched = Adder(name="device_link_prefetched_steps")
# of those programs, the ones launched from one host buffer: both sides'
# slots handed to the program call, which places each device's half
link_staged = Adder(name="device_link_staged_steps")
link_bytes = Adder(name="device_link_bytes")
# payload capacity of every slot side filled: set against device_link_bytes
# it says how full the slots travel
link_capacity = Adder(name="device_link_capacity_bytes")
# the lane: programs dispatched, the messages they carried (one, or one
# each way: over lane_steps it is how often a pair formed) and those
# messages' bytes, which never enter device_link_bytes
lane_steps = Adder(name="device_link_lane_steps")
lane_messages = Adder(name="device_link_lane_messages")
lane_bytes = Adder(name="device_link_lane_bytes")
# of those programs, the ones that carried their message's tag beside it
lane_tagged = Adder(name="device_link_lane_tagged_steps")
# unary calls whose attachment was a device array: the requests and the
# answers that took the lane, their bodies' bytes, and the attachments that
# went as host bytes instead where the socket under the call has no lane
unary_lane_requests = Adder(name="device_link_unary_lane_requests")
unary_lane_replies = Adder(name="device_link_unary_lane_replies")
unary_lane_bytes = Adder(name="device_link_unary_lane_bytes")
unary_bytes_fallbacks = Adder(name="device_link_unary_bytes_fallbacks")
link_acks = Adder(name="device_link_ack_steps")  # wire-mode catch-up steps
link_errors = Adder(name="device_link_errors")  # fail() calls, all links
# send() attempts refused with EOVERCROWDED after a full window-stall wait
link_overcrowded = Adder(name="device_link_overcrowded")

_link_ids = itertools.count(1)  # per-link bvar namespace: device_link_<n>_*

# Every live link, for the interpreter-exit quiesce: a teardown-triggered
# close frame dispatches one final exchange step on a worker fiber; if the
# process exits while that fiber is inside the XLA dispatch (or the CQ
# watcher inside the PJRT wait), CPython finalizes under it and the C++
# teardown aborts ("terminate called ... FATAL: exception not rethrown").
# The atexit hook outwaits in-flight drives/steps (bounded), then drains
# the completion watchers.
import weakref

_all_links: "weakref.WeakSet" = weakref.WeakSet()
_links_lock = threading.Lock()


def _quiesce_links(timeout: float = 10.0) -> None:
    deadline = time.monotonic() + timeout
    with _links_lock:
        links = list(_all_links)
    for link in links:
        while link.busy and time.monotonic() < deadline:
            time.sleep(0.01)
    # the drives above may have submitted completion watches: drain them
    from incubator_brpc_tpu.runtime import device_butex as _db

    if _db._watchers is not None:
        _db._watchers.quiesce(timeout=max(0.1, deadline - time.monotonic()))


import atexit

atexit.register(_quiesce_links)


# A delivered step's row, as _hand_over_train writes it: the train's first
# seq, its stamps in the order written (time.monotonic_ns()), two counts,
# then the CPU clock (time.thread_time_ns()) of the thread that wrote the
# stamp, where a stage begins and ends on it. A stage is the difference of
# the stamps beside it (us), a count is fed as it stands; launch, ready,
# reorder_wait, readback and pump add up to step_rtt. One step in
# bvar.CPU_CLOCK_EVERY carries the CPU stamps, the others -1.
STEP_STAMPS = (
    "seq",
    "previous_dispatch",  # the drive's dispatch before this one; -1 = none
    "held_since",  # the drive began to hold the train back, else = dispatch
    "dispatch", "launched", "ready", "deliver", "host", "delivered",
    "inflight", "backlog_slots",
    "dispatch_cpu", "launched_cpu",  # the drive's thread
    "deliver_cpu", "host_cpu", "delivered_cpu",  # the in-order deliverer's
)
# (the link's recorder _m_<this>, scale, what of the row it is fed)
STEP_COLUMNS = (
    ("rtt", 1e-3, ("dispatch", "delivered")),
    ("launch", 1e-3, ("dispatch", "launched")),
    ("ready", 1e-3, ("launched", "ready")),
    ("reorder_wait", 1e-3, ("ready", "deliver")),
    ("readback", 1e-3, ("deliver", "host")),
    ("pump", 1e-3, ("host", "delivered")),
    ("dispatch_interval", 1e-3, ("previous_dispatch", "dispatch")),
    ("hold", 1e-3, ("held_since", "dispatch")),
    ("inflight", 1, "inflight"),
    ("backlog", 1, "backlog_slots"),
    # one thread begins and ends these: its CPU clock beside the wall clock
    ("launch_cpu", 1e-3, ("dispatch_cpu", "launched_cpu")),
    ("readback_cpu", 1e-3, ("deliver_cpu", "host_cpu")),
    ("pump_cpu", 1e-3, ("host_cpu", "delivered_cpu")),
)
# a recorder _m_<this> is exposed as device_link_<n>_<this>_us, but for
STEP_EXPOSED = {
    "rtt": "step_rtt_us", "inflight": "inflight_at_dispatch",
    "backlog": "backlog_slots_at_dispatch",
}


class _Step:
    """One exchange step's timeline (a train of slots a side): stamps each
    written once by the thread that does the work, on both clocks
    (``bvar.clocks``: ``t_*`` wall, ``c_*`` that thread's CPU)."""

    __slots__ = (
        "t_dispatch", "c_dispatch", "t_previous", "inflight", "backlog", "t_held",
        "t_launched", "c_launched", "watcher",
    )

    def __init__(
        self, t_dispatch: int, c_dispatch: int, t_previous: int, inflight: int,
        backlog: int, t_held: int,
    ):
        self.t_dispatch = t_dispatch  # slots filled, seq taken
        self.c_dispatch = c_dispatch  # -1: this step is not timed on the CPU clock
        # the drive's previous dispatch; none: never taken
        self.t_previous = t_previous or RecorderFeed.MISSING
        self.inflight = inflight  # undrained slots, this train's included
        # slots the fuller side's backlog would fill when the train was cut
        self.backlog = backlog
        # the drive's first look that found less credit than the train its
        # backlog wanted needs; a train never held: its dispatch
        self.t_held = t_held or t_dispatch
        self.t_launched = 0  # the step call and the host-copy request returned
        self.c_launched = RecorderFeed.MISSING
        # DeviceCompletionButex.watch fills these: a watcher thread took
        # the job, block_until_ready returned
        self.watcher = [0, 0]

    @property
    def timed(self) -> bool:
        """This step's stamps carry the CPU clock."""
        return self.c_dispatch >= 0

    def launched(self) -> None:
        self.t_launched, self.c_launched = clocks(self.timed)


# What a lane program carries beside its body: the message's tag, opaque
# words of the sender's that the receiver is handed with the array
LANE_TAG_WORDS = 64
LANE_TAG_BYTES = LANE_TAG_WORDS * 4

# A delivered lane message's row, as _hand_over_message writes it: stamps
# (time.monotonic_ns()) in the order taken, the message's bytes, then the
# launching thread's CPU clock around the launch (one program in
# bvar.CPU_CLOCK_EVERY carries it, on the row of the message whose sender
# launched; the others -1). ``taken`` is the launch taking the message off
# its direction's queue and ``launched`` the program call's return: the two
# messages of one program share both, whoever's thread made the call.
# ``ready`` is the body seen ready on the receiver's device; ``paired`` the
# moment its tag was in hand on the host and its turn in the lane's order
# had come.
LANE_STAMPS = (
    "seq", "taken", "launched", "ready", "paired", "queued",
    "nbytes", "taken_cpu", "launched_cpu",
)
# (the link's recorder device_link_<n>_lane_<this>, scale, what it is fed)
LANE_COLUMNS = (
    ("step_us", 1e-3, (
        ("taken", "launched"), ("launched", "ready"), ("ready", "paired"),
        ("paired", "queued"),
    )),
    ("launch_us", 1e-3, ("taken", "launched")),
    ("ready_us", 1e-3, ("launched", "ready")),
    ("pair_wait_us", 1e-3, ("ready", "paired")),
    ("deliver_us", 1e-3, ("paired", "queued")),
    ("launch_cpu_us", 1e-3, ("taken_cpu", "launched_cpu")),
)


# A unary call whose attachment crossed by the lane leaves a row on each
# side of the link (rpc/channel.py, rpc/server.py write them), stamps of
# time.monotonic_ns() in the order taken; one never taken is MISSING (an
# answer that came as bytes has no ``answer_handed``). The caller's row:
UNARY_CALL_STAMPS = ("entered", "request_sent", "answer_handed", "returned")
UNARY_CALL_COLUMNS = (
    ("request_tx_us", 1e-3, ("entered", "request_sent")),
    ("client_wake_us", 1e-3, ("answer_handed", "returned")),
    ("call_us", 1e-3, ("entered", "returned")),
)
# and the server's, ``request_handed`` the lane's hand-over of the request
UNARY_SERVE_STAMPS = ("request_handed", "handler_in", "handler_out", "reply_sent")
UNARY_SERVE_COLUMNS = (
    ("server_dispatch_us", 1e-3, ("request_handed", "handler_in")),
    ("reply_tx_us", 1e-3, ("handler_out", "reply_sent")),
)
# the two FIFO carriers of a link, as CarrierOrder indexes them
BYTE_STREAM, LANE = 0, 1


class CarrierOrder:
    """The reader's side of an order kept across two FIFO carriers, a
    link's byte stream and its lane, either of which may be the faster.
    Each item names how many counted items of the *other* carrier its
    writer had sent before it (the writer counts an item once its carrier
    has it, one send at a time a carrier); ``arrive`` releases it once
    that many have been released, behind what its own carrier brought
    before it: ``release`` sees the items in the order written. Each
    carrier is FIFO and an item names only what was sent before it, so
    whatever is held waits for something still on its way. A stream's
    messages (rpc/stream.py) and the frames and bodies of unary calls whose
    frame is too long for the lane's tag (``DeviceSocket``) take the same
    stage."""

    def __init__(self, release):
        self._release = release  # called under the lock, in the order written
        self._lock = threading.Lock()
        # a carrier: what arrived and waits for the other carrier, oldest
        # first, (item, items of the other carrier before it, counted)
        self.held = (deque(), deque())
        self.released = [0, 0]  # counted items released, a carrier

    def arrive(self, carrier: int, item, after: int, counted: bool = True) -> None:
        with self._lock:
            held, released = self.held, self.released
            held[carrier].append((item, after, counted))
            while True:
                for c, queue in enumerate(held):
                    if queue and queue[0][1] <= released[1 - c]:
                        item, _after, counted = queue.popleft()
                        released[c] += counted
                        self._release(item)
                        break
                else:
                    return

    def clear(self) -> None:
        """What is held will not be released: what it waits for can no
        longer arrive (a closed stream, a failed socket)."""
        with self._lock:
            for queue in self.held:
                queue.clear()


def array_carrier(sock, array) -> tuple:
    """What becomes of a device array handed to ``sock`` as a stream's
    message or a unary call's attachment, chosen from what the socket is
    and what the array is; nothing configures it. ``(array, b"")``: the
    socket's link has a lane and takes the array whole. ``(None, its
    bytes)``: there is no second device to land on (a host socket, a link
    on one shared device), so its bytes go as host bytes. ``(None, None)``:
    refused (``lane_accepts`` said no: not whole on this side's device, no
    dimension or element, deleted or donated; or a multi-controller link,
    which has no lane yet). Anything but a ``jax.Array`` is a
    ``TypeError``."""
    import jax

    if not isinstance(array, jax.Array):
        raise TypeError(
            f"a stream message or an attachment is bytes, an IOBuf or a "
            f"jax.Array, not {type(array).__name__}"
        )
    if array.is_deleted():
        return None, None
    lane = getattr(sock, "lane", None)
    if lane is None:
        return None, np.asarray(array).tobytes()
    if not lane.lane_accepts(sock.side, array):
        return None, None
    return array, b""


class _LaneStep:
    """One lane message's timeline and what it landed: made when
    ``lane_send`` takes the message, kept until the receiving socket was
    handed it in its turn (or the link failed)."""

    __slots__ = (
        "seq", "to", "nbytes", "array", "sent_tag", "parked", "rc",
        "t_taken", "c_taken", "t_launched", "c_launched", "watcher", "body",
        "landed_tag", "tag", "outputs",
    )

    def __init__(self, seq: int, to: int, array, sent_tag: bytes):
        self.seq, self.to, self.nbytes = seq, to, array.nbytes
        # what the sender gave, until a program holds it
        self.array, self.sent_tag = array, sent_tag
        # its sender waits here while another thread of the link launches:
        # set once the message has an ``rc`` (it crossed as that thread's
        # passenger, or the link failed) or the launch is its sender's
        self.parked: Optional[threading.Event] = None
        self.rc: Optional[int] = None  # lane_send's code, once it has one
        self.t_taken = self.t_launched = 0
        self.c_taken = self.c_launched = RecorderFeed.MISSING
        # DeviceCompletionButex.watch fills these; a program's messages share it
        self.watcher = [0, 0]
        self.body = None  # the array on the receiver's device
        self.landed_tag = None  # the tag's shard there, its host copy asked for
        self.tag = None  # its words on the host, once the body was seen ready
        self.outputs = None  # the program's whole outputs, until then

    def pairs_with(self, other: "_LaneStep") -> bool:
        """One program's two halves are one global array: the messages it
        carries each way have one shape and dtype."""
        mine, theirs = self.array, other.array
        return mine.shape == theirs.shape and mine.dtype == theirs.dtype


def lane_program(mesh, sharding):
    """The lane's program over a link's two-device ``mesh`` (``sharding``
    cuts a first dimension in two along it): it exchanges its two operands,
    the bodies' halves and the tags' rows, and returns both as they landed,
    a device's shard what the other device sent. Jitted here and named
    ``device_link_lane``: the benchmark finds its executions in a trace by
    that name."""
    import jax
    from jax.sharding import PartitionSpec as P

    both_ways = [(0, 1), (1, 0)]

    def device_link_lane(halves, tags):
        return jax.shard_map(
            lambda x, t: (
                jax.lax.ppermute(x, "link", both_ways),
                jax.lax.ppermute(t, "link", both_ways),
            ),
            mesh=mesh, in_specs=(P("link"), P("link")),
            out_specs=(P("link"), P("link")),
        )(halves, tags)

    return jax.jit(
        device_link_lane,
        in_shardings=(sharding, sharding), out_shardings=(sharding, sharding),
    )


def _recorders(prefix: str, exposed: Dict[str, str]) -> Dict[str, LatencyRecorder]:
    """A recorder a key of ``exposed``, exposed as ``<prefix>_<its value>``."""
    return {k: LatencyRecorder(name=f"{prefix}_{v}") for k, v in exposed.items()}


class _InOrder:
    """The path from a completion to its delivery, for either carrier of a
    link. Programs dispatched under consecutive sequence numbers (a train
    takes ``span`` = its slots a side, a lane message one) finish out of
    order on the completion watchers' threads and are handed over strictly
    in the order taken: ``drain`` admits ONE deliverer at a time and pops
    the next sequence number under the owner's lock, so what is handed
    over can never interleave (a mis-ordered chunk of the byte stream
    would corrupt every frame after it). That lock guards ``next`` and
    ``waiting`` beside what the owner counts under it."""

    def __init__(self, lock, hand_over, handed=None):
        self._lock = lock
        # hand_over(seq, item): the carrier's delivery, inside the turn;
        # handed(span): what follows it once the turn was given up
        self._hand_over, self._handed = hand_over, handed
        self._turn = threading.Lock()  # one in-order deliverer
        self.next = 0  # next seq to hand over
        # seq -> (span, item): finished, waiting for its turn. None: cleared
        self.waiting: Optional[Dict[int, tuple]] = {}

    def land(self, seq: int, span: int, item) -> bool:
        """Under the lock: ``item`` finished. False once cleared: nothing
        is kept, its turn would not come."""
        if self.waiting is None:
            return False
        self.waiting[seq] = (span, item)
        return True

    def clear(self) -> int:
        """Under the lock, for good (the link failed): drop what waits for
        its turn. Returns how many sequence numbers went with it."""
        dropped, self.waiting = self.waiting or {}, None
        return sum(span for span, _item in dropped.values())

    def drain(self) -> None:
        """Hand over what has landed and is next, until the next has not.
        Whoever landed an item calls it, on any thread."""
        while True:
            with self._turn:
                with self._lock:
                    found = self.waiting and self.waiting.pop(self.next, None)
                    if not found:
                        return
                    seq, (span, item) = self.next, found
                    self.next += span
                self._hand_over(seq, item)
            if self._handed is not None:
                self._handed(span)


class DeviceLink:
    """One established two-party link: the QP pair + CQ + window."""

    # a link of this class has the lane wherever its exchange is a
    # ppermute; MultiControllerLink has none yet and refuses device arrays
    carries_arrays = True

    def __init__(
        self,
        devices: List,
        slot_words: int = 16384,
        window: int = 8,
        host_loopback: Optional[bool] = None,
        ack_mode: str = "local",
    ):
        """``host_loopback``: when both parties share ONE device the
        exchange is a pure swap — the peer's bytes are already on this
        host (they were queued here) and the consumer is this host's
        messenger, so a device round trip would be a host→HBM copy and a
        readback that move no information. Default (None) takes the host
        swap for the shared-device geometry; ``False`` forces the jitted
        on-device swap (tests, ``chip_smoke.py``).

        ``ack_mode``: how the credit window learns about drained steps.
        'local' (default) gates on this process's shared delivery counter
        — correct and cheapest when both parties live in one controller.
        'wire' gates on the CUMULATIVE-DELIVERED count carried in received
        slot headers (word 3) — the information flow a multi-controller
        deployment has, where each host only observes its own deliveries:
        the RDMA endpoint's piggybacked imm-data acks, with ack-only steps
        dispatched when acks lag half the window (the accumulated-ack +
        SendImm scheme, rdma_endpoint.h:117-123,176-195)."""
        if slot_words < 64:
            raise ValueError("slot_words too small")
        if ack_mode not in ("local", "wire"):
            raise ValueError(f"unknown ack_mode {ack_mode!r}")
        self.devices = devices  # [dev_side0, dev_side1]
        self.slot_words = slot_words
        self.window = window
        self.ack_mode = ack_mode
        self._peer_ack = 0  # wire mode: max delivered-count seen in rows
        self._acks_sent = 0  # wire mode: highest ack value put on the wire
        self._host_loopback = host_loopback
        self._slot_bytes = slot_words * 4
        self._lock = threading.Lock()
        self._out: List[deque] = [deque(), deque()]  # pending bytes per side
        self._out_nbytes = [0, 0]
        self._close_pending = [False, False]
        self._closed = False
        # admission gate mc_link flips when its close dance freezes the
        # step budget: bytes queued after the freeze could never be
        # dispatched, so they must be REFUSED, not silently dropped —
        # checked in the same critical section that admits the queue
        # extension (always False for the in-process link)
        self._send_blocked = False
        # seq, credit and acks count SLOTS: a train of k takes k seqs
        self._seq = 0  # slots dispatched
        self._inflight = 0  # slots dispatched, not yet drained
        # completed trains, (step output, timeline) each, to the sockets in
        # order; its next is the count of slots delivered
        self._trains = _InOrder(self._lock, self._hand_over_train, self._train_handed)
        self._deliver_tid: Optional[int] = None  # thread inside _deliver
        self._driving = False
        self._wbutex = Butex(0)  # writers park here on backlog
        self._cq = DeviceCompletionButex()
        self.socks: List[Optional["DeviceSocket"]] = [None, None]
        self._pool = global_worker_pool()
        # -- per-link instrumentation (scraped at /brpc_metrics): rtt per
        # exchange step, a train (dispatch -> end of its in-order delivery)
        # and the stages that add up to it — launch (the step call, which
        # stages the train's host buffer, and the host-copy request), ready
        # (watch -> block_until_ready returned),
        # reorder_wait (ready -> its in-order delivery begins), readback
        # (_rows_to_host), pump (feeding delivered bytes into the
        # messenger). flush = the staging gather into one side's train;
        # dispatch_interval = the host time between one drive's consecutive
        # dispatches; inflight_at_dispatch = how many slots of the window
        # are in use (a count, not a time); hold = how long the drive kept
        # a train back for the credit of a longer one (before its dispatch,
        # so outside step_rtt); plus bytes-per-second windows each way.
        # Retired (hidden from the registry) when the link dies so churning
        # links don't accumulate.
        self.link_id = next(_link_ids)
        pfx = f"device_link_{self.link_id}"
        self._m_out_bytes = Adder()
        self._m_in_bytes = Adder()
        # a recorder a column of the steps' feed, and the two without one
        made = _recorders(pfx, {
            what: STEP_EXPOSED.get(what, what + "_us")
            for what in (*(c[0] for c in STEP_COLUMNS), "flush", "send_wait")
        })
        for what, recorder in made.items():
            setattr(self, "_m_" + what, recorder)
        self._m_out_rate = PerSecond(self._m_out_bytes, name=f"{pfx}_out_bytes_second")
        self._m_in_rate = PerSecond(self._m_in_bytes, name=f"{pfx}_in_bytes_second")
        # a delivered step's row waits here for the sampler thread: fourteen
        # feeds a step on the delivering thread would sit between one step
        # and the next. The last 16 Ki rows stay (30 s of a busy link)
        self._step_feed = RecorderFeed(
            [(made[what], scale, span) for what, scale, span in STEP_COLUMNS],
            stamps=STEP_STAMPS,
            name=f"{pfx}_steps",
            ring_rows=1 << 14,
            worker=(("dispatch", "launched"), ("deliver", "host"), ("host", "delivered")),
            call=(("dispatch", "delivered"),),
        )
        # one row a send(): when it first parked over the backlog budget
        # (admitted at once: when it was admitted) and when it was admitted
        # or gave up
        self._send_feed = RecorderFeed(
            ((self._m_send_wait, 1e-3, ("parked", "admitted")),),
            stamps=("parked", "admitted"),
            name=f"{pfx}_sends",
            ring_rows=1 << 14,
            call=(("parked", "admitted"),),
        )
        self._metrics_retired = False
        self._steps_taken = 0  # trains dispatched: which carry the CPU clock
        self._last_dispatch_ns = 0  # this drive's previous dispatch; 0 = none
        self._held_since_ns = 0  # the drive is holding a train back; 0 = not
        # -- the lane (ppermute geometry only; _build_step makes its feed).
        # A list of two is indexed by the receiving side: each direction
        # has its own sequence and its own in-order deliverer
        self._lane_lock = threading.Lock()
        self._lane_seq = [0, 0]  # messages lane_send took for that side
        # _LaneSteps seen ready, tag in hand, to that side's socket in order
        self._lanes = [
            _InOrder(self._lane_lock, self._hand_over_message) for _to in (0, 1)
        ]
        # and by the SENDING side: the messages taken that no program has
        # yet, oldest first; a launch takes the head of each
        self._lane_out = (deque(), deque())
        # a thread of this link is on its way to a launch or inside one:
        # whoever sends meanwhile parks, and is carried or handed the next
        self._lane_launching = False
        # (shape, dtype) -> (program, a placeholder a device, a device's shard)
        self._lane_programs: Dict[tuple, tuple] = {}
        self._lane_inflight = 0  # programs dispatched, bodies not yet seen ready
        self._launch_order = None  # the process's order of collective launches
        self._lane_feed: Optional[RecorderFeed] = None
        # a row a unary call that carried a device attachment: the caller's
        # side's and the serving side's (made with the lane)
        self.unary_calls: Optional[RecorderFeed] = None
        self.unary_serves: Optional[RecorderFeed] = None
        self._build_step()
        with _links_lock:
            _all_links.add(self)

    def _retire_metrics(self) -> None:
        """Drop this link's names from the expose registry (terminal).
        The aggregate device_link_* counters live on."""
        if self._metrics_retired:
            return
        self._metrics_retired = True
        retired = [self._m_flush, self._m_out_rate, self._m_in_rate]
        for feed in (self._step_feed, self._send_feed, self._lane_feed,
                     self.unary_calls, self.unary_serves):
            if feed is not None:
                feed.flush()  # profile() still reads the recorders
                retired += [recorder for recorder, *_rest in feed.columns]
        for v in retired:
            try:
                v.hide()
            except Exception:
                pass

    def _maybe_retire_metrics(self) -> None:
        """Clean-close path: the base link never reaches fail() on an
        orderly ECLOSE dance, so once every handshaken side's socket has
        left CONNECTED the link carries no more traffic — drop its names
        then too (churning links must not accumulate registry entries)."""
        from incubator_brpc_tpu.transport.sock import CONNECTED

        socks = [s for s in self.socks if s is not None]
        if socks and all(s.state != CONNECTED for s in socks):
            self._retire_metrics()

    # -- the ICI primitive ---------------------------------------------------

    def _build_step(self) -> None:
        import jax
        from jax.sharding import (
            Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
        )

        width = LINK_HEADER_WORDS + self.slot_words
        self._width = width
        same_device = (
            len({getattr(d, "id", i) for i, d in enumerate(self.devices)}) == 1
        )
        if self._host_loopback is None:
            self._host_loopback = same_device
        if self._host_loopback:
            # shared-device geometry: pure host swap — no dispatch, no
            # readback (the on-chip fast path; VERDICT r3 item 1). All the
            # link machinery above the step (slot packing, seq/ack headers,
            # credit window, in-order delivery) still runs.
            self._mesh = None
            self._sharding = None
            self._step = None
            return
        if same_device:
            # forced device loop on one chip (tests exercising dispatch)
            self._mesh = None
            self._sharding = SingleDeviceSharding(self.devices[0])
            self._step = jax.jit(
                lambda slots: slots[::-1], in_shardings=self._sharding
            )
            self._warm_step()
            return

        mesh = Mesh(np.asarray(self.devices), ("link",))
        self._mesh = mesh
        self._sharding = NamedSharding(mesh, P("link"))

        def exchange(slots):
            return jax.shard_map(
                lambda x: jax.lax.ppermute(x, "link", [(0, 1), (1, 0)]),
                mesh=mesh,
                in_specs=P("link"),
                out_specs=P("link"),
            )(slots)

        # in_shardings: a train is staged once. The drive hands the step
        # one (2, k, width) host buffer and the runtime cuts it into each
        # device's half inside the program call (as DeviceEndpoint's
        # programs take their host rows); a committed array of this
        # sharding (MultiControllerLink) is taken as it is
        self._step = jax.jit(
            exchange, in_shardings=self._sharding, out_shardings=self._sharding
        )
        self._warm_step()
        if not self.carries_arrays:
            return
        # the lane exists where the exchange is a ppermute between two
        # devices: a row a delivered program, fed by the sampler as the
        # steps' rows are
        lane = f"device_link_{self.link_id}_lane"
        made = _recorders(lane, {what: what for what, *_rest in LANE_COLUMNS})
        from incubator_brpc_tpu.parallel.collective import launch_order

        self._launch_order = launch_order
        self._lane_feed = RecorderFeed(
            [(made[what], scale, span) for what, scale, span in LANE_COLUMNS],
            stamps=LANE_STAMPS,
            name=f"{lane}_steps",
            ring_rows=1 << 14,
            worker=(("taken", "launched"), ("paired", "queued")),
            call=(("taken", "queued"),),
        )
        unary = f"device_link_{self.link_id}_unary"
        columns = (*UNARY_CALL_COLUMNS, *UNARY_SERVE_COLUMNS)
        made = _recorders(unary, {what: what for what, *_rest in columns})
        self.unary_calls = RecorderFeed(
            [(made[what], scale, span) for what, scale, span in UNARY_CALL_COLUMNS],
            stamps=UNARY_CALL_STAMPS,
            name=f"{unary}_calls",
            ring_rows=1 << 14,
            worker=(("entered", "request_sent"),),
            call=(("entered", "returned"),),
        )
        self.unary_serves = RecorderFeed(
            [(made[what], scale, span) for what, scale, span in UNARY_SERVE_COLUMNS],
            stamps=UNARY_SERVE_STAMPS,
            name=f"{unary}_serves",
            ring_rows=1 << 14,
            worker=(("handler_in", "handler_out"), ("handler_out", "reply_sent")),
            call=(("request_handed", "reply_sent"),),
        )

    def _warm_step(self) -> None:
        """Run the exchange once on empty rows at every train length the
        window admits (1, 2, 4, ...): one program a length, compiled here
        in the handshake, so that no dispatch of live traffic compiles. Each
        is launched from a host buffer, asked for and read back as a live
        train is, so that the first of those does not pay the staging's or
        the transfer path's first use either."""
        k = 1
        while k <= self.window:
            out = self._step(np.zeros((2, k, self._width), dtype=np.uint32))
            self._request_host(out)
            self._rows_to_host(out)
            k *= 2

    @property
    def geometry(self) -> str:
        """Which exchange ``_build_step`` chose from the device pair:
        ``"host-swap"`` (one shared device: no dispatch, no readback),
        ``"device-swap"`` (one shared device, jitted on-device swap) or
        ``"ppermute"`` (two devices: the shard_map step over the link
        mesh)."""
        if self._step is None:
            return "host-swap"
        return "device-swap" if self._mesh is None else "ppermute"

    @staticmethod
    def _request_host(out) -> None:
        """Ask the runtime for the host copy of a step's output, every
        addressable shard of it, as soon as the step is dispatched: the
        transfers queue behind the exchange on each device and run beside
        each other and beside the watcher's hand-over, and ``_rows_to_host``
        finds them landed or landing instead of asking for one after the
        other (PERF.md, PR 36)."""
        out.copy_to_host_async()

    # -- the lane: device arrays, HBM to HBM ---------------------------------

    @property
    def has_lane(self) -> bool:
        """This link can carry a device array as a device array: its
        exchange is the ``ppermute`` between two devices. A link on one
        shared device (host swap, device swap) has no lane; a stream over
        it sends an array's bytes."""
        return self._lane_feed is not None

    def lane_accepts(self, side: int, array) -> bool:
        """Whether ``lane_send`` can take ``array`` from ``side``: a
        ``jax.Array`` of at least one dimension and one element that lies
        whole on the device this side of the link drives and was neither
        deleted nor donated to a program."""
        import jax

        return (
            self.has_lane
            and isinstance(array, jax.Array)
            and not array.is_deleted()
            and array.ndim >= 1
            and array.size > 0
            and array.devices() == {self.devices[side]}
        )

    def _lane_program(self, shape: tuple, dtype) -> tuple:
        """The lane's program for messages of one shape and dtype, either
        way or both: ``(program, placeholders, shards)``. It exchanges two
        operands. The body is one global array cut in two along its first
        dimension, a device's half the message that device sends as it
        lies, or ``placeholders[device]``, made here once and kept, where
        it sends none. The tags are one ``(2, LANE_TAG_WORDS)`` array, a
        row a device. Shard ``shards[side]`` of either output is what
        landed on ``side``'s device. Compiled and run once at the first use
        of a shape (``warm_lane``), asked for and read back as a live
        message's is, so that no message of live traffic, alone or one of
        a pair, compiles or pays a first use."""
        import jax

        key = (tuple(shape), np.dtype(dtype).name)
        found = self._lane_programs.get(key)
        if found is not None:
            return found
        program = lane_program(self._mesh, self._sharding)
        placeholders = [
            jax.device_put(np.zeros(shape, dtype=dtype), d) for d in self.devices
        ]
        out, tags = program(
            self._lane_operand(placeholders),
            np.zeros((2, LANE_TAG_WORDS), dtype=np.uint32),
        )
        held = [s.device for s in out.addressable_shards]
        shards = [held.index(d) for d in self.devices]
        landed = [tags.addressable_data(shard) for shard in shards]
        for tag in landed:
            self._request_host(tag)
        jax.block_until_ready(out)
        for tag in landed:
            self._tag_to_host(tag)
        with self._lane_lock:
            found = self._lane_programs.setdefault(
                key, (program, placeholders, shards)
            )
        return found

    def _lane_operand(self, halves):
        """Both halves, side 0's then side 1's, as one global array,
        neither copied."""
        import jax

        first = halves[0]
        return jax.make_array_from_single_device_arrays(
            (2 * first.shape[0],) + tuple(first.shape[1:]), self._sharding, halves
        )

    @staticmethod
    def _tag_to_host(landed) -> np.ndarray:
        """A landed tag's ``LANE_TAG_WORDS`` words on the host, from the
        copy the launch asked for at the dispatch: the receiver is
        handed what crossed, not what the sender holds."""
        return np.asarray(landed).reshape(-1)

    def warm_lane(self, side: int, shape: tuple, dtype) -> None:
        """Compile the lane's program for messages of ``shape`` and
        ``dtype`` and run it once, placeholders, tags and all. One program
        serves both sides, a message alone and a pair, so ``side`` picks
        nothing: warmed for one it is warm for the other. A deployment
        calls this for the shapes it will send before it opens a measured
        window; a shape never warmed compiles at its first message."""
        if not self.has_lane:
            raise ValueError("this link has no lane (one shared device)")
        self._lane_program(shape, dtype)

    def lane_send(self, side: int, array, tag) -> int:
        """Send ``array`` (``lane_accepts`` said yes) and its ``tag`` to
        the other side, whole, by one dispatch of the lane's program: one
        call into the runtime, no host copy of the body. ``tag`` is at most
        ``LANE_TAG_BYTES`` bytes the link does not read; the receiving
        socket is handed them, zero-padded to ``LANE_TAG_WORDS`` words,
        with the array on its device, after every message ``lane_send``
        took for that side before this one. Returns when the program call
        that carries the message has returned, made on this thread or, for
        a message that crossed beside the other direction's, on that
        one's. 0; ``EINVAL`` for a longer tag, with nothing taken or sent;
        ``EFAILEDSOCKET`` on a dead link or where the dispatch raised (for
        both messages of the program), which fails the link. The array may
        be dropped by the caller once this returns (the program holds it)
        but not written or donated until the message was consumed."""
        if len(tag) > LANE_TAG_BYTES:
            return ErrorCode.EINVAL
        to = 1 - side
        with self._lane_lock:
            if self._closed:
                return ErrorCode.EFAILEDSOCKET
            message = _LaneStep(self._lane_seq[to], to, array, bytes(tag))
            self._lane_seq[to] += 1
            self._lane_out[side].append(message)
            if self._lane_launching:
                message.parked = threading.Event()
            self._lane_launching = True
        if message.parked is not None:
            # a launch of this link is under way: it takes this message
            # with it if it is the head of its direction and fits, and
            # whoever launches hands the next launch to a head that waits
            message.parked.wait()
        if message.rc is None:
            self._lane_launch(side, message)
        return message.rc

    def _lane_launch(self, side: int, mine: _LaneStep) -> None:
        """One program, on the thread of ``mine``'s sender, whose turn it
        is: ``mine`` is the head of its direction. The program carries it
        and, if the other direction's head waits and is of the same shape
        and dtype, that one too, each in its own half with its tag in its
        own row; a half nobody fills is the placeholder, its row zero. Both
        get the launch's code. The next launch is handed on at the take, to
        a head that still waits, the other direction's first: its sender
        wakes beside this launch and stands at the order when it ends."""
        # Lane programs are launched from many threads once unary calls
        # ride the lane (callers one way, handlers' workers the other), and
        # each is a collective over both devices: the launches are ordered
        # (``collective.launch_order``), or the two devices could see two
        # of them in different orders and each wait in a permute the other
        # has not reached. Held from the take off the queues to the program
        # call's return, so the order launched is the order handed over;
        # the host work before and the watch after lie outside it.
        carried: List[_LaneStep] = []
        out = self._lane_out
        following = None
        try:
            with self._launch_order:
                with self._lane_lock:
                    if out[side] and out[side][0] is mine:
                        carried.append(out[side].popleft())
                        other = out[1 - side]
                        if other and other[0].pairs_with(mine):
                            carried.append(other.popleft())
                        self._lane_inflight += 1
                        # whose launch is next: woken now, it comes to the
                        # order while this launch holds it
                        waiting = out[1 - side] or out[side]
                        following = waiting[0] if waiting else None
                        self._lane_launching = following is not None
                if not carried:
                    return  # the link failed meanwhile, and mine with it
                timed = mine.seq % CPU_CLOCK_EVERY == 0
                t_taken, c_taken = clocks(timed)
                if following is not None:
                    following.parked.set()
                program, placeholders, shards = self._lane_program(
                    mine.array.shape, mine.array.dtype
                )
                # one host buffer of tags made anew for each program, which
                # its ``in_shardings`` place (a train's staging: no
                # ``device_put``), and never written after it was handed
                # over: the runtime may still be reading it
                halves = list(placeholders)
                tags = np.zeros((2, LANE_TAG_WORDS), dtype=np.uint32)
                for message in carried:
                    sender, tag = 1 - message.to, message.sent_tag
                    halves[sender] = message.array
                    tags[sender].view(np.uint8)[: len(tag)] = np.frombuffer(
                        tag, dtype=np.uint8
                    )
                bodies, landed = program(self._lane_operand(halves), tags)
            rc = 0
        except Exception:
            logger.exception("device link lane dispatch failed")
            with self._lane_lock:
                self._lane_inflight -= 1  # never dispatched: nothing to land
            rc = ErrorCode.EFAILEDSOCKET
        for message in carried:
            message.array = None  # the program holds it, or nothing will
            message.rc = rc
        for message in carried[1:]:
            message.parked.set()
        if rc != 0:
            self.fail("lane dispatch failed")  # whoever waits for a launch hears it
            return
        for message in carried:
            message.body = bodies.addressable_data(shards[message.to])
            message.landed_tag = landed.addressable_data(shards[message.to])
            self._request_host(message.landed_tag)
            message.watcher = mine.watcher
            lane_bytes << message.nbytes
        t_launched, c_launched = clocks(timed)
        mine.c_taken, mine.c_launched = c_taken, c_launched
        for message in carried:
            message.t_taken, message.t_launched = t_taken, t_launched
        # the sending halves of the outputs are nobody's, and dropping
        # a buffer of a program still running waits the program out
        # (0.8 ms of the writer a message on the chip; PERF.md section
        # 6, PR 40): the completion watcher drops them, once it has
        # handed the messages over
        mine.outputs = (bodies, landed)
        lane_steps << 1
        lane_messages << len(carried)
        lane_tagged << 1
        self._cq.watch(
            [message.body for message in carried],
            on_complete=lambda _bodies, error: self._lane_landed(carried, error),
            stamps=mine.watcher,
        )

    def _lane_landed(self, carried: List[_LaneStep], error) -> None:
        """Completion watcher: a lane program's bodies are ready on their
        receivers' devices (or failed). Read each message's tag from the
        host copy asked for at the dispatch and hand it over, on its own
        side, when its turn there comes."""
        # the program's whole outputs die with this call, on this thread
        # and after the hand-over: dropping them costs 0.7 ms on the chip
        # even now (PERF.md section 6, PR 40), and the messages do not
        # wait for it
        outputs, carried[0].outputs = carried[0].outputs, None  # noqa: F841
        if error is None:
            try:
                for step in carried:
                    step.tag = self._tag_to_host(step.landed_tag)
            except Exception as e:  # noqa: BLE001 — a device failure is data here
                error = e
        with self._lane_lock:
            self._lane_inflight -= 1
            if error is None:
                for step in carried:
                    self._lanes[step.to].land(step.seq, 1, step)
        if error is not None:
            logger.error("device link lane program failed: %s", error)
            self.fail(f"lane program failed: {error}")
            return
        for step in carried:
            self._lanes[step.to].drain()

    def _hand_over_message(self, seq: int, step: _LaneStep) -> None:
        """The lane's in-order hand-over: a landed message to the socket
        of the side it was sent to, then its program's row, a number a
        position of ``LANE_STAMPS``."""
        paired = time.monotonic_ns()
        sock = self.socks[step.to]
        try:
            if sock is not None:
                sock._lane_deliver(step.tag, step.body)
        except Exception:
            logger.exception("device link lane delivery raised")
        self._lane_feed.rows.append((
            seq, step.t_taken, step.t_launched, step.watcher[1],
            paired, time.monotonic_ns(),
            step.nbytes, step.c_taken, step.c_launched,
        ))

    # -- send side -----------------------------------------------------------

    def attach(self, side: int, sock: "DeviceSocket") -> None:
        self.socks[side] = sock

    def send(self, side: int, data, timeout: Optional[float] = 10.0) -> int:
        """Queue bytes (bytes or IOBuf) for the peer. 0, or EOVERCROWDED
        when the backlog stays above the window's byte budget past
        ``timeout``. The in-order deliverer thread never parks here (a
        handler responding inline during delivery would deadlock the link
        waiting on itself) — its writes are admitted past the budget,
        bounded by one response per delivered request.

        An IOBuf is queued as zero-copy views of its blocks (kept alive by
        the IOBuf itself): the only host copy of outbound payload bytes is
        the gather into the slot — the registered-ring staging write of the
        RDMA template (rdma_endpoint.h:105-123)."""
        if self._closed:
            return ErrorCode.EFAILEDSOCKET
        if isinstance(data, (bytes, bytearray, memoryview)):
            chunks = [[memoryview(data).cast("B"), data]]
        else:  # IOBuf: views stay valid while the IOBuf is referenced
            chunks = [[v, data] for v in data.views() if len(v)]
        n = sum(len(v) for v, _ in chunks)
        if n == 0:
            return 0
        budget = self.window * self._slot_bytes
        deadline = None
        t_parked = 0  # monotonic_ns of the first park; 0 = admitted at once
        while True:
            with self._lock:
                if self._closed or self._send_blocked:
                    return ErrorCode.EFAILEDSOCKET
                if (
                    self._out_nbytes[side] <= budget
                    or threading.get_ident() == self._deliver_tid
                ):
                    self._out[side].extend(chunks)
                    self._out_nbytes[side] += n
                    break
                seq = self._wbutex.load()
            # window stall: park until a step drains (credit released)
            if deadline is None:
                t_parked = time.monotonic_ns()
                deadline = time.monotonic() + (timeout if timeout else 10.0)
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                link_overcrowded << 1
                self._send_feed.rows.append((t_parked, time.monotonic_ns()))
                return ErrorCode.EOVERCROWDED
            self._wbutex.wait(seq, timeout=remaining)
        now = time.monotonic_ns()
        self._send_feed.rows.append((t_parked or now, now))
        self._kick()
        return 0

    def close(self, side: int) -> None:
        with self._lock:
            if self._closed:
                return
            self._close_pending[side] = True
        self._kick()

    def _kick(self) -> None:
        with self._lock:
            if self._driving or self._closed:
                return
            self._driving = True
            self._last_dispatch_ns = 0  # the queue ran dry: no interval
        self._pool.spawn(self._drive)

    # -- the drainer (single-drainer discipline, like Socket's KeepWrite) ----

    def _has_work(self) -> bool:
        return bool(
            self._out[0] or self._out[1]
            or self._close_pending[0] or self._close_pending[1]
        )

    def _credit_locked(self) -> int:
        """Free slots of the credit window, under the link lock. 'local':
        the window less the dispatched-but-undrained slots (this process
        sees both deliveries). 'wire': less how far our seq runs ahead of
        the peer's CUMULATIVE-DELIVERED count as carried in received slot
        word 3 — the only signal a multi-controller host has
        (rdma_endpoint.h:176-195). Below 1 the window is full (a wire-mode
        catch-up step runs it one over)."""
        if self.ack_mode == "wire":
            return self.window - (self._seq - self._peer_ack)
        return self.window - self._inflight

    def _train_len_locked(self) -> tuple:
        """Slots a side the next step may carry and the slots its backlog
        wants, from what the link observes under its lock. Each is the
        largest power of two (one compiled program a length,
        ``_warm_step``) within the slots the fuller side's backlog fills:
        the first within the free credit too, the second within the whole
        window. Where the first is the shorter, a delivery still to come
        would bring the credit of a longer train (``_drive`` holds for it).
        Both are one where a step goes out with no data or no credit
        (close-only, wire-mode catch-up), and on the host swap, which
        dispatches no program a train could save. Returns the two lengths
        and the backlog's slots they were taken from, for the train's
        timeline."""
        backlog = -(-max(self._out_nbytes) // self._slot_bytes)
        if self._step is None:
            return 1, 1, backlog
        admitted = max(1, min(backlog, self._credit_locked()))
        wanted = max(1, min(backlog, self.window))
        return (
            1 << (admitted.bit_length() - 1),
            1 << (wanted.bit_length() - 1),
            backlog,
        )

    def _take_seq_locked(self, k: int, backlog: int) -> tuple:
        """Under the link lock, a train of ``k`` slots a side filled: take
        its seqs, count its slots in flight and start its timeline.
        ``backlog`` is what ``_train_len_locked`` took ``k`` from."""
        seq = self._seq
        self._seq += k
        self._inflight += k
        now, now_cpu = clocks(self._steps_taken % CPU_CLOCK_EVERY == 0)
        self._steps_taken += 1
        last, self._last_dispatch_ns = self._last_dispatch_ns, now
        held, self._held_since_ns = self._held_since_ns, 0
        return seq, _Step(now, now_cpu, last, self._inflight, backlog, held)

    def _drive(self) -> None:
        while True:
            ack_only = False
            with self._lock:
                if self._closed or not self._has_work():
                    self._driving = False
                    return
                need = None
                if self._credit_locked() < 1:
                    # wire mode: when the acks we have put on the wire lag
                    # our deliveries by nearly a full window, the peer may
                    # be blocked on US — dispatch ONE over-window catch-up
                    # step carrying the fresh cumulative ack (and any
                    # queued data; a pure ack frame would starve data at
                    # window=1). The accumulated-ack + SendImm scheme,
                    # rdma_endpoint.h:117-123,176-195. Threshold window-1
                    # (was window/2, VERDICT r5 item 8): acks are
                    # cumulative, so ONE catch-up step flushes the whole
                    # backlog — batching to the window edge halves the
                    # over-window steps the link pays per byte while
                    # deliveries (which cap the lag at `window`) still
                    # guarantee the threshold is reachable, so the
                    # two-sided stall cannot wedge.
                    if (
                        self.ack_mode == "wire"
                        and self._trains.next - self._acks_sent
                        >= max(1, self.window - 1)
                    ):
                        ack_only = True
                    else:
                        # credit comes back at DELIVERY in both modes
                        # (local: _inflight falls; wire: deliveries advance
                        # _peer_ack), and _wbutex bumps on each one. Not
                        # the completion count: it moves before the train
                        # is delivered, and a train that took the whole
                        # window has no later completion to wake for
                        need = self._wbutex.load()
                if need is None:
                    k, wanted, backlog = self._train_len_locked()
                    if k < wanted and self._inflight and not ack_only:
                        # the backlog would fill a longer train than the
                        # free credit admits, and slots are still out: a
                        # short train now would keep the link at two or
                        # three short trains in flight, whose threads meet
                        # at the interpreter lock (PERF.md, PR 30 and 32).
                        # Hold for the delivery that brings the credit;
                        # it is already in flight, so this cannot wedge
                        if not self._held_since_ns:
                            self._held_since_ns = time.monotonic_ns()
                        need = self._wbutex.load()
                    else:
                        # the train is staged once: both sides' slots in
                        # one host buffer, made anew for every train and
                        # never written after the call below (the runtime
                        # may still be reading it when the call returns)
                        both = np.empty((2, k, self._width), dtype=np.uint32)
                        for side in (0, 1):
                            self._fill_train_locked(side, k, both[side])
                        seq, step = self._take_seq_locked(k, backlog)
            if need is not None:
                self._wbutex.wait(need, timeout=1.0)
                continue
            if ack_only:
                link_acks << 1
            if self._step is None:
                # host-loopback fast path: the swap IS the exchange —
                # deliver side i the peer's outbound row, no device hop.
                # Guarded like the dispatch path: a raising handler during
                # the synchronous delivery must fail the link, not strand
                # _driving=True with the queue wedged.
                link_steps << 1
                link_slots << k
                step.launched()
                step.watcher[0] = step.watcher[1] = step.t_launched
                try:
                    self._on_step_done(
                        seq, ("host", [both[1], both[0]]), None, k, step
                    )
                except Exception:
                    logger.exception("loopback link delivery failed")
                    self.fail("loopback delivery failed")
                    with self._lock:
                        self._driving = False
                    return
                continue
            try:
                # one call into the runtime a train: the program's
                # in_shardings place each device's half of the buffer
                out = self._step(both)
                self._request_host(out)
            except Exception:
                logger.exception("device link step dispatch failed")
                self._dispatch_failed(k)
                return
            step.launched()
            link_steps << 1
            link_slots << k
            link_prefetched << 1
            link_staged << 1
            if step.t_held != step.t_dispatch:
                link_held << 1
            self._cq.watch(
                out,
                on_complete=lambda arrays, error, _seq=seq, _k=k, _step=step: (
                    self._on_step_done(_seq, arrays, error, _k, _step)
                ),
                stamps=step.watcher,
            )

    def _dispatch_failed(self, k: int) -> None:
        """The drive's dispatch of a train raised: nothing of
        it will ever be delivered, so its ``k`` slots come back off the
        credit here (the idle check would wait out its timeout on them),
        the link fails and the drive ends."""
        self.fail("link step dispatch failed")
        with self._lock:
            self._inflight -= k
            self._driving = False

    def _fill_train_locked(self, side: int, k: int, train: np.ndarray) -> None:
        """Pack queued views head-to-tail into one side's train: ``k``
        slots, rows of ``train``, the ``(k, width)`` part of the drive's
        staging buffer that is this side's. Byte stream: a frame
        may split across slots and trains; the receiver's messenger re-cuts.
        ONE gather copy per byte — the staging write into the 'ring'.
        np.empty, not np.zeros: the receiver only reads ``used`` bytes,
        so a full-slot memset per step would touch every byte twice
        (VERDICT r3 weak #5); only the header words are written below."""
        t0 = time.perf_counter()
        q = self._out[side]
        cap = self._slot_bytes
        base = LINK_HEADER_WORDS * 4
        ack = self._trains.next
        total = 0
        for j in range(k):
            row = train[j]
            rb = row.view(np.uint8)
            used = 0
            while q and used < cap:
                entry = q[0]
                view = entry[0]
                take = min(len(view), cap - used)
                rb[base + used : base + used + take] = np.frombuffer(
                    view[:take], dtype=np.uint8
                )
                if take == len(view):
                    q.popleft()  # keepalive dropped with the entry
                else:
                    entry[0] = view[take:]
                used += take
            total += used
            if self._step is not None and used < cap:
                # the whole row crosses the wire on the device path: an
                # uninitialized tail would ship this process's freed heap
                # to the peer (free in the full-slot steady state)
                rb[base + used :] = 0
            flags = F_DATA if used else 0
            if not q and self._close_pending[side]:
                flags |= F_CLOSE
                self._close_pending[side] = False
            row[0] = LINK_MAGIC
            row[1] = used
            row[2] = (self._seq + j) & 0xFFFFFFFF
            # words 3(+5) carry the cumulative delivered count on the wire
            # (the RDMA endpoint's piggybacked imm-data ack slot), 64-bit.
            # ack_mode='local' gates the window on the shared in-process
            # counter and only WRITES these; ack_mode='wire' — the
            # multi-controller flow — gates on the values READ from
            # received rows (_deliver).
            row[3] = ack & 0xFFFFFFFF
            row[4] = flags
            row[5] = (ack >> 32) & 0xFFFFFFFF
            row[6:LINK_HEADER_WORDS] = 0  # reserved words must not leak heap
        self._acks_sent = ack  # words 3+5 carry this
        self._out_nbytes[side] -= total
        link_capacity << k * cap
        if total:
            link_bytes << total
            self._m_out_bytes << total
        self._m_flush << (time.perf_counter() - t0) * 1e6

    # -- receive side --------------------------------------------------------

    def _on_step_done(self, seq: int, arrays, error, k: int, step: _Step) -> None:
        """A step settled: ``arrays`` is its output, a train of ``k``
        slots a side whose first seq is ``seq``, ``step`` its timeline."""
        if error is not None:
            logger.error("device link step failed: %s", error)
            self.fail(f"link step failed: {error}")
        with self._lock:
            landed = error is None and self._trains.land(seq, k, (arrays, step))
            if not landed:
                # it failed, or the link did: nobody will be handed it, so
                # its slots come back off the credit here (_dispatch_failed)
                self._inflight -= k
        if landed:
            self._trains.drain()
            self._kick()

    def _hand_over_train(self, seq: int, landed: tuple) -> None:
        """The trains' in-order hand-over: a completed train read back and
        fed to the sockets, then its row, a number a position of
        ``STEP_STAMPS``, the deliverer's ``clocks()`` around both in it."""
        arrays, step = landed
        self._deliver_tid = threading.get_ident()
        begin = host = clocks(step.timed)
        try:
            rows = self._rows_to_host(arrays)
            host = clocks(step.timed)
            self._deliver(rows)
        finally:
            self._deliver_tid = None
            end = clocks(step.timed)
            self._step_feed.rows.append((
                seq, step.t_previous, step.t_held,
                step.t_dispatch, step.t_launched, step.watcher[1],
                begin[0], host[0], end[0],
                step.inflight, step.backlog,
                step.c_dispatch, step.c_launched, begin[1], host[1], end[1],
            ))

    def _train_handed(self, k: int) -> None:
        """The window credit (inflight) is released only after delivery —
        un-drained outputs are the occupied ring."""
        with self._lock:
            self._inflight -= k
        self._wbutex.add(1)
        self._wbutex.wake_all()

    def _rows_to_host(self, arrays) -> List[Optional[np.ndarray]]:
        """A step's output on the host: per side the train it received,
        ``(k, width)``, in one readback a side."""
        import jax

        if isinstance(arrays, tuple) and arrays[0] == "host":
            return arrays[1]  # loopback fast path: already host rows
        if self._mesh is None:
            host = np.asarray(jax.device_get(arrays))
            return [host[0], host[1]]
        rows: List[Optional[np.ndarray]] = [None, None]
        for shard in arrays.addressable_shards:
            idx = shard.index[0]
            side = int(idx.start if isinstance(idx, slice) else idx)
            rows[side] = np.asarray(shard.data).reshape(-1, self._width)
        return rows

    def _deliver(self, rows: List[Optional[np.ndarray]]) -> None:
        """One completed exchange, read back: after the permute, side i's
        device holds the PEER's outbound train — feed its slots, in slot
        order, into side i's socket in one go."""
        base = LINK_HEADER_WORDS * 4
        for side in (0, 1):
            train = rows[side]
            if train is None:
                continue  # not addressable from this host (multi-controller)
            chunks = []
            closing = False
            ack = 0
            for row in train:
                if int(row[0]) != LINK_MAGIC:
                    self.fail("bad link slot magic")
                    return
                used = int(row[1])
                ack = max(ack, int(row[3]) | (int(row[5]) << 32))
                if used:
                    # ZERO-copy delivery: the read IOBuf's block wraps the
                    # step output's own buffer (external block + release-cb
                    # — the HBM-backed IOBuf of the RDMA template,
                    # block_pool.h:20-66 / iobuf.cpp:258-306); the train
                    # stays alive until the last ref drops. Payload bytes
                    # materialize once, at the handler/parse boundary.
                    chunks.append(
                        memoryview(row.view(np.uint8))[base : base + used]
                    )
                    self._m_in_bytes << used
                if int(row[4]) & F_CLOSE:
                    closing = True  # the stream ends at this slot
                    break
            if self.ack_mode == "wire":
                # the peer's cumulative-delivered count rides words 3+5
                # (the piggybacked imm-data ack, 64-bit so it cannot
                # wrap); this is the ONLY credit signal in wire mode
                with self._lock:
                    if ack > self._peer_ack:
                        self._peer_ack = ack
            sock = self.socks[side]
            if sock is None:
                continue
            if chunks:
                sock._feed(chunks)
            if closing:
                sock.set_failed(ErrorCode.ECLOSE, "peer closed device link")

    def fail(self, reason: str) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            for side in (0, 1):
                self._out[side].clear()
                self._out_nbytes[side] = 0
            # what landed and waits for its turn: it will not come now
            # (a train's slots come back off the credit); the sockets'
            # failure below is what the streams on both ends hear
            self._inflight -= self._trains.clear()
        with self._lane_lock:
            for lane in self._lanes:
                lane.clear()
            # the messages no program has yet: none will, and their senders
            # (one of them may be on its way to the launch) hear it
            for queue in self._lane_out:
                while queue:
                    message = queue.popleft()
                    message.array, message.rc = None, ErrorCode.EFAILEDSOCKET
                    if message.parked is not None:
                        message.parked.set()
        link_errors << 1
        self._retire_metrics()
        # party-death feedback for the collective fault plane: a session
        # whose lockstep traffic rode THIS link can never converge once
        # the link is dead — abort it so every party exits with ESESSION
        # (same moment the hooks above retire telemetry)
        try:
            from incubator_brpc_tpu.parallel.mc_dispatch import (
                abort_sessions_for_devices,
            )

            abort_sessions_for_devices(
                [d.id for d in self.devices if d is not None],
                f"device link failed: {reason}",
            )
        except Exception:
            logger.exception("link-death session abort failed")
        self._wbutex.add(1)
        self._wbutex.wake_all()
        for sock in self.socks:
            if sock is not None:
                sock.set_failed(ErrorCode.EFAILEDSOCKET, reason)

    @property
    def inflight_steps(self) -> int:
        with self._lock:
            return self._inflight

    @property
    def busy(self) -> bool:
        """A drive runs, a train's slots are out or a lane program's body
        was not yet seen ready: each carrier read under its own lock."""
        with self._lock:
            trains = self._driving or self._inflight != 0
        with self._lane_lock:
            return trains or self._lane_inflight != 0

    def profile(self) -> dict:
        """Structured snapshot of this link's PR 1 recorders — the
        telemetry that was scrape-only until the topology-aware
        scheduler needed a programmatic read (parallel/mc_dispatch).
        ``gbps`` sums both directions' measured bytes/s; a fresh link
        reads 0.0 until the 1 Hz bvar sampler has a window."""
        self._step_feed.flush()  # a programmatic read counts the last steps
        out_bps = float(self._m_out_rate.get_value() or 0.0)
        in_bps = float(self._m_in_rate.get_value() or 0.0)
        return {
            "link_id": int(self.link_id),
            "devices": [getattr(d, "id", None) for d in self.devices],
            "rtt_us": float(self._m_rtt.latency()),
            "rtt_p99_us": float(self._m_rtt.latency_percentile(0.99)),
            "steps": int(self._m_rtt.count()),
            "out_bytes_s": out_bps,
            "in_bytes_s": in_bps,
            "out_bytes": int(self._m_out_bytes.get_value()),
            "in_bytes": int(self._m_in_bytes.get_value()),
            "gbps": (out_bps + in_bps) / 1e9,
        }


class DeviceSocket:
    """Socket-shaped endpoint over one side of a DeviceLink: the messenger,
    channel and server paths treat it exactly like a TCP Socket (same duck
    surface), but ``write`` stages bytes onto the link and reads arrive
    from link completions — no fd anywhere."""

    def __init__(
        self,
        link: DeviceLink,
        side: int,
        messenger=None,
        user_message_handler=None,
        context: Optional[dict] = None,
        remote: Optional[EndPoint] = None,
    ):
        from incubator_brpc_tpu.iobuf import IOBuf
        from incubator_brpc_tpu.transport.sock import CONNECTED, _registry

        self.link = link
        self.side = side
        self.messenger = messenger
        self.user_message_handler = user_message_handler
        self.context: dict = dict(context) if context else {}
        dev = link.devices[1 - side]
        self.remote = remote or EndPoint(ip=f"tpu://{getattr(dev, 'id', 0)}", port=0)
        self.state = CONNECTED
        self.error_code = 0
        self.error_text = ""
        self.preferred_protocol = None
        self.is_client = side == 0
        self.inline_read = False
        self.on_failed: List = []
        self.on_revived: List = []
        self._read_buf = IOBuf()
        self._feed_lock = threading.Lock()
        # who is handed this side's device messages raw, (sock, tag, body)
        # in the lane's order, in the messenger's place (a test's sink).
        # None: the messenger cuts the tag as the tbus_std frame it is
        self.lane_receiver = None
        # a unary call's frame too long for the lane's tag rides the byte
        # stream, its attachment the lane: the writer's counts a carrier
        # and their lock, and the reader's order stage, which lays each
        # body by for the frame written after it
        self._unary_wrote = [0, 0]
        self._unary_send_lock = threading.Lock()
        self._unary_order = CarrierOrder(self._unary_released)
        self._unary_body = None
        self.id = _registry.insert(self)
        link.attach(side, self)

    # -- write path ----------------------------------------------------------

    def write(
        self,
        data,
        on_error=None,
        timeout: Optional[float] = None,
        drain_inline: bool = False,
    ) -> int:
        from incubator_brpc_tpu.transport.sock import CONNECTED

        # drain_inline is the TCP writer's caller-driven-drain fast path;
        # the link always drains via its own single-drainer step loop, so
        # the hint is accepted (stream writers pass it) and ignored
        if self.state != CONNECTED:
            return ErrorCode.EFAILEDSOCKET
        # bytes and IOBufs both queue zero-copy (the link keeps the IOBuf
        # alive and gathers straight from its block views into the slot).
        # A synchronous failure is reported ONCE, via the return code —
        # the TCP Socket.write contract; also firing on_error would
        # arbitrate the same failure twice (a queued id error delivered
        # at unlock), burning a retry attempt.
        return self.link.send(self.side, data, timeout=timeout)

    def write_device_message(
        self, meta, payload: bytes, correlation_id: int, array,
        flags: int = 0, error_code: int = 0,
    ) -> int:
        """A unary call's tbus_std frame (request or answer) whose
        attachment is ``array``, which ``array_carrier`` gave to the lane.
        The frame, attachment-less, is the array's tag where it fits
        ``LANE_TAG_BYTES``: both cross in one program and nothing of the
        call rides the byte stream. A longer frame rides the byte stream
        and names the bodies sent before it, its own included
        (``arrays_before``); its body crosses the lane first, tagged with
        the frames sent before it (``frames_before``), and the far socket's
        ``CarrierOrder`` brings the two together, as a stream's carriers
        are. ``lane_send``'s and ``write``'s codes."""
        meta = meta if meta is not None else Meta()
        frame = pack_frame(meta, payload, correlation_id, flags, error_code)
        if len(frame) <= LANE_TAG_BYTES:
            return self.link.lane_send(self.side, array, frame)
        with self._unary_send_lock:
            wrote = self._unary_wrote
            body = Meta(extra={"unary_body": 1, "frames_before": wrote[BYTE_STREAM]})
            rc = self.link.lane_send(self.side, array, pack_frame(body, b"", 0))
            if rc != 0:
                return rc
            wrote[LANE] += 1
            meta = dataclasses.replace(
                meta, extra=dict(meta.extra, arrays_before=wrote[LANE])
            )
            rc = self.write(
                pack_frame_iobuf(meta, payload, correlation_id, flags, error_code)
            )
            if rc == 0:
                wrote[BYTE_STREAM] += 1
            return rc

    def hold_for_body(self, proto, frame) -> bool:
        """The messenger's question for every frame cut off this socket's
        byte stream, in wire order: a unary frame that names a body on the
        lane (``arrays_before``) is kept here until the lane has handed that
        body over, and dispatched with it as its attachment."""
        extra = getattr(getattr(frame, "meta", None), "extra", None)
        after = extra.get("arrays_before") if extra else None
        if not after or getattr(frame, "is_stream", False):
            return False
        self._unary_order.arrive(BYTE_STREAM, (proto, frame), int(after))
        return True

    def hold_body(self, body, after: int) -> None:
        """The lane handed over the body of such a frame, sent after
        ``after`` of them."""
        self._unary_order.arrive(LANE, body, after)

    def _unary_released(self, item) -> None:
        """``CarrierOrder``'s release, in the order written: a body, then
        the frame it belongs to, which goes where a frame cut off the byte
        stream goes, on a worker (a handler may block)."""
        if not isinstance(item, tuple):
            self._unary_body = item
            return
        proto, frame = item
        frame.attachment, self._unary_body = self._unary_body, None
        frame.handed_ns = time.monotonic_ns()  # whole: frame and body in hand
        global_worker_pool().spawn(self.messenger._process_one, self, proto, frame)

    @property
    def lane(self) -> Optional[DeviceLink]:
        """The link, where its exchange runs between two devices: a writer
        of device arrays asks it (``lane_accepts``) and hands them over
        with their tags (``lane_send``), or is refused. ``None`` on one shared device, where there is nothing
        to cross and a writer of arrays sends their bytes. A host
        ``Socket`` has no such attribute."""
        return self.link if self.link.geometry == "ppermute" else None

    # -- read path (driven by link completions) ------------------------------

    def _feed(self, chunks) -> None:
        """Link delivery: append a delivered train's byte-stream chunks, in
        order, and run the normal messenger cut loop once over them
        (completions feeding InputMessenger — the
        rdma_completion_queue.cpp:152 shape). A memoryview is wrapped
        zero-copy as an external block (its backing step-output buffer is
        kept alive until the last ref drops); small chunks copy into
        pooled blocks where the external-block bookkeeping would cost more
        than the memcpy."""
        with self._feed_lock:  # per-socket reader serialization
            for data in chunks:
                if isinstance(data, memoryview) and len(data) >= 4096:
                    self._read_buf.append_external(data)
                else:
                    self._read_buf.append(bytes(data))
            if self.messenger is not None and len(self._read_buf):
                self.messenger.process(self)

    def _lane_deliver(self, tag: np.ndarray, body) -> None:
        """Lane delivery: one device message, its tag's words as they were
        read back on this side and its body on this side's device, in the
        order the far side sent them. To the link the tag is opaque; the
        messenger cuts it as a tbus_std frame (a stream's data frame, a
        unary call's request or answer) and fails the socket where it is
        none."""
        from incubator_brpc_tpu.transport.sock import CONNECTED

        if self.state != CONNECTED:
            return
        receiver = self.lane_receiver
        if receiver is not None:
            receiver(self, tag, body)
            return
        process = getattr(self.messenger, "process_device_message", None)
        if process is not None:
            process(self, tag, body)

    # -- lifecycle -----------------------------------------------------------

    def set_failed(self, code: int = ErrorCode.EFAILEDSOCKET, reason: str = "") -> bool:
        from incubator_brpc_tpu.transport.sock import CONNECTED, FAILED

        if self.state != CONNECTED:
            return False
        self.state = FAILED
        self.error_code = code
        self.error_text = reason
        # a frame or a body held for its other half: that will not come
        self._unary_order.clear()
        self._unary_body = None
        if code != ErrorCode.ECLOSE:
            self.link.fail(reason)
        else:
            self.link.close(self.side)
        for cb in list(self.on_failed):
            try:
                cb(self)
            except Exception:
                logger.exception("device socket on_failed raised")
        self.link._maybe_retire_metrics()
        return True

    def recycle(self) -> None:
        from incubator_brpc_tpu.transport.sock import RECYCLED, _registry

        if getattr(self, "_recycled", False):
            return  # idempotent: the link map and channels may both settle us
        self._recycled = True
        self.set_failed(ErrorCode.ECLOSE, "recycled")
        self.state = RECYCLED
        _registry.recycle(self.id)

    # sync fast path: a device socket has no fd to poll — callers join
    def try_read_ownership(self) -> bool:
        return False

    def kick_poller(self) -> None:
        pass

    def __repr__(self) -> str:
        return f"<DeviceSocket side={self.side} dev={self.link.devices[self.side]}>"


# -- rendezvous + handshake ---------------------------------------------------


class LinkHub:
    """Cookie rendezvous for link halves. Single-controller JAX: both
    parties live in one process, so the hub is process-global (the
    reference's analog is the rdmacm exchange). A multi-controller
    deployment would rendezvous through the distributed runtime instead —
    the link step itself is already SPMD-dispatchable per host.

    Un-taken cookies expire after ``ttl`` seconds (a client whose
    handshake RPC timed out never collects its link): expiry fails the
    link and recycles its server-side socket so nothing leaks."""

    def __init__(self, ttl: float = 60.0) -> None:
        self._lock = threading.Lock()
        self._links: Dict[str, tuple] = {}  # cookie -> (link, created_ts)
        self._ttl = ttl

    def _prune_locked(self) -> None:
        import time as _time

        now = _time.monotonic()
        for cookie in [
            c for c, (_, ts) in self._links.items() if now - ts > self._ttl
        ]:
            link, _ = self._links.pop(cookie)
            link.fail("handshake abandoned (cookie expired)")
            for sock in link.socks:
                if sock is not None:
                    sock.recycle()

    def create(
        self, cookie: str, devices, slot_words: int, window: int,
        ack_mode: str = "local",
    ) -> DeviceLink:
        import time as _time

        with self._lock:
            self._prune_locked()
            if cookie in self._links:
                raise ValueError("cookie already in use")
            link = DeviceLink(
                devices, slot_words=slot_words, window=window, ack_mode=ack_mode
            )
            self._links[cookie] = (link, _time.monotonic())
            return link

    def take(self, cookie: str) -> Optional[DeviceLink]:
        with self._lock:
            self._prune_locked()
            entry = self._links.pop(cookie, None)
            return entry[0] if entry is not None else None


link_hub = LinkHub()
_cookie_counter = itertools.count(1)


class DeviceLinkMap:
    """Client-side dedup of established device links keyed by
    (endpoint, local device, geometry) — the SocketMap analog for the
    device plane (reference socket_map.h:35 keys connections by
    {EndPoint, rdma, ssl, auth}; rdma_endpoint.h:42-213 runs one QP per
    peer, unbounded peers). Every Channel — single-server, LB-resolved,
    or a PartitionChannel sub-channel — shares ONE link per peer+geometry;
    a dead link is recycled and re-handshaken on the next get. This is
    what turns the two-party DeviceLink into an N-party fabric: a client
    device holds a star of links, one per peer device."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._links: Dict[tuple, "DeviceSocket"] = {}
        # per-endpoint establishment locks; never deleted (deleting a lock
        # another thread holds would let two handshakes race on one key) —
        # bounded by the distinct peers this process ever contacts
        self._key_locks: Dict[tuple, threading.Lock] = {}
        self._cred_refs: Dict[tuple, tuple] = {}  # keep id()-keyed objects alive
        # re-handshake backoff per key: (consecutive_failures,
        # next_allowed_monotonic). A dead peer must not be storm-redialed
        # by every caller that wants the link — failures double the wait
        # (device_link_backoff_initial_ms .. _max_ms), success clears it —
        # the device-plane analog of the circuit breaker's exponential
        # isolation (reference rdma_endpoint re-establishment discipline)
        self._backoff: Dict[tuple, tuple] = {}

    def _key_lock(self, key: tuple) -> threading.Lock:
        with self._lock:
            lk = self._key_locks.get(key)
            if lk is None:
                lk = self._key_locks[key] = threading.Lock()
            return lk

    def get_or_create(
        self,
        ep: EndPoint,
        device_index: int = 0,
        slot_words: int = 16384,
        window: int = 8,
        timeout_ms: float = 60000,
        ack_mode: str = "local",
        controller: str = "single",
        auth=None,
        ssl_context=None,
        ssl_server_hostname=None,
    ) -> "DeviceSocket":
        """``auth``/``ssl_*`` are the calling channel's credentials: the
        handshake must present them (an auth-requiring or TLS server
        rejects a bare bootstrap), and they are part of the link identity —
        channels with different credentials never share a link (the
        reference keys SocketMap by {EndPoint, rdma, ssl, auth},
        socket_map.h:35)."""
        from incubator_brpc_tpu.transport.sock import CONNECTED

        ident = (
            f"auth-{id(auth):x}" if auth is not None else "",
            f"ssl-{id(ssl_context):x}" if ssl_context is not None else "",
            ssl_server_hostname or "",
        )
        key = (
            ep.ip, ep.port, device_index, slot_words, window, ack_mode,
            controller, ident,
        )
        if auth is not None or ssl_context is not None:
            # the key embeds id()s: retain the credential objects for the
            # entry's lifetime, or a GC'd auth object's recycled address
            # would alias a DIFFERENT principal onto this link
            with self._lock:
                self._cred_refs[key] = (auth, ssl_context)
        # per-key lock: a thundering herd to one peer produces ONE
        # handshake, while links to OTHER peers establish concurrently
        with self._key_lock(key):
            with self._lock:
                ds = self._links.get(key)
            if ds is not None and ds.state == CONNECTED:
                return ds
            if ds is not None:
                ds.recycle()  # free the dead link's registry slot
                with self._lock:
                    self._links.pop(key, None)
            # exponential re-handshake backoff: while a recent attempt to
            # this peer failed, refuse instantly instead of dialing — the
            # caller's retry/LB machinery routes around the peer
            from time import monotonic as _mono

            from incubator_brpc_tpu.utils.flags import get_flag as _gf

            with self._lock:
                bo = self._backoff.get(key)
            if bo is not None and _mono() < bo[1]:
                raise ConnectionError(
                    f"device link to {ep.ip}:{ep.port} backing off after "
                    f"{bo[0]} failed handshake(s) "
                    f"({max(0.0, bo[1] - _mono()) * 1e3:.0f} ms left)"
                )
            # The handshake rides a fresh host channel to the peer (the
            # reference's TCP-piggybacked magic+cookie) carrying the
            # caller's credentials; the global client socket map dedupes
            # the underlying TCP connection, so the channel object itself
            # is throwaway — built per establishment, never cached (a
            # cached one would freeze the first caller's timeout forever).
            from incubator_brpc_tpu.rpc.channel import Channel, ChannelOptions

            try:
                boot = Channel()
                if not boot.init(
                    EndPoint(ip=ep.ip, port=ep.port),
                    options=ChannelOptions(
                        timeout_ms=timeout_ms,
                        auth=auth,
                        ssl_context=ssl_context,
                        ssl_server_hostname=ssl_server_hostname,
                    ),
                ):
                    raise ConnectionError(
                        f"device-link bootstrap channel init failed for {ep}"
                    )
                if controller == "multi":
                    from incubator_brpc_tpu.transport.mc_link import (
                        establish_mc_link,
                    )

                    ds = establish_mc_link(
                        boot,
                        device_index=device_index,
                        slot_words=slot_words,
                        window=window,
                        timeout_ms=timeout_ms,
                    )
                else:
                    ds = establish_device_link(
                        boot,
                        device_index=device_index,
                        slot_words=slot_words,
                        window=window,
                        timeout_ms=timeout_ms,
                        ack_mode=ack_mode,
                    )
            except Exception:
                # failed handshake: arm/double the backoff window so the
                # next caller fails fast instead of re-storming the peer
                failures = (bo[0] if bo is not None else 0) + 1
                wait_ms = min(
                    int(_gf("device_link_backoff_initial_ms"))
                    * (2 ** (failures - 1)),
                    int(_gf("device_link_backoff_max_ms")),
                )
                with self._lock:
                    self._backoff[key] = (failures, _mono() + wait_ms / 1e3)
                raise
            with self._lock:
                self._backoff.pop(key, None)  # healthy again
                # opportunistic sweep: recycle dead entries so a long-lived
                # process contacting many ephemeral peers does not
                # accumulate dead sockets in the registry
                for k, old in [
                    (k, v) for k, v in self._links.items() if v.state != CONNECTED
                ]:
                    old.recycle()
                    del self._links[k]
                    if k != key:
                        self._cred_refs.pop(k, None)
                self._links[key] = ds
            return ds

    def live_links(self) -> List["DeviceSocket"]:
        from incubator_brpc_tpu.transport.sock import CONNECTED

        with self._lock:
            return [ds for ds in self._links.values() if ds.state == CONNECTED]

    def link_profile(self) -> Dict[int, dict]:
        """Per-PEER-device snapshot of the live star's measured link
        telemetry: {peer global device id: DeviceLink.profile()}.  This
        is what the topology-aware session scheduler consumes (order
        party fan-out and chunk routes by measured GB/s instead of mesh
        order — TASP) and what ``rpc_view --links`` renders: the
        rtt/bytes-per-second recorders have been live since PR 1, but
        scrape-only.  Two links to one peer device (distinct geometry
        keys) keep the faster-measured entry — the scheduler wants the
        best current estimate of the PEER, not of any one link."""
        prof: Dict[int, dict] = {}
        for ds in self.live_links():
            link = ds.link
            peer = link.devices[1 - ds.side]
            pid = getattr(peer, "id", None)
            if pid is None:
                continue
            p = link.profile()
            have = prof.get(int(pid))
            if have is None or p["gbps"] > have["gbps"]:
                prof[int(pid)] = p
        return prof


device_link_map = DeviceLinkMap()


def link_profile() -> Dict[int, dict]:
    """The process-global star's per-peer telemetry snapshot (see
    :meth:`DeviceLinkMap.link_profile`)."""
    return device_link_map.link_profile()


def make_handshake_handler(server):
    """The server half of the handshake: an ordinary RPC handler on the
    host socket (the TCP-piggybacked magic+cookie of socket.cpp:1692-1704).
    Builds the link + the server-side DeviceSocket bound to this server's
    messenger and method map."""

    def handshake(cntl, request: bytes) -> bytes:
        import jax

        try:
            req = json.loads(request.decode())
        except ValueError as e:
            cntl.set_failed(ErrorCode.EREQUEST, f"bad handshake: {e}")
            return b""
        if not isinstance(req, dict):
            cntl.set_failed(ErrorCode.EREQUEST, "bad handshake: not an object")
            return b""
        if req.get("controller") == "multi":
            # the multi-controller deployment: peer devices live in
            # DIFFERENT processes; the link half built here is lockstep
            # SPMD with the proposer's (transport/mc_link.py)
            from incubator_brpc_tpu.transport.mc_link import (
                accept_mc_handshake,
            )

            return accept_mc_handshake(server, cntl, req)
        try:
            cookie = req["cookie"]
            client_dev = int(req["device"])
            slot_words = int(req.get("slot_words", 16384))
            window = int(req.get("window", 8))
            ack_mode = str(req.get("ack_mode", "local"))
        except (ValueError, KeyError, TypeError) as e:
            cntl.set_failed(ErrorCode.EREQUEST, f"bad handshake: {e}")
            return b""
        devices = jax.devices()
        server_dev = getattr(server.options, "device_index", None)
        if server_dev is None:
            # prefer a device different from the client's (a real second
            # chip / virtual mesh neighbor); fall back to sharing one
            server_dev = (client_dev + 1) % len(devices)
        if client_dev >= len(devices) or server_dev >= len(devices):
            cntl.set_failed(ErrorCode.EREQUEST, "device index out of range")
            return b""
        try:
            link = link_hub.create(
                cookie,
                [devices[client_dev], devices[server_dev]],
                slot_words=slot_words,
                window=window,
                ack_mode=ack_mode,
            )
        except ValueError as e:
            cntl.set_failed(ErrorCode.EREQUEST, str(e))
            return b""
        ds = DeviceSocket(
            link,
            side=1,
            messenger=server._messenger,
            context={"server": server},
        )
        server._device_socks.append(ds)

        def _forget(sock, _server=server):
            # a dead link must not accumulate on a long-running server:
            # drop it from the list and free its registry slot
            try:
                _server._device_socks.remove(sock)
            except ValueError:
                pass
            sock.recycle()

        # fabriclint: allow(lifecycle-callback) self-pruning hook: removes the dead link from the server list and recycles it — firing the hook IS the teardown, and the server fails every device sock at stop
        ds.on_failed.append(_forget)
        return json.dumps(
            {
                "device": server_dev,
                "slot_words": slot_words,
                "window": window,
                # fingerprints of this server's device-kernel methods: the
                # client's fused combo dispatch only lowers a call when the
                # peer advertises the SAME kernel under that name
                "device_methods": {
                    full: dm.fingerprint()
                    for full, dm in getattr(server, "_device_methods", {}).items()
                },
            }
        ).encode()

    return handshake


def establish_device_link(
    channel,
    device_index: int = 0,
    slot_words: int = 16384,
    window: int = 8,
    timeout_ms: float = 60000,
    ack_mode: str = "local",
) -> DeviceSocket:
    """Client half: propose over the host socket, then attach side 0.
    ``channel`` must be an initialized single-server Channel whose normal
    (TCP) path carries the handshake RPC."""
    from incubator_brpc_tpu.rpc.controller import Controller

    cookie = f"link-{next(_cookie_counter)}-{id(channel):x}"
    payload = json.dumps(
        {
            "cookie": cookie,
            "device": device_index,
            "slot_words": slot_words,
            "window": window,
            "ack_mode": ack_mode,
        }
    ).encode()
    cntl = channel._call_host(
        HANDSHAKE_SERVICE,
        HANDSHAKE_METHOD,
        payload,
        cntl=Controller(timeout_ms=timeout_ms),
    )
    if cntl.failed():
        raise ConnectionError(f"device handshake failed: {cntl.error_text}")
    link = link_hub.take(cookie)
    if link is None:
        raise ConnectionError("device handshake succeeded but link not found")
    try:
        advertised = json.loads(cntl.response_payload.decode()).get(
            "device_methods", {}
        )
    except (ValueError, AttributeError):
        advertised = {}
    from incubator_brpc_tpu.rpc import channel as channel_mod

    ds = DeviceSocket(
        link,
        side=0,
        messenger=channel_mod._client_messenger,
    )
    # the peer's device-kernel fingerprints gate the fused combo dispatch
    ds.device_methods = advertised
    return ds
