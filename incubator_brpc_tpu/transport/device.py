"""Device transport — the ``transport=tpu`` slot (reference analog:
src/brpc/rdma/rdma_endpoint.h:42-213 per-connection QP with send/recv
rings and credit-window flow control, block_pool.h registered-memory
blocks, rdma_completion_queue CQ delivery).

A ``DeviceEndpoint`` is the RdmaEndpoint re-thought for XLA:

- the "registered memory" is HBM itself: requests are framed into uint32
  device buffers (ops/framing), the *entire server hot path* — parse,
  verify, dispatch, handle, respond — is one fused XLA computation
  (models/tensor_echo), and only the response crosses back;
- the "credit window" bounds in-flight device dispatches
  (``window_size``, like _local_window_capacity rdma_endpoint.h:176-195):
  callers park on a butex when the window is full, completions release
  credits;
- the "completion queue" is a DeviceCompletionButex watcher
  (rdma_completion_queue delivering CQ events, here PJRT readiness);
- frames are bucketed to power-of-two payload sizes so XLA compiles one
  program per geometry and reuses it (static shapes; the block-pool
  fixed-block discipline applied to programs instead of buffers);
- calls queued together leave as one dispatch whatever their buckets:
  the rows are stacked at the widest bucket among them, under
  ``MAX_STACKED_WORDS``.

- a service may keep state in HBM between calls (models/record_table):
  the endpoint owns it, hands it to every dispatch, donated, and takes
  the next one back; dispatches launch in the order they took it, and a
  dispatch sees its batch whole (docs/DEVICE_PLANE.md has the contract);
- or state that a step reads and never replaces (models/expert_shard: a
  rank's expert weights): nothing is donated, no dispatch waits its turn
  and a program that raises loses nothing;
- a call's operand may be a ``jax.Array`` that lies on the endpoint's
  device (``call_words(..., operand=...)``; a unary call's attachment,
  through ``server_handler``): it rides the same window, queue, drain,
  watchers and stage recorders as a call of host words, alone in its
  dispatch, and neither it nor its answer, a ``jax.Array`` too, is ever
  in host memory: the host sees the request's words going in and one
  response frame of a row's width coming back (the error code, the
  service's tally). The operand's type selects the path, nothing else.

``DeviceEndpoint.call_bytes`` adapts the host byte world: a request's
bytes are queued as they lie, a dispatch pads them into the bucket (or
hands them to the program as they are, where one call fills its row), and
responses are cut at the length the service says its method answers that
request with (an echo: the request's own; a record read: 1,000 B to 8),
the bucket being the larger of the request and the answer.
``server_handler`` plugs
an endpoint into an ordinary Server method map, giving the full
host-RPC → HBM → fused-step → response path — the reference's
"flip transport=tpu and rerun the same example pair" moment (SURVEY §7
step 5).
"""

from __future__ import annotations

import ctypes
import itertools
import threading
from collections import deque
from functools import partial
from typing import Optional, Tuple

import time as _time

import jax
import numpy as np
from jax.sharding import SingleDeviceSharding

from incubator_brpc_tpu import native
from incubator_brpc_tpu.bvar import (
    CPU_CLOCK_EVERY,
    Adder,
    LatencyRecorder,
    RecorderFeed,
    clocks,
)
from incubator_brpc_tpu.ops import framing
from incubator_brpc_tpu.runtime.butex import Butex, ETIMEDOUT
from incubator_brpc_tpu.runtime.device_butex import DeviceCompletionButex
from incubator_brpc_tpu.utils.status import ErrorCode

MIN_BUCKET_WORDS = 64
MAX_BUCKET_WORDS = 1 << 24  # 64 MiB of uint32
# The most words (padded rows x widest bucket) a dispatch may stack once
# it holds calls of different buckets: 1 MiB, sixteen rows of a 64 KiB
# bucket. What it stands on (PERF.md section 6, PR 53's chip runs): per
# byte a dispatch costs about 0.6 ms a MiB where two callers share the
# interpreter (4 MiB alone, echo_4m_c2: device_readback_us 1,428 + the copy
# inside the launch, ~1.0 of its 1,481; the stack is borrowed) and up to
# 1.5 where sixteen do (2.2 MiB stacked in the expert shard's cell:
# device_stack_us 1,469 + device_readback_us 1,953), per dispatch the
# launch alone costs 2.5-4.1 ms at sixteen callers (device_launch_us, same
# runs), so under 1 MiB widening adds about a third or less of what one
# saved dispatch gives back. Calls of one bucket stack as they always did,
# whatever their size. Move it only on chip evidence, written into PERF.md.
MAX_STACKED_WORDS = 1 << 18

# credit held -> response parsed, per call: the total the stages split
device_latency = LatencyRecorder(name="device_transport_latency")
# per dispatch that reached the device, fed by the completion watcher
m_dispatches = Adder(name="device_transport_dispatches")
m_dispatch_rows = Adder(name="device_transport_dispatch_rows")
m_dispatch_pad_rows = Adder(name="device_transport_dispatch_pad_rows")
m_dispatch_words = Adder(name="device_transport_dispatch_words")
m_dispatch_widened_rows = Adder(name="device_transport_dispatch_widened_rows")
# words of the operands that no call wrote and the dispatch zeroed (rows'
# tails, pad rows), and dispatches whose operand was the request's own memory
m_dispatch_zeroed_words = Adder(name="device_transport_dispatch_zeroed_words")
m_dispatch_borrowed = Adder(name="device_transport_dispatch_borrowed")
# calls whose operand was a jax.Array on the endpoint's device, served where
# it lay; and those whose tensor came as host bytes or lay on another device
# and was put there first (and, for bytes, whose answer was read back)
m_device_operands = Adder(name="device_transport_device_operands")
m_device_operand_fallbacks = Adder(name="device_transport_device_operand_fallbacks")

# A completed call's row, as _PendingCall.row writes it: the dispatch it
# rode, its stamps in the order written (time.monotonic_ns()), the host
# plane's three around them (-1: never taken), then the CPU clock
# (time.thread_time_ns()) of the thread that wrote the stamp, for the
# stages of the dispatch that one thread begins and ends, on one dispatch
# in bvar.CPU_CLOCK_EVERY (-1 on the others). The caller's own stamps carry
# none: a read is a system call, and seven a call cost 6.8% of the calls/s
# at 256 B on the chip's host (PERF.md, PR 35).
_WALL = (
    "entry", "words", "credit_held", "enqueued", "batched", "stacked",
    "launched", "cq_taken", "ready", "readback", "woke", "exit",
)
STAMPS = ("seq",) + _WALL + (
    "cut", "plane_callback", "sent",
    # the drain or -tx thread of the dispatch, then its completion watcher
    "batched_cpu", "stacked_cpu", "launched_cpu", "ready_cpu", "readback_cpu",
    # the service's state in the dispatch's hand, inside stacked -> launched
    "state",
)
# One recorder per stage of a call through call_bytes (us, one sample per
# completed call), each the difference of the stamps beside it, so that
# the first nine means add up to the time inside call_bytes; the host
# plane's three lie around them. ingress: the request's frame cut off the
# wire (the Python messenger's stamp, or the C++ cutter's on the native
# plane) -> server_handler entered. plane_callback, native plane only: the
# cut -> the reactor's frame callback had the interpreter; part of
# ingress. egress: call_bytes returned -> the response was handed to the
# connection's write. docs/OBSERVABILITY.md has the table. The stage is
# defined here and nowhere else: the sampler does the subtraction.
STAGES = {
    # the adapter's host work: the request's bytes seen as words, a
    # writeable array copied as it is queued, response words back to bytes
    "copy": (("entry", "words"), ("credit_held", "enqueued"), ("woke", "exit")),
    "credit_wait": ("words", "credit_held"),
    "queue_wait": ("enqueued", "batched"),
    "stack": ("batched", "stacked"),
    "launch": ("stacked", "launched"),
    "cq_wait": ("launched", "cq_taken"),
    "ready": ("cq_taken", "ready"),
    "readback": ("ready", "readback"),
    "wake": ("readback", "woke"),
    "ingress": ("cut", "entry"),
    "plane_callback": ("cut", "plane_callback"),
    "egress": ("exit", "sent"),
}
# the stages of a dispatch, each begun and ended by one thread: these
# carry its CPU clock too, <stage>_cpu_us beside <stage>_us, once a timed
# dispatch and credited to each of its calls as the wall stage is (wall
# less CPU = that thread was off the processor)
CPU_STAGES = ("stack", "launch", "readback")


# by stage; the names are written out so that a search for one finds it
_recorders = {
    "copy": LatencyRecorder(name="device_transport_copy_us"),
    "credit_wait": LatencyRecorder(name="device_transport_credit_wait_us"),
    "queue_wait": LatencyRecorder(name="device_transport_queue_wait_us"),
    "stack": LatencyRecorder(name="device_transport_stack_us"),
    "launch": LatencyRecorder(name="device_transport_launch_us"),
    "cq_wait": LatencyRecorder(name="device_transport_cq_wait_us"),
    "ready": LatencyRecorder(name="device_transport_ready_us"),
    "readback": LatencyRecorder(name="device_transport_readback_us"),
    "wake": LatencyRecorder(name="device_transport_wake_us"),
    "ingress": LatencyRecorder(name="device_transport_ingress_us"),
    "plane_callback": LatencyRecorder(name="device_transport_plane_callback_us"),
    "egress": LatencyRecorder(name="device_transport_egress_us"),
    "stack_cpu": LatencyRecorder(name="device_transport_stack_cpu_us"),
    "launch_cpu": LatencyRecorder(name="device_transport_launch_cpu_us"),
    "readback_cpu": LatencyRecorder(name="device_transport_readback_cpu_us"),
    # a part of the launch, not a tenth stage: rows stacked -> the service's
    # state in the dispatch's hand, i.e. the wait for the dispatch before it
    # to hand the state on (a service that keeps none waits for nothing)
    "state_wait": LatencyRecorder(name="device_transport_state_wait_us"),
}
m_copy, m_credit_wait, m_queue_wait, m_stack, m_launch = (
    _recorders[s] for s in ("copy", "credit_wait", "queue_wait", "stack", "launch")
)
m_cq_wait, m_ready, m_readback, m_wake = (
    _recorders[s] for s in ("cq_wait", "ready", "readback", "wake")
)
m_ingress, m_plane_callback, m_egress = (
    _recorders[s] for s in ("ingress", "plane_callback", "egress")
)

# completed calls' rows wait here for the sampler thread: the write path
# is one append. The last 32 Ki rows stay (30 s of the fastest cell)
_stage_feed = RecorderFeed(
    [(_recorders[stage], 1e-3, span) for stage, span in STAGES.items()]
    + [
        (_recorders[stage + "_cpu"], 1e-3,
         tuple(stamp + "_cpu" for stamp in STAGES[stage]))
        for stage in CPU_STAGES
    ]
    + [(_recorders["state_wait"], 1e-3, ("stacked", "state"))],
    stamps=STAMPS,
    name="device_transport",
    ring_rows=1 << 15,
    worker=(STAGES["stack"], STAGES["launch"], STAGES["ready"], STAGES["readback"]),
    call=(("entry", "exit"),),
)


def _record(pending: "_PendingCall", cntl, sent_ns: Optional[int]) -> None:
    """One row for ``_stage_feed`` from a completed call: its stamps, and
    from the server-side controller of the RPC it served, if any, the
    host plane's around them. A sampled rpcz span gets the same timeline
    as annotations."""
    cut_ns = entered = -1
    span = None
    if cntl is not None:
        span = getattr(cntl, "_span", None)
        arrival = getattr(cntl, "_arrival_ts", None)
        if arrival is not None:
            cut_ns = int(arrival * 1e9)
            entered = getattr(cntl, "_plane_callback_ns", None) or -1
    _stage_feed.rows.append(
        pending.row(cut_ns, entered, -1 if sent_ns is None else sent_ns)
    )
    if span is not None:
        if entered > 0:  # before the span's own start
            span.annotate("device plane_callback", entered)
        pending.annotate(span)
        if sent_ns is not None:
            span.annotate("device sent", sent_ns)


def flush_stage_recorders() -> None:
    """Feed the stage recorders now instead of within the second (tests,
    a reader that wants the last calls counted)."""
    _stage_feed.flush()


def _bucket_words(n: int) -> int:
    b = MIN_BUCKET_WORDS
    while b < n:
        b <<= 1
    if b > MAX_BUCKET_WORDS:
        raise ValueError(f"payload of {n} words exceeds max bucket")
    return b


def _pad_rows(b: int) -> int:
    """Rows the program runs for a batch of ``b`` calls: the next power of
    two, so jit compiles O(log max_batch) programs per bucket."""
    return 1 << (b - 1).bit_length()


def _stack_rows(rows: np.ndarray, sources: list) -> int:
    """Write ``rows``, a fresh ``(bpad, bucket) uint32`` array, every word
    once: call ``i``'s bytes (``sources[i]``: contiguous ``uint32`` or
    ``uint8``) at the head of row ``i``, zeros in the row's tail and in the
    pad rows whole. One native call, which keeps the interpreter lock or lets
    it go once by the bytes it writes (the rule at ``native.LIB_HELD``); the
    same words with numpy where the library is absent. Returns the words
    zeroed."""
    n, row_bytes = len(sources), rows.shape[1] * 4
    lens = [src.nbytes for src in sources]
    if native.LIB is not None:
        rc = native.lib_for(rows.nbytes).tb_stack_rows(
            rows.ctypes.data, rows.shape[0], row_bytes,
            (ctypes.c_void_p * n)(*[src.ctypes.data for src in sources]),
            (ctypes.c_size_t * n)(*lens), n,
        )
        if rc != 0:
            raise ValueError(f"a call's words overrun a row of {row_bytes} bytes")
    else:
        as_bytes = rows.view(np.uint8)
        for i, src in enumerate(sources):
            as_bytes[i, : lens[i]] = src.view(np.uint8)
            as_bytes[i, lens[i] :] = 0
        as_bytes[n:] = 0
    return rows.size - sum(-(-k // 4) for k in lens)


class _Dispatch:
    """One (batch, bucket) program execution, shared by the calls stacked
    into it; ``bucket`` is the widest among theirs. Its stamps are each
    written once, by the thread that does the work, on both clocks
    (``bvar.clocks``: ``t_*`` wall, ``c_*`` that thread's CPU, -1 unless the
    dispatch is ``timed``): the drain or ``-tx`` thread up to ``launched``,
    a completion watcher from there."""

    __slots__ = (
        "seq", "rows", "pad_rows", "bucket", "widened_rows", "zeroed_words",
        "borrowed", "timed",
        "t_batched", "t_stacked", "t_launched", "watcher", "t_readback",
        "c_batched", "c_stacked", "c_launched", "c_readback", "t_state",
    )

    def __init__(
        self, seq: int, rows: int, pad_rows: int, bucket: int, widened_rows: int
    ):
        self.seq = seq  # the endpoint's dispatch number
        self.rows = rows  # calls stacked
        self.pad_rows = pad_rows  # rows the program ran (next power of two)
        self.bucket = bucket  # payload words per row as the program ran it
        self.widened_rows = widened_rows  # calls whose own bucket is narrower
        self.zeroed_words = 0  # of the operand: tails and pad rows
        self.borrowed = 0  # 1: the operand is the one call's own words
        self.timed = seq % CPU_CLOCK_EVERY == 0  # its stamps carry the CPU clock
        self.t_batched, self.c_batched = clocks(self.timed)  # taken off the queue
        self.t_stacked, self.c_stacked = 0, -1  # the operand built
        self.t_state = -1  # the service's state in hand, its turn come
        # the program call, which stages the rows, returned
        self.t_launched, self.c_launched = 0, -1
        # DeviceCompletionButex.watch fills these: a watcher thread took
        # the job, block_until_ready returned, and (the third slot, which
        # only a timed dispatch gives it) the watcher's CPU clock at that
        # return
        self.watcher = [0, 0, -1] if self.timed else [0, 0]
        self.t_readback, self.c_readback = 0, -1  # device_get returned; 0 = never completed


class _PendingCall:
    """One call and its timeline: ``time.monotonic_ns()`` stamps written
    by the caller's thread, plus the stamps of the dispatch it rode."""

    __slots__ = (
        "ready", "response_words", "operand", "response_array", "error_code", "error",
        "t_entry", "t_words", "t_credit", "t_enqueued", "dispatch",
        "t_woke", "t_exit",
    )

    def __init__(self):
        self.ready = Butex(0)
        self.response_words = None
        self.operand = None  # a device operand, where the call carries one
        self.response_array = None  # its answer, on the device
        self.error_code = 0
        self.error: Optional[BaseException] = None
        self.t_entry = 0  # call_bytes entered (call_words: same as t_words)
        self.t_words = 0  # payload is words: call_words entered
        self.t_credit = 0  # credit held
        self.t_enqueued = 0  # its words queued with their bucket
        self.dispatch: Optional[_Dispatch] = None
        self.t_woke = 0  # caller running again after wait
        self.t_exit = 0  # call_bytes returns (call_words: stays 0)

    def settle(self) -> None:
        self.ready.add(1)
        self.ready.wake_all()

    def wait(self, timeout: Optional[float]) -> bool:
        while self.ready.load() == 0:
            if self.ready.wait(0, timeout=timeout) == ETIMEDOUT:
                return False
        if not self.t_woke:
            self.t_woke = _time.monotonic_ns()
        return True

    def completed(self) -> bool:
        """The call went the whole way: through a dispatch to a readback
        (whatever the response said). Only such calls are recorded."""
        return self.dispatch is not None and self.dispatch.t_readback != 0

    def row(self, cut: int = -1, plane_callback: int = -1, sent: int = -1) -> tuple:
        """The call's row for ``_stage_feed``, a number a position of
        ``STAMPS``; -1 where the host plane took no stamp, and for the exit
        of a raw ``call_words``, which has none."""
        d = self.dispatch
        t_cq, t_ready = d.watcher[:2]
        return (
            d.seq,
            self.t_entry, self.t_words, self.t_credit, self.t_enqueued,
            d.t_batched, d.t_stacked, d.t_launched, t_cq, t_ready,
            d.t_readback, self.t_woke, self.t_exit or -1,
            cut, plane_callback, sent,
            d.c_batched, d.c_stacked, d.c_launched, d.watcher[-1] if d.timed else -1,
            d.c_readback, d.t_state,
        )

    def timeline(self):
        """``(name, monotonic_ns)`` of every stamp, in the order written."""
        return tuple(zip(_WALL, self.row()[1 : 1 + len(_WALL)]))

    def stages(self) -> dict:
        """``{stage: ns}`` as ``_stage_feed``'s table cuts them from the
        call's stamps (``<stage>_cpu`` on the CPU clock, ``state_wait`` a
        part of ``launch``); the nine of a call through call_bytes add up
        to ``t_exit - t_entry``."""
        values = _stage_feed.read(self.row())
        names = list(STAGES) + [stage + "_cpu" for stage in CPU_STAGES]
        names.append("state_wait")
        return {n: v for n, v in zip(names, values) if v is not None}

    def annotate(self, span) -> None:
        """A sampled server span gets the call's stamps as annotations,
        offsets on the span's monotonic clock; calls of one dispatch
        share its number."""
        d = self.dispatch
        for name, at in self.timeline():
            if at <= 0:
                continue
            if name == "batched":
                name = (
                    f"batched dispatch={d.seq} rows={d.rows} "
                    f"pad_rows={d.pad_rows} bucket={d.bucket}"
                )
            span.annotate("device " + name, at)


# what an endpoint holds in place of a state it can no longer vouch for: a
# program call raised with the state donated to it
_LOST = object()


class _StepProgram:
    """The service's jitted step as the endpoint runs it: ``program(rows,
    cids, mids) -> response frames`` (with a device operand: ``program(row,
    operand, cid, mid) -> (answer, response frame)``). State that the step
    replaces is taken from the endpoint in turn, donated to the step, and
    the next one put back before the turn passes on; state that it only
    reads (or none) is handed to every call as it lies, side by side.
    ``dispatch`` gets the moment the state was in hand."""

    __slots__ = ("_endpoint", "_jitted")

    def __init__(self, endpoint: "DeviceEndpoint", jitted):
        self._endpoint, self._jitted = endpoint, jitted

    def __call__(self, rows, cids, mids, dispatch: Optional["_Dispatch"] = None):
        return self.run((rows, cids, mids), dispatch)

    def run(self, operands: tuple, dispatch: Optional["_Dispatch"] = None):
        """The jitted step on ``operands``, the state handed through."""
        ep = self._endpoint
        if ep._state_turn is None:  # nothing to take in turn, nothing to lose
            if dispatch is not None:
                dispatch.t_state = _time.monotonic_ns()
            return self._jitted(ep._state, *operands)[1]
        with ep._state_turn:
            if dispatch is not None:
                dispatch.t_state = _time.monotonic_ns()
            state = ep._state
            if state is _LOST:
                raise RuntimeError(
                    "the endpoint lost its service's state: an earlier "
                    "dispatch raised with the state donated to it"
                )
            ep._state = _LOST  # until the step hands the next one back
            ep._state, answer = self._jitted(state, *operands)
        return answer

    def _cache_size(self) -> int:
        """Compiled geometries and fast-path entries (tests)."""
        return self._jitted._cache_size()


class DeviceEndpoint:
    """One device-resident service behind a credit window.

    The service (``models/tensor_echo``, ``models/record_table``,
    ``models/expert_shard``) gives ``init_state(device)``: what it keeps
    in HBM between calls, or ``None``; ``dispatch_step(state, rows, cids,
    mids) -> (state', response frames)``, jittable, over a whole batch of
    zero-padded payload rows, ``state'`` ``None`` where the step only
    reads its state; ``answer_bytes(method_id, request_bytes)``: how long
    that method's answer to such a request is; and ``account(mids,
    frames)``, called on the host with a completed dispatch's method ids
    and response frames (its counters). A call's bucket is the larger of
    its request and its answer, its row is zero-padded to its bucket or
    the widest bucket of the calls that share its dispatch, and it gets
    back the answer's first ``answer_bytes`` bytes, so those must not
    depend on that width.

    The state is the endpoint's: it lies on ``device``, every dispatch
    takes it, donates it to the program and puts the next one back, so a
    table is updated where it lies. One dispatch holds it at a time
    (``_state_turn``): dispatches launch in the order they took it, the
    wait is ``device_transport_state_wait_us``, and the calls of one
    dispatch are in flight together, so the service fixes their order
    among themselves. A program call that raises with the state in its
    hand fails the endpoint: the state may be gone, every later dispatch
    fails, none is answered from a copy. A service that keeps nothing has
    no turn to wait for and nothing to lose, and neither has one whose
    step hands no state back (a rank's expert weights): the endpoint
    learns that from the step's abstract result when it is built, donates
    nothing, and every dispatch reads the state where it lies.

    A dispatch launches with one call of the jitted step program on the
    stacked host rows: the call stages its numpy arguments itself, onto
    ``device`` (both programs pin ``in_shardings`` there), and nothing
    else touches the device between rows stacked and the call's return
    (``device_transport_launch_us``).

    **A device operand.** A service that takes a tensor also gives
    ``dispatch_tensor(state, row, operand, cid, mid) -> (state', answer,
    response frame)``, jittable: ``row`` is the request's words zero-padded
    to ``MIN_BUCKET_WORDS`` (256 B, a lane tag's length), ``operand`` the
    ``jax.Array`` the call carries, ``answer`` a ``jax.Array`` that stays on
    the device, and the frame, ``8 + MIN_BUCKET_WORDS`` words, is all the
    host reads of the call: its error code, and whatever the service's
    ``account`` reads of a frame. ``call_words(words, operand=<jax.Array>)``
    is such a call: same credit, queue, drain, watcher and stamps, a
    dispatch of its own (a tensor fills a program), no ``np`` row of the
    operand, no ``device_put`` and no read-back of operand or answer;
    ``pending.response_array`` is the answer. An operand that lies on
    another device, or came as host bytes, is put on ``device`` first and
    counted (``device_transport_device_operand_fallbacks``); a service
    without ``dispatch_tensor`` answers ``EREQUEST``."""

    def __init__(
        self,
        service=None,
        device=None,
        window_size: int = 8,
        max_batch: int = 16,
    ):
        from incubator_brpc_tpu.models.tensor_echo import TensorEchoService

        self.service = service or TensorEchoService()
        self.device = device if device is not None else jax.devices()[0]
        self.window_size = window_size
        # Micro-batching: concurrent calls stack into ONE [B, width]
        # dispatch of the vmapped step (batch sizes padded to powers of
        # two so jit compiles a handful of programs, not one per B;
        # width the widest bucket among them). This is the TPU-idiomatic
        # fix for per-dispatch fixed costs: 16 concurrent callers pay
        # ~1-2 dispatches, not 16 — and the stacked rows feed the MXU
        # together. Clamped to the window: at most window_size calls hold
        # credits concurrently, so a larger batch ceiling could never form.
        self.max_batch = max(1, min(max_batch, window_size))
        self._credits = Butex(window_size)
        self._cq = DeviceCompletionButex()
        # (bucket, mid_u32, the call's words as call_words keeps them,
        # cid_u32, pending, words of the answer); a device operand rides on
        # its pending call
        self._queue = deque()
        self._qlock = threading.Lock()
        self._draining = False
        self._dispatch_seq = itertools.count(1)
        # what the service keeps on the device between dispatches (None:
        # nothing) and whose turn it is to hold it. A service without
        # state, or whose step hands none back (it only reads what it
        # keeps), takes no turn: its dispatches launch side by side,
        # nothing is donated and a program call that raises loses nothing
        self._state = self.service.init_state(self.device)
        replaced = self._state is not None and self._step_replaces_state()
        self._state_turn = threading.Lock() if replaced else None
        # the service's step over (state, stacked rows, cids, mids), the
        # state donated; jit's per-shape cache gives one compiled program
        # per (batch, bucket) geometry — the fixed-block discipline. One
        # row alone runs as a batch of one and answers one frame. The two
        # are named for the trace: jit_step_row, jit_step_batch.
        # in_shardings: a dispatch hands these the host arrays, which
        # must land on self.device and not the default one, and must run
        # the executable a caller warmed with arrays already committed
        # there (without it each route compiles its own)
        on_device = SingleDeviceSharding(self.device)
        service = self.service

        def step_batch(state, rows, cids, mids):
            return service.dispatch_step(state, rows, cids, mids)

        def step_row(state, padded, cid_lo, mid):
            state, frames = step_batch(state, padded[None], cid_lo[None], mid[None])
            return state, frames[0]

        donated = (0,) if replaced else ()
        self._program = _StepProgram(
            self, jax.jit(step_row, in_shardings=on_device, donate_argnums=donated))
        self._batch_program = _StepProgram(
            self, jax.jit(step_batch, in_shardings=on_device, donate_argnums=donated))
        # the step of a call whose operand is a device array, where the
        # service has one: jit_step_tensor. The operand is committed to
        # ``device`` already and the answer stays there
        self._tensor_program = None
        if hasattr(service, "dispatch_tensor"):

            def step_tensor(state, row, operand, cid_lo, mid):
                state, answer, frame = service.dispatch_tensor(
                    state, row, operand, cid_lo, mid)
                return state, (answer, frame)

            self._tensor_program = _StepProgram(
                self,
                jax.jit(step_tensor, in_shardings=on_device, donate_argnums=donated))

    def _step_replaces_state(self) -> bool:
        """Whether the service's step hands a next state back, from its
        abstract result on one row of the narrowest bucket: nothing runs."""
        ids = jax.ShapeDtypeStruct((1,), np.uint32)
        rows = jax.ShapeDtypeStruct((1, MIN_BUCKET_WORDS), np.uint32)
        state, _frames = jax.eval_shape(
            self.service.dispatch_step, self._state, rows, ids, ids)
        return state is not None

    def _lose_state(self) -> None:
        """A dispatch failed once its program held the state: what the
        endpoint holds now was computed from it."""
        if self._state_turn is not None:
            with self._state_turn:
                self._state = _LOST

    # -- credit window (rdma_endpoint.h:176-195) ----------------------------

    def _acquire_credit(self, timeout: Optional[float]) -> bool:
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            c = self._credits.load()
            if c > 0 and self._credits.compare_exchange(c, c - 1):
                return True
            if c > 0:
                continue  # CAS race: retry
            remaining = None
            if deadline is not None:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    return False
            self._credits.wait(0, timeout=remaining)

    def _release_credit(self) -> None:
        self._credits.add(1)
        self._credits.wake(1)  # one credit frees one waiter, no herd

    @property
    def inflight(self) -> int:
        return self.window_size - self._credits.load()

    # -- call paths ---------------------------------------------------------

    def call_words(
        self,
        payload_words: np.ndarray,
        method_id: int = 0,
        correlation_id: int = 1,
        timeout: Optional[float] = 10.0,
        operand=None,
    ) -> _PendingCall:
        """Async: frame → HBM → dispatch fused step → watch completion.
        Returns a _PendingCall the caller can wait on; the credit is held
        until the response settles (the per-WR ack discipline).
        ``payload_words``: ``uint32`` words, or their bytes as ``uint8``
        (``call_bytes``, a length that is no multiple of 4). A read-only
        array is queued as it is and must stay unchanged until the call
        settles; a writeable one is copied here, so the caller may write to
        it once this returns. ``operand``: a ``jax.Array`` on ``device`` the
        call carries beside its words (at most ``MIN_BUCKET_WORDS`` of
        them; host bytes, or an array that lies elsewhere, are put there first:
        a fallback, counted); the answer is then ``pending.response_array``,
        on the device, and ``response_words`` the frame's payload."""
        pending = _PendingCall()
        pending.t_entry = pending.t_words = _time.monotonic_ns()
        if not self._acquire_credit(timeout):
            pending.error_code = ErrorCode.EOVERCROWDED
            pending.settle()
            return pending
        pending.t_credit = _time.monotonic_ns()
        # the words of the answer the caller is owed, and the bucket that
        # holds both the request and it
        words = payload_words
        if words.dtype not in (np.uint32, np.uint8):
            words = words.astype(np.uint32)
        sent = -(-words.nbytes // 4)
        try:
            if operand is None:
                n = -(-self.service.answer_bytes(method_id, 4 * sent) // 4)
                bucket = _bucket_words(max(1, n, sent))
            elif self._tensor_program is None or sent > MIN_BUCKET_WORDS:
                raise ValueError("no step for a device operand, or a frame too long")
            else:
                n = bucket = MIN_BUCKET_WORDS
                if isinstance(operand, jax.Array) and operand.devices() == {self.device}:
                    m_device_operands << 1
                else:  # host bytes, as words; or an array that lies elsewhere
                    if not isinstance(operand, (jax.Array, np.ndarray)):
                        operand = np.frombuffer(operand, dtype=np.uint32)
                    operand = jax.device_put(operand, self.device)
                    m_device_operand_fallbacks << 1
        except ValueError:
            # oversized payload: the credit MUST come back (a leak here
            # shrinks the window forever) and the caller gets the settled-
            # pending contract, not a raw exception
            self._release_credit()
            pending.error_code = ErrorCode.EREQUEST
            pending.settle()
            return pending
        # no padded row is built: the dispatch writes the words into its
        # operand, or hands them over as they lie. They must not change
        # under it, and they are read as one aligned run of bytes
        flags = words.flags
        if words is payload_words and (
            flags.writeable or not (flags.c_contiguous and flags.aligned)
        ):
            words = words.copy()
        pending.operand = operand
        pending.t_enqueued = _time.monotonic_ns()
        with self._qlock:
            self._queue.append(
                (
                    bucket,
                    np.uint32(method_id),
                    words,
                    np.uint32(correlation_id & 0xFFFFFFFF),
                    pending,
                    n,
                )
            )
            if self._draining:
                return pending  # the live drainer will pick it up
            self._draining = True
        # a DEDICATED thread, not a worker-pool fiber: handler fibers
        # block waiting on these dispatches, so a saturated pool could
        # strand the drainer behind the very callers it must unblock
        threading.Thread(
            target=self._drain, name="tbrpc-dev-batch", daemon=True
        ).start()
        return pending

    # -- the batching drainer (single-drainer, like the link's _kick) -------

    def _drain(self) -> None:
        while True:
            with self._qlock:
                if not self._queue:
                    self._draining = False
                    return
                # a batch is the FIFO prefix of the queue, stacked at the
                # widest bucket in it (rows x bucket = program identity;
                # mids/cids are per-row arguments). Calls of one bucket
                # stack whatever their size; once buckets differ the
                # stacked array stays under MAX_STACKED_WORDS. A prefix
                # only: nothing is reordered, nothing can starve. A call with
                # a device operand rides alone
                batch = [self._queue.popleft()]
                bucket, mixed = batch[0][0], False
                while (
                    batch[0][4].operand is None
                    and self._queue
                    and len(batch) < self.max_batch
                    and self._queue[0][4].operand is None
                ):
                    joining = self._queue[0][0]
                    if mixed or joining != bucket:
                        widest = max(bucket, joining)
                        stacked = _pad_rows(len(batch) + 1) * widest
                        if stacked > MAX_STACKED_WORDS:
                            break
                        bucket, mixed = widest, True
                    batch.append(self._queue.popleft())
                more = bool(self._queue)
            if more:
                # staggered arrivals: submit THIS batch on its own thread
                # so the next batch's host→device submission overlaps it
                # — a single submitting thread
                # would serialize exactly the fixed costs the window
                # exists to overlap (dedicated threads for the same
                # reason as _drain itself)
                threading.Thread(
                    target=self._dispatch_batch,
                    args=(bucket, batch),
                    name="tbrpc-dev-batch-tx",
                    daemon=True,
                ).start()
            else:
                self._dispatch_batch(bucket, batch)

    def _dispatch_batch(self, bucket: int, batch: list) -> None:
        """Run ``batch`` as one program execution over rows of ``bucket``
        words, the widest bucket among its entries."""
        b = len(batch)
        # pad rows are zero frames whose (flagged-garbage) response rows
        # are simply ignored
        bpad = _pad_rows(b)
        dispatch = _Dispatch(
            next(self._dispatch_seq), b, bpad, bucket,
            sum(entry[0] != bucket for entry in batch),
        )
        cids = np.zeros(bpad, dtype=np.uint32)
        mids = np.zeros(bpad, dtype=np.uint32)
        for i, (_, mid, _words, cid, pending, _n) in enumerate(batch):
            cids[i] = cid
            mids[i] = mid
            pending.dispatch = dispatch
        operand = batch[0][4].operand
        try:
            alone = batch[0][2]
            if bpad == 1 and alone.dtype == np.uint32 and alone.size == bucket:
                # a call alone that fills its row: the row is word for word
                # the request, which the entry keeps alive until it settles
                rows, dispatch.borrowed = alone, 1
            else:
                rows = np.empty((bpad, bucket), dtype=np.uint32)
                dispatch.zeroed_words = _stack_rows(
                    rows, [entry[2] for entry in batch])
                if bpad == 1:
                    rows = rows[0]
            dispatch.t_stacked, dispatch.c_stacked = clocks(dispatch.timed)
            # the launch is the program call alone: it stages the host
            # arrays itself. rows, cids and mids are not written again (the
            # runtime may still be reading them)
            if operand is not None:  # (the answer on the device, one frame)
                response = self._tensor_program.run(
                    (rows, operand, cids[0], mids[0]), dispatch)
            elif bpad == 1:  # a call alone: the one-row program, one frame back
                response = self._program(rows, cids[0], mids[0], dispatch)
            else:
                response = self._batch_program(rows, cids, mids, dispatch)
        except Exception as e:  # dispatch failed: settle the whole batch
            for _, _mid, _words, _cid, pending, _n in batch:
                self._release_credit()
                pending.error = e
                pending.error_code = ErrorCode.EINTERNAL
                pending.settle()
            return
        dispatch.t_launched, dispatch.c_launched = clocks(dispatch.timed)

        def on_complete(arrays, error, _batch=batch, _single=(bpad == 1)):
            try:
                host = None
                if error is None:
                    # of a device operand's answer the frame alone is read
                    frames = arrays if operand is None else arrays[1]
                    host = np.asarray(jax.device_get(frames))
                    if _single:
                        host = host[None]
            except Exception as e:  # noqa: BLE001 — fetch failed
                error, host = e, None
            dispatch.t_readback, dispatch.c_readback = clocks(dispatch.timed)
            for i, (_, _mid, _words, _cid, pending, n) in enumerate(_batch):
                try:
                    if error is not None:
                        pending.error = error
                        pending.error_code = ErrorCode.EINTERNAL
                    else:
                        _, words, err = _parse_response(host[i])
                        pending.error_code = int(err)
                        pending.response_words = words[:n]
                        if operand is not None:
                            pending.response_array = arrays[0]
                    device_latency << (
                        _time.monotonic_ns() - pending.t_credit
                    ) / 1e3
                except Exception as e:  # noqa: BLE001 — parse failed
                    pending.error = e
                    pending.error_code = ErrorCode.EINTERNAL
                    pending.response_words = None
                finally:
                    self._release_credit()
                    pending.settle()
            if error is not None:
                self._lose_state()  # what the next state was computed from
            # after the callers are awake: this thread is a pooled
            # watcher, so the adders keep one agent each (a drain thread
            # lives for a run of dispatches, a -tx thread for one)
            m_dispatches << 1
            m_dispatch_rows << dispatch.rows
            m_dispatch_pad_rows << dispatch.pad_rows
            m_dispatch_words << dispatch.pad_rows * dispatch.bucket
            m_dispatch_widened_rows << dispatch.widened_rows
            m_dispatch_zeroed_words << dispatch.zeroed_words
            m_dispatch_borrowed << dispatch.borrowed
            if host is not None:
                self.service.account(mids[:b], host[:b])

        self._cq.watch(
            response, on_complete=on_complete, stamps=dispatch.watcher
        )

    def call_bytes(
        self,
        payload: bytes,
        method_id: int = 0,
        correlation_id: int = 1,
        timeout: Optional[float] = 10.0,
        cntl=None,
    ) -> Tuple[int, bytes]:
        """Sync byte adapter: see the bytes as words, run, cut the response at the
        byte length the service says this method answers such a request
        with (``answer_bytes``; an echo's is the request's own). ``cntl``:
        the server-side controller of the RPC this call serves, if any —
        its arrival stamp gives the ingress time, and its rpcz span, if
        sampled, gets the call's timeline as annotations."""
        t_entry = _time.monotonic_ns()
        nbytes = len(payload)
        # the request's own memory, read-only and kept alive by the view
        words = np.frombuffer(payload, dtype=np.uint8 if nbytes % 4 else np.uint32)
        # ONE deadline budget across credit-wait + completion-wait
        deadline = None if timeout is None else _time.monotonic() + timeout
        pending = self.call_words(
            words, method_id=method_id, correlation_id=correlation_id,
            timeout=timeout,
        )
        pending.t_entry = t_entry
        return self._settled(
            pending, deadline, cntl, b"",
            lambda: pending.response_words.tobytes()[
                : self.service.answer_bytes(method_id, nbytes)])

    @staticmethod
    def _settled(pending, deadline, cntl, nothing, answer) -> tuple:
        """The sync adapters' way out: wait what is left of the deadline,
        take ``answer()`` of a call that ended well (``nothing`` otherwise),
        stamp the exit and leave the call's row."""
        remaining = None
        if deadline is not None:
            remaining = max(0.0, deadline - _time.monotonic())
        if not pending.wait(remaining):
            return ErrorCode.ERPCTIMEDOUT, nothing
        out = nothing if pending.error_code else answer()
        pending.t_exit = _time.monotonic_ns()
        if pending.completed():
            after_send = getattr(cntl, "_after_send", None)
            if after_send is None:
                _record(pending, cntl, None)
            else:
                # the server calls back once the response is written, so
                # the way out is on the call's row too
                after_send.append(partial(_record, pending, cntl))
        return pending.error_code, out

    def call_tensor(
        self,
        payload: bytes,
        tensor,
        method_id: int = 0,
        correlation_id: int = 1,
        timeout: Optional[float] = 10.0,
        cntl=None,
    ) -> Tuple[int, object]:
        """Sync adapter for a call that carries a tensor: ``payload`` is the
        request's frame (at most 256 B), ``tensor`` its operand. A
        ``jax.Array`` is answered ``(code, jax.Array on device)`` and never
        touches the host; host bytes (a caller without a lane) are put on
        the device, and the answer is read back as bytes: the fallback.
        Stamps and rows as ``call_bytes``."""
        t_entry = _time.monotonic_ns()
        deadline = None if timeout is None else _time.monotonic() + timeout
        as_bytes = not isinstance(tensor, jax.Array)
        words = np.frombuffer(
            payload, dtype=np.uint8 if len(payload) % 4 else np.uint32)
        pending = self.call_words(
            words, method_id=method_id, correlation_id=correlation_id,
            timeout=timeout, operand=tensor,
        )
        pending.t_entry = t_entry

        def answer():
            array = pending.response_array
            return np.asarray(array).tobytes() if as_bytes else array

        return self._settled(pending, deadline, cntl, None, answer)

    def warm_tensor(self, shape: tuple, dtype=np.uint32, method_id: int = 0) -> None:
        """Compile the step for a device operand of ``shape`` and run it
        once on a zero frame and a zero operand (answered with an error,
        without a product): no live call of that shape compiles."""
        operand = jax.device_put(np.zeros(shape, dtype=dtype), self.device)
        row = np.zeros(MIN_BUCKET_WORDS, dtype=np.uint32)
        jax.block_until_ready(self._tensor_program.run(
            (row, operand, np.uint32(1), np.uint32(method_id))))

    def warm(
        self, payload_bytes: int, timeout: float = 300.0, method_id: int = 0
    ) -> None:
        """Compile every (batch, bucket) geometry a ``method_id`` request
        of this payload size can hit — single + each power-of-two batch up
        to max_batch — so a timed or latency-sensitive workload never pays
        XLA compilation mid-flight. Batch formation
        depends on arrival timing, so a concurrency burst does NOT
        reliably warm the larger geometries; this does. The rows it runs
        are a dispatch's pad rows (zero payload, method 0), so a service
        with state must answer those without touching it."""
        n_words = max(
            1, (payload_bytes + 3) // 4,
            (self.service.answer_bytes(method_id, payload_bytes) + 3) // 4,
        )
        bucket = _bucket_words(n_words)
        # host-typed arguments, as _dispatch_batch hands them
        row = np.zeros(bucket, dtype=np.uint32)
        outs = [self._program(row, np.uint32(1), np.uint32(0))]
        b = 2
        while b <= self.max_batch:
            ids = np.zeros(b, dtype=np.uint32)
            outs.append(
                self._batch_program(
                    np.zeros((b, bucket), dtype=np.uint32), ids, ids
                )
            )
            b <<= 1
        jax.block_until_ready(outs)

    # -- host-plane integration --------------------------------------------

    def server_handler(self, method_id: int = 0, timeout: float = 60.0):
        """An ordinary Server handler that delegates to this endpoint: the
        request payload goes to HBM, the fused step runs, the response
        comes back — RPC in, device compute, RPC out. ``timeout`` budgets
        credit-wait + queued-batch dispatch + completion (under bursts a
        call may ride the second or third micro-batch). A request whose
        attachment is a ``jax.Array`` (a unary tensor call over a link's
        lane) is a call with a device operand, where the service takes
        one: the answer is ``cntl.response_attachment``, a ``jax.Array``."""

        def handler(cntl, request: bytes) -> bytes:
            tensor = cntl.request_attachment
            if self._tensor_program is not None and (
                isinstance(tensor, jax.Array) or len(tensor)
            ):
                # the attachment is the operand, the request its frame; the
                # answer goes back as the attachment, where it lies
                code, answer = self.call_tensor(
                    request, tensor, method_id=method_id,
                    correlation_id=cntl.call_id or 1, timeout=timeout, cntl=cntl,
                )
                if code:
                    cntl.set_failed(code, f"device call failed ({code})")
                else:
                    cntl.response_attachment = answer
                return b""
            code, out = self.call_bytes(
                request,
                method_id=method_id,
                correlation_id=cntl.call_id or 1,
                timeout=timeout,
                cntl=cntl,
            )
            if code:
                cntl.set_failed(code, f"device call failed ({code})")
                return b""
            return out

        return handler


def _parse_response(host_frame: np.ndarray):
    """Host-side parse of a device response frame (the 8-word header layout
    of ops/framing.py, read with numpy — no second device round-trip).
    Word 7 is the error code on responses."""
    header = host_frame[: framing.HEADER_WORDS]
    payload = host_frame[framing.HEADER_WORDS :]
    return header, payload, header[7]
