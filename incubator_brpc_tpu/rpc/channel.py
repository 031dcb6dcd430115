"""Channel — the client endpoint (reference src/brpc/channel.cpp:285
CallMethod, controller.cpp:545-676 OnVersionedRPCReturned / 941 IssueRPC).

Call flow (mirrors SURVEY.md §3.1):
  call_method
    ├ create ranged call id (2 + max_retry versions, channel.cpp:307)
    ├ register timeout / backup timers on the TimerThread
    ├ _issue_rpc: pick socket (single server or LB), pack, Socket.write
    │   (write failure → CallIdSpace.error → retry arbitration)
    └ sync: join the call id   (async: done runs when the id is destroyed)

  response path (reader fiber): tbus_std.process_response
    └ lock call id → _on_rpc_returned: retry / backup-win / end
      EndRPC: cancel timers, unlock_and_destroy (wakes joiners), run done.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import Callable, Optional, Union

from incubator_brpc_tpu import protocol as proto_pkg
from incubator_brpc_tpu.protocol import compress as compress_mod
from incubator_brpc_tpu.protocol.tbus_std import (
    FLAG_RESPONSE,
    Meta,
    ParsedFrame,
    pack_frame_iobuf,
)
from incubator_brpc_tpu.rpc.controller import (
    HOST_BYTES,
    RETRIABLE,
    Attachment,
    Controller,
)
from incubator_brpc_tpu.runtime.correlation_id import call_id_space
from incubator_brpc_tpu.runtime.timer_thread import global_timer_thread
from incubator_brpc_tpu.runtime.worker_pool import global_worker_pool
from incubator_brpc_tpu.transport import device_link
from incubator_brpc_tpu.transport.messenger import InputMessenger
from incubator_brpc_tpu.transport.socket_map import SocketMap
from incubator_brpc_tpu.bvar import Adder, PassiveStatus, RecorderFeed
from incubator_brpc_tpu.utils.endpoint import EndPoint, str2endpoint
from incubator_brpc_tpu.utils.flags import define_flag, get_flag
from incubator_brpc_tpu.utils.status import ErrorCode, berror

logger = logging.getLogger(__name__)

_client_messenger = InputMessenger()
_client_socket_map = SocketMap(messenger=_client_messenger)


def start_cancel(call_id: int) -> None:
    """Cancel an in-flight RPC by its call id from ANY thread — the
    reference's brpc::StartCancel(CallId) (controller.cpp:699, routed
    through bthread_id_error): the id's error hook runs under the id
    lock, fails the call with ECANCELED (never retried), wakes joiners
    and runs the done callback. A no-op once the call has settled (the
    versioned id is dead and the error call is dropped)."""
    call_id_space.error(call_id, ErrorCode.ECANCELED, "canceled by caller")


class NoServerError(ConnectionError):
    """LB selection failed: every candidate excluded or the cluster is
    empty (reference ExcludedServers -> EHOSTDOWN)."""


def _recycle_when_drained(sock) -> None:
    """Close once queued writes flushed: recycling immediately would drop
    frames still on the MPSC queue (e.g. a stream's CLOSE)."""
    from incubator_brpc_tpu.transport.sock import when_drained

    when_drained(sock, lambda s: s.recycle())


def _track_inflight(sock, cid: int) -> None:
    """Record a written-but-unanswered correlation id on its connection so
    connection death fails the call NOW, not at its deadline (the
    reference fails every id parked on a Socket at SetFailed — the
    per-socket id wait list). Stale entries (timed-out calls whose
    response never came) are dropped when the id no longer locks.

    Error delivery is CLAIM-based: whoever atomically removes the cid
    from the set (response path, EndRPC, a write's on_error, or the
    socket-failure sweep) owns it — a request sitting in the write queue
    at set_failed would otherwise be errored twice (the queue's on_error
    AND the sweep), costing a phantom retry or a duplicate on the wire."""
    ctx = sock.context
    cids = ctx.get("_inflight_cids")
    if cids is None:
        cids = ctx.setdefault("_inflight_cids", set())

        def _fail_inflight(sk):
            from incubator_brpc_tpu.runtime.worker_pool import (
                global_worker_pool as _pool,
            )

            pending = sk.context.get("_inflight_cids")
            while pending:
                try:
                    c = pending.pop()  # atomic claim under the GIL
                except KeyError:
                    break
                _pool().spawn(
                    call_id_space.error,
                    c,
                    ErrorCode.EFAILEDSOCKET,
                    f"connection to {sk.remote} failed with the call in flight",
                )

        # fabriclint: allow(lifecycle-callback) closure reads only the failing socket's own context, hooked once per socket, dies with it — pins no channel state
        sock.on_failed.append(_fail_inflight)
    cids.add(cid)


def _claim_inflight(sock, cid: int) -> bool:
    """True iff this caller atomically removed the cid (and may deliver
    its error); False = another path already owns it."""
    cids = sock.context.get("_inflight_cids")
    if cids is None:
        return True  # never tracked (pre-track failure): caller owns it
    try:
        cids.remove(cid)
        return True
    except KeyError:
        return False


def process_response(sock, frame: ParsedFrame) -> None:
    """tbus_std Protocol.process_response hook: route a response frame to
    its in-flight RPC via the correlation id (baidu_rpc_protocol.cpp:543).

    On a reactor thread (inline reads) a contended id — a concurrent
    timeout/backup holder, possibly mid-reconnect — must not park the
    reactor: the blocking lock is deferred to a pool fiber."""
    from incubator_brpc_tpu.runtime.correlation_id import EBUSY
    from incubator_brpc_tpu.transport.event_dispatcher import on_reactor_thread

    cid = frame.correlation_id
    cids = sock.context.get("_inflight_cids")
    if cids is not None:
        cids.discard(cid)
    on_reactor = on_reactor_thread()
    rc, cntl = call_id_space.lock(cid, nowait=on_reactor)
    if rc == EBUSY:
        global_worker_pool().spawn(_process_response_blocking, sock, frame)
        return
    if rc != 0 or cntl is None:
        return  # stale/duplicate response after EndRPC: drop
    channel = cntl._channel
    if channel is None:
        call_id_space.unlock(cid)
        return
    channel._on_rpc_returned(cntl, frame, sock)


def _process_response_blocking(sock, frame: ParsedFrame) -> None:
    cid = frame.correlation_id
    rc, cntl = call_id_space.lock(cid)
    if rc != 0 or cntl is None:
        return
    channel = cntl._channel
    if channel is None:
        call_id_space.unlock(cid)
        return
    channel._on_rpc_returned(cntl, frame, sock)


# bind the live hook (registration itself happens at protocol import)
proto_pkg.TBUS_STD.process_response = process_response


# -- retry budget --------------------------------------------------------------
#
# The SRE retry-budget discipline: retries are only safe while they are a
# small fraction of traffic — once a backend browns out, per-call retry
# caps (max_retry) still multiply offered load by (1 + max_retry), and
# the retry storm finishes the backend off.  Every Channel therefore owns
# a token bucket: each issued call deposits ``retry_budget_ratio``
# tokens, each retry withdraws one, and an empty bucket makes the call
# FAIL FAST with the original error instead of retrying.  Steady-state
# retry volume is thus capped at ~ratio of call volume, while the bucket
# cap still absorbs short error bursts at full retry fidelity.

define_flag(
    "retry_budget_ratio",
    0.1,
    "per-channel retry budget (SRE-style): each issued call deposits "
    "this many retry tokens and each retry attempt withdraws one, so "
    "sustained retry volume is capped at this fraction of call volume; "
    "an exhausted budget fails the call fast with the original error "
    "instead of amplifying a brownout into a retry storm; 0 disables",
    lambda v: 0 <= v <= 1,
)

# burst allowance: a full bucket funds this many back-to-back retries
# before the ratio gates (and is also the bucket's starting balance, so
# young channels are not penalized for their first errors)
_RETRY_BUDGET_CAP = 50.0

# codes that never draw from the budget: deliberate, non-amplifying
# control signals — a propagated deadline died (EDEADLINE), a collective
# session aborted cooperatively (ESESSION), admission control shed the
# request (ELIMIT).  None of them is in the default RETRIABLE set, but a
# custom retry_policy may retry them, and that decision must not burn
# budget meant for connectivity failures.
RETRY_BUDGET_EXEMPT = frozenset(
    {ErrorCode.EDEADLINE, ErrorCode.ESESSION, ErrorCode.ELIMIT}
)

retry_budget_exhausted = Adder(name="retry_budget_exhausted")
_live_budgets = weakref.WeakSet()
# aggregate balance across live channels — budget state in /vars (the
# per-channel value is intentionally not a bvar: channels are many and
# short-lived; the aggregate plus the exhaustion counter is the signal)
retry_budget_tokens = PassiveStatus(
    lambda: round(sum(b.balance() for b in list(_live_budgets)), 2),
    name="retry_budget_tokens",
)


class RetryBudget:
    """Token-bucket retry budget for one channel (see module note)."""

    def __init__(self, ratio: float):
        self._ratio = float(ratio)
        self._tokens = _RETRY_BUDGET_CAP
        self._lock = threading.Lock()
        if self._ratio > 0:
            _live_budgets.add(self)

    def on_call(self) -> None:
        """One issued call funds ``ratio`` of a future retry."""
        if self._ratio <= 0:
            return
        with self._lock:
            self._tokens = min(_RETRY_BUDGET_CAP, self._tokens + self._ratio)

    def acquire(self, code: int) -> bool:
        """May one retry for this error run? Exempt codes never draw."""
        if self._ratio <= 0 or code in RETRY_BUDGET_EXEMPT:
            return True
        with self._lock:
            if self._tokens >= 1.0:
                self._tokens -= 1.0
                return True
        retry_budget_exhausted << 1
        return False

    def balance(self) -> float:
        with self._lock:
            return self._tokens


class ChannelOptions:
    def __init__(
        self,
        timeout_ms: float = Controller.DEFAULT_TIMEOUT_MS,
        max_retry: int = Controller.DEFAULT_MAX_RETRY,
        backup_request_ms: float = -1,
        connect_timeout: float = 5.0,
        protocol: str = "tbus_std",
        auth=None,
        connection_type: str = "single",
        transport: str = "tcp",
        device_index: int = 0,
        link_slot_words: int = 16384,
        link_window: int = 8,
        link_ack_mode: str = "local",
        link_controller: str = "single",
        native_plane: bool = False,
        ssl_context=None,
        ssl_server_hostname=None,
        retry_policy=None,
    ):
        self.timeout_ms = timeout_ms
        self.max_retry = max_retry
        self.backup_request_ms = backup_request_ms
        self.connect_timeout = connect_timeout
        self.protocol = protocol
        self.auth = auth  # Authenticator (rpc/auth.py)
        # "single" (shared main socket), "pooled" (exclusive connection per
        # in-flight call, parked for reuse), "short" (fresh connection,
        # closed after the call) — reference AdaptiveConnectionType
        if connection_type not in ("single", "pooled", "short"):
            raise ValueError(f"unknown connection_type {connection_type!r}")
        self.connection_type = connection_type
        # "tcp" (host sockets) or "tpu" (two-party device link: handshake
        # over the host socket, frames over the device plane — the
        # reference's ChannelOptions.use_rdma slot, channel.h)
        if transport not in ("tcp", "tpu"):
            raise ValueError(f"unknown transport {transport!r}")
        if transport == "tpu" and connection_type != "single":
            raise ValueError("transport='tpu' supports connection_type='single'")
        self.transport = transport
        self.device_index = device_index
        self.link_slot_words = link_slot_words
        self.link_window = link_window
        # 'local' | 'wire': how the link's credit window learns about
        # drained steps (wire = the multi-controller piggybacked-ack flow)
        self.link_ack_mode = link_ack_mode
        # 'single' (both link halves in this process — the default, the
        # in-process JAX model) | 'multi' (the peer is a DIFFERENT process
        # holding its own device: lockstep SPMD dispatch coordinated over
        # a control stream, transport/mc_link.py; requires
        # jax.distributed.initialize on both hosts)
        if link_controller not in ("single", "multi"):
            raise ValueError(f"unknown link_controller {link_controller!r}")
        self.link_controller = link_controller
        # Route eligible sync calls through the native client (src/tbnet):
        # pack/write/read/match in C++ with the GIL released, one shared
        # connection with an elected completion-pump reader. Calls that
        # need Python-plane features (streams, backup, auth, compression,
        # LB targets) use the regular path, call by call. Where the
        # library cannot be had at all, init() says so once.
        self.native_plane = native_plane
        # ssl.SSLContext for TLS to the server(s) (reference
        # ChannelOptions.ssl_options). TLS sockets pump ciphertext through
        # the same reactor; the native fast path is skipped (no TLS stack
        # in src/tbnet).
        self.ssl_context = ssl_context
        self.ssl_server_hostname = ssl_server_hostname
        # fn(cntl) -> bool: should THIS failed attempt retry? (reference
        # RetryPolicy::DoRetry, retry_policy.h:26 — cntl.error_code is the
        # attempt's error; None = the default retriable-code set). Retry
        # budget (max_retry) is enforced regardless.
        self.retry_policy = retry_policy


class Channel:
    """Client channel to a single server or (via ``lb`` + naming) a set.

    ``init()`` accepts an "ip:port" / EndPoint for a single server, or a
    naming url ("list://a:1,b:2", "file://path") plus a load-balancer name
    — the reference's dual Init (channel.cpp:201-273).
    """

    def __init__(self):
        self._options = ChannelOptions()
        self._single_server: Optional[EndPoint] = None
        self._lb = None  # LoadBalancerWithNaming (lb/__init__.py), task #5
        self._socket_map = _client_socket_map
        self._init_done = False
        self._retry_budget: Optional[RetryBudget] = None
        self._device_sock = None  # transport="tpu": last-used link (the
        # links themselves live in the process-wide DeviceLinkMap)
        self._native_ch = None  # NativeClientChannel (lazy; native_plane)
        self._native_lock = threading.Lock()
        self._native_tls = threading.local()  # pooled: one conn per thread

    def init(
        self,
        target: Union[str, EndPoint],
        lb_name: str = "",
        options: Optional[ChannelOptions] = None,
    ) -> bool:
        if options is not None:
            self._options = options
        if isinstance(target, EndPoint):
            self._single_server = target
        elif "://" in str(target) and not str(target).startswith("unix://"):
            # transport='tpu' works for LB targets too: the LB picks the
            # peer, the DeviceLinkMap resolves it to an established link
            # (one per peer device — the N-party fabric star)
            from incubator_brpc_tpu.lb import LoadBalancerWithNaming

            self._lb = LoadBalancerWithNaming(
                str(target),
                lb_name or "rr",
                socket_map=self._socket_map,
                key_tag=self._auth_key_tag(),
                conn_kwargs=self._conn_kwargs(),
            )
            if not self._lb.start():
                return False
        else:
            self._single_server = str2endpoint(str(target))
        self._retry_budget = RetryBudget(float(get_flag("retry_budget_ratio")))
        self._init_done = True
        if self._options.native_plane:
            from incubator_brpc_tpu.transport import native_plane as np_mod

            if not np_mod.NET_AVAILABLE:
                # every call then takes the Python plane and gets the same
                # answers: only this line tells which plane was measured
                logger.warning(
                    "Channel(native_plane=True) to %s cannot be honoured: "
                    "libtbutil.so could not be built or loaded; calls take "
                    "the Python plane", target,
                )
        return True

    def init_with_lb(self, lb, options: Optional[ChannelOptions] = None) -> bool:
        """Init with a pre-built LoadBalancerWithNaming-compatible object
        (select_server/feedback/start/stop) — the seam PartitionChannel uses
        to feed each sub-channel a filtered server view
        (partition_channel.cpp builds sub-channels the same way)."""
        if options is not None:
            self._options = options
        if not lb.start():
            return False
        self._lb = lb
        self._retry_budget = RetryBudget(float(get_flag("retry_budget_ratio")))
        self._init_done = True
        return True

    # -- public call surface -------------------------------------------------

    def call_method(
        self,
        service: str,
        method: str,
        request: bytes,
        cntl: Optional[Controller] = None,
        done: Optional[Callable[[Controller], None]] = None,
        attachment: Attachment = b"",
        request_stream=None,
    ) -> Controller:
        """The CallMethod entry (channel.cpp:285). Synchronous when ``done``
        is None (joins the call id); asynchronous otherwise.

        ``attachment`` is host bytes or a ``jax.Array``. What becomes of an
        array follows from the socket the call goes out on, as for a
        stream's message (``device_link.array_carrier``; nothing configures
        it): over a ``transport="tpu"`` link between two devices it crosses
        the link's lane as it lies, the handler reads
        ``cntl.request_attachment`` as a ``jax.Array`` of that shape and
        dtype on its own device, and what it sets as
        ``cntl.response_attachment``, bytes or an array on its device,
        comes back the same way: no byte of either lies in host memory. A
        host socket or a link on one shared device sends the array's bytes;
        an array that is not whole on the link's client device, has no
        element, or was deleted or donated fails the call with ``EINVAL``
        and nothing is sent, as does any array over a multi-controller
        link. The controller holds the array until the call ends; the
        caller may not write into, donate or delete it before (a retry or a
        backup request sends it again)."""
        assert self._init_done, "Channel.init() not called"
        entered_ns = 0 if isinstance(attachment, HOST_BYTES) else time.monotonic_ns()
        if self._retry_budget is not None:
            self._retry_budget.on_call()
        if cntl is None:
            cntl = Controller(
                timeout_ms=self._options.timeout_ms,
                max_retry=self._options.max_retry,
                backup_request_ms=self._options.backup_request_ms,
            )
        cntl._channel = self
        cntl._service = service
        cntl._method = method
        cntl._request_payload = request
        cntl.request_attachment = attachment
        if entered_ns:
            cntl._unary = [entered_ns, RecorderFeed.MISSING, RecorderFeed.MISSING]
        cntl._done = done
        if request_stream is not None:
            cntl._request_stream = request_stream
        cntl._mark_start()

        # deadline propagation (reference RpcRequestMeta.timeout_ms): a
        # call issued inside a server handler inherits what is LEFT of the
        # caller's propagated budget when that is tighter than this call's
        # own timeout — budgets only shrink across hops. An already-spent
        # budget fails fast with EDEADLINE: no wire traffic for work the
        # edge caller has given up on.
        from incubator_brpc_tpu.rpc.deadline import current_deadline

        _ambient = current_deadline()
        if _ambient is not None:
            if not cntl._deadline or _ambient < cntl._deadline:
                cntl._deadline = _ambient
                cntl.timeout_ms = max(
                    0.0, (_ambient - cntl._start_ts) * 1000.0
                )
            if cntl._deadline <= cntl._start_ts:
                cntl.set_failed(
                    ErrorCode.EDEADLINE,
                    "propagated deadline already expired",
                )
                cntl._mark_end()
                if done is not None:
                    done(cntl)
                return cntl

        # native fast path: a sync, stream-less, unauthenticated,
        # uncompressed call to a single TCP server rides src/tbnet end to
        # end (C++ pack/write/pump; correlation handled by the native
        # channel's own cid space). Transport failures fall through to the
        # regular path, whose dial/retry machinery owns recovery.
        if (
            done is None
            and request_stream is None
            and self._options.native_plane
            and cntl._unary is None
            and self._native_eligible(cntl)
            and self._native_call(cntl, service, method, request, attachment)
        ):
            return cntl

        # one id covers the first send + every retry/backup
        # (bthread_id_create_ranged with 2 + max_retry, channel.cpp:307)
        cid = call_id_space.create(
            data=cntl,
            on_error=self._handle_id_error,
            version_range=2 + max(0, cntl.max_retry),
        )
        cntl.call_id = cid

        from incubator_brpc_tpu.builtin.rpcz import start_client_span

        cntl._span = start_client_span(cntl)

        timer = global_timer_thread()
        pool = global_worker_pool()
        # Sync calls without backup requests enforce their deadline from
        # the caller's own wait loop (_sync_wait) — no timer round trip.
        # Async calls and backup-enabled calls need the TimerThread.
        needs_timeout_timer = done is not None or (
            cntl.backup_request_ms and cntl.backup_request_ms > 0
        )
        if needs_timeout_timer and cntl.timeout_ms is not None and cntl.timeout_ms > 0:
            cntl._timer_ids.append(
                timer.schedule(
                    lambda: pool.spawn(
                        call_id_space.error,
                        cid,
                        ErrorCode.ERPCTIMEDOUT,
                        f"deadline {cntl.timeout_ms} ms exceeded",
                    ),
                    delay=cntl.timeout_ms / 1000.0,
                )
            )
        if cntl.backup_request_ms and cntl.backup_request_ms > 0:
            cntl._timer_ids.append(
                timer.schedule(
                    lambda: pool.spawn(
                        call_id_space.error,
                        cid,
                        ErrorCode.EBACKUPREQUEST,
                        "",
                    ),
                    delay=cntl.backup_request_ms / 1000.0,
                )
            )

        if done is None:
            cntl._want_poll = True
        rc, _ = call_id_space.lock(cid)
        if rc == 0:
            self._issue_rpc(cntl)
            call_id_space.unlock(cid)
        # Only the initial caller-thread issue may pre-claim read ownership:
        # a later retry on a pool thread claiming a socket after the sync
        # caller stopped polling would leave a connection nobody reads.
        cntl._want_poll = False

        if done is None:
            self._sync_wait(cntl, cid)
            if cntl._unary is not None:
                self._unary_row(cntl)
        return cntl

    @staticmethod
    def _unary_row(cntl: Controller) -> None:
        """The caller's row of a call whose request crossed a link's lane,
        once the caller runs again (an asynchronous call: before ``done``);
        a failed call leaves none."""
        stamps = cntl._unary
        if stamps[1] < 0 or cntl.failed():
            return
        link = getattr(cntl._sent_sockets[-1], "link", None)
        if link is not None and link.unary_calls is not None:
            link.unary_calls.rows.append((*stamps, time.monotonic_ns()))

    def _sync_wait(self, cntl: Controller, cid: int) -> None:
        """Synchronous completion. When the request's socket is otherwise
        idle, the caller becomes its reader and processes the response on
        its OWN thread — a sync round trip then involves zero reactor or
        fiber wakeups on the client (Socket.poll_and_process; the reference
        parks on the id butex instead because bthread wakes are ~free,
        bthread_id_join). Falls back to the plain join when another thread
        is already reading the socket."""
        import time as _time

        from incubator_brpc_tpu.transport.sock import CONNECTED as _UP

        deadline = cntl._deadline or None
        # whether a TimerThread entry owns this call's deadline (see
        # call_method); if not, THIS loop delivers ERPCTIMEDOUT
        has_timer = bool(cntl._timer_ids)

        def _deadline_hit() -> bool:
            if has_timer or deadline is None or _time.monotonic() < deadline:
                return False
            call_id_space.error(
                cid, ErrorCode.ERPCTIMEDOUT, f"deadline {cntl.timeout_ms} ms exceeded"
            )
            return True

        def _join_with_deadline() -> None:
            # the deadline stays enforced even with no TimerThread entry:
            # a dead server that never answers must still yield
            # ERPCTIMEDOUT, not an unbounded park
            while call_id_space.valid(cid):
                remaining = None if deadline is None else deadline - _time.monotonic()
                if call_id_space.join(cid, timeout=remaining):
                    return
                if _deadline_hit():
                    break
            call_id_space.join(cid)

        sock = cntl._poll_owned
        if sock is None:
            sock = cntl._sent_sockets[-1] if cntl._sent_sockets else None
            if sock is None or not sock.try_read_ownership():
                _join_with_deadline()
                return
        cntl._poll_sock = sock
        try:
            while call_id_space.valid(cid):
                if _deadline_hit():
                    break
                if sock.state != _UP:
                    break
                # 0.5s safety tick: a missed kick (no eventfd) or a
                # response rerouted to another socket (retry/backup) is
                # picked up by the next valid() check
                t = 0.5
                if deadline is not None:
                    t = min(t, max(0.001, deadline - _time.monotonic()))
                if not sock.poll_and_process(t):
                    break
        finally:
            cntl._poll_sock = None
            cntl._poll_owned = None
            sock.release_read_ownership()
        _join_with_deadline()

    # convenience alias
    call = call_method

    # -- native fast path ----------------------------------------------------

    def _native_eligible(self, cntl: Controller) -> bool:
        from incubator_brpc_tpu.transport.native_plane import (
            _NATIVE_COMPRESS_WIRE,
        )

        return (
            self._single_server is not None
            and not self._single_server.ip.startswith("unix://")
            and self._options.transport == "tcp"
            and self._options.ssl_context is None
            # the two protocols the C++ channel packs natively (tbnet.h);
            # baidu_std rides the same fast path with wire-exact PRPC bytes
            and self._options.protocol in ("tbus_std", "baidu_std")
            # auth and compression ride the fast path on baidu_std: the
            # credential stamps RpcMeta field 7 (first-request fight in
            # C++), compressed payloads stamp field 3 and the server's
            # native codec table answers in kind.  tbus_std carries both
            # in JSON meta the Python route owns, so it keeps the old
            # gates.
            and (
                self._options.auth is None
                or self._options.protocol == "baidu_std"
            )
            and self._options.connection_type in ("single", "pooled")
            and (
                not cntl.compress_type
                or (
                    self._options.protocol == "baidu_std"
                    and cntl.compress_type in _NATIVE_COMPRESS_WIRE
                )
            )
            and not (cntl.backup_request_ms and cntl.backup_request_ms > 0)
            and not cntl._force_host
        )

    def _native_fresh_or_none(self, cached):
        """Reuse `cached` if healthy, else dial a replacement (None on
        connect failure). Shared by the pooled and single storage slots."""
        from incubator_brpc_tpu.transport import native_plane as np_mod

        if cached is not None and cached.healthy():
            return cached
        if cached is not None:
            cached.close()
        try:
            nch = np_mod.NativeClientChannel(
                self._single_server.ip,
                self._single_server.port,
                connect_timeout_ms=int(self._options.connect_timeout * 1000),
                protocol=self._options.protocol,
            )
        except OSError:
            return None
        if (
            self._options.auth is not None
            and self._options.protocol == "baidu_std"
        ):
            # fresh connection, fresh credential: the C++ channel stamps
            # it until the first successful response proves the conn
            # (attach_credential's fight, natively)
            try:
                nch.set_auth(self._options.auth.generate_credential())
            except Exception:
                logger.exception(
                    "generate_credential failed; native path disabled"
                )
                nch.close()
                return None
        return nch

    def _native_channel(self):
        from incubator_brpc_tpu.transport import native_plane as np_mod

        if not np_mod.NET_AVAILABLE:
            return None
        if self._options.connection_type == "pooled":
            # pooled + native = one exclusive connection per caller thread
            # (no completion-pump contention; the reference's pooled type
            # gives each in-flight call its own fd for the same reason)
            ch = self._native_fresh_or_none(getattr(self._native_tls, "ch", None))
            self._native_tls.ch = ch
            return ch
        with self._native_lock:
            ch = self._native_fresh_or_none(self._native_ch)
            self._native_ch = ch
            return ch

    def _native_call(
        self, cntl: Controller, service, method, request, attachment
    ) -> bool:
        """One attempt over the native channel. True = the RPC completed
        (ok, RPC error, or timeout — none retriable under the default
        policy); False = transport trouble, caller falls through to the
        regular path which dials fresh and owns retries."""
        import errno as _errno

        nch = self._native_channel()
        if nch is None:
            return False
        from incubator_brpc_tpu.builtin.rpcz import (
            end_client_span,
            in_trace_context,
            start_client_span,
        )
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        # captured BEFORE start_client_span stamps fresh ids: a caller
        # continuing an external trace (cntl.trace_id pre-set) is
        # indistinguishable from a generated id afterwards
        preset_trace = bool(
            cntl.trace_id or cntl.span_id or cntl.trace_sampled
        )
        cntl._span = start_client_span(cntl)
        # start_client_span ALWAYS stamps trace ids on the controller.
        # Traced frames now stay on the server's C++ fast path (the
        # cutter decodes RpcRequestMeta fields 3-6/9 natively and the
        # telemetry drain parents the server span), but untraced calls
        # still skip the per-call submeta encode — so stamp the wire only
        # when the trace is actually observable: this hop sampled a span,
        # the caller set a log_id or their own trace ids/sampled bit, or
        # we're inside a server handler's trace context.
        traced = (
            cntl._span is not None
            or bool(cntl.log_id)
            or preset_trace
            or in_trace_context()
        )
        request_wire = request
        if cntl.compress_type:
            # same codec registry the server's C++ table mirrors: the
            # compressed bytes are identical on both planes
            request_wire = compress_mod.compress(cntl.compress_type, request)
        rc, err_code, resp_meta, body = nch.call(
            service,
            method,
            request_wire,
            attachment,
            timeout_ms=cntl.timeout_ms,
            log_id=cntl.log_id if traced else 0,
            trace_id=cntl.trace_id if traced else 0,
            span_id=cntl.span_id if traced else 0,
            parent_span_id=cntl.parent_span_id if traced else 0,
            sampled=cntl.trace_sampled if traced else 0,
            compress=cntl.compress_type or "",
        )
        if rc < 0:
            if rc == -_errno.ETIMEDOUT:
                cntl.set_failed(
                    ErrorCode.ERPCTIMEDOUT,
                    f"deadline {cntl.timeout_ms} ms exceeded",
                )
                cntl.remote_side = self._single_server
                cntl._mark_end()
                if cntl._span is not None:
                    end_client_span(cntl)
                return True
            if rc == -_errno.EBADMSG:
                # the response's correlation id carried another reactor
                # shard's tag (tb_channel cid partitioning): a protocol-
                # level bad answer, not a dead connection — surface it as
                # EREQUEST and keep the channel (the C++ side already
                # counted it in tb_channel_cid_misroutes)
                cntl.set_failed(
                    ErrorCode.EREQUEST,
                    "response correlation id from the wrong reactor shard",
                )
                cntl.remote_side = self._single_server
                cntl._mark_end()
                if cntl._span is not None:
                    end_client_span(cntl)
                return True
            # connection-level failure: recycle and let the regular path
            # (fresh dial + retry arbitration) handle this call
            with self._native_lock:
                if self._native_ch is nch:
                    self._native_ch = None
            nch.close()
            if cntl._span is not None:
                end_client_span(cntl)
            cntl._span = None
            return False
        cntl.remote_side = self._single_server
        if err_code:
            meta = nch.decode_resp_meta(resp_meta) if resp_meta else Meta()
            cntl.set_failed(int(err_code), meta.error_text or berror(int(err_code)))
        else:
            meta = nch.decode_resp_meta(resp_meta) if resp_meta else None
            blen = len(body)
            att = meta.attachment_size if meta is not None else 0
            if att > blen:
                cntl.set_failed(ErrorCode.ERESPONSE, "attachment exceeds body")
            else:
                cntl.response_meta = meta
                payload = body.to_bytes(blen - att)
                if meta is not None and meta.compress:
                    # the server recompressed the response (floor
                    # permitting): decompress like the Python plane's
                    # response path
                    try:
                        payload = compress_mod.decompress(
                            meta.compress, payload
                        )
                    except Exception as e:
                        cntl.set_failed(
                            ErrorCode.ERESPONSE, f"decompress failed: {e}"
                        )
                        payload = None
                if payload is not None:
                    cntl.response_payload = payload
                    cntl.response_attachment = (
                        body.to_bytes(att, pos=blen - att) if att else b""
                    )
        cntl._mark_end()
        if cntl._span is not None:
            end_client_span(cntl)
        return True

    # -- issue / return paths (run under the call-id lock) -------------------

    def _auth_key_tag(self) -> str:
        """Connection-pool partition for this channel's credentials — the
        reference's SocketMapKey carries the Authenticator for the same
        reason (socket_map.h:35). FIFO-correlated protocols partition by
        protocol too: their responses carry no ids, so a socket's inbound
        bytes are only decodable when exactly one such protocol ever
        spoke on it (two channels to one endpoint speaking esp and
        nova would otherwise corrupt each other's response framing)."""
        a = self._options.auth
        tag = ""
        if a is not None:
            tag = getattr(a, "_smap_tag", None)
            if tag is None:
                tag = f"auth-{id(a):x}"
                a._smap_tag = tag
        proto_name = self._options.protocol
        if proto_name != "tbus_std":
            from incubator_brpc_tpu.protocol.registry import protocol_registry

            if proto_name in protocol_registry and protocol_registry.get(
                proto_name
            ).fifo_responses:
                tag = f"{tag}|fifo-{proto_name}"
        if self._options.ssl_context is not None:
            # TLS and plaintext must never share a connection — and neither
            # may two channels with DIFFERENT TLS configs (client certs,
            # verification modes): the context's identity partitions too,
            # like the reference SocketMapKey's ssl settings
            tag = f"{tag}|ssl-{id(self._options.ssl_context):x}"
        return tag

    def _conn_kwargs(self) -> dict:
        """Extra Socket.connect kwargs every connection of this channel
        needs (TLS today; the SocketMapKey's ssl slot, socket_map.h:35)."""
        if self._options.ssl_context is None:
            return {}
        return {
            "ssl_context": self._options.ssl_context,
            "ssl_server_hostname": self._options.ssl_server_hostname,
        }

    def _dispose_attempt_sock(self, kind: str, sock, reusable: bool = True) -> None:
        """One attempt's connection settles (Call::OnComplete disposition,
        controller.cpp:698): pooled returns to the pool ONLY when the call
        finished cleanly — a timed-out or superseded attempt may still have
        a request in flight, and parking it would head-of-line-block the
        next caller (the reference closes non-single connections on error
        for the same reason). Short connections drain then close."""
        if kind == "pooled" and reusable:
            # keyed by the connection's actual remote: pooled secondaries
            # of LB targets park under their own endpoint's entry
            self._socket_map.return_pooled(
                sock.remote, sock, key_tag=self._auth_key_tag()
            )
        else:
            _recycle_when_drained(sock)

    def _call_host(self, service, method, request, cntl=None):
        """A call forced onto the HOST (TCP) path even when this channel's
        transport is 'tpu' — the handshake itself must ride the bootstrap
        socket (the reference's deferred-handshake-over-TCP,
        socket.cpp:1692-1704)."""
        if cntl is None:
            cntl = Controller()
        cntl._force_host = True
        return self.call_method(service, method, request, cntl=cntl)

    def _get_device_socket(self, cntl: Controller, ep: Optional[EndPoint] = None):
        """transport='tpu': the established DeviceSocket for the target
        endpoint, from the process-wide DeviceLinkMap (re-handshaking a
        dead link; the host socket below it reconnects via its own paths).
        Links are shared across channels — the SocketMap dedupe semantics
        on the device plane."""
        from incubator_brpc_tpu.transport.device_link import device_link_map

        target = ep if ep is not None else self._single_server
        ds = device_link_map.get_or_create(
            target,
            device_index=self._options.device_index,
            slot_words=self._options.link_slot_words,
            window=self._options.link_window,
            timeout_ms=cntl.timeout_ms or 60000,
            ack_mode=self._options.link_ack_mode,
            controller=self._options.link_controller,
            auth=self._options.auth,
            ssl_context=self._options.ssl_context,
            ssl_server_hostname=self._options.ssl_server_hostname,
        )
        self._device_sock = ds  # last-used link (introspection/tests)
        return ds

    def _pick_socket(self, cntl: Controller):
        ctype = self._options.connection_type
        if self._options.transport == "tpu" and not getattr(
            cntl, "_force_host", False
        ):
            if self._single_server is not None:
                return self._get_device_socket(cntl)
            # LB target: the LB resolves a healthy host socket (health
            # checks and exclusion run on the host plane), then the link
            # map supplies the device link to that peer
            host = self._lb.select_server(excluded=cntl._excluded_sockets)
            if host is None:
                raise NoServerError("no available server (all excluded or empty)")
            try:
                ds = self._get_device_socket(cntl, ep=host.remote)
            except (OSError, ConnectionError):
                # settle the LB's pick (la charges in-flight on select):
                # an un-settled failed handshake would depress the peer's
                # weight forever
                self._lb.feedback(host, 0.0, ErrorCode.EFAILEDSOCKET)
                raise
            reg = getattr(self._lb, "register_socket", None)
            if reg is not None:
                reg(ds, host.remote)  # feedback/exclusion track the link
            return ds
        if self._single_server is not None:
            if ctype == "single":
                sock = self._socket_map.get_or_create(
                    self._single_server,
                    timeout=self._options.connect_timeout,
                    key_tag=self._auth_key_tag(),
                    **self._conn_kwargs(),
                )
                from incubator_brpc_tpu.transport.sock import CONNECTED

                if sock.state != CONNECTED:
                    # dropped-but-healthy peer: reconnect inline instead of
                    # burning the attempt against a dead socket until the
                    # health probe fires (ConnectIfNot, socket.cpp:1591)
                    sock.connect_if_not(self._options.connect_timeout)
                return sock
            if ctype == "pooled":
                sock = self._socket_map.get_pooled(
                    self._single_server,
                    timeout=self._options.connect_timeout,
                    key_tag=self._auth_key_tag(),
                    **self._conn_kwargs(),
                )
            else:  # short: fresh connection, closed at EndRPC
                sock = self._socket_map.get_short(
                    self._single_server,
                    timeout=self._options.connect_timeout,
                    **self._conn_kwargs(),
                )
            # disposed together at EndRPC — a backup request keeps the
            # previous attempt's connection in flight, so NOTHING may be
            # settled mid-call
            cntl._call_socks.append((ctype, sock))
            return sock
        # LB targets: the LB resolves a healthy MAIN socket per endpoint;
        # pooled/short secondaries hang off that endpoint's map entry (the
        # reference's SharedPart design, socket_map.h:35 +
        # Socket::GetPooledSocket/GetShortSocket)
        sock = self._lb.select_server(excluded=cntl._excluded_sockets)
        if sock is None:
            raise NoServerError("no available server (all excluded or empty)")
        if ctype == "single":
            return sock
        ep = sock.remote
        if ctype == "pooled":
            sec = self._socket_map.get_pooled(
                ep,
                timeout=self._options.connect_timeout,
                key_tag=self._auth_key_tag(),
                **self._conn_kwargs(),
            )
        else:  # short
            sec = self._socket_map.get_short(
                ep,
                timeout=self._options.connect_timeout,
                **self._conn_kwargs(),
            )
        # LB feedback and retry exclusion track the secondary's id too
        reg = getattr(self._lb, "register_socket", None)
        if reg is not None:
            reg(sec, ep)
        cntl._call_socks.append((ctype, sec))
        return sec

    def _issue_rpc(self, cntl: Controller) -> None:
        """IssueRPC (controller.cpp:941): pick socket, pack, write. Called
        with the call id locked."""
        cid = cntl.call_id
        try:
            sock = self._pick_socket(cntl)
        except NoServerError as e:
            # every candidate excluded / empty cluster: EHOSTDOWN, letting
            # retry arbitration decide (reference ExcludedServers,
            # controller.cpp:578-615)
            self._arbitrate_error(cntl, ErrorCode.EHOSTDOWN, str(e))
            return
        except (OSError, ConnectionError) as e:
            # connection failed: arbitrate like a socket failure
            self._arbitrate_error(cntl, ErrorCode.EFAILEDSOCKET, str(e))
            return
        cntl.remote_side = sock.remote
        cntl._sent_sockets.append(sock)
        if cntl._want_poll and cntl._poll_owned is None and sock.try_read_ownership():
            # sync caller will drive this socket's reads (see _sync_wait);
            # claiming before the write keeps the post-send GIL window tiny
            cntl._poll_owned = sock
        # the wire deadline is the budget REMAINING now (retries re-stamp,
        # so every hop sees what is actually left, not the original spec);
        # a sub-ms residue still rides as 1 so "deadline present" survives
        # integer ms truncation
        import time as _time0

        wire_timeout = 0
        if cntl._deadline:
            wire_timeout = max(
                1, int((cntl._deadline - _time0.monotonic()) * 1000)
            )
        meta = Meta(
            service=cntl._service,
            method=cntl._method,
            compress=cntl.compress_type,
            timeout_ms=wire_timeout,
            log_id=cntl.log_id,
            trace_id=cntl.trace_id,
            span_id=cntl.span_id,
            parent_span_id=cntl.parent_span_id,
            sampled=cntl.trace_sampled,
            stream_id=(
                cntl._request_stream.id if cntl._request_stream is not None else 0
            ),
            extra=dict(cntl.request_extra) if cntl.request_extra else {},
        )
        if self._options.auth is not None:
            from incubator_brpc_tpu.rpc.auth import attach_credential

            attach_credential(meta, sock, self._options.auth)
        attachment, array = cntl.request_attachment, None
        try:
            payload = cntl._request_payload
            if cntl.compress_type:
                payload = compress_mod.compress(cntl.compress_type, payload)
            proto_name = self._options.protocol
            if cntl._unary is not None:
                # a device array: the lane where this socket has one, its
                # bytes where there is no second device, else refused
                array, attachment = device_link.array_carrier(sock, attachment)
                if attachment is None:
                    cntl.set_failed(
                        ErrorCode.EINVAL,
                        f"the attachment cannot cross to {sock.remote}: not "
                        "whole on the link's device, empty, deleted or "
                        "donated, or the link has no lane yet",
                    )
                    self._end_rpc(cntl)
                    return
                if array is None:
                    device_link.unary_bytes_fallbacks << 1
                elif proto_name != "tbus_std":
                    raise ValueError("a device array rides tbus_std frames only")
            if array is not None:
                data = None  # the frame is the array's tag: packed by the socket
            elif proto_name == "tbus_std":
                data = pack_frame_iobuf(
                    meta,
                    payload,
                    cid,
                    attachment=attachment,
                )
            else:
                # protocol selected by name (reference AdaptiveProtocolType):
                # the registry's packer produces that protocol's exact bytes
                from incubator_brpc_tpu.protocol.registry import protocol_registry

                if proto_name not in protocol_registry:
                    raise ValueError(f"unknown protocol {proto_name!r}")
                proto = protocol_registry.get(proto_name)
                if proto.pack_request is None:
                    raise ValueError(f"protocol {proto_name!r} cannot pack requests")
                if proto.fifo_responses and sock.remote is not None:
                    meta.extra["http_host"] = f"{sock.remote.ip}:{sock.remote.port}"
                if proto.fifo_responses:
                    # response frames on this connection belong to this
                    # protocol — the legacy client rows gate their scan on
                    # it (a client socket has no Server context to gate by)
                    sock.context["fifo_protocol"] = proto_name
                data = proto.pack_request(
                    meta,
                    payload,
                    cid,
                    attachment=attachment,
                )
                if proto.fifo_responses:
                    # no wire correlation id: record the cid in the
                    # connection's FIFO atomically with the write, so the
                    # pending order always equals the wire order
                    self._write_fifo_correlated(sock, cntl, cid, data)
                    return
        except (ValueError, TypeError) as e:
            # unknown codec / bad frame inputs: fail the RPC, never leak the
            # locked id out of IssueRPC
            cntl.set_failed(ErrorCode.EREQUEST, f"pack failed: {e}")
            self._end_rpc(cntl)
            return
        pool = global_worker_pool()
        import time as _time

        remaining = None
        if cntl._deadline:
            remaining = max(0.001, cntl._deadline - _time.monotonic())
        _track_inflight(sock, cid)
        if array is not None:
            rc = sock.write_device_message(meta, payload, cid, array)
            cntl._unary[1] = time.monotonic_ns()
            if rc == 0:
                device_link.unary_lane_requests << 1
                device_link.unary_lane_bytes << array.nbytes
        else:
            rc = sock.write(
                data,
                on_error=lambda code, text: (
                    pool.spawn(call_id_space.error, cid, code, text)
                    if _claim_inflight(sock, cid)
                    else None
                ),
                timeout=remaining,
            )
        if rc != 0:
            self._arbitrate_error(cntl, rc, f"write to {sock.remote} failed")

    def _write_fifo_correlated(self, sock, cntl: Controller, cid: int, data) -> None:
        """Write a frame whose response matches by connection order (HTTP):
        append the cid to the socket's pending FIFO and write under one
        lock so two callers can't interleave order; dead sockets clear the
        FIFO (late responses then fail their id lock and drop). Called with
        the id locked, like the rest of IssueRPC."""
        import collections

        lock = sock.context.get("_fifo_lock")
        if lock is None:
            lock = sock.context.setdefault("_fifo_lock", threading.Lock())
        pending = sock.context.get("http_pending")
        if pending is None:
            pending = sock.context.setdefault("http_pending", collections.deque())

            def _fail_fifo(s):
                # fail every call still waiting for an ordered response —
                # same fail-fast-at-SetFailed invariant as _track_inflight
                # (clearing alone left them hanging until their deadline)
                lk = s.context.get("_fifo_lock")
                q = s.context.get("http_pending")
                drained = []
                if lk is not None and q is not None:
                    with lk:
                        drained = list(q)
                        q.clear()
                for c in drained:
                    global_worker_pool().spawn(
                        call_id_space.error,
                        c,
                        ErrorCode.EFAILEDSOCKET,
                        f"connection to {s.remote} failed with the call in flight",
                    )

            # fabriclint: allow(lifecycle-callback) closure reads only the failing socket's own context, hooked once per socket (guarded by http_pending creation), dies with it
            sock.on_failed.append(_fail_fifo)
        pool = global_worker_pool()
        with lock:
            # append BEFORE the write: the inline drain can flush the
            # request and the reactor can process its response before this
            # thread takes another step — the cid must already be in the
            # FIFO. A refused write removes it under the SAME lock, so no
            # concurrent writer can interleave and land behind a dead head.
            pending.append(cid)
            try:
                rc = sock.write(
                    data,
                    on_error=lambda code, text: pool.spawn(
                        call_id_space.error, cid, code, text
                    ),
                )
            except BaseException:
                # an exception must not strand a dead cid at the FIFO head
                # (it would shift every later response one call off)
                try:
                    pending.remove(cid)
                except ValueError:
                    pass
                raise
            if rc != 0:
                try:
                    pending.remove(cid)
                except ValueError:
                    pass  # a (failed) response path already consumed it
        if rc != 0:
            self._arbitrate_error(cntl, rc, f"write to {sock.remote} failed")

    def _handle_id_error(self, cid: int, cntl: Controller, code: int, text: str) -> None:
        """CallIdSpace on_error: runs with the id locked — the
        OnVersionedRPCReturned error path (controller.cpp:545)."""
        self._arbitrate_error(cntl, code, text)
        # _arbitrate_error either destroyed the id (terminal) or left it
        # locked after re-issuing; unlock in the latter case.
        if call_id_space.valid(cid):
            call_id_space.unlock(cid)

    def _arbitrate_error(self, cntl: Controller, code: int, text: str) -> None:
        """Retry / backup / fail decision. Id is locked; does NOT unlock
        (caller decides), but EndRPC destroys."""
        if code == ErrorCode.EBACKUPREQUEST:
            # backup timer fired: issue a duplicate, keep the original
            # in flight (controller.cpp:565-598)
            if not cntl.has_backup_request:
                cntl.has_backup_request = True
                # the attempts in flight RIGHT NOW are merely raced, not
                # failed: EndRPC settles them as EBACKUPREQUEST (ignored
                # by the circuit breaker) — later retried-away attempts
                # still settle as genuine failures
                cntl._backup_superseded = {s.id for s in cntl._sent_sockets}
                if cntl._sent_sockets:
                    cntl._excluded_sockets.add(cntl._sent_sockets[-1].id)
                self._issue_rpc(cntl)
            return
        if self._should_retry(cntl, code) and cntl.retried_count < cntl.max_retry:
            if self._budget_allows(code):
                cntl.retried_count += 1
                if cntl._sent_sockets:
                    cntl._excluded_sockets.add(cntl._sent_sockets[-1].id)
                cntl._reset_for_retry()
                self._issue_rpc(cntl)
                return
            # budget exhausted: fail fast with the ORIGINAL error — the
            # whole point is NOT multiplying a brownout's offered load
            text = f"{text} (retry budget exhausted)"
        cntl.set_failed(code, text)
        self._end_rpc(cntl)

    def _budget_allows(self, code: int) -> bool:
        """One retry's draw against this channel's retry budget (exempt
        codes pass without drawing; no budget = unlimited)."""
        b = self._retry_budget
        return b is None or b.acquire(code)

    def _should_retry(self, cntl: Controller, code: int) -> bool:
        """RetryPolicy::DoRetry (retry_policy.h): the channel's custom
        policy sees the attempt's error on the controller; default = the
        retriable-code set. ECANCELED never retries — a cancel is the
        caller's decision, not a transient."""
        if code == ErrorCode.ECANCELED:
            return False
        policy = self._options.retry_policy
        if policy is None:
            return code in RETRIABLE
        saved = cntl.error_code
        cntl.error_code = code  # DoRetry reads cntl->ErrorCode()
        try:
            return bool(policy(cntl))
        except Exception:
            logger.exception("retry_policy raised; not retrying")
            return False
        finally:
            cntl.error_code = saved  # probing must not settle the call

    def _on_rpc_returned(self, cntl: Controller, frame: ParsedFrame, sock) -> None:
        """Response arrived (id locked by process_response)."""
        budget_note = ""
        if frame.error_code != 0 and self._should_retry(
            cntl, frame.error_code
        ) and (
            cntl.retried_count < cntl.max_retry
        ):
            if not self._budget_allows(frame.error_code):
                # same marker as the _arbitrate_error seam: a triager
                # must be able to tell budget-capped failures apart on
                # BOTH response paths
                budget_note = " (retry budget exhausted)"
                frame_error_retry = False
            else:
                frame_error_retry = True
        else:
            frame_error_retry = False
        if frame_error_retry:
            cntl.retried_count += 1
            cntl._excluded_sockets.add(sock.id)
            from incubator_brpc_tpu.transport.event_dispatcher import (
                on_reactor_thread,
            )

            if on_reactor_thread():
                # re-issuing may dial a fresh connection (blocking): hand
                # off to a fiber; the id STAYS locked across the handoff
                # (the lock is state, not thread-bound)
                def _retry_off_reactor():
                    self._issue_rpc(cntl)
                    call_id_space.unlock(cntl.call_id)

                global_worker_pool().spawn(_retry_off_reactor)
                return
            self._issue_rpc(cntl)
            call_id_space.unlock(cntl.call_id)
            return
        if frame.error_code != 0:
            cntl.set_failed(
                frame.error_code,
                (
                    (frame.meta.error_text if frame.meta else "")
                    or f"remote error {frame.error_code}"
                )
                + budget_note,
            )
        else:
            payload = frame.payload
            if frame.meta and frame.meta.compress:
                try:
                    payload = compress_mod.decompress(frame.meta.compress, payload)
                except Exception as e:
                    cntl.set_failed(ErrorCode.ERESPONSE, f"decompress failed: {e}")
                    self._end_rpc(cntl)
                    return
            cntl.response_payload = payload
            cntl.response_attachment = frame.attachment
            cntl.response_meta = frame.meta
            if cntl._unary is not None:  # missing: the answer came as bytes
                cntl._unary[2] = getattr(frame, "handed_ns", RecorderFeed.MISSING)
            if self._options.auth is not None:
                # a successful response proves the connection: stop sending
                # credentials on it (FightAuthentication settled)
                from incubator_brpc_tpu.rpc.auth import mark_authenticated

                mark_authenticated(sock)
            if (
                cntl._request_stream is not None
                and frame.meta is not None
                and frame.meta.stream_id
            ):
                # handshake complete: the server's stream id arrived
                cntl._request_stream._connect(sock, frame.meta.stream_id)
                if cntl._span is not None:
                    cntl._span.annotate(
                        f"stream {cntl._request_stream.id} connected to "
                        f"remote stream {frame.meta.stream_id}"
                    )
        self._end_rpc(cntl)

    def _end_rpc(self, cntl: Controller) -> None:
        """EndRPC: cancel timers, destroy the id (wakes joiners), run done.
        Called with the id locked; the id is dead afterwards."""
        cntl._mark_end()
        if self._lb is not None:
            # every issued attempt (retries, backup duplicates) was a
            # select() — feed each back exactly once so LA's in-flight
            # accounting balances (Call::OnComplete does per-call Feedback,
            # controller.cpp:698-777). A backup-raced attempt is not a
            # node failure (it may be healthy-but-slow, possibly even
            # answered): exactly the sockets in flight when the backup
            # fired settle as EBACKUPREQUEST, which the LB's circuit
            # breaker ignores — attempts retried away on a genuine error
            # still charge their node's error windows.
            last = cntl._sent_sockets[-1] if cntl._sent_sockets else None
            raced = getattr(cntl, "_backup_superseded", ())
            for sock in cntl._sent_sockets:
                if sock is last:
                    code = cntl.error_code
                elif sock.id in raced:
                    code = ErrorCode.EBACKUPREQUEST
                else:
                    code = ErrorCode.EFAILEDSOCKET
                self._lb.feedback(sock, cntl.latency_us, code)
        timer = global_timer_thread()
        for tid in cntl._timer_ids:
            timer.unschedule(tid)
        cntl._timer_ids.clear()
        for sock in cntl._sent_sockets:
            cids = sock.context.get("_inflight_cids")
            if cids is not None:
                cids.discard(cntl.call_id)
        if cntl._span is not None:
            from incubator_brpc_tpu.builtin.rpcz import end_client_span

            end_client_span(cntl)
        # settle every attempt's pooled/short connection now — except one a
        # live stream is bound to, which is released when the stream ends.
        # A pooled socket is only reusable when this was a clean,
        # single-attempt success (a timed-out or superseded attempt may
        # still carry an in-flight request).
        reusable = cntl.ok() and len(cntl._call_socks) <= 1
        stream_sock = (
            cntl._request_stream._sock if cntl._request_stream is not None else None
        )
        for kind, sock in cntl._call_socks:
            if sock is stream_sock:
                cb = lambda _k=kind, _s=sock, _r=reusable: (  # noqa: E731
                    self._dispose_attempt_sock(_k, _s, _r)
                )
                sock.context["_stream_dispose"] = cb
                from incubator_brpc_tpu.rpc import stream as stream_mod

                if cntl._request_stream.state == stream_mod.CLOSED:
                    # the stream raced us and already ran _unhook_socket:
                    # whoever pops the callback runs it (dict.pop is atomic)
                    late = sock.context.pop("_stream_dispose", None)
                    if late is not None:
                        late()
                continue
            self._dispose_attempt_sock(kind, sock, reusable)
        cntl._call_socks.clear()
        if cntl._request_stream is not None:
            from incubator_brpc_tpu.rpc import stream as stream_mod

            if cntl._request_stream.state == stream_mod.CONNECTING:
                # RPC ended without the server accepting: kill the half-open
                # stream so writers don't block forever
                cntl._request_stream._fail(
                    cntl.error_code or ErrorCode.EREQUEST,
                    cntl.error_text or "stream not accepted",
                )
        call_id_space.unlock_and_destroy(cntl.call_id)
        ps = cntl._poll_sock
        if ps is not None:
            # a sync caller is poll-driving some socket: if the RPC ended on
            # a different path (other socket, timer), wake it now
            ps.kick_poller()
        if cntl._done is not None:
            if cntl._unary is not None:
                self._unary_row(cntl)
            global_worker_pool().spawn(cntl._done, cntl)
