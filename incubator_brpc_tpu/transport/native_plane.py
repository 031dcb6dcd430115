"""Native network plane — Python face of src/tbnet.

The reference's L2–L4 data plane is C++ (SURVEY.md §2 rules out Python
stand-ins); tbnet is the native epoll reactor + tbus_std messenger + method
dispatcher, and this module is the seam between it and the Python L5:

- ``NativeServerPlane`` replaces the Python Acceptor/EventDispatcher for a
  Server: tbus_std AND baidu_std (PRPC) frames cut, verified and (for
  natively-registered methods) ANSWERED without the interpreter in the
  protocol they arrived in; other frames surface here as one callback per
  frame (flag 0x100 marks PRPC metas) and run through the exact same
  ``Server.process_request`` path (admission, auth, rpcz, dump) over a
  ``NativeConnSock`` facade; connections that open with any OTHER
  protocol (the HTTP portal, nshead...) are handed off wholesale to a
  real Python ``Socket`` — one port, every protocol, like the reference's
  protocol scan (input_messenger.cpp:60-129).
- ``NativeClientChannel`` is the client fast path: pack/write/read/match in
  C++ with the GIL released; concurrent callers share one connection and
  elect a completion-pump reader (the single-connection multi-caller shape
  of the reference client).
- The **telemetry ring** keeps the fast path observable: every natively
  dispatched request appends a completion record (method/latency/sizes/
  error/cid + a 1/N sample flag) to a lock-free MPSC ring in C++; the
  drain here (background thread + forced drain on scrape/stop) fans
  records out to per-method ``LatencyRecorder``s, sampled /rpcz server
  spans, and ``AutoConcurrencyLimiter`` feedback — the reference feeds
  bvar/rpcz from inside every protocol's ProcessRequest the same way
  (docs/OBSERVABILITY.md "Native telemetry ring").
"""

from __future__ import annotations

import ctypes
import logging
import socket as _pysocket
import threading
import time
from typing import Dict, Optional

from incubator_brpc_tpu import native
from incubator_brpc_tpu.bvar import (
    Adder,
    IntRecorder,
    LatencyRecorder,
    PassiveStatus,
)
from incubator_brpc_tpu.native import (
    AUTH_FN,
    CLOSED_FN,
    FRAME_FN,
    HANDOFF_FN,
    LIB,
    LIB_HELD,
)
from incubator_brpc_tpu.utils.endpoint import EndPoint
from incubator_brpc_tpu.utils.status import ErrorCode

logger = logging.getLogger(__name__)

NET_AVAILABLE = native.NATIVE_AVAILABLE

KIND_ECHO = 1
KIND_NOP = 2

# flags mirrored from protocol/tbus_std.py (also in tbnet.cc)
_FLAG_RESPONSE = 1
_FLAG_STREAM = 2
# internal callback-only flag from tbnet.cc: the frame arrived on a
# baidu_std (PRPC) connection and its meta is RpcMeta proto bytes
_FLAG_WIRE_PRPC = 0x100
# internal callback-only flag: the connection's credential was verified
# on the native plane — server_check honors the cached verdict
_FLAG_CONN_AUTHED = 0x200

# tb_channel_set_protocol values (tbnet.h)
_CH_PROTO = {"tbus_std": 0, "baidu_std": 1}

# tb_telemetry_record ABI size — the fourth copy of the layout contract
# (header struct / ctypes mirror / numpy dtype are cross-checked by
# fabriclint's ffi-struct pass; fabricscan's plane-parity pass diffs
# this constant against the static_assert in src/tbnet/tbnet.cc)
_TELEMETRY_RECORD_BYTES = 64

# sampled-word bit layout (tbnet.cc kTeleSampleBit/kTeleCodecShift/
# kTeleWireForced): bit 0 = rpcz sample election, bits 1-2 = request
# codec id, bit 3 = the sampled bit arrived ON THE WIRE (head-based
# coherent sampling — the edge's decision, which already forced bit 0)
_TEL_SAMPLE_BIT = 1
_TEL_CODEC_SHIFT = 1
_TEL_WIRE_FORCED = 8

# wire CompressType <-> codec names the native plane implements (the
# baidu_std table restricted to what the C++ codec table speaks)
_NATIVE_COMPRESS_WIRE = {"snappy": 1, "gzip": 2, "zlib1": 3}
_NATIVE_COMPRESS_NAMES = {v: k for k, v in _NATIVE_COMPRESS_WIRE.items()}

# client fast-path instrumentation: per-call round-trip latency (Python
# boundary included), transport errors, and the pipelined pump's
# ns/request, scrapeable from /brpc_metrics on any process that ran a pump
native_client_calls = Adder(name="native_client_calls")
native_client_errors = Adder(name="native_client_errors")
native_client_call_us = LatencyRecorder(name="native_client_call_us")
native_pump_ns = IntRecorder(name="native_pump_ns")
# the same pipelined pump over the baidu_std (PRPC) wire
prpc_pump_ns = IntRecorder(name="prpc_pump_ns")

# process-wide compress/auth telemetry summed across every live native
# plane (a stopping plane folds its final counts into the retired
# tallies first, so neither gauge ever moves backwards)
import weakref as _weakref  # noqa: E402  (module-bvar support)

_planes_tally_lock = threading.Lock()
_live_planes: "_weakref.WeakSet" = _weakref.WeakSet()
_retired_compress_saved = 0
_retired_auth_rejects = 0


def _sum_compress_saved() -> int:
    total = _retired_compress_saved
    for plane in list(_live_planes):
        st = plane.compress_stats()
        total += max(0, st["in_raw"] - st["in_wire"])
        total += max(0, st["out_raw"] - st["out_wire"])
    return total


def _sum_auth_rejects() -> int:
    total = _retired_auth_rejects
    for plane in list(_live_planes):
        total += plane.stats().get("auth_rejects", 0)
    return total


# bytes kept OFF the wire by native codecs: (decompressed request bytes -
# their wire bytes) + (raw response bytes - their wire bytes)
native_compress_bytes_saved = PassiveStatus(
    _sum_compress_saved, name="native_compress_bytes_saved"
)
# requests rejected ERPCAUTH by the native auth seam
native_auth_rejects = PassiveStatus(
    _sum_auth_rejects, name="native_auth_rejects"
)


def _native_kind(handler) -> Optional[int]:
    return getattr(handler, "_native_kind", None)


# int (*)(void* ud, const char* req, size_t len, char** resp, size_t* n)
NATIVE_METHOD_FN = ctypes.CFUNCTYPE(
    ctypes.c_int,
    ctypes.c_void_p,
    ctypes.c_char_p,
    ctypes.c_size_t,
    ctypes.POINTER(ctypes.c_char_p),
    ctypes.POINTER(ctypes.c_size_t),
)


def native_method_lib(lib_path: str, symbol: str, fallback) -> "object":
    """Tag ``fallback`` (the ordinary Python handler, used when the native
    plane is off) with a shared-library implementation of the same method:
    ``symbol`` in ``lib_path`` must be a ``tb_native_fn``
    (src/tbnet/tbnet.h). When the server runs on the native plane, requests
    to this method are answered entirely on the C++ loop thread — the
    generalization of the built-in echo/nop kinds to USER code (the
    reference's whole request path is native user code,
    baidu_rpc_protocol.cpp:307-503).

    The two implementations must agree: the Python fallback is the
    method's portable semantics, the .so its native fast path."""
    try:
        fallback._native_lib = (lib_path, symbol)
        return fallback
    except AttributeError:  # bound methods can't carry attributes: wrap

        def handler(cntl, request, _fb=fallback):
            return _fb(cntl, request)

        handler._native_lib = (lib_path, symbol)
        return handler


def native_echo(cntl, request: bytes) -> bytes:
    """Echo handler the native plane can run without the interpreter; works
    identically as a plain Python handler when the plane is off."""
    cntl.response_attachment = cntl.request_attachment
    return request


native_echo._native_kind = KIND_ECHO


def native_nop(cntl, request: bytes) -> bytes:
    """No-op handler (empty response); native kind 2."""
    return b""


native_nop._native_kind = KIND_NOP


def native_long_running(handler):
    """Mark a native .so method (``native_method_lib``) long-running: with
    a dispatch pool enabled (``ServerOptions.native_dispatch_workers``)
    its requests always defer to the work-stealing pool instead of
    running inline on the reactor loop thread — one slow handler can't
    stall its reactor's frame cut/pack work.  No-op without a pool, and
    for plain Python handlers (the Python route has its own worker
    pool)."""
    try:
        handler._native_long_running = True
        return handler
    except AttributeError:  # bound methods can't carry attributes: wrap

        def wrapped(cntl, request, _fb=handler):
            return _fb(cntl, request)

        wrapped._native_long_running = True
        return wrapped


def _resolve_num_reactors(nloops) -> int:
    """None = auto from the process affinity mask (the per-core
    EventDispatcher default), capped so a 96-core host doesn't mint 96
    loop threads for one port."""
    if nloops:
        return max(1, int(nloops))
    import os

    try:
        ncpu = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        ncpu = os.cpu_count() or 1
    return max(1, min(16, ncpu))


# a copy longer than this gives the interpreter lock up while it runs
_HELD_COPY_MAX = 1 << 16


def _copy_out(iobuf_h, n: int, pos: int) -> bytes:
    """``n`` bytes at ``pos`` of a tb_iobuf, for the reactor's frame
    callback: the calling thread queues for the interpreter lock again
    after every CDLL call, so short copies keep the lock (LIB_HELD) and
    only one that outlasts a hand-over of it lets the lock go."""
    if n <= 0:
        return b""
    out = ctypes.create_string_buffer(n)
    lib = LIB_HELD if n <= _HELD_COPY_MAX else LIB
    got = lib.tb_iobuf_copy_to(iobuf_h, out, n, pos)
    return ctypes.string_at(out, got)


class NativeConnSock:
    """Socket facade over a tbnet connection token — just enough surface
    for the Python request path (process_request, streams, auth): write,
    context, remote, failure hooks. The real fd lives in C++."""

    def __init__(self, token: int, server):
        self.token = token
        self.context: Dict = {"server": server}
        self.on_failed = []
        self.on_revived = []
        self.error_code = 0
        self.error_text = ""
        self.state = 0  # transport/sock.CONNECTED
        self._state_lock = threading.Lock()  # set_failed vs _mark_closed race
        self.preferred_protocol = None
        self.user_message_handler = None
        ip = ctypes.create_string_buffer(64)
        port = LIB.tb_conn_peer(token, ip, 64)
        self.remote = (
            EndPoint(ip=ip.value.decode(), port=port) if port >= 0 else None
        )

    def write(self, data, on_error=None, timeout=None) -> int:
        from incubator_brpc_tpu.iobuf import IOBuf

        if isinstance(data, (bytes, bytearray, memoryview)):
            buf = IOBuf()
            buf.append(bytes(data))
        else:
            buf = data
        if LIB.tb_conn_write(self.token, buf._h) != 0:
            if on_error is not None:
                try:
                    on_error(ErrorCode.EFAILEDSOCKET, "native conn gone")
                except Exception:
                    logger.exception("write on_error callback failed")
            return ErrorCode.EFAILEDSOCKET
        return 0

    def set_failed(self, code: int = ErrorCode.EFAILEDSOCKET, reason: str = "") -> bool:
        # Fail IMMEDIATELY, like Socket.set_failed: flip state and run the
        # failure hooks inline rather than waiting for the C++ loop to
        # observe EPOLLHUP and call back — writes after this report failure
        # and stream failure callbacks fire without a reactor round trip.
        with self._state_lock:
            if self.state != 0:
                return False
            self.state = 1  # FAILED
            self.error_code = code
            self.error_text = reason
        # fabriclint: allow(ffi-unchecked) -1 means the token is already stale — the connection died under us, which is exactly the state set_failed wants
        LIB.tb_conn_close(self.token)
        for cb in list(self.on_failed):
            try:
                cb(self)
            except Exception:
                logger.exception("on_failed callback raised")
        return True

    def mark_native_authenticated(self) -> None:
        """The Python route verified this connection's credential
        (rpc/auth.server_check): cache the verdict on the C++ conn so its
        later frames ride the native fast path without re-fighting auth."""
        # fabriclint: allow(ffi-unchecked) -1 means the token went stale (conn died); there is nothing to cache on a dead connection
        LIB.tb_conn_set_authenticated(self.token)

    def _mark_closed(self) -> None:
        """tbnet says the connection died: run failure hooks (streams)."""
        with self._state_lock:
            if self.state != 0:
                return
            self.state = 1  # FAILED
            if not self.error_code:
                self.error_code = ErrorCode.EEOF
                self.error_text = "native conn closed"
        for cb in list(self.on_failed):
            try:
                cb(self)
            except Exception:
                logger.exception("on_failed callback raised")

    def __repr__(self) -> str:
        return f"<NativeConnSock token={self.token:#x} remote={self.remote}>"


def _drain_pump(plane_ref, stop_event) -> None:
    """Background telemetry drain. Module-level with a weakref on
    purpose: the thread must not pin an abandoned plane against GC (its
    __del__ -> stop() is the cleanup backstop); it exits when the plane
    is collected or stop() sets the event."""
    from incubator_brpc_tpu.utils.flags import get_flag

    while True:
        interval = max(
            0.005, float(get_flag("native_telemetry_drain_ms")) / 1e3
        )
        if stop_event.wait(interval):
            return
        plane = plane_ref()
        if plane is None:
            return
        try:
            plane.drain_telemetry()
        except Exception:
            logger.exception("native telemetry drain failed")
        del plane  # release between ticks: don't pin across the wait


class NativeServerPlane:
    def __init__(self, server, nloops: Optional[int] = None,
                 dispatch_workers: int = 0):
        if not NET_AVAILABLE:
            raise RuntimeError("native plane unavailable")
        self._server = server
        # serializes the tb_server_stats native read against destroy: a
        # /brpc_metrics scrape snapshots the expose registry before stop()
        # hides the per-port gauges, so stats() can race tb_server_destroy
        self._stats_lock = threading.Lock()
        # one reactor per core by default: each owns its own epoll loop,
        # listener (SO_REUSEPORT), telemetry ring, and cut/pack buffers;
        # connections shard round-robin at accept and never migrate
        self.num_reactors = _resolve_num_reactors(nloops)
        self._srv = LIB.tb_server_create(self.num_reactors)
        from incubator_brpc_tpu.utils.flags import get_flag

        LIB.tb_server_set_max_body(
            self._srv, int(get_flag("max_body_size")) + 64 * 1024
        )
        # production-shaped traffic knobs, shared with the Python route so
        # the planes answer byte-identically: the response-compression
        # floor and the decompress-bomb ceiling
        LIB.tb_server_set_compress_min_bytes(
            self._srv, int(get_flag("native_compress_min_bytes"))
        )
        LIB.tb_server_set_max_decompress(
            self._srv, int(get_flag("max_decompress_bytes"))
        )
        # work-stealing dispatch pool for long-running / queue-pressured
        # native methods (0 = every native method runs inline)
        self._dispatch_workers = max(0, int(dispatch_workers))
        if self._dispatch_workers:
            if LIB.tb_server_set_dispatch_pool(
                self._srv, self._dispatch_workers
            ) != 0:
                logger.warning("dispatch pool rejected (listen already?)")
        # telemetry ring (tb_server_set_telemetry must precede listen):
        # every natively-dispatched completion is recorded in C++ and
        # drained here into per-method latency summaries, sampled rpcz
        # spans, and limiter feedback — the fast path stays observable
        # without the interpreter on it
        self._telemetry = bool(get_flag("native_telemetry"))
        if self._telemetry:
            LIB.tb_server_set_telemetry(
                self._srv,
                int(get_flag("native_telemetry_ring_size")),
                int(get_flag("native_telemetry_sample_every")),
            )
        self._tel_lock = threading.Lock()  # serializes drains (one consumer)
        self._tel_recorders: Dict[int, LatencyRecorder] = {}  # method idx ->
        self._tel_drained = 0  # records pulled off the rings so far
        # per-reactor drained roll-up (the rings themselves are per
        # reactor in C++; drops come from tb_server_reactor_stats)
        self._tel_drained_per = [0] * self.num_reactors
        # 4096-record drain batches: numpy's fixed per-batch costs
        # amortize to ~tens of ns per record (the drain shares cores
        # with the hot path it observes)
        self._tel_batch = (native.TelemetryRecord * 4096)()
        self._drain_stop = threading.Event()
        self._drain_thread: Optional[threading.Thread] = None
        # keep callback objects alive for the server's lifetime
        self._frame_cb = FRAME_FN(self._on_frame)
        self._handoff_cb = HANDOFF_FN(self._on_handoff)
        self._closed_cb = CLOSED_FN(self._on_closed)
        LIB.tb_server_set_frame_cb(self._srv, self._frame_cb, None)
        LIB.tb_server_set_handoff_cb(self._srv, self._handoff_cb, None)
        LIB.tb_server_set_closed_cb(self._srv, self._closed_cb, None)
        self._socks: Dict[int, NativeConnSock] = {}
        self._socks_lock = threading.Lock()
        self._stats_snap = None  # (monotonic, stats dict) for the gauges
        self._handoff_socks: set = set()  # live handed-off Python Sockets
        self._user_libs: list = []  # dlopened user-method libraries
        self._native_names: list = []  # fulls registered for C++ dispatch
        # natively-registered methods with no limit of their own: the
        # server-wide ADAPTIVE limit is distributed to them per-method
        # (the C++ plane has no server-level gate)
        self._auto_targets: list = []
        self._stopped = False
        self.port = 0

    # -- registration ------------------------------------------------------

    def register_methods(self) -> None:
        """Register native-kind handlers (echo/nop) for pure-C++ dispatch;
        everything else stays on the per-frame Python route. Gates the
        Python route enforces per request must not be skippable by a fast
        path. A CONSTANT server-wide max_concurrency has no native
        enforcement, so servers configured with one keep ALL methods on
        the Python route. A server-wide "auto" limit IS enforceable
        natively, as a per-method ceiling pushed through
        tb_server_set_native_max_concurrency every time the adaptive
        limit moves (Server._on_server_limit_change). The Authenticator
        is ALSO enforceable natively now: a token-table authenticator
        (``native_tokens()``) verifies constant-time in C, an arbitrary
        one verifies through a per-connection callback deferral (one GIL
        crossing per connection, verdict cached on the conn), and
        rejects answer ERPCAUTH byte-identically to the Python route —
        so auth-configured servers ride the fast path too."""
        from incubator_brpc_tpu.rpc.concurrency_limiter import (
            AutoConcurrencyLimiter,
        )

        # gate on the RESOLVED limiter, not the raw spec: "12" is a
        # constant limit too (create_concurrency_limiter accepts numeric
        # strings) and must keep methods on the Python route like any
        # other constant
        lim = self._server._server_limiter
        if lim is not None and not isinstance(lim, AutoConcurrencyLimiter):
            return
        auth = self._server.options.auth
        if auth is not None and not self._configure_auth(auth):
            # an auth seam the native plane cannot arrange (FFI rejection)
            # must fail CLOSED: no native registrations, every frame runs
            # the Python route's server_check
            return
        for full, prop in self._server.methods().items():
            kind = _native_kind(prop.handler)
            if kind is not None:
                rc = LIB.tb_server_register_native(
                    self._srv, full.encode(), kind, prop.status.max_concurrency
                )
                if rc != 0:
                    # duplicate / key collision: the method stays on the
                    # Python route — and must NOT claim a telemetry index
                    # (_native_names positions mirror the C++ table)
                    logger.warning(
                        "native registration of %s rejected; it stays on "
                        "the Python route", full
                    )
                    continue
                self._native_names.append(full)
                if prop.status.limiter is None:
                    self._auto_targets.append(full)
                continue
            lib_spec = getattr(prop.handler, "_native_lib", None)
            if lib_spec is not None:
                # user method from a shared library: dlopen + dlsym, then
                # hand the raw fn pointer to tbnet — requests to it never
                # touch the interpreter (the dlopen handle stays alive for
                # the plane's lifetime)
                path, symbol = lib_spec
                try:
                    dll = ctypes.CDLL(path)
                    fn = ctypes.cast(getattr(dll, symbol), ctypes.c_void_p)
                except (OSError, AttributeError) as e:
                    logger.warning(
                        "native method lib %s:%s unavailable (%s); "
                        "%s stays on the Python route", path, symbol, e, full
                    )
                    continue
                rc = LIB.tb_server_register_native_fn(
                    self._srv, full.encode(), fn, None,
                    prop.status.max_concurrency,
                )
                if rc == 0:
                    self._user_libs.append(dll)  # keepalive
                    self._native_names.append(full)
                    if prop.status.limiter is None:
                        self._auto_targets.append(full)
                    if getattr(prop.handler, "_native_long_running", False):
                        if LIB.tb_server_set_native_long_running(
                            self._srv, full.encode(), 1
                        ) != 0:
                            logger.warning(
                                "long-running flag rejected for %s", full
                            )
                else:
                    logger.warning(
                        "native registration of %s rejected (duplicate or "
                        "method-key collision); it stays on the Python "
                        "route", full
                    )

    def _configure_auth(self, auth) -> bool:
        """Arrange native auth verification for ``auth`` (pre-listen).
        Token-table authenticators (a ``native_tokens()`` hook returning
        the accepted credential strings) verify entirely in C —
        constant-time, no interpreter even on first frames.  Anything
        else verifies through a ctypes trampoline: ONE GIL crossing per
        connection (the verdict caches on the conn), zero on the steady
        state.  False = the plane could not arrange it (caller falls
        back to Python-route-only dispatch, fail closed)."""
        tokens_hook = getattr(auth, "native_tokens", None)
        tokens = tokens_hook() if callable(tokens_hook) else None
        if tokens:
            import struct as _struct

            blob = b"".join(
                _struct.pack("<I", len(t)) + t
                for t in (
                    s.encode() if isinstance(s, str) else bytes(s)
                    for s in tokens
                )
            )
            return LIB.tb_server_set_auth_tokens(self._srv, blob, len(blob)) == 0

        def _verify(_ud, data_ptr, data_len, ip, port, _auth=auth):
            try:
                cred = (
                    ctypes.string_at(data_ptr, data_len)
                    if data_ptr and data_len
                    else b""
                ).decode(errors="replace")
                remote = EndPoint(
                    ip=(ip or b"").decode(), port=int(port)
                )
                return 0 if _auth.verify_credential(cred, remote) else 1
            except Exception:
                logger.exception("native auth verifier raised; rejecting")
                return 1

        # keepalive: the CFUNCTYPE must outlive the C++ server
        self._auth_cb = AUTH_FN(_verify)
        return LIB.tb_server_set_auth(self._srv, self._auth_cb, None) == 0

    def set_native_max_concurrency(self, full_name: str, n: int) -> bool:
        """Runtime retune of a natively-registered method's admission
        limit (no-op False if the method is not native). Guarded against
        the stopped plane: a limiter update racing tb_server_destroy (a
        straggler completion after Server.stop) must not touch freed
        state."""
        with self._stats_lock:
            if self._srv is None:
                return False
            return (
                LIB.tb_server_set_native_max_concurrency(
                    self._srv, full_name.encode(), int(n)
                )
                == 0
            )

    def native_method_names(self) -> list:
        """Methods dispatched on the C++ plane (registration order)."""
        return list(self._native_names)

    def auto_limit_targets(self) -> list:
        """Natively-registered methods that follow the server-wide
        adaptive limit (no per-method limiter of their own)."""
        return list(self._auto_targets)

    def set_auto_limit_target(self, full_name: str, follow: bool) -> None:
        """Flip whether a native method follows the server-wide adaptive
        limit: a per-method limit set at runtime must STOP the server-wide
        pushes from clobbering it (and vice versa when cleared back to
        unlimited)."""
        if full_name not in self._native_names:
            return
        if follow and full_name not in self._auto_targets:
            self._auto_targets.append(full_name)
        elif not follow and full_name in self._auto_targets:
            self._auto_targets.remove(full_name)

    def native_max_concurrency(self, full_name: str) -> int:
        """Current native-plane limit; -1 = not natively registered (or
        the plane already stopped)."""
        with self._stats_lock:
            if self._srv is None:
                return -1
            return int(
                LIB.tb_server_get_native_max_concurrency(
                    self._srv, full_name.encode()
                )
            )

    def listen(self, ip: str, port: int) -> int:
        rc = LIB.tb_server_listen(self._srv, ip.encode(), port)
        if rc < 0:
            raise OSError(-rc, "tb_server_listen failed")
        self.port = rc
        # surface the C++ plane's counters as bvars (scraped from
        # /brpc_metrics and /vars like everything else); port-scoped names
        # since one process may run several native planes. Hidden at stop.
        self._m_stats = [
            PassiveStatus(
                (lambda _k=k: self._stats_snapshot()[_k]),
                name=f"native_plane_{self.port}_{k}",
            )
            for k in ("accepted", "native_reqs", "cb_frames", "handoffs",
                      "live_conns", "deadline_sheds", "auth_rejects")
        ]
        # the process-wide native_compress_bytes_saved / native_auth_rejects
        # gauges sum across live planes
        _live_planes.add(self)
        # per-reactor families (native_reactor_<port>_<i>_*): connection
        # shard occupancy, dispatched requests, and ring drops per
        # reactor — the roll-up above stays the per-port truth, these
        # make skewed sharding and a hot reactor visible.  The memoized
        # snapshot (the _stats_snapshot pattern) keeps one scrape to one
        # native read per reactor, with the three values per row taken
        # at the same instant.
        for i in range(self.num_reactors):
            self._m_stats.extend(
                PassiveStatus(
                    (lambda _i=i, _k=k: self._reactor_snapshot(_i)[_k]),
                    name=f"native_reactor_{self.port}_{i}_{k}",
                )
                for k in ("conns", "reqs", "dropped")
            )
            if self._telemetry:
                self._m_stats.append(
                    PassiveStatus(
                        (lambda _i=i: self._tel_drained_per[_i]),
                        name=f"native_reactor_{self.port}_{i}_drained",
                    )
                )
        if self._telemetry:
            self._m_stats.append(
                PassiveStatus(
                    self.telemetry_dropped,
                    name=f"native_plane_{self.port}_telemetry_dropped",
                )
            )
            self._m_stats.append(
                PassiveStatus(
                    lambda: self._tel_drained,
                    name=f"native_plane_{self.port}_telemetry_drained",
                )
            )
            # scrapes force a drain so /brpc_metrics and /vars see
            # completions recorded microseconds — not a drain interval —
            # ago; the background pump covers unscraped servers.  Both
            # hold only a WEAK reference to the plane: a started-then-
            # abandoned plane must stay collectable so the __del__ ->
            # stop() backstop can still fire (a bound-method hook in the
            # module-global list would pin it for process lifetime).
            import weakref

            from incubator_brpc_tpu.builtin import prometheus

            wr = weakref.ref(self)

            def _scrape_drain(_wr=wr):
                plane = _wr()
                if plane is not None:
                    plane.drain_telemetry()

            self._scrape_hook = _scrape_drain
            prometheus.register_scrape_hook(_scrape_drain)
            self._drain_thread = threading.Thread(
                target=_drain_pump,
                args=(wr, self._drain_stop),
                name=f"native-telemetry-{self.port}",
                daemon=True,
            )
            self._drain_thread.start()
        return rc

    # -- telemetry drain ---------------------------------------------------

    def telemetry_dropped(self) -> int:
        """Ring-overflow drop count, summed across every reactor's ring."""
        with self._stats_lock:
            if self._srv is None:
                return getattr(self, "_final_tel_dropped", 0)
            return int(LIB.tb_server_telemetry_dropped(self._srv))

    def reactor_stats(self, reactor: int) -> Dict[str, int]:
        """One reactor's live connections, natively-dispatched request
        count, and telemetry-ring drops (zeros after stop or for an
        out-of-range index)."""
        with self._stats_lock:
            if self._srv is None:
                final = getattr(self, "_final_reactor_stats", None)
                if final is not None and 0 <= reactor < len(final):
                    return final[reactor]
                return {"conns": 0, "reqs": 0, "dropped": 0}
            vals = [ctypes.c_uint64() for _ in range(3)]
            rc = LIB.tb_server_reactor_stats(
                self._srv, int(reactor), *[ctypes.byref(v) for v in vals]
            )
            if rc != 0:
                return {"conns": 0, "reqs": 0, "dropped": 0}
            return {
                "conns": vals[0].value,
                "reqs": vals[1].value,
                "dropped": vals[2].value,
            }

    # fabriclint: hotpath
    def drain_telemetry(self) -> int:
        """Pull every completed record off each reactor's C++ ring and
        fan it out: per-method latency summaries, sampled rpcz server
        spans, and limiter feedback (Server._on_native_completion).
        Batched PER RING (one reactor's records per numpy pass — still
        vectorized) with a per-reactor drained roll-up. Returns the
        record count. Serialized: the background pump, scrape hooks, and
        the stop-time flush never interleave batches."""
        if not self._telemetry:
            return 0
        total = 0
        # fabriclint: allow(hotpath-lock) consumer-side serialization: one acquisition per drain call (not per record), required by the single-consumer ring contract
        with self._tel_lock:
            # batch cap: a drain races live producers, and a scrape-path
            # caller must not spin forever against a sustained flood —
            # 256 batches (~1M records) per call ACROSS the rings, the
            # rest next cycle
            budget = 256
            # fabriclint: allow(hotpath-loop) iterates reactors (<=16), never records; per-ring batches bounded by the shared budget below
            for reactor in range(self.num_reactors):
                # fabriclint: allow(hotpath-loop) bounded by the shared 256-batch budget; per-RECORD work stays vectorized in _consume_records
                while budget > 0:
                    budget -= 1
                    # fabriclint: allow(hotpath-lock) guards the native handle against tb_server_destroy; once per 4096-record batch, not per record
                    with self._stats_lock:
                        if self._srv is None:
                            budget = 0
                            break
                        n = int(
                            LIB.tb_server_drain_telemetry_ring(
                                self._srv, reactor, self._tel_batch,
                                len(self._tel_batch),
                            )
                        )
                    if n <= 0:
                        break
                    # fan-out OUTSIDE _stats_lock: limiter feedback can
                    # push a new adaptive limit back down through
                    # set_native_max_concurrency, which takes _stats_lock
                    self._consume_records(self._tel_batch, n)
                    total += n
                    self._tel_drained_per[reactor] += n
                    # loop until an EMPTY return, not a short batch: the
                    # C++ drain can return fewer than it popped
                    # (clock-invalid records are discarded there), so a
                    # short batch does not mean the ring is dry
                if budget <= 0:
                    break
            self._tel_drained += total
        return total

    # the drain is on the clock: at full pump rate the ring produces
    # ~1 M records/s, so per-record Python costs are the difference
    # between a <5% and a ~50% instrumentation tax on a shared core —
    # everything per-record below is vectorized (numpy over the ctypes
    # batch buffer), with Python-level loops only over the FEW records
    # that matter individually (limiter samples, sampled spans)
    _REC_DTYPE = None  # numpy structured dtype mirror of TelemetryRecord

    @classmethod
    def _rec_dtype(cls):
        if cls._REC_DTYPE is None:
            import numpy as np

            cls._REC_DTYPE = np.dtype(
                [
                    ("method_idx", "<u4"),
                    ("error_code", "<u4"),
                    ("start_ns", "<u8"),
                    ("latency_ns", "<u8"),
                    ("correlation_id", "<u8"),
                    ("request_size", "<u4"),
                    ("response_size", "<u4"),
                    ("sampled", "<u4"),
                    ("reactor_id", "<u4"),
                    ("trace_id", "<u8"),
                    ("span_id", "<u8"),
                ]
            )
            assert cls._REC_DTYPE.itemsize == _TELEMETRY_RECORD_BYTES, (
                "telemetry drain dtype drifted from the 64-byte record ABI"
            )
        return cls._REC_DTYPE

    # fabriclint: hotpath
    def _consume_records(self, batch, n: int) -> None:
        import numpy as np

        from incubator_brpc_tpu.builtin import rpcz as rpcz_mod
        from incubator_brpc_tpu.rpc.concurrency_limiter import (
            AutoConcurrencyLimiter,
        )
        from incubator_brpc_tpu.utils.flags import get_flag
        from incubator_brpc_tpu.utils.status import ErrorCode as _EC

        arr = np.frombuffer(batch, dtype=self._rec_dtype(), count=n)
        names = self._native_names
        server = self._server
        method_ids = arr["method_idx"]
        errors = arr["error_code"]
        lat_us = arr["latency_ns"] * 1e-3
        ok = errors == 0
        # natively-shed requests (propagated deadline expired before
        # dispatch, recorded EDEADLINE in C++) feed the SAME global
        # counter the Python route's sheds increment — one
        # deadline_shed_count covers both planes (vectorized: one sum)
        nshed = int((errors == _EC.EDEADLINE).sum())
        if nshed:
            from incubator_brpc_tpu.rpc.server import deadline_shed_count

            deadline_shed_count << nshed
        server_lim = server._server_limiter
        server_auto = isinstance(server_lim, AutoConcurrencyLimiter)
        interval = int(get_flag("auto_cl_sampling_interval_us"))
        methods = server.methods()
        feed = []  # (done_us, full, error_code, latency_us) across methods
        # fabriclint: allow(hotpath-loop) iterates DISTINCT method indices (bounded by the native method table), never records
        for idx in np.unique(method_ids):
            if idx >= len(names):
                continue  # table drift (never expected): drop, don't crash
            full = names[idx]
            mask = method_ids == idx
            succ = mask & ok
            nsucc = int(succ.sum())
            if nsucc:
                # per-method latency summary: exact count/sum/max, a
                # strided subsample for the percentile reservoir
                recorder = self._tel_recorders.get(int(idx))
                if recorder is None:
                    recorder = LatencyRecorder()
                    base = (
                        "native_method_"
                        + full.replace(".", "_")
                        + "_latency_us"
                    )
                    # two native planes in one process can serve the same
                    # method name; expose() keeps the FIRST registrant
                    # and returns False — fall back to a port-scoped name
                    # instead of silently exposing nothing
                    if not recorder.expose(base):
                        recorder.expose(
                            f"native_method_{self.port}_"
                            + full.replace(".", "_")
                            + "_latency_us"
                        )
                    self._tel_recorders[int(idx)] = recorder
                vals = lat_us[succ]
                # ceil stride so the subsample spans the WHOLE batch
                # (floor would feed only the head when nsucc % 64 != 0)
                recorder.record_batch(
                    nsucc,
                    float(vals.sum()),
                    float(vals.max()),
                    vals[:: -(-nsucc // 64)][:64].tolist(),
                )
            # limiter feedback — only when an adaptive limiter is actually
            # listening (constant limits ignore on_responded entirely),
            # decimated to its sampling interval so a 100 k-record drain
            # feeds the handful of samples the limiter would keep anyway.
            # ELIMIT refusals are excluded like the Python route (a
            # refused request never reaches on_responded); deadline sheds
            # likewise — shed work never ran the method, so its "latency"
            # says nothing the limiter should adapt to.
            prop = methods.get(full)
            method_auto = prop is not None and isinstance(
                prop.status.limiter, AutoConcurrencyLimiter
            )
            if not (server_auto or method_auto):
                continue
            fb = mask & (errors != _EC.ELIMIT) & (errors != _EC.EDEADLINE)
            if not fb.any():
                continue
            done_us = (arr["start_ns"][fb] + arr["latency_ns"][fb]) // 1000
            fb_err = errors[fb]
            fb_lat = lat_us[fb]
            order = np.argsort(done_us, kind="stable")
            ts = done_us[order]
            picks = []
            i = 0
            step = max(1, interval)
            # fabriclint: allow(hotpath-loop) decimation walk: one searchsorted jump per limiter SAMPLE, capped at 1024 — O(picks log n), not O(records)
            while i < len(ts) and len(picks) < 1024:
                picks.append(order[i])
                i = int(np.searchsorted(ts, ts[i] + step, side="left"))
            # errors beyond the decimation still matter (all-fail
            # halving): force-feed a bounded number of them
            err_pos = np.flatnonzero(fb_err != 0)[:256]
            # fabriclint: allow(hotpath-loop) bounded by the decimated picks (1024) + forced errors (256), not by batch size
            for j in {int(p) for p in picks} | {int(p) for p in err_pos}:
                feed.append(
                    (int(done_us[j]), full, int(fb_err[j]), float(fb_lat[j]))
                )
        # ONE globally time-ordered feed across every method:
        # on_responded's pre-lock interval check keeps only
        # forward-moving timestamps, so feeding per-method sequences
        # back-to-back would let the first method's newest sample mask
        # every other method's older ones from the SHARED server limiter
        feed.sort()
        # fabriclint: allow(hotpath-loop) feed is the decimated limiter sample set (<=1280 per method), already bounded above
        for done, full, err, lat in feed:
            server._on_native_completion(full, err, lat, now_us=done)
        if rpcz_mod.rpcz_enabled():
            # bit 0 = sample election (local 1/N OR wire-forced)
            sampled_idx = np.flatnonzero(arr["sampled"] & _TEL_SAMPLE_BIT)
            if len(sampled_idx):
                # wall/monotonic anchor: record timestamps are
                # CLOCK_MONOTONIC ns, spans carry wall-clock start_real_us
                wall_anchor_us = time.time() * 1e6
                mono_anchor_ns = native.monotonic_ns()
                # fabriclint: allow(hotpath-loop) iterates 1/N sample-flagged + wire-forced records only (bounded well below batch size)
                for i in sampled_idx:
                    rec = arr[int(i)]
                    idx = int(rec["method_idx"])
                    if idx >= len(names):
                        continue
                    sampled_word = int(rec["sampled"])
                    forced = bool(sampled_word & _TEL_WIRE_FORCED)
                    # the 1/N flag elects; the shared token bucket still
                    # bounds spans/second (rpcz_samples_per_second) like
                    # every other producer — a ring-rate native flood
                    # must not turn the drain into a disk-append loop.
                    # Wire-FORCED records (the edge's head-based decision)
                    # ride through a dry bucket: coherent sampling means a
                    # trace sampled at the edge must not lose this hop —
                    # the edge's own limiter already bounded trace starts.
                    # CONTINUE (not break) past refused locally-elected
                    # records: a forced record later in the batch must
                    # still be scanned, or a dry bucket would tear the
                    # fleet trace this bit exists to keep coherent.
                    if not rpcz_mod._limiter.grab() and not forced:
                        continue
                    service, _, method = names[idx].partition(".")
                    codec = (sampled_word >> _TEL_CODEC_SHIFT) & 3
                    # wire trace context: parent the server span into the
                    # CALLER's trace (the caller's span id becomes this
                    # span's parent); fresh ids only when the wire
                    # carried none — a Dapper trace no longer breaks at a
                    # natively-dispatched hop
                    wire_trace = int(rec["trace_id"])
                    wire_span = int(rec["span_id"])
                    rpcz_mod.span_store.submit(
                        rpcz_mod.Span(
                            trace_id=wire_trace or rpcz_mod._new_id(),
                            span_id=rpcz_mod._new_id(),
                            parent_span_id=wire_span,
                            span_type=rpcz_mod.SPAN_TYPE_SERVER,
                            service=service,
                            method=method,
                            error_code=int(rec["error_code"]),
                            start_real_us=int(
                                wall_anchor_us
                                - (mono_anchor_ns - int(rec["start_ns"]))
                                / 1e3
                            ),
                            start_mono_ns=int(rec["start_ns"]),
                            latency_us=float(rec["latency_ns"]) / 1e3,
                            request_size=int(rec["request_size"]),
                            response_size=int(rec["response_size"]),
                            annotations=(
                                [(
                                    0.0,
                                    "compress="
                                    + _NATIVE_COMPRESS_NAMES.get(
                                        codec, str(codec)
                                    ),
                                )]
                                if codec
                                else []
                            ),
                        )
                    )

    def _reactor_snapshot(self, reactor: int) -> Dict[str, int]:
        """reactor_stats memoized for ~50 ms (the _stats_snapshot
        discipline): one scrape renders 3 gauges per reactor off ONE
        native read, and a row's values come from the same instant.
        Benign race on the cache slot — worst case one extra read."""
        now = time.monotonic()
        cache = getattr(self, "_reactor_snaps", None)
        if cache is None:
            cache = self._reactor_snaps = {}
        snap = cache.get(reactor)
        if snap is None or now - snap[0] > 0.05:
            snap = (now, self.reactor_stats(reactor))
            cache[reactor] = snap
        return snap[1]

    def _stats_snapshot(self) -> Dict[str, int]:
        """stats() memoized for ~50 ms: one /brpc_metrics scrape touches
        all five per-port gauges — a single native read feeds them all,
        and the five samples come from the same instant instead of five
        slightly different ones (benign race on the cache slot: worst
        case is one extra native read)."""
        now = time.monotonic()
        snap = self._stats_snap
        if snap is None or now - snap[0] > 0.05:
            snap = (now, self.stats())
            self._stats_snap = snap
        return snap[1]

    # -- callbacks from loop threads --------------------------------------

    def _sock_for(self, token: int) -> NativeConnSock:
        with self._socks_lock:
            s = self._socks.get(token)
            if s is None:
                s = NativeConnSock(token, self._server)
                self._socks[token] = s
            return s

    # fabriclint: hotpath
    def _on_frame(self, _ctx, token, cid_lo, cid_hi, flags, error_code,
                  meta_ptr, meta_len, body_h, cut_ns) -> None:
        # this thread holds the interpreter from here; cut_ns was read in
        # C++ before it asked for it
        entered_ns = time.monotonic_ns()
        from incubator_brpc_tpu.protocol.tbus_std import Meta, ParsedFrame

        try:
            # the body is ours: copied out below, then freed
            blen = LIB_HELD.tb_iobuf_size(body_h)
            meta_bytes = (
                ctypes.string_at(meta_ptr, meta_len) if meta_len else b""
            )
            is_prpc = bool(flags & _FLAG_WIRE_PRPC)
            if is_prpc:
                # baidu_std frame off the C++ cut loop: the meta is RpcMeta
                # proto bytes; responses must leave in PRPC, which
                # _send_response keys off frame.wire_protocol
                from incubator_brpc_tpu.protocol.baidu_std import (
                    RpcMeta,
                    rpc_meta_to_meta,
                )

                meta = rpc_meta_to_meta(RpcMeta.decode(meta_bytes))
            else:
                meta = Meta.from_bytes(meta_bytes)
            att = meta.attachment_size
            if att > blen:
                # consumed, unrecoverable: kill the connection (the Python
                # messenger's FatalParseError path)
                # fabriclint: allow(ffi-unchecked) the conn is being killed for a fatal parse; a stale token means it is already dead — both outcomes are the goal
                LIB.tb_conn_close(token)
                return
            payload = _copy_out(body_h, blen - att, 0)
            attachment = _copy_out(body_h, att, blen - att)
            frame = ParsedFrame(
                meta=meta,
                payload=payload,
                attachment=attachment,
                correlation_id=cid_lo | (cid_hi << 32),
                flags=flags & ~(_FLAG_WIRE_PRPC | _FLAG_CONN_AUTHED),
                error_code=error_code,
            )
            # the cut's own time on time.monotonic()'s clock, as the
            # Python messenger stamps it: the deadline-shed baseline
            # (Server.process_request measures mid-queue expiry from it)
            # and where device_transport_ingress_us starts
            frame.arrival_ts = cut_ns / 1e9
            frame.plane_callback_ns = entered_ns
            if is_prpc:
                frame.wire_protocol = "baidu_std"
            sock = self._sock_for(token)
            if flags & _FLAG_CONN_AUTHED:
                # the C++ plane already verified this connection's
                # credential: server_check must honor the cached verdict
                sock.context["authenticated"] = True
            self._dispatch(sock, frame)
        except Exception:
            logger.exception("native frame dispatch failed")
        finally:
            LIB_HELD.tb_iobuf_destroy(body_h)

    # fabriclint: hotpath
    def _dispatch(self, sock: NativeConnSock, frame) -> None:
        """Mirror of InputMessenger._process_one for pre-cut frames."""
        from incubator_brpc_tpu import protocol as proto_pkg

        if getattr(frame, "wire_protocol", None) == "baidu_std":
            from incubator_brpc_tpu.protocol.baidu_std import BAIDU_STD

            proto = BAIDU_STD
        else:
            proto = proto_pkg.TBUS_STD
        if frame.is_stream and proto.process_stream is not None:
            proto.process_stream(sock, frame)  # in wire order, inline
            return
        if frame.is_response:
            if proto.process_response is not None:
                proto.process_response(sock, frame)
            return
        if self._server.options.usercode_inline:
            self._server.process_request(sock, frame)
        else:
            from incubator_brpc_tpu.runtime.worker_pool import global_worker_pool

            global_worker_pool().spawn(
                self._server.process_request, sock, frame
            )

    def _on_handoff(self, _ctx, fd, buffered_ptr, buffered_len) -> None:
        """Connection speaking neither tbus_std nor baidu_std: wrap the fd
        in a real Python Socket so the full protocol scan (HTTP portal,
        nshead, redis...) runs exactly as with the Python acceptor."""
        try:
            data = (
                ctypes.string_at(buffered_ptr, buffered_len)
                if buffered_len
                else b""
            )
            conn = _pysocket.socket(fileno=fd)
            try:
                peer = conn.getpeername()
            except OSError:
                peer = None
            from incubator_brpc_tpu.transport.sock import Socket

            sock = Socket.from_accepted(
                conn,
                peer,
                messenger=self._server._messenger,
                context={"server": self._server},
                inline_read=self._server.options.usercode_inline,
                preread=data,
            )
            with self._socks_lock:
                self._handoff_socks.add(sock)
            # self-pruning: a dead handed-off connection must not pin its
            # Socket (and buffers) for the server's lifetime
            # fabriclint: allow(lifecycle-callback) self-pruning set hook on a handed-off connection this plane owns; plane stop closes the socks, firing it
            sock.on_failed.append(self._forget_handoff)
        except Exception:
            logger.exception("native handoff failed")

    def _forget_handoff(self, sock) -> None:
        with self._socks_lock:
            self._handoff_socks.discard(sock)

    def _on_closed(self, _ctx, token) -> None:
        with self._socks_lock:
            sock = self._socks.pop(token, None)
        if sock is not None:
            try:
                sock._mark_closed()
            except Exception:
                logger.exception("conn-closed hook raised")

    # -- lifecycle ---------------------------------------------------------

    def stop(self) -> None:
        if self._stopped:
            return
        self._stopped = True
        self._drain_stop.set()
        if self._drain_thread is not None:
            self._drain_thread.join(timeout=5.0)
            self._drain_thread = None
        if self._telemetry:
            from incubator_brpc_tpu.builtin import prometheus

            hook = getattr(self, "_scrape_hook", None)
            if hook is not None:
                prometheus.unregister_scrape_hook(hook)
                self._scrape_hook = None
        for v in getattr(self, "_m_stats", []):
            try:
                v.hide()  # free the port-scoped names for the next plane
            except Exception:
                pass
        # stop joins the loop threads, so no callback can be in flight when
        # destroy frees the epoll/event fds and the method table
        LIB.tb_server_stop(self._srv)
        self._final_stats = self.stats()
        self._final_reactor_stats = [
            self.reactor_stats(i) for i in range(self.num_reactors)
        ]
        self._final_compress = self.compress_stats()
        # fold the finals into the retired tallies so the process-wide
        # gauges keep this plane's contribution without double-counting
        global _retired_compress_saved, _retired_auth_rejects
        with _planes_tally_lock:
            if self in _live_planes:
                _live_planes.discard(self)
                fc = self._final_compress
                _retired_compress_saved += max(
                    0, fc["in_raw"] - fc["in_wire"]
                ) + max(0, fc["out_raw"] - fc["out_wire"])
                _retired_auth_rejects += self._final_stats.get(
                    "auth_rejects", 0
                )
        # loops quiescent: flush the telemetry tail so the last
        # completions still reach the summaries/limiters, THEN freeze the
        # drop counter (the flush itself can add clock-invalid discards)
        # and free the per-method summary names
        try:
            self.drain_telemetry()
            self._final_tel_dropped = self.telemetry_dropped()
        except Exception:
            logger.exception("final telemetry drain failed")
        for recorder in self._tel_recorders.values():
            try:
                recorder.hide()
            except Exception:
                pass
        with self._socks_lock:
            handoffs = list(self._handoff_socks)
            self._handoff_socks.clear()
        for sock in handoffs:
            try:
                sock.set_failed(ErrorCode.ECLOSE, "server stopped")
            except Exception:
                pass
        with self._socks_lock:
            socks, self._socks = list(self._socks.values()), {}
        for s in socks:
            s._mark_closed()
        with self._stats_lock:
            srv, self._srv = self._srv, None
        LIB.tb_server_destroy(srv)

    def stats(self) -> Dict[str, int]:
        with self._stats_lock:
            if self._srv is not None:
                vals = [ctypes.c_uint64() for _ in range(5)]
                LIB.tb_server_stats(
                    self._srv, *[ctypes.byref(v) for v in vals]
                )
                keys = (
                    "accepted", "native_reqs", "cb_frames", "handoffs",
                    "live_conns",
                )
                out = dict(zip(keys, (v.value for v in vals)))
                out["deadline_sheds"] = int(
                    LIB.tb_server_deadline_sheds(self._srv)
                )
                out["auth_rejects"] = int(
                    LIB.tb_server_auth_rejects(self._srv)
                )
                return out
        return getattr(
            self,
            "_final_stats",
            dict.fromkeys(
                ("accepted", "native_reqs", "cb_frames", "handoffs",
                 "live_conns", "deadline_sheds", "auth_rejects"),
                0,
            ),
        )

    def compress_stats(self) -> Dict[str, int]:
        """Native codec byte counters: request wire/raw bytes in,
        response raw/wire bytes out (the native_compress_bytes_saved
        feed)."""
        with self._stats_lock:
            if self._srv is None:
                return getattr(
                    self,
                    "_final_compress",
                    dict.fromkeys(
                        ("in_wire", "in_raw", "out_raw", "out_wire"), 0
                    ),
                )
            vals = [ctypes.c_uint64() for _ in range(4)]
            LIB.tb_server_compress_stats(
                self._srv, *[ctypes.byref(v) for v in vals]
            )
            return dict(
                zip(("in_wire", "in_raw", "out_raw", "out_wire"),
                    (v.value for v in vals))
            )

    def close_idle(self, idle_s: float) -> int:
        """Cull native connections with no read activity for ``idle_s``
        (Server's idle_timeout_s enforcement for native ports; the C++
        side shutdown()s, the owning loop reaps)."""
        with self._stats_lock:
            if self._srv is None:
                return 0
            return int(
                LIB.tb_server_close_idle(
                    self._srv, int(max(0.0, idle_s) * 1000)
                )
            )

    def pause_accept(self) -> None:
        """Lame-duck: close the listener while live connections keep
        being served (drained by the owner's grace window)."""
        with self._stats_lock:
            if self._srv is not None:
                LIB.tb_server_pause_accept(self._srv)

    def connection_count(self) -> int:
        with self._socks_lock:
            live_handoffs = sum(1 for s in self._handoff_socks if s.state == 0)
        return self.stats()["live_conns"] + live_handoffs

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


# process-global fault schedule for native CLIENT channels: armed on
# every subsequently-created NativeClientChannel while the
# ``fault_injection`` master flag is on (so rpc_press --fault-rate runs
# stay on the C++ plane instead of forcing the Python socket seam).
# Redials inherit it — an injected close heals into a re-armed channel,
# matching the Python seam's process-wide injector.
_native_client_fault = None


def install_native_client_fault(
    fail_every: int = 0,
    close_every: int = 0,
    delay_every: int = 0,
    delay_ms: int = 0,
    error_code: int = 0,
) -> None:
    """Install (or clear, with all zeros) the process-global native-client
    fault schedule (see tb_channel_set_fault). Deterministic counter
    scheduling like rpc/fault_injector.py; acts only behind the
    ``fault_injection`` master flag."""
    global _native_client_fault
    spec = (
        max(0, int(fail_every)),
        max(0, int(close_every)),
        max(0, int(delay_every)),
        max(0, int(delay_ms)),
        max(0, int(error_code)),
    )
    _native_client_fault = spec if any(spec[:3]) else None


class NativeClientChannel:
    """Client fast path over one shared native connection.

    ``protocol`` selects the wire format the C++ channel emits:
    "tbus_std" (default) or "baidu_std" — the latter sends wire-exact PRPC
    frames (header + proto2 RpcMeta) so the native client interop-tests
    byte-for-byte against protocol/baidu_std.py and against reference
    binaries."""

    _META_CACHE_MAX = 1024

    def __init__(
        self,
        ip: str,
        port: int,
        connect_timeout_ms: int = 5000,
        protocol: str = "tbus_std",
    ):
        if not NET_AVAILABLE:
            raise RuntimeError("native plane unavailable")
        if protocol not in _CH_PROTO:
            raise ValueError(f"unsupported native protocol {protocol!r}")
        err = ctypes.c_int(0)
        self._meta_cache: Dict[tuple, bytes] = {}
        self._lock = threading.Lock()
        self._closed = False
        self._inflight = 0  # calls inside C; destroy only when drained
        self._ch = LIB.tb_channel_connect(
            ip.encode(), port, connect_timeout_ms, ctypes.byref(err)
        )
        if not self._ch:
            raise OSError(err.value, f"connect {ip}:{port} failed")
        self.protocol = protocol
        if protocol != "tbus_std":
            if LIB.tb_channel_set_protocol(self._ch, _CH_PROTO[protocol]) != 0:
                # the C++ side refused the protocol id: the channel would
                # silently speak tbus_std — fail construction instead
                LIB.tb_channel_destroy(self._ch)
                self._ch = None
                raise ValueError(
                    f"native channel rejected protocol {protocol!r}"
                )
        # reusable per-thread response-meta buffer: a fresh 64 KB
        # create_string_buffer per call costs more than the whole native
        # round trip
        self._tls = threading.local()
        spec = _native_client_fault
        if spec is not None:
            from incubator_brpc_tpu.utils.flags import get_flag

            if get_flag("fault_injection"):
                self.set_fault(*spec)

    def healthy(self) -> bool:
        return not self._closed and LIB.tb_channel_error(self._ch) == 0

    @property
    def reactor(self) -> int:
        """Client reactor shard this channel pinned at connect — the top
        8 bits of every correlation id it mints (-1 once closed)."""
        with self._lock:
            if self._ch is None:
                return -1
            return int(LIB.tb_channel_reactor(self._ch))

    def cid_misroutes(self) -> int:
        """Responses seen with a WRONG shard tag in their correlation id
        (each answered EREQUEST to the re-tagged pending instead of
        crashing or stranding its caller)."""
        with self._lock:
            if self._ch is None:
                return 0
            return int(LIB.tb_channel_cid_misroutes(self._ch))

    def set_request_compress(self, name: str) -> None:
        """Channel-default request compress_type (baidu_std only): stamps
        RpcMeta field 3 on every request this channel emits.  The CALLER
        compresses payloads with the matching protocol/compress.py codec
        — the same algorithm the server's C++ table runs, so the planes
        stay byte-identical.  "" clears."""
        wire = _NATIVE_COMPRESS_WIRE.get(name, 0)
        if name and wire == 0:
            raise ValueError(f"codec {name!r} is not native-plane capable")
        if LIB.tb_channel_set_compress(self._ch, wire) != 0:
            raise RuntimeError("tb_channel_set_compress rejected the codec")

    def set_auth(self, credential) -> None:
        """Arm the connection's credential (RpcMeta field 7,
        authentication_data): stamped on requests until the first
        successful response proves the connection — the reference's
        first-request auth fight.  A redialed channel re-arms with a
        fresh credential."""
        data = (
            credential.encode()
            if isinstance(credential, str)
            else bytes(credential)
        )
        # fabriclint: allow(ffi-unchecked) current C++ always accepts; the credential is copied synchronously into the channel
        LIB.tb_channel_set_auth(self._ch, data, len(data))

    def set_fault(
        self,
        fail_every: int = 0,
        close_every: int = 0,
        delay_every: int = 0,
        delay_ms: int = 0,
        error_code: int = 0,
    ) -> None:
        """Arm the C++ channel's counter-scheduled fault seam
        (tb_channel_set_fault) — the native analog of the Python
        Socket.write injector: every Nth call fails/closes/delays,
        deterministically. 0 disables a schedule."""
        rc = LIB.tb_channel_set_fault(
            self._ch,
            max(0, int(fail_every)),
            max(0, int(close_every)),
            max(0, int(delay_every)),
            max(0, int(delay_ms)),
            max(0, int(error_code)),
        )
        if rc != 0:  # current C++ always accepts; guard future revs
            raise RuntimeError("tb_channel_set_fault rejected the schedule")

    def set_trace(
        self,
        trace_id: int,
        span_id: int = 0,
        parent_span_id: int = 0,
        log_id: int = 0,
        sampled: int = 1,
        every: int = 1,
    ) -> None:
        """Arm ambient trace context for the pipelined ``pump``
        (tb_channel_set_trace): every ``every``'th pump frame carries the
        Dapper fields in its RpcRequestMeta — counter-scheduled exact
        rate like the fault seam — with a distinct per-frame span id
        (``span_id + sequence``).  ``sampled=1`` is the head-based
        coherent-sampling election: every traced frame forces a span at
        every hop it touches.  baidu_std channels only; ``every=0``
        disarms."""
        rc = LIB.tb_channel_set_trace(
            self._ch,
            int(log_id) & ((1 << 64) - 1),
            int(trace_id) & ((1 << 64) - 1),
            int(span_id) & ((1 << 64) - 1),
            int(parent_span_id) & ((1 << 64) - 1),
            1 if sampled else 0,
            max(0, int(every)),
        )
        if rc != 0:
            raise ValueError(
                "traced pumps ride the PRPC wire: use protocol='baidu_std'"
            )

    def _meta_bytes(
        self,
        service: str,
        method: str,
        att_len: int,
        log_id: int = 0,
        trace_id: int = 0,
        span_id: int = 0,
        parent_span_id: int = 0,
        sampled: int = 0,
        timeout_ms: int = 0,
    ) -> bytes:
        traced = bool(
            log_id or trace_id or span_id or parent_span_id or sampled
        )
        # the propagated deadline (RpcRequestMeta field 8 / JSON
        # timeout_ms) joins the cache KEY, not the uncached path: clients
        # overwhelmingly reuse one configured timeout per channel, so the
        # steady state stays one dict hit per call
        if self.protocol == "baidu_std":
            # the RpcRequestMeta submessage only — correlation_id and
            # attachment_size live OUTSIDE it, spliced in by the C++
            # channel, so the cache key never depends on the attachment.
            # Traced calls (log_id / Dapper ids) build uncached: the ids
            # change per call and MUST reach the wire — the server parents
            # its span into the client's trace off them.
            from incubator_brpc_tpu.protocol.baidu_std import (
                encode_request_submeta,
            )

            if traced:
                return encode_request_submeta(
                    service, method, log_id, trace_id, span_id,
                    parent_span_id, timeout_ms=timeout_ms, sampled=sampled,
                )
            key = (service, method, timeout_ms)
            m = self._meta_cache.get(key)
            if m is None:
                m = encode_request_submeta(
                    service, method, timeout_ms=timeout_ms
                )
                if len(self._meta_cache) >= self._META_CACHE_MAX:
                    # overflow = one-shot keys flooded it (decrementing
                    # propagated deadlines mint a fresh timeout per call):
                    # clear rather than freeze, so hot configured-timeout
                    # keys re-cache immediately instead of never again
                    self._meta_cache.clear()
                self._meta_cache[key] = m
            return m
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        if traced or att_len:
            return Meta(
                service=service,
                method=method,
                timeout_ms=timeout_ms,
                log_id=log_id,
                trace_id=trace_id,
                span_id=span_id,
                parent_span_id=parent_span_id,
                sampled=sampled,
            ).to_bytes(attachment_size=att_len)
        key = (service, method, timeout_ms)
        m = self._meta_cache.get(key)
        if m is None:
            m = Meta(
                service=service, method=method, timeout_ms=timeout_ms
            ).to_bytes()
            if len(self._meta_cache) >= self._META_CACHE_MAX:
                self._meta_cache.clear()  # see the baidu_std branch
            self._meta_cache[key] = m
        return m

    def decode_resp_meta(self, resp_meta: bytes):
        """Response meta bytes -> framework Meta: JSON on tbus_std, RpcMeta
        proto bytes on baidu_std (the raw bytes tb_channel_call copied
        out)."""
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        if not resp_meta:
            return Meta()
        if self.protocol == "baidu_std":
            from incubator_brpc_tpu.protocol.baidu_std import (
                RpcMeta,
                rpc_meta_to_meta,
            )

            return rpc_meta_to_meta(RpcMeta.decode(resp_meta))
        return Meta.from_bytes(resp_meta)

    def call(
        self,
        service: str,
        method: str,
        payload: bytes,
        attachment: bytes = b"",
        timeout_ms: int = 500,
        log_id: int = 0,
        trace_id: int = 0,
        span_id: int = 0,
        parent_span_id: int = 0,
        sampled: int = 0,
        compress: str = "",
    ):
        """One native round trip. Returns (rc, err_code, resp_meta_bytes,
        body: IOBuf) — rc < 0 is a transport errno, err_code the server's
        RPC error. Nonzero log_id/trace_id/span_id/parent_span_id travel
        in the request meta exactly as the Python packers send them
        (Dapper propagation); ``sampled`` is the head-based coherent-
        sampling bit — set at the edge, it forces span collection at
        every downstream hop.  Traced frames STAY on the server's C++
        fast path (the cutter decodes the trace fields natively).
        ``compress`` (baidu_std only) names the codec the
        CALLER already compressed ``payload`` with — it rides the wire's
        compress_type; the response body comes back as wire bytes (the
        caller decompresses per the response meta)."""
        import errno as _errno

        from incubator_brpc_tpu.iobuf import IOBuf
        from incubator_brpc_tpu.protocol.tbus_std import FLAG_BODY_CRC
        from incubator_brpc_tpu.utils.flags import get_flag

        with self._lock:
            if self._closed:
                return -_errno.EPIPE, 0, b"", IOBuf()
            self._inflight += 1
        try:
            meta = self._meta_bytes(
                service, method, len(attachment), log_id, trace_id, span_id,
                parent_span_id, sampled,
                timeout_ms=(
                    max(1, int(timeout_ms))
                    if timeout_ms and timeout_ms > 0 else 0
                ),
            )
            if self.protocol == "baidu_std":
                # flags_extra carries the per-call compress_type in PRPC
                # mode (the tbus flag space is meaningless there); the
                # tbus body-crc flag must NOT leak into it
                flags = _NATIVE_COMPRESS_WIRE.get(compress, 0)
            else:
                flags = FLAG_BODY_CRC if get_flag("tbus_body_crc") else 0
            body = IOBuf()
            tls = self._tls
            try:
                meta_out = tls.meta_out
                meta_len = tls.meta_len
                err_code = tls.err_code
            except AttributeError:
                meta_out = tls.meta_out = ctypes.create_string_buffer(64 * 1024)
                meta_len = tls.meta_len = ctypes.c_uint32(0)
                err_code = tls.err_code = ctypes.c_uint32(0)
            t0 = time.perf_counter()
            rc = LIB.tb_channel_call(
                self._ch,
                meta,
                len(meta),
                payload,
                len(payload),
                attachment,
                len(attachment),
                flags,
                body._h,
                meta_out,
                64 * 1024,
                ctypes.byref(meta_len),
                ctypes.byref(err_code),
                int(timeout_ms) if timeout_ms and timeout_ms > 0 else 0,
            )
            native_client_calls << 1
            if rc < 0:
                native_client_errors << 1
            else:
                native_client_call_us << (time.perf_counter() - t0) * 1e6
            # string_at copies meta_len bytes; .raw[:n] would materialize
            # the whole 64 KiB scratch per call
            resp_meta = (
                ctypes.string_at(meta_out, meta_len.value)
                if meta_len.value
                else b""
            )
            return rc, err_code.value, resp_meta, body
        finally:
            destroy = False
            with self._lock:
                self._inflight -= 1
                destroy = self._closed and self._inflight == 0 and self._ch
                if destroy:
                    ch, self._ch = self._ch, None
            if destroy:
                LIB.tb_channel_destroy(ch)

    def pump(
        self,
        service: str,
        method: str,
        payload: bytes,
        n: int,
        inflight: int = 64,
        timeout_ms: int = 60000,
    ) -> float:
        """Pipelined native load run (example/rdma_performance client
        analog): n requests with `inflight` outstanding, entirely in C++.
        Returns ns/request. Requires exclusive use of this channel."""
        import errno as _errno

        with self._lock:
            if self._closed:
                raise OSError(_errno.EPIPE, "channel closed")
            self._inflight += 1
        try:
            meta = self._meta_bytes(service, method, 0)
            rc = LIB.tb_channel_pump(
                self._ch, meta, len(meta), payload, len(payload), n, inflight,
                timeout_ms,
            )
            if rc < 0:
                native_client_errors << 1
                raise OSError(-rc, "native pump failed")
            if self.protocol == "baidu_std":
                prpc_pump_ns << int(rc)
            else:
                native_pump_ns << int(rc)
            return float(rc)
        finally:
            destroy = False
            with self._lock:
                self._inflight -= 1
                destroy = self._closed and self._inflight == 0 and self._ch
                if destroy:
                    ch, self._ch = self._ch, None
            if destroy:
                LIB.tb_channel_destroy(ch)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._inflight > 0 or not self._ch:
                return  # last call out destroys
            ch, self._ch = self._ch, None
        LIB.tb_channel_destroy(ch)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
