"""ctypes bindings for the native runtime (src/tbutil → libtbutil.so).

The data plane of the host runtime is C++ (SURVEY.md §2 rules out Python
stand-ins for L1): blocks, refcounts, vectored fd IO, regions, and the
versioned-id resource pool all live in native code; Python holds opaque
handles. If the shared library is missing it is built on demand with
`make -C src` (g++ is baked into the image); `NATIVE_AVAILABLE` reports
whether the native path loaded, and iobuf.py provides a pure-Python
fallback so the package stays importable on a toolchain-less host.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# TBNET_LIB points the loader at an alternate build of the same ABI — the
# sanitizer harness (tools/fabriclint/san.py) sets it to the ASAN/TSAN
# .so; an override is never auto-built (a missing path must fail loudly
# into the pure-Python fallback, not silently rebuild the plain lib).
_LIB_OVERRIDE = os.environ.get("TBNET_LIB") or None
_LIB_PATH = _LIB_OVERRIDE or os.path.join(
    _REPO_ROOT, "src", "build", "libtbutil.so"
)

_lib = None
_lib_lock = threading.Lock()


class _Ref(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p), ("length", ctypes.c_size_t)]


class TbusHdr(ctypes.Structure):
    """Mirror of tb_tbus_hdr (src/tbutil/tbutil.h)."""

    _fields_ = [
        ("body_len", ctypes.c_uint32),
        ("flags", ctypes.c_uint32),
        ("cid_lo", ctypes.c_uint32),
        ("cid_hi", ctypes.c_uint32),
        ("meta_len", ctypes.c_uint32),
        ("crc", ctypes.c_uint32),
        ("error_code", ctypes.c_uint32),
    ]


class TelemetryRecord(ctypes.Structure):
    """Mirror of tb_telemetry_record (src/tbnet/tbnet.h): one completion
    record per natively-dispatched request, drained in batches."""

    _fields_ = [
        ("method_idx", ctypes.c_uint32),
        ("error_code", ctypes.c_uint32),
        ("start_ns", ctypes.c_uint64),
        ("latency_ns", ctypes.c_uint64),
        ("correlation_id", ctypes.c_uint64),
        ("request_size", ctypes.c_uint32),
        ("response_size", ctypes.c_uint32),
        ("sampled", ctypes.c_uint32),
        ("reactor_id", ctypes.c_uint32),
        # wire-propagated trace context (0 = the request carried none):
        # the drain parents the server span into the caller's trace
        ("trace_id", ctypes.c_uint64),
        ("span_id", ctypes.c_uint64),
    ]


RELEASE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_void_p)

# tbnet callbacks (src/tbnet/tbnet.h): the per-frame Python route and the
# protocol-sniff connection handoff
FRAME_FN = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,  # ctx
    ctypes.c_uint64,  # conn token
    ctypes.c_uint32,  # cid_lo
    ctypes.c_uint32,  # cid_hi
    ctypes.c_uint32,  # flags
    ctypes.c_uint32,  # error_code
    ctypes.c_void_p,  # meta ptr
    ctypes.c_size_t,  # meta len
    ctypes.c_void_p,  # body tb_iobuf* (ownership transfers)
    ctypes.c_uint64,  # cut_ns: CLOCK_MONOTONIC when the frame was cut
)
HANDOFF_FN = ctypes.CFUNCTYPE(
    None,
    ctypes.c_void_p,  # ctx
    ctypes.c_int,  # fd (ownership transfers)
    ctypes.c_void_p,  # buffered bytes
    ctypes.c_size_t,  # buffered len
)
CLOSED_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_uint64)
# credential verifier (tb_server_set_auth): int (*)(void* ud,
# const char* auth_data, size_t auth_len, const char* peer_ip, int port)
# — auth_data is a raw pointer (may contain NULs), hence c_void_p + len
AUTH_FN = ctypes.CFUNCTYPE(
    ctypes.c_int,
    ctypes.c_void_p,  # ud
    ctypes.c_void_p,  # auth data ptr
    ctypes.c_size_t,  # auth data len
    ctypes.c_char_p,  # peer ip (NUL-terminated textual)
    ctypes.c_int,  # peer port
)


# The declared C ABI: name -> (restype, argtypes), one entry per
# extern "C" function in src/tbutil/tbutil.h and src/tbnet/tbnet.h.
# Module-level (not hidden inside _declare) so fabriclint's FFI checker
# (tools/fabriclint/ffi_check.py) can cross-check every entry against the
# parsed headers — count, width, and signedness drift here corrupts
# silently at runtime, so it must fail loudly at lint time instead.
b = ctypes.c_void_p  # shorthand: any opaque native handle
SIGNATURES = {
    "tb_set_block_size": (None, [ctypes.c_size_t]),
    "tb_block_size": (ctypes.c_size_t, []),
    "tb_block_pool_stats": (
        None,
        [ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_size_t)],
    ),
    "tb_iobuf_read_burst": (ctypes.c_size_t, []),
    "tb_iobuf_create": (b, []),
    "tb_iobuf_handle_pool_stats": (
        None,
        [ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_size_t)],
    ),
    "tb_iobuf_destroy": (None, [b]),
    "tb_iobuf_clear": (None, [b]),
    "tb_iobuf_size": (ctypes.c_size_t, [b]),
    "tb_iobuf_block_count": (ctypes.c_size_t, [b]),
    "tb_iobuf_append": (None, [b, ctypes.c_char_p, ctypes.c_size_t]),
    "tb_iobuf_append_external": (
        None,
        [b, ctypes.c_void_p, ctypes.c_size_t, RELEASE_FN, ctypes.c_void_p],
    ),
    "tb_iobuf_append_iobuf": (None, [b, b]),
    "tb_iobuf_cutn": (ctypes.c_size_t, [b, b, ctypes.c_size_t]),
    "tb_iobuf_popn": (ctypes.c_size_t, [b, ctypes.c_size_t]),
    "tb_iobuf_copy_to": (
        ctypes.c_size_t,
        [b, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t],
    ),
    "tb_iobuf_refs": (ctypes.c_int, [b, ctypes.POINTER(_Ref), ctypes.c_int]),
    "tb_iobuf_block_shared_count": (ctypes.c_int, [b, ctypes.c_size_t]),
    "tb_iobuf_cut_into_fd": (
        ctypes.c_long,
        [b, ctypes.c_int, ctypes.c_size_t],
    ),
    "tb_iobuf_append_from_fd": (
        ctypes.c_long,
        [b, ctypes.c_int, ctypes.c_size_t],
    ),
    "tb_iobuf_append_from_fd_bulk": (
        ctypes.c_long,
        [b, ctypes.c_int, ctypes.c_size_t, ctypes.c_size_t],
    ),
    "tb_region_register": (
        ctypes.c_int,
        [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t],
    ),
    "tb_iobuf_append_from_region": (
        ctypes.c_int,
        [b, ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t],
    ),
    "tb_region_free_blocks": (ctypes.c_size_t, [ctypes.c_int]),
    # ---- a dispatch's operand (transport/device.py _stack_rows) ----
    "tb_stack_rows": (
        ctypes.c_int,
        [
            b,  # dst
            ctypes.c_size_t,  # rows
            ctypes.c_size_t,  # row_bytes
            ctypes.POINTER(ctypes.c_void_p),  # srcs
            ctypes.POINTER(ctypes.c_size_t),  # lens
            ctypes.c_size_t,  # n
        ],
    ),
    "tb_crc32": (
        ctypes.c_uint32,
        [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t],
    ),
    "tb_crc32c": (
        ctypes.c_uint32,
        [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t],
    ),
    "tb_iobuf_crc32c": (
        ctypes.c_uint32,
        [b, ctypes.c_uint32, ctypes.c_size_t, ctypes.c_size_t],
    ),
    "tb_tbus_peek": (ctypes.c_int, [b, ctypes.POINTER(TbusHdr)]),
    "tb_tbus_cut": (
        ctypes.c_int,
        [b, ctypes.POINTER(TbusHdr), ctypes.c_void_p, b],
    ),
    "tb_tbus_pack": (
        None,
        [
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_int,
        ],
    ),
    "tb_fast_rand": (ctypes.c_uint64, []),
    "tb_fast_rand_less_than": (ctypes.c_uint64, [ctypes.c_uint64]),
    "tb_monotonic_ns": (ctypes.c_uint64, []),
    "tb_sleep_until_ns": (ctypes.c_uint64, [ctypes.c_uint64]),
    "tb_task_times": (
        ctypes.c_long,
        [ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_long],
    ),
    "tb_respool_create": (b, [ctypes.c_size_t]),
    "tb_respool_destroy": (None, [b]),
    "tb_respool_get": (b, [b, ctypes.POINTER(ctypes.c_uint64)]),
    "tb_respool_address": (b, [b, ctypes.c_uint64]),
    "tb_respool_return": (ctypes.c_int, [b, ctypes.c_uint64]),
    "tb_respool_live": (ctypes.c_size_t, [b]),
    "tb_objpool_create": (b, [ctypes.c_size_t]),
    "tb_objpool_destroy": (None, [b]),
    "tb_objpool_get": (b, [b]),
    "tb_objpool_return": (None, [b, ctypes.c_void_p]),
    "tb_objpool_live": (ctypes.c_size_t, [b]),
    "tb_objpool_free_count": (ctypes.c_size_t, [b]),
    "tb_flatmap_create": (b, [ctypes.c_size_t]),
    "tb_flatmap_destroy": (None, [b]),
    "tb_flatmap_insert": (
        ctypes.c_int,
        [b, ctypes.c_uint64, ctypes.c_uint64],
    ),
    "tb_flatmap_get": (
        ctypes.c_int,
        [b, ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)],
    ),
    "tb_flatmap_erase": (ctypes.c_int, [b, ctypes.c_uint64]),
    "tb_flatmap_size": (ctypes.c_size_t, [b]),
    "tb_flatmap_capacity": (ctypes.c_size_t, [b]),
    "tb_cimap_create": (b, [ctypes.c_size_t]),
    "tb_cimap_destroy": (None, [b]),
    "tb_cimap_set": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
         ctypes.c_size_t],
    ),
    "tb_cimap_get": (
        ctypes.c_long,
        [b, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
         ctypes.c_size_t],
    ),
    "tb_cimap_erase": (ctypes.c_int, [b, ctypes.c_char_p, ctypes.c_size_t]),
    "tb_cimap_size": (ctypes.c_size_t, [b]),
    "tb_cimap_key_at": (
        ctypes.c_long,
        [b, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t],
    ),
    "tb_mru_create": (b, [ctypes.c_size_t]),
    "tb_mru_destroy": (None, [b]),
    "tb_mru_put": (ctypes.c_int, [b, ctypes.c_uint64, ctypes.c_uint64]),
    "tb_mru_get": (
        ctypes.c_int,
        [b, ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)],
    ),
    "tb_mru_size": (ctypes.c_size_t, [b]),
    # ---- tbnet (src/tbnet): native network plane ----
    "tb_server_create": (b, [ctypes.c_int]),
    "tb_server_num_reactors": (ctypes.c_int, [b]),
    # work-stealing dispatch pool (per-reactor Chase–Lev deques; worker
    # threads steal) + the per-method long-running deferral flag
    "tb_server_set_dispatch_pool": (ctypes.c_int, [b, ctypes.c_int]),
    "tb_server_set_native_long_running": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_int],
    ),
    "tb_server_set_frame_cb": (None, [b, FRAME_FN, ctypes.c_void_p]),
    "tb_server_set_handoff_cb": (None, [b, HANDOFF_FN, ctypes.c_void_p]),
    "tb_server_set_closed_cb": (None, [b, CLOSED_FN, ctypes.c_void_p]),
    "tb_server_set_max_body": (None, [b, ctypes.c_size_t]),
    # production-shaped traffic knobs: response-compression floor,
    # decompress-bomb ceiling, and the auth seam (verifier callback or
    # constant-time token table; rejects answered ERPCAUTH natively)
    "tb_server_set_compress_min_bytes": (None, [b, ctypes.c_size_t]),
    "tb_server_set_max_decompress": (None, [b, ctypes.c_size_t]),
    "tb_server_set_auth": (ctypes.c_int, [b, AUTH_FN, ctypes.c_void_p]),
    "tb_server_set_auth_tokens": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_size_t],
    ),
    "tb_server_auth_rejects": (ctypes.c_uint64, [b]),
    "tb_server_compress_stats": (
        None,
        [b] + [ctypes.POINTER(ctypes.c_uint64)] * 4,
    ),
    "tb_server_get_native_max_concurrency": (
        ctypes.c_long,
        [b, ctypes.c_char_p],
    ),
    "tb_server_set_native_max_concurrency": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_uint32],
    ),
    "tb_server_register_native": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_int, ctypes.c_uint32],
    ),
    # user C callback methods: int (*)(void* ud, const char* req,
    # size_t len, char** resp, size_t* resp_len) — the fn pointer is
    # passed as a raw void* (dlsym'd from a user .so, or a ctypes
    # CFUNCTYPE cast down)
    # fabriclint: allow(ffi-callback) fn arrives as a dlsym'd void* from a user .so by design; its layout contract is NATIVE_METHOD_FN, checked against the tb_native_fn typedef globally
    "tb_server_register_native_fn": (
        ctypes.c_int,
        [b, ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
         ctypes.c_uint32],
    ),
    # completion-record telemetry ring (per-method latency / rpcz /
    # limiter feedback for natively-dispatched requests)
    "tb_server_set_telemetry": (
        None,
        [b, ctypes.c_uint32, ctypes.c_uint32],
    ),
    "tb_server_drain_telemetry": (
        ctypes.c_long,
        [b, ctypes.POINTER(TelemetryRecord), ctypes.c_size_t],
    ),
    # one reactor's ring only (the per-ring batched drain's shape)
    "tb_server_drain_telemetry_ring": (
        ctypes.c_long,
        [b, ctypes.c_int, ctypes.POINTER(TelemetryRecord), ctypes.c_size_t],
    ),
    "tb_server_telemetry_dropped": (ctypes.c_uint64, [b]),
    # per-reactor live_conns / native_reqs / ring drops
    "tb_server_reactor_stats": (
        ctypes.c_int,
        [b, ctypes.c_int] + [ctypes.POINTER(ctypes.c_uint64)] * 3,
    ),
    "tb_server_listen": (ctypes.c_int, [b, ctypes.c_char_p, ctypes.c_int]),
    "tb_server_port": (ctypes.c_int, [b]),
    "tb_server_stop": (None, [b]),
    "tb_server_destroy": (None, [b]),
    "tb_server_stats": (
        None,
        [b] + [ctypes.POINTER(ctypes.c_uint64)] * 5,
    ),
    "tb_server_deadline_sheds": (ctypes.c_uint64, [b]),
    # lame-duck: stop accepting while live connections drain
    "tb_server_pause_accept": (None, [b]),
    # idle reap for native ports (returns connections culled)
    "tb_server_close_idle": (ctypes.c_long, [b, ctypes.c_uint64]),
    "tb_conn_respond": (
        ctypes.c_int,
        [
            ctypes.c_uint64,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_uint32,
        ],
    ),
    "tb_conn_write": (ctypes.c_int, [ctypes.c_uint64, b]),
    "tb_conn_peer": (
        ctypes.c_int,
        [ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t],
    ),
    "tb_conn_close": (ctypes.c_int, [ctypes.c_uint64]),
    # cache a Python-route auth verdict on the C++ conn (fast-path reuse)
    "tb_conn_set_authenticated": (ctypes.c_int, [ctypes.c_uint64]),
    "tb_channel_connect": (
        b,
        [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
         ctypes.POINTER(ctypes.c_int)],
    ),
    # wire protocol: 0 = tbus_std (default), 1 = baidu_std (PRPC);
    # must be set before the first send
    "tb_channel_set_protocol": (ctypes.c_int, [b, ctypes.c_int]),
    # channel-default request compress_type (RpcMeta field 3; caller
    # compresses payloads) and the first-request credential (field 7)
    "tb_channel_set_compress": (ctypes.c_int, [b, ctypes.c_int]),
    "tb_channel_set_auth": (
        ctypes.c_int,
        [b, ctypes.c_void_p, ctypes.c_size_t],
    ),
    # counter-scheduled client fault injection (fail/close/delay every
    # Nth call; the native analog of the Socket.write seam)
    "tb_channel_set_fault": (
        ctypes.c_int,
        [b] + [ctypes.c_uint32] * 5,
    ),
    # ambient trace context for the pipelined pump: every Nth frame
    # carries the Dapper fields (counter-scheduled, exact-rate like the
    # fault seam), span_id incremented per traced frame
    "tb_channel_set_trace": (
        ctypes.c_int,
        [b] + [ctypes.c_uint64] * 4 + [ctypes.c_int, ctypes.c_uint32],
    ),
    "tb_channel_call": (
        ctypes.c_long,
        [
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_uint32,
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
        ],
    ),
    "tb_channel_send": (
        ctypes.c_uint64,
        [
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_int),
        ],
    ),
    "tb_channel_recv": (
        ctypes.c_long,
        [
            b,
            ctypes.POINTER(ctypes.c_uint64),
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int,
        ],
    ),
    "tb_channel_error": (ctypes.c_int, [b]),
    # client reactor shard pinned at connect + wrong-shard cid counter
    "tb_channel_reactor": (ctypes.c_int, [b]),
    "tb_channel_cid_misroutes": (ctypes.c_uint64, [b]),
    "tb_channel_destroy": (None, [b]),
    "tb_channel_pump": (
        ctypes.c_long,
        [
            b,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
        ],
    ),
    # ---- codec table (protocol/compress.py prefers these over its
    # pure-Python twins so both planes run the identical codec) ----
    "tb_codec_compress": (
        ctypes.c_long,
        [ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t, b],
    ),
    "tb_codec_decompress": (
        ctypes.c_long,
        [ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t, b],
    ),
    # ---- RpcMeta scanner (differential-testing surface): the server cut
    # path's proto2 scanner over one meta blob, so the wire-decoder fuzz
    # (tests/test_wire_differential.py) diffs it against baidu_std's
    # pure-Python decoder on identical bytes ----
    "tb_scan_prpc_meta": (
        ctypes.c_long,
        [
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
            # trace out-params (RpcRequestMeta 3/4/5/6 + field-9 sampled)
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
        ],
    ),
    # ---- work-stealing deque (Chase–Lev; the dispatch pool's queue) ----
    "tb_wsq_create": (b, [ctypes.c_size_t]),
    "tb_wsq_destroy": (None, [b]),
    "tb_wsq_push": (ctypes.c_int, [b, ctypes.c_uint64]),
    "tb_wsq_pop": (ctypes.c_int, [b, ctypes.POINTER(ctypes.c_uint64)]),
    "tb_wsq_steal": (ctypes.c_int, [b, ctypes.POINTER(ctypes.c_uint64)]),
    "tb_wsq_size": (ctypes.c_long, [b]),
}
del b


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    for name, (restype, argtypes) in SIGNATURES.items():
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes
    return lib


def _build() -> bool:
    src_dir = os.path.join(_REPO_ROOT, "src")
    if not os.path.isdir(src_dir):
        return False
    try:
        subprocess.run(
            ["make", "-C", src_dir],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except subprocess.CalledProcessError as e:
        import logging

        logging.getLogger(__name__).warning(
            "building libtbutil.so failed; using the pure-Python plane:\n%s",
            (e.stderr or b"").decode(errors="replace")[-2000:],
        )
        return False
    except (OSError, subprocess.SubprocessError):
        return False
    return os.path.exists(_LIB_PATH)


def load():
    """Load (building on demand) and return the declared CDLL, or None."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_LIB_PATH):
            # an override must exist as given: building the PLAIN lib
            # here would burn ~a minute producing a .so the override
            # path will never load
            if _LIB_OVERRIDE is not None or not _build():
                return None
        try:
            _lib = _declare(ctypes.CDLL(_LIB_PATH))
        except OSError:
            return None
        except AttributeError:
            # Stale prebuilt .so missing a newer symbol. The library is
            # already dlopen'd into THIS process (ctypes never dlcloses and
            # dlopen dedupes by path), so a rebuild cannot help until the
            # next interpreter: rebuild for that one, fall back to pure
            # Python now instead of crashing package import.
            import logging

            logging.getLogger(__name__).warning(
                "libtbutil.so is stale (missing symbol); rebuilding for the "
                "next process and using the pure-Python fallback in this one"
            )
            if _LIB_OVERRIDE is None:  # never rebuild over an override
                _build()
            return None
        return _lib


LIB = load()
NATIVE_AVAILABLE = LIB is not None
# The same library through ctypes.PyDLL, whose calls keep the interpreter
# lock. A ctypes.CDLL call gives the lock up and queues for it again behind
# every other thread of the process: with 16 handler threads that turn
# costs ~0.5 ms on the chip's host (PERF.md, PR 27), a thousand times the
# work of a size, a few hundred bytes copied or a free. So this file is the
# one home of a rule, and iobuf.py, protocol/tbus_std.py,
# transport/native_plane.py and transport/device.py are its callers: **a
# native call that cannot block and whose work is bounded keeps the lock;
# only a call that touches a file descriptor, or copies or checksums more
# than _HELD_COPY_MAX bytes, lets it go.** What must hold for a call made
# through LIB_HELD (audited against src/tbutil, PR 49;
# docs/OBSERVABILITY.md): it takes no native
# lock that a thread can hold while it waits for the interpreter (the
# block cache's, the regions', the object pools' and the flat map's are
# leaf mutexes around a few pointer moves, and a release callback is
# called outside all of them); it never blocks on I/O; its work is
# O(references of a chain) or at most the limit in bytes.
LIB_HELD = _declare(ctypes.PyDLL(_LIB_PATH)) if NATIVE_AVAILABLE else None
# a copy or a checksum longer than this gives the interpreter lock up
# while it runs (~10 us of memcpy: far under a hand-over)
_HELD_COPY_MAX = 1 << 16


def lib_for(nbytes: int):
    """The handle for a call that copies or checksums ``nbytes``: the one
    that keeps the interpreter lock up to the limit, the one that lets it
    go beyond."""
    return LIB_HELD if nbytes <= _HELD_COPY_MAX else LIB


def monotonic_ns() -> int:
    if LIB is not None:
        return LIB.tb_monotonic_ns()
    import time

    return time.monotonic_ns()


def fast_rand() -> int:
    if LIB is not None:
        return LIB.tb_fast_rand()
    import random

    return random.getrandbits(64)


def crc32(data: bytes, seed: int = 0) -> int:
    if LIB is not None:
        return lib_for(len(data)).tb_crc32(seed, data, len(data))
    import zlib

    return zlib.crc32(data, seed) & 0xFFFFFFFF


_CRC32C_TABLE = None


def _crc32c_py(data, seed: int = 0) -> int:
    """Table-driven CRC32C for the no-native fallback (slow; only runs when
    libtbutil could not be built). Same chaining contract as tb_crc32c."""
    global _CRC32C_TABLE
    if _CRC32C_TABLE is None:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else c >> 1
            table.append(c)
        _CRC32C_TABLE = table
    crc = ~seed & 0xFFFFFFFF
    tab = _CRC32C_TABLE
    for byte in bytes(data):
        crc = tab[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    return ~crc & 0xFFFFFFFF


def crc32c(data, seed: int = 0) -> int:
    """CRC32C (Castagnoli; SSE4.2-accelerated when native). zlib-style
    chaining: pass the previous return value as ``seed``."""
    if LIB is not None:
        return lib_for(len(data)).tb_crc32c(seed, bytes(data), len(data))
    return _crc32c_py(data, seed)


class ResourcePool:
    """Versioned-id slab (src/tbutil ResourcePool; reference
    resource_pool.h:24-83). Ids stay stale-detectable forever."""

    def __init__(self, item_size: int = 8):
        if LIB is None:
            raise RuntimeError("native runtime unavailable")
        self._p = LIB.tb_respool_create(item_size)

    def get(self) -> int:
        out = ctypes.c_uint64()
        LIB.tb_respool_get(self._p, ctypes.byref(out))
        return out.value

    def address(self, rid: int):
        return LIB.tb_respool_address(self._p, rid)

    def return_(self, rid: int) -> bool:
        return LIB.tb_respool_return(self._p, rid) == 0

    @property
    def live(self) -> int:
        return LIB.tb_respool_live(self._p)

    def __del__(self):
        p, self._p = getattr(self, "_p", None), None
        if p and LIB is not None:
            LIB.tb_respool_destroy(p)


class ObjectPool:
    """Pointer-addressed fixed-size object slab (src/tbutil ObjectPool;
    reference object_pool.h). Memory never returns to the OS."""

    def __init__(self, item_size: int = 8):
        if LIB is None:
            raise RuntimeError("native runtime unavailable")
        self._p = LIB.tb_objpool_create(item_size)

    def get(self) -> int:
        return LIB.tb_objpool_get(self._p) or 0

    def return_(self, item: int) -> None:
        LIB.tb_objpool_return(self._p, item)

    @property
    def live(self) -> int:
        return LIB.tb_objpool_live(self._p)

    @property
    def free_count(self) -> int:
        return LIB.tb_objpool_free_count(self._p)

    def __del__(self):
        p, self._p = getattr(self, "_p", None), None
        if p and LIB is not None:
            LIB.tb_objpool_destroy(p)


class FlatMap:
    """Native open-addressing u64→u64 map (src/tbutil FlatMap; reference
    containers/flat_map.h) — the hot-path id table for native transports."""

    def __init__(self, initial_capacity: int = 16):
        if LIB is None:
            raise RuntimeError("native runtime unavailable")
        self._m = LIB.tb_flatmap_create(initial_capacity)
        if not self._m:
            raise MemoryError("tb_flatmap_create failed")

    def __setitem__(self, key: int, value: int) -> None:
        if LIB.tb_flatmap_insert(self._m, key, value) < 0:
            raise MemoryError("flatmap grow failed")

    def get(self, key: int, default=None):
        # one probe under a leaf mutex, once a request on the server's
        # method table: by the rule at LIB_HELD it keeps the lock
        out = ctypes.c_uint64()
        if LIB_HELD.tb_flatmap_get(self._m, key, ctypes.byref(out)):
            return out.value
        return default

    def __getitem__(self, key: int) -> int:
        v = self.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def __contains__(self, key: int) -> bool:
        return LIB.tb_flatmap_get(self._m, key, None) == 1

    def __delitem__(self, key: int) -> None:
        if not LIB.tb_flatmap_erase(self._m, key):
            raise KeyError(key)

    def __len__(self) -> int:
        return LIB.tb_flatmap_size(self._m)

    @property
    def capacity(self) -> int:
        return LIB.tb_flatmap_capacity(self._m)

    def __del__(self):
        m, self._m = getattr(self, "_m", None), None
        if m and LIB is not None:
            LIB.tb_flatmap_destroy(m)


class CaseIgnoredMap:
    """Native case-ignored string map (src/tbutil tb_cimap; reference
    CaseIgnoredFlatMap, containers/case_ignored_flat_map.h — the HTTP
    header table type). Keys compare case-insensitively; stored keys keep
    their original spelling."""

    def __init__(self, initial_capacity: int = 16):
        if LIB is None:
            raise RuntimeError("native runtime unavailable")
        self._m = LIB.tb_cimap_create(initial_capacity)
        if not self._m:
            raise MemoryError("tb_cimap_create failed")

    @staticmethod
    def _b(s) -> bytes:
        return s.encode("latin-1") if isinstance(s, str) else bytes(s)

    def __setitem__(self, key, value) -> None:
        k, v = self._b(key), self._b(value)
        if LIB.tb_cimap_set(self._m, k, len(k), v, len(v)) < 0:
            raise MemoryError("cimap set failed")

    def get(self, key, default=None):
        k = self._b(key)
        n = LIB.tb_cimap_get(self._m, k, len(k), None, 0)
        while True:
            if n < 0:
                return default
            if n == 0:
                return ""
            buf = ctypes.create_string_buffer(n)
            m = LIB.tb_cimap_get(self._m, k, len(k), buf, n)
            if m == n:
                return buf.raw.decode("latin-1")
            n = m  # value replaced between the probe and the copy: retry

    def __getitem__(self, key):
        v = self.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def __contains__(self, key) -> bool:
        k = self._b(key)
        return LIB.tb_cimap_get(self._m, k, len(k), None, 0) >= 0

    def __delitem__(self, key) -> None:
        k = self._b(key)
        if not LIB.tb_cimap_erase(self._m, k, len(k)):
            raise KeyError(key)

    def __len__(self) -> int:
        return LIB.tb_cimap_size(self._m)

    def keys(self):
        out = []
        i = 0
        buf = ctypes.create_string_buffer(256)
        while True:
            n = LIB.tb_cimap_key_at(self._m, i, buf, 256)
            if n < 0:
                return out
            if n <= 256:
                out.append(buf.raw[:n].decode("latin-1"))
            else:  # key longer than the scratch: refetch until stable
                while True:
                    big = ctypes.create_string_buffer(n)
                    m = LIB.tb_cimap_key_at(self._m, i, big, n)
                    if m < 0:
                        break  # entry vanished mid-iteration
                    if m <= n:
                        out.append(big.raw[:m].decode("latin-1"))
                        break
                    n = m
            i += 1

    def __del__(self):
        m, self._m = getattr(self, "_m", None), None
        if m and LIB is not None:
            LIB.tb_cimap_destroy(m)


class MRUCache:
    """Native bounded u64→u64 MRU cache (src/tbutil tb_mru; reference
    MRUCache, containers/mru_cache.h): get/put freshen the entry, inserts
    past capacity evict the least-recently-used one."""

    def __init__(self, capacity: int):
        if LIB is None:
            raise RuntimeError("native runtime unavailable")
        self._m = LIB.tb_mru_create(capacity)
        if not self._m:
            raise MemoryError("tb_mru_create failed")

    def put(self, key: int, value: int) -> bool:
        """True when the key already existed (value replaced)."""
        return LIB.tb_mru_put(self._m, key, value) == 1

    def get(self, key: int, default=None):
        out = ctypes.c_uint64()
        if LIB.tb_mru_get(self._m, key, ctypes.byref(out)):
            return out.value
        return default

    def __contains__(self, key: int) -> bool:
        return LIB.tb_mru_get(self._m, key, None) == 1

    def __len__(self) -> int:
        return LIB.tb_mru_size(self._m)

    def __del__(self):
        m, self._m = getattr(self, "_m", None), None
        if m and LIB is not None:
            LIB.tb_mru_destroy(m)
