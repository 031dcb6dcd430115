"""IOBuf + native runtime tests — the acceptance subset the reference keeps
in test/iobuf_unittest.cpp (share/cut semantics, refcounts via the
block_shared_count white-box hook, external-block release ordering) plus
region-pool and ResourcePool coverage."""

import errno
import os
import socket
import sys
import zlib

import pytest

from incubator_brpc_tpu import iobuf as iob
from incubator_brpc_tpu import native
from incubator_brpc_tpu.iobuf import _NativeIOBuf, _PyIOBuf

IMPLS = [_PyIOBuf] + ([_NativeIOBuf] if native.NATIVE_AVAILABLE else [])


def test_native_loaded():
    # The image bakes g++; the native path must be live in CI.
    assert native.NATIVE_AVAILABLE


@pytest.mark.parametrize("impl", IMPLS)
class TestIOBufSemantics:
    def test_append_roundtrip(self, impl):
        b = impl()
        b.append(b"hello ")
        b.append(b"world")
        assert len(b) == 11
        assert b.to_bytes() == b"hello world"

    def test_large_append_spans_blocks(self, impl):
        b = impl()
        data = os.urandom(50_000)  # > several 8 KB blocks
        b.append(data)
        assert len(b) == len(data)
        assert b.to_bytes() == data
        if impl is _NativeIOBuf:
            assert b.block_count >= 5

    def test_cutn_moves_bytes(self, impl):
        b = impl()
        b.append(b"abcdefghij")
        head = b.cutn(4)
        assert head.to_bytes() == b"abcd"
        assert b.to_bytes() == b"efghij"
        assert len(b) == 6

    def test_cut_more_than_size(self, impl):
        b = impl()
        b.append(b"xy")
        out = b.cutn(10)
        assert out.to_bytes() == b"xy"
        assert len(b) == 0

    def test_share_bumps_refcount_no_copy(self, impl):
        a = impl()
        a.append(b"shared-bytes")
        c = impl()
        c.append_iobuf(a)
        assert c.to_bytes() == b"shared-bytes"
        assert a.to_bytes() == b"shared-bytes"
        assert a.block_shared_count(0) == 2
        c.clear()
        assert a.block_shared_count(0) == 1

    def test_partial_cut_shares_block(self, impl):
        a = impl()
        a.append(b"0123456789")
        head = a.cutn(3)
        # both halves reference the same block
        assert head.block_shared_count(0) == 2
        assert a.block_shared_count(0) == 2
        assert head.to_bytes() == b"012"
        assert a.to_bytes() == b"3456789"

    def test_popn(self, impl):
        b = impl()
        b.append(b"0123456789")
        assert b.popn(4) == 4
        assert b.to_bytes() == b"456789"
        assert b.popn(100) == 6
        assert len(b) == 0

    def test_copy_to_with_pos(self, impl):
        b = impl()
        b.append(b"0123")
        b.append(b"4567")
        assert b.to_bytes(4, pos=2) == b"2345"
        assert len(b) == 8  # non-consuming

    def test_external_release_after_last_ref(self, impl):
        released = []
        buf = bytearray(b"external-payload")
        a = impl()
        a.append_external(buf, release_cb=lambda o: released.append(o))
        c = impl()
        c.append_iobuf(a)
        a.clear()
        assert released == []  # c still holds a ref
        c.clear()
        assert len(released) == 1
        assert released[0] is buf

    def test_external_zero_copy_read(self, impl):
        buf = bytearray(b"zcview")
        a = impl()
        a.append_external(buf)
        assert a.to_bytes() == b"zcview"
        views = a.views()
        assert b"".join(bytes(v) for v in views) == b"zcview"
        a.clear()

    def test_views_concat_equals_bytes(self, impl):
        b = impl()
        b.append(b"abc")
        b.append(os.urandom(20_000))
        total = b.to_bytes()
        assert b"".join(bytes(v) for v in b.views()) == total

    def test_append_after_cut_does_not_corrupt_shared_tail(self, impl):
        # Appending to `a` after sharing its tail block must never change
        # bytes already visible through the share (CAS-claim contract).
        a = impl()
        a.append(b"AAAA")
        c = impl()
        c.append_iobuf(a)
        a.append(b"BBBB")
        assert c.to_bytes() == b"AAAA"
        assert a.to_bytes() == b"AAAABBBB"

    def test_fd_roundtrip(self, impl):
        s1, s2 = socket.socketpair()
        try:
            out = impl()
            payload = os.urandom(100_000)
            out.append(payload)
            received = impl()
            while len(out) > 0:
                nw = out.cut_into_fd(s1.fileno())
                assert nw > 0
                while True:
                    nr = received.append_from_fd(s2.fileno(), 1 << 20)
                    if nr <= 0 or len(received) >= len(payload) - len(out):
                        break
            while len(received) < len(payload):
                nr = received.append_from_fd(s2.fileno(), 1 << 20)
                assert nr > 0
            assert received.to_bytes() == payload
        finally:
            s1.close()
            s2.close()

    def test_fd_eagain(self, impl):
        s1, s2 = socket.socketpair()
        try:
            s2.setblocking(False)
            got = impl()
            rc = got.append_from_fd(s2.fileno())
            assert rc == -errno.EAGAIN or rc == -errno.EWOULDBLOCK
        finally:
            s1.close()
            s2.close()


@pytest.mark.skipif(not native.NATIVE_AVAILABLE, reason="native only")
class TestNativeOnly:
    def test_crc32_matches_zlib(self):
        data = os.urandom(4096)
        assert native.crc32(data) == zlib.crc32(data) & 0xFFFFFFFF

    def test_fast_rand(self):
        vals = {native.fast_rand() for _ in range(64)}
        assert len(vals) > 60
        assert all(native.LIB.tb_fast_rand_less_than(10) < 10 for _ in range(100))

    def test_block_pool_reuses(self):
        b = _NativeIOBuf()
        b.append(os.urandom(64_000))
        mid = iob.block_pool_stats()
        assert mid["live"] >= 8  # 64 KB over 8 KB blocks
        b.clear()
        after = iob.block_pool_stats()
        # clear() parks blocks in the caches instead of freeing them
        assert after["cached"] > mid["cached"]
        assert after["live"] == mid["live"]

    def test_region_allocator_exhaust_and_reuse(self):
        slab = bytearray(4 * 1024)
        rid = iob.register_region(slab, 1024)
        assert rid >= 0
        assert iob.region_free_blocks(rid) == 4
        b = _NativeIOBuf()
        assert b.append_from_region(rid, b"x" * 3000)
        assert iob.region_free_blocks(rid) == 1
        # exhaustion: only 1 block (1024 B) left but 2000 B requested
        c = _NativeIOBuf()
        assert not c.append_from_region(rid, b"y" * 2000)
        c.clear()
        b.clear()
        assert iob.region_free_blocks(rid) == 4  # release returned blocks
        # region data actually lives in the caller's slab
        d = _NativeIOBuf()
        assert d.append_from_region(rid, b"Z" * 10)
        assert bytes(slab[:10]) == b"Z" * 10 or b"Z" * 10 in bytes(slab)
        d.clear()

    def test_resource_pool_versioned_ids(self):
        pool = native.ResourcePool(16)
        rid1 = pool.get()
        assert pool.address(rid1) is not None
        assert pool.live == 1
        assert pool.return_(rid1)
        assert pool.address(rid1) is None  # stale after return
        assert not pool.return_(rid1)  # double-return rejected
        rid2 = pool.get()
        # slot reused but version moved on — old id still dead (ABA-safe)
        assert (rid2 & 0xFFFFFFFF) == (rid1 & 0xFFFFFFFF)
        assert rid2 != rid1
        assert pool.address(rid1) is None
        assert pool.address(rid2) is not None

    def test_monotonic_ns_advances(self):
        t1 = native.monotonic_ns()
        t2 = native.monotonic_ns()
        assert t2 >= t1 > 0


# -- PR 49: which handle serves a call (the rule at native.LIB_HELD) ----------

LIMIT = native._HELD_COPY_MAX
HELD_SIZES = [0, 1, LIMIT, LIMIT + 1]
# the calls that touch a file descriptor: never through the held handle
FD_CALLS = {
    "tb_iobuf_cut_into_fd", "tb_iobuf_append_from_fd",
    "tb_iobuf_append_from_fd_bulk", "tb_conn_write",
}
# the calls that sleep or walk /proc (PR 51: the lock probe's tick and the
# processors by thread, which may run beside any test): never held either
BLOCKING_CALLS = {"tb_sleep_until_ns", "tb_task_times"}
# the calls whose work is their byte count, and the argument that gives it
SIZED_CALLS = {
    "tb_iobuf_append": lambda a: a[2],
    "tb_iobuf_append_from_region": lambda a: a[3],
    "tb_iobuf_copy_to": lambda a: a[2],
    "tb_tbus_pack": lambda a: a[4] + a[6],
    "tb_tbus_cut": lambda a: a[1]._obj.body_len,
    "tb_crc32": lambda a: a[2],
    "tb_crc32c": lambda a: a[2],
}


def pattern(n: int) -> bytes:
    return (bytes(range(251)) * (n // 251 + 1))[:n]


def filled(data: bytes) -> "_NativeIOBuf":
    b = _NativeIOBuf()
    b.append(data)
    return b


def through_a_socket(write, read):
    """What ``read(fd)`` gathers of what ``write(fd)`` sends, both on a
    non-blocking pair until the writer has nothing left."""
    s1, s2 = socket.socketpair()
    try:
        s1.setblocking(False)
        s2.setblocking(False)
        while True:
            rc = write(s1.fileno())
            while read(s2.fileno()) > 0:
                pass
            if rc <= 0 and rc not in (-errno.EAGAIN, -errno.EWOULDBLOCK):
                break  # 0: the writer has nothing left
    finally:
        s1.close()
        s2.close()


def _len(data):
    assert len(filled(data)) == len(data)
    return (len(filled(data)),)


def _block_count(data):
    return (filled(data).block_count,)


def _block_shared_count(data):
    a = filled(data)
    b = _NativeIOBuf()
    b.append_iobuf(a)
    return (a.block_shared_count(0), a.block_shared_count(a.block_count + 3))


def _append(data):
    b = filled(b"head")
    b.append(data)
    b.append(bytearray(b"tail"))
    b.append(memoryview(b"!"))
    assert b.to_bytes() == b"head" + data + b"tail!"
    return (b.to_bytes(), len(b), b.block_count)


def _append_external(data):
    released = []
    # an empty writable buffer cannot be wrapped: the empty owner is bytes
    owner = bytearray(data) or b""
    b = _NativeIOBuf()
    b.append_external(owner, released.append)
    seen = (b.to_bytes(), len(b), b.block_count, list(released))
    assert seen == (data, len(data), 1, [])
    b.clear()
    return seen + (len(released), released[0] is owner)


def _append_iobuf(data):
    a, b = filled(data), filled(b"to:")
    b.append_iobuf(a)
    assert (b.to_bytes(), a.to_bytes()) == (b"to:" + data, data)
    return (b.to_bytes(), len(b), b.block_count, len(a), a.to_bytes())


def _append_from_region(data):
    block = 16 << 10
    slab = bytearray(max(block, -(-len(data) // block) * block))
    rid = iob.register_region(slab, block)
    b = _NativeIOBuf()
    ok = b.append_from_region(rid, data)
    seen = (ok, b.to_bytes(), len(b), b.block_count, iob.region_free_blocks(rid))
    assert seen[:3] == (True, data, len(data))
    b.clear()
    return seen + (iob.region_free_blocks(rid), bytes(slab[:len(data)]) == data)


def _cutn(data):
    b = filled(data + b"rest")
    head = b.cutn(len(data))
    assert (head.to_bytes(), b.to_bytes()) == (data, b"rest")
    return (head.to_bytes(), len(head), head.block_count, b.to_bytes(), len(b))


def _cut_into(data):
    b, out = filled(data + b"rest"), filled(b">")
    moved = b.cut_into(out, len(data))
    assert (moved, out.to_bytes(), b.to_bytes()) == (len(data), b">" + data, b"rest")
    return (moved, out.to_bytes(), len(out), b.to_bytes(), b.block_count)


def _popn(data):
    b = filled(data + b"rest")
    seen = (b.popn(len(data)), b.to_bytes(), len(b), b.block_count, b.popn(99), len(b))
    assert seen[:3] + seen[4:] == (len(data), b"rest", 4, 4, 0)
    return seen


def _clear(data):
    b = filled(data)
    b.clear()
    b.clear()
    return (len(b), b.block_count, b.to_bytes())


def _to_bytes(data):
    b = filled(data)
    n = len(data)
    assert (b.to_bytes(), b.to_bytes(n // 2, n // 3)) == (data, data[n // 3:][:n // 2])
    return (b.to_bytes(), b.to_bytes(n), b.to_bytes(n + 7), b.to_bytes(n // 2, n // 3),
            b.to_bytes(None, n), b.to_bytes(0), len(b), b.block_count)


def _views(data):
    b = filled(data)
    views = b.views()
    assert b"".join(bytes(v) for v in views) == data
    return (b"".join(bytes(v) for v in views), len(views), all(v.readonly for v in views))


def _cut_into_fd(data):
    b, got = filled(data), _PyIOBuf()
    through_a_socket(b.cut_into_fd, got.append_from_fd)
    assert got.to_bytes() == data
    return (got.to_bytes(), len(b), b.block_count)


def _append_from_fd(data):
    src, b = _PyIOBuf(), _NativeIOBuf()
    src.append(data)
    through_a_socket(src.cut_into_fd, b.append_from_fd)
    assert b.to_bytes() == data
    return (b.to_bytes(), len(b), b.block_count)


def _append_from_fd_bulk(data):
    src, b = _PyIOBuf(), _NativeIOBuf()
    src.append(data)
    through_a_socket(
        src.cut_into_fd, lambda fd: b.append_from_fd_bulk(fd, 1 << 20, 256 << 10))
    assert b.to_bytes() == data
    return (b.to_bytes(), len(b))


def _del(data):
    released = []
    before = iob.block_pool_stats()["live"]
    b = filled(data)
    b.append_external(bytearray(b"owned"), released.append)
    del b
    return (len(released), iob.block_pool_stats()["live"] <= before + 9)


METHODS = {
    "__len__": _len, "block_count": _block_count,
    "block_shared_count": _block_shared_count, "append": _append,
    "append_external": _append_external, "append_iobuf": _append_iobuf,
    "append_from_region": _append_from_region, "cutn": _cutn,
    "cut_into": _cut_into, "popn": _popn, "clear": _clear,
    "to_bytes": _to_bytes, "views": _views, "cut_into_fd": _cut_into_fd,
    "append_from_fd": _append_from_fd,
    "append_from_fd_bulk": _append_from_fd_bulk, "__del__": _del,
}


class Recording:
    """A library handle that notes every call made through it."""

    def __init__(self, lib):
        self._lib, self.calls = lib, []

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self.calls.append((name, args))
            return fn(*args)

        return call

    def names(self):
        return [name for name, _ in self.calls]


def serve_with(monkeypatch, lib, held):
    """Make ``lib`` and ``held`` the two handles every caller of the rule
    sees."""
    from incubator_brpc_tpu.protocol import tbus_std

    monkeypatch.setattr(native, "LIB", lib)
    monkeypatch.setattr(native, "LIB_HELD", held)
    for module in (iob, tbus_std):
        monkeypatch.setattr(module, "LIB", lib)
        monkeypatch.setattr(module, "LIB_HELD", held)


@pytest.mark.skipif(not native.NATIVE_AVAILABLE, reason="native only")
class TestWhichHandleServesACall:
    def test_the_limit_has_one_home(self):
        import subprocess

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        found = subprocess.run(
            ["grep", "-rnE", r"^\s*_HELD_COPY_MAX\s*=", "incubator_brpc_tpu"],
            cwd=root, capture_output=True, text=True).stdout.strip().splitlines()
        assert len(found) == 1 and found[0].startswith(
            "incubator_brpc_tpu/native.py:"), found
        assert native.lib_for(LIMIT) is native.LIB_HELD
        assert native.lib_for(LIMIT + 1) is native.LIB
        assert native.lib_for(0) is native.LIB_HELD

    @pytest.mark.parametrize("n", HELD_SIZES)
    @pytest.mark.parametrize("method", sorted(METHODS))
    def test_a_method_answers_alike_whichever_handle_serves_it(
        self, monkeypatch, method, n
    ):
        """The same bytes, sizes and block counts by the rule, with every
        call releasing the lock (the parent's way), and with every call but
        an fd's keeping it."""
        data = pattern(n)
        by_rule = METHODS[method](data)
        serve_with(monkeypatch, native.LIB, native.LIB)
        all_released = METHODS[method](data)
        monkeypatch.undo()
        lib, held = native.LIB, native.LIB_HELD

        class FdOnly:  # the released handle, for the calls on an fd alone
            def __getattr__(self, name):
                return getattr(lib if name in FD_CALLS else held, name)

        serve_with(monkeypatch, FdOnly(), held)
        all_held = METHODS[method](data)
        monkeypatch.undo()
        assert by_rule == all_released == all_held

    def frame_trip(self, n, over_a_socket):
        """Pack a frame of ``n`` payload bytes, write it, read it, cut it."""
        from incubator_brpc_tpu.protocol.tbus_std import (
            FLAG_RESPONSE, Meta, pack_frame_iobuf, parse_frame_iobuf)

        payload = pattern(n)
        out = pack_frame_iobuf(
            Meta(service="tensor", method="echo"), payload, 0x1_0000_0007,
            flags=FLAG_RESPONSE)
        total = len(out)  # as Socket._enqueue and _drain_once ask
        rbuf = iob.IOBuf()
        if over_a_socket:
            s1, s2 = socket.socketpair()
            try:
                assert out.cut_into_fd(s1.fileno(), 4 << 20) == total
                assert len(out) == 0
                assert rbuf.append_from_fd(s2.fileno(), iob.read_burst_bytes()) == total
            finally:
                s1.close()
                s2.close()
        else:
            rbuf.append_iobuf(out)
        assert len(rbuf) == total
        frame, consumed = parse_frame_iobuf(rbuf, max_total=64 << 20)
        assert consumed == total and len(rbuf) == 0
        assert frame.payload == payload and frame.correlation_id == 0x1_0000_0007
        assert frame.meta.method == "echo" and frame.is_response
        del out, rbuf, frame

    def test_a_256_byte_frame_lets_the_lock_go_on_the_fd_alone(self, monkeypatch):
        lib, held = Recording(native.LIB), Recording(native.LIB_HELD)
        serve_with(monkeypatch, lib, held)
        self.frame_trip(256, over_a_socket=True)
        monkeypatch.undo()
        # (the lock probe's reading of the processors may fall beside it)
        assert [name for name in lib.names() if name not in BLOCKING_CALLS] == [
            "tb_iobuf_cut_into_fd", "tb_iobuf_append_from_fd"]
        assert {"tb_tbus_pack", "tb_tbus_peek", "tb_tbus_cut", "tb_iobuf_copy_to",
                "tb_iobuf_create", "tb_iobuf_size", "tb_iobuf_destroy"} <= set(
                    held.names())

    @pytest.mark.parametrize("n", [256, LIMIT - 64, LIMIT + 1, 1 << 20])
    def test_nothing_on_an_fd_or_over_the_limit_keeps_the_lock(self, monkeypatch, n):
        lib, held = Recording(native.LIB), Recording(native.LIB_HELD)
        serve_with(monkeypatch, lib, held)
        self.frame_trip(n, over_a_socket=n < LIMIT)
        assert native.crc32c(pattern(n)) == native._crc32c_py(pattern(n))
        monkeypatch.undo()
        assert not (FD_CALLS | BLOCKING_CALLS) & set(held.names())
        for name, args in held.calls:
            if name in SIZED_CALLS:
                assert SIZED_CALLS[name](args) <= LIMIT, (name, n)
        for name, args in lib.calls:
            assert (name in FD_CALLS | BLOCKING_CALLS
                    or SIZED_CALLS[name](args) > LIMIT), (name, n)
        if n > LIMIT:
            # the cut, the copy and the pack of a long frame do release
            assert {"tb_tbus_pack", "tb_tbus_cut", "tb_iobuf_copy_to"} <= set(
                lib.names())

    def test_the_adders_count_a_frame_packed_and_a_frame_cut(self):
        from incubator_brpc_tpu.protocol import tbus_std

        def counts():
            return (tbus_std.m_frames_held.get_value(),
                    tbus_std.m_frames_released.get_value())

        h0, r0 = counts()
        self.frame_trip(256, over_a_socket=True)
        h1, r1 = counts()
        assert (h1 - h0, r1 - r0) == (2, 0)
        self.frame_trip(1 << 20, over_a_socket=False)
        h2, r2 = counts()
        assert (h2 - h1, r2 - r1) == (0, 2)
        # a payload at the limit: its pack keeps the lock, its cut, longer
        # by the meta, lets it go
        self.frame_trip(LIMIT, over_a_socket=False)
        h3, r3 = counts()
        assert (h3 - h2, r3 - r2) == (1, 1)

    @pytest.mark.parametrize("how", ["destroy", "clear", "popn"])
    def test_external_blocks_dropped_from_two_threads_release_once(self, how):
        """The release callback re-enters the interpreter on the thread
        that holds its lock: no deadlock under the held handle, and each
        owner released exactly once, wherever its last reference drops."""
        import threading

        per_thread, released, lock = 200, [], threading.Lock()

        def release(owner):
            with lock:  # a Python lock taken inside the native call
                released.append(bytes(owner[:8]))

        shared = [_NativeIOBuf() for _ in range(2)]

        def worker(t):
            for i in range(per_thread):
                b = _NativeIOBuf()
                b.append(b"pooled bytes before")
                b.append_external(bytearray(b"%d:%06d" % (t, i)), release)
                b.append_external(bytearray(b"%d+%06d" % (t, i)), release)
                if i % 3 == 0:
                    # the other thread's chain holds a reference too: the
                    # last drop may happen there
                    shared[1 - t].append_iobuf(b)
                if how == "destroy":
                    del b
                elif how == "clear":
                    b.clear()
                else:
                    assert b.popn(len(b)) > 0

        threads = [threading.Thread(target=worker, args=(t,), daemon=True)
                   for t in range(2)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)  # the two meet inside the calls
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads), "deadlock"
        for chain in shared:
            if how == "destroy":
                chain.__del__()
            elif how == "clear":
                chain.clear()
            else:
                chain.popn(len(chain))
        assert len(released) == 2 * 2 * per_thread
        assert len(set(released)) == len(released)
