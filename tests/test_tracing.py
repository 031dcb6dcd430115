"""Distributed tracing on the native plane (ISSUE 15).

Covers the tentpole end to end:

- **fast path**: traced PRPC frames (RpcRequestMeta fields 3-6 + the
  field-9 sampled bit) are decoded by the C++ cutter and answered
  without the interpreter — ``cb_frames == 0`` under a traced flood;
- **wire byte-identity**: a ``NativeClientChannel`` traced request is
  byte-identical to ``baidu_std.pack_request`` with the same fields,
  and the native and Python server planes answer a traced request with
  identical bytes;
- **coherent sampling**: the head-based sampled bit rides the wire and
  overrides local election (token bucket AND the telemetry ring's 1/N);
- **drain parenting**: sampled native completions join the CALLER's
  trace (fresh ids only when the wire carried none);
- **fleet assembly**: client → server A → server B (B in a REAL second
  process) yields one trace id with parent→child links across all
  hops, pulled from both nodes by ``rpc_view --trace --targets``;
- **collective sessions**: every party's session span carries the
  proposer's trace id;
- the ``SpanStore.by_trace`` index (satellite 1) and the /hotspots
  503-with-retry hardening (satellite 6).
"""

from __future__ import annotations

import os
import socket
import struct
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from incubator_brpc_tpu.protocol import baidu_std  # noqa: E402
from incubator_brpc_tpu.protocol.tbus_std import Meta  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel,
    ChannelOptions,
    Controller,
    Server,
    ServerOptions,
)
from incubator_brpc_tpu.transport import native_plane  # noqa: E402
from incubator_brpc_tpu.transport.native_plane import (  # noqa: E402
    NativeClientChannel,
    native_echo,
)

pytestmark = pytest.mark.skipif(
    not native_plane.NET_AVAILABLE, reason="native runtime unavailable"
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def native_server():
    created = []

    def make(services=None, options=None):
        opts = options or ServerOptions(
            native_plane=True, usercode_inline=True
        )
        opts.native_plane = True
        srv = Server(opts)
        for name, handlers in (services or {}).items():
            srv.add_service(name, handlers)
        created.append(srv)
        assert srv.start(0)
        assert srv._native_plane is not None
        return srv

    yield make
    for srv in created:
        srv.stop()


@pytest.fixture
def clean_spans():
    from incubator_brpc_tpu.builtin.rpcz import span_store

    span_store.clear()
    yield span_store
    span_store.clear()


def _read_prpc_frame(sock: socket.socket, buf: bytes = b"") -> bytes:
    while True:
        if len(buf) >= 12:
            total = 12 + struct.unpack(">I", buf[4:8])[0]
            if len(buf) >= total:
                return buf[:total]
        data = sock.recv(65536)
        assert data, "connection closed mid-frame"
        buf += data


TRACE_META = dict(
    log_id=7, trace_id=0x1F00DBEEF, span_id=0xABCDEF, parent_span_id=0x77,
    sampled=1,
)


class TestTracedWireByteIdentity:
    """Satellite: traced frames are byte-identical across the planes."""

    def test_native_client_traced_request_matches_pack_request(self):
        # capture the native client's traced request bytes on a raw
        # fake server; the call itself times out (never answered) —
        # only the emitted frame matters here
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]
        nch = NativeClientChannel("127.0.0.1", port, protocol="baidu_std")
        try:
            rc, _err, _m, _b = nch.call(
                "svc", "echo", b"traced-payload", attachment=b"AT",
                timeout_ms=200, **TRACE_META,
            )
            assert rc < 0  # timed out: nobody answered
            conn, _ = lst.accept()
            conn.settimeout(5)
            wire = _read_prpc_frame(conn)
            conn.close()
        finally:
            nch.close()
            lst.close()
        # the cid is the channel's to mint: decode it, then the WHOLE
        # frame must equal the Python packer's output for those fields
        rm = baidu_std.RpcMeta.decode(
            wire[12:12 + struct.unpack(">I", wire[8:12])[0]]
        )
        assert rm.trace_id == TRACE_META["trace_id"]
        assert rm.span_id == TRACE_META["span_id"]
        assert rm.parent_span_id == TRACE_META["parent_span_id"]
        assert rm.log_id == TRACE_META["log_id"]
        assert rm.sampled == 1
        expected = baidu_std.pack_request(
            # timeout_ms: the native client stamps the propagated
            # deadline (field 8) from the call's budget — part of the
            # byte-identical submessage
            Meta(service="svc", method="echo", timeout_ms=200, **TRACE_META),
            b"traced-payload",
            correlation_id=rm.correlation_id,
            attachment=b"AT",
        )
        assert wire == expected

    def test_native_and_python_servers_answer_traced_identically(
        self, native_server
    ):
        req = baidu_std.pack_request(
            Meta(service="svc", method="echo", **TRACE_META),
            b"traced", correlation_id=55,
        )

        def roundtrip(port):
            s = socket.create_connection(("127.0.0.1", port))
            try:
                s.settimeout(10)
                s.sendall(req)
                return _read_prpc_frame(s)
            finally:
                s.close()

        nsrv = native_server({"svc": {"echo": native_echo}})
        native_resp = roundtrip(nsrv.port)
        stats = nsrv._native_plane.stats()
        assert stats["native_reqs"] >= 1 and stats["cb_frames"] == 0, (
            "a traced request fell off the interpreter-free plane"
        )
        psrv = Server(ServerOptions(usercode_inline=True))
        psrv.add_service("svc", {"echo": native_echo})
        assert psrv.start(0)
        try:
            python_resp = roundtrip(psrv.port)
        finally:
            psrv.stop()
        assert native_resp == python_resp

    def test_traced_tbus_frame_stays_native(self, native_server):
        # the tbus JSON scanner decodes the same keys natively
        srv = native_server({"svc": {"echo": native_echo}})
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{srv.port}",
            options=ChannelOptions(native_plane=True),  # tbus_std wire
        )
        cntl = Controller()
        cntl.trace_id = 0x5151
        cntl.span_id = 0x52
        cntl.trace_sampled = 1
        c = ch.call_method("svc", "echo", b"t", cntl=cntl)
        assert c.ok(), c.error_text
        stats = srv._native_plane.stats()
        assert stats["native_reqs"] >= 1
        assert stats["cb_frames"] == 0


class TestTracedFloodStaysNative:
    """Acceptance: a traced PRPC flood is interpreter-free — the pump's
    counter-scheduled traced template included."""

    def test_traced_pump_zero_cb_frames(self, native_server, tuned_flags,
                                        clean_spans):
        tuned_flags("enable_rpcz", True)
        srv = native_server({"svc": {"echo": native_echo}})
        nch = NativeClientChannel("127.0.0.1", srv.port, protocol="baidu_std")
        try:
            nch.set_trace(
                trace_id=0xF00D, span_id=100, parent_span_id=9,
                sampled=1, every=1,
            )
            nch.pump("svc", "echo", b"x" * 64, 3000, inflight=32)
        finally:
            nch.close()
        stats = srv._native_plane.stats()
        assert stats["native_reqs"] >= 3000
        assert stats["cb_frames"] == 0
        srv._native_plane.drain_telemetry()
        spans = clean_spans.by_trace(0xF00D)
        # every frame carried the sampled bit; spans are bounded only by
        # the ring (drops under a full-rate pump are the documented
        # overflow discipline), so SOME — typically most — survive
        assert len(spans) > 100
        # per-frame distinct span ids parent the server spans
        assert len({sp.parent_span_id for sp in spans}) == len(spans)

    def test_traced_pump_close_to_bare_pump(self, native_server,
                                            tuned_flags):
        # same-run ratio gate with a deliberately generous bound: the
        # tripwire is the catastrophic regression — traced frames falling
        # back to the interpreter route is a >10x cliff, so 2x catches it
        # through shared-container noise without flaking
        tuned_flags("enable_rpcz", False)  # isolate the wire/record cost
        srv = native_server({"svc": {"echo": native_echo}})
        nch = NativeClientChannel("127.0.0.1", srv.port, protocol="baidu_std")
        try:
            nch.pump("svc", "echo", b"x" * 64, 2000, inflight=64)  # warm
            bare = min(
                nch.pump("svc", "echo", b"x" * 64, 20000, inflight=64)
                for _ in range(3)
            )
            nch.set_trace(trace_id=0xBEE, span_id=1, sampled=1, every=1)
            traced = min(
                nch.pump("svc", "echo", b"x" * 64, 20000, inflight=64)
                for _ in range(3)
            )
        finally:
            nch.close()
        assert srv._native_plane.stats()["cb_frames"] == 0
        assert traced < bare * 2.0, (
            f"traced pump {traced:.0f} ns vs bare {bare:.0f} ns — traced "
            "traffic is no longer near the fast path"
        )

    def test_set_trace_rejected_on_tbus_channel(self, native_server):
        srv = native_server({"svc": {"echo": native_echo}})
        nch = NativeClientChannel("127.0.0.1", srv.port)  # tbus_std
        try:
            with pytest.raises(ValueError):
                nch.set_trace(trace_id=1, every=1)
        finally:
            nch.close()


class TestCoherentSampling:
    """The head-based sampled bit overrides every local election."""

    def test_wire_sampled_bit_overrides_ring_election(
        self, native_server, tuned_flags, clean_spans
    ):
        # local 1/N election effectively off (huge N): only the wire
        # bit can sample — and it must, on every traced request
        tuned_flags("enable_rpcz", True)
        tuned_flags("native_telemetry_sample_every", 1_000_000)
        srv = native_server({"svc": {"echo": native_echo}})
        nch = NativeClientChannel("127.0.0.1", srv.port, protocol="baidu_std")
        try:
            for i in range(50):
                rc, err, _m, _b = nch.call(
                    "svc", "echo", b"x", trace_id=0xCAFE, span_id=i + 1,
                    sampled=1, timeout_ms=2000,
                )
                assert rc >= 0 and err == 0
            # unsampled traced calls: ids propagate, no forced span
            for i in range(50):
                rc, err, _m, _b = nch.call(
                    "svc", "echo", b"x", trace_id=0xD00D, span_id=i + 1,
                    timeout_ms=2000,
                )
                assert rc >= 0 and err == 0
        finally:
            nch.close()
        srv._native_plane.drain_telemetry()
        assert len(clean_spans.by_trace(0xCAFE)) == 50
        assert len(clean_spans.by_trace(0xD00D)) == 0
        assert srv._native_plane.stats()["cb_frames"] == 0

    def test_forced_records_survive_refused_elected_ones(
        self, native_server, tuned_flags, clean_spans
    ):
        # regression (review find): with the token bucket dry, a
        # locally-ELECTED record ahead of a wire-FORCED one in the same
        # drain batch must not end the scan — the forced span still
        # submits (continue, not break)
        tuned_flags("enable_rpcz", True)
        tuned_flags("native_telemetry_sample_every", 2)  # elect plenty
        tuned_flags("rpcz_samples_per_second", 0.000001)  # bucket dry
        srv = native_server({"svc": {"echo": native_echo}})
        nch = NativeClientChannel("127.0.0.1", srv.port, protocol="baidu_std")
        try:
            for i in range(20):
                # untraced (election fodder) then traced+forced
                rc, err, _m, _b = nch.call("svc", "echo", b"x",
                                           timeout_ms=2000)
                assert rc >= 0 and err == 0
                rc, err, _m, _b = nch.call(
                    "svc", "echo", b"x", trace_id=0xFACE, span_id=i + 1,
                    sampled=1, timeout_ms=2000,
                )
                assert rc >= 0 and err == 0
        finally:
            nch.close()
        srv._native_plane.drain_telemetry()
        assert len(clean_spans.by_trace(0xFACE)) == 20

    def test_server_span_forced_by_meta_sampled(self, tuned_flags):
        # Python-plane twin of the ring override: a drained token bucket
        # refuses unforced spans but MUST honor the wire's sampled bit
        from incubator_brpc_tpu.builtin import rpcz

        tuned_flags("enable_rpcz", True)
        # grab() clamps tokens to min(rate, ...): the tiny rate makes
        # the shared bucket dry from the next call on, no drain loop
        tuned_flags("rpcz_samples_per_second", 0.000001)

        class _C:
            _request_payload = b""

        meta_plain = Meta(service="s", method="m", trace_id=5, span_id=6)
        meta_forced = Meta(
            service="s", method="m", trace_id=5, span_id=6, sampled=1
        )
        assert rpcz.start_server_span(_C(), meta_plain) is None
        span = rpcz.start_server_span(_C(), meta_forced)
        assert span is not None
        assert span.trace_id == 5 and span.parent_span_id == 6
        rpcz.clear_parent_span(span)

    def test_client_span_decides_sampled_bit_once(self, tuned_flags):
        # the edge that samples stamps sampled=1; inside a serving span
        # the bit propagates even when this hop's bucket is dry
        from incubator_brpc_tpu.builtin import rpcz

        tuned_flags("enable_rpcz", True)
        # a refill-rate high enough that the shared bucket (possibly
        # drained by an earlier test) regains a token within the clock
        # resolution of the grab itself
        tuned_flags("rpcz_samples_per_second", 10_000_000)
        time.sleep(0.01)

        class _C:
            _request_payload = b""
            _service = "s"
            _method = "m"
            log_id = 0
            trace_id = 0
            span_id = 0
            parent_span_id = 0
            trace_sampled = 0

        c1 = _C()
        span = rpcz.start_client_span(c1)
        assert span is not None and c1.trace_sampled == 1
        # dry bucket, no ambient parent: no span, no sampled bit.  The
        # tiny rate FIRST: grab() clamps tokens to min(rate, ...), so
        # the bucket is dry from the next call on (draining by looping
        # at a high refill rate would never terminate)
        tuned_flags("rpcz_samples_per_second", 0.000001)
        c2 = _C()
        assert rpcz.start_client_span(c2) is None
        assert c2.trace_sampled == 0
        # dry bucket but inside a serving span: the bit still propagates
        meta = Meta(service="s", method="m", trace_id=9, span_id=8, sampled=1)
        server_span = rpcz.start_server_span(_C(), meta)
        assert server_span is not None
        try:
            c3 = _C()
            assert rpcz.start_client_span(c3) is None  # bucket still dry
            assert c3.trace_sampled == 1
            assert c3.trace_id == 9
            assert c3.parent_span_id == server_span.span_id
        finally:
            rpcz.clear_parent_span(server_span)


class TestSpanStoreTraceIndex:
    """Satellite 1: by_trace is index-backed, exact across eviction."""

    def _span(self, trace, span_id, start=1):
        from incubator_brpc_tpu.builtin.rpcz import Span

        return Span(
            trace_id=trace, span_id=span_id, start_real_us=start
        )

    def test_index_tracks_submit_and_ring_eviction(self, tuned_flags,
                                                   clean_spans):
        tuned_flags("rpcz_max_spans", 10)
        store = clean_spans
        for i in range(10):
            store.submit(self._span(1000 + i, i + 1))
        assert [sp.span_id for sp in store.by_trace(1000)] == [1]
        # the ring is full: the next submit evicts trace 1000's span
        store.submit(self._span(2000, 99))
        assert store.by_trace(1000) == []
        assert [sp.span_id for sp in store.by_trace(2000)] == [99]
        # several spans of ONE trace accumulate in order
        for i in range(3):
            store.submit(self._span(3000, 200 + i))
        assert [sp.span_id for sp in store.by_trace(3000)] == [200, 201, 202]

    def test_index_survives_maxlen_shrink_and_clear(self, tuned_flags,
                                                    clean_spans):
        store = clean_spans
        tuned_flags("rpcz_max_spans", 100)
        for i in range(20):
            store.submit(self._span(7000, i + 1))
        tuned_flags("rpcz_max_spans", 5)
        store.submit(self._span(7000, 500))
        kept = store.by_trace(7000)
        assert [sp.span_id for sp in kept] == [17, 18, 19, 20, 500]
        store.clear()
        assert store.by_trace(7000) == []
        assert len(store) == 0

    def test_index_matches_scan_semantics(self, tuned_flags, clean_spans):
        # oracle: the index answers exactly what the old O(n) scan did
        import random

        rng = random.Random(99)
        tuned_flags("rpcz_max_spans", 50)
        store = clean_spans
        for i in range(300):
            store.submit(self._span(rng.randrange(1, 9), i + 1))
        with store._lock:
            ring = list(store._spans)
        for t in range(1, 9):
            assert store.by_trace(t) == [
                sp for sp in ring if sp.trace_id == t
            ]
        # trace id 0 means "untraced": never indexed, never queryable
        assert store.by_trace(0) == []


def _start_node_b(tmp_path):
    """A REAL second process running a native-plane echo server with
    rpcz on — the second live node of the fleet-assembly acceptance."""
    import subprocess

    script = tmp_path / "node_b.py"
    script.write_text(
        "import sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from incubator_brpc_tpu.utils.flags import set_flag_unchecked\n"
        "set_flag_unchecked('enable_rpcz', True)\n"
        "set_flag_unchecked('native_telemetry_drain_ms', 20)\n"
        "from incubator_brpc_tpu.rpc import Server, ServerOptions\n"
        "from incubator_brpc_tpu.transport.native_plane import native_echo\n"
        "srv = Server(ServerOptions(native_plane=True, usercode_inline=True))\n"
        "srv.add_service('svc', {'echo': native_echo})\n"
        "assert srv.start(0)\n"
        "print(srv.port, flush=True)\n"
        "time.sleep(120)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        env=env,
        text=True,
    )
    line = proc.stdout.readline().strip()
    if not line.isdigit():
        proc.kill()
        pytest.skip("node B failed to start")
    return proc, int(line)


class TestMultiHopFleetAssembly:
    """Acceptance: client → server A → server B (B natively dispatched,
    in a second PROCESS) yields one trace id with parent→child links
    across every hop, assembled by rpc_view --trace from two live
    nodes."""

    def test_one_trace_across_two_processes(self, tmp_path, tuned_flags,
                                            clean_spans):
        tuned_flags("enable_rpcz", True)
        proc_b, port_b = _start_node_b(tmp_path)
        srv_a = None
        try:
            down = Channel()
            assert down.init(
                f"127.0.0.1:{port_b}",
                options=ChannelOptions(
                    native_plane=True, protocol="baidu_std"
                ),
            )

            def relay(cntl, request):
                # hop A: a Python handler cascading to B — the nested
                # call inherits A's server span as parent (thread-local)
                c = down.call_method("svc", "echo", request)
                assert c.ok(), c.error_text
                return c.response_payload

            srv_a = Server(ServerOptions(usercode_inline=True))
            srv_a.add_service("front", {"relay": relay})
            assert srv_a.start(0)

            edge = Channel()
            assert edge.init(f"127.0.0.1:{srv_a.port}")
            cntl = Controller(timeout_ms=10000)
            c = edge.call_method("front", "relay", b"fleet", cntl=cntl)
            assert c.ok(), c.error_text
            trace_id = cntl.trace_id
            assert trace_id != 0

            # node B's background drain parents its native server span;
            # poll both nodes' /rpcz until the trace is complete
            from tools.rpc_view import scrape_rpcz

            deadline = time.monotonic() + 15
            spans_a = spans_b = []
            while time.monotonic() < deadline:
                try:
                    spans_a = scrape_rpcz(
                        f"127.0.0.1:{srv_a.port}", f"{trace_id:x}"
                    )
                    spans_b = scrape_rpcz(
                        f"127.0.0.1:{port_b}", f"{trace_id:x}"
                    )
                except OSError:
                    spans_a = spans_b = []
                if spans_b and len(spans_a) >= 3:
                    break
                time.sleep(0.1)
            assert spans_b, "node B never surfaced the traced hop"
            # every hop shares the ONE trace id
            for sp in spans_a + spans_b:
                assert sp.trace_id == trace_id
            # parent→child links across the hops: A's server span is the
            # edge client span's child; A's downstream client span is
            # A's server span's child; B's server span parents to A's
            # downstream client span — all stitched by span ids
            by_id = {sp.span_id: sp for sp in spans_a}
            a_client = [
                sp for sp in spans_a
                if sp.span_type == "client" and sp.parent_span_id in by_id
            ]
            assert a_client, "A's nested client span must parent to A's span"
            b_server = spans_b[0]
            assert any(
                b_server.parent_span_id == sp.span_id for sp in spans_a
            ), "B's span must be a child of a span on node A"

            # the fleet puller renders the merged cross-process tree
            from tools.rpc_view import main as view_main

            rc = view_main([
                "--trace", f"{trace_id:x}",
                "--targets",
                f"127.0.0.1:{srv_a.port},127.0.0.1:{port_b}",
            ])
            assert rc == 0
        finally:
            if srv_a is not None:
                srv_a.stop()
            proc_b.kill()
            proc_b.wait(timeout=10)


class TestHotspotsRetry:
    """Satellite 6: /hotspots answers 503-with-Retry-After while a run
    holds the profile lock, and remote windows are clamped."""

    def test_profile_in_progress_is_503_with_retry(self):
        import threading

        from incubator_brpc_tpu.builtin import hotspots, pages

        class _Frame:
            path = "/hotspots"
            query = {"seconds": "0.2"}
            method = "GET"
            headers = {}

        started = threading.Event()

        def hold():
            with hotspots._profile_lock:
                hotspots._profile_until = time.monotonic() + 0.5
                started.set()
                time.sleep(0.4)
            hotspots._profile_until = 0.0

        t = threading.Thread(target=hold)
        t.start()
        started.wait(5)
        try:
            resp = pages._hotspots(None, _Frame())
        finally:
            t.join()
        assert resp[0] == 503
        assert len(resp) == 4 and "Retry-After" in resp[3]
        assert int(resp[3]["Retry-After"]) >= 1

    def test_seconds_clamped(self, monkeypatch):
        from incubator_brpc_tpu.builtin import hotspots, pages

        seen = {}

        def fake_sample(seconds):
            seen["seconds"] = seconds
            return {"samples": 0, "stacks": [], "flat": []}

        monkeypatch.setattr(hotspots, "sample_cpu", fake_sample)

        class _Frame:
            path = "/hotspots"
            query = {"seconds": "600"}
            method = "GET"
            headers = {}

        status, _ctype, _body = pages._hotspots(None, _Frame())
        assert status == 200
        assert seen["seconds"] == 10.0
        _Frame.query = {"seconds": "nan"}
        assert pages._hotspots(None, _Frame())[0] == 400

    def test_retry_after_header_reaches_the_wire(self, native_server):
        import threading

        from incubator_brpc_tpu.builtin import hotspots
        from incubator_brpc_tpu.protocol.http import http_call

        srv = native_server({"svc": {"echo": native_echo}})
        started = threading.Event()

        def hold():
            with hotspots._profile_lock:
                hotspots._profile_until = time.monotonic() + 1.0
                started.set()
                time.sleep(0.8)
            hotspots._profile_until = 0.0

        t = threading.Thread(target=hold)
        t.start()
        started.wait(5)
        try:
            status, headers, _body = http_call(
                "127.0.0.1", srv.port, "/hotspots?seconds=0.2", timeout=10
            )
        finally:
            t.join()
        assert status == 503
        assert "retry-after" in {k.lower() for k in headers}
