"""Pod-wide metrics exposition (/brpc_metrics) + device-plane
instrumentation tests (reference builtin/prometheus_metrics_service.cpp;
format per the Prometheus text exposition format v0.0.4).

Covers: exposition-format golden rendering (counter/gauge/summary,
escaping, quantile labels), scrape-under-load against a live server,
device-link/collective bvars appearing and advancing after traffic,
collective rpcz spans parented into the proposing RPC's trace, and the
satellite fixes riding this PR (async-handler session reap, lazy
ParsedFrame.payload, opt-in collective registration, rpc_view --metrics).
"""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from incubator_brpc_tpu.builtin.prometheus import (  # noqa: E402
    CONTENT_TYPE,
    escape_label_value,
    render_metrics,
    sanitize_metric_name,
)
from incubator_brpc_tpu.bvar import (  # noqa: E402
    Adder,
    IntRecorder,
    LatencyRecorder,
    Maxer,
    PassiveStatus,
    PerSecond,
)
from incubator_brpc_tpu.protocol import http as http_mod  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel,
    ChannelOptions,
    Server,
    ServerOptions,
)
from incubator_brpc_tpu.utils.flags import (  # noqa: E402
    flag_registry,
    set_flag,
)
from incubator_brpc_tpu.utils.status import ErrorCode  # noqa: E402

# -- exposition-format validator ----------------------------------------------

_COMMENT_RE = re.compile(
    r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .+)?$"
)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"([^\"\\\n]|\\\\|\\\"|\\n)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"([^\"\\\n]|\\\\|\\\"|\\n)*\")*\})?"
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$"
)


def validate_exposition(text: str) -> None:
    """Every line must be a TYPE/HELP comment or a well-formed sample."""
    if not text:
        return  # an empty exposition (nothing matched the prefix) is valid
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line:
            continue
        assert _COMMENT_RE.match(line) or _SAMPLE_RE.match(line), (
            f"invalid exposition line: {line!r}"
        )


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _sample_value(text: str, name: str):
    """Value of the (unlabelled) sample ``name`` in an exposition body."""
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    return None


@pytest.fixture
def hidden():
    """Collects bvars created by a test and hides them afterwards so the
    global registry stays clean for other tests."""
    created = []
    yield created.append
    for var in created:
        var.hide()


# -- golden rendering ---------------------------------------------------------


class TestRendering:
    def test_adder_renders_as_counter(self, hidden):
        a = Adder(name="obsx_requests_total")
        hidden(a)
        a << 7
        text = render_metrics(prefix="obsx_")
        assert "# TYPE obsx_requests_total counter" in text
        assert "obsx_requests_total 7" in text
        validate_exposition(text)

    def test_passive_status_and_recorders_render_as_gauges(self, hidden):
        ps = PassiveStatus(lambda: 2.5, name="obsx_gauge")
        rec = IntRecorder(name="obsx_avg")
        mx = Maxer(name="obsx_max")
        for v in (ps, rec, mx):
            hidden(v)
        rec << 10
        rec << 20
        mx << 42
        text = render_metrics(prefix="obsx_")
        assert "# TYPE obsx_gauge gauge" in text
        assert "obsx_gauge 2.5" in text
        assert "obsx_avg 15.0" in text
        assert "obsx_max 42" in text
        validate_exposition(text)

    def test_window_renders_as_gauge(self, hidden):
        base = Adder()
        rate = PerSecond(base, name="obsx_rate")
        hidden(rate)
        text = render_metrics(prefix="obsx_")
        assert "# TYPE obsx_rate gauge" in text
        validate_exposition(text)

    def test_latency_recorder_renders_as_summary(self, hidden):
        lr = LatencyRecorder(name="obsx_latency")
        hidden(lr)
        for v in (100, 200, 300, 400):
            lr << v
        text = render_metrics(prefix="obsx_")
        assert "# TYPE obsx_latency summary" in text
        for q in ("0.5", "0.9", "0.99", "0.999"):
            assert f'obsx_latency{{quantile="{q}"}}' in text
        assert "obsx_latency_sum 1000" in text
        assert "obsx_latency_count 4" in text
        assert "obsx_latency_max_latency 400.0" in text
        assert "# TYPE obsx_latency_qps gauge" in text
        validate_exposition(text)

    def test_non_numeric_values_are_skipped(self, hidden):
        s = PassiveStatus(lambda: "not-a-number", name="obsx_stringy")
        hidden(s)
        text = render_metrics(prefix="obsx_")
        assert "obsx_stringy" not in text
        validate_exposition(text)

    def test_numeric_flags_mirrored_as_gauges(self):
        text = render_metrics(prefix="flag_max_body_size")
        assert "# TYPE flag_max_body_size gauge" in text
        assert _sample_value(text, "flag_max_body_size") == float(
            flag_registry.get("max_body_size")
        )
        validate_exposition(text)

    def test_escape_label_value(self):
        assert escape_label_value('a"b') == 'a\\"b'
        assert escape_label_value("a\\b") == "a\\\\b"
        assert escape_label_value("a\nb") == "a\\nb"
        assert escape_label_value('q"\\' + "\n") == 'q\\"\\\\\\n'

    def test_sanitize_metric_name(self):
        assert sanitize_metric_name("ok_name") == "ok_name"
        assert sanitize_metric_name("9starts_with_digit") == (
            "_9starts_with_digit"
        )
        assert sanitize_metric_name("dots.and-dashes") == "dots_and_dashes"

    def test_prefix_filters(self, hidden):
        a = Adder(name="obsx_inside")
        hidden(a)
        text = render_metrics(prefix="obsx_inside")
        assert "obsx_inside" in text
        assert "\nprocess_" not in text and "flag_max_body_size" not in text


# -- live server scrape -------------------------------------------------------


@pytest.fixture
def portal_server():
    server = Server()
    server.add_service("obsdemo", {"echo": lambda cntl, req: req})
    assert server.start(0)
    yield server
    server.stop()
    server.join(timeout=5)


@pytest.fixture
def echo_server_factory():
    """Builds servers with per-test-unique service names: method bvar
    names dedup globally (expose() keeps the FIRST registrant), so a test
    asserting on its own method summary must not reuse a service name a
    previous test's dead server still holds in the registry."""
    servers = []

    def make(service: str):
        server = Server()
        server.add_service(service, {"echo": lambda cntl, req: req})
        assert server.start(0)
        servers.append(server)
        return server

    yield make
    for server in servers:
        server.stop()
        server.join(timeout=5)


def _fetch(server, path):
    return http_mod.http_call("127.0.0.1", server.port, path)


class TestPortalScrape:
    def test_scrape_is_valid_and_typed(self, portal_server):
        status, headers, body = _fetch(portal_server, "/brpc_metrics")
        assert status == 200
        assert headers.get("content-type", "").startswith("text/plain")
        text = body.decode()
        validate_exposition(text)
        assert "# TYPE" in text

    def test_index_links_brpc_metrics(self, portal_server):
        status, _, body = _fetch(portal_server, "/")
        assert status == 200 and b"/brpc_metrics" in body

    def test_method_summary_advances_with_traffic(self, echo_server_factory):
        server = echo_server_factory("obstraffic")
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        for i in range(5):
            assert ch.call_method("obstraffic", "echo", b"x%d" % i).ok()
        _, _, body = _fetch(server, "/brpc_metrics")
        text = body.decode()
        name = "method_obstraffic_echo_latency"
        assert f"# TYPE {name} summary" in text
        assert _sample_value(text, f"{name}_count") >= 5
        assert f'{name}{{quantile="0.99"}}' in text

    def test_prefix_query(self, portal_server):
        _, _, body = _fetch(
            portal_server, "/brpc_metrics?prefix=method_obsdemo"
        )
        text = body.decode()
        validate_exposition(text)
        for line in text.splitlines():
            if not line.startswith("#"):
                assert line.startswith("method_obsdemo")

    def test_scrape_under_load(self, portal_server):
        """Scrapes stay valid while traffic hammers the same server."""
        stop = threading.Event()
        errs = []

        def pound():
            ch = Channel()
            assert ch.init(f"127.0.0.1:{portal_server.port}")
            i = 0
            while not stop.is_set():
                c = ch.call_method("obsdemo", "echo", b"load-%d" % i)
                if c.failed():
                    errs.append(c.error_text)
                i += 1

        threads = [threading.Thread(target=pound) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(5):
                status, _, body = _fetch(portal_server, "/brpc_metrics")
                assert status == 200
                validate_exposition(body.decode())
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errs, errs[:3]


# -- device-plane metrics -----------------------------------------------------


class TestDeviceLinkMetrics:
    def test_link_bvars_appear_and_advance(self, portal_server):
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{portal_server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=30000),
        )
        body = b"device-plane payload " * 64
        for _ in range(3):
            cntl = ch.call_method("obsdemo", "echo", body)
            assert cntl.ok(), cntl.error_text
        link = ch._device_sock.link
        link._step_feed.flush()  # the sampler thread would, within the second
        # direct bvar reads: latency recorders and byte counters advanced
        assert link._m_rtt.count() > 0
        assert link._m_flush.count() > 0
        assert link._m_pump.count() > 0
        assert link._m_out_bytes.get_value() >= len(body) * 3
        assert link._m_in_bytes.get_value() >= len(body) * 3
        # and the same names are scrapeable from the live portal
        _, _, raw = _fetch(portal_server, "/brpc_metrics")
        text = raw.decode()
        validate_exposition(text)
        pfx = f"device_link_{link.link_id}"
        assert f"# TYPE {pfx}_step_rtt_us summary" in text
        assert _sample_value(text, f"{pfx}_step_rtt_us_count") > 0
        assert f"# TYPE {pfx}_out_bytes_second gauge" in text
        assert f"# TYPE {pfx}_in_bytes_second gauge" in text
        assert _sample_value(text, "device_link_bytes") > 0

    def test_link_metrics_retire_on_clean_close(self, portal_server):
        """An orderly ECLOSE dance (no fail()) must also drop the link's
        registry names — churning links cannot accumulate entries."""
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{portal_server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=30000),
        )
        assert ch.call_method("obsdemo", "echo", b"x").ok()
        link = ch._device_sock.link
        pfx = f"device_link_{link.link_id}"
        assert f"{pfx}_step_rtt_us" in render_metrics(prefix=pfx)
        # one side starts the orderly close; the F_CLOSE dance takes the
        # peer side down too, and the second ECLOSE retires the names
        ch._device_sock.set_failed(ErrorCode.ECLOSE, "clean close")
        assert _wait(lambda: render_metrics(prefix=pfx) == "")

    def test_link_metrics_retire_on_failure(self, portal_server):
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{portal_server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=30000),
        )
        assert ch.call_method("obsdemo", "echo", b"x").ok()
        link = ch._device_sock.link
        pfx = f"device_link_{link.link_id}"
        assert f"{pfx}_step_rtt_us" in render_metrics(prefix=pfx)
        link.fail("test-induced failure")
        assert render_metrics(prefix=pfx) == ""
        from incubator_brpc_tpu.transport.device_link import link_errors

        assert link_errors.get_value() > 0


class TestDeviceCallSpan:
    """A sampled server span of a device call carries the call's stamps as
    annotations on the monotonic clock; an unsampled call adds nothing."""

    STAGES = [
        "entry", "words", "credit_held", "enqueued", "batched", "stacked",
        "launched", "cq_taken", "ready", "readback", "woke", "exit", "sent",
    ]

    @pytest.fixture(scope="class")
    def device_server(self):
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        endpoint = DeviceEndpoint(window_size=4)
        endpoint.warm(64)
        server = Server()
        server.add_service("tensor", {"echo": endpoint.server_handler()})
        assert server.start(0)
        yield server
        server.stop()
        server.join(timeout=5)

    def _call(self, server, payload=b"span-me"):
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        cntl = ch.call_method("tensor", "echo", payload)
        assert cntl.ok(), cntl.error_text
        return cntl

    def test_sampled_span_lists_the_stages_in_order(
        self, device_server, tuned_flags
    ):
        from incubator_brpc_tpu.builtin.rpcz import span_store

        tuned_flags("enable_rpcz", True)
        tuned_flags("rpcz_samples_per_second", 10_000_000)
        time.sleep(0.01)  # the token bucket fills at the new rate
        span_store.clear()
        t0 = time.monotonic_ns()
        cntl = self._call(device_server)
        t1 = time.monotonic_ns()
        assert _wait(lambda: any(
            sp.span_type == "server" and sp.method == "echo"
            for sp in span_store.recent()
        ))
        span = [
            sp for sp in span_store.recent()
            if sp.span_type == "server" and sp.method == "echo"
        ][0]
        # the span is the RPC's own: the ids the wire carried
        assert span.trace_id == cntl.trace_id
        assert span.parent_span_id == cntl.span_id
        assert span.service == "tensor"
        texts = [text for _, text in span.annotations]
        assert texts[0] == "processing"
        device = [t.split()[1] for t in texts if t.startswith("device ")]
        assert device == self.STAGES
        batched = [t for t in texts if t.startswith("device batched")][0]
        assert re.fullmatch(
            r"device batched dispatch=\d+ rows=1 pad_rows=1 bucket=64", batched
        )
        # offsets: in order, from the span's monotonic start, inside the call
        offsets = [off for off, _ in span.annotations]
        assert offsets == sorted(offsets) and offsets[0] >= 0
        assert t0 <= span.start_mono_ns <= t1
        # "exit" is inside the client's call; "sent" is stamped once the
        # write has returned, which the client may have seen by then
        assert span.start_mono_ns + offsets[-2] * 1e3 <= t1
        # the device path lies inside the span's own latency
        assert offsets[-1] <= span.latency_us + 1000
        # and /rpcz shows them
        _, _, body = _fetch(device_server, "/rpcz?min_latency_us=0")
        line = [
            ln for ln in body.decode().splitlines()
            if " server tensor.echo" in ln
        ][0]
        assert "device credit_held" in line and "device readback" in line
        span_store.clear()

    def test_unsampled_call_adds_nothing(
        self, device_server, tuned_flags, monkeypatch
    ):
        from incubator_brpc_tpu.builtin import rpcz
        from incubator_brpc_tpu.transport import device

        tuned_flags("enable_rpcz", False)
        rpcz.span_store.clear()
        made = []
        monkeypatch.setattr(
            rpcz.Span, "__post_init__", lambda self: made.append(self),
            raising=False,
        )
        monkeypatch.setattr(
            device._PendingCall, "annotate",
            lambda self, span: made.append(span),
        )
        self._call(device_server, b"no-span")
        assert made == [] and len(rpcz.span_store) == 0


class TestSpanClock:
    def test_annotate_takes_offsets_from_the_monotonic_clock(self, monkeypatch):
        from incubator_brpc_tpu.builtin import rpcz

        before = time.monotonic_ns()
        span = rpcz.Span(start_real_us=int(time.time() * 1e6))
        assert before <= span.start_mono_ns <= time.monotonic_ns()
        # the wall clock stepping back an hour moves no offset
        real = time.time
        monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
        span.annotate("now")
        span.annotate("stamped", at_ns=span.start_mono_ns + 2_500_000)
        (off_now, _), (off_at, text) = span.annotations
        assert 0 <= off_now < 1e6
        assert off_at == 2500.0 and text == "stamped"

    def test_start_mono_ns_round_trips_and_is_not_identity(self):
        from incubator_brpc_tpu.builtin.rpcz import (
            Span,
            span_from_dict,
            span_to_dict,
        )

        span = Span(trace_id=5, span_id=6, start_real_us=10)
        back = span_from_dict(span_to_dict(span))
        assert back.start_mono_ns == span.start_mono_ns
        assert Span(trace_id=5, span_id=6, start_real_us=10) == span


# -- collective sessions ------------------------------------------------------


class TestCollectiveObservability:
    def test_collective_registration_is_opt_in(self):
        server = Server()  # no jax.distributed in-process: default OFF
        assert server.start(0)
        try:
            assert not server.has_method("_tpu_transport.collective")
            assert server.has_method("_tpu_transport.handshake")
        finally:
            server.stop()
            server.join(timeout=5)

    def test_collective_opt_in_gets_concurrency_limit(self):
        server = Server(
            ServerOptions(
                enable_collective_service=True, collective_max_concurrency=2
            )
        )
        assert server.start(0)
        try:
            assert server.has_method("_tpu_transport.collective")
            assert (
                server.method_max_concurrency("_tpu_transport.collective")
                == 2
            )
        finally:
            server.stop()
            server.join(timeout=5)

    def test_session_span_parented_to_proposing_rpc(self, monkeypatch):
        from incubator_brpc_tpu.builtin.rpcz import span_store
        from incubator_brpc_tpu.parallel import mc_collective

        monkeypatch.setattr(
            mc_collective,
            "run_collective_session",
            lambda parties, idx, steps, width, seed: (
                np.zeros(width, np.float32),
                0.001,
            ),
        )
        server = Server(ServerOptions(enable_collective_service=True))
        assert server.start(0)
        assert set_flag("enable_rpcz", True)
        span_store.clear()
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            payload = json.dumps(
                {
                    "parties": [0, 1],
                    "index": 1,
                    "steps": 3,
                    "width": 4,
                    "seed": 7,
                }
            ).encode()
            cntl = ch.call_method("_tpu_transport", "collective", payload)
            assert cntl.ok(), cntl.error_text
            assert cntl.trace_id
            spans = [
                s
                for s in span_store.recent(limit=500)
                if s.span_type == "collective"
            ]
            assert spans, "no collective span sampled"
            span = spans[-1]
            # parented into the proposing RPC's trace
            assert span.trace_id == cntl.trace_id
            assert span.parent_span_id == cntl.span_id
            notes = " ".join(text for _, text in span.annotations)
            assert "steps=3" in notes and "width=4" in notes
            assert "parties=[0, 1]" in notes
            # and visible on the /rpcz page under the client's trace id
            _, _, body = _fetch(server, f"/rpcz?trace_id={cntl.trace_id:x}")
            assert b"collective" in body
            # session bvars advanced (the stub bypasses
            # run_collective_session, so count the handler-side counters
            # via /brpc_metrics presence instead)
            _, _, raw = _fetch(server, "/brpc_metrics")
            assert "# TYPE mc_collective_sessions counter" in raw.decode()
        finally:
            set_flag("enable_rpcz", False)
            span_store.clear()
            server.stop()
            server.join(timeout=5)

    def test_session_bvars_count_real_sessions(self):
        """run_collective_session itself feeds the session counters —
        single-party degenerate session, no cross-process fabric needed."""
        import jax

        from incubator_brpc_tpu.parallel.mc_collective import (
            collective_sessions,
            collective_steps,
            run_collective_session,
        )

        before = collective_sessions.get_value()
        steps_before = collective_steps.get_value()
        own, elapsed = run_collective_session(
            [jax.devices()[0].id], 0, steps=2, width=8, seed=3
        )
        assert own.shape == (8,)
        assert collective_sessions.get_value() == before + 1
        assert collective_steps.get_value() == steps_before + 2


# -- satellite: async binary-handler session reap -----------------------------


class _CountingFactory:
    def __init__(self):
        self.created = []
        self.destroyed = []

    def create(self):
        obj = object()
        self.created.append(obj)
        return obj

    def destroy(self, obj):
        self.destroyed.append(obj)


class TestAsyncResponseReap:
    def test_async_handler_without_response_is_reaped(self):
        factory = _CountingFactory()
        server = Server(
            ServerOptions(session_local_data_factory=factory)
        )
        held = []

        def never_responds(cntl, req):
            cntl.session_local_data()
            cntl.set_async()
            held.append(cntl)
            return None

        server.add_service(
            "leak", {"never": never_responds}, max_concurrency=1
        )
        assert server.start(0)
        old = flag_registry.get("async_response_timeout_s")
        assert set_flag("async_response_timeout_s", 0.3)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{server.port}",
                options=ChannelOptions(timeout_ms=10000),
            )
            cntl = ch.call_method("leak", "never", b"x")
            assert cntl.failed()
            assert cntl.error_code == ErrorCode.ERPCTIMEDOUT
            assert "async handler" in cntl.error_text
            st = server.method_status("leak", "never")
            assert _wait(lambda: st.processing == 0)
            # the session-handler refcount drained: the pooled object can
            # be given back when the connection dies (the leak ADVICE r5
            # describes left it pinned forever)
            sock = held[0]._sock
            assert _wait(
                lambda: sock.context.get("_session_nhandlers", 0) == 0
            )
            # admission slot released: with max_concurrency=1 a second
            # call is admitted (it would be ELIMIT if the slot leaked)
            cntl2 = ch.call_method("leak", "never", b"y")
            assert cntl2.error_code == ErrorCode.ERPCTIMEDOUT
            # connection death pools the session object back
            sock.set_failed(ErrorCode.ECLOSE, "test closes")
            assert _wait(
                lambda: "_session_local_data" not in sock.context
            )
        finally:
            flag_registry.set_unchecked("async_response_timeout_s", old)
            server.stop()
            server.join(timeout=5)

    def test_send_response_then_return_finishes_once(self):
        server = Server()

        def double_finisher(cntl, req):
            cntl.send_response(b"first")
            return b"second"  # must be ignored: the finish is once-only

        server.add_service("once", {"both": double_finisher})
        assert server.start(0)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            cntl = ch.call_method("once", "both", b"x")
            assert cntl.ok(), cntl.error_text
            assert cntl.response_payload == b"first"
            assert _wait(lambda: server._nprocessing == 0)
            assert server._nprocessing == 0  # not driven negative
        finally:
            server.stop()
            server.join(timeout=5)


# -- satellite: lazy ParsedFrame.payload --------------------------------------


class TestLazyStreamPayload:
    def test_stream_frame_payload_materializes_lazily(self):
        import incubator_brpc_tpu.rpc.stream  # noqa: F401 — binds process_stream
        from incubator_brpc_tpu import native
        from incubator_brpc_tpu.iobuf import IOBuf
        from incubator_brpc_tpu.protocol.tbus_std import (
            FLAG_STREAM,
            Meta,
            pack_frame,
            parse_frame_iobuf,
        )

        if not native.NATIVE_AVAILABLE:
            pytest.skip("zero-copy stream cut needs the native IOBuf")
        payload = b"stream-bytes-" * 37
        raw = pack_frame(Meta(stream_id=9), payload, 0x77, flags=FLAG_STREAM)
        buf = IOBuf()
        buf.append(raw)
        frame, consumed = parse_frame_iobuf(buf)
        assert consumed == len(raw)
        assert frame.is_stream
        assert frame.payload_iobuf is not None
        assert frame._payload == b""  # the cut itself stayed zero-copy
        assert frame.payload == payload  # lazy materialization on access
        assert frame.payload == payload  # cached, stable

    def test_payload_setter_still_works(self):
        from incubator_brpc_tpu.protocol.tbus_std import Meta, ParsedFrame

        frame = ParsedFrame(meta=Meta(), payload=b"abc")
        assert frame.payload == b"abc"
        frame.payload = b"xyz"
        assert frame.payload == b"xyz"


# -- satellite: rpc_view --metrics --------------------------------------------


class TestRpcViewMetrics:
    TEXT1 = (
        "# TYPE c counter\nc 5\n"
        "# TYPE g gauge\ng 2.5\n"
        "# TYPE s summary\n"
        's{quantile="0.5"} 100.0\ns_sum 300\ns_count 3\n'
    )
    TEXT2 = (
        "# TYPE c counter\nc 15\n"
        "# TYPE g gauge\ng 2.5\n"
        "# TYPE s summary\n"
        's{quantile="0.5"} 150.0\ns_sum 900\ns_count 6\n'
    )

    def test_parse_exposition(self):
        from tools.rpc_view import parse_exposition

        values, types = parse_exposition(self.TEXT1)
        assert values["c"] == 5.0
        assert values['s{quantile="0.5"}'] == 100.0
        assert types == {"c": "counter", "g": "gauge", "s": "summary"}

    def test_delta_lines(self):
        from tools.rpc_view import metrics_delta_lines, parse_exposition

        v1, t = parse_exposition(self.TEXT1)
        v2, _ = parse_exposition(self.TEXT2)
        lines = metrics_delta_lines(v1, v2, t, seconds=2.0)
        joined = "\n".join(lines)
        assert "c 5 -> 15  (+10, 5.0/s)" in joined
        assert "s_count 3 -> 6" in joined  # summary counters rate too
        assert 's{quantile="0.5"} 150' in joined  # traffic: quantiles shown
        assert "\ng " not in joined and not joined.startswith("g ")  # unchanged

    def test_metrics_mode_against_live_server(
        self, echo_server_factory, capsys
    ):
        from tools.rpc_view import metrics_mode

        server = echo_server_factory("obsview1")
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        for i in range(3):
            assert ch.call_method("obsview1", "echo", b"m%d" % i).ok()
        rc = metrics_mode(
            f"127.0.0.1:{server.port}", 0, prefix="method_obsview1"
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "method_obsview1_echo_latency_count" in out

    def test_metrics_mode_delta_against_live_server(
        self, echo_server_factory, capsys
    ):
        import tools.rpc_view as rv

        server = echo_server_factory("obsview2")
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        assert ch.call_method("obsview2", "echo", b"warm").ok()

        # traffic flows WHILE metrics_mode sits between its two scrapes,
        # so the second scrape sees a real delta
        stop = threading.Event()

        def drive():
            i = 0
            while not stop.is_set():
                ch.call_method("obsview2", "echo", b"d%d" % i)
                i += 1

        t = threading.Thread(target=drive)
        t.start()
        try:
            rc = rv.metrics_mode(
                f"127.0.0.1:{server.port}", 0.3, prefix="method_obsview2"
            )
        finally:
            stop.set()
            t.join()
        out = capsys.readouterr().out
        assert rc == 0
        assert "method_obsview2_echo_latency_count" in out
        assert "/s)" in out  # rate column rendered

    def test_content_type_constant(self):
        assert CONTENT_TYPE.startswith("text/plain")


# -- native telemetry ring (PR 4) ---------------------------------------------
#
# The C++ dispatch plane records every natively-answered request into a
# lock-free completion ring (src/tbnet); transport/native_plane.py drains
# it into per-method latency summaries, sampled rpcz server spans, and
# adaptive-limiter feedback. These tests drive PURE-native PRPC floods
# (cb_frames stays 0 — no interpreter on the request path) and assert the
# observability plane still sees everything.

from incubator_brpc_tpu.transport import native_plane as np_mod  # noqa: E402

# (flag snapshot/restore comes from the shared ``tuned_flags`` fixture
# in conftest.py)


@pytest.mark.skipif(
    not np_mod.NET_AVAILABLE, reason="native runtime unavailable"
)
class TestNativeTelemetry:
    def _native_server(self, service: str, **opts):
        from incubator_brpc_tpu.rpc import native_echo

        srv = Server(ServerOptions(native_plane=True, **opts))
        srv.add_service(service, {"echo": native_echo})
        assert srv.start(0)
        assert srv._native_plane is not None, "native plane did not engage"
        return srv

    def test_per_method_summary_advances_pure_native(self, tuned_flags):
        # flood over the baidu_std C++ fast path, then SCRAPE: the
        # /brpc_metrics render must force-drain the ring (scrape hook) and
        # show the per-method summary — without one Python-routed request
        tuned_flags("native_telemetry", True)
        tuned_flags("native_telemetry_sample_every", 0)
        srv = self._native_server("telsvc1")
        try:
            ch = np_mod.NativeClientChannel(
                "127.0.0.1", srv.port, protocol="baidu_std"
            )
            try:
                ch.pump("telsvc1", "echo", b"y" * 64, 1000, inflight=32)
            finally:
                ch.close()
            _, _, body = _fetch(srv, "/brpc_metrics?prefix=native_method_")
            text = body.decode()
            name = "native_method_telsvc1_echo_latency_us"
            assert f"# TYPE {name} summary" in text
            assert _sample_value(text, f"{name}_count") == 1000
            assert f'{name}{{quantile="0.99"}}' in text
            stats = srv._native_plane.stats()
            assert stats["native_reqs"] >= 1000
            assert stats["cb_frames"] == 0, "flood was not pure-native"
        finally:
            srv.stop()

    def test_sampled_spans_land_at_configured_rate(self, tuned_flags):
        from incubator_brpc_tpu.builtin.rpcz import span_store

        tuned_flags("native_telemetry", True)
        tuned_flags("native_telemetry_sample_every", 8)
        tuned_flags("enable_rpcz", True)
        # the shared rpcz token bucket ALSO bounds native spans/second;
        # raise it so this test observes the exact 1/N election alone
        tuned_flags("rpcz_samples_per_second", 10_000_000)
        srv = self._native_server("telsvc2")
        try:
            ch = np_mod.NativeClientChannel(
                "127.0.0.1", srv.port, protocol="baidu_std"
            )
            try:
                ch.pump("telsvc2", "echo", b"z" * 32, 800, inflight=32)
            finally:
                ch.close()
            srv._native_plane.drain_telemetry()
            spans = [
                sp
                for sp in span_store.recent(limit=20000)
                if sp.service == "telsvc2"
            ]
            # counter-based 1/N sampling is exact: ticks 0,8,16,...
            assert len(spans) == 800 // 8
            sp = spans[0]
            assert sp.span_type == "server" and sp.method == "echo"
            assert sp.trace_id != 0 and sp.span_id != 0
            assert sp.request_size == 32 and sp.response_size == 32
            assert sp.latency_us >= 0
            assert srv._native_plane.stats()["cb_frames"] == 0
        finally:
            srv.stop()

    def test_adaptive_limit_moves_without_python_route(self, tuned_flags):
        # the PR 3 blind spot: a 100%-native server used to hold its last
        # pushed limit because the adaptive signal came only from
        # Python-routed completions. The telemetry drain closes it: the
        # limiter must move off its seed from native completions alone,
        # and the new limit must land back in the C++ admission table.
        tuned_flags("native_telemetry", True)
        tuned_flags("native_telemetry_sample_every", 0)
        tuned_flags("auto_cl_initial_max_concurrency", 40)
        tuned_flags("auto_cl_sampling_interval_us", 20)
        tuned_flags("auto_cl_min_sample_count", 20)
        tuned_flags("auto_cl_max_sample_count", 100)
        tuned_flags("auto_cl_sample_window_size_ms", 50)
        srv = self._native_server("telsvc3", max_concurrency="auto")
        plane = srv._native_plane
        try:
            assert "telsvc3.echo" in plane.native_method_names()
            seed = 40
            assert srv.max_concurrency == seed
            assert plane.native_max_concurrency("telsvc3.echo") == seed
            ch = np_mod.NativeClientChannel(
                "127.0.0.1", srv.port, protocol="baidu_std"
            )
            try:
                for _ in range(4):
                    ch.pump("telsvc3", "echo", b"q" * 16, 5000, inflight=16)
                    plane.drain_telemetry()
            finally:
                ch.close()
            assert srv.max_concurrency != seed, (
                "adaptive limit never moved off its seed despite a "
                "pure-native flood"
            )
            # the moved limit is pushed back into the C++ admission table
            assert (
                plane.native_max_concurrency("telsvc3.echo")
                == srv.max_concurrency
            )
            assert plane.stats()["cb_frames"] == 0, "flood was not pure-native"
        finally:
            srv.stop()

    def test_ring_overflow_drops_instead_of_stalling(self, tuned_flags):
        tuned_flags("native_telemetry", True)
        tuned_flags("native_telemetry_sample_every", 0)
        tuned_flags("native_telemetry_ring_size", 64)
        tuned_flags("native_telemetry_drain_ms", 60000)  # bg pump idles
        srv = self._native_server("telsvc4")
        plane = srv._native_plane
        try:
            ch = np_mod.NativeClientChannel(
                "127.0.0.1", srv.port, protocol="baidu_std"
            )
            try:
                # 2000 completions into a 64-slot ring with nobody
                # draining: the hot path must keep answering (drop, not
                # block) and count what it sheds
                ch.pump("telsvc4", "echo", b"w" * 8, 2000, inflight=32)
                dropped = plane.telemetry_dropped()
                assert dropped > 0
                drained = plane.drain_telemetry()
                assert 0 < drained <= 64
                # the server is still alive and answering
                rc, err, _, body = ch.call("telsvc4", "echo", b"alive")
                assert rc >= 0 and err == 0 and body.to_bytes() == b"alive"
            finally:
                ch.close()
            assert plane.telemetry_dropped() + plane._tel_drained >= 2000
        finally:
            srv.stop()

    def test_telemetry_disabled_records_nothing(self, tuned_flags):
        tuned_flags("native_telemetry", False)
        srv = self._native_server("telsvc5")
        plane = srv._native_plane
        try:
            ch = np_mod.NativeClientChannel(
                "127.0.0.1", srv.port, protocol="baidu_std"
            )
            try:
                ch.pump("telsvc5", "echo", b"n" * 8, 200, inflight=16)
            finally:
                ch.close()
            assert plane.drain_telemetry() == 0
            assert plane.telemetry_dropped() == 0
            assert plane._tel_recorders == {}
        finally:
            srv.stop()


# -- satellites: SpanStore reload/round-trip + /rpcz query upgrades -----------


class TestSpanStoreSatellites:
    def test_rpcz_max_spans_reload_applies(self, tuned_flags):
        # deque(maxlen=...) froze the flag value read at construction;
        # submit() must re-check it so a runtime retune takes effect
        from incubator_brpc_tpu.builtin.rpcz import Span, SpanStore

        tuned_flags("rpcz_max_spans", 10)
        store = SpanStore()
        for i in range(10):
            store.submit(Span(trace_id=i + 1, span_id=i + 1))
        assert len(store) == 10
        tuned_flags("rpcz_max_spans", 4)
        store.submit(Span(trace_id=100, span_id=100))
        assert len(store) == 4  # shrank live, newest kept
        assert store.recent(limit=10)[-1].trace_id == 100
        tuned_flags("rpcz_max_spans", 6)
        for i in range(6):
            store.submit(Span(trace_id=200 + i, span_id=200 + i))
        assert len(store) == 6  # grew live

    def test_json_mode_rejects_cleanly_when_rpcz_off(
        self, portal_server, tuned_flags
    ):
        # a machine consumer must get JSON and a non-2xx, never a 200
        # text blob it cannot parse
        tuned_flags("enable_rpcz", False)
        status, headers, body = _fetch(portal_server, "/rpcz?json=1")
        assert status == 503
        assert "json" in headers.get("content-type", "")
        assert "rpcz is off" in json.loads(body.decode())["error"]

    def test_load_spans_round_trips_persisted_spans(
        self, tuned_flags, tmp_path
    ):
        from incubator_brpc_tpu.builtin.rpcz import (
            Span,
            SpanStore,
            load_spans,
        )

        tuned_flags("rpcz_database_dir", str(tmp_path))
        store = SpanStore()
        span = Span(
            trace_id=0xFEED,
            span_id=0xBEEF,
            parent_span_id=0x1,
            span_type="server",
            service="persist",
            method="echo",
            remote_side="127.0.0.1:9",
            log_id=7,
            error_code=3,
            start_real_us=123456789,
            latency_us=42.5,
            request_size=10,
            response_size=20,
        )
        span.annotations.append((1.25, "queued"))
        span.annotations.append((2.5, "done"))
        store.submit(span)
        store.close_db()
        loaded = load_spans(str(tmp_path / "rpcz.jsonl"))
        assert len(loaded) == 1
        # dataclass equality covers every field — including annotations
        # normalized back to the (offset, text) TUPLES live spans hold
        # (the JSON round trip turned them into lists before this PR)
        assert loaded[0] == span
        assert isinstance(loaded[0].annotations[0], tuple)

    def test_load_spans_skips_torn_lines(self, tmp_path):
        from incubator_brpc_tpu.builtin.rpcz import load_spans

        p = tmp_path / "rpcz.jsonl"
        p.write_text(
            '{"trace_id": 1, "span_id": 2, "type": "server"}\n'
            '{"trace_id": 3, "span_id":'  # torn tail (crash mid-write)
        )
        loaded = load_spans(str(p))
        assert len(loaded) == 1 and loaded[0].trace_id == 1
        assert load_spans(str(tmp_path / "missing.jsonl")) == []


class TestRpczQueries:
    @pytest.fixture
    def trace_server(self, portal_server, tuned_flags):
        from incubator_brpc_tpu.builtin.rpcz import Span, span_store

        tuned_flags("enable_rpcz", True)
        span_store.clear()
        mk = Span
        span_store.submit(mk(
            trace_id=0xABC, span_id=1, parent_span_id=0, span_type="server",
            service="q", method="root", latency_us=900, start_real_us=100,
        ))
        span_store.submit(mk(
            trace_id=0xABC, span_id=2, parent_span_id=1, span_type="client",
            service="q", method="child1", latency_us=300, start_real_us=200,
        ))
        span_store.submit(mk(
            trace_id=0xABC, span_id=3, parent_span_id=1, span_type="client",
            service="q", method="child2", latency_us=100, error_code=7,
            start_real_us=300,
        ))
        span_store.submit(mk(
            trace_id=0xABC, span_id=4, parent_span_id=2, span_type="server",
            service="q", method="grandchild", latency_us=50,
            start_real_us=400,
        ))
        yield portal_server
        span_store.clear()

    def test_trace_id_renders_parent_child_tree(self, trace_server):
        _, _, body = _fetch(trace_server, "/rpcz?trace_id=abc")
        lines = body.decode().splitlines()
        assert len(lines) == 4
        assert lines[0].startswith("trace=abc span=1")  # root, no indent
        assert lines[1].startswith("  trace=abc span=2")
        assert lines[2].startswith("    trace=abc span=4")  # under child1
        assert lines[3].startswith("  trace=abc span=3")

    def test_min_latency_filter_is_latency_ordered(self, trace_server):
        _, _, body = _fetch(trace_server, "/rpcz?min_latency_us=200")
        lines = body.decode().splitlines()
        assert len(lines) == 2
        assert "span=1" in lines[0] and "span=2" in lines[1]  # worst first

    def test_error_only_filter(self, trace_server):
        _, _, body = _fetch(trace_server, "/rpcz?error_only=1")
        lines = [ln for ln in body.decode().splitlines() if ln]
        assert len(lines) == 1 and "error=7" in lines[0]

    def test_json_mode_serves_span_dicts(self, trace_server):
        status, headers, body = _fetch(trace_server, "/rpcz?json=1")
        assert status == 200 and "json" in headers.get("content-type", "")
        rows = json.loads(body.decode())
        assert len(rows) == 4
        by_span = {r["span_id"]: r for r in rows}
        assert by_span[3]["error_code"] == 7
        assert by_span[1]["type"] == "server"
        assert by_span[1]["latency_us"] == 900

    def test_bad_query_values_rejected(self, trace_server):
        status, _, _ = _fetch(trace_server, "/rpcz?min_latency_us=abc")
        assert status == 400
        status, _, _ = _fetch(trace_server, "/rpcz?trace_id=zzz")
        assert status == 400


class TestSpanRetention:
    """rpcz_keep_span_seconds: age pruning against the HOST clock, with
    non-wall-time (synthetic/replayed) spans exempt — one skewed
    producer must never purge the process-global store."""

    def _span(self, start_us, span_id):
        from incubator_brpc_tpu.builtin.rpcz import Span

        return Span(
            trace_id=0xF0, span_id=span_id, parent_span_id=0,
            span_type="server", service="r", method="m",
            latency_us=10, start_real_us=start_us,
        )

    def test_wall_clock_spans_age_out_synthetic_spans_survive(
        self, tuned_flags
    ):
        import time as _time

        from incubator_brpc_tpu.builtin.rpcz import SpanStore

        tuned_flags("rpcz_keep_span_seconds", 60)
        store = SpanStore()
        now_us = _time.time() * 1e6
        store.submit(self._span(100, 1))  # synthetic clock: exempt
        store.submit(self._span(now_us - 120e6, 2))  # 2 min old: stale
        store.submit(self._span(now_us - 1e6, 3))  # fresh
        store.submit(self._span(now_us, 4))  # triggers the prune
        ids = [s.span_id for s in store.recent()]
        assert 2 not in ids, ids  # aged out past the 60 s horizon
        assert {1, 3, 4} <= set(ids), ids  # exempt + fresh survive

    def test_skewed_future_span_cannot_purge_the_store(self, tuned_flags):
        import time as _time

        from incubator_brpc_tpu.builtin.rpcz import SpanStore

        tuned_flags("rpcz_keep_span_seconds", 60)
        store = SpanStore()
        now_us = _time.time() * 1e6
        store.submit(self._span(now_us, 1))
        # a producer 10 hours in the future: must not evict span 1
        store.submit(self._span(now_us + 36000e6, 2))
        ids = {s.span_id for s in store.recent()}
        assert ids == {1, 2}, ids
