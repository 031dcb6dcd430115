"""The deployment ``echo_device_native`` on the CPU: ``Server(native_plane=
True)`` + ``DeviceEndpoint(16, 16).server_handler()`` + ``Channel(
native_plane=True)`` against the benchmark's plain reference
(``benchmark/references/echo_identity.py``), beside the same calls over the
Python plane; the stamps and recorders the native plane brings to a device
call; and the benchmark's deployment file with its guarantee rows. Nothing
here is a device number."""

from __future__ import annotations

import json
import logging
import os
import re
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, manifest  # noqa: E402
from incubator_brpc_tpu import native  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel,
    ChannelOptions,
    Controller,
    Server,
    ServerOptions,
)
from incubator_brpc_tpu.transport import device, native_plane  # noqa: E402
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402
from incubator_brpc_tpu.utils.status import ErrorCode  # noqa: E402

needs_native = pytest.mark.skipif(
    not native_plane.NET_AVAILABLE, reason="native runtime unavailable"
)

CALLERS = 16
# 260 B is 65 words: one over the edge of the 64-word bucket
SIZES = (64, 260, 4096, 65536)
MIX = {"sizes": list(SIZES), "pool_per_size": 2}
SEED = 2**31 + 27
REFERENCE = manifest.load_module("references", "echo_identity.py")
NATIVE_CONFIG = manifest.load_json("configs", "echo_device_native.json")


@pytest.fixture(scope="module")
def endpoint():
    return DeviceEndpoint(window_size=16, max_batch=16)


def serve(handler, native_on: bool) -> Server:
    server = Server(ServerOptions(native_plane=native_on))
    server.add_service("tensor", {"echo": handler})
    assert server.start(0)
    return server


def connect(server: Server, native_on: bool, protocol: str = "tbus_std") -> Channel:
    channel = Channel()
    assert channel.init(
        f"127.0.0.1:{server.port}",
        options=ChannelOptions(native_plane=native_on, protocol=protocol),
    )
    return channel


def call(channel: Channel, request: bytes) -> Controller:
    # a shape's first call compiles its program: seconds on the CPU
    return channel.call_method(
        "tensor", "echo", request, cntl=Controller(timeout_ms=60000)
    )


def sixteen_callers(channel: Channel) -> dict:
    """Every caller sends its own seeded pool, every size; returns
    ``{(caller, size, i): (request, controller)}``."""
    out, errors = {}, []

    def caller(c):
        try:
            pool = generator.make_pool(MIX, SEED, c)
            for size in SIZES:
                for i, request in enumerate(pool[size]):
                    out[(c, size, i)] = (request, call(channel, request))
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(CALLERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(out) == CALLERS * len(SIZES) * MIX["pool_per_size"]
    return out


def wait_until(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def recorder_counts():
    device.flush_stage_recorders()
    return {
        "ingress": (device.m_ingress.count(), device.m_ingress.latency_sum()),
        "plane_callback": (
            device.m_plane_callback.count(),
            device.m_plane_callback.latency_sum(),
        ),
        "egress": (device.m_egress.count(), device.m_egress.latency_sum()),
        "wake": (device.m_wake.count(), device.m_wake.latency_sum()),
    }


def gained(before, after, name):
    return tuple(a - b for a, b in zip(after[name], before[name]))


@needs_native
class TestAgainstTheReference:
    @pytest.mark.parametrize("protocol", ["tbus_std", "baidu_std"])
    def test_sixteen_callers_get_their_own_bytes_on_both_planes(
        self, endpoint, protocol
    ):
        answers = {}
        for plane in ("native", "python"):
            on = plane == "native"
            server = serve(endpoint.server_handler(), on)
            try:
                assert (server._native_plane is not None) == on
                channel = connect(server, on, protocol)
                calls = sixteen_callers(channel)
                if on:
                    # the calls went the C++ way on both ends
                    assert channel._native_ch is not None
                    stats = server._native_plane.stats()
                    assert stats["cb_frames"] >= len(calls)
                    assert stats["handoffs"] == 0
            finally:
                server.stop()
                server.join(timeout=10)
            for key, (request, cntl) in calls.items():
                assert cntl.ok(), (plane, key, cntl.error_text)
                want = REFERENCE.expected(request, b"")
                got = (cntl.response_payload, cntl.response_attachment)
                assert got == want, (plane, key)
            answers[plane] = {k: c.response_payload for k, (_r, c) in calls.items()}
        assert answers["native"] == answers["python"]
        # pools differ by caller, so a swapped answer could not have passed
        assert len(set(answers["native"].values())) == len(answers["native"])

    @pytest.mark.parametrize("protocol", ["tbus_std", "baidu_std"])
    def test_a_frame_the_device_flags_fails_the_call(self, endpoint, protocol):
        # no method 7 in the service: the step answers with an error frame
        server = serve(endpoint.server_handler(method_id=7), True)
        try:
            channel = connect(server, True, protocol)
            cntl = call(channel, b"flag me" * 9)
            assert cntl.failed() and cntl.error_code == ErrorCode.ENOMETHOD
            assert cntl.response_payload == b""
            assert channel._native_ch is not None
        finally:
            server.stop()
            server.join(timeout=10)


@needs_native
class TestTheDeploymentFile:
    """``benchmark/deployments/device_echo_native.py`` as ``run.py`` builds
    it, driven by the benchmark's own caller and reference."""

    TRAFFIC = {"service": "tensor", "method": "echo", "carrier": "payload"}

    def drive(self, deployment, calls=6):
        channel = Channel()
        assert channel.init(
            f"127.0.0.1:{deployment.port}",
            options=ChannelOptions(**NATIVE_CONFIG["channel_options"]),
        )
        send = generator.channel_caller(channel, self.TRAFFIC, REFERENCE)
        pool = generator.make_pool({"sizes": [256], "pool_per_size": calls}, SEED, 0)
        return [send(data)[1] for data in pool[256]], channel

    def build(self, control=None):
        module = manifest.load_module("deployments", "device_echo_native.py")
        return module.Deployment(NATIVE_CONFIG, control, None)

    def test_it_is_the_echo_deployment_with_the_plane_changed(self):
        python = manifest.load_json("configs", "echo_device.json")
        for key in ("endpoint", "method_id", "allocator", "chips", "generator",
                    "reference", "reduced"):
            assert NATIVE_CONFIG[key] == python[key], key
        assert NATIVE_CONFIG["guarantees"][:2] == python["guarantees"]
        assert len(NATIVE_CONFIG["guarantees"]) == 3
        assert NATIVE_CONFIG["server_options"] == {"native_plane": True}
        assert NATIVE_CONFIG["channel_options"] == {"native_plane": True}
        module = manifest.load_module("deployments", "device_echo_native.py")
        assert module.CONTROLS == ("flip_bit", "stale")
        # warm-up and close are device_echo's own, not copies
        assert not {"warm", "close"} & set(vars(module.Deployment))
        assert module.Deployment.__mro__[1].__module__.endswith("device_echo_py")

    def test_sound_calls_hold_the_third_guarantee(self):
        deployment = self.build()
        try:
            statuses, channel = self.drive(deployment)
            assert statuses == [generator.OK] * 6
            assert channel._native_ch is not None
            rows = deployment.holds()
            assert all(held for *_rest, held in rows), rows
            what = {row[0]: row[1] for row in rows}
            assert what["server_plane"] == "NativeServerPlane"
            assert what["frames_the_native_callback_delivered"] >= 6
            assert what["native_reactors"] >= 1
        finally:
            deployment.close()

    @pytest.mark.parametrize("control", ["flip_bit", "stale"])
    def test_a_broken_answer_is_caught_on_this_plane_too(self, control):
        deployment = self.build(control)
        try:
            statuses, _ = self.drive(deployment)
            # a flipped bit spoils every answer; the first stale answer is
            # the call's own, every later one its predecessor's
            wrong = statuses if control == "flip_bit" else statuses[1:]
            assert wrong and all(s == generator.MISMATCH for s in wrong), statuses
            assert all(held for *_rest, held in deployment.holds())
        finally:
            deployment.close()

    def test_a_fallen_back_plane_is_not_held(self, monkeypatch, caplog):
        monkeypatch.setattr(native_plane, "NET_AVAILABLE", False)
        with caplog.at_level(logging.WARNING):
            deployment = self.build()
            try:
                statuses, channel = self.drive(deployment)
                rows = deployment.holds()
            finally:
                deployment.close()
        # the fall-back serves, so the responses alone would not tell
        assert statuses == [generator.OK] * 6 and channel._native_ch is None
        held = {row[0]: row for row in rows}
        assert held["server_plane"][1] == "python"
        assert not any(ok for *_rest, ok in rows)
        # and each end said so once
        said = [r.getMessage() for r in caplog.records
                if "native_plane=True" in r.getMessage()]
        assert len([m for m in said if m.startswith("Server")]) == 1, said
        assert len([m for m in said if m.startswith("Channel")]) == 1, said

    def test_not_held_reaches_the_result_line(self, tmp_path):
        """``run.py --rehearse-on-cpu`` on a checkout whose library cannot
        be had: ``NOT HELD`` and ``correct: false``."""
        import subprocess

        absent = str(tmp_path / "no_such_libtbutil.so")
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
             "--workload", "echo_256b_c16_native", "--seed", str(SEED),
             "--seconds", "1", "--trace", "0", "--rehearse-on-cpu"],
            cwd=ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, TBNET_LIB=absent),
        )
        assert r.returncode == 0, r.stderr[-2000:]
        lines = r.stdout.strip().splitlines()
        result = json.loads(lines[-1])
        assert result["correct"] is False and result["failed"] == 0
        assert any(
            ln.startswith("CHECK server_plane: python") and "NOT HELD" in ln
            for ln in lines
        )


@needs_native
class TestTheCutStamp:
    def test_arrival_is_the_c_side_cut_on_the_c_clock(self):
        """``frame.arrival_ts`` is read in ``tbnet.cc`` where the frame
        leaves the cut loop, on ``tb_monotonic_ns()``'s clock, which is
        ``time.monotonic()``'s: it lies between the client's send and the
        callback's entry, not at the callback's mercy."""
        seen = []

        def handler(cntl, request):
            seen.append((cntl._arrival_ts, cntl._plane_callback_ns,
                         time.monotonic_ns()))
            return request

        a, b = native.LIB.tb_monotonic_ns(), time.monotonic_ns()
        assert abs(b - a) < 50_000_000  # one clock
        server = serve(handler, True)
        try:
            channel = connect(server, True)
            for i in range(5):
                t0 = native.LIB.tb_monotonic_ns()
                cntl = call(channel, b"cut-%d" % i)
                t1 = native.LIB.tb_monotonic_ns()
                assert cntl.ok(), cntl.error_text
                arrival, entered, in_handler = seen[-1]
                cut_ns = arrival * 1e9
                slack = 2_000  # the float seconds round by under a us
                assert t0 - slack <= cut_ns <= entered + slack
                assert entered <= in_handler <= t1
        finally:
            server.stop()
            server.join(timeout=10)

    def test_the_python_plane_has_no_callback_stamp(self):
        seen = []

        def handler(cntl, request):
            seen.append((cntl._arrival_ts, cntl._plane_callback_ns))
            return request

        server = serve(handler, False)
        try:
            cntl = call(connect(server, False), b"py")
            assert cntl.ok()
            arrival, entered = seen[0]
            assert arrival is not None and entered is None
        finally:
            server.stop()
            server.join(timeout=10)

    def test_a_frame_that_waited_out_its_budget_is_shed_from_the_cut(self):
        """The deadline shed reads the same field on the same clock: a
        budget that ran out between the cut and process_request is
        answered EDEADLINE without the handler."""
        from incubator_brpc_tpu.protocol.tbus_std import (
            Meta,
            ParsedFrame,
            try_parse_frame,
        )

        hits = []
        server = serve(lambda cntl, request: hits.append(1) or request, True)
        try:
            class CaptureSock:
                remote, context, written = None, {}, []

                def write(self, data, on_error=None, timeout=None):
                    self.written.append(
                        data.to_bytes() if hasattr(data, "to_bytes") else data
                    )
                    return 0

            sock = CaptureSock()
            frame = ParsedFrame(
                meta=Meta(service="tensor", method="echo", timeout_ms=50),
                payload=b"late", correlation_id=9,
            )
            # as _on_frame stamps it: the C++ clock's cut, 80 ms ago
            frame.arrival_ts = (native.LIB.tb_monotonic_ns() - 80_000_000) / 1e9
            server.process_request(sock, frame)
            assert not hits
            response, _ = try_parse_frame(sock.written[0])
            assert response.error_code == ErrorCode.EDEADLINE
        finally:
            server.stop()
            server.join(timeout=10)


@needs_native
class TestTheCallbackKeepsTheLock:
    """The reactor's frame callback queues for the interpreter lock again
    after every CDLL call; on the chip's host that was ~0.5 ms a turn with
    16 handler threads behind it (PERF.md, PR 27). Its short calls go
    through a PyDLL handle, which keeps the lock."""

    def test_the_held_handle_is_the_same_library_declared_alike(self):
        import ctypes

        assert isinstance(native.LIB_HELD, ctypes.PyDLL)
        assert native.LIB_HELD._name == native.LIB._name
        for name, (restype, argtypes) in native.SIGNATURES.items():
            fn = getattr(native.LIB_HELD, name)
            assert fn.restype == restype and fn.argtypes == argtypes, name

    @pytest.mark.parametrize("n", [1, 255, 65536, 65537, 300_000])
    def test_copy_out_gives_the_bytes_whichever_handle_copies(self, n):
        from incubator_brpc_tpu.iobuf import IOBuf

        data = bytes(range(256)) * (n // 256 + 1)
        buf = IOBuf()
        buf.append(data[:n])
        assert native_plane._copy_out(buf._h, n, 0) == data[:n]
        tail = min(n, 100)
        assert native_plane._copy_out(buf._h, tail, n - tail) == data[n - tail:n]
        assert native_plane._copy_out(buf._h, 0, 0) == b""
        assert len(buf) == n  # a copy, not a cut

    def test_the_callback_makes_no_call_that_gives_the_lock_up(self, monkeypatch):
        """Every library call ``_on_frame`` makes for a small frame goes
        through the held handle (the dispatch is stubbed: what follows the
        callback is the worker pool's)."""
        import ctypes

        from incubator_brpc_tpu.protocol.tbus_std import Meta

        class Loud:
            def __getattr__(self, name):
                raise AssertionError(f"LIB.{name} gives the lock up")

        got = []
        server = serve(lambda cntl, request: request, True)
        try:
            plane = server._native_plane
            monkeypatch.setattr(plane, "_dispatch", lambda sock, frame: got.append(frame))
            token = 0x7357
            plane._sock_for(token)  # a connection's facade is made once
            meta = Meta(service="tensor", method="echo", attachment_size=3).to_bytes()
            meta_buf = ctypes.create_string_buffer(meta, len(meta))
            body = native.LIB.tb_iobuf_create()  # _on_frame frees it
            native.LIB.tb_iobuf_append(body, b"x" * 256 + b"att", 259)
            cut_ns = native.LIB.tb_monotonic_ns()
            monkeypatch.setattr(native_plane, "LIB", Loud())
            plane._on_frame(
                None, token, 9, 0, 0, 0,
                ctypes.addressof(meta_buf), len(meta), body, cut_ns,
            )
        finally:
            monkeypatch.undo()
            server.stop()
            server.join(timeout=10)
        (frame,) = got
        assert (frame.payload, frame.attachment) == (b"x" * 256, b"att")
        assert frame.correlation_id == 9
        assert frame.arrival_ts == cut_ns / 1e9 <= frame.plane_callback_ns / 1e9


class TestHostPlaneRecorders:
    @pytest.mark.parametrize("native_on", [
        pytest.param(True, marks=needs_native), False,
    ])
    def test_one_sample_a_call_on_the_way_in_and_the_way_out(
        self, endpoint, native_on
    ):
        server = serve(endpoint.server_handler(), native_on)
        try:
            channel = connect(server, native_on)
            cold = recorder_counts()
            assert call(channel, b"warm" * 16).ok()
            # a row is appended once the response is written, which the
            # client may see first
            assert wait_until(
                lambda: gained(cold, recorder_counts(), "egress")[0] == 1
            )
            before = recorder_counts()
            for i in range(8):
                assert call(channel, b"%03d!" % i * 16).ok()
            assert wait_until(
                lambda: gained(before, recorder_counts(), "egress")[0] == 8
            )
            after = recorder_counts()
        finally:
            server.stop()
            server.join(timeout=10)
        n_in, us_in = gained(before, after, "ingress")
        n_cb, us_cb = gained(before, after, "plane_callback")
        n_out, us_out = gained(before, after, "egress")
        assert n_in == n_out == 8
        assert 0 < us_out < 8 * 1e6 and 0 < us_in < 8 * 1e6
        if native_on:
            # the reactor's wait for the interpreter is a part of the way in
            assert n_cb == 8 and 0 < us_cb <= us_in
        else:
            assert (n_cb, us_cb) == (0, 0)

    def test_a_direct_call_records_its_stages_without_the_host_plane(
        self, endpoint
    ):
        before = recorder_counts()
        code, out = endpoint.call_bytes(b"direct" * 8, timeout=30)
        assert code == 0 and out == b"direct" * 8
        after = recorder_counts()
        assert gained(before, after, "wake")[0] == 1
        for name in ("ingress", "plane_callback", "egress"):
            assert gained(before, after, name) == (0, 0), name

    def test_the_names_are_exposed_for_the_benchmark_to_snapshot(self):
        from incubator_brpc_tpu.bvar import expose_registry

        names = [name for name, _ in expose_registry.snapshot("device_transport")]
        assert "device_transport_plane_callback_us" in names
        assert "device_transport_egress_us" in names


@needs_native
class TestNativeDeviceCallSpan:
    def test_a_sampled_span_carries_the_planes_two_marks(self, tuned_flags):
        from incubator_brpc_tpu.builtin.rpcz import span_store

        ep = DeviceEndpoint(window_size=4)
        ep.warm(64)
        tuned_flags("enable_rpcz", True)
        tuned_flags("rpcz_samples_per_second", 10_000_000)
        time.sleep(0.01)  # the token bucket fills at the new rate
        span_store.clear()
        server = serve(ep.server_handler(), True)
        try:
            cntl = call(connect(server, True), b"span-me")
            assert cntl.ok(), cntl.error_text

            def spans():
                return [sp for sp in span_store.recent()
                        if sp.span_type == "server" and sp.method == "echo"]

            assert wait_until(lambda: bool(spans()))
            span = spans()[0]
        finally:
            server.stop()
            server.join(timeout=10)
            span_store.clear()
        marks = [(off, text.split()[1]) for off, text in span.annotations
                 if text.startswith("device ")]
        names = [name for _off, name in marks]
        assert names[0] == "plane_callback" and names[1] == "entry"
        assert names[-2:] == ["exit", "sent"]
        offsets = [off for off, _name in marks]
        assert offsets == sorted(offsets)
        # the callback ran before process_request started the span
        assert offsets[0] <= 0 <= offsets[1]
        assert re.match(r"device batched dispatch=\d+ rows=1", [
            t for _o, t in span.annotations if t.startswith("device batched")
        ][0])
