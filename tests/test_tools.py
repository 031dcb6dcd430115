"""rpc_dump capture + rpc_replay/rpc_press/rpc_view tool tests (reference
src/brpc/rpc_dump.{h,cpp}, tools/rpc_replay, tools/rpc_press)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from incubator_brpc_tpu.rpc import Channel, ChannelOptions, Server  # noqa: E402
from incubator_brpc_tpu.rpc.dump import RpcDumper, load_dump_file  # noqa: E402
from incubator_brpc_tpu.utils.flags import flag_registry, set_flag  # noqa: E402


@pytest.fixture
def echo_server():
    server = Server()
    seen = []

    def echo(cntl, request):
        seen.append(request)
        return request

    server.add_service("dump", {"echo": echo})
    assert server.start(0)
    yield server, seen
    server.stop()
    server.join(timeout=5)


class TestRpcDump:
    def test_server_samples_when_enabled(self, echo_server, tmp_path):
        from incubator_brpc_tpu.rpc.dump import reset_global_dumper

        server, _ = echo_server
        old_dir = flag_registry.get("rpc_dump_dir")
        flag_registry.set_unchecked("rpc_dump_dir", str(tmp_path))
        assert set_flag("rpc_dump", True)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            for i in range(5):
                assert ch.call_method("dump", "echo", b"req-%d" % i).ok()
        finally:
            set_flag("rpc_dump", False)
            flag_registry.set_unchecked("rpc_dump_dir", old_dir)
            reset_global_dumper()  # drop the handle into tmp_path
        files = [f for f in os.listdir(tmp_path) if f.startswith("requests.")]
        assert files
        samples = []
        for f in files:
            samples.extend(load_dump_file(str(tmp_path / f)))
        payloads = {p for _, p, _ in samples}
        assert {b"req-%d" % i for i in range(5)} <= payloads
        meta = samples[0][0]
        assert (meta.service, meta.method) == ("dump", "echo")

    def test_sampling_budget_caps_rate(self, tmp_path):
        d = RpcDumper(directory=str(tmp_path))
        flag_registry.set_unchecked("rpc_dump_max_requests_per_second", 3)
        try:
            from incubator_brpc_tpu.protocol.tbus_std import Meta

            taken = [d.sample(Meta(service="s", method="m"), b"x") for _ in range(10)]
            assert taken.count(True) == 3
        finally:
            flag_registry.set_unchecked("rpc_dump_max_requests_per_second", 100)
        d.close()

    def test_file_rotation(self, tmp_path):
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        flag_registry.set_unchecked("rpc_dump_max_requests_in_one_file", 2)
        try:
            d = RpcDumper(directory=str(tmp_path))
            for i in range(5):
                assert d.sample(Meta(service="s", method="m"), b"%d" % i)
            d.close()
        finally:
            flag_registry.set_unchecked("rpc_dump_max_requests_in_one_file", 1000)
        files = sorted(os.listdir(tmp_path))
        assert len(files) == 3  # 2 + 2 + 1


class TestReplay:
    def test_replay_reissues_samples(self, echo_server, tmp_path):
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        server, seen = echo_server
        d = RpcDumper(directory=str(tmp_path))
        for i in range(4):
            assert d.sample(Meta(service="dump", method="echo"), b"replay-%d" % i)
        d.close()

        from tools.rpc_replay import load_requests, run_replay

        requests = load_requests(str(tmp_path))
        assert len(requests) == 4
        # generous timeout: a loaded host can stall >1s and flake the
        # default; the assertion is about correctness, not latency
        stats = run_replay(
            requests, f"127.0.0.1:{server.port}", threads=2, times=2,
            timeout_ms=15000,
        )
        assert stats == {"ok": 8, "fail": 0, "total": 8}
        assert sorted(seen) == sorted([b"replay-%d" % i for i in range(4)] * 2)


class TestPress:
    def test_press_drives_load(self, echo_server):
        server, _ = echo_server
        from tools.rpc_press import run_press

        stats = run_press(
            f"127.0.0.1:{server.port}",
            "dump",
            "echo",
            b"press",
            threads=2,
            duration=0.5,
        )
        assert stats["fail"] == 0
        assert stats["ok"] > 10
        assert stats["latency_us_p99"] >= stats["latency_us_p50"] > 0

    def test_press_reactor_mode_reports_distribution(self):
        # --reactors N --conns-per-reactor M: the sharded-accept load
        # run against a multi-reactor native server, per-reactor conn
        # distribution scraped from the target's /vars
        from incubator_brpc_tpu.rpc import (
            Server,
            ServerOptions,
            native_echo,
        )
        from incubator_brpc_tpu.transport import native_plane as np_mod
        from tools.rpc_press import run_reactor_press

        if not np_mod.NET_AVAILABLE:
            import pytest as _pytest

            _pytest.skip("native runtime unavailable")
        srv = Server(
            ServerOptions(
                native_plane=True, usercode_inline=True, num_reactors=4
            )
        )
        srv.add_service("demo", {"echo": native_echo})
        assert srv.start(0)
        try:
            stats = run_reactor_press(
                f"127.0.0.1:{srv.port}", "demo", "echo", b"press",
                reactors=4, conns_per_reactor=1, duration=0.5,
                timeout_ms=15000,
            )
            assert stats["fail"] == 0
            assert stats["ok"] > 10
            assert stats["cid_misroutes"] == 0
            # round-robin accept sharding: 4 conns spread one per reactor
            assert stats["reactor_conns"] == {0: 1, 1: 1, 2: 1, 3: 1}
            assert len(stats["client_shards"]) == 4
        finally:
            srv.stop()

    def test_press_over_device_links(self, echo_server):
        # --transport tpu: the rdma_performance client's use_rdma flag —
        # the same load loop over the device plane
        server, _ = echo_server
        from tools.rpc_press import run_press

        # the first call over a device pair builds the link and compiles
        # its step (seconds); links are shared per pair, so one call here
        # keeps that out of the half-second load window below
        warm = Channel()
        assert warm.init(
            f"127.0.0.1:{server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=60000),
        )
        assert warm.call_method("dump", "echo", b"warm").ok()

        stats = run_press(
            f"127.0.0.1:{server.port}",
            "dump",
            "echo",
            b"press-tpu",
            threads=2,
            duration=0.5,
            timeout_ms=60000,
            transport="tpu",
        )
        assert stats["fail"] == 0
        assert stats["ok"] > 5


class TestView:
    def test_view_prints_samples(self, tmp_path, capsys):
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        d = RpcDumper(directory=str(tmp_path))
        assert d.sample(Meta(service="v", method="m"), b"hello-view")
        d.close()
        from tools.rpc_view import main as view_main

        path = os.path.join(str(tmp_path), sorted(os.listdir(tmp_path))[0])
        assert view_main([path]) == 0
        out = capsys.readouterr().out
        assert "v.m" in out and "hello-view" in out and "1/1 samples" in out

    def test_view_filters_and_json(self, tmp_path, capsys):
        from incubator_brpc_tpu.protocol.tbus_std import Meta

        d = RpcDumper(directory=str(tmp_path))
        assert d.sample(Meta(service="a", method="m1"), b"one")
        assert d.sample(Meta(service="b", method="m2"), b"two")
        d.close()
        from tools.rpc_view import main as view_main

        path = os.path.join(str(tmp_path), sorted(os.listdir(tmp_path))[0])
        assert view_main(["--service", "b", "--json", path]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        import json as _json

        rows = [_json.loads(line) for line in out]
        assert len(rows) == 1 and rows[0]["service"] == "b"

    def test_view_proxies_target_portal(self, echo_server):
        # the reference rpc_view shape: a front server relaying every path
        # to the target's builtin portal (rpc_view.cpp)
        from incubator_brpc_tpu.protocol.http import http_call
        from tools.rpc_view import make_proxy_server, serve_proxy

        target_server, _ = echo_server
        assert make_proxy_server("not-a-target") is None
        assert serve_proxy(0, "not-a-target") == 2
        front = make_proxy_server(f"127.0.0.1:{target_server.port}")
        assert front is not None and front.start(0)
        try:
            status, _, body = http_call("127.0.0.1", front.port, "/health")
            assert status == 200
            assert b"OK" in body and b"rpc_view of" in body  # tagged relay
            status, _, body = http_call("127.0.0.1", front.port, "/vars")
            assert status == 200 and b"socket_in_bytes" in body
            status, _, body = http_call("127.0.0.1", front.port, "/")
            assert status == 200 and b"rpc_view of" in body  # html tag
        finally:
            front.stop()


class TestViewRpcz:
    """rpc_view --rpcz: the scrape-side twin of --metrics for the
    tracing plane (fetches /rpcz?json=1, prints spans or one trace
    tree)."""

    @pytest.fixture
    def traced_server(self, echo_server, tuned_flags):
        from incubator_brpc_tpu.builtin.rpcz import Span, span_store

        server, _ = echo_server
        tuned_flags("enable_rpcz", True)
        span_store.clear()
        span_store.submit(Span(
            trace_id=0xBEE, span_id=1, parent_span_id=0, span_type="server",
            service="tool", method="root", latency_us=500, start_real_us=10,
        ))
        span_store.submit(Span(
            trace_id=0xBEE, span_id=2, parent_span_id=1, span_type="client",
            service="tool", method="leaf", latency_us=100, error_code=9,
            start_real_us=20,
        ))
        yield server
        span_store.clear()

    def test_rpcz_mode_prints_recent_spans(self, traced_server, capsys):
        from tools.rpc_view import main as view_main

        rc = view_main(
            ["--rpcz", "--target", f"127.0.0.1:{traced_server.port}"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "2 spans" in out
        assert "tool.root" in out and "tool.leaf" in out

    def test_rpcz_mode_assembles_trace_tree(self, traced_server, capsys):
        from tools.rpc_view import main as view_main

        rc = view_main([
            "--rpcz", "--target", f"127.0.0.1:{traced_server.port}",
            "--trace-id", "bee",
        ])
        out = capsys.readouterr().out
        assert rc == 0
        lines = [ln for ln in out.splitlines() if "trace=" in ln]
        assert lines[0].startswith("trace=bee span=1")
        assert lines[1].startswith("  trace=bee span=2")  # child indented

    def test_rpcz_mode_filters(self, traced_server, capsys):
        from tools.rpc_view import main as view_main

        rc = view_main([
            "--rpcz", "--target", f"127.0.0.1:{traced_server.port}",
            "--error-only",
        ])
        out = capsys.readouterr().out
        assert rc == 0
        assert "1 spans" in out and "error=9" in out

    def test_rpcz_mode_bad_target(self, capsys):
        from tools.rpc_view import main as view_main

        assert view_main(["--rpcz", "--target", "not-a-target"]) == 2
        # unreachable port: a clean error, not a traceback
        assert view_main(["--rpcz", "--target", "127.0.0.1:1"]) == 1


class TestParallelHttp:
    def test_fetches_portal_urls_concurrently(self, echo_server):
        from tools.parallel_http import fetch_all

        server, _ = echo_server
        port = server.port
        urls = [
            f"http://127.0.0.1:{port}/health",
            f"http://127.0.0.1:{port}/version",
            f"http://127.0.0.1:{port}/vars.json",
            f"http://127.0.0.1:{port}/does-not-exist",
        ]
        results = fetch_all(urls, threads=4, timeout_ms=5000)
        by_url = {r[0]: r for r in results}
        assert by_url[urls[0]][1] == 200
        assert by_url[urls[1]][1] == 200
        assert by_url[urls[2]][1] == 200 and by_url[urls[2]][2] > 2
        # a 404 is a completed fetch with an error status, not a crash
        assert by_url[urls[3]][1] in (None, 404)
