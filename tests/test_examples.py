"""Every example pair must keep running (the reference treats example/
as living documentation; SURVEY §1 L7). Each runs in-process on the
virtual mesh — tensor_echo_tpu is exercised via its own module path in
test_device_transport, so only the host-plane examples run here."""

import runpy
import sys

import pytest

EXAMPLES = [
    "examples/echo.py",
    "examples/parallel_echo.py",
    "examples/streaming_echo.py",
    "examples/partition_echo.py",
    "examples/backup_request.py",
    "examples/multi_protocol.py",
    "examples/tls_echo.py",
    "examples/rtmp_relay.py",
    "examples/naming_failover.py",
    "examples/overload_and_breaker.py",
    "examples/cache_clients.py",
    "examples/link_performance.py",
    "examples/http_upload.py",
    "examples/session_data_and_thread_local.py",
    "examples/dynamic_partition_echo.py",
    "examples/multi_threaded_echo.py",
    "examples/cancel_echo.py",
    "examples/cascade_echo.py",
    "examples/selective_echo.py",
    "examples/asynchronous_echo.py",
    "examples/ubrpc_compack.py",
    "examples/nshead_extension.py",
    "examples/expert_shard.py",
]


@pytest.mark.parametrize("path", EXAMPLES)
def test_example_runs(path, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [path])
    runpy.run_path(path, run_name="__main__")
    out = capsys.readouterr().out
    assert out.strip()  # every example prints its result
