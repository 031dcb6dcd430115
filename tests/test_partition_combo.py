"""The partitioned deployment (``benchmark/deployments/partition_echo.py``:
a ``PartitionChannel`` over ``Channel(transport="tpu")`` to three shards,
fused into one ``shard_map`` all-gather dispatch) on the CPU's forced host
devices with small rows: the fused call, the host fan-out and the plain
reference ``partition_concat`` on seeded payloads; the attachment a combo
channel now takes; concurrent callers through the fused dispatch; the
recorders, adders and the span PR 33 gave ``rpc/combo.py``; the must-fail
controls. Every test that could hang runs under a time limit of its own."""

import copy
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, manifest, spans, xplane  # noqa: E402
from test_stream_link_deployment import limited  # noqa: E402 — a test's own time limit

CONFIG = manifest.load_json("configs", "partition_echo_ici.json")
ROW = 512
TRAFFIC = {
    "sizes": [3 * ROW], "carrier": "payload",
    "service": "PartitionEcho", "method": "Echo",
}
REFERENCE = manifest.load_module("references", "partition_concat.py")
STAGES = ("resolve", "pack", "put", "launch_wait", "launch", "gather", "merge")


def payload(seed: int, size: int) -> bytes:
    return np.random.default_rng(seed).bytes(size)


def deploy(control=None):
    """The configuration as its file states it, but for rows of 512 B."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs a 4+ device mesh")
    config = copy.deepcopy(CONFIG)
    config["row_bytes"] = ROW
    module = manifest.load_module("deployments", "partition_echo.py")
    deployment = module.Deployment(config, control, None)
    deployment.warm(TRAFFIC)
    return module, deployment


def call(channel, request: bytes, **more):
    from incubator_brpc_tpu.rpc import Controller

    cntl = channel.call_method(
        "PartitionEcho", "Echo", request, cntl=Controller(timeout_ms=60000), **more)
    assert cntl.ok(), cntl.error_text
    return cntl


def combo_vars():
    """The program's counters now, as the harness snapshots them, every
    waiting row of the combo feed fed first."""
    from incubator_brpc_tpu.rpc.combo import COMBO_VARS

    COMBO_VARS.calls.flush()
    return spans.counters()


def gained(before: dict, after: dict, name: str):
    a, b = before[f"device_link_combo_{name}"], after[f"device_link_combo_{name}"]
    return {k: b[k] - a[k] for k in b} if isinstance(b, dict) else b - a


@pytest.mark.parametrize("size", [3 * ROW, 2 * ROW + 17, ROW - 5],
                         ids=["three_rows", "two_rows_and_a_part", "under_a_row"])
@limited(120)
def test_fused_host_fanout_and_reference_give_the_same_bytes(size):
    """Exactly three rows; two rows and a part; shorter than one row, so two
    shards get an empty slice."""
    _, deployment = deploy()
    try:
        channel = deployment.channel()
        request = payload(size, size)
        fused = call(channel, request)
        assert fused.collective_fused is True
        channel.fuse_device_calls = False
        host = call(channel, request)
        assert getattr(host, "collective_fused", False) is False
        want = REFERENCE.merged(request, ROW, 3)
        assert fused.response_payload == host.response_payload == want
        rows = REFERENCE.slices(request, ROW, 3)
        assert [len(r) for r in rows] == [
            max(0, min(ROW, size - i * ROW)) for i in range(3)]
        assert b"".join(rows) == request
    finally:
        deployment.close()


def test_the_reference_refuses_what_does_not_fit_its_rows():
    assert REFERENCE.expected(b"a", b"b") == (b"a", b"b")
    assert (REFERENCE.PARTITIONS, REFERENCE.ROW_BYTES) == (
        CONFIG["partitions"], CONFIG["row_bytes"])
    assert REFERENCE.slices(b"abcde", 2, 3) == [b"ab", b"cd", b"e"]
    assert REFERENCE.slices(b"ab", 2, 3) == [b"ab", b"", b""]
    with pytest.raises(ValueError):
        REFERENCE.slices(b"abcdefg", 2, 3)


@limited(120)
def test_an_attachment_keeps_a_call_from_fusing():
    _, deployment = deploy()
    try:
        channel = deployment.channel()
        request = payload(7, 3 * ROW)
        before = combo_vars()
        cntl = call(channel, request, attachment=b"rides along")
        after = combo_vars()
        assert getattr(cntl, "collective_fused", False) is False
        assert cntl.response_payload == request
        assert gained(before, after, "host_fanout") == 1
        assert gained(before, after, "fused") == 0
    finally:
        deployment.close()


@limited(60)
def test_the_host_fan_out_forwards_the_attachment_and_joins_the_answers():
    """Upstream appends the parent's request attachment to every sub-call;
    here the sub-calls' response attachments come back in channel order."""
    from incubator_brpc_tpu.rpc import (
        Channel, Controller, ParallelChannel, SelectiveChannel, Server, SubCall,
    )

    seen = []

    def echo(i):
        def handler(cntl, request):
            seen.append((i, cntl.request_attachment))
            cntl.response_attachment = b"%d:" % i + cntl.request_attachment
            return request
        return handler

    class SkipMiddle:
        def map(self, i, n, service, method, request):
            return SubCall.skip() if i == 1 else SubCall()

    servers = []
    try:
        pc, sc = ParallelChannel(), SelectiveChannel()
        for i in range(3):
            server = Server()
            server.add_service("EchoService", {"Echo": echo(i)})
            assert server.start(0)
            servers.append(server)
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            pc.add_channel(ch, call_mapper=SkipMiddle())
            if i == 0:
                sc.add_channel(ch)
        cntl = pc.call_method("EchoService", "Echo", b"req", attachment=b"att",
                              cntl=Controller(timeout_ms=10000))
        assert cntl.ok(), cntl.error_text
        assert sorted(seen) == [(0, b"att"), (2, b"att")]  # not the skipped one
        assert cntl.response_payload == b"reqreq"
        assert cntl.response_attachment == b"0:att2:att"
        # a stream rides one connection: a combo channel refuses it cleanly
        refused = pc.call_method("EchoService", "Echo", b"req", request_stream=object())
        assert refused.failed() and "stream" in refused.error_text
        one = sc.call_method("EchoService", "Echo", b"req", attachment=b"sel")
        assert one.ok() and one.response_attachment == b"0:sel"
    finally:
        for server in servers:
            server.stop()
            server.join(timeout=5)


@limited(120)
def test_four_callers_through_one_fused_channel_each_get_their_own_bytes():
    _, deployment = deploy()
    try:
        channel = deployment.channel()
        wrong, errors = [], []

        def caller(c):
            try:
                for i in range(25):
                    request = payload(1000 * c + i, 3 * ROW - (i % 5))
                    cntl = call(channel, request)
                    if not cntl.collective_fused or cntl.response_payload != request:
                        wrong.append((c, i))
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))

        before = combo_vars()
        threads = [threading.Thread(target=caller, args=(c,)) for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        assert not any(t.is_alive() for t in threads), "callers hung"
        assert not errors and not wrong, (errors, wrong)
        after = combo_vars()
        assert gained(before, after, "fused") == 100
        assert gained(before, after, "host_fanout") == 0
        # calls_not_fused reads the whole process, and other tests fan out
        held = {name: (value, ok) for name, value, _l, ok in deployment.holds()}
        assert held["partition_distinct_devices"] == (3, True)
        assert held["partition_geometry"] == ("ppermute", True)
    finally:
        deployment.close()


@limited(120)
def test_stage_recorders_add_up_to_the_call_and_adders_count_exactly():
    """The seven stages lie end to end inside the call: their sum is never
    over the call's, and what they leave out (the mapper's cuts, a few
    statements between stamps) is under 40% of a sub-millisecond call on
    the CPU (the chip's share is ``combo_unattributed_pct``, PERF.md)."""
    _, deployment = deploy()
    try:
        channel = deployment.channel()
        sizes = [3 * ROW, 2 * ROW + 17, ROW - 5, 3 * ROW] * 10
        before = combo_vars()
        for i, size in enumerate(sizes):
            call(channel, payload(i, size))
        after = combo_vars()
        calls = gained(before, after, "call_us")
        assert calls["count"] == len(sizes)
        stages = [gained(before, after, f"{s}_us") for s in STAGES]
        assert all(s["count"] == len(sizes) for s in stages)
        covered = sum(s["sum"] for s in stages)
        assert 0.6 * calls["sum"] <= covered <= calls["sum"]
        assert gained(before, after, "fused") == len(sizes)
        assert gained(before, after, "rows") == 3 * len(sizes)
        assert gained(before, after, "bytes") == sum(sizes)
        assert gained(before, after, "host_fanout") == 0
        assert gained(before, after, "mc_lowered") == 0
    finally:
        deployment.close()


@limited(120)
def test_a_fused_call_under_rpcz_leaves_one_span_that_names_its_lowering(tuned_flags):
    from incubator_brpc_tpu.builtin import rpcz

    _, deployment = deploy()
    try:
        channel = deployment.channel()
        tuned_flags("enable_rpcz", True)
        before = {s.span_id for s in rpcz.span_store.recent(500)}
        call(channel, payload(5, 3 * ROW))
        mine = [
            s for s in rpcz.span_store.recent(500)
            if s.span_id not in before and s.service == "PartitionEcho"
        ]
        assert len(mine) == 1
        span = mine[0]
        assert span.span_type == rpcz.SPAN_TYPE_COLLECTIVE and span.method == "Echo"
        notes = " ".join(text for _at, text in span.annotations)
        assert "lowering=fused" in notes and "partitions=3" in notes
        assert f"request_bytes={3 * ROW}" in notes
        for stage in STAGES:
            assert f"{stage}_us=" in notes
        assert span.latency_us > 0
    finally:
        deployment.close()


@pytest.mark.parametrize("control", ["flip_bit", "stale", "swap"])
@limited(120)
def test_a_control_comes_out_mismatched(control):
    module, deployment = deploy(control)
    assert control in module.CONTROLS
    try:
        send = generator.channel_caller(deployment.channel(), TRAFFIC, REFERENCE)
        before = combo_vars()
        statuses = [send(payload(seed, 3 * ROW))[1] for seed in (41, 42)]
        # warm() made the call a first stale answer is taken from
        assert statuses == [generator.MISMATCH, generator.MISMATCH]
        assert gained(before, combo_vars(), "fused") == 2  # broken under the fused path
    finally:
        deployment.close()


def test_the_swap_control_comes_out_not_correct_in_the_rehearsal():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "partition_star_4", "--seed", str(2**31 + 33),
         "--seconds", "1", "--trace", "0", "--rehearse-on-cpu", "--control", "swap"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["failed"] > 0
    assert any("NOT HELD" in line for line in lines)
    assert any(line.startswith("CHECK calls_not_fused: 0 ") for line in lines)


def test_a_traced_run_without_handler_spans_labels_its_gaps():
    """No handler runs on the fused path: ``run.py`` hands ``label_gaps``
    empty handler spans, and every gap is the client's wait or no call."""
    empty = np.zeros((0, 2), np.int64)
    gaps = (np.array([0, 5_000]), np.array([2_000, 9_000]))
    calls = (np.array([1_000]), np.array([6_000]))
    out = dict(
        (name, seconds) for name, seconds in
        xplane.label_gaps(gaps, (empty[:, 0], empty[:, 1]), calls)
        if name.startswith("total:")
    )
    assert out == {
        "total:" + xplane.HANDLER: 0.0,
        "total:" + xplane.CLIENT_WAIT: 2e-6,
        "total:" + xplane.NO_CALL: 4e-6,
    }
