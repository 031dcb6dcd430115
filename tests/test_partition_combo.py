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
import time

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


def deploy(control=None, kernel=None):
    """The configuration as its file states it, but for rows of 512 B (and,
    where a test gives one, for the kernel its shards serve)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs a 4+ device mesh")
    config = copy.deepcopy(CONFIG)
    config["row_bytes"] = ROW
    module = manifest.load_module("deployments", "partition_echo.py")
    if kernel is not None:
        module.echo_kernel = kernel  # a fresh module every load: this one's only
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


# -- PR 47: the answers joined once, or the mergers called in turn ------------
# three mergers of a call, the partitions whose merger notes its calls, and
# whether a fused call's answer is one join (tests/test_combo.py has the cases
# on ``merge_responses`` alone)
from test_combo import MERGER_KINDS, MERGER_NOTES, incremental  # noqa: E402

# the request's size and the rows the mapper cuts it into: ``ROW`` is the
# kernel's width, so a cut of ROW - 9 leaves every ``n`` under it
MERGED_ROWS = {
    "full_rows": (3 * ROW, ROW),
    "a_short_row": (2 * ROW + 17, ROW),
    "an_empty_last_row": (2 * ROW, ROW),
    "every_n_under_the_width": (3 * (ROW - 9), ROW - 9),
}


@pytest.mark.parametrize("rows", list(MERGED_ROWS))
@pytest.mark.parametrize("kind", list(MERGER_KINDS))
@limited(120)
def test_the_answer_is_one_join_or_the_mergers_called_in_turn(kind, rows):
    """Fused and fanned out over the host: the default mergers' answer equals
    the incremental merge's and the plain reference's; a user's merger (a
    subclass, or ``merge`` set on an instance) is called once a partition, in
    channel order, with ``bytes``, and then the default ones are too; the
    adder counts the fused calls that were joined."""
    from incubator_brpc_tpu.rpc import CallMapper, SubCall

    size, cut = MERGED_ROWS[rows]
    noting, joins = MERGER_NOTES[kind]

    class Cut(CallMapper):
        def map(self, i, n, service, method, request):
            return SubCall(request=request[i * cut:(i + 1) * cut])

    _, deployment = deploy()
    try:
        channel = deployment.channel()
        log = []
        channel._subs = [
            (sub, Cut(), merger)
            for (sub, _mapper, _merger), merger in zip(
                channel._subs, MERGER_KINDS[kind](log))]
        request = payload(size, size)
        slices = REFERENCE.slices(request, cut, 3)
        want = incremental(slices)
        assert want == REFERENCE.merged(request, cut, 3) == request
        for fuse in (True, False):
            del log[:]
            channel.fuse_device_calls = fuse
            before = combo_vars()
            cntl = call(channel, request)
            after = combo_vars()
            assert getattr(cntl, "collective_fused", False) is fuse
            assert type(cntl.response_payload) is bytes
            assert cntl.response_payload == want, (kind, rows, fuse)
            assert log == [(who, bytes, bytes, slices[who]) for who in noting]
            assert gained(before, after, "fused") == int(fuse)
            assert gained(before, after, "joined") == int(fuse and joins)
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


def reversed_kernel(data, n):
    """Reads its whole row: the answer's first ``n`` bytes are the row's
    last, so a tail that is not zero shows in every short row's answer."""
    return data[::-1], n


def reversed_answer(row: bytes) -> bytes:
    """What a shard serving ``reversed_kernel`` answers ``row``, plainly."""
    return (bytes(ROW - len(row)) + row[::-1])[:len(row)]


def broadcast_channel(deployment):
    """The deployment's shards behind a channel with the default
    ``CallMapper``: every partition gets the whole request."""
    from incubator_brpc_tpu.rpc import ChannelOptions, PartitionChannel

    n = deployment.partitions
    url = "list://" + ",".join(
        f"127.0.0.1:{s.port} {i}/{n}" for i, s in enumerate(deployment.servers))
    channel = PartitionChannel(fail_limit=1)
    assert channel.init(url, partition_count=n, lb_name=CONFIG["lb"],
                        options=ChannelOptions(**CONFIG["channel_options"]))
    return channel


@pytest.mark.parametrize("n", [0, 1, ROW - 1, ROW])
def test_pack_into_a_dirty_row_gives_the_row_pack_makes(n):
    from incubator_brpc_tpu.rpc.device_method import DeviceMethod

    dm = DeviceMethod(reversed_kernel, width=ROW)
    request = payload(n, n)
    row, length = dm.pack(request)
    assert row.dtype == np.uint8 and row.shape == (ROW,) and length.dtype == np.int32
    assert bytes(row) == request + bytes(ROW - n) and int(length) == n
    buffer = np.full((3, ROW), 0xAB, dtype=np.uint8)
    assert dm.pack_into(buffer[1], request) == n
    assert bytes(buffer[1]) == bytes(row)
    assert set(bytes(buffer[0]) + bytes(buffer[2])) == {0xAB}  # its own row only
    for packer in (dm.pack, lambda r: dm.pack_into(buffer[0], r)):
        with pytest.raises(ValueError, match="exceeds device-method width"):
            packer(bytes(ROW + 1))


# the sizes of the requests one thread sends in turn through one channel; the
# rows a request is cut into are REFERENCE.slices', so ROW + 1 is (ROW, 1, 0)
STAGING_CASES = {
    "rows_of_0": (False, [0]),
    "a_row_of_1": (False, [1]),
    "a_row_of_width_less_1": (False, [ROW - 1]),
    "a_row_of_width": (False, [ROW]),
    "every_row_of_width": (False, [3 * ROW]),
    "unequal_rows_width_1_0": (False, [ROW + 1]),
    "unequal_rows_width_width_less_1": (False, [3 * ROW - 1]),
    "broadcast_short": (True, [ROW - 9]),
    "broadcast_of_width": (True, [ROW]),
    "short_after_full_width": (False, [3 * ROW, 5, 3 * ROW, ROW + 7]),
    "broadcast_short_after_full_width": (True, [ROW, 3, ROW, 0]),
}


@pytest.mark.parametrize("case", list(STAGING_CASES))
@limited(120)
def test_a_kernel_that_reads_its_whole_row_answers_the_same_fused_and_fanned_out(case):
    """The fused call stages its rows in a buffer that is not zero-filled
    (``DeviceMethod.pack_into``): a tail left unzeroed, or bytes of an
    earlier call's buffer, would come back through ``reversed_kernel`` where
    the host fan-out (``DeviceMethod.pack``) and the plain reference answer
    zeros."""
    broadcast, sizes = STAGING_CASES[case]
    _, deployment = deploy(kernel=reversed_kernel)
    channel = None
    try:
        channel = broadcast_channel(deployment) if broadcast else deployment.channel()
        for at, size in enumerate(sizes):
            request = payload(1000 + at, size).replace(b"\x00", b"\x01")
            rows = [request] * 3 if broadcast else REFERENCE.slices(request, ROW, 3)
            want = b"".join(reversed_answer(row) for row in rows)
            channel.fuse_device_calls = True
            fused = call(channel, request)
            assert fused.collective_fused is True
            channel.fuse_device_calls = False
            host = call(channel, request)
            assert getattr(host, "collective_fused", False) is False
            assert fused.response_payload == want, (case, at, "fused")
            assert host.response_payload == want, (case, at, "host fan-out")
    finally:
        if broadcast and channel is not None:
            channel.stop()
        deployment.close()


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


def run_callers(callers: int, calls: int, answered_rightly, seconds: float) -> None:
    """``callers`` threads make ``calls`` calls each, ``answered_rightly(c,
    i)`` one of them; none may hang, raise or be answered wrongly."""
    wrong, errors = [], []

    def caller(c):
        try:
            wrong.extend((c, i) for i in range(calls) if not answered_rightly(c, i))
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds)
    assert not any(t.is_alive() for t in threads), "callers hung"
    assert not errors and not wrong, (errors, wrong)


@limited(120)
def test_four_callers_through_one_fused_channel_each_get_their_own_bytes():
    _, deployment = deploy()
    try:
        channel = deployment.channel()

        def echoed(c, i):
            request = payload(1000 * c + i, 3 * ROW - (i % 5))
            cntl = call(channel, request)
            return cntl.collective_fused and cntl.response_payload == request

        before = combo_vars()
        run_callers(4, 25, echoed, 90)
        after = combo_vars()
        assert gained(before, after, "fused") == 100
        assert gained(before, after, "host_fanout") == 0
        # calls_not_fused reads the whole process, and other tests fan out
        held = {name: (value, ok) for name, value, _l, ok in deployment.holds()}
        assert held["partition_distinct_devices"] == (3, True)
        assert held["partition_geometry"] == ("ppermute", True)
    finally:
        deployment.close()


@limited(240)
def test_eight_callers_of_mixed_lengths_each_get_the_answer_to_their_own_request():
    """The staging buffer's life under threads: 8 callers x 100 calls of
    seeded, distinct payloads of every length from nothing to three rows
    (every tenth three full rows) through one channel, the shards serving
    ``reversed_kernel``, so another call's bytes in a row or in its tail
    come back as a mismatch."""
    _, deployment = deploy(kernel=reversed_kernel)
    try:
        channel = deployment.channel()
        sizes = np.random.default_rng(34).integers(0, 3 * ROW + 1, (8, 100))
        sizes[:, ::10] = 3 * ROW

        def answered(c, i):
            request = payload(1000 * c + i, int(sizes[c, i])).replace(b"\x00", b"\x01")
            cntl = call(channel, request)
            want = b"".join(reversed_answer(r) for r in REFERENCE.slices(request, ROW, 3))
            return cntl.collective_fused and cntl.response_payload == want

        before = combo_vars()
        run_callers(8, 100, answered, 200)
        after = combo_vars()
        assert gained(before, after, "fused") == 800
        assert gained(before, after, "host_fanout") == 0
    finally:
        deployment.close()


@pytest.mark.parametrize("size", [3 * ROW, ROW + 7], ids=["full_width", "short_rows"])
@limited(120)
def test_a_fused_call_stages_its_operands_once(size, monkeypatch):
    """Between the ``pack`` stamp and the ``launch`` stamp of a fused call
    (PR 34): no ``np.stack``, no zero-filled row, no array assembled from
    single-device parts, and one ``jax.device_put`` at the most."""
    import jax

    from incubator_brpc_tpu.rpc import combo

    _, deployment = deploy()
    try:
        channel = deployment.channel()
        call(channel, payload(1, size))  # this size's rows have been through once
        noted, stamps = [], []

        def noting(module, name):
            fn = getattr(module, name)

            def noted_call(*args, **kwargs):
                noted.append((name, threading.get_ident(), time.monotonic_ns()))
                return fn(*args, **kwargs)

            monkeypatch.setattr(module, name, noted_call)

        for module, name in ((np, "stack"), (np, "zeros"), (jax, "device_put"),
                             (jax, "make_array_from_single_device_arrays")):
            noting(module, name)
        record = combo._FusedCall.record

        def recording(self):
            stamps.append((threading.get_ident(), list(self.stamps)))
            return record(self)

        monkeypatch.setattr(combo._FusedCall, "record", recording)
        request = payload(2, size)
        cntl = call(channel, request)
        assert cntl.collective_fused is True and cntl.response_payload == request
        ((thread, t),) = stamps
        # t[0] the call, then the stamp each stage starts at, then the end
        assert len(t) == 2 + len(STAGES)
        pack, launch = t[1 + STAGES.index("pack")], t[1 + STAGES.index("launch")]
        staged = [name for name, who, at in noted if who == thread and pack <= at <= launch]
        assert staged in ([], ["device_put"]), staged
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
        assert gained(before, after, "joined") == len(sizes)  # the default merger
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
        after = combo_vars()
        assert gained(before, after, "fused") == 2  # broken under the fused path
        # a control's merger is a subclass and always runs; flip_bit keeps the default
        assert gained(before, after, "joined") == (2 if control == "flip_bit" else 0)
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
