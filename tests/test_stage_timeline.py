"""The stage timelines of PR 35: rows that hold stamps on two clocks, the
sampler's arithmetic, the ring the rows stay in and the benchmark's reading
of it (``benchmark/timeline.py``).

- every feed's column table feeds the recorders what the parent's
  subtractions on the hot path fed them, from the same stamps;
- a stage that sleeps reads CPU far under wall, one that spins about wall;
- the ring wraps, keeps order and returns only rows that were fed;
- the hot path still writes one ``append`` a call, step, send, write and
  fused call;
- on a synthetic trace and ring the three idle shares add up to 100 and the
  stage open in a planted gap is named.
"""

import os
import sys
import threading
import time
import types
from collections import deque

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import timeline, xplane  # noqa: E402
from incubator_brpc_tpu import bvar  # noqa: E402
from incubator_brpc_tpu.bvar import LatencyRecorder, RecorderFeed, Ring, clocks  # noqa: E402
from test_stream_link_deployment import limited  # noqa: E402 — a test's own time limit


# -- the tables against the parent's arithmetic ------------------------------


def fresh(columns, stamps):
    """A feed of its own with the table's spans and new recorders."""
    return RecorderFeed(
        [(LatencyRecorder(), scale, span) for scale, span in columns], stamps=stamps)


def endpoint_table():
    from incubator_brpc_tpu.transport import device

    columns = [(1e-3, span) for span in device.STAGES.values()]
    rng = np.random.default_rng(35)
    rows, old = [], []
    for i in range(50):
        # stamps in the order written, each 1 to 5,000 ns after the last
        t = dict(zip(
            ("cut", "plane_callback") + device._WALL,
            (1_000_000 * (i + 1) + np.cumsum(rng.integers(1, 5000, 14))).tolist(),
        ))
        served = i % 3 != 0  # a direct call has no host plane around it
        native = served and i % 2
        t["sent"] = t["exit"] + int(rng.integers(1, 5000)) if served else -1
        if not served:
            t["cut"] = -1
        if not native:
            t["plane_callback"] = -1
        t["seq"] = i
        rows.append(tuple(t.get(stamp, 0) for stamp in device.STAMPS))
        # the parent's _PendingCall.stages() and _record, on the hot path
        old.append((
            (t["words"] - t["entry"]) + (t["enqueued"] - t["credit_held"])
            + (t["exit"] - t["woke"]),
            t["credit_held"] - t["words"], t["batched"] - t["enqueued"],
            t["stacked"] - t["batched"], t["launched"] - t["stacked"],
            t["cq_taken"] - t["launched"], t["ready"] - t["cq_taken"],
            t["readback"] - t["ready"], t["woke"] - t["readback"],
            t["entry"] - t["cut"] if served else None,
            t["plane_callback"] - t["cut"] if native else None,
            t["sent"] - t["exit"] if served else None,
        ))
    return fresh(columns, device.STAMPS), rows, old, [1e-3] * 12


def link_table():
    from incubator_brpc_tpu.transport import device_link

    wall = [c for c in device_link.STEP_COLUMNS if not c[0].endswith("_cpu")]
    rng = np.random.default_rng(36)
    rows, old, previous = [], [], 0
    for i in range(50):
        at = (2_000_000 * (i + 1) + np.cumsum(rng.integers(1, 9000, 7))).tolist()
        held, dispatch, launched, ready, begin, host, end = at
        if i % 4:
            held = dispatch  # a train that went at once
        seen = -1 if i % 5 == 0 else int(rng.integers(1, 20))
        rows.append((
            8 * i, previous or -1, held, dispatch, launched, ready, begin, host, end,
            1 + i % 8, seen, 0, 0, 0, 0, 0,
        ))
        # the parent's _take_seq_locked and _record_step
        interval = dispatch - previous if previous else 0
        old.append((
            end - dispatch, launched - dispatch, ready - launched, begin - ready,
            host - begin, end - host, interval or None, dispatch - held,
            1 + i % 8, None if seen < 0 else seen,
        ))
        previous = dispatch
    feed = fresh([(scale, span) for _a, scale, span in wall], device_link.STEP_STAMPS)
    return feed, rows, old, [scale for _a, scale, _s in wall]


def combo_table():
    from incubator_brpc_tpu.rpc import combo

    spans = [("call", "end")] + [combo._FUSED_SPAN[s] for s in combo.FUSED_STAGES]
    rng = np.random.default_rng(37)
    rows, old = [], []
    for i in range(50):
        t = (3_000_000 * (i + 1) + np.cumsum(rng.integers(1, 9000, 10))).tolist()
        rows.append((*t, *[0] * 8))
        end = t[9]
        t = t[:9]
        # the parent's _FusedCall.record
        old.append((end - t[0],) + tuple(b - a for a, b in zip(t[1:], t[2:])))
    return fresh([(1e-3, s) for s in spans], combo.FUSED_STAMPS), rows, old, [1e-3] * 8


def stream_table():
    rng = np.random.default_rng(38)
    rows, old = [], []
    for i in range(50):
        enter = 4_000_000 * (i + 1)
        parked = bool(i % 3)
        admitted = enter + int(rng.integers(1, 9000)) if parked else enter
        ahead = int(rng.integers(0, 1 << 21))
        rows.append((enter, admitted, ahead))
        old.append((admitted - enter if parked else 0, ahead))  # the parent's write()
    feed = fresh(
        [(1e-3, ("enter", "admitted")), (1, "ahead")], ("enter", "admitted", "ahead"))
    return feed, rows, old, [1e-3, 1]


@pytest.mark.parametrize(
    "table", [endpoint_table, link_table, combo_table, stream_table])
def test_a_column_table_feeds_what_the_hot_path_subtractions_fed(table):
    feed, rows, old, scales = table()
    feed.rows.extend(rows)
    feed.flush()
    assert len(feed.columns) == len(scales) == len(old[0])
    for (recorder, *_rest), scale, column in zip(feed.columns, scales, zip(*old)):
        # the loop the parent's flush ran over durations, kept as the reference
        column = [v for v in column if v is not None]
        assert recorder.count() == len(column)
        assert recorder.latency_sum() == pytest.approx(sum(column) * scale)
        assert recorder.max_latency() == pytest.approx(max(column) * scale)
        assert sorted(recorder._percentile.merged_samples()) == pytest.approx(
            sorted(v * scale for v in column[::16]))


def test_the_programs_own_feeds_are_built_from_those_tables():
    from incubator_brpc_tpu.rpc import combo, stream
    from incubator_brpc_tpu.transport import device

    feed = device._stage_feed
    # PR 37: the state's stamp follows PR 35's 21, its recorder their columns
    assert feed.stamps == device.STAMPS and len(feed.stamps) == 22
    assert feed.stamps[-1] == "state"
    names = [r._exposed_name for r, *_rest in feed.columns]
    assert names == (
        [f"device_transport_{s}_us" for s in device.STAGES]
        + [f"device_transport_{s}_cpu_us" for s in device.CPU_STAGES]
        + ["device_transport_state_wait_us"])
    assert len(combo.COMBO_VARS.calls.stamps) == 18
    for namespace in (stream.HOST_VARS, stream.LINK_VARS):
        for one in (namespace.writes, namespace.feedbacks, namespace.delivers,
                    namespace.consumes):
            assert one.ring is not None and bvar.feeds()[one.name] is one
    assert bvar.feeds()["device_transport"] is feed
    assert bvar.feeds()["device_link_combo_calls"] is combo.COMBO_VARS.calls


def test_the_process_cpu_clock_is_read_only_when_asked():
    import incubator_brpc_tpu.runtime.device_butex  # noqa: F401 — exposes it
    from benchmark import spans

    before = spans.counters()["device_transport_process_cpu_us"]
    spin_until = time.process_time() + 0.05
    while time.process_time() < spin_until:
        pass
    gained = spans.counters()["device_transport_process_cpu_us"] - before
    assert 0.04e6 <= gained < 60e6  # us, a plain number: delta() subtracts it


# -- the second clock ----------------------------------------------------------


def two_clock_feed():
    wall, cpu = LatencyRecorder(), LatencyRecorder()
    feed = RecorderFeed(
        ((wall, 1e-3, ("begin", "end")), (cpu, 1e-3, ("begin_cpu", "end_cpu"))),
        stamps=("begin", "end", "begin_cpu", "end_cpu"),
    )
    return feed, wall, cpu


def staged(work) -> tuple:
    """``work`` as one stage of this thread, stamped on both clocks."""
    feed, wall, cpu = two_clock_feed()
    begin, begin_cpu = clocks()
    work()
    end, end_cpu = clocks()
    feed.rows.append((begin, end, begin_cpu, end_cpu))
    feed.flush()
    return wall.latency_sum(), cpu.latency_sum()


def test_a_stage_that_sleeps_reads_cpu_far_under_wall():
    wall, cpu = staged(lambda: time.sleep(0.1))
    assert wall >= 100_000 and cpu < 0.2 * wall, (wall, cpu)


def test_a_stage_that_spins_reads_cpu_about_wall():
    def spin():
        until = time.thread_time() + 0.1
        while time.thread_time() < until:
            pass

    wall, cpu = staged(spin)
    # the thread was on a processor for all of its 100 ms: wall is longer
    # only by what the machine's other work took from it
    assert 100_000 <= cpu <= 1.02 * wall, (wall, cpu)


def test_a_thread_waiting_for_the_interpreter_reads_as_off_the_processor():
    """The case the clock is for: a stage of pure Python while another
    thread holds the interpreter most of the time."""
    stop = threading.Event()

    def hog():
        while not stop.is_set():
            sum(range(20000))

    def work():
        for _ in range(200):
            sum(range(20000))

    alone_wall, alone_cpu = staged(work)
    hogs = [threading.Thread(target=hog, daemon=True) for _ in range(3)]
    for t in hogs:
        t.start()
    try:
        wall, cpu = staged(work)
    finally:
        stop.set()
        for t in hogs:
            t.join(10)
            assert not t.is_alive()
    assert cpu <= 1.02 * wall
    # the work is the same; what four threads add is waiting
    assert wall - cpu > 2 * (alone_wall - alone_cpu), (wall, cpu, alone_wall, alone_cpu)
    assert wall > 1.5 * cpu, (wall, cpu)


@limited(120)
def test_one_dispatch_in_four_carries_both_clocks_and_cpu_stays_under_wall():
    from incubator_brpc_tpu.bvar import CPU_CLOCK_EVERY
    from incubator_brpc_tpu.transport import device
    from incubator_brpc_tpu.transport.device import DeviceEndpoint

    assert CPU_CLOCK_EVERY == 4
    ep = DeviceEndpoint(window_size=4, max_batch=4)
    ep.warm(64)
    cpu_stages = {"stack_cpu", "launch_cpu", "readback_cpu"}
    for seq in range(1, 5):  # one call a dispatch: dispatches 1 to 4
        pending = ep.call_words(np.arange(16, dtype=np.uint32), timeout=30)
        assert pending.wait(30) and pending.completed() and pending.dispatch.seq == seq
        stages = pending.stages()
        assert set(stages) >= {"stack", "launch", "readback"}
        assert "copy" not in stages  # a raw call_words has no exit
        if seq % 4:
            assert not cpu_stages & set(stages) and not pending.dispatch.timed
            continue
        assert cpu_stages <= set(stages) and pending.dispatch.timed
        for stage in ("stack", "launch", "readback"):
            assert 0 <= stages[stage + "_cpu"] <= stages[stage] * 1.02 + 20_000, stage
    device.flush_stage_recorders()
    before = {s: device._recorders[s].count() for s in device._recorders}
    for _ in range(4):  # dispatches 5 to 8
        code, out = ep.call_bytes(b"both clocks " * 8, timeout=30)
        assert code == 0 and out == b"both clocks " * 8
    device.flush_stage_recorders()
    gained = {s: device._recorders[s].count() - n for s, n in before.items()}
    # direct calls: every stage of the call once a call, the CPU twin of each
    # stage of its dispatch once in four; the host plane around it never
    for stage in ("copy", "credit_wait", "queue_wait", "stack", "launch", "cq_wait",
                  "ready", "readback", "wake"):
        assert gained[stage] == 4, stage
    for stage in cpu_stages:
        assert gained[stage] == 1, stage
    for stage in ("ingress", "plane_callback", "egress"):
        assert gained[stage] == 0, stage
    names, rows = device._stage_feed.timeline()
    last = dict(zip(names, rows[-1].tolist()))
    assert last["seq"] == 8 and last["sent"] == last["cut"] == -1
    assert min(last[n] for n in names if n.endswith("_cpu")) >= 0  # timed: all five
    before_it = dict(zip(names, rows[-2].tolist()))
    assert {before_it[n] for n in names if n.endswith("_cpu")} == {-1}  # or none
    # the stage definitions did not drift: the nine stages are the call
    nine = device._stage_feed.read(rows[-1].tolist())[:9]
    assert list(device.STAGES)[:9] == [
        "copy", "credit_wait", "queue_wait", "stack", "launch", "cq_wait", "ready",
        "readback", "wake"]
    assert sum(nine) == last["exit"] - last["entry"] > 0


def test_the_callers_own_stamps_carry_no_cpu_clock():
    """A ``thread_time_ns`` read is a system call of 5.8 us on the chip's
    host: seven a call cost 6.8% of the calls/s at 256 B (PERF.md, PR 35),
    so only the dispatch's threads read it, on one dispatch in four."""
    from incubator_brpc_tpu.transport import device

    assert device.CPU_STAGES == ("stack", "launch", "readback")
    assert [s for s in device.STAMPS if s.endswith("_cpu")] == [
        "batched_cpu", "stacked_cpu", "launched_cpu", "ready_cpu", "readback_cpu"]


# -- the ring ------------------------------------------------------------------


def test_the_ring_wraps_keeps_order_and_returns_only_fed_rows():
    ring = Ring(("a", "b"), 8)
    names, rows = ring.read()
    assert names == ("a", "b") and rows.shape == (0, 2)
    table = np.arange(40, dtype=np.int64).reshape(20, 2)
    ring.extend(table[:3])
    assert ring.read()[1].tolist() == table[:3].tolist()
    ring.extend(table[3:7])
    assert ring.read()[1].tolist() == table[:7].tolist()
    ring.extend(table[7:11])  # wraps: the oldest three go
    assert ring.read()[1].tolist() == table[3:11].tolist()
    ring.extend(table[11:12])
    assert ring.read()[1].tolist() == table[4:12].tolist()
    whole = Ring(("a", "b"), 8)
    whole.extend(table)  # more than it holds, at once
    assert whole.read()[1].tolist() == table[12:].tolist()
    whole.extend(table[:2])
    assert whole.read()[1].tolist() == table[14:].tolist() + table[:2].tolist()
    copy = whole.read()[1]
    copy[:] = -5  # a reader's copy is its own
    assert whole.read()[1].min() >= 0


def test_a_feed_keeps_the_rows_it_has_fed_and_no_others():
    rec = LatencyRecorder()
    feed = RecorderFeed(
        ((rec, 1, ("b", "e")),), stamps=("seq", "b", "e"), name="test_kept_rows",
        ring_rows=16, worker=(("b", "e"),))
    assert bvar.feeds()["test_kept_rows"] is feed
    for i in range(10):
        feed.rows.append((i, 100 + i, 100 + 3 * i))
    names, rows = feed.timeline()  # flushes what waits first
    assert names == ("seq", "b", "e") and rows[:, 0].tolist() == list(range(10))
    assert rec.count() == 10 and rec.latency_sum() == sum(2 * i for i in range(10))
    for i in range(10, 30):
        feed.rows.append((i, 100 + i, 100 + 3 * i))
    assert feed.timeline()[1][:, 0].tolist() == list(range(14, 30))
    assert rec.count() == 30
    assert RecorderFeed(((rec, 1),)).timeline() is None  # keeps no rows
    del feed
    assert "test_kept_rows" not in bvar.feeds()  # held weakly, like a Window


def test_the_sampler_keeps_its_own_passes():
    rec = LatencyRecorder()
    feed = RecorderFeed(((rec, 1),))
    feed.rows.append((1,))
    deadline = time.monotonic() + 5
    while rec.count() == 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    time.sleep(0.05)  # the pass that fed it ends after the feed
    names, rows = bvar.sampler_passes()
    assert names == ("begin", "end", "begin_cpu", "end_cpu") and len(rows) >= 1
    last = dict(zip(names, rows[-1].tolist()))
    assert 0 < last["begin"] <= last["end"] <= time.monotonic_ns()
    # the pass's CPU time is its thread's, so no more than its wall time:
    # but a thread clock may tick in 10 ms (the chip's host's does, PERF.md
    # section 5, and a tick can fall inside a pass of microseconds), and the
    # two clocks of a stamp are read one after the other
    wall = last["end"] - last["begin"]
    assert 0 <= last["end_cpu"] - last["begin_cpu"] <= wall + 10_000_000 + 1_000_000


def test_concurrent_flushes_feed_and_keep_each_row_once():
    rec = LatencyRecorder()
    feed = RecorderFeed(((rec, 1),), name="test_flush_race", ring_rows=1 << 15)
    stop = threading.Event()

    def flusher():
        while not stop.is_set():
            feed.flush()

    def writer(w):
        for i in range(2000):
            feed.rows.append((w * 2000 + i,))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        flushers = [threading.Thread(target=flusher) for _ in range(2)]
        writers = [threading.Thread(target=writer, args=(w,)) for w in range(12)]
        for t in flushers + writers:
            t.start()
        for t in writers:
            t.join(60)
            assert not t.is_alive()
        stop.set()
        for t in flushers:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    kept = feed.timeline()[1][:, 0]
    assert rec.count() == 24000 and sorted(kept.tolist()) == list(range(24000))
    for w in range(12):  # each writer's rows in its own order
        mine = kept[(kept >= w * 2000) & (kept < (w + 1) * 2000)]
        assert mine.tolist() == sorted(mine.tolist())


# -- one append on the hot path --------------------------------------------------


class Counted(deque):
    """A feed's ``rows`` that counts what the hot path does to it."""

    def __init__(self):
        super().__init__(maxlen=1 << 16)
        self.appends = self.extends = self.extended = 0

    def append(self, row):
        self.appends += 1
        assert all(isinstance(v, int) for v in row), row  # the fast way round
        super().append(row)

    def extend(self, rows):
        rows = list(rows)
        self.extends += 1
        self.extended += len(rows)
        super().extend(rows)


@limited(120)
def test_an_endpoint_call_appends_once(monkeypatch):
    from incubator_brpc_tpu.transport import device
    from incubator_brpc_tpu.transport.device import DeviceEndpoint

    ep = DeviceEndpoint(window_size=4, max_batch=4)
    ep.warm(64)
    rows = Counted()
    monkeypatch.setattr(device._stage_feed, "rows", rows)
    for i in range(12):
        assert ep.call_bytes(b"%04d" % i * 8, timeout=30)[0] == 0
    assert (rows.appends, rows.extends) == (12, 0)


@limited(180)
def test_a_step_a_send_and_a_stream_write_append_once_each(monkeypatch):
    from benchmark import generator, manifest
    from incubator_brpc_tpu.rpc import stream
    from incubator_brpc_tpu.transport import device_link
    from test_stream_link_deployment import TRAFFIC, deploy, payload

    module, deployment = deploy()
    try:
        send = generator.channel_caller(
            deployment.channel(), TRAFFIC,
            manifest.load_module("references", "stream_sink.py"))
        assert send(payload(34))[1] == generator.OK  # the link is up and warm
        link = deployment.link
        counted = {
            "steps": (link._step_feed, Counted()), "sends": (link._send_feed, Counted()),
            "writes": (stream.LINK_VARS.writes, Counted()),
            "consumes": (stream.LINK_VARS.consumes, Counted()),
            "delivers": (stream.LINK_VARS.delivers, Counted()),
        }
        time.sleep(0.3)  # the first transfer's last feedback and receipt
        for feed, rows in counted.values():
            feed.flush()
            monkeypatch.setattr(feed, "rows", rows)
        before = (link._m_rtt.count(), link._m_send_wait.count())
        assert send(payload(35))[1] == generator.OK
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and counted["writes"][1].appends < 17:
            time.sleep(0.01)
        time.sleep(0.3)
        rows = {what: r for what, (_f, r) in counted.items()}
        assert rows["writes"].appends == 17  # sixteen messages and the receipt
        assert rows["consumes"].appends == rows["delivers"].extends  # one a batch
        assert rows["delivers"].extended == 17  # a row a message
        for feed, _rows in counted.values():
            feed.flush()
        # one row a delivered step, one a send
        assert rows["steps"].appends == link._m_rtt.count() - before[0] > 0
        assert rows["sends"].appends == link._m_send_wait.count() - before[1] >= 17
        assert rows["steps"].extends == rows["sends"].extends == 0
        names, steps = link._step_feed.timeline()
        step = dict(zip(names, steps[-1].tolist()))
        assert step["dispatch"] <= step["launched"] <= step["ready"] <= step["deliver"]
        assert step["deliver"] <= step["host"] <= step["delivered"]
        assert step["held_since"] <= step["dispatch"] and step["seq"] >= 0
        # one step in four carries the CPU clock, on all five stamps or none
        cpu = steps[:, [names.index(n) for n in names if n.endswith("_cpu")]]
        timed = (cpu >= 0).all(axis=1)
        assert ((cpu >= 0).any(axis=1) == timed).all()
        assert timed.sum() == -(-len(steps) // bvar.CPU_CLOCK_EVERY)
        step = dict(zip(names, steps[timed][-1].tolist()))
        assert 0 <= step["launched_cpu"] - step["dispatch_cpu"] <= (
            step["launched"] - step["dispatch"]) * 1.02 + 20_000
        assert link._m_launch_cpu.count() == link._m_pump_cpu.count() == timed.sum()
    finally:
        deployment.close()


@limited(180)
def test_a_fused_call_appends_once_and_its_cpu_stays_under_wall(monkeypatch):
    from incubator_brpc_tpu.rpc.combo import COMBO_VARS, FUSED_CPU_STAGES, FUSED_STAMPS
    from test_partition_combo import ROW, call, deploy, payload

    _module, deployment = deploy()
    try:
        channel = deployment.channel()
        call(channel, payload(1, 3 * ROW))
        COMBO_VARS.calls.flush()
        rows = Counted()
        monkeypatch.setattr(COMBO_VARS.calls, "rows", rows)
        for i in range(6):
            call(channel, payload(2 + i, 3 * ROW))
        assert (rows.appends, rows.extends) == (6, 0)
        stamps, kept = COMBO_VARS.calls.timeline()
        assert stamps == FUSED_STAMPS and len(kept) >= 7
        # one call in four carries the caller's CPU clock, on all its stamps
        timed = kept[kept[:, stamps.index("resolve_cpu")] >= 0]
        assert len(timed) >= 1 and (timed[:, stamps.index("resolve_cpu"):] >= 0).all()
        names = [r._exposed_name for r, *_rest in COMBO_VARS.calls.columns]
        values = dict(zip(names, COMBO_VARS.calls.read(timed[-1].tolist())))
        for stage in FUSED_CPU_STAGES:
            wall = values[f"device_link_combo_{stage}_us"]
            cpu = values[f"device_link_combo_{stage}_cpu_us"]
            assert 0 <= cpu <= wall * 1.02 + 20_000, (stage, wall, cpu)
        untimed = kept[kept[:, stamps.index("resolve_cpu")] < 0]
        if len(untimed):  # the wall stages all the same, the CPU twins nothing
            values = dict(zip(names, COMBO_VARS.calls.read(untimed[-1].tolist())))
            assert values["device_link_combo_pack_us"] > 0
            assert values["device_link_combo_pack_cpu_us"] is None
    finally:
        deployment.close()


# -- benchmark/timeline.py on a synthetic trace and ring ---------------------------

# far from every stamp a real clock gives in this process
T0 = 2_000_000_000_000_000_000
MS = 1_000_000


def planted_run():
    """A window of 100 ms on two chips. The busier one works for 1 ms every
    10 ms (ten operations), so it is idle in ten gaps of 9 ms, 90 ms in all."""
    start = T0 + np.arange(10, dtype=np.int64) * 10 * MS
    busy = xplane.Events(["fusion"] * 10, start + 9 * MS, start + 10 * MS)
    quiet = xplane.Events(["fusion"], [T0 + 5 * MS], [T0 + 6 * MS])
    return types.SimpleNamespace(
        t_open=T0, t_close=T0 + 100 * MS,
        devices={"/device:TPU:0": {"ops": quiet}, "/device:TPU:1": {"ops": busy}},
    )


def planted_feeds():
    """Worker spans cover the first 3 ms of every gap, call spans the first
    6 ms; one row lies outside the window and one was never stamped."""
    work = RecorderFeed(
        ((LatencyRecorder(), 1e-3, ("stacked", "launched")),),
        stamps=("seq", "stacked", "launched"), name="test_planted_worker",
        ring_rows=64, worker=(("stacked", "launched"),))
    calls = RecorderFeed(
        ((LatencyRecorder(), 1e-3, ("entry", "exit")),),
        stamps=("entry", "exit"), name="test_planted_calls", ring_rows=64,
        call=(("entry", "exit"),))
    for i in range(10):
        gap = T0 + i * 10 * MS
        work.rows.append((i, gap, gap + 3 * MS))
        calls.rows.append((gap, gap + 6 * MS))
    work.rows.append((98, T0 - 50 * MS, T0 - 40 * MS))
    work.rows.append((99, -1, T0 + 7 * MS))
    # the one long silence: the busier chip's last gap is covered whole
    calls.rows.append((T0 + 90 * MS, T0 + 99 * MS))
    return work, calls


def test_idle_shares_add_up_and_a_planted_gap_names_its_stage(capsys):
    feeds = planted_feeds()  # held: the registry is weak
    run = planted_run()
    shares = timeline.idle_shares(run)
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    # of 90 ms idle: 30 under a worker span, 27 + 3 more under a call span only
    assert shares["worker_open"] == pytest.approx(100 * 30 / 90)
    assert shares["waiting_only"] == pytest.approx(100 * 33 / 90)
    assert shares["outside"] == pytest.approx(100 * 27 / 90)
    printed = [
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("idle gap")]
    assert len(printed) == timeline.LONGEST
    for line in printed:
        assert line.startswith("idle gap 0.009000 s at +0.0")
        assert "test_planted_worker:stacked->launched 0.003000 s" in line
        assert "no sampler pass inside" in line
    assert any("test_planted_calls:entry->exit 0.009000 s" in line for line in printed)
    assert timeline.idle_shares(run) is shares  # computed once a run
    assert capsys.readouterr().out == ""
    del feeds


def test_a_gap_names_the_sampler_pass_inside_it():
    gaps = (np.array([T0], np.int64), np.array([T0 + 40 * MS], np.int64))
    worker = [("w:a->b", np.array([T0 + MS]), np.array([T0 + 2 * MS]))]
    passes = (np.array([T0 - 5 * MS, T0 + 10 * MS]), np.array([T0 - MS, T0 + 12 * MS]))
    (line,) = timeline.describe_gaps(gaps, worker, [], passes, T0)
    assert line == (
        "idle gap 0.040000 s at +0.000 s: open w:a->b 0.001000 s; "
        "sampler pass 0.002000 s at +0.010")
    assert timeline.classify(gaps, worker, []) == {
        "worker_open": MS, "waiting_only": 0, "outside": 39 * MS}
    empty = timeline.describe_gaps(gaps, [], [], (passes[0][:0], passes[1][:0]), T0)
    assert empty == [
        "idle gap 0.040000 s at +0.000 s: open no span of the program; "
        "no sampler pass inside"]


def test_nothing_to_read_is_none(monkeypatch):
    run = planted_run()
    assert timeline.idle_shares(run) is None  # no feed has a row in this window
    run = planted_run()
    run.devices = {}  # a rehearsal on the CPU: no device plane
    assert timeline.idle_shares(run) is None
    feeds = planted_feeds()
    monkeypatch.delattr(bvar, "feeds")  # a program from before PR 35
    assert timeline.spans(T0, T0 + 100 * MS) is None
    assert timeline.idle_shares(planted_run()) is None
    del feeds
