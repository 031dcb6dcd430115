"""The reduction from a trace to device numbers: interval arithmetic on
made-up intervals, then the whole reduction on the recorded trace in
``data/`` (a 0.4 s traced window of ``echo_4m_c2`` on a TPU v5 lite, from
a chip run of PR 24; the numbers asserted are that file's own)."""

import os

import numpy as np
import pytest

from benchmark import roofline, xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data", "echo_4m_c2.xplane.pb")


def test_union_and_covered():
    u = xplane.union([10, 0, 12, 30], [20, 5, 25, 40])
    assert u[0].tolist() == [0, 10, 30] and u[1].tolist() == [5, 25, 40]
    got = xplane.covered(*u, [0, 3, 24, 26, -5], [50, 12, 31, 29, 0])
    assert got.tolist() == [30, 4, 2, 0, 0]
    assert xplane.covered(*xplane.union([], []), [0], [9]).tolist() == [0]


def test_busy_and_gaps_inside_a_window():
    ops = xplane.Events(["a", "b", "a"], [5, 8, 40], [10, 20, 60])
    busy_s, (g0, g1) = xplane.busy(ops, 0, 50)
    assert busy_s == pytest.approx(25e-9)
    assert list(zip(g0.tolist(), g1.tolist())) == [(0, 5), (20, 40)]
    assert ops.clip(0, 50).seconds_by_name() == pytest.approx(
        {"a": 15e-9, "b": 12e-9})


def test_gaps_are_named_by_what_the_host_was_doing():
    gaps = (np.array([0, 100, 300]), np.array([50, 200, 1000]))
    handler = (np.array([120]), np.array([180]))
    client = (np.array([110, 300]), np.array([190, 400]))
    out = dict(map(tuple, xplane.label_gaps(gaps, handler, client, top=0)))
    assert out == pytest.approx({
        "total:" + xplane.HANDLER: 60e-9,
        "total:" + xplane.CLIENT_WAIT: (20 + 100) * 1e-9,
        "total:" + xplane.NO_CALL: (50 + 20 + 600) * 1e-9,
    })
    longest = xplane.label_gaps(gaps, handler, client, top=2)[3:]
    assert longest == [["one_gap:" + xplane.NO_CALL, pytest.approx(700e-9)],
                       ["one_gap:" + xplane.HANDLER, pytest.approx(100e-9)]]


def test_names_are_made_safe():
    assert xplane.safe_name("%fusion.1 = u32[4,8]{1,0}") == "fusion.1_u32_4_8_1_0"


def test_the_least_bytes_of_the_echo_step():
    assert roofline.bucket_words(4 << 20) == 1 << 20
    assert roofline.bucket_words(1) == 64 and roofline.bucket_words(257) == 128
    assert roofline.echo_step_bytes(1 << 20) == 4 * ((2 << 20) + 8)
    assert roofline.echo_step_bytes(64, rows=2) == 2 * 4 * 136


def test_hlo_lines_become_short_names():
    line = ("%fusion = (u32[]{:T(128)}, u32[1048576]{0:T(1024)S(1)}) "
            "fusion(u32[1048576]{0:T(1024)} %padded.1), kind=kLoop")
    assert xplane.safe_name(line) == "fusion_u32_u32_1048576"


def test_steps_are_the_program_executions_with_operations():
    modules = xplane.Events(["stage", "step", "stage", "step"],
                            [0, 10, 50, 60], [2, 40, 52, 90])
    ops = xplane.Events(["a", "b", "a"], [12, 30, 61], [20, 38, 80])
    steps = xplane.with_ops(modules, ops)
    assert steps.names == ["step", "step"] and steps.start.tolist() == [10, 60]
    assert len(xplane.with_ops(modules, xplane.Events([], [], []))) == 0


def test_the_recorded_trace_reduces_to_its_own_numbers():
    trace = xplane.read_trace(RECORDED)
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.sync_ns == 46967827
    d = trace.devices["/device:TPU:0"]
    assert (len(d["ops"]), len(d["modules"]), len(d["steps"])) == (56, 84, 28)
    assert {n.split("(")[0] for n in d["steps"].names} == {"jit__lambda"}
    lo, hi = int(d["ops"].start.min()), int(d["ops"].end.max())
    busy_s, gaps = xplane.busy(d["ops"], lo, hi)
    assert busy_s == pytest.approx(760845e-9) and len(gaps[0]) == 55
    step_ns = int((d["steps"].end - d["steps"].start).sum())
    assert step_ns == 791045  # 28.25 us a step
    share = (28 * roofline.echo_step_bytes(1 << 20) / 819e9) / (step_ns / 1e9)
    assert share == pytest.approx(0.3625, abs=1e-3)
    assert [n for n, _ in xplane.top_ops([d["ops"]])] == [
        "concatenate.1_u32_1048584", "fusion_u32_u32_1048576"]
    moved = trace.shifted(1000)
    assert moved.sync_ns == trace.sync_ns + 1000
    assert moved.devices["/device:TPU:0"]["ops"].start[0] == d["ops"].start[0] + 1000
