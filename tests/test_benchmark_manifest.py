"""BENCHMARK.json against the files it names, inside tier-1: every cell
resolves its configuration, traffic, deployment, reference and per-layer
readers, and every reader of the program's own recorders (PR 25) gives the
expected number from a hand-made run and ``None`` from a program that
lacks the recorder. The benchmark's own tests are
``python -m pytest benchmark/tests -q``; tier-1 does not collect those."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    manifest, roofline, roofline_lane, roofline_table, xplane,
)

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
KV_CELL = "kv_blocks_ici_32m_c1"  # PR 39's: device blocks over the lane
HBM_CELL = "link_echo_ici_hbm_1m_c4"  # PR 44's: a unary call carries a tensor
EXPERT_CELL = "expert_ffn_ep32_n256_c16"  # PR 48's: a step bound on the device
# PR 54's: the four-chip expert step, a tensor operand into DeviceEndpoint
EXCHANGE_CELL = "expert_exchange_ep32_n8192_c4"
# the three readers of the lane that go by adders and the trace, not by the
# link with most trains: PR 44's cell, whose windows need no train, joined them
LANE_BY_ADDERS = ("lane_messages_per_step", "lane_step_ici_pct", "lane_tagged_pct")
ECHO_CELLS = ["echo_256b_c16", "echo_4m_c2", "echo_mixed_c16"]
DEVICE_STAGES = (
    "copy", "credit_wait", "queue_wait", "stack", "launch",
    "cq_wait", "ready", "readback", "wake",
)
T_OPEN, T_CLOSE = 1_000_000_000, 21_000_000_000


def recorder(count, mean_us):
    return {"count": count, "sum": count * mean_us}


# the one configuration that has a stream and link geometry to read
STREAM_CONFIG = {
    "stream": {"max_buf_size": 2097152},
    "channel_options": {"link_slot_words": 16384},
}


def hand_made_run(counters: dict):
    """What ``run.py`` hands a reader, by hand: 100 handler spans of 1 ms,
    one device with 50 step executions of 2 us inside the window and no
    operation of its own."""
    t_in = T_OPEN + np.arange(100, dtype=np.int64) * 10_000_000
    start = T_OPEN + np.arange(50, dtype=np.int64) * 1_000_000
    steps = xplane.Events(["jit_step"] * 50, start, start + 2_000)
    return types.SimpleNamespace(
        counters=counters,
        handler=np.stack([t_in, t_in + 1_000_000], axis=1),
        devices={"/device:TPU:0": {
            "steps": steps, "ops": xplane.Events([], [], [])}},
        t_open=T_OPEN, t_close=T_CLOSE, window_s=20.0,
        peaks={"hbm_bytes_per_s": 819e9, "ici_bits_per_s_per_chip": 1600e9},
        traffic={"sizes": [256]}, done=np.zeros((100, 6)),
        cell=types.SimpleNamespace(config=STREAM_CONFIG),
    )


# the program's counters over a window, as spans.delta would give them:
# nine stages of 100 us each, so 900 of a 1,000 us handler are covered
DEVICE = {f"device_transport_{s}_us": recorder(100, 100.0) for s in DEVICE_STAGES}
DEVICE.update({
    "device_transport_ingress_us": recorder(100, 2500.0),
    "device_transport_egress_us": recorder(100, 400.0),
    "device_transport_plane_callback_us": recorder(100, 150.0),
    "device_transport_dispatches": 40,
    "device_transport_dispatch_rows": 100,
    "device_transport_dispatch_pad_rows": 128,
    "device_transport_dispatch_words": 128 * 64,
    "device_transport_dispatch_widened_rows": 60,
    # PR 53: 28 pad rows whole and a quarter of each call's row; 8 of the 40
    # dispatches were a call alone that filled its row
    "device_transport_dispatch_zeroed_words": 28 * 64 + 100 * 16,
    "device_transport_dispatch_borrowed": 8,
})
# PR 35: the CPU clock of the stages one thread begins and ends
DEVICE_CPU = {"stack": 60.0, "launch": 45.0, "readback": 30.0}
DEVICE.update({
    f"device_transport_{s}_cpu_us": recorder(100, us) for s, us in DEVICE_CPU.items()
})
# two links: 3 is the busiest; 2 must not be read
LINK = {
    "device_link_2_step_rtt_us": recorder(5, 9e9),
    "device_link_2_launch_us": recorder(5, 9e9),
    "device_link_3_step_rtt_us": recorder(680, 4100.0),
    "device_link_3_flush_us": recorder(1360, 40.0),
    "device_link_3_launch_us": recorder(680, 1200.0),
    "device_link_3_ready_us": recorder(680, 1500.0),
    "device_link_3_reorder_wait_us": recorder(680, 100.0),
    "device_link_3_readback_us": recorder(680, 800.0),
    "device_link_3_pump_us": recorder(680, 500.0),
    "device_link_3_dispatch_interval_us": recorder(640, 2000.0),
    "device_link_3_inflight_at_dispatch": recorder(680, 2.5),
    "device_link_2_send_wait_us": recorder(5, 9e9),
    "device_link_3_send_wait_us": recorder(900, 350.0),
    "device_link_3_backlog_slots_at_dispatch": recorder(680, 10.5),
    "device_link_2_hold_us": recorder(5, 9e9),
    "device_link_3_hold_us": recorder(680, 750.0),
    "device_link_2_launch_cpu_us": recorder(5, 9e9),
    "device_link_3_launch_cpu_us": recorder(680, 700.0),
    "device_link_3_readback_cpu_us": recorder(680, 600.0),
    "device_link_3_pump_cpu_us": recorder(680, 450.0),
    "device_link_held_steps": 170,
    "device_link_prefetched_steps": 680,
    "device_link_staged_steps": 680,
    "device_link_bytes": 40 * (1 << 20),
    "device_link_capacity_bytes": 2 * 680 * 65536,
    "device_link_steps": 680,
    "device_link_slots": 4 * 680,
}
# a stream's counters over a window: 640 one-message batches
STREAM = {
    "device_link_stream_write_wait_us": recorder(640, 7000.0),
    "device_link_stream_unconsumed_at_write": recorder(640, 1048576.0),
    "device_link_stream_feedback_lag_us": recorder(640, 21000.0),
    "device_link_stream_deliver_us": recorder(640, 450.0),
    "device_link_stream_messages": 640,
    "device_link_stream_batches": 320,
    # a host stream's numbers must not be read for the link's
    "stream_write_wait_us": recorder(7, 9e9),
    "stream_messages": 9_000_000,
}
# a fused combo channel's counters over a window (PR 33): 200 calls of
# 1,000 us whose seven stages cover 900, three rows of 1 MiB each
COMBO_STAGES = {
    "resolve": 50.0, "pack": 150.0, "put": 200.0, "launch_wait": 25.0,
    "launch": 125.0, "gather": 250.0, "merge": 100.0,
}
COMBO = {
    "device_link_combo_call_us": recorder(200, 1000.0),
    **{f"device_link_combo_{s}_us": recorder(200, us)
       for s, us in COMBO_STAGES.items()},
    **{f"device_link_combo_{s}_cpu_us": recorder(200, COMBO_STAGES[s] / 4)
       for s in ("pack", "put", "launch", "gather", "merge")},
    "device_link_combo_fused": 200,
    "device_link_combo_joined": 150,  # PR 47: 50 calls ran a user's merger
    "device_link_combo_host_fanout": 40,
    "device_link_combo_mc_lowered": 10,
    "device_link_combo_rows": 600,
    "device_link_combo_bytes": 600 << 20,
}
# PR 39's lane over a window, on the busiest link: 640 paired programs
# of one 2 MiB block each, and the stream that handed them over beside
# 40 bytes messages of 64 B
LANE_STAGES = {
    "launch": 300.0, "ready": 450.0, "pair_wait": 1200.0, "deliver": 50.0,
}
LANE = {
    **LINK,
    "device_link_2_lane_step_us": recorder(5, 9e9),
    "device_link_3_lane_step_us": recorder(640, 2000.0),
    **{f"device_link_3_lane_{s}_us": recorder(640, us)
       for s, us in LANE_STAGES.items()},
    "device_link_3_lane_launch_cpu_us": recorder(160, 250.0),
    "device_link_lane_steps": 640,
    "device_link_lane_messages": 640,
    "device_link_lane_bytes": 640 * 2097152,
    "device_link_lane_tagged_steps": 640,  # PR 40: each with its message's tag
    "device_link_stream_device_messages": 640,
    "device_link_stream_device_bytes": 640 * 2097152,
    "device_link_stream_bytes": 40 * 64,
    "device_transport_kv_pages_written": 640,
}
HBM_DISPATCHED = (
    100.0 * 4 * (2 * 128 * 64 + roofline.FRAME_HEADER_WORDS * 128)
    / 819e9 / (50 * 2_000 / 1e9)
)
# PR 42's hand-over over a window: 40 submits of 12.5 us, ten of which found
# every watcher inside a job
CQ = {
    "device_transport_cq_submit_us": recorder(40, 12.5),
    "device_transport_cq_backlog": recorder(40, 0.25),
}
# the cells whose dispatches call ``watch``: all but the star, whose fused
# call reads its answer on the caller's own thread
CQ_CELLS = [
    "echo_256b_c16", "echo_4m_c2", "link_echo_ici_1m", "echo_mixed_c16",
    "echo_256b_c16_native", "link_stream_ici", "ycsb_b_zipf_c16", KV_CELL,
    HBM_CELL, EXPERT_CELL, EXCHANGE_CELL,
]
# PR 44's unary calls over a window, on the busiest link by such calls (3;
# no train crossed, so no step_rtt names it): 400 calls of 10,000 us whose
# four stages cover 5,000, the lane's two flights 3,000 (800 programs, 900 +
# 600 us from launch to hand-over) and the hand-made handler 1,000; 100
# attachments went as host bytes
UNARY_STAGES = {
    "request_tx": 2200.0, "server_dispatch": 300.0, "reply_tx": 2100.0,
    "client_wake": 400.0,
}
UNARY_LANE_STAGES = {
    "launch": 1900.0, "ready": 900.0, "pair_wait": 600.0, "deliver": 80.0,
}
UNARY = {
    "device_link_2_unary_call_us": recorder(5, 9e9),
    "device_link_2_unary_request_tx_us": recorder(5, 9e9),
    "device_link_2_lane_ready_us": recorder(5, 9e9),
    "device_link_3_unary_call_us": recorder(400, 10000.0),
    **{f"device_link_3_unary_{s}_us": recorder(400, us)
       for s, us in UNARY_STAGES.items()},
    **{f"device_link_3_lane_{s}_us": recorder(800, us)
       for s, us in UNARY_LANE_STAGES.items()},
    "device_link_unary_lane_requests": 400,
    "device_link_unary_lane_replies": 400,
    "device_link_unary_lane_bytes": 800 << 20,
    "device_link_unary_bytes_fallbacks": 100,
}
# PR 49's frames over a window: 300 packed or cut with every sized native
# call under the held limit, 100 with one over it
HELD_FRAMES = {
    "device_transport_frames_held": 300,
    "device_transport_frames_released": 100,
}
# PR 51: the lock probe's 2,000 ticks of a window, 1,800 of which found the
# lock taken and 10 of which waited the switch interval out, one stall of
# 150 ms; 30 + 10 CPU seconds by thread
LOCK = {
    "device_transport_lock_wait_us": recorder(2000, 900.0),
    "device_transport_machine_late_us": recorder(2000, 60.0),
    "device_transport_lock_probes": 2000,
    "device_transport_lock_busy": 1800,
    "device_transport_lock_forced": 10,
    "device_transport_lock_stall_us": 150_000,
    "device_transport_cpu_python_threads_us": 30e6,
    "device_transport_cpu_other_threads_us": 10e6,
}
LOCK_READERS = {
    "lock_wait_us": 900.0, "lock_busy_pct": 90.0, "lock_forced_pct": 0.5,
    "machine_late_us": 60.0, "stall_ms": 150.0,
    "host_cpu_python_threads_cores": 1.5, "host_cpu_other_threads_cores": 0.5,
}
EXPECTED = {
    **{metric: (LOCK, value) for metric, value in LOCK_READERS.items()},
    "host_plane_held_frames_pct": (HELD_FRAMES, 75.0),
    "host_plane_held_frames_pct.goodput": (HELD_FRAMES, 75.0),
    **{f"device_{s}_us": (DEVICE, 100.0) for s in DEVICE_STAGES},
    "device_path_unattributed_pct": (DEVICE, 10.0),
    "host_plane_ingress_us": (DEVICE, 2500.0),
    "host_plane_egress_us": (DEVICE, 400.0),
    "native_plane_callback_us": (DEVICE, 150.0),
    "dispatch_rows": (DEVICE, 2.5),
    "dispatch_pad_pct": (DEVICE, 100.0 * 28 / 128),
    "dispatch_widened_pct": (DEVICE, 60.0),
    "dispatch_zeroed_pct": (DEVICE, 100.0 * (28 * 64 + 100 * 16) / (128 * 64)),
    "dispatch_borrowed_pct": (DEVICE, 20.0),
    "echo_step_hbm_pct_dispatched": (DEVICE, HBM_DISPATCHED),
    "link_flush_us": (LINK, 40.0),
    "link_launch_us": (LINK, 1200.0),
    "link_ready_us": (LINK, 1500.0),
    "link_reorder_wait_us": (LINK, 100.0),
    "link_readback_us": (LINK, 800.0),
    "link_pump_us": (LINK, 500.0),
    "link_dispatch_interval_us": (LINK, 2000.0),
    "link_window_used": (LINK, 2.5),
    "link_slot_fill_pct": (LINK, 100.0 * 40 * (1 << 20) / (2 * 680 * 65536)),
    "link_slots_per_step": (LINK, 4.0),
    "link_send_wait_us": (LINK, 350.0),
    "link_backlog_slots": (LINK, 10.5),
    "link_hold_us": (LINK, 750.0),
    "link_held_pct": (LINK, 25.0),
    "link_prefetched_pct": (LINK, 100.0),
    "link_staged_pct": (LINK, 100.0),
    "stream_write_wait_us": (STREAM, 7000.0),
    "stream_feedback_lag_us": (STREAM, 21000.0),
    "stream_deliver_us": (STREAM, 450.0),
    "stream_window_used_pct": (STREAM, 50.0),
    "stream_messages_per_batch": (STREAM, 2.0),
    "combo_call_us": (COMBO, 1000.0),
    **{f"combo_{s}_us": (COMBO, us) for s, us in COMBO_STAGES.items()},
    "combo_unattributed_pct": (COMBO, 10.0),
    "combo_fused_pct": (COMBO, 80.0),
    "combo_joined_pct": (COMBO, 75.0),  # PR 47: of the fused calls
    # PR 35: the second clock
    **{f"device_{s}_cpu_us": (DEVICE, us) for s, us in DEVICE_CPU.items()},
    "link_launch_cpu_us": (LINK, 700.0),
    "link_readback_cpu_us": (LINK, 600.0),
    "link_pump_cpu_us": (LINK, 450.0),
    **{f"combo_{s}_cpu_us": (COMBO, COMBO_STAGES[s] / 4)
       for s in ("pack", "put", "launch", "gather", "merge")},
    # 30 s of CPU time in a window of 20: a processor and a half kept busy
    "host_cpu_cores": ({"device_transport_process_cpu_us": 30e6}, 1.5),
    # PR 37: the wait for the table, a part of the launch
    "table_state_wait_us": (
        {"device_transport_state_wait_us": recorder(100, 640.0)}, 640.0),
    # PR 39: the lane's stages, a row a paired program
    "lane_step_us": (LANE, 2000.0),
    **{f"lane_{s}_us": (LANE, us) for s, us in LANE_STAGES.items()},
    "lane_launch_cpu_us": (LANE, 250.0),
    "lane_messages_per_step": (LANE, 1.0),
    "lane_tagged_pct": (LANE, 100.0),
    # PR 42: the hand-over to the completion watchers, a row a submit
    "cq_submit_us": (CQ, 12.5),
    "cq_backlog": (CQ, 0.25),
    "stream_device_bytes_pct": (
        LANE, 100.0 * 640 * 2097152 / (640 * 2097152 + 40 * 64)),
    # PR 44: a unary call's stages, a row a call on each side of the link
    "unary_call_us": (UNARY, 10000.0),
    **{f"unary_{s}_us": (UNARY, us) for s, us in UNARY_STAGES.items()},
    **{f"unary_lane_{s}_us": (UNARY, us) for s, us in UNARY_LANE_STAGES.items()},
    "unary_unattributed_pct": (UNARY, 10.0),
    "unary_device_calls_pct": (UNARY, 80.0),
}
# PR 37's record table over a window: 40 dispatches that ran 128 rows, 90 of
# them reads and 10 updates, two of which a later row of their dispatch replaced
TABLE = {
    **DEVICE,
    "device_transport_table_reads": 90,
    "device_transport_table_updates": 10,
    "device_transport_table_overwritten_rows": 2,
    "device_transport_state_wait_us": recorder(100, 640.0),
}
# PR 31's and PR 33's device_trace readers: not a counter's mean, so
# outside EXPECTED
TRACE_READERS = {"link_step_ici_pct", "combo_step_kernel_us", "combo_gather_ici_pct"}
# PR 37's: the table step's device time and its share of the HBM roofline
TABLE_TRACE_READERS = {"table_step_kernel_us", "table_step_hbm_pct"}
# PR 39's: the lane program's share of the interconnect, the pool's write
LANE_TRACE_READERS = {
    "lane_step_ici_pct", "kv_page_write_kernel_us", "kv_page_write_hbm_pct"}
# PR 35's readers of the program's kept rows (benchmark/timeline.py): their
# numbers are checked in tests/test_stage_timeline.py
SPAN_READERS = {"idle_worker_open_pct", "idle_waiting_only_pct", "idle_outside_pct"}
# what the benchmark had before PR 25 reads no recorder this PR added
OLDER = {
    "host_plane_us", "device_path_us", "calls_per_dispatch",
    "echo_step_kernel_us", "echo_step_hbm_pct", "link_step_rtt_us",
    "link_steps_per_call", "device_idle_pct",
}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_and_readers(name):
    cell = manifest.Cell(BENCH, name)
    assert cell.chips == cell.config["chips"]
    assert hasattr(cell.deployment(), "Deployment")
    assert cell.reference().expected(b"a", b"b") == (b"a", b"b")
    assert cell.traffic["arrival"] in ("closed", "open")
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"])), m["name"]
        assert m["moves"] in reported, (name, m["name"])


def test_every_metric_is_accounted_for():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names))
    # a later PR may add more
    assert (OLDER | set(EXPECTED) | TRACE_READERS | TABLE_TRACE_READERS
            | LANE_TRACE_READERS | SPAN_READERS <= set(names))
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for m in BENCH["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]
        if m["name"] in EXPECTED:
            assert m["source"] == "program_counter"
            assert f"`{m['name']}`" in perf, m["name"]  # PERF.md section 3 names it


def test_new_metrics_report_in_the_cells_the_issue_gives_them():
    """At least these cells: a later cell of an echo configuration joins
    the lists of the metrics its deployment feeds (PR 27's did)."""
    cells = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    for name in sorted(set(EXPECTED) | TRACE_READERS | LANE_TRACE_READERS):
        if name == "link_step_ici_pct":
            # sums every collective-permute: it would count the lane's
            # programs as the trains', so PR 39's cell stays off its list
            assert cells[name] == ["link_echo_ici_1m", "link_stream_ici"]
        elif name.startswith("cq_"):
            assert cells[name] == CQ_CELLS, name
        elif name.startswith("link_"):
            # every link cell drives the link: PR 31's and PR 39's joined them
            assert cells[name] == ["link_echo_ici_1m", "link_stream_ici", KV_CELL], name
        elif name in LANE_BY_ADDERS:
            assert cells[name] == [KV_CELL, HBM_CELL], name
        elif name.startswith(("lane_", "kv_")) or name == "stream_device_bytes_pct":
            # only the KV block stream sends a device array over trains too
            assert cells[name] == [KV_CELL], name
        elif name == "unary_device_calls_pct":
            # moves goodput, which PR 54's cell does not report
            assert cells[name] == [HBM_CELL], name
        elif name.startswith("unary_"):
            # PR 44's cell makes a unary call with a device attachment, and
            # PR 54's three a layer call
            assert cells[name] == [HBM_CELL, EXCHANGE_CELL], name
        elif name.startswith("combo_"):
            # only the partitioned deployment builds a combo channel
            assert cells[name] == ["partition_star_4"], name
        elif name.startswith("stream_"):
            # only the streaming deployments open a stream
            assert cells[name] == ["link_stream_ici", KV_CELL], name
        elif name == "native_plane_callback_us":
            # only the native plane feeds it
            assert cells[name] == ["echo_256b_c16_native"]
        elif name == "table_state_wait_us":
            # the record table keeps a state to wait for; the expert shard
            # keeps one that no dispatch waits for, and reports that
            assert cells[name] == ["ycsb_b_zipf_c16", EXPERT_CELL]
        elif name == "host_plane_held_frames_pct":
            # the cells of host_plane_egress_us's list that report the
            # call_rate it moves; the one that reports goodput has the
            # entry beside it
            assert cells[name] == [
                "echo_256b_c16", "echo_mixed_c16", "echo_256b_c16_native",
                "ycsb_b_zipf_c16", EXPERT_CELL]
            assert cells[name + ".goodput"] == ["echo_4m_c2"]
        elif name == "host_plane_held_frames_pct.goodput":
            assert cells[name] == ["echo_4m_c2"]
        elif name in ("dispatch_pad_pct", "dispatch_widened_pct"):
            assert {"echo_256b_c16", "echo_mixed_c16"} <= set(cells[name])
            assert "echo_4m_c2" not in cells[name]
        else:
            assert set(ECHO_CELLS) <= set(cells[name]), name
    for name, listed in cells.items():
        # the native cell is echo_256b_c16 with the plane changed: it
        # reports whatever that cell reports
        if listed and "echo_256b_c16" in listed:
            assert "echo_256b_c16_native" in listed, name


def test_each_configuration_is_the_file_the_manifest_names():
    link_options = manifest.load_json(
        "configs", "link_performance_ici.json")["channel_options"]
    expected = {
        "echo_device": ("device_echo", "echo_identity", {}, None),
        "echo_device_native": (
            "device_echo_native", "echo_identity",
            {"native_plane": True}, {"native_plane": True},
        ),
        "link_performance_ici": ("link_echo", "echo_identity", None, None),
        # the stream rides the link link_performance_ici states
        "link_stream_sink_ici": ("link_stream", "stream_sink", link_options, None),
        # the star is three of that link
        "partition_echo_ici": (
            "partition_echo", "partition_concat", link_options, None),
        # the KV block stream rides it too, letter for letter
        "kv_block_stream_ici": (
            "kv_block_stream", "kv_block_pool", link_options, None),
        # and the unary tensor call: link_performance_ici's link as it is
        "link_performance_ici_hbm": (
            "link_echo_hbm", "tensor_echo_identity", link_options, None),
    }
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert set(expected) <= set(configs)  # a later PR may add more
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(sources) == len(set(sources))
    for name, (deployment, reference, channel_options, server_options) in (
            expected.items()):
        config = manifest.load_json("configs", name + ".json")
        assert configs[name]["file"] == f"benchmark/configs/{name}.json"
        assert config["deployment"] == deployment
        assert config["reference"] == reference
        assert config["reduced"] == configs[name]["reduced"] == []
        if channel_options is not None:
            assert config["channel_options"] == channel_options
        assert config.get("server_options") == server_options


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_gives_the_expected_number(metric):
    counters, expected = EXPECTED[metric]
    read = manifest.load_module("layers", metric + ".py").read
    assert read(hand_made_run(dict(counters))) == pytest.approx(expected)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_gives_none_where_the_program_lacks_the_recorder(metric):
    """The parent of PR 25 has none of these counters, and the driver runs
    these readers over it: nothing to read is ``None``, never an error."""
    read = manifest.load_module("layers", metric + ".py").read
    before_pr25 = {
        "device_transport_latency": recorder(100, 7000.0),
        "device_transport_calls": 100,
        "device_link_steps": 680,
        "device_link_bytes": 40 * (1 << 20),
        "device_link_3_step_rtt_us": recorder(680, 4100.0),
        "device_link_3_flush_us": recorder(1360, 40.0)
        if metric != "link_flush_us" else recorder(0, 0.0),
        "device_link_3_pump_us": recorder(680, 1300.0)
        if metric != "link_pump_us" else recorder(0, 0.0),
    }
    assert read(hand_made_run(before_pr25)) is None
    assert read(hand_made_run({})) is None


@pytest.mark.parametrize("metric, adder", [
    ("dispatch_zeroed_pct", "device_transport_dispatch_zeroed_words"),
    ("dispatch_borrowed_pct", "device_transport_dispatch_borrowed"),
])
def test_operand_reader_is_none_on_the_parents_counters(metric, adder):
    """PR 53's parent feeds every dispatch adder but these two, and the driver
    reads it with this PR's readers: ``None``, and the line leaves it out. A
    window without a dispatch has no share either; one in which the adder
    stood still reads 0."""
    read = manifest.load_module("layers", metric + ".py").read
    parents = {k: v for k, v in DEVICE.items() if k != adder}
    assert read(hand_made_run(parents)) is None
    idle = dict(DEVICE, device_transport_dispatches=0, device_transport_dispatch_words=0)
    assert read(hand_made_run(idle)) is None
    assert read(hand_made_run(dict(DEVICE, **{adder: 0}))) == 0.0


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_idle_share_reader_is_a_program_span_in_every_cell_and_none_without_rows(metric):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    assert set(entry["workloads"]) == set(CELLS)
    read = manifest.load_module("layers", metric + ".py").read
    # no feed of the program holds a row inside this hand-made window, and a
    # parent from before PR 35 has no feeds at all: nothing to read is None
    assert read(hand_made_run(dict(DEVICE))) is None
    assert read(hand_made_run({})) is None


def test_the_new_entries_only_follow_the_old():
    """``per_layer`` only grows: PR 34's 57 entries lead, in their order."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[56] == "combo_gather_ici_pct" and names[57] == "device_stack_cpu_us"
    assert all(m["moves"] == "latency_p50_us" for m in BENCH["per_layer"][57:72])
    # PR 36's one entry follows PR 35's fifteen
    assert names[72] == "link_prefetched_pct"
    assert BENCH["per_layer"][72] == {
        "name": "link_prefetched_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "link", "moves": "goodput",
        "workloads": ["link_echo_ici_1m", "link_stream_ici", KV_CELL],
    }
    # PR 37's three follow it, and its configuration and cell the old ones
    assert names[73:76] == [
        "table_step_kernel_us", "table_step_hbm_pct", "table_state_wait_us"]
    # PR 38's one entry follows them
    assert BENCH["per_layer"][76] == {
        "name": "link_staged_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "link", "moves": "goodput",
        "workloads": ["link_echo_ici_1m", "link_stream_ici", KV_CELL],
    }
    # PR 39's eleven follow it, each in its one cell
    assert names[77:88] == [
        "lane_step_us", "lane_launch_us", "lane_ready_us", "lane_pair_wait_us",
        "lane_deliver_us", "lane_launch_cpu_us", "lane_messages_per_step",
        "lane_step_ici_pct", "stream_device_bytes_pct",
        "kv_page_write_kernel_us", "kv_page_write_hbm_pct"]
    assert all(
        m["workloads"] == (
            [KV_CELL, HBM_CELL] if m["name"] in LANE_BY_ADDERS else [KV_CELL])
        for m in BENCH["per_layer"][77:89])
    assert [m["layer"] for m in BENCH["per_layer"][77:88]] == (
        ["link"] * 8 + ["stream"] + ["device program"] * 2)
    # PR 40's one entry follows them
    assert names[88] == "lane_tagged_pct"
    assert BENCH["per_layer"][88] == {
        "name": "lane_tagged_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "link", "moves": "goodput",
        "workloads": [KV_CELL, HBM_CELL],
    }
    # PR 42's two follow it, in the nine cells that call ``watch``
    assert BENCH["per_layer"][89:91] == [
        {"name": "cq_submit_us", "unit": "us", "better": "lower",
         "source": "program_counter",
         "layer": "host to HBM crossing and completion",
         "moves": "latency_p50_us", "workloads": CQ_CELLS},
        {"name": "cq_backlog", "unit": "jobs", "better": "lower",
         "source": "program_counter",
         "layer": "host to HBM crossing and completion",
         "moves": "latency_p50_us", "workloads": CQ_CELLS},
    ]
    assert set(CELLS) - set(CQ_CELLS) == {"partition_star_4"}
    # PR 44's eleven follow them, each in its one cell
    assert names[91:102] == [
        "unary_request_tx_us", "unary_server_dispatch_us", "unary_reply_tx_us",
        "unary_client_wake_us", "unary_call_us", "unary_unattributed_pct",
        "unary_device_calls_pct", "unary_lane_launch_us", "unary_lane_ready_us",
        "unary_lane_pair_wait_us", "unary_lane_deliver_us"]
    assert all(
        (m["workloads"], m["source"]) == (
            [HBM_CELL] if m["name"] == "unary_device_calls_pct"
            else [HBM_CELL, EXCHANGE_CELL], "program_counter")
        for m in BENCH["per_layer"][91:102])
    assert [m["layer"] for m in BENCH["per_layer"][91:102]] == [
        "link", "host plane", "link", "host plane", "host plane", "host plane",
        "host plane", "link", "link", "link", "link"]
    # PR 47's one entry follows them, in the star's cell
    assert BENCH["per_layer"][102] == {
        "name": "combo_joined_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "combo", "moves": "goodput",
        "workloads": ["partition_star_4"],
    }
    # PR 48's five follow it, the last, in the expert shard's cell
    assert names[103:108] == [
        "expert_step_kernel_us", "expert_step_hbm_pct", "expert_step_mxu_pct",
        "expert_layers_per_dispatch", "expert_tokens_per_dispatch"]
    assert all(
        (m["workloads"], m["layer"]) == ([EXPERT_CELL], "device program")
        for m in BENCH["per_layer"][103:108])
    # PR 49's engagement counter follows them, the last: one quantity under
    # two names, because its cells report two different end-to-end metrics
    assert names[108:110] == [
        "host_plane_held_frames_pct", "host_plane_held_frames_pct.goodput"]
    assert [(m["moves"], m["layer"], m["source"], m["unit"], m["better"])
            for m in BENCH["per_layer"][108:110]] == [
        ("call_rate", "host plane", "program_counter", "%", "higher"),
        ("goodput", "host plane", "program_counter", "%", "higher")]
    # PR 51's seven follow them, the last: the server process in every cell
    assert names[110:117] == list(LOCK_READERS)
    assert all(
        (m["layer"], m["moves"], m["source"], m["better"], m["workloads"])
        == ("server process", "latency_p50_us", "program_counter", "lower", CELLS)
        for m in BENCH["per_layer"][110:117])
    # PR 53's two follow them, the last: how far the one-pass operand engages,
    # in the six cells with a DeviceEndpoint, beside device_stack_us
    stack = next(m for m in BENCH["per_layer"] if m["name"] == "device_stack_us")
    assert len(names) >= 119
    assert names[117:119] == ["dispatch_zeroed_pct", "dispatch_borrowed_pct"]
    assert [(m["better"], m["unit"], m["source"]) for m in BENCH["per_layer"][117:119]] == [
        ("lower", "%", "program_counter"), ("higher", "%", "program_counter")]
    assert all(
        (m["layer"], m["moves"], m["workloads"])
        == (stack["layer"], "latency_p50_us", stack["workloads"])
        for m in BENCH["per_layer"][117:119])
    egress = next(m for m in BENCH["per_layer"] if m["name"] == "host_plane_egress_us")
    assert sorted(
        BENCH["per_layer"][108]["workloads"] + BENCH["per_layer"][109]["workloads"]
    ) == sorted(egress["workloads"])
    for entry, source, layer, moves in zip(
            BENCH["per_layer"][73:76],
            ("device_trace", "device_trace", "program_counter"),
            ("device program", "device program",
             "host to HBM crossing and completion"),
            ("latency_p50_us", "call_rate", "latency_p50_us")):
        assert (entry["source"], entry["layer"], entry["moves"]) == (
            source, layer, moves)
        # the table's step is the table's alone; the wait for the state is
        # also reported where a state is kept and never waited for (PR 48)
        assert entry["workloads"] == ["ycsb_b_zipf_c16"] + (
            [EXPERT_CELL] if entry["name"] == "table_state_wait_us" else [])
    assert [c["name"] for c in BENCH["configs"]][5:] == [
        "ycsb_b_device_table", "kv_block_stream_ici", "link_performance_ici_hbm",
        "expert_shard_dsv3_ep32", "expert_exchange_dsv3_ep32"]
    assert CELLS[7:] == [
        "ycsb_b_zipf_c16", KV_CELL, HBM_CELL, EXPERT_CELL, EXCHANGE_CELL]
    assert [w["chips"] for w in BENCH["workloads"][7:]] == [1, 4, 4, 1, 4]
    # PR 54's nine follow PR 53's, the last, in the four-chip expert cell; the
    # file holds 128 per-layer metrics, which is as many as it may
    assert names[119:] == [
        "exchange_call_us", "exchange_fanout_us", "exchange_device_operands_pct",
        "exchange_step_kernel_us", "exchange_step_hbm_pct", "exchange_step_mxu_pct",
        "exchange_combine_hbm_pct", "exchange_lane_ici_pct",
        "exchange_lane_messages_per_step"]
    assert all(m["workloads"] == [EXCHANGE_CELL] for m in BENCH["per_layer"][119:])
    assert [(m["layer"], m["moves"], m["source"]) for m in BENCH["per_layer"][119:]] == [
        ("expert exchange", "latency_p50_us", "program_counter"),
        ("expert exchange", "latency_p50_us", "program_counter"),
        ("host to HBM crossing and completion", "call_rate", "program_counter"),
        ("device program", "latency_p50_us", "device_trace"),
        ("device program", "call_rate", "device_trace"),
        ("device program", "call_rate", "device_trace"),
        ("expert exchange", "latency_p50_us", "device_trace"),
        ("link", "call_rate", "device_trace"),
        ("link", "call_rate", "program_counter")]
    for m in BENCH["end_to_end"] + BENCH["per_layer"][:91]:
        # an older metric gained a cell's name at the end of its list or not
        # at all: PR 48's last, PR 44's before it, PR 39's before that
        workloads = list(m.get("workloads", ()))
        if EXCHANGE_CELL in workloads:  # PR 54's last of all
            assert workloads.pop() == EXCHANGE_CELL, m["name"]
        if EXPERT_CELL in workloads:
            assert workloads.pop() == EXPERT_CELL, m["name"]
        if HBM_CELL in workloads:
            assert workloads.pop() == HBM_CELL, m["name"]
        listed = [w for w in workloads if w != KV_CELL]
        if KV_CELL in workloads:
            assert workloads[-1] == KV_CELL, m["name"]
        if "ycsb_b_zipf_c16" in listed and m["name"] not in (
                "table_step_kernel_us", "table_step_hbm_pct", "table_state_wait_us"):
            assert listed[-1] == "ycsb_b_zipf_c16", m["name"]


def test_every_per_layer_entry_has_its_reader_file():
    for m in BENCH["per_layer"]:
        # a missing file raises here, under the entry's name
        assert callable(manifest.load_module("layers", m["name"] + ".py").read)


def test_prefetched_share_counts_the_trains_asked_for_at_dispatch():
    read = manifest.load_module("layers", "link_prefetched_pct.py").read
    # a window that opened over a link from before the request: 510 of 680
    part = {**LINK, "device_link_prefetched_steps": 510}
    assert read(hand_made_run(part)) == pytest.approx(75.0)
    # the host swap dispatches no program and asks for nothing
    assert read(hand_made_run({**LINK, "device_link_prefetched_steps": 0})) == 0.0
    # a program without the adder (the parent), or a window with no step
    without = {k: v for k, v in LINK.items() if k != "device_link_prefetched_steps"}
    assert read(hand_made_run(without)) is None
    assert read(hand_made_run({"device_link_prefetched_steps": 0})) is None


@pytest.mark.parametrize(
    "counters,share",
    [
        # every train of the window launched from one host buffer
        (LINK, 100.0),
        # a window that opened over a link from before the staging: 170 of 680
        ({**LINK, "device_link_staged_steps": 170}, 25.0),
        # the host swap dispatches no program and stages nothing
        ({**LINK, "device_link_staged_steps": 0}, 0.0),
        # a program without the adder (the parent), or a window with no step
        ({k: v for k, v in LINK.items() if k != "device_link_staged_steps"}, None),
        ({"device_link_staged_steps": 0}, None),
    ],
    ids=["every-train", "a-quarter", "host-swap", "no-adder", "no-step"],
)
def test_staged_share_counts_the_trains_launched_from_one_host_buffer(counters, share):
    read = manifest.load_module("layers", "link_staged_pct.py").read
    value = read(hand_made_run(dict(counters)))
    assert value is None if share is None else value == pytest.approx(share)


@pytest.mark.parametrize(
    "counters,share",
    [
        # every lane program of the window carried its message's tag
        (LANE, 100.0),
        # a window that opened over a lane from before the tag: 160 of 640
        ({**LANE, "device_link_lane_tagged_steps": 160}, 25.0),
        # a program without the adder (the parent: a header on the byte
        # stream paired with the body), or a window with no lane program
        ({k: v for k, v in LANE.items() if k != "device_link_lane_tagged_steps"}, None),
        ({**LINK, "device_link_lane_tagged_steps": 0}, None),
    ],
    ids=["every-program", "a-quarter", "no-adder", "no-lane-program"],
)
def test_tagged_share_counts_the_lane_programs_that_carried_their_tag(counters, share):
    read = manifest.load_module("layers", "lane_tagged_pct.py").read
    value = read(hand_made_run(dict(counters)))
    assert value is None if share is None else value == pytest.approx(share)


@pytest.mark.parametrize("metric", ["cq_submit_us", "cq_backlog"])
@pytest.mark.parametrize(
    "gain,value",
    [
        (recorder(40, 3.5), 3.5),  # a window of 40 submits
        # a backlog of 0 at every submit is a reading, not a silence
        (recorder(40, 0.0), 0.0),
        # a program without the recorder (the parent), or a window in which
        # nothing was handed over (the star's)
        (None, None),
        (recorder(0, 0.0), None),
    ],
    ids=["a-window", "all-zero", "no-recorder", "no-submit"],
)
def test_hand_over_readers_give_a_number_with_the_recorder_and_none_without(
        metric, gain, value):
    read = manifest.load_module("layers", metric + ".py").read
    counters = dict(DEVICE)
    if gain is not None:
        counters["device_transport_" + metric] = gain
    got = read(hand_made_run(counters))
    assert got is None if value is None else got == pytest.approx(value)


def test_unattributed_share_needs_every_stage_and_a_handler_span():
    read = manifest.load_module("layers", "device_path_unattributed_pct.py").read
    short = dict(DEVICE)
    del short["device_transport_wake_us"]
    assert read(hand_made_run(short)) is None
    run = hand_made_run(dict(DEVICE))
    run.handler = run.handler[:0]
    assert read(run) is None


def test_roofline_share_needs_device_time_and_peaks():
    read = manifest.load_module("layers", "echo_step_hbm_pct_dispatched.py").read
    run = hand_made_run(dict(DEVICE))
    run.devices = {}
    assert read(run) is None
    run = hand_made_run(dict(DEVICE))
    run.peaks = None  # a rehearsal on the CPU has no peaks
    assert read(run) is None


def permute_run(counters: dict):
    """Two chips, each with 100 exchange programs in the window: a
    ``collective-permute-start`` of 1 us and a ``-done`` of 9 us, a copy
    that is not the step's, and one permute outside the window."""
    run = hand_made_run(counters)
    start = T_OPEN + np.arange(100, dtype=np.int64) * 1_000_000
    names = (
        ["%collective-permute-start = (u32[1,8,16392]{2,1,0}, u32[1,8,16392]"
         "{2,1,0}, u32[], u32[]) collective-permute-start(u32[1,8,16392] %p)"] * 100
        + ["%collective-permute-done = u32[1,8,16392]{2,1,0} "
           "collective-permute-done(%collective-permute-start)"] * 101
        + ["%copy.3 = u32[1,8,16392]{2,1,0} copy(%collective-permute-done)"] * 100
    )
    starts = np.concatenate(
        (start, start + 1_000, [T_CLOSE + 5_000], start + 10_000))
    ends = np.concatenate(
        (start + 1_000, start + 10_000, [T_CLOSE + 9_000], start + 50_000))
    ops = xplane.Events(names, starts, ends)
    run.devices = {
        plane: {"ops": ops, "steps": xplane.Events([], [], [])}
        for plane in ("/device:TPU:0", "/device:TPU:1")
    }
    return run


def test_link_step_ici_share_from_a_fabricated_trace():
    read = manifest.load_module("layers", "link_step_ici_pct.py").read
    run = permute_run({"device_link_slots": 800})
    # 800 slots of 64 KiB a side in 100 x 10 us of permute on each chip,
    # against the chip's 1,600 Gbit/s
    share = 100.0 * (800 * 16384 * 4 / 1e-3) / (1600e9 / 8)
    assert read(run) == pytest.approx(share)
    assert 0 < share < 100
    for spoil in ("slots", "trace", "peaks", "ici", "words"):
        run = permute_run({"device_link_slots": 800})
        if spoil == "slots":
            run.counters = {}  # a program from before PR 30
        elif spoil == "trace":
            run.devices = {}  # a CPU rehearsal: no device plane
        elif spoil == "peaks":
            run.peaks = None
        elif spoil == "ici":
            run.peaks = {"hbm_bytes_per_s": 819e9}
        else:
            run.cell = types.SimpleNamespace(config={"channel_options": {}})
        assert read(run) is None, spoil
    # a chip that ran no permute in the window does not count in the mean
    run = permute_run({"device_link_slots": 800})
    run.devices["/device:TPU:2"] = {
        "ops": xplane.Events(["%copy.9 = u32[8]{0} copy(%p)"], [T_OPEN], [T_OPEN + 9]),
        "steps": xplane.Events([], [], []),
    }
    assert read(run) == pytest.approx(share)


def gather_run(counters: dict):
    """Three shard chips, each with 100 executions of the fused program in
    the window: an ``all-gather`` of 20 us and the copy after it inside a
    program execution of 50 us, a link's exchange program that is not the
    combo's, and one all-gather outside the window."""
    run = hand_made_run(counters)
    start = T_OPEN + np.arange(100, dtype=np.int64) * 1_000_000
    names = (
        ["%all-gather.5 = u8[3,1,1048576]{2,1,0} all-gather(u8[1,1048576] "
         "%bitcast.3), channel_id=1, replica_groups={{0,1,2}}"] * 101
        + ["%copy_bitcast_fusion = u8[3,1048576]{1,0} fusion(%all-gather.5)"] * 100
    )
    starts = np.concatenate((start, [T_CLOSE + 5_000], start + 20_000))
    ends = np.concatenate((start + 20_000, [T_CLOSE + 9_000], start + 50_000))
    ops = xplane.Events(names, starts, ends)
    steps = xplane.Events(
        ["jit_combo_fused(123)"] * 100 + ["jit_exchange(9)"] * 5,
        np.concatenate((start, start[:5] + 500_000)),
        np.concatenate((start + 50_000, start[:5] + 900_000)),
    )
    run.devices = {
        f"/device:TPU:{i}": {"ops": ops, "steps": steps} for i in (1, 2, 3)
    }
    run.cell = types.SimpleNamespace(
        config={"partitions": 3, "row_bytes": 1048576})
    return run


def test_combo_device_readers_from_a_fabricated_trace():
    kernel = manifest.load_module("layers", "combo_step_kernel_us.py").read
    share = manifest.load_module("layers", "combo_gather_ici_pct.py").read
    run = gather_run(dict(COMBO))
    assert kernel(run) == pytest.approx(50.0)  # the exchange program is not read
    # 200 calls, the other two 1 MiB rows each, in 100 x 20 us of all-gather
    # on each chip, against the chip's 1,600 Gbit/s
    want = 100.0 * (200 * 2 * 1048576 / 2e-3) / (1600e9 / 8)
    assert share(run) == pytest.approx(want)
    assert 0 < want < 105
    for spoil in ("calls", "trace", "peaks", "ici", "rows"):
        run = gather_run(dict(COMBO))
        if spoil == "calls":
            run.counters = {}  # a program from before PR 33
        elif spoil == "trace":
            run.devices = {}  # a CPU rehearsal: no device plane
        elif spoil == "peaks":
            run.peaks = None
        elif spoil == "ici":
            run.peaks = {"hbm_bytes_per_s": 819e9}
        else:
            run.cell = types.SimpleNamespace(config=STREAM_CONFIG)
        assert share(run) is None, spoil
        if spoil == "trace":
            assert kernel(run) is None
    # another cell's trace holds no fused program and no all-gather
    assert kernel(permute_run(dict(COMBO))) is None
    assert share(permute_run(dict(COMBO))) is None


def test_combo_shares_need_every_recorder_and_a_call():
    unattributed = manifest.load_module("layers", "combo_unattributed_pct.py").read
    fused = manifest.load_module("layers", "combo_fused_pct.py").read
    short = dict(COMBO)
    del short["device_link_combo_merge_us"]
    assert unattributed(hand_made_run(short)) is None
    idle = dict(COMBO, device_link_combo_fused=0, device_link_combo_host_fanout=0,
                device_link_combo_mc_lowered=0)
    assert fused(hand_made_run(idle)) is None


@pytest.mark.parametrize(
    "joined,fused,share",
    [
        (200, 200, 100.0),  # every merger of every call the default one
        (0, 200, 0.0),  # a user's merger ran in every call: a reading
        (None, 200, None),  # a program without the adder (the parent)
        (0, 0, None),  # a window without a fused call
        (None, None, None),  # a program from before PR 33
    ],
    ids=["every-call", "no-call-joined", "no-adder", "no-fused-call", "no-combo"],
)
def test_joined_share_counts_the_fused_calls_joined_once(joined, fused, share):
    read = manifest.load_module("layers", "combo_joined_pct.py").read
    counters = {
        k: v for k, v in COMBO.items()
        if k not in ("device_link_combo_joined", "device_link_combo_fused")}
    if joined is not None:
        counters["device_link_combo_joined"] = joined
    if fused is not None:
        counters["device_link_combo_fused"] = fused
    value = read(hand_made_run(counters))
    assert value is None if share is None else value == pytest.approx(share)


def test_window_share_needs_the_configuration_to_state_a_window():
    read = manifest.load_module("layers", "stream_window_used_pct.py").read
    run = hand_made_run(dict(STREAM))
    run.cell = types.SimpleNamespace(config={"channel_options": {}})
    assert read(run) is None


# -- PR 37: the record table's configuration, cell and readers ----------------

YCSB_JOINS = (
    [f"device_{s}_us" for s in DEVICE_STAGES]
    + ["device_path_unattributed_pct", "device_path_us", "host_plane_ingress_us",
       "host_plane_egress_us", "dispatch_rows", "dispatch_pad_pct",
       "dispatch_widened_pct", "dispatch_zeroed_pct", "dispatch_borrowed_pct",
       "device_stack_cpu_us", "device_launch_cpu_us",
       "device_readback_cpu_us", "host_cpu_cores", "idle_worker_open_pct",
       "idle_waiting_only_pct", "idle_outside_pct", "device_idle_pct"])
YCSB_STAYS_OUT = (
    "echo_step_kernel_us", "echo_step_hbm_pct", "echo_step_hbm_pct_dispatched",
    "calls_per_dispatch", "host_plane_us", "native_plane_callback_us")


@pytest.mark.parametrize("metric", YCSB_JOINS)
def test_the_table_cell_joins_the_metric_its_deployment_feeds(metric):
    cell = manifest.Cell(BENCH, "ycsb_b_zipf_c16")
    assert metric in [m["name"] for m in cell.per_layer]


@pytest.mark.parametrize("metric", YCSB_STAYS_OUT)
def test_the_table_cell_stays_out_of_what_reads_an_echo_or_another_process(metric):
    cell = manifest.Cell(BENCH, "ycsb_b_zipf_c16")
    assert metric not in [m["name"] for m in cell.per_layer]


def test_the_table_cell_reports_rate_and_tails():
    cell = manifest.Cell(BENCH, "ycsb_b_zipf_c16")
    assert {m["name"] for m in cell.end_to_end} == {
        "call_rate", "latency_p50_us", "latency_p99_us", "setup_s"}
    assert cell.traffic == {
        "what": cell.traffic["what"], "arrival": "closed", "callers": 16,
        "sizes": [128], "carrier": "payload", "service": "ycsb",
        "method": "operation", "pool_per_size": 2048,
        "warm_calls_per_caller": 8, "warm_seconds": 3.0,
    }


def test_the_table_configuration_keeps_the_sources_shapes():
    config = manifest.load_json("configs", "ycsb_b_device_table.json")
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "ycsb_b_device_table"]
    assert entry["file"] == "benchmark/configs/ycsb_b_device_table.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"] == ["recordcount"]
    assert (config["deployment"], config["reference"], config["generator"]) == (
        "record_table", "ycsb_record_store", "in_process")
    # workloads/workloadb and CoreWorkload.java's defaults, as they are
    assert (config["fieldcount"], config["fieldlength"]) == (10, 100)
    assert (config["readproportion"], config["updateproportion"]) == (0.95, 0.05)
    assert config["scanproportion"] == config["insertproportion"] == 0
    assert config["readallfields"] is True and config["writeallfields"] is False
    assert (config["requestdistribution"], config["zipfian_constant"]) == (
        "zipfian", 0.99)
    # cut to one chip: 8 GiB of rows of 256 words, half of its memory
    assert config["recordcount"] * config["row_words"] * 4 == 8 << 30
    assert config["rehearsal_records"] == 4096
    assert config["endpoint"] == {"window_size": 16, "max_batch": 16}
    assert len(config["guarantees"]) >= 5
    for key in ("key", "table_seed", "threadcount", "operationcount", "generator",
                "allocator"):
        assert key in config["assumed"], key
    reference = manifest.load_module("references", "ycsb_record_store.py")
    assert (reference.FIELDCOUNT, reference.FIELDLENGTH) == (10, 100)
    assert (reference.READPROPORTION, reference.ZIPFIAN_CONSTANT) == (0.95, 0.99)
    assert reference.RECORDCOUNT == config["recordcount"]


def test_the_table_deployment_has_the_two_controls_every_configuration_has():
    deployment = manifest.load_module("deployments", "record_table.py")
    assert deployment.CONTROLS == ("flip_bit", "stale")
    for name in ("port", "warm", "holds", "close", "channel"):
        assert name == "port" or callable(getattr(deployment.Deployment, name))


def test_roofline_table_counts_three_reads_and_an_update_by_hand():
    # four rows ran: a request row each at the narrowest bucket, 64 words;
    # a read reads its 1,024 B row and writes a frame of 32 + 1,000 B;
    # the update writes its 100 B field
    by_hand = 4 * 256 + 3 * (1024 + 32 + 1000) + 1 * 100
    assert by_hand == 7292
    assert roofline_table.table_step_bytes(reads=3, updates=1, rows_run=4) == by_hand
    # pad rows are rows the step had to read to know they ask nothing
    assert roofline_table.table_step_bytes(3, 1, 8) == by_hand + 4 * 256
    assert roofline_table.table_step_bytes(0, 0, 0) == 0
    assert roofline_table.REQUEST_ROW_BYTES == 4 * roofline.bucket_words(8)
    assert roofline_table.FRAME_HEADER_BYTES == 4 * roofline.FRAME_HEADER_WORDS


def test_table_step_readers_from_fabricated_counters_and_a_fabricated_trace():
    kernel = manifest.load_module("layers", "table_step_kernel_us.py").read
    share = manifest.load_module("layers", "table_step_hbm_pct.py").read
    wait = manifest.load_module("layers", "table_state_wait_us.py").read
    run = hand_made_run(dict(TABLE))
    # 50 executions of 2 us in the window
    assert kernel(run) == pytest.approx(2.0)
    # 128 rows run, 90 reads, 10 updates, in 100 us of steps, against 819 GB/s
    least = 128 * 256 + 90 * (1024 + 32 + 1000) + 10 * 100
    assert least == 218_808
    want = 100.0 * least / 819e9 / 100e-6
    assert share(run) == pytest.approx(want) and 0 < want < 105
    assert wait(run) == pytest.approx(640.0)
    # a program without the table's counters (the parent, an echo cell)
    for reader in (kernel, share, wait):
        assert reader(hand_made_run(dict(DEVICE))) is None
        assert reader(hand_made_run({})) is None
    # a CPU rehearsal: no device plane, no peaks
    run = hand_made_run(dict(TABLE))
    run.devices = {}
    assert kernel(run) is None and share(run) is None
    run = hand_made_run(dict(TABLE))
    run.peaks = None
    assert share(run) is None and kernel(run) == pytest.approx(2.0)
    # a window that served nothing ran no row
    idle = dict(TABLE, device_transport_dispatch_pad_rows=0)
    assert share(hand_made_run(idle)) is None


def lane_run(counters: dict):
    """The prefill chip and the decode chip over a window: 640 executions
    of the lane's program of 25 us on each, 160 of the pool's write of 40
    us on the decode chip, an exchange program that is neither, and one
    lane program outside the window."""
    run = hand_made_run(counters)
    start = T_OPEN + np.arange(640, dtype=np.int64) * 1_000_000
    lane = "jit_device_link_lane(1234567890)"
    both = xplane.Events(
        [lane] * 641 + ["jit_exchange(99)"] * 640,
        np.concatenate((start, [T_CLOSE + 5_000], start + 100_000)),
        np.concatenate((start + 25_000, [T_CLOSE + 30_000], start + 130_000)),
    )
    write = xplane.Events(
        ["jit_kv_page_write(42)"] * 160, start[:160] + 200_000, start[:160] + 240_000)
    decode = xplane.Events(
        both.names + write.names,
        np.concatenate((both.start, write.start)),
        np.concatenate((both.end, write.end)),
    )
    none = xplane.Events([], [], [])
    run.devices = {
        "/device:TPU:0": {"steps": both, "ops": none},
        "/device:TPU:1": {"steps": decode, "ops": none},
    }
    run.cell = types.SimpleNamespace(
        config={"prefill_device": 0, "block_bytes": 2097152})
    return run


def test_lane_and_page_write_shares_from_a_fabricated_trace():
    ici = manifest.load_module("layers", "lane_step_ici_pct.py").read
    kernel = manifest.load_module("layers", "kv_page_write_kernel_us.py").read
    hbm = manifest.load_module("layers", "kv_page_write_hbm_pct.py").read
    # 640 blocks of 2 MiB in 640 x 25 us of the lane's program on the
    # sending chip alone, against the chip's 1,600 Gbit/s
    share = 100.0 * (640 * 2097152 / 16e-3) / (1600e9 / 8)
    assert ici(lane_run(dict(LANE))) == pytest.approx(share)
    assert 0 < share < 100
    assert kernel(lane_run(dict(LANE))) == pytest.approx(40.0)
    # 640 pages, each a block read and a page written, in 160 x 40 us
    written = 100.0 * (2 * 640 * 2097152 / 819e9) / 6.4e-3
    assert hbm(lane_run(dict(LANE))) == pytest.approx(written)
    assert 0 < written < 100
    assert roofline_lane.program_time(
        lane_run({}).devices, T_OPEN, T_CLOSE, "device_link_lane") == (1280, 32_000_000)
    for read in (ici, kernel, hbm):
        # a program without the lane or the pool (the parent), a CPU
        # rehearsal with no device plane, a machine with no peaks on record
        assert read(lane_run({})) is None
        run = lane_run(dict(LANE))
        run.devices = {}
        assert read(run) is None
    for read in (ici, hbm):
        run = lane_run(dict(LANE))
        run.peaks = None
        assert read(run) is None
    # the exchange's programs are not the lane's: a trace without the
    # lane's own name reads nothing
    run = lane_run(dict(LANE))
    for lines in run.devices.values():
        lines["steps"] = xplane.Events(["jit_exchange(99)"], [T_OPEN], [T_OPEN + 9])
    assert ici(run) is None and kernel(run) is None and hbm(run) is None


# -- PR 44: the unary tensor call's configuration, cell and readers ---------------


def test_the_tensor_echo_configuration_keeps_the_sources_shape():
    config = manifest.load_json("configs", "link_performance_ici_hbm.json")
    host = manifest.load_json("configs", "link_performance_ici.json")
    cell = manifest.Cell(BENCH, HBM_CELL)
    # upstream's attachment_size, echoed, in the device's memory
    assert config["attachment"] == {
        "dtype": "uint32", "words": 262144, "bytes": 1048576}
    assert cell.traffic["sizes"] == [config["attachment"]["bytes"]]
    assert 4 * config["attachment"]["words"] == config["attachment"]["bytes"]
    assert (cell.traffic["callers"], cell.traffic["arrival"]) == (4, "closed")
    assert cell.traffic["carrier"] == "attachment"
    assert (cell.traffic["service"], cell.traffic["method"]) == ("EchoService", "Echo")
    assert cell.traffic["warm_calls_per_caller"] == 4
    # link_performance_ici's link, checks, generator and allocator as they are
    for key in ("channel_options", "link", "generator", "allocator", "chips"):
        assert config[key] == host[key], key
    assert config["generator"] == "in_process" and cell.chips == 4
    assert config["architecture"] is None and config["reduced"] == []
    assert "use_rdma=true" in config["source"]
    assert {m["name"] for m in cell.end_to_end} == {
        "goodput", "latency_p50_us", "setup_s"}
    entry = [c for c in BENCH["configs"] if c["name"] == "link_performance_ici_hbm"]
    assert entry[0]["reduced"] == [] and entry[0]["source"].startswith(
        "apache/brpc example/rdma_performance")
    four_chip = [w["chips"] for w in BENCH["workloads"]].count(4)
    assert four_chip <= len(CELLS) // 2  # the half a benchmark may give four chips


def test_the_tensor_echo_deployment_has_the_four_controls():
    assert manifest.Cell(BENCH, HBM_CELL).deployment().CONTROLS == (
        "flip_bit", "stale", "swap", "host_bytes")


def test_the_tensor_echo_reference_is_the_identity_on_a_seeded_tensor():
    reference = manifest.Cell(BENCH, HBM_CELL).reference()
    tensor = reference.content(2**31 + 7, 3, 11, 262144)
    assert tensor.dtype == np.uint32 and tensor.nbytes == 1048576
    assert np.array_equal(tensor, reference.content(2**31 + 7, 3, 11, 262144))
    for other in ((2**31 + 8, 3, 11), (2**31 + 7, 2, 11), (2**31 + 7, 3, 12)):
        assert not np.array_equal(tensor, reference.content(*other, 262144))
    request, answer = reference.expected(b"ping", tensor)
    assert request == b"ping" and answer is tensor


def test_the_unary_readers_go_by_the_link_with_most_unary_calls_not_by_trains():
    """A window of the cell need hold no train: the readers find their link
    by its unary calls, where ``stages.link_recorder`` finds none."""
    from benchmark import stages, stages_unary

    run = hand_made_run(dict(UNARY))
    assert stages.link_recorder(run, "lane_ready_us") is None
    assert stages_unary.link_recorder(run, "lane_ready_us") == pytest.approx(900.0)
    # trains on another link do not mislead them
    run = hand_made_run({**UNARY, "device_link_2_step_rtt_us": recorder(680, 1.0)})
    assert stages_unary.link_recorder(run, "unary_call_us") == pytest.approx(10000.0)


def test_unary_unattributed_share_needs_every_stage_and_a_handler_span():
    read = manifest.load_module("layers", "unary_unattributed_pct.py").read
    for missing in ("device_link_3_unary_client_wake_us", "device_link_3_lane_ready_us",
                    "device_link_3_unary_call_us"):
        short = dict(UNARY)
        del short[missing]
        assert read(hand_made_run(short)) is None, missing
    run = hand_made_run(dict(UNARY))
    run.handler = run.handler[:0]
    assert read(run) is None


@pytest.mark.parametrize(
    "counters,share",
    [
        ({"device_link_unary_lane_requests": 300,
          "device_link_unary_bytes_fallbacks": 0}, 100.0),
        ({"device_link_unary_lane_requests": 0,
          "device_link_unary_bytes_fallbacks": 50}, 0.0),
        # a window without such a call (the host_bytes control), and the parent
        ({"device_link_unary_lane_requests": 0,
          "device_link_unary_bytes_fallbacks": 0}, None),
        ({}, None),
    ],
    ids=["every-call", "all-fell-back", "no-device-call", "no-adder"],
)
def test_unary_device_share_counts_the_requests_that_took_the_lane(counters, share):
    read = manifest.load_module("layers", "unary_device_calls_pct.py").read
    value = read(hand_made_run(dict(counters)))
    assert value is None if share is None else value == pytest.approx(share)


def test_the_lane_share_counts_a_unary_cells_two_directions_right():
    """Both directions' bytes over both executions on TPU_0, the sending
    one and the receiving one: each moves one message through that chip's
    interconnect, so the share cannot pass 100."""
    ici = manifest.load_module("layers", "lane_step_ici_pct.py").read
    calls, nbytes = 400, 1048576
    start = T_OPEN + np.arange(2 * calls, dtype=np.int64) * 1_000_000
    lane = xplane.Events(["jit_device_link_lane(7)"] * 2 * calls, start, start + 12_000)
    run = hand_made_run({**UNARY, "device_link_lane_bytes": 2 * calls * nbytes})
    none = xplane.Events([], [], [])
    run.devices = {"/device:TPU:0": {"steps": lane, "ops": none},
                   "/device:TPU:1": {"steps": lane, "ops": none}}
    run.cell = types.SimpleNamespace(config={})  # no prefill_device: TPU_0
    share = 100.0 * (2 * calls * nbytes / (2 * calls * 12e-6)) / (1600e9 / 8)
    assert ici(run) == pytest.approx(share) and 0 < share < 100


# -- PR 51: the lock probe's readers and the gap lines ------------------------


@pytest.mark.parametrize(
    "metric", ["lock_wait_us", "lock_busy_pct", "lock_forced_pct",
               "machine_late_us", "stall_ms"])
def test_lock_reader_gives_none_in_a_window_without_a_tick(metric):
    """A program that has the probe's names and no probe (no native
    library) counts nothing: no number, and no 0 that reads as a finding."""
    silent = {
        **LOCK, "device_transport_lock_probes": 0, "device_transport_lock_busy": 0,
        "device_transport_lock_forced": 0, "device_transport_lock_stall_us": 0,
        "device_transport_lock_wait_us": recorder(0, 0.0),
        "device_transport_machine_late_us": recorder(0, 0.0),
    }
    read = manifest.load_module("layers", metric + ".py").read
    assert read(hand_made_run(silent)) is None


def test_the_two_cpu_gains_add_up_to_the_process():
    cores = [
        manifest.load_module("layers", f"host_cpu_{kind}_threads_cores.py").read(
            hand_made_run({**LOCK, "device_transport_process_cpu_us": 40e6}))
        for kind in ("python", "other")
    ]
    whole = manifest.load_module("layers", "host_cpu_cores.py").read(
        hand_made_run({"device_transport_process_cpu_us": 40e6}))
    assert sum(cores) == pytest.approx(whole)


def lock_ticks(*ticks):
    """``(due, woken, running)`` arrays from ``(due, late, wait)`` triples."""
    due = np.array([t[0] for t in ticks], np.int64)
    woken = due + np.array([t[1] for t in ticks], np.int64)
    return due, woken, woken + np.array([t[2] for t in ticks], np.int64)


def test_the_probe_line_of_a_gap_says_who_had_the_lock():
    from benchmark import timeline_lock

    ms = 1_000_000
    # five gaps, the longest first: held, free, none inside, a stall, late
    starts = T_OPEN + np.array([0, 100, 200, 300, 600], np.int64) * ms
    gaps = (starts, starts + np.array([50, 40, 4, 30, 20], np.int64) * ms)
    busy_ns = 100_000
    ticks = lock_ticks(
        *[(T_OPEN + at * ms, 50_000, 5 * ms) for at in (5, 20, 35)],  # waited
        *[(T_OPEN + at * ms, 60_000, 9_000) for at in (105, 118, 131)],  # prompt
        (T_OPEN + 301 * ms, 40_000, 150 * ms),  # a stall across the fourth
        (T_OPEN + 601 * ms, 15 * ms, 8_000),  # the machine woke it 15 ms late
    )
    stalls = [(int(ticks[1][6]), 150 * ms, "interpreter lock stall: thread 'gc'")]
    lines = timeline_lock.describe_gaps(gaps, ticks, stalls, busy_ns, T_OPEN)
    assert len(lines) == 5
    held, free, stalled, late, empty = lines
    assert held.startswith("lock in gap 0.050000 s at +0.000 s: 3 ticks,")
    assert "longest wait 5000.0 us" in held and held.endswith("lock held throughout")
    assert "3 ticks, longest wait 9.0 us, longest lateness 60.0 us" in free
    assert "lock free" in free and "look in the runtime" in free
    assert "1 ticks" in stalled and stalled.endswith(
        "lock held throughout; interpreter lock stall: thread 'gc'")
    assert late.endswith("machine late") and "longest lateness 15000.0 us" in late
    assert empty == "lock in gap 0.004000 s at +0.200 s: no tick inside"
    # of two waiting and one prompt tick the line counts, and names no stall
    mixed = timeline_lock.describe_gaps(
        (starts[:1], starts[:1] + 50 * ms),
        lock_ticks((T_OPEN + 5 * ms, 0, 5 * ms), (T_OPEN + 20 * ms, 0, 5 * ms),
                   (T_OPEN + 35 * ms, 0, 9_000)),
        [], busy_ns, T_OPEN)
    assert mixed == [
        "lock in gap 0.050000 s at +0.000 s: 3 ticks, longest wait 5000.0 us, "
        "longest lateness 0.0 us: lock held at 2 of 3 ticks"]


def test_the_windows_line_differences_the_readings_nearest_its_edges():
    from benchmark import timeline_lock

    s = 1_000_000_000
    readings = [
        (T_OPEN - 3 * s, {"worker": (1 * s, 0, 4)}),
        (T_OPEN + s // 10, {"worker": (2 * s, 1 * s, 4), "gone": (5 * s, 0, 1)}),
        (T_OPEN + 10 * s, {"worker": (9 * s, 2 * s, 4)}),
        (T_CLOSE - s // 10, {"worker": (22 * s, 3 * s, 4), "tpu": (2 * s, 0, 9),
                             "gone": (1 * s, 0, 1)}),
        (T_CLOSE + 4 * s, {"worker": (30 * s, 3 * s, 4)}),
    ]
    line = timeline_lock.describe_threads(readings, T_OPEN, T_CLOSE)
    # 20 s of CPU and 2 s of waiting over 19.8 s; a name born in the window
    # counts from nothing, one whose tasks ended counts for nothing
    assert line == (
        "processors by thread over 19.80 s: worker 1.010 (runq 9.1%), "
        "tpu 0.101 (runq 0.0%); in all 1.111")
    assert timeline_lock.describe_threads(readings[:1], T_OPEN, T_CLOSE) is None


def test_the_lock_report_is_silent_on_a_run_without_a_device_plane_or_a_probe(capsys):
    """The parent's tree under these files, and a rehearsal: nothing to
    read prints nothing and returns ``None``, once."""
    from benchmark import timeline_lock

    run = hand_made_run(dict(LOCK))
    run.devices = {}
    assert timeline_lock.report(run) is None and run.lock_report is None
    assert capsys.readouterr().out == ""
