"""The four-chip expert step's cell (PR 54) rehearsed on the CPU at the
configuration's ``rehearsal`` size: ``benchmark/run.py`` end to end through
three ``Server(device_index=i)``, three ``Channel(transport="tpu")``, the
unary tensor call and ``DeviceEndpoint.server_handler``; correct as it
stands, and not correct under each of the deployment's controls."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "expert_exchange_ep32_n8192_c4"


def rehearse(*more):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 54), "--seconds", "1", "--rehearse-on-cpu",
         *more],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses_correct(trace):
    result, lines = rehearse("--trace", trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["compilations_in_window"] == 0
    assert any(line.startswith("REHEARSAL unit") for line in lines)
    assert any(line.startswith("pool: 2 micro-batches") for line in lines)
    assert not any("NOT HELD" in line for line in lines)
    for check in (
        "answers_outside_tolerance: 0 ", "tokens_sent_and_not_served: 0 ",
        "token_expert_pairs_sent_and_not_served: 0 ",
        "calls_into_the_endpoints_without_a_device_operand: 0 ",
        "device_operand_fallbacks: 0 ", "malformed_operands_of_6_not_failed_EREQUEST: 0 ",
        "answers_outside_after_the_malformed: 0 ", "exchange_distinct_rank_devices: 3 ",
        "exchange_geometry: ppermute ",
    ):
        assert any(line.startswith("CHECK " + check) for line in lines), check


# what each control breaks first: the check that must say NOT HELD
CONTROLS = {
    "flip_bit": "answers_outside_tolerance",
    "stale": "answers_outside_tolerance",
    "drop_tokens": "token_expert_pairs_sent_and_not_served",
    "wrong_layer": "answers_outside_tolerance",
    "low_precision": "answers_outside_tolerance",
    "swap": "answers_outside_tolerance",
    "host_bytes": "device_operand_fallbacks",
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_broken_guarantee_comes_out_not_correct(control):
    result, lines = rehearse("--trace", "0", "--control", control)
    assert result["correct"] is False
    held = [line for line in lines if line.startswith("CHECK " + CONTROLS[control])]
    assert held and "NOT HELD" in held[0]
    if control == "host_bytes":  # the A/B: the same calls, guarantee (3) alone
        assert result["failed"] == 0
        assert any(line.startswith("CHECK answers_outside_tolerance: 0 ")
                   for line in lines)
        assert any(line.startswith("CHECK payload_bytes_on_the_byte_stream")
                   and "NOT HELD" in line for line in lines)
    else:
        assert result["failed"] > 0
