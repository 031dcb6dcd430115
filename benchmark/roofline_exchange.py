"""What the four-chip expert step has to move and to multiply, from what it
served. Kept with the benchmark so that no PR that claims a gain can change
the count. The counts are of the least work, whatever implements it.

The sizes are DeepSeek-V3's published ones (``configs/
expert_exchange_dsv3_ep32.json``): an expert is three matrices of 7,168 x
2,048 in bf16; a row of a tensor operand is a token's 7,168 bf16 and its 8
float32 gate weights, a row of the answer the 7,168 bf16.

**A rank's step** (``jit_step_tensor`` on the ranks' chips). Least bytes: an
expert's weights once for every distinct (layer, expert) that got a token in
a program call (``device_transport_expert_weight_sets``: a tensor call is a
program call of its own), a token's operand row read and its answer row
written (``device_transport_expert_tokens``). The padding of the operand to
its capacity, the sort, the gathered copy of the pairs' rows, the float32
rows added back and an expert read again for a second tile are what a share
under 100% shows. Useful operations: a multiply and an add for every weight
of an expert for every (token, expert) pair
(``device_transport_expert_pairs``); rows that pad an expert's last tile do
no useful work.

**The source's gather and combine** (``jit_expert_exchange_gather``,
``jit_expert_exchange_combine`` on the source's chip). Least bytes: the gather
reads a token sent once and writes its operand row; the combine reads a
token's partial sum once a rank it came from and writes the combined
micro-batch whole (``device_transport_expert_exchange_tokens_sent``, the layer
calls, the micro-batch's tokens).

**The lanes** (``jit_device_link_lane`` on the source's chip): every byte of
``device_link_lane_bytes``, both directions over the three links, enters or
leaves the source's chip once, over its interconnect; the chip's whole 1,600
Gbit/s is the peak (``roofline_lane``'s count for a chip that talks to
three), so the share reads low and cannot pass 100.

Device time is that of the programs' own executions, told by their names in
the trace's step line (``roofline_lane.program_time``)."""

HIDDEN, INTERMEDIATE, HELD = 7168, 2048, 8
EXPERT_BYTES = 2 * 3 * HIDDEN * INTERMEDIATE  # 88,080,384 B in bf16
OPERAND_ROW_BYTES = 2 * HIDDEN + 4 * HELD  # 14,368: a token and its gate weights
ANSWER_ROW_BYTES = 2 * HIDDEN  # 14,336
PAIR_FLOPS = 2 * 3 * HIDDEN * INTERMEDIATE  # 88,080,384 a (token, expert)

STEP_PROGRAM = "step_tensor"
GATHER_PROGRAM = "expert_exchange_gather"
COMBINE_PROGRAM = "expert_exchange_combine"

TOKENS = "device_transport_expert_tokens"
PAIRS = "device_transport_expert_pairs"
WEIGHT_SETS = "device_transport_expert_weight_sets"
TOKENS_SENT = "device_transport_expert_exchange_tokens_sent"
CALLS = "device_transport_expert_exchange_call_us"


def step_bytes(weight_sets: int, tokens: int) -> int:
    return weight_sets * EXPERT_BYTES + tokens * (OPERAND_ROW_BYTES + ANSWER_ROW_BYTES)


def step_flops(pairs: int) -> int:
    return pairs * PAIR_FLOPS


def source_bytes(tokens_sent: int, calls: int, micro_batch_tokens: int) -> int:
    """Gather and combine together, over ``calls`` layer calls."""
    gather = tokens_sent * (ANSWER_ROW_BYTES + OPERAND_ROW_BYTES)
    combine = tokens_sent * ANSWER_ROW_BYTES + calls * micro_batch_tokens * ANSWER_ROW_BYTES
    return gather + combine


SOURCE_PLANE = "/device:TPU:0"


def step_time(run) -> tuple:
    """``(executions, device ns)`` of the ranks' tensor step inside the
    window, over every device plane but the source's."""
    from benchmark import roofline_lane

    executions = total_ns = 0
    for plane in run.devices:
        if plane != SOURCE_PLANE:
            n, ns = roofline_lane.program_time(
                run.devices, run.t_open, run.t_close, STEP_PROGRAM, plane)
            executions, total_ns = executions + n, total_ns + ns
    return executions, total_ns
