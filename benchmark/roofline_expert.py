"""What the expert shard's step has to move and to multiply, from what it
served. Kept with the benchmark so that no PR that claims a gain can
change the count.

The sizes are DeepSeek-V3's published ones (``configs/
expert_shard_dsv3_ep32.json``): an expert is three matrices of 7,168 x
2,048 in bf16.

**Least bytes** of the steps of a window: an expert's weights once for
every distinct (layer, expert) that got a token in a dispatch
(``device_transport_expert_weight_sets``: rows of one dispatch that name
one layer could share the read, whether the program does or not), and a
token's row read and its answer's row written
(``device_transport_expert_tokens``). What the program moves besides (the
request rows' padding to their bucket, the frames it builds and sums, the
gate weights, a layer's weights read again for a second row of that layer)
is what a share under 100% shows.

**Useful operations**: a multiply and an add for every weight of an expert,
for every (token, expert) pair the router made
(``device_transport_expert_pairs``). Rows that the kernel pads a block of
tokens with do no useful work and are not counted, so the share of the
MXU's peak is what the traffic's tokens an expert allow, far under 100% at
~8 tokens an expert a row."""

HIDDEN, INTERMEDIATE = 7168, 2048
EXPERT_BYTES = 2 * 3 * HIDDEN * INTERMEDIATE  # 88,080,384 B in bf16
TOKEN_BYTES = 2 * HIDDEN  # a row of bf16, in or out
PAIR_FLOPS = 2 * 3 * HIDDEN * INTERMEDIATE  # 88,080,384 a (token, expert)

TOKENS = "device_transport_expert_tokens"
PAIRS = "device_transport_expert_pairs"
WEIGHT_SETS = "device_transport_expert_weight_sets"


def step_bytes(weight_sets: int, tokens: int) -> int:
    return weight_sets * EXPERT_BYTES + 2 * tokens * TOKEN_BYTES


def step_flops(pairs: int) -> int:
    return pairs * PAIR_FLOPS
