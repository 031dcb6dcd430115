"""The four-chip expert step (PR 54) on the CPU at a small size (hidden 64,
intermediate 32, 32 experts in 4 groups, top-4, 4 ranks of 8, 3 layers; the
grouped kernel in Pallas's interpreter, tiles of 16 pairs; the forced host
devices): the rank's step for a tensor operand against the host-rows step
and the plain reference for any split of the tokens over the experts, the
shares against the uncut layer, ``DeviceEndpoint`` with a device operand
(credit window, malformed operands, a raising step, no fallback), a layer
call over three in-process servers against the reference, and the new
readers."""

import functools
import json
import os
import sys
import threading
import types

import ml_dtypes
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import manifest, roofline_exchange, xplane  # noqa: E402
from incubator_brpc_tpu.models import expert_exchange, expert_shard  # noqa: E402
from incubator_brpc_tpu.models.expert_shard import FFN, ExpertShardService  # noqa: E402
from incubator_brpc_tpu.ops import framing  # noqa: E402
from incubator_brpc_tpu.transport import device  # noqa: E402
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402
from incubator_brpc_tpu.utils.status import ErrorCode  # noqa: E402

ref = manifest.load_module("references", "moe_expert_exchange.py")
share = ref.share

MOE = ref.Moe(hidden_size=64, moe_intermediate_size=32, n_routed_experts=32,
              num_experts_per_tok=4, n_group=4, topk_group=2)
HIDDEN, HELD, LAYERS, EP, SEED = 64, 8, 3, 4, 54
TW, WIDE = HIDDEN // 2, HIDDEN // 2 + HELD
# bf16's rounding of the gated intermediate and of the answer at a hidden of
# 64 (test_expert_shard.py's limits; 0.004 and 0.017 read here)
REL_L2, OVER_RMS = 0.01, 0.05


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of 16 pairs: a dozen tokens an expert already take several."""
    monkeypatch.setattr(expert_shard, "TILE", 16)


def service(rank=0, cls=ExpertShardService):
    return cls(HIDDEN, 32, HELD, LAYERS, seed=SEED, first_expert=rank * HELD)


@pytest.fixture(scope="module")
def shard():
    s = service()
    return s, s.init_state(jax.devices()[0])


def tokens(n, salt=0):
    return share.micro_batch(b"tests/test_expert_exchange", salt, 0, n, HIDDEN)


def split(n, kind, rng):
    """Gate weights ``[n, HELD]`` for a forced split of ``n`` tokens."""
    w = np.zeros((n, HELD), np.float32)
    if kind == "evenly":
        w[np.arange(n), np.arange(n) % HELD] = 1.0 + rng.random(n)
    elif kind == "all_on_one":
        w[:, 5] = 1.0 + rng.random(n)
    elif kind == "none_on_some":  # two experts each, of the first three only
        w[np.arange(n), np.arange(n) % 3] = 0.5 + rng.random(n)
        w[np.arange(n), (np.arange(n) + 1) % 3] = 0.5 + rng.random(n)
    elif kind == "every_expert":  # more pairs than one pass of the step takes
        w[:] = 0.25 + rng.random((n, HELD))
    else:  # "router": a token's own top experts among the eight
        w = rng.random((n, HELD)).astype(np.float32)
        w[w < 0.6] = 0
        w[np.arange(n), rng.integers(0, HELD, n)] = 0.7
    return w.astype(np.float32)


def words_of(rows):
    """bf16 rows two a word, the even column low, by numpy."""
    return np.ascontiguousarray(np.asarray(rows).astype(ml_dtypes.bfloat16)).view(np.uint32)


def operand_of(x, w, rows):
    """The tensor operand for token rows ``x`` and weights ``w``, by numpy."""
    n, tw = x.shape[0], x.shape[1] // 2
    out = np.zeros((rows, tw + HELD), np.uint32)
    out[:n, :tw] = words_of(x)
    out[:n, tw:] = np.asarray(w, np.float32).view(np.uint32)
    return out


def head(layer, n, hidden=HIDDEN, held=HELD):
    row = np.zeros(device.MIN_BUCKET_WORDS, np.uint32)
    row[:4] = [layer, n, hidden, held]
    return row


def answer_rows(answer, n):
    """``float32[n, hidden]`` of a tensor answer's first ``n`` rows."""
    words = np.ascontiguousarray(np.asarray(answer)[:n, :-HELD])
    return words.view(ml_dtypes.bfloat16).astype(np.float32)


def want(layer, x, w, rank=0):
    dense = np.zeros((x.shape[0], MOE.n_routed_experts), np.float32)
    mine = share.held(MOE, rank, EP)
    dense[:, mine.start : mine.stop] = w
    return np.asarray(share.routed(MOE, SEED, layer, x, jnp.asarray(dense), mine))


def within(answer, wanted):
    rel_l2, over_rms = share.outside(answer, wanted)
    return rel_l2 <= REL_L2 and over_rms <= OVER_RMS


# -- the rank's step for a tensor ------------------------------------------------


@pytest.mark.parametrize(
    "kind", ["evenly", "all_on_one", "none_on_some", "router", "every_expert"])
@pytest.mark.parametrize("n,rows", [(1, 16), (21, 32), (48, 48)])
def test_tensor_step_equals_host_rows_step_equals_reference(shard, kind, n, rows):
    s, state = shard
    rng = np.random.default_rng([n, len(kind)])
    x, w, layer = tokens(n, salt=n), split(n, kind, rng), n % LAYERS
    _, answer, frame = jax.jit(s.dispatch_tensor)(
        state, head(layer, n), operand_of(x, w, rows), np.uint32(9), np.uint32(FFN))
    frame, answer = np.asarray(frame), np.asarray(answer)
    assert frame[framing.HEADER_WORDS - 1] == 0 and frame[3] == 9
    assert answer.shape == (rows, WIDE) and answer.dtype == np.uint32
    assert not answer[n:].any() and not answer[:, TW:].any()
    got = answer_rows(answer, n)
    assert within(got, want(layer, x, w))
    # the tally: tokens, pairs, the layer, the experts that got a token
    mask = sum(1 << e for e in range(HELD) if (w[:, e] != 0).any())
    assert frame[-4:].tolist() == [n, int((w != 0).sum()), layer, mask]
    # the step over host rows (PR 48's) answers the same tokens alike
    request = share.pack_request(layer, x, w)
    width = device._bucket_words(len(request) // 4)
    row = np.zeros(width, np.uint32)
    row[: len(request) // 4] = np.frombuffer(request, np.uint32)
    _, frames = jax.jit(s.step)(state, row[None], np.uint32([9]), np.uint32([FFN]))
    start = framing.HEADER_WORDS
    theirs = share.unpack_answer(
        np.asarray(frames)[0, start : start + n * TW].tobytes(), HIDDEN)
    # both round one float32 sum to bf16; the sums differ in their order
    assert np.abs(got - theirs).max() <= 2 ** -6 * max(1.0, np.abs(theirs).max())
    assert np.asarray(frames)[0, -4:].tolist() == frame[-4:].tolist()


MALFORMED = {
    "another_shape": lambda op, n: (head(0, n), op[:, :-1]),
    "another_dtype": lambda op, n: (head(0, n), op.astype(np.int32)),
    "t_over_the_capacity": lambda op, n: (head(0, op.shape[0] + 1), op),
    "t_not_the_operands": lambda op, n: (head(0, n - 1), op),
    "layer_out_of_range": lambda op, n: (head(LAYERS, n), op),
    "another_hidden": lambda op, n: (head(0, n, hidden=HIDDEN + 2), op),
    "a_weight_not_finite": lambda op, n: (
        head(0, n), _with(op, (0, WIDE - 1), 0x7FC00000)),
    "a_token_no_weight_names": lambda op, n: (
        head(0, n), _with(op, (slice(0, 1), slice(TW, WIDE)), 0)),
    "words_after_the_head": lambda op, n: (_with(head(0, n), 5, 1), op),
}


def _with(array, at, value):
    out = array.copy()
    out[at] = value
    return out


@pytest.mark.parametrize("kind", sorted(MALFORMED))
def test_a_malformed_operand_is_a_bad_request_without_a_product(shard, kind):
    s, state = shard
    n = 20
    x, w = tokens(n, salt=3), split(n, "router", np.random.default_rng(3))
    row, operand = MALFORMED[kind](operand_of(x, w, 32), n)
    _, answer, frame = jax.jit(s.dispatch_tensor)(
        state, row, operand, np.uint32(4), np.uint32(FFN))
    frame = np.asarray(frame)
    assert frame[framing.HEADER_WORDS - 1] == expert_shard.EREQUEST
    assert not np.asarray(answer).any() and not frame[-4:].any()


def test_an_unknown_method_and_a_pad_row_are_no_method(shard):
    s, state = shard
    x, w = tokens(4), split(4, "evenly", np.random.default_rng(0))
    for row, mid in ((head(0, 4), 7), (np.zeros(64, np.uint32), 0)):
        _, answer, frame = jax.jit(s.dispatch_tensor)(
            state, row, operand_of(x, w, 16), np.uint32(4), np.uint32(mid))
        assert np.asarray(frame)[framing.HEADER_WORDS - 1] == expert_shard.ENOMETHOD
        assert not np.asarray(answer).any()


# -- the source's plan, gather and combine ---------------------------------------


def routed(x, layer):
    return np.asarray(ref.gate_weights(MOE, SEED, layer, x))


FIRSTS = [0, HELD, 2 * HELD]
# (tokens, hidden): one the kernels' blocks divide (128 tokens, 128 operand
# rows, 128 words), and one that meets none of them (the plain programs)
SHAPES = {"in_blocks": (256, 256), "off_blocks": (48, 64)}


def forced(n, ranks_of):
    """Dense gate weights that send token ``t`` to the ranks ``ranks_of(t)``."""
    weights = np.zeros((n, 3 * HELD), np.float32)
    for t in range(n):
        for r in ranks_of(t):
            weights[t, r * HELD + t % HELD] = 0.25 + (t % 7) / 8
    return weights


def every_number_of_ranks(t):
    return [(), (t % 3,), (t % 3, (t + 1) % 3), (0, 1, 2)][(t // 3) % 4]


def answers_from(rng, plan, capacity, hidden, scale=lambda r: 1.0):
    """An answer a rank: its tokens' rows random bf16; the rows past them and
    the gates' words whatever a rank may leave there (any bits, NaNs among
    them), which no sum may take up."""
    answers = []
    for r, sent in enumerate(plan.tokens):
        answer = rng.integers(0, 1 << 32, (capacity, hidden // 2 + HELD), dtype=np.uint32)
        rows = (rng.standard_normal((sent, hidden)) * scale(r)).astype(ml_dtypes.bfloat16)
        answer[:sent, : hidden // 2] = words_of(rows)
        answers.append(answer)
    return answers


# a case: (rng, n, hidden) -> x, the dense gate weights, whether operands
# have pad rows, and what makes the answers from the plan (None: random)


def case_router(rng, n, hidden):
    x = share.micro_batch(b"tests/test_expert_exchange", 11, 0, n, hidden)
    moe = ref.Moe(hidden_size=hidden, moe_intermediate_size=32, n_routed_experts=32,
                  num_experts_per_tok=4, n_group=4, topk_group=2)
    return x, np.asarray(ref.gate_weights(moe, SEED, 1, x)), True, None


def case_no_pad_rows(rng, n, hidden):  # every rank is sent exactly a capacity of tokens
    return rng.standard_normal((n, hidden)), forced(
        n, lambda t: [r for r in range(3) if (t + r) % 2]), False, None


def case_pad_rows_and_a_rank_sent_nothing(rng, n, hidden):
    return rng.standard_normal((n, hidden)), forced(
        n, lambda t: [r for r in (0, 2) if t % (r + 2) == 0]), True, None


def case_tokens_sent_to_0_1_2_and_3_ranks(rng, n, hidden):
    return rng.standard_normal((n, hidden)), forced(n, every_number_of_ranks), True, None


def case_a_sum_that_depends_on_the_order(rng, n, hidden):
    """2**30 + 1 - 2**30 is 0 in the order of the ranks and 1 in any other;
    the random rows' scales are 2**12 apart, so theirs differ too."""

    def answers(plan, capacity):
        out = answers_from(rng, plan, capacity, hidden, lambda r: 4096.0 ** (1 - r))
        index = np.asarray(plan.index)
        for value, r in zip((2.0**30, 1.0, -(2.0**30)), range(3)):
            row = int(np.nonzero(index[r] == 9)[0][0])  # token 9 goes to all three
            out[r][row, : hidden // 2] = words_of(
                np.full((1, hidden), value, ml_dtypes.bfloat16))
        return out

    return rng.standard_normal((n, hidden)), forced(n, every_number_of_ranks), True, answers


def case_negative_zero_inf_and_nan_in_x(rng, n, hidden):
    x = rng.standard_normal((n, hidden)).astype(np.float32)
    x[3, 10], x[3, 11], x[5, 0], x[5, hidden - 1] = -0.0, np.inf, np.nan, -np.inf
    x[12, :] = -0.0
    return x, forced(n, lambda t: (0, 1, 2)), True, None


def case_negative_zero_inf_and_nan_in_an_answer(rng, n, hidden):
    def answers(plan, capacity):
        out = answers_from(rng, plan, capacity, hidden)
        for r, (row, column, value) in enumerate(
                [(0, 7, np.inf), (2, 20, np.nan), (1, 33, -np.inf)]):
            rows = out[r][: plan.tokens[r], : hidden // 2].view(ml_dtypes.bfloat16)
            rows[row, column] = value
        # token 4 goes to rank 1 alone: 0.0 + -0.0 is 0.0, as in the parent's sum
        row = int(np.nonzero(np.asarray(plan.index)[1] == 4)[0][0])
        out[1][row, : hidden // 2] = words_of(np.full((1, hidden), -0.0, ml_dtypes.bfloat16))
        return out

    return rng.standard_normal((n, hidden)), forced(n, every_number_of_ranks), True, answers


CASES = {
    name[len("case_"):]: case for name, case in sorted(globals().items())
    if name.startswith("case_")}
# (case, tokens, hidden): every case on both sides of the selection, and one
# of several blocks of the sum, steps of the pack and chunks of 128 words
RUNS = [(case, *SHAPES[shape]) for case in sorted(CASES) for shape in sorted(SHAPES)]
RUNS.append(("tokens_sent_to_0_1_2_and_3_ranks", 384, 512))


# the module's gather off the TPU, as ``ExpertExchange`` jits it there
GATHER = jax.jit(functools.partial(expert_exchange.gather, interpret=True))


def same_bits(got, want):
    """``got`` is ``want`` bit for bit, a NaN for a NaN."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    if got.dtype == np.uint32:
        return np.testing.assert_array_equal(got, want)
    nan = np.isnan(want.astype(np.float32))
    assert np.array_equal(np.isnan(got.astype(np.float32)), nan)
    np.testing.assert_array_equal(
        np.where(nan, 0, got.view(np.uint16)), np.where(nan, 0, want.view(np.uint16)))


@pytest.mark.parametrize("case,n,hidden", RUNS, ids=lambda v: str(v))
def test_plan_gather_and_combine_are_numpys(case, n, hidden):
    """The two programs against numpy and against the plain formulation
    (the parent's programs), bit for bit (a NaN for a NaN), on shapes the
    kernels' blocks divide and on shapes they do not."""
    rng = np.random.default_rng(55)
    x, weights, pad_rows, answers = CASES[case](rng, n, hidden)
    x = np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    most = max(int((weights[:, f : f + HELD] != 0).any(axis=1).sum()) for f in FIRSTS)
    in_blocks = n % 128 == 0
    capacity = most + 7 * pad_rows
    if in_blocks:
        capacity = -(-capacity // 128) * 128
        assert (capacity > most) == pad_rows
    assert expert_exchange._in_blocks(3, n, hidden, capacity, HELD) == in_blocks
    plan = expert_exchange.plan_layer(weights, FIRSTS, HELD, capacity, jax.devices()[0])
    operands = GATHER(jnp.asarray(x), plan.index, plan.gates)
    plain = jax.jit(expert_exchange.gather_plain)(jnp.asarray(x), plan.index, plan.gates)
    sent = []
    for r, first in enumerate(FIRSTS):
        sub = weights[:, first : first + HELD]
        rows = np.nonzero((sub != 0).any(axis=1))[0]
        sent.append(rows)
        assert plan.tokens[r] == len(rows) and plan.pairs[r] == int((sub != 0).sum())
        index = np.asarray(plan.index[r])
        np.testing.assert_array_equal(index[: len(rows)], rows)
        assert (index[len(rows) :] >= n).all()  # pad rows: no token
        inverse = np.asarray(plan.inverse[r])
        np.testing.assert_array_equal(inverse[rows], np.arange(len(rows)))
        assert (np.delete(inverse, rows) == capacity).all()
        # the token's own bits two a word (a -0.0 its sign, an inf or a NaN
        # its own half of its own word), its gate weights, zero rows after
        same_bits(operands[r], operand_of(x[rows], sub[rows], capacity))
        same_bits(plain[r], np.asarray(operands[r]))
    # the combine: ((0 + a0) + a1) + a2 in float32 at each token's place, a
    # rank that was not sent the token adding 0.0, rounded to bf16 once
    answers = (answers or (lambda plan, capacity: answers_from(rng, plan, capacity, hidden)))(
        plan, capacity)
    y = np.asarray(jax.jit(
        lambda index, inverse, *a: expert_exchange.combine(
            n, hidden, index, inverse, *a, interpret=True)
    )(plan.index, plan.inverse, *map(jnp.asarray, answers)))
    expect = np.zeros((n, hidden), np.float32)
    with np.errstate(invalid="ignore"):
        for rows, answer in zip(sent, answers):
            part = np.zeros((n, hidden), np.float32)
            part[rows] = answer_rows(answer, len(rows))
            expect = expect + part
    same_bits(y, expect.astype(ml_dtypes.bfloat16))
    same_bits(jax.jit(
        lambda inverse, *a: expert_exchange.combine_plain(n, hidden, inverse, *a)
    )(plan.inverse, *map(jnp.asarray, answers)), y)
    if case == "a_sum_that_depends_on_the_order":
        assert (y[9].astype(np.float32) == 0).all()
    if case == "negative_zero_inf_and_nan_in_an_answer":
        assert (~np.isfinite(y.astype(np.float32))).sum() == 3
        assert not y[4].view(np.uint16).any()  # +0.0: the sum began at 0.0
    if case == "negative_zero_inf_and_nan_in_x":
        assert (np.asarray(operands[0])[12, : hidden // 2] == 0x80008000).all()
    if case == "tokens_sent_to_0_1_2_and_3_ranks":
        assert sorted({sum(t in rows for rows in sent) for t in range(n)}) == [0, 1, 2, 3]
        assert not y[[t for t in range(n) if not every_number_of_ranks(t)]].any()
    if case == "pad_rows_and_a_rank_sent_nothing":
        assert plan.tokens[1] == 0
    if case == "no_pad_rows":
        assert plan.tokens == (capacity,) * 3


def test_a_rank_sent_more_than_an_operand_holds_is_refused():
    x = tokens(40, salt=11)
    with pytest.raises(expert_exchange.CapacityExceeded, match="rank 0 would be sent"):
        expert_exchange.plan_layer(routed(x, 1), FIRSTS, HELD, 4, jax.devices()[0])


@pytest.mark.parametrize("tokens_,hidden,capacity,ranks,served", [
    (8192, 7168, 2048, 3, True),  # the cell's
    (8192, 7168, 2000, 3, False), (8000, 7168, 2048, 3, False),
    (8192, 7104, 2048, 3, False),  # 128 words do not divide a row's 3,552
    (8192, 7168, 2048, 7, False),  # seven ranks' rings are more than the chip's VMEM
    (8192, 16384, 2048, 3, False)])
def test_the_shapes_select_the_programs_and_none_is_refused(
        tokens_, hidden, capacity, ranks, served):
    """PR 55's hand-in raised ``ValueError`` on the chip for shapes the
    kernels' blocks do not divide; the plain programs serve them (compiled
    for a v5e in ``tests/test_kv_page_pool.py``)."""
    exchange = expert_exchange.ExpertExchange(
        [None] * ranks, [r * HELD for r in range(ranks)], HELD, hidden, tokens_,
        capacity, jax.devices()[0])
    assert exchange.operand_shape == (capacity, hidden // 2 + HELD)
    assert expert_exchange._in_blocks(ranks, tokens_, hidden, capacity, HELD) == served


def test_the_programs_keep_the_names_the_roofline_reads():
    """``benchmark/roofline_exchange.py`` finds the device time of the
    source's gather and combine by their programs' names in the trace
    (``GATHER_PROGRAM``, ``COMBINE_PROGRAM`` after ``jit_``), and
    ``exchange_combine_hbm_pct`` is that time against the least bytes: all
    device work of either stays inside the program of that name."""
    n, hidden, capacity = 128, 256, 128  # the kernels' shapes
    exchange = expert_exchange.ExpertExchange(
        [None] * 3, FIRSTS, HELD, hidden, n, capacity, jax.devices()[0])
    plan = exchange.plan(forced(n, every_number_of_ranks))
    x = jnp.zeros((n, hidden), jnp.bfloat16)
    gather = exchange._gather.lower(x, plan.index, plan.gates)
    operands = jax.eval_shape(exchange._gather, x, plan.index, plan.gates)
    combine = exchange._combine.lower(plan.index, plan.inverse, *operands)
    for lowered, name in ((gather, roofline_exchange.GATHER_PROGRAM),
                          (combine, roofline_exchange.COMBINE_PROGRAM)):
        assert f"module @jit_{name} " in lowered.as_text()
    assert roofline_exchange.GATHER_PROGRAM == "expert_exchange_gather"
    assert roofline_exchange.COMBINE_PROGRAM == "expert_exchange_combine"


def test_the_shares_add_up_to_the_uncut_layer():
    """Ranks 0-2 through the program (gather, the tensor step of each rank,
    combine), plus the fourth rank's share and the shared expert from the
    reference, is the reference's whole layer."""
    n, capacity, layer = 24, 24, 2
    x = tokens(n, salt=5)
    weights = routed(x, layer)
    plan = expert_exchange.plan_layer(
        weights, [0, HELD, 2 * HELD], HELD, capacity, jax.devices()[0])
    operands = GATHER(
        x.astype(jnp.bfloat16), plan.index, plan.gates)
    answers = []
    for rank in range(3):
        s = service(rank)
        _, answer, frame = jax.jit(s.dispatch_tensor)(
            s.init_state(jax.devices()[0]), head(layer, plan.tokens[rank]),
            operands[rank], np.uint32(1), np.uint32(FFN))
        assert np.asarray(frame)[7] == 0
        answers.append(answer)
    combined = np.asarray(
        expert_exchange.combine(
            n, HIDDEN, plan.index, plan.inverse, *answers, interpret=True).astype(jnp.float32))
    reference = np.asarray(
        ref.combined(MOE, SEED, layer, x, jnp.asarray(weights), [0, 1, 2], EP))
    assert within(combined, reference)
    rest = np.asarray(share.share(MOE, SEED, layer, x, 3, EP)) + np.asarray(
        share.shared(MOE, SEED, layer, x))
    whole = np.asarray(share.moe_layer(MOE, SEED, layer, x))
    assert within(combined + rest, whole)
    np.testing.assert_allclose(reference + rest, whole, rtol=1e-4, atol=1e-4)
    # a token none of the three ranks was sent stays zero, here and there
    unsent = ~(weights[:, : 3 * HELD] != 0).any(axis=1)
    assert not combined[unsent].any() and not reference[unsent].any()


# -- DeviceEndpoint with a device operand ----------------------------------------


def good_call(n=20, rows=32, layer=1, salt=7):
    x, w = tokens(n, salt=salt), split(n, "router", np.random.default_rng(salt))
    operand = jax.device_put(operand_of(x, w, rows), jax.devices()[0])
    return head(layer, n)[:4], operand, want(layer, x, w), n


def counters():
    return (device.m_device_operands.get_value(),
            device.m_device_operand_fallbacks.get_value(),
            expert_shard.m_pairs.get_value())


def pairs_counted(least, timeout=5.0):
    """The pairs the service has counted, once ``least`` are (``account``
    runs on the watcher after the callers wake)."""
    import time

    deadline = time.monotonic() + timeout
    while expert_shard.m_pairs.get_value() < least and time.monotonic() < deadline:
        time.sleep(0.01)
    return expert_shard.m_pairs.get_value()


def test_a_device_operand_is_served_where_it_lies_and_keeps_the_window():
    ep = DeviceEndpoint(service=service(), window_size=2, max_batch=2)
    words, operand, wanted, n = good_call()
    before = counters()
    seen, lock = [], threading.Lock()
    watched = ep._tensor_program

    def run(operands, dispatch=None):
        with lock:
            seen.append(ep.inflight)
        assert operands[1] is operand  # no copy, no device_put
        return watched.run(operands, dispatch)

    ep._tensor_program = types.SimpleNamespace(run=run)
    results = []

    def caller():
        pending = ep.call_words(words, method_id=FFN, operand=operand, timeout=120)
        assert pending.wait(120)
        results.append(pending)

    threads = [threading.Thread(target=caller) for _ in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 5 and max(seen) <= 2 and ep.inflight == 0
    for pending in results:
        assert pending.error_code == 0 and pending.completed()
        answer = pending.response_array
        assert isinstance(answer, jax.Array) and answer.devices() == {ep.device}
        assert within(answer_rows(answer, n), wanted)
        assert pending.dispatch.rows == 1  # a tensor call rides alone
        stages = pending.stages()
        assert set(device.STAGES) - {"copy", "ingress", "plane_callback", "egress"} <= set(stages)
    device.flush_stage_recorders()
    after = counters()
    assert after[0] - before[0] == 5 and after[1] == before[1]
    sent = int((np.asarray(operand)[:, TW:].view(np.float32) != 0).sum())
    # account() read the frames' tallies
    assert pairs_counted(before[2] + 5 * sent) >= before[2] + 5 * sent


def test_call_tensor_through_the_server_handler_answers_an_array():
    ep = DeviceEndpoint(service=service(), window_size=4)
    words, operand, wanted, n = good_call(salt=8)
    cntl = types.SimpleNamespace(
        request_attachment=operand, response_attachment=b"", call_id=5,
        failed=lambda: False)
    failures = []
    cntl.set_failed = lambda code, text: failures.append(code)
    out = ep.server_handler(method_id=FFN)(cntl, words.tobytes())
    assert out == b"" and not failures
    assert isinstance(cntl.response_attachment, jax.Array)
    assert within(answer_rows(cntl.response_attachment, n), wanted)
    # a request without an attachment goes the host-words way, as it did
    cntl.request_attachment = b""
    ep.server_handler(method_id=FFN)(cntl, b"\x00" * 16)
    assert failures == [ErrorCode.EREQUEST]


@pytest.mark.parametrize("kind", sorted(MALFORMED))
def test_the_endpoint_answers_erequest_for_a_malformed_operand(kind):
    ep = DeviceEndpoint(service=service(), window_size=2)
    n = 20
    x, w = tokens(n, salt=3), split(n, "router", np.random.default_rng(3))
    row, operand = MALFORMED[kind](operand_of(x, w, 32), n)
    before = counters()
    code, answer = ep.call_tensor(
        row[:6].tobytes(), jax.device_put(operand, ep.device), method_id=FFN,
        timeout=120)
    assert code == ErrorCode.EREQUEST and answer is None
    words, good, wanted, m = good_call()
    code, answer = ep.call_tensor(words.tobytes(), good, method_id=FFN, timeout=120)
    assert code == 0 and within(answer_rows(answer, m), wanted)
    after = counters()
    assert after[1] == before[1] and ep.inflight == 0
    sent = int((np.asarray(good)[:, TW:].view(np.float32) != 0).sum())
    assert pairs_counted(before[2] + sent) >= before[2] + sent


def test_a_frame_too_long_and_a_service_without_the_step_are_bad_requests():
    ep = DeviceEndpoint(service=service(), window_size=2)
    words, operand, _, _ = good_call()
    long = np.zeros(device.MIN_BUCKET_WORDS + 1, np.uint32)
    pending = ep.call_words(long, method_id=FFN, operand=operand)
    assert pending.error_code == ErrorCode.EREQUEST and ep.inflight == 0
    echo = DeviceEndpoint(window_size=2)  # the tensor echo takes host words only
    pending = echo.call_words(words, method_id=0, operand=operand)
    assert pending.error_code == ErrorCode.EREQUEST and echo.inflight == 0


def test_a_raising_step_fails_its_call_and_loses_nothing():
    class Raises(ExpertShardService):
        armed = False

        def dispatch_tensor(self, state, row, operand, cid_lo, mid):
            if Raises.armed and operand.shape[0] == 48:
                raise RuntimeError("the step raised")
            return super().dispatch_tensor(state, row, operand, cid_lo, mid)

    ep = DeviceEndpoint(service=service(cls=Raises), window_size=2)
    assert ep._state_turn is None  # read-only state: nothing to take in turn
    words, operand, wanted, n = good_call()
    x, w = tokens(n, salt=7), split(n, "router", np.random.default_rng(7))
    other = jax.device_put(operand_of(x, w, 48), ep.device)
    Raises.armed = True
    pending = ep.call_words(words, method_id=FFN, operand=other, timeout=120)
    assert pending.wait(120) and pending.error_code == ErrorCode.EINTERNAL
    assert "the step raised" in repr(pending.error) and ep.inflight == 0
    code, answer = ep.call_tensor(words.tobytes(), operand, method_id=FFN, timeout=120)
    assert code == 0 and within(answer_rows(answer, n), wanted)
    assert all(not m.is_deleted() for m in ep._state)


def test_host_bytes_and_another_devices_array_are_fallbacks_and_counted():
    ep = DeviceEndpoint(service=service(), device=jax.devices()[1], window_size=2)
    words, operand, wanted, n = good_call()  # lies on device 0
    before = counters()
    code, answer = ep.call_tensor(words.tobytes(), operand, method_id=FFN, timeout=120)
    assert code == 0 and answer.devices() == {jax.devices()[1]}
    assert within(answer_rows(answer, n), wanted)
    code, answer = ep.call_tensor(
        words.tobytes(), np.asarray(operand).tobytes(), method_id=FFN, timeout=120)
    assert code == 0 and isinstance(answer, bytes)
    rows = np.frombuffer(answer, np.uint32).reshape(-1, WIDE)
    assert within(answer_rows(rows, n), wanted)
    after = counters()
    assert after[0] == before[0] and after[1] - before[1] == 2


# -- a layer call over three in-process servers -------------------------------------


@pytest.fixture(scope="module")
def unit():
    """Three ranks on devices 1-3 behind ``Server(device_index=i)``, three
    ``Channel(transport="tpu")`` from device 0."""
    from incubator_brpc_tpu.rpc import Channel, ChannelOptions, Controller, Server, ServerOptions

    found = jax.devices()
    servers, channels, endpoints = [], [], []
    for i in (1, 2, 3):
        endpoint = DeviceEndpoint(service=service(i - 1), device=found[i], window_size=2)
        server = Server(ServerOptions(device_index=i))
        server.add_service("experts", {"ffn": endpoint.server_handler(method_id=FFN)})
        assert server.start(0)
        channel = Channel()
        assert channel.init(
            f"127.0.0.1:{server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=60000,
                                   link_controller="single"))
        channel.call_method("experts", "ffn", b"", cntl=Controller(timeout_ms=60000))
        assert channel._device_sock.link.has_lane
        servers.append(server), channels.append(channel), endpoints.append(endpoint)
    yield channels, endpoints, found[0]
    for server in servers:
        server.stop()
    for server in servers:
        server.join(timeout=10)


def test_a_layer_call_over_three_servers_agrees_with_the_reference(unit):
    channels, endpoints, source = unit
    n, capacity = 64, 48
    exchange = expert_exchange.ExpertExchange(
        channels, [0, HELD, 2 * HELD], HELD, HIDDEN, n, capacity, source)
    exchange.warm()
    x = jax.device_put(tokens(n, salt=21), source)
    before = counters()
    sent_before = expert_exchange.m_tokens_sent.get_value()
    for layer in range(LAYERS):
        weights = ref.gate_weights(MOE, SEED, layer, x)
        plan = exchange.plan(weights)
        answer = exchange.call_layer(x.astype(jnp.bfloat16), plan, layer)
        assert not answer.failed(), answer.error_text
        assert answer.y.devices() == {source} and answer.y.dtype == jnp.bfloat16
        for part, endpoint in zip(answer.parts, endpoints):
            assert isinstance(part, jax.Array) and part.devices() == {source}
        wanted = ref.combined(MOE, SEED, layer, x, weights, [0, 1, 2], EP)
        far = np.asarray(ref.outside(answer.y, wanted))
        assert far[0] <= REL_L2 and far[1] <= OVER_RMS
        assert share.outside(
            np.asarray(answer.y.astype(jnp.float32)), np.asarray(wanted)
        ) == pytest.approx(tuple(far), rel=1e-3)
    after = counters()
    assert after[0] - before[0] == 3 * LAYERS and after[1] == before[1]
    expert_exchange.flush_recorders()
    assert expert_exchange.m_tokens_sent.get_value() > sent_before
    assert expert_exchange._recorders["call"].count() >= LAYERS
    # a sub-call that fails fails the layer call, and says which rank
    bad = exchange.call_layer(x.astype(jnp.bfloat16), plan, LAYERS)
    assert bad.failed() and "rank 0" in bad.error_text
    assert all(c.error_code == ErrorCode.EREQUEST for c in bad.controllers)


def test_no_tensor_waits_on_a_rank_for_a_collection(unit):
    """A finished call's controller dies with its last reference
    (``Server._finish`` cuts the closures that named it), and its operand and
    answer with it: on the chip tens of calls' 29 MB tensors waited for the
    cyclic collector and a rank's chip peaked 2.7 GB over its weights
    (PERF.md, PR 54). Nor do the source's controllers keep them."""
    import gc
    import time

    channels, endpoints, source = unit
    n, capacity = 64, 48
    exchange = expert_exchange.ExpertExchange(
        channels, [0, HELD, 2 * HELD], HELD, HIDDEN, n, capacity, source)
    x = jax.device_put(tokens(n, salt=22), source)
    plan = exchange.plan(ref.gate_weights(MOE, SEED, 0, x))
    xb = x.astype(jnp.bfloat16)

    def held(on_source):
        return sum(
            1 for a in jax.live_arrays()
            if a.shape == exchange.operand_shape
            and (source in a.devices()) == on_source)

    for _ in range(2):
        assert not exchange.call_layer(xb, plan, 0).failed()
    gc.collect()
    time.sleep(0.2)
    before = held(False), held(True)
    gc.disable()
    try:
        for _ in range(12):
            assert not exchange.call_layer(xb, plan, 0).failed()
        time.sleep(0.2)
        after = held(False), held(True)
    finally:
        gc.enable()
    # in a cycle: two arrays a call a rank, 72 here, and as many at the source
    assert after[0] - before[0] <= 6 and after[1] - before[1] <= 6, (before, after)


# -- the cell's files and readers ----------------------------------------------------

CELL = "expert_exchange_ep32_n8192_c4"
T_OPEN, T_CLOSE = 1_000_000_000, 21_000_000_000


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def events(name, count, ns):
    start = T_OPEN + np.arange(count, dtype=np.int64) * 10_000_000
    return xplane.Events([name] * count, start, start + ns)


def hand_made_run(counters_):
    nothing = xplane.Events([], [], [])
    source = xplane.Events(
        ["jit_expert_exchange_gather"] * 100 + ["jit_expert_exchange_combine"] * 100
        + ["jit_device_link_lane"] * 500,
        np.concatenate([events("", 100, 0).start, events("", 100, 0).start + 5_000_000,
                        T_OPEN + np.arange(500, dtype=np.int64) * 1_000_000]),
        np.concatenate([events("", 100, 0).start + 300_000,
                        events("", 100, 0).start + 5_000_000 + 700_000,
                        T_OPEN + np.arange(500, dtype=np.int64) * 1_000_000 + 600_000]))
    devices = {"/device:TPU:0": {"steps": source, "ops": nothing}}
    for rank in (1, 2, 3):
        devices[f"/device:TPU:{rank}"] = {
            "steps": events("jit_step_tensor", 100, 2_000_000), "ops": nothing}
    return types.SimpleNamespace(
        counters=counters_, t_open=T_OPEN, t_close=T_CLOSE, devices=devices,
        cell=manifest.Cell(bench(), CELL),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
               "ici_bits_per_s_per_chip": 1600e9})


def test_the_new_readers_read_the_counters_and_none_without_them():
    cell = manifest.Cell(bench(), CELL)
    mine = sorted(m["name"] for m in cell.per_layer if m["name"].startswith("exchange_"))
    assert mine == [
        "exchange_call_us", "exchange_combine_hbm_pct", "exchange_device_operands_pct",
        "exchange_fanout_us", "exchange_lane_ici_pct", "exchange_lane_messages_per_step",
        "exchange_step_hbm_pct", "exchange_step_kernel_us", "exchange_step_mxu_pct"]
    served = {
        "device_transport_expert_tokens": 300 * 1750,
        "device_transport_expert_pairs": 300 * 2050,
        "device_transport_expert_weight_sets": 300 * 8,
        "device_transport_expert_exchange_tokens_sent": 100 * 5250,
        "device_transport_expert_exchange_call_us": {"count": 100, "sum": 2_000_000.0},
        "device_transport_expert_exchange_fanout_us": {"count": 100, "sum": 1_500_000.0},
        "device_transport_device_operands": 300,
        "device_transport_device_operand_fallbacks": 0,
        "device_link_lane_bytes": 600 * 29_425_664,
        "device_link_lane_messages": 600, "device_link_lane_steps": 500,
    }
    run, bare = hand_made_run(served), hand_made_run({})
    step_s, source_s, lane_s = 300 * 0.002, 100 * 0.001, 500 * 0.0006
    want_values = {
        "exchange_call_us": 20000.0, "exchange_fanout_us": 15000.0,
        "exchange_device_operands_pct": 100.0,
        "exchange_step_kernel_us": 2000.0,
        "exchange_step_hbm_pct": 100.0 * (
            (2400 * 88_080_384 + 525_000 * (14_368 + 14_336)) / 819e9) / step_s,
        "exchange_step_mxu_pct": 100.0 * (615_000 * 88_080_384 / 197e12) / step_s,
        "exchange_combine_hbm_pct": 100.0 * (
            (525_000 * (14_336 + 14_368 + 14_336) + 100 * 8192 * 14_336) / 819e9
        ) / source_s,
        "exchange_lane_ici_pct": 100.0 * (600 * 29_425_664 / 200e9) / lane_s,
        "exchange_lane_messages_per_step": 1.2,
    }
    for name in mine:
        read = cell.reader(name)
        assert read(run) == pytest.approx(want_values[name]), name
        if name == "exchange_step_kernel_us":  # the trace alone: no program, no time
            bare.devices = {"/device:TPU:1": run.devices["/device:TPU:0"]}
        assert read(bare) is None, name
        assert 0 < want_values[name] and (
            not name.endswith("_pct") or want_values[name] <= 100), name
    assert roofline_exchange.EXPERT_BYTES == 88_080_384 == roofline_exchange.PAIR_FLOPS
    assert roofline_exchange.OPERAND_ROW_BYTES == 14_368
    assert roofline_exchange.ANSWER_ROW_BYTES == 14_336


def test_the_configuration_states_the_deployment():
    cell = manifest.Cell(bench(), CELL)
    config, entry = cell.config, [
        c for c in bench()["configs"] if c["name"] == "expert_exchange_dsv3_ep32"][0]
    assert cell.chips == config["chips"] == 4
    assert config["architecture"] == "DeepSeek-V3"
    assert config["reduced"] == entry["reduced"] == [
        "n_routed_experts", "num_hidden_layers"]
    # no width differs from DeepSeek-V3's config.json (PR 48's file holds it)
    shard_config = manifest.load_json("configs", "expert_shard_dsv3_ep32.json")
    for key, value in shard_config.items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            if key not in ("n_routed_experts", "num_hidden_layers", "chips",
                           "micro_batch_tokens", "weight_seed"):
                assert config[key] == value, key
    assert config["rope_scaling"] == shard_config["rope_scaling"]
    assert config["n_routed_experts"] == 24 and config["num_hidden_layers"] == 12
    assert config["published"]["n_routed_experts"] == 256
    assert config["unit"]["ranks"] == [0, 1, 2]
    assert config["unit"]["weight_bytes_a_rank"] == 8_455_716_864
    operand = config["operand"]
    assert operand["capacity_rows"] * operand["row_words"] * 4 == operand["bytes"]
    assert operand["bytes"] == 29_425_664 and operand["answer_bytes"] == 29_360_128
    assert operand["row_words"] == config["hidden_size"] // 2 + 8
    assert config["channel_options"] == manifest.load_json(
        "configs", "link_performance_ici.json")["channel_options"]
    assert cell.traffic["callers"] == 4 and cell.traffic["arrival"] == "closed"
    assert cell.deployment().CONTROLS == (
        "flip_bit", "stale", "drop_tokens", "wrong_layer", "low_precision",
        "swap", "host_bytes")
    assert {"call_rate", "latency_p50_us", "setup_s"} == {
        m["name"] for m in cell.end_to_end}
    tolerance = config["tolerance"]
    assert 0 < tolerance["rel_l2"] < 0.05 and 0 < tolerance["element_over_rms"] < 0.3
    four = [w["name"] for w in bench()["workloads"] if w["chips"] == 4]
    assert len(four) == 6 and len(bench()["workloads"]) == 12
