"""The expert shard (PR 48): ``models/expert_shard.ExpertShardService``, a
service whose state the step reads and never replaces, behind
``DeviceEndpoint`` and behind ``Server``/``Channel``, on the CPU at a small
size (hidden 64, intermediate 32, 32 experts in 4 groups, top-4, 4 ranks of
8, 3 layers; the kernel in Pallas's interpreter) against the benchmark's
plain reference (``benchmark/references/moe_expert_share.py``, which
imports nothing of the program): forced splits of a block's tokens over the
experts, rows of different layers and buckets in one dispatch, the error
cases, the four ranks' shares against the uncut layer, the endpoint's third
kind of state, the cell's must-fail controls and the new readers."""

import json
import os
import struct
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import manifest, roofline_expert, xplane  # noqa: E402
from incubator_brpc_tpu.models import expert_shard  # noqa: E402
from incubator_brpc_tpu.models.expert_shard import FFN, ExpertShardService  # noqa: E402
from incubator_brpc_tpu.models.record_table import RecordTableService  # noqa: E402
from incubator_brpc_tpu.ops import framing  # noqa: E402
from incubator_brpc_tpu.transport import device  # noqa: E402
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402
from incubator_brpc_tpu.utils.status import ErrorCode  # noqa: E402

ref = manifest.load_module("references", "moe_expert_share.py")

MOE = ref.Moe(hidden_size=64, moe_intermediate_size=32, n_routed_experts=32,
              num_experts_per_tok=4, n_group=4, topk_group=2)
HIDDEN, HELD, LAYERS, EP, SEED = 64, 8, 3, 4, 48
HEAD = struct.Struct("<4I")
# bf16's rounding of the gated intermediate and of the answer, at a hidden
# of 64 (fewer terms to average over than at 7,168): 0.004 and 0.013 read
REL_L2, OVER_RMS = 0.01, 0.05


def service(rank=0):
    return ExpertShardService(
        HIDDEN, 32, HELD, LAYERS, seed=SEED, first_expert=rank * HELD)


@pytest.fixture(scope="module")
def shard():
    s = service()
    return s, s.init_state(jax.devices()[0]), jax.jit(s.step)


@pytest.fixture
def endpoint():
    return DeviceEndpoint(service=service(), window_size=16, max_batch=16)


def tokens(n, salt=0):
    return ref.micro_batch(b"tests/test_expert_shard", salt, 0, n, HIDDEN)


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
    else:  # "router": a token's own top experts among the eight
        w = rng.random((n, HELD)).astype(np.float32)
        w[w < 0.6] = 0
        w[np.arange(n), rng.integers(0, HELD, n)] = 0.7
    return w.astype(np.float32)


def want(layer, x, w, rank=0):
    """The reference's answer: the rank's experts alone, by those weights."""
    dense = np.zeros((x.shape[0], MOE.n_routed_experts), np.float32)
    mine = ref.held(MOE, rank, EP)
    dense[:, mine.start : mine.stop] = w
    return np.asarray(ref.routed(MOE, SEED, layer, x, jnp.asarray(dense), mine))


def as_row(request: bytes, width: int) -> np.ndarray:
    row = np.zeros(width, np.uint32)
    row[: len(request) // 4] = np.frombuffer(request, np.uint32)
    return row


def answer_of(frame: np.ndarray, n: int) -> np.ndarray:
    start = framing.HEADER_WORDS
    return ref.unpack_answer(frame[start : start + n * HIDDEN // 2].tobytes(), HIDDEN)


def within(answer, wanted):
    rel_l2, over_rms = ref.outside(answer, wanted)
    return rel_l2 <= REL_L2 and over_rms <= OVER_RMS


# -- the weights and the step against the reference --------------------------


def test_the_weights_are_the_references_on_both_sides(shard):
    s, state, _ = shard
    order = s.hidden_order  # the hidden axis as stored: even columns, then odd
    assert sorted(order) == list(range(HIDDEN)) and order[:3].tolist() == [0, 2, 4]
    for layer, expert in ((0, 0), (1, 3), (2, 7)):
        gate, up, down = (np.asarray(m[layer, expert], np.float32) for m in state)
        theirs = [np.asarray(m) for m in ref.expert_weights(MOE, SEED, layer, expert)]
        np.testing.assert_array_equal(gate, theirs[0][order])
        np.testing.assert_array_equal(up, theirs[1][order])
        np.testing.assert_array_equal(down, theirs[2][:, order])
    salt = ref.weight_salt(SEED, 1, 3, ref.UP)
    np.testing.assert_array_equal(  # numpy computes the function alike
        ref.weight_values(np.uint32(salt), (HIDDEN, 32), HIDDEN, xp=np)[order],
        np.asarray(state[1][1, 3], np.float32))
    other = service(rank=1).init_state(jax.devices()[0])
    assert not np.array_equal(np.asarray(other[0][0, 0]), np.asarray(state[0][0, 0]))
    assert all(m.dtype == jnp.bfloat16 for m in state)


@pytest.mark.parametrize("kind", ["evenly", "all_on_one", "none_on_some", "router"])
@pytest.mark.parametrize("n,width", [(1, 64), (7, 512), (20, 1024), (21, 2048)])
def test_step_serves_any_split_without_dropping_a_token(shard, kind, n, width):
    _, state, step = shard
    rng = np.random.default_rng([n, len(kind)])
    x, w, layer = tokens(n, salt=n), split(n, kind, rng), n % LAYERS
    row = as_row(ref.pack_request(layer, x, w), width)
    _, frames = step(state, row[None], np.uint32([9]), np.uint32([FFN]))
    frame = np.asarray(frames)[0]
    assert frame[7] == 0 and frame[5] == FFN and frame[3] == 9
    assert frame[2] == framing.FLAG_RESPONSE
    assert within(answer_of(frame, n), want(layer, x, w))
    # what the row cost, in the frame's last words
    served = (w != 0).any(axis=0)
    mask = sum(1 << e for e in range(HELD) if served[e])
    assert frame[-4:].tolist() == [n, int((w != 0).sum()), layer, mask]


def test_rows_of_different_layers_and_buckets_share_a_dispatch(shard):
    _, state, step = shard
    rng = np.random.default_rng(4)
    asked = [(2, 5, "router"), (0, 22, "evenly"), (1, 9, "all_on_one"),
             (2, 3, "none_on_some"), (0, 22, "router")]
    rows, wanted = [], []
    for layer, n, kind in asked:
        x, w = tokens(n, salt=layer + n), split(n, kind, rng)
        rows.append(as_row(ref.pack_request(layer, x, w), 2048))
        wanted.append(want(layer, x, w))
    rows += [np.zeros(2048, np.uint32)] * 3  # a dispatch's pad rows
    mids = np.uint32([FFN] * 5 + [0] * 3)
    _, frames = step(state, np.stack(rows), np.arange(8, dtype=np.uint32), mids)
    frames = np.asarray(frames)
    for i, (layer, n, _kind) in enumerate(asked):
        assert frames[i, 7] == 0 and frames[i, -2] == layer
        assert within(answer_of(frames[i], n), wanted[i]), i
    assert (frames[5:, 7] == expert_shard.ENOMETHOD).all()
    assert not frames[5:, 8:].any()
    # alone, in its own narrower bucket, a row is answered the same
    layer, n, _ = asked[2]
    _, alone = step(state, rows[2][None, :512], np.uint32([2]), np.uint32([FFN]))
    alone = answer_of(np.asarray(alone)[0], n)
    assert within(alone, wanted[2])
    np.testing.assert_array_equal(alone, answer_of(frames[2], n))


def request(layer=1, n=6, hidden=HIDDEN, held=HELD, claim=None, spoil=None):
    x, w = tokens(n), split(n, "router", np.random.default_rng(n))
    if spoil is not None:
        w[spoil] = np.nan
    body = ref.pack_request(layer, x, w)[HEAD.size :]
    return HEAD.pack(layer, n if claim is None else claim, hidden, held) + body


@pytest.mark.parametrize("what,bad", [
    ("a layer out of range", request(layer=LAYERS)),
    ("more tokens than were sent", request(claim=7)),
    ("fewer tokens than were sent", request(claim=5)),
    ("no token", request(claim=0)),
    ("more tokens than the row holds", request(claim=400)),
    ("a weight that is not finite", request(spoil=(2, 3))),
    ("another rank's shapes", request(hidden=HIDDEN * 2)),
    ("another count of experts", request(held=4)),
    ("the last token's weights cut off", request()[: -4 * HELD]),
])
def test_a_malformed_request_is_a_bad_request_and_costs_nothing(shard, what, bad):
    _, state, step = shard
    good = request()
    rows = np.stack([as_row(good, 512), as_row(bad, 512)])
    _, frames = step(state, rows, np.uint32([1, 2]), np.uint32([FFN, FFN]))
    frames = np.asarray(frames)
    assert frames[0, 7] == 0 and frames[0, -4] == 6, what
    assert frames[1, 7] == expert_shard.EREQUEST, what
    assert not frames[1, 8:].any(), what  # no answer, and no tally


def test_unknown_methods_and_rows_too_narrow_for_a_token(shard):
    s, state, step = shard
    assert s.tokens_that_fit(64) == 1 and s.tokens_that_fit(32) == 0
    row = as_row(request(), 512)
    _, frames = step(state, row[None], np.uint32([1]), np.uint32([5]))
    assert np.asarray(frames)[0, 7] == expert_shard.ENOMETHOD
    narrow = jax.jit(s.step)(
        state, np.zeros((2, 32), np.uint32), np.uint32([1, 2]), np.uint32([FFN, 0]))[1]
    assert np.asarray(narrow)[:, 7].tolist() == [
        expert_shard.EREQUEST, expert_shard.ENOMETHOD]


def test_answer_bytes_follow_from_the_requests(shard):
    s, _, _ = shard
    assert s.answer_bytes(FFN, 16 + 5 * s.token_bytes) == 5 * 2 * HIDDEN
    assert s.answer_bytes(FFN, 16 + 5 * s.token_bytes - 4) == 16 + 5 * s.token_bytes - 4
    assert s.answer_bytes(FFN, 16) == 16 and s.answer_bytes(0, 100) == 100
    assert s.weight_bytes == 2 * 3 * LAYERS * HELD * HIDDEN * 32
    with pytest.raises(ValueError):
        ExpertShardService(63, 32, 8, 1)


# -- the shares add up to the uncut layer -------------------------------------


def test_the_four_ranks_shares_and_the_shared_expert_are_the_whole_layer():
    x, layer = tokens(48, salt=3), 1
    router = ref.router_weights(MOE, SEED, layer)
    whole = np.asarray(ref.moe_layer(MOE, SEED, layer, x))
    total = np.array(ref.shared(MOE, SEED, layer, x))  # counted once
    by_reference = total.copy()
    sent = 0
    for rank in range(EP):
        s = service(rank)
        state = s.init_state(jax.devices()[0])
        rows, w = ref.sent_here(MOE, x, router, rank, EP)
        sent += int((w != 0).sum())
        width = 1 << int(np.ceil(np.log2(4 + len(rows) * (HIDDEN // 2 + HELD))))
        row = as_row(ref.pack_request(layer, x[jnp.asarray(rows)], w), width)
        _, frames = jax.jit(s.step)(
            state, row[None], np.uint32([rank]), np.uint32([FFN]))
        frame = np.asarray(frames)[0]
        assert frame[7] == 0
        total[rows] += answer_of(frame, len(rows))
        by_reference += np.asarray(ref.share(MOE, SEED, layer, x, rank, EP))
    assert sent == 48 * MOE.num_experts_per_tok  # every pair went to one rank
    np.testing.assert_allclose(by_reference, whole, rtol=1e-5, atol=1e-5)
    # four answers rounded to bf16 each, against the float32 layer
    rel_l2, over_rms = ref.outside(total, whole)
    assert rel_l2 <= 2 * REL_L2 and over_rms <= 2 * OVER_RMS


def test_the_router_is_the_published_one():
    x = tokens(64, salt=9)
    w = np.asarray(ref.router(MOE, x, ref.router_weights(MOE, SEED, 0)))
    chosen = w != 0
    assert (chosen.sum(axis=1) == MOE.num_experts_per_tok).all()
    np.testing.assert_allclose(w.sum(axis=1), MOE.routed_scaling_factor, rtol=1e-5)
    groups = chosen.reshape(64, MOE.n_group, -1).any(axis=2).sum(axis=1)
    assert (groups <= MOE.topk_group).all()
    # the weights are the scores of the chosen, in proportion
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(x @ ref.router_weights(MOE, SEED, 0).T))
    for t in range(4):
        ratio = w[t][chosen[t]] / scores[t][chosen[t]]
        np.testing.assert_allclose(ratio, ratio[0], rtol=1e-4)


# -- through the endpoint, and through Server and Channel -----------------------


def test_through_the_endpoint_alone_and_in_a_batch(endpoint):
    rng = np.random.default_rng(12)
    calls = []
    for i in range(12):
        n, layer = int(rng.integers(1, 23)), i % LAYERS
        x, w = tokens(n, salt=100 + i), split(n, "router", rng)
        calls.append((ref.pack_request(layer, x, w), want(layer, x, w), n))
    code, out = endpoint.call_bytes(calls[0][0], method_id=FFN)
    assert code == 0 and len(out) == calls[0][2] * 2 * HIDDEN
    assert within(ref.unpack_answer(out, HIDDEN), calls[0][1])
    before = (expert_shard.m_tokens.get_value(), expert_shard.m_pairs.get_value(),
              expert_shard.m_layers.get_value(), device.m_dispatches.get_value())
    results, errors = [None] * len(calls), []

    def caller(i):
        try:
            results[i] = endpoint.call_bytes(calls[i][0], method_id=FFN, timeout=60)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(len(calls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    for (code, out), (_req, wanted, n) in zip(results, calls):
        assert code == 0 and len(out) == n * 2 * HIDDEN
        assert within(ref.unpack_answer(out, HIDDEN), wanted)
    assert endpoint.inflight == 0
    # account() runs on the watcher after the callers wake
    deadline = threading.Event()
    for _ in range(200):
        if expert_shard.m_tokens.get_value() - before[0] == sum(n for *_r, n in calls):
            break
        deadline.wait(0.01)
    assert expert_shard.m_tokens.get_value() - before[0] == sum(n for *_r, n in calls)
    dispatches = device.m_dispatches.get_value() - before[3]
    layers = expert_shard.m_layers.get_value() - before[2]
    assert dispatches <= layers <= min(len(calls), LAYERS * dispatches)


def test_through_server_and_channel(endpoint):
    from incubator_brpc_tpu.rpc import Channel, Controller, Server

    server = Server()
    server.add_service("experts", {"ffn": endpoint.server_handler(method_id=FFN)})
    assert server.start(0)
    try:
        channel = Channel()
        assert channel.init(f"127.0.0.1:{server.port}")
        x, w = tokens(11, salt=7), split(11, "router", np.random.default_rng(7))
        cntl = channel.call_method(
            "experts", "ffn", ref.pack_request(2, x, w),
            cntl=Controller(timeout_ms=120000))
        assert cntl.ok(), cntl.error_text
        assert within(ref.unpack_answer(cntl.response_payload, HIDDEN), want(2, x, w))
        cntl = channel.call_method(
            "experts", "ffn", request(layer=LAYERS), cntl=Controller(timeout_ms=120000))
        assert cntl.failed() and cntl.error_code == ErrorCode.EREQUEST
    finally:
        server.stop()
        server.join(timeout=10)


# -- the endpoint's third kind of state: read and never replaced -----------------


def test_the_weights_are_not_donated_and_no_dispatch_takes_a_turn(endpoint):
    assert endpoint._state_turn is None  # dispatches launch side by side
    weights = endpoint._state
    for _ in range(2):
        assert endpoint.call_bytes(request(), method_id=FFN)[0] == 0
    assert endpoint._state is weights
    assert not any(m.is_deleted() for m in weights)
    # a service that replaces its state still takes turns and donates
    table = DeviceEndpoint(service=RecordTableService(4096, seed=1), window_size=4)
    assert isinstance(table._state_turn, type(threading.Lock()))
    old = table._state
    assert table.call_bytes(struct.pack("<Q", 1), method_id=1)[0] == 0
    assert old.is_deleted() and not table._state.is_deleted()
    assert DeviceEndpoint(window_size=4)._state_turn is None  # and an echo none


def test_a_program_that_raises_loses_no_weights(endpoint, monkeypatch):
    good = request()

    def raises(*_args):
        raise RuntimeError("made to raise")

    monkeypatch.setattr(endpoint._program, "_jitted", raises)
    code, out = endpoint.call_bytes(good, method_id=FFN)
    assert code == ErrorCode.EINTERNAL and out == b""
    monkeypatch.undo()
    assert endpoint.call_bytes(good, method_id=FFN)[0] == 0
    # nor does a dispatch that fails on the device

    def watch(arrays, on_complete, stamps=None):
        import time

        stamps[0] = stamps[1] = time.monotonic_ns()
        on_complete(arrays, RuntimeError("the device said no"))

    monkeypatch.setattr(endpoint._cq, "watch", watch)
    assert endpoint.call_bytes(good, method_id=FFN)[0] == ErrorCode.EINTERNAL
    monkeypatch.undo()
    assert endpoint.call_bytes(good, method_id=FFN)[0] == 0
    assert endpoint.inflight == 0


def test_warm_compiles_every_geometry_and_runs_no_product(endpoint):
    tokens_before = expert_shard.m_tokens.get_value()
    endpoint.warm(16 + 20 * endpoint.service.token_bytes, method_id=FFN)
    sizes = endpoint._program._cache_size(), endpoint._batch_program._cache_size()
    assert sizes == (1, 4)  # one bucket: alone, and batches of 2, 4, 8, 16
    assert expert_shard.m_tokens.get_value() == tokens_before


# -- the cell's readers and the deployment's controls ---------------------------

CELL = "expert_ffn_ep32_n256_c16"
T_OPEN, T_CLOSE = 1_000_000_000, 21_000_000_000


def hand_made_run(counters):
    """Fifty step executions of 4 ms inside the window."""
    start = T_OPEN + np.arange(50, dtype=np.int64) * 100_000_000
    steps = xplane.Events(["jit_step_batch"] * 50, start, start + 4_000_000)
    return types.SimpleNamespace(
        counters=counters, t_open=T_OPEN, t_close=T_CLOSE,
        devices={"/device:TPU:0": {"steps": steps, "ops": xplane.Events([], [], [])}},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def test_the_new_readers_read_the_counters_and_none_without_them():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cell = manifest.Cell(bench, CELL)
    mine = [m["name"] for m in cell.per_layer if m["name"].startswith("expert_")]
    assert sorted(mine) == [
        "expert_layers_per_dispatch", "expert_step_hbm_pct",
        "expert_step_kernel_us", "expert_step_mxu_pct",
        "expert_tokens_per_dispatch"]
    served = {
        "device_transport_dispatches": 50, "device_transport_expert_tokens": 8250,
        "device_transport_expert_pairs": 9650, "device_transport_expert_layers": 140,
        "device_transport_expert_weight_sets": 1120,
    }
    run, bare = hand_made_run(served), hand_made_run(
        {"device_transport_dispatches": 50})
    want_values = {
        "expert_step_kernel_us": 4000.0,
        "expert_step_hbm_pct": 100.0 * (
            (1120 * 88_080_384 + 2 * 8250 * 14_336) / 819e9) / 0.2,
        "expert_step_mxu_pct": 100.0 * (9650 * 88_080_384 / 197e12) / 0.2,
        "expert_layers_per_dispatch": 2.8,
        "expert_tokens_per_dispatch": 165.0,
    }
    for name in mine:
        read = cell.reader(name)
        assert read(run) == pytest.approx(want_values[name]), name
        assert read(bare) is None, name
    assert 0 < want_values["expert_step_hbm_pct"] <= 100
    assert roofline_expert.EXPERT_BYTES == 88_080_384 == roofline_expert.PAIR_FLOPS
    # the cell reports the endpoint's stage metrics beside the table's cell
    names = {m["name"] for m in cell.per_layer}
    table = {m["name"] for m in manifest.Cell(bench, "ycsb_b_zipf_c16").per_layer}
    assert table - names == {"table_step_kernel_us", "table_step_hbm_pct"}
    config = cell.config
    assert config["unit"]["weight_bytes"] == 8_455_716_864 == 2 * 3 * 12 * 8 * 7168 * 2048
    assert cell.deployment().CONTROLS[2:] == ("drop_tokens", "wrong_layer", "low_precision")


def rehearse(*more):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0",
         "--rehearse-on-cpu", *more],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_the_cell_rehearses_correct():
    result, lines = rehearse()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["compilations_in_window"] == 0
    assert any(line.startswith("REHEARSAL unit") for line in lines)
    assert any(line.startswith("CHECK answers_outside_tolerance: 0 ") for line in lines)
    assert not any("NOT HELD" in line for line in lines)


@pytest.mark.parametrize("control", ["drop_tokens", "wrong_layer", "low_precision"])
def test_a_broken_guarantee_comes_out_not_correct(control):
    result, lines = rehearse("--control", control)
    assert result["correct"] is False
    held = [line for line in lines if line.startswith("CHECK answers_outside")]
    assert held and "NOT HELD" in held[0]
