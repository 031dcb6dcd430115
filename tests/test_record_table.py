"""The record table (PR 37): ``models/record_table.RecordTableService`` and a
service with state behind ``DeviceEndpoint``, on the CPU at 4,096 records,
against the benchmark's plain reference
(``benchmark/references/ycsb_record_store.py``, which imports nothing of
the program): the step on seeded batches that name a record twice, the
endpoint under 16 threads against the reference's register check, the
answer that is longer than its request, donation, failure, the Zipfian
draw, and the echo service through the endpoint's two programs as the
harness calls them, bit for bit as the parent's programs answered."""

import os
import struct
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import manifest  # noqa: E402
from incubator_brpc_tpu.models import record_table  # noqa: E402
from incubator_brpc_tpu.models.record_table import (  # noqa: E402
    READ, UPDATE, RecordTableService,
)
from incubator_brpc_tpu.models.tensor_echo import TensorEchoService  # noqa: E402
from incubator_brpc_tpu.ops import framing  # noqa: E402
from incubator_brpc_tpu.transport import device  # noqa: E402
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402
from incubator_brpc_tpu.utils.status import ErrorCode  # noqa: E402

ref = manifest.load_module("references", "ycsb_record_store.py")

RECORDS, SEED = 4096, 37
KEY = struct.Struct("<Q")
HEAD = struct.Struct("<QI")


def service():
    return RecordTableService(RECORDS, seed=SEED)


@pytest.fixture(scope="module")
def step():
    return jax.jit(service().step)


@pytest.fixture
def endpoint():
    return DeviceEndpoint(service=service(), window_size=16, max_batch=16)


def words(data: bytes, width: int) -> np.ndarray:
    row = np.zeros(width, np.uint32)
    row[: len(data) // 4] = np.frombuffer(data, np.uint32)
    return row


def random_batch(rng, b: int, hot: int):
    """``b`` operations over ``hot`` keys, so that records repeat: about
    half reads, half updates, a few of them of one field."""
    ops = []
    for _ in range(b):
        key = int(rng.integers(0, hot)) * 97 % RECORDS
        if rng.random() < 0.5:
            ops.append((READ, key, None, None))
        else:
            ops.append((UPDATE, key, int(rng.integers(0, 2)), rng.bytes(100)))
    return ops


def as_rows(ops, width: int, pad_to: int):
    rows = np.zeros((pad_to, width), np.uint32)
    mids = np.zeros(pad_to, np.uint32)
    for i, (mid, key, field, value) in enumerate(ops):
        wire = KEY.pack(key) if mid == READ else HEAD.pack(key, field) + value
        rows[i], mids[i] = words(wire, width), mid
    cids = np.arange(1, pad_to + 1, dtype=np.uint32) + np.uint32(1 << 31)
    return rows, cids, mids


def reference_dispatch(store, ops):
    """What the service's docstring promises of one dispatch: its reads see
    the store as it was before its updates; the updates in row order."""
    answers = [store.read(key) if mid == READ else None
               for mid, key, _f, _v in ops]
    for mid, key, field, value in ops:
        if mid == UPDATE:
            store.update(key, field, value)
    return answers


# -- the first content and the step against the reference --------------------


def test_first_content_is_the_references_on_both_sides():
    table = np.asarray(service().init_state(jax.devices()[0]))
    assert table.shape == (RECORDS, 256) and table.dtype == np.uint32
    for key in (0, 1, 4095, 1234):
        assert table[key, :250].tobytes() == ref.first_content(SEED, key)
    assert ref.first_content(SEED, 1) != ref.first_content(SEED + 1, 1)


def test_the_table_is_built_in_pieces(monkeypatch):
    monkeypatch.setattr(record_table, "PIECE_ROWS", 512)
    pieces = np.asarray(service().init_state(jax.devices()[0]))
    monkeypatch.setattr(record_table, "PIECE_ROWS", 1 << 18)
    whole = np.asarray(service().init_state(jax.devices()[0]))
    np.testing.assert_array_equal(pieces, whole)
    with pytest.raises(ValueError):
        monkeypatch.setattr(record_table, "PIECE_ROWS", 1000)
        service().init_state(jax.devices()[0])


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("b,pad_to,hot", [(1, 1, 1), (2, 2, 1), (5, 8, 2), (16, 16, 3)])
def test_step_against_the_reference_on_batches_that_repeat_records(
        step, seed, b, pad_to, hot):
    rng = np.random.default_rng([seed, b])
    store = ref.RecordStore(RECORDS, SEED)
    table = service().init_state(jax.devices()[0])
    seen_twice = False
    for _ in range(4):  # dispatches in a row: the state is carried
        ops = random_batch(rng, b, hot)
        keys = [key for _m, key, _f, _v in ops]
        seen_twice |= len(set(keys)) < len(keys)
        rows, cids, mids = as_rows(ops, 256, pad_to)
        table, frames = step(table, rows, cids, mids)
        frames = np.asarray(frames)
        want = reference_dispatch(store, ops)
        for i, (mid, _key, _f, _v) in enumerate(ops):
            assert frames[i, 7] == 0 and frames[i, 5] == mid
            assert frames[i, 3] == cids[i] and frames[i, 2] == framing.FLAG_RESPONSE
            if mid == READ:
                assert frames[i, 8:258].tobytes() == want[i], (seed, i)
            else:
                assert frames[i, 8] == 0  # the status
        for i in range(b, pad_to):  # pad rows: unknown method, nothing else
            assert frames[i, 7] == record_table.ENOMETHOD
    assert seen_twice or b == 1
    # the whole table is the reference's: nothing else was touched
    host = np.asarray(table)
    for key in range(0, RECORDS, 97):
        assert host[key, :250].tobytes() == store.read(key)
    for key in {k for k in store._held}:
        assert host[key, :250].tobytes() == store.read(key)


@pytest.mark.parametrize("case", ["read_update", "update_update", "three_updates",
                                  "update_other_field"])
def test_two_rows_of_one_dispatch_name_one_record(step, case):
    a, b, c = (bytes([n]) * 100 for n in (1, 2, 3))
    ops = {
        "read_update": [(READ, 7, None, None), (UPDATE, 7, 3, a), (READ, 7, None, None)],
        "update_update": [(UPDATE, 7, 3, a), (UPDATE, 7, 3, b)],
        "three_updates": [(UPDATE, 7, 3, a), (UPDATE, 7, 3, b), (UPDATE, 7, 3, c)],
        "update_other_field": [(UPDATE, 7, 3, a), (UPDATE, 7, 4, b)],
    }[case]
    table = service().init_state(jax.devices()[0])
    rows, cids, mids = as_rows(ops, 256, 4)
    table, frames = step(table, rows, cids, mids)
    frames, host = np.asarray(frames), np.asarray(table)
    first = ref.first_content(SEED, 7)
    store = ref.RecordStore(RECORDS, SEED)
    reference_dispatch(store, ops)
    assert host[7, :250].tobytes() == store.read(7)
    overwritten = frames[: len(ops), 9].tolist()
    if case == "read_update":
        # both reads see the table as it was before the dispatch's update
        assert frames[0, 8:258].tobytes() == frames[2, 8:258].tobytes() == first
        assert ref.field_of(host[7, :250].tobytes(), 3) == a
    elif case == "update_update":
        assert ref.field_of(host[7, :250].tobytes(), 3) == b and overwritten == [1, 0]
    elif case == "three_updates":
        assert ref.field_of(host[7, :250].tobytes(), 3) == c and overwritten == [1, 1, 0]
    else:
        assert overwritten == [0, 0]
    assert (frames[: len(ops), 7] == 0).all()


@pytest.mark.parametrize("what", ["key_past_the_table", "key_high_word", "field_past_the_record",
                                  "unknown_method", "update_key_past_the_table"])
def test_a_request_out_of_range_is_an_error_and_touches_nothing(step, what):
    value = b"\x55" * 100
    wire, mid = {
        "key_past_the_table": (KEY.pack(RECORDS), READ),
        "key_high_word": (HEAD.pack(1 << 32, 0) + value, UPDATE),
        "field_past_the_record": (HEAD.pack(5, 10) + value, UPDATE),
        "unknown_method": (HEAD.pack(5, 0) + value, 9),
        "update_key_past_the_table": (HEAD.pack(RECORDS + 5, 1) + value, UPDATE),
    }[what]
    svc = service()
    table = svc.init_state(jax.devices()[0])
    before = np.asarray(table).copy()
    rows = np.stack([words(wire, 64), words(HEAD.pack(5, 0) + value, 64)])
    table, frames = step(table, rows, np.uint32([3, 4]), np.uint32([mid, UPDATE]))
    frames, after = np.asarray(frames), np.asarray(table)
    want = record_table.ENOMETHOD if what == "unknown_method" else record_table.EREQUEST
    assert frames[0, 7] == want and not frames[0, 8:].any()
    # the row beside it was served, and is the only change
    assert frames[1, 7] == 0
    before[5, :25] = np.frombuffer(value, np.uint32)
    np.testing.assert_array_equal(after, before)


def test_a_program_narrower_than_a_record_answers_no_read(step):
    table = service().init_state(jax.devices()[0])
    rows = np.stack([words(KEY.pack(3), 64)])
    _table, frames = step(table, rows, np.uint32([1]), np.uint32([READ]))
    assert np.asarray(frames)[0, 7] == record_table.EREQUEST


# -- behind the endpoint -------------------------------------------------------


def test_a_read_answers_a_thousand_bytes_to_eight(endpoint):
    code, out = endpoint.call_bytes(KEY.pack(11), method_id=READ)
    assert code == 0 and len(out) == 1000 == endpoint.service.answer_bytes(READ, 8)
    assert out == ref.first_content(SEED, 11)
    value = bytes(range(100))
    code, status = endpoint.call_bytes(HEAD.pack(11, 9) + value, method_id=UPDATE)
    assert (code, status) == (0, ref.STATUS_OK)
    code, out = endpoint.call_bytes(KEY.pack(11), method_id=READ)
    assert code == 0 and out == ref.first_content(SEED, 11)[:900] + value
    code, out = endpoint.call_bytes(KEY.pack(RECORDS), method_id=READ)
    assert code == ErrorCode.EREQUEST and out == b""
    code, out = endpoint.call_bytes(b"what", method_id=5)
    assert code == ErrorCode.ENOMETHOD


def test_the_bucket_is_the_larger_of_request_and_answer(endpoint):
    read = endpoint.call_words(words(KEY.pack(1), 2), method_id=READ)
    update = endpoint.call_words(
        words(HEAD.pack(1, 0) + b"\x01" * 100, 28), method_id=UPDATE)
    assert read.wait(30) and update.wait(30)
    assert len(read.response_words) == 250 and len(update.response_words) == 1
    # alone, a read rides a 256-word bucket and an update a 64-word one
    solo = endpoint.call_words(
        words(HEAD.pack(2, 0) + b"\x02" * 100, 28), method_id=UPDATE)
    assert solo.wait(30) and solo.dispatch.bucket == 64
    solo = endpoint.call_words(words(KEY.pack(2), 2), method_id=READ)
    assert solo.wait(30) and solo.dispatch.bucket == 256


def test_the_old_table_is_donated_and_a_dispatch_that_raises_fails_the_endpoint(
        endpoint, monkeypatch):
    old = endpoint._state
    assert endpoint.call_bytes(KEY.pack(1), method_id=READ)[0] == 0
    assert old.is_deleted() and not endpoint._state.is_deleted()

    def raises(*_args):
        raise RuntimeError("made to raise")

    monkeypatch.setattr(endpoint._program, "_jitted", raises)
    code, out = endpoint.call_bytes(KEY.pack(1), method_id=READ)
    assert code == ErrorCode.EINTERNAL and out == b""
    monkeypatch.undo()  # the program is whole again; the state is not
    assert endpoint._state is device._LOST
    for _ in range(2):
        code, out = endpoint.call_bytes(KEY.pack(1), method_id=READ)
        assert code == ErrorCode.EINTERNAL and out == b""
    assert endpoint.inflight == 0  # every credit came back


def test_a_dispatch_that_fails_on_the_device_fails_the_endpoint(endpoint, monkeypatch):
    def watch(arrays, on_complete, stamps=None):
        stamps[0] = stamps[1] = time.monotonic_ns()  # as a watcher would
        on_complete(arrays, RuntimeError("the device said no"))

    monkeypatch.setattr(endpoint._cq, "watch", watch)
    assert endpoint.call_bytes(KEY.pack(1), method_id=READ)[0] == ErrorCode.EINTERNAL
    monkeypatch.undo()
    assert endpoint._state is device._LOST
    assert endpoint.call_bytes(KEY.pack(1), method_id=READ)[0] == ErrorCode.EINTERNAL


def test_an_echo_endpoint_has_no_turn_and_loses_nothing(monkeypatch):
    ep = DeviceEndpoint(window_size=4)
    assert ep._state is None and not isinstance(ep._state_turn, type(threading.Lock()))

    def raises(*_args):
        raise RuntimeError("made to raise")

    monkeypatch.setattr(ep._program, "_jitted", raises)
    assert ep.call_bytes(b"once")[0] == ErrorCode.EINTERNAL
    monkeypatch.undo()
    assert ep.call_bytes(b"again") == (0, b"again")


def test_warm_compiles_every_program_sixteen_callers_can_form(endpoint):
    endpoint.warm(8, method_id=READ)
    endpoint.warm(112, method_id=UPDATE)
    sizes = endpoint._program._cache_size(), endpoint._batch_program._cache_size()
    assert sizes == (2, 8)  # two buckets: alone, and batches of 2, 4, 8, 16
    table = np.asarray(endpoint._state)
    assert table[0, :250].tobytes() == ref.first_content(SEED, 0)  # pads wrote nothing
    assert endpoint.call_bytes(KEY.pack(1), method_id=READ)[0] == 0


@pytest.mark.parametrize("writer", ["native", "numpy"])
def test_a_pad_row_and_a_rows_tail_never_carry_an_update(endpoint, monkeypatch, writer):
    """PR 53: a dispatch's operand is an ``np.empty`` array written once. Its
    pad row must read as a zero frame and every row's tail as zeros, with
    either writer, or the step would update a record nobody named."""
    from incubator_brpc_tpu import native

    if writer == "numpy":
        monkeypatch.setattr(native, "LIB", None)
    elif native.LIB is None:
        pytest.skip("the native library could not be had here")
    # np.empty hands out what the allocator has: leave it rows that read as
    # updates of record 0, field 0 (the size of this dispatch's operand)
    for _ in range(4):
        dirty = np.empty((4, 256), np.uint32)
        dirty[:] = np.frombuffer(
            (HEAD.pack(0, 0) + b"\xee" * 100).ljust(1024, b"\xee"), np.uint32)
        del dirty
    requests = [
        (UPDATE, HEAD.pack(7, 3) + b"\x07" * 100),
        (READ, KEY.pack(0)),
        (UPDATE, HEAD.pack(9, 0) + b"\x09" * 100),
    ]
    before = np.array(endpoint._state)
    operands, inner = [], endpoint._batch_program
    monkeypatch.setattr(
        endpoint, "_batch_program",
        lambda rows, *rest: operands.append(rows) or inner(rows, *rest))
    with endpoint._qlock:
        endpoint._draining = True  # the three wait for one drain
    pendings = [
        endpoint.call_words(np.frombuffer(data, np.uint32), method_id=mid)
        for mid, data in requests
    ]
    endpoint._drain()
    assert all(p.wait(60) and p.error_code == 0 for p in pendings)
    (operand,) = operands
    assert operand.shape == (4, 256) and not operand[3].any()
    for row, (_mid, data) in zip(operand, requests):
        assert row[: len(data) // 4].tobytes() == data
        assert not row[len(data) // 4 :].any()
    assert pendings[1].response_words.tobytes()[:1000] == ref.first_content(SEED, 0)
    after = np.array(endpoint._state)
    changed = {int(k) for k in np.flatnonzero((before != after).any(axis=1))}
    assert changed == {7, 9}
    assert after[7, 75:100].tobytes() == b"\x07" * 100
    assert after[9, :25].tobytes() == b"\x09" * 100
    for key, field in ((7, 3), (9, 0)):
        same = np.ones(256, bool)
        same[25 * field : 25 * field + 25] = False
        assert (after[key][same] == before[key][same]).all()


@pytest.mark.parametrize("seed", [1, 2])
def test_sixteen_threads_against_the_references_register(endpoint, seed):
    """The benchmark's client in small: operations read off seeded payloads,
    every answer judged by the reference once it is back."""
    workload = ref.Workload(64)  # few records: calls collide
    register = ref.Register(SEED)
    reads, updates, rows = (
        record_table.m_reads, record_table.m_updates, device.m_dispatch_rows)
    before = reads.get_value(), updates.get_value(), rows.get_value()
    device.flush_stage_recorders()
    waits = device._recorders["state_wait"].count()
    wrong, errors, sent_ops = [], [], [0, 0]
    lock = threading.Lock()

    def caller(c):
        rng = np.random.default_rng([seed, c])
        try:
            for _ in range(40):
                kind, key, field, value = workload.parts(rng.bytes(128))
                sent = time.monotonic_ns()
                if kind == ref.READ:
                    code, out = endpoint.call_bytes(KEY.pack(key), method_id=READ)
                    answered = time.monotonic_ns()
                    assert code == 0
                    bad = register.wrong_fields(key, out, sent, answered)
                else:
                    entry = register.sent(key, field, value, sent)
                    code, out = endpoint.call_bytes(
                        HEAD.pack(key, field) + value, method_id=UPDATE)
                    assert code == 0
                    register.acknowledged(entry, time.monotonic_ns())
                    bad = out != ref.STATUS_OK
                with lock:
                    sent_ops[kind == ref.UPDATE] += 1
                    if bad:
                        wrong.append((c, kind, key))
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors and not wrong
    # nothing in flight: every field an update went to shows a value the
    # register still allows, read back through the endpoint
    for key, touched in register.updated().items():
        sent = time.monotonic_ns()
        code, out = endpoint.call_bytes(KEY.pack(key), method_id=READ)
        assert code == 0
        assert register.wrong_fields(key, out, sent, time.monotonic_ns()) == 0
        sent_ops[0] += 1
    device.flush_stage_recorders()
    assert reads.get_value() - before[0] == sent_ops[0]
    assert updates.get_value() - before[1] == sent_ops[1]
    assert rows.get_value() - before[2] == sum(sent_ops)
    assert device._recorders["state_wait"].count() - waits == sum(sent_ops)


def test_the_state_wait_lies_inside_the_launch(endpoint):
    pending = endpoint.call_words(words(KEY.pack(1), 2), method_id=READ)
    assert pending.wait(30)
    d = pending.dispatch
    assert 0 < d.t_stacked <= d.t_state <= d.t_launched
    stages = pending.stages()
    assert 0 <= stages["state_wait"] <= stages["launch"]
    assert "device_transport_state_wait_us" in [
        r._exposed_name for r, *_rest in device._stage_feed.columns]


def test_account_counts_what_was_served_and_what_was_overwritten():
    svc = service()
    frames = np.zeros((5, 8 + 64), np.uint32)
    mids = np.uint32([READ, UPDATE, UPDATE, UPDATE, 0])
    frames[2, 9] = 1  # replaced by a later row of its dispatch
    frames[3, 7] = record_table.EREQUEST  # not served
    frames[4, 7] = record_table.ENOMETHOD  # a pad row
    adders = (record_table.m_reads, record_table.m_updates,
              record_table.m_overwritten_rows)
    before = [a.get_value() for a in adders]
    svc.account(mids, frames)
    assert [a.get_value() - b for a, b in zip(adders, before)] == [1, 2, 1]


# -- the reference's own parts -------------------------------------------------


def test_the_register_allows_what_overlaps_and_forbids_what_was_replaced():
    register = ref.Register(SEED)
    first = ref.field_of(ref.first_content(SEED, 3), 2)
    a, b, c = (bytes([n]) * 100 for n in (1, 2, 3))
    may = lambda sent, answered: ref.Register.may_show(  # noqa: E731
        first, register._of(3).get(2, []), sent, answered)
    assert may(10, 20) == {first}
    wa = register.sent(3, 2, a, 30)
    assert may(25, 35) == {first, a}  # in flight while the read was
    assert may(10, 20) == {first}  # sent after the read was answered
    register.acknowledged(wa, 40)
    assert may(45, 50) == {a}  # acknowledged before the read was sent
    assert may(35, 50) == {first, a}  # the read overlapped the update
    wb = register.sent(3, 2, b, 60)
    wc = register.sent(3, 2, c, 65)  # overlaps b: either may stand
    register.acknowledged(wb, 70)
    register.acknowledged(wc, 75)
    assert may(80, 90) == {b, c}
    wd = register.sent(3, 2, a, 100)  # its call fails: nobody knows
    assert may(200, 210) == {b, c, a} and wd[1] is None
    # a torn field equals no value it may show
    record = bytearray(ref.first_content(SEED, 3))
    record[200:250], record[250:300] = b[:50], c[:50]
    assert register.wrong_fields(3, bytes(record), 80, 90) == 1
    assert register.wrong_fields(3, b"short", 80, 90) == ref.FIELDCOUNT


def test_the_record_store_is_a_dict_of_records():
    store = ref.RecordStore(8, SEED)
    assert store.read(3) == ref.first_content(SEED, 3)
    assert store.update(3, 0, b"\x07" * 100) == ref.STATUS_OK
    assert store.read(3) == b"\x07" * 100 + ref.first_content(SEED, 3)[100:]
    with pytest.raises(KeyError):
        store.read(8)
    with pytest.raises(ValueError):
        store.update(3, 10, b"\x07" * 100)


@pytest.fixture(scope="module")
def workload_b():
    return ref.Workload(1 << 23)


def test_zipfian_shares_at_two_to_the_23(workload_b):
    zipf = workload_b.zipfian
    assert zipf.share(0) == pytest.approx(0.056, abs=0.0005)
    assert sum(zipf.share(r) for r in range(10)) == pytest.approx(0.166, abs=0.001)
    # two operations name one record with the sum of the squared shares
    ranks = np.arange(1, (1 << 23) + 1, dtype=np.float64)
    assert float(((1 / ranks ** 0.99 / zipf.zetan) ** 2).sum()) == pytest.approx(
        0.0052, abs=0.0002)


@pytest.mark.parametrize("seed", [3, 4])
def test_the_zipfian_draw_follows_the_analytic_shares(workload_b, seed):
    rng = np.random.default_rng(seed)
    n = 60_000
    draws = np.array([workload_b.zipfian.draw(ref.unit(int(x)))
                      for x in rng.integers(0, 1 << 63, n, dtype=np.uint64) * 2])
    assert draws.min() == 0 and draws.max() < 1 << 23
    assert (draws == 0).mean() == pytest.approx(0.056, abs=0.004)
    # the closed form puts a little more on ranks 2 to 9 than the exact law
    assert (draws < 10).mean() == pytest.approx(0.166, abs=0.015)


def test_the_scramble_and_the_operation_off_a_payload(workload_b):
    # FNV-1a 64 of eight zero octets, by hand: offset basis times prime^8
    h = ref.FNV_OFFSET_BASIS_64
    for _ in range(8):
        h = (h * ref.FNV_PRIME_64) & ((1 << 64) - 1)
    assert ref.fnv1a64(0) == ((1 << 64) - h if h >> 63 else h)
    assert workload_b.key(0) == ref.fnv1a64(0) % (1 << 23)  # u = 0: rank 0
    rng = np.random.default_rng(5)
    kinds = []
    for _ in range(4000):
        payload = rng.bytes(128)
        method, wire = workload_b.operation(payload)
        kind, key, field, value = workload_b.parts(payload)
        kinds.append(kind)
        assert method == kind and 0 <= key < 1 << 23
        assert ref.operation(payload) == (method, wire)
        if kind == ref.READ:
            assert wire == KEY.pack(key) and len(wire) == 8
        else:
            assert wire == HEAD.pack(key, field) + value and len(wire) == 112
            assert 0 <= field < 10 and value == payload[17:117]
    assert kinds.count(ref.UPDATE) / len(kinds) == pytest.approx(0.05, abs=0.015)
    with pytest.raises(ValueError):
        workload_b.parts(b"short")


# -- the echo service through the endpoint's programs, as before --------------


@pytest.fixture(scope="module")
def echo_endpoint():
    svc = TensorEchoService()
    svc.add_method(7, lambda payload: payload ^ jnp.uint32(0x5A5A5A5A))
    return svc, DeviceEndpoint(service=svc, window_size=16, max_batch=16)


def parents_frame(svc, padded, cid, mid):
    """The parent's program, written out: ``jit(service.step)`` over the
    frame built from ``(padded, cid, mid)``."""
    return np.asarray(jax.jit(
        lambda p, c, m: svc.step(framing.frame(p, (c, jnp.uint32(0)), method_id=m))
    )(padded, cid, mid))


@pytest.mark.parametrize("bucket", [64, 1024])
@pytest.mark.parametrize("mid", [0, 7, 3])
def test_echo_through_the_one_row_program_as_the_harness_calls_it(
        echo_endpoint, bucket, mid):
    svc, ep = echo_endpoint
    rng = np.random.default_rng([bucket, mid])
    padded = rng.integers(0, 1 << 32, bucket, dtype=np.uint32)
    # benchmark/deployments/device_echo.py:79: a committed row, two scalars
    got = ep._program(
        jax.device_put(jnp.asarray(padded), ep.device),
        jnp.uint32(0x80000001), jnp.uint32(mid))
    want = parents_frame(svc, padded, np.uint32(0x80000001), np.uint32(mid))
    assert got.shape == (8 + bucket,) and got.devices() == {ep.device}
    np.testing.assert_array_equal(np.asarray(got), want)
    assert want[7] == (1002 if mid == 3 else 0)


@pytest.mark.parametrize("bucket", [64, 1024])
@pytest.mark.parametrize("batch", [2, 4, 16])
def test_echo_through_the_batch_program_as_the_harness_calls_it(
        echo_endpoint, bucket, batch):
    svc, ep = echo_endpoint
    rng = np.random.default_rng([bucket, batch])
    rows = rng.integers(0, 1 << 32, (batch, bucket), dtype=np.uint32)
    cids = rng.integers(1 << 31, 1 << 32, batch, dtype=np.uint32)
    mids = np.uint32([0, 7] * (batch // 2))
    # benchmark/deployments/device_echo.py:90: committed rows, two vectors
    got = ep._batch_program(
        jax.device_put(jnp.asarray(rows), ep.device),
        jnp.asarray(cids), jnp.asarray(mids))
    assert got.shape == (batch, 8 + bucket) and got.devices() == {ep.device}
    for i in range(batch):
        np.testing.assert_array_equal(
            np.asarray(got[i]), parents_frame(svc, rows[i], cids[i], mids[i]))


def test_the_echo_service_is_the_service_with_no_state():
    svc = TensorEchoService()
    assert svc.init_state(jax.devices()[0]) is None
    assert svc.answer_bytes(0, 37) == 37 and svc.account(None, None) is None
    rows = np.arange(128, dtype=np.uint32).reshape(2, 64)
    state, frames = svc.dispatch_step(None, rows, np.uint32([1, 2]), np.uint32([0, 0]))
    assert state is None and frames.shape == (2, 72)
    np.testing.assert_array_equal(np.asarray(frames)[:, 8:], rows)
