"""Device transport tests — the transport=tpu slot (reference
brpc_rdma_unittest.cpp shape: endpoint rings, credit window, completion
delivery; runs on the virtual CPU mesh devices here)."""

import contextlib
import os
import threading
import time

import numpy as np
import pytest

from incubator_brpc_tpu.transport.device import DeviceEndpoint, _bucket_words
from incubator_brpc_tpu.utils.status import ErrorCode


class TestBuckets:
    def test_power_of_two_buckets(self):
        assert _bucket_words(1) == 64
        assert _bucket_words(64) == 64
        assert _bucket_words(65) == 128
        assert _bucket_words(1000) == 1024
        with pytest.raises(ValueError):
            _bucket_words(1 << 26)


@pytest.fixture(scope="module")
def endpoint():
    return DeviceEndpoint(window_size=4)


class TestDeviceCalls:
    def test_word_echo_roundtrip(self, endpoint):
        words = np.arange(100, dtype=np.uint32)
        pending = endpoint.call_words(words, correlation_id=7)
        assert pending.wait(timeout=30)
        assert pending.error_code == 0
        np.testing.assert_array_equal(pending.response_words, words)

    def test_byte_echo_roundtrip(self, endpoint):
        payload = b"device-transport-payload!"  # not word-aligned
        code, out = endpoint.call_bytes(payload, timeout=30)
        assert code == 0
        assert out == payload

    def test_unknown_method_is_enomethod(self, endpoint):
        code, _ = endpoint.call_bytes(b"xxxx", method_id=999, timeout=30)
        assert code == 1002  # ENOMETHOD from the device dispatch table

    def test_pipelined_calls_within_window(self, endpoint):
        pendings = [
            endpoint.call_words(
                np.full(32, i, dtype=np.uint32), correlation_id=i + 1
            )
            for i in range(4)
        ]
        for i, p in enumerate(pendings):
            assert p.wait(timeout=30)
            assert p.error_code == 0
            assert p.response_words[0] == i

    def test_credit_window_bounds_inflight(self):
        ep = DeviceEndpoint(window_size=2)
        n = 8
        results = []
        lock = threading.Lock()

        def caller(i):
            code, out = ep.call_bytes(b"abcd" * 8, timeout=30)
            with lock:
                results.append(code)

        ts = [threading.Thread(target=caller, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert results == [0] * n  # window stalls, nothing fails
        assert ep.inflight == 0  # every credit returned

    def test_server_handler_integration(self, endpoint):
        """Full host-RPC → device step → response path: the reference's
        'flip transport=tpu and rerun the same example pair' (SURVEY §7
        step 5)."""
        from incubator_brpc_tpu.rpc import Channel, Server

        server = Server()
        server.add_service("tensor", {"echo": endpoint.server_handler()})
        assert server.start(0)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            cntl = ch.call_method(
                "tensor", "echo", b"rpc-over-hbm", cntl=None
            )
            assert cntl.ok(), cntl.error_text
            assert cntl.response_payload == b"rpc-over-hbm"
        finally:
            server.stop()
            server.join(timeout=5)


class TestBatchedDispatch:
    """Micro-batched DeviceEndpoint: concurrent calls stack into one
    vmapped dispatch; per-row method ids and correlation ids must route
    independently inside the batch."""

    def test_mixed_methods_in_one_batch(self):
        import threading

        import jax.numpy as jnp
        import numpy as np

        from incubator_brpc_tpu.models.tensor_echo import TensorEchoService
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        svc = TensorEchoService()
        svc.add_method(3, lambda p: p * jnp.uint32(2))
        svc.add_method(5, lambda p: p + jnp.uint32(10))
        ep = DeviceEndpoint(service=svc, window_size=32, max_batch=16)
        ep.warm(64)
        results = {}

        def worker(i):
            mid = (0, 3, 5)[i % 3]
            words = np.full(16, i + 1, dtype=np.uint32)
            pending = ep.call_words(
                words, method_id=mid, correlation_id=i + 1, timeout=60
            )
            assert pending.wait(60)
            results[i] = (mid, pending.error_code, pending.response_words)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(18)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 18
        for i, (mid, code, words) in results.items():
            assert code == 0, (i, code)
            base = i + 1
            want = {0: base, 3: base * 2, 5: base + 10}[mid]
            assert (words == want).all(), (i, mid, words[:4])

    def test_unknown_method_in_batch_errors_only_its_row(self):
        import threading

        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        ep = DeviceEndpoint(window_size=16, max_batch=8)
        ep.warm(32)
        results = {}

        def worker(i):
            mid = 999 if i == 3 else 0
            code, out = ep.call_bytes(
                b"row%02d" % i, method_id=mid, correlation_id=i + 1, timeout=60
            )
            results[i] = (code, out)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (code, out) in results.items():
            if i == 3:
                assert code == 1002, (i, code)  # ENOMETHOD, only this row
            else:
                assert code == 0 and out == b"row%02d" % i, (i, code)


def _transport_counters():
    """count and sum of every stage recorder, value of every dispatch adder."""
    from incubator_brpc_tpu.transport import device

    recs = {
        name: getattr(device, "m_" + name)
        for name in (
            "copy", "credit_wait", "queue_wait", "stack", "launch",
            "cq_wait", "ready", "readback", "wake",
        )
    }
    recs["latency"] = device.device_latency
    device.flush_stage_recorders()  # the sampler thread would, within the second
    out = {k: (r.count(), r.latency_sum()) for k, r in recs.items()}
    for name in (
        "dispatches", "dispatch_rows", "dispatch_pad_rows", "dispatch_words",
        "dispatch_widened_rows",
    ):
        out[name] = getattr(device, "m_" + name).get_value()
    return out


def _wait_until(pred, timeout=10.0):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


STAGES = (
    "copy", "credit_wait", "queue_wait", "stack", "launch",
    "cq_wait", "ready", "readback", "wake",
)


class TestStageRecorders:
    """One timeline per call, stamped where the work happens, fed to one
    always-on recorder per stage when the call returns."""

    def test_one_sample_per_call_and_the_dispatch_adders_add_up(self):
        ep = DeviceEndpoint(window_size=8, max_batch=8)
        sizes = (24, 300, 5000, 24, 300, 24)  # three buckets, mixed
        for size in set(sizes):
            ep.warm(size)
        dispatched = []  # (calls, bucket) of every batch, from the inside
        widened = []  # (its widest bucket, calls of a narrower one) of each
        inner = ep._dispatch_batch

        def recording(bucket, batch):
            dispatched.append((len(batch), bucket))
            widened.append((
                max(entry[0] for entry in batch),
                sum(entry[0] < bucket for entry in batch),
            ))
            inner(bucket, batch)

        ep._dispatch_batch = recording
        before = _transport_counters()
        failures = []

        def caller(i):
            payload = bytes([i + 1]) * sizes[i % len(sizes)]
            for _ in range(3):
                code, out = ep.call_bytes(payload, timeout=60)
                if code or out != payload:
                    failures.append((i, code))

        threads = [threading.Thread(target=caller, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not failures and not any(t.is_alive() for t in threads)
        calls = 12 * 3
        # the adders are fed by the watcher once its callers are awake
        assert _wait_until(
            lambda: _transport_counters()["dispatch_rows"]
            - before["dispatch_rows"] == calls
        )
        after = _transport_counters()
        for stage in STAGES + ("latency",):
            assert after[stage][0] - before[stage][0] == calls, stage
        pow2 = lambda b: 1 << (b - 1).bit_length()  # noqa: E731
        assert sum(b for b, _ in dispatched) == calls
        gained = {k: after[k] - before[k] for k in after if k.startswith("dispatch")}
        assert gained["dispatches"] == len(dispatched)
        assert gained["dispatch_rows"] == calls
        assert gained["dispatch_pad_rows"] == sum(pow2(b) for b, _ in dispatched)
        assert gained["dispatch_pad_rows"] >= gained["dispatch_rows"]
        assert gained["dispatch_words"] == sum(
            pow2(b) * bucket for b, bucket in dispatched
        )
        assert [w for w, _ in widened] == [bucket for _, bucket in dispatched]
        assert gained["dispatch_widened_rows"] == sum(n for _, n in widened)

    def test_stamps_are_monotone_along_a_call(self, endpoint):
        import time

        t0 = time.monotonic_ns()
        pendings = [
            endpoint.call_words(np.full(40, i, dtype=np.uint32), correlation_id=i + 1)
            for i in range(4)
        ]
        for p in pendings:
            assert p.wait(timeout=30) and p.completed()
        t1 = time.monotonic_ns()
        for p in pendings:
            names, stamps = zip(*[(n, t) for n, t in p.timeline() if n != "exit"])
            assert names == (
                "entry", "words", "credit_held", "enqueued", "batched",
                "stacked", "launched", "cq_taken", "ready", "readback", "woke",
            )
            assert all(stamps), names  # every stamp written
            assert list(stamps) == sorted(stamps), list(zip(names, stamps))
            assert t0 <= stamps[0] and stamps[-1] <= t1
            assert p.t_exit == 0  # only the byte adapter returns
            d = p.dispatch
            assert 1 <= d.rows <= d.pad_rows and d.bucket == 64 and d.seq >= 1
        # a second wait does not stamp again
        woke = pendings[0].t_woke
        assert pendings[0].wait(timeout=1) and pendings[0].t_woke == woke

    def test_calls_of_one_dispatch_share_its_record(self):
        ep = DeviceEndpoint(window_size=8, max_batch=8)
        ep.warm(64)
        with ep._qlock:  # hold the drain so that the four calls stack
            ep._draining = True
        pendings = [
            ep.call_words(np.full(16, i, dtype=np.uint32), correlation_id=i + 1)
            for i in range(4)
        ]
        ep._drain()
        for p in pendings:
            assert p.wait(timeout=30) and p.error_code == 0
        assert len({id(p.dispatch) for p in pendings}) == 1
        d = pendings[0].dispatch
        assert (d.rows, d.pad_rows, d.bucket) == (4, 4, 64)

    def test_stage_means_add_up_to_the_total(self, endpoint):
        """queue_wait through readback is credit held -> response parsed
        (device_transport_latency) but for the pad copy and the parse."""
        endpoint.call_bytes(b"warm" * 8, timeout=30)
        before = _transport_counters()
        for i in range(30):
            code, out = endpoint.call_bytes(b"%04d" % i * 8, timeout=30)
            assert code == 0 and out == b"%04d" % i * 8
        after = _transport_counters()
        mean = lambda k: (  # noqa: E731
            (after[k][1] - before[k][1]) / (after[k][0] - before[k][0])
        )
        inside = sum(
            mean(k)
            for k in ("queue_wait", "stack", "launch", "cq_wait", "ready", "readback")
        )
        total = mean("latency")
        assert total > 0 and abs(inside - total) <= 0.10 * total, (inside, total)

    def test_stage_times_reach_the_recorders_through_the_sampler(self):
        """Ten feeds a call on the caller's thread cost 5% of the calls/s at
        256 B (PR 25, on the chip): a completed call appends its numbers
        and bvar's sampler thread feeds the recorders within the second."""
        from incubator_brpc_tpu.transport import device

        before = _transport_counters()
        ingress = device.m_ingress.count()
        # a row holds stamps, not times: stage i is cut (i + 1) us long
        # (the copy: its three pieces 200, 300 and 500 ns), from t = 1 ms
        stamp = dict.fromkeys(device.STAMPS, -1)  # -1: never taken
        stamp.update(seq=7, entry=1_000_000, words=1_000_200)
        stamp["credit_held"] = stamp["words"] + 2000
        stamp["enqueued"] = stamp["credit_held"] + 300
        at = stamp["enqueued"]
        for i, name in enumerate(
            ("batched", "stacked", "launched", "cq_taken", "ready", "readback", "woke"),
            start=3,
        ):
            at = stamp[name] = at + 1000 * i
        stamp["exit"] = stamp["woke"] + 500
        for _ in range(20):
            device._stage_feed.rows.append(tuple(stamp[n] for n in device.STAMPS))
        # came through a server: ingress, no native callback, egress
        stamp.update(cut=stamp["entry"] - 500, sent=stamp["exit"] + 700)
        device._stage_feed.rows.append(tuple(stamp[n] for n in device.STAMPS))
        assert device.m_wake.count() == before["wake"][0]  # they wait
        assert _wait_until(  # no flush of ours: the 1 Hz sampler feeds them
            lambda: device.m_wake.count() - before["wake"][0] == 21, timeout=5
        )
        after = _transport_counters()
        for i, stage in enumerate(STAGES):
            assert after[stage][0] - before[stage][0] == 21, stage
            total = after[stage][1] - before[stage][1]
            assert total == pytest.approx(21 * (i + 1))  # ns in, us out
        assert device.m_ingress.count() - ingress == 1
        assert device.m_wake.max_latency() >= 9.0

    def test_a_call_that_never_reached_the_device_records_nothing(self):
        ep = DeviceEndpoint(window_size=1)
        before = _transport_counters()
        # oversize: the credit comes back, the pending settles with EREQUEST
        p = ep.call_words(np.zeros((1 << 24) + 1, dtype=np.uint32), timeout=5)
        assert p.wait(timeout=5) and p.error_code == ErrorCode.EREQUEST
        assert not p.completed() and ep.inflight == 0
        assert _transport_counters() == before

    def test_device_transport_calls_is_gone(self):
        from incubator_brpc_tpu.bvar import expose_registry
        from incubator_brpc_tpu.transport import device

        names = [name for name, _ in expose_registry.snapshot("device_transport")]
        assert "device_transport_calls" not in names
        assert not hasattr(device, "device_calls")
        for stage in STAGES + ("ingress",):
            assert f"device_transport_{stage}_us" in names
        assert "device_transport_latency" in names

    def test_server_handler_records_ingress_once_a_call(self, endpoint):
        from incubator_brpc_tpu.rpc import Channel, Server
        from incubator_brpc_tpu.transport.device import (
            flush_stage_recorders,
            m_ingress,
        )

        flush_stage_recorders()
        server = Server()
        server.add_service("tensor", {"echo": endpoint.server_handler()})
        assert server.start(0)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{server.port}")
            before = (m_ingress.count(), m_ingress.latency_sum())
            for i in range(5):
                cntl = ch.call_method("tensor", "echo", b"ingress-%d" % i)
                assert cntl.ok(), cntl.error_text
            # a call's row is appended once its response is written, which
            # the client may see first
            assert _wait_until(
                lambda: flush_stage_recorders()
                or m_ingress.count() - before[0] == 5
            )
            # cut off the wire before the handler: a positive time, and
            # far under a second on any machine
            gained = m_ingress.latency_sum() - before[1]
            assert 0 < gained < 5 * 1e6
        finally:
            server.stop()
            server.join(timeout=5)


def _stacked_batch(ep, bucket, b, methods=(0, 7)):
    """A batch of ``b`` queue entries as ``call_words`` makes them (credit
    held, row padded into its bucket), built here so that the batch size
    is the test's and not the timing's. Correlation ids have the top bit
    set; method ids alternate over ``methods``."""
    from incubator_brpc_tpu.transport.device import _PendingCall

    rng = np.random.default_rng(bucket * 131 + b)
    batch, sent = [], []
    for i in range(b):
        assert ep._acquire_credit(5)
        n = bucket - (i % 3)  # not every row fills its bucket
        words = rng.integers(0, 1 << 32, size=n, dtype=np.uint32)
        padded = np.zeros(bucket, dtype=np.uint32)
        padded[:n] = words
        mid = methods[i % len(methods)]
        cid = 0x80000000 | (0x01010101 * (i + 1) & 0x7FFFFFFF)
        pending = _PendingCall()
        pending.t_credit = pending.t_enqueued = time.monotonic_ns()
        batch.append((bucket, np.uint32(mid), padded, np.uint32(cid), pending, n))
        sent.append((mid, cid, words))
    return batch, sent


class _Counting:
    """Stands in for a module name (``device.jax``, ``device.jnp``): the
    named attributes are wrapped to note when they were called, every
    other attribute is the module's own."""

    def __init__(self, module, names, calls):
        self._module = module
        for name in names:
            setattr(self, name, self._noting(name, getattr(module, name), calls))

    @staticmethod
    def _noting(name, fn, calls):
        def noted(*args, **kwargs):
            calls.append((name, time.monotonic_ns()))
            return fn(*args, **kwargs)

        return noted

    def __getattr__(self, name):
        return getattr(self._module, name)


@contextlib.contextmanager
def _response_frames(ep):
    """The device arrays each dispatch of ``ep`` hands its watcher."""
    frames = []
    watch = ep._cq.watch

    def keeping(arrays, **kw):
        frames.append(arrays)
        return watch(arrays, **kw)

    ep._cq.watch = keeping
    try:
        yield frames
    finally:
        ep._cq.watch = watch


XOR = 0x5A5A5A5A


@pytest.fixture(scope="module")
def two_method_endpoint():
    import jax.numpy as jnp

    from incubator_brpc_tpu.models.tensor_echo import TensorEchoService

    svc = TensorEchoService()
    svc.add_method(7, lambda p: p ^ jnp.uint32(XOR))
    return DeviceEndpoint(service=svc, window_size=16, max_batch=16)


def _queue_then_drain(ep, calls, monkeypatch):
    """``calls``: ``(words, method id)`` each. All are queued while the
    drain is held, as calls that arrived together, then one drain runs.
    Returns the pending calls, in queue order, and the batches the drain
    formed, ``(bucket, [correlation ids])`` each, in no order (a batch
    with more behind it leaves on a thread of its own)."""
    batches, lock = [], threading.Lock()
    inner = ep._dispatch_batch

    def recording(bucket, batch):
        with lock:
            batches.append((bucket, [int(entry[3]) for entry in batch]))
        inner(bucket, batch)

    monkeypatch.setattr(ep, "_dispatch_batch", recording)
    with ep._qlock:
        ep._draining = True
    pendings = [
        ep.call_words(words, method_id=mid, correlation_id=i + 1, timeout=60)
        for i, (words, mid) in enumerate(calls)
    ]
    ep._drain()
    for pending in pendings:
        assert pending.wait(timeout=120)
    assert ep.inflight == 0
    return pendings, batches


def _payloads(sizes, seed):
    """Words of ``sizes`` lengths, no two calls alike."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 1 << 32, size=n, dtype=np.uint32) for n in sizes]


def _counters_at_rest():
    """The counters once no watcher of an earlier test is still feeding
    the dispatch adders (it does so after its callers are awake)."""
    last = _transport_counters()
    for _ in range(500):
        time.sleep(0.02)
        now = _transport_counters()
        if now == last:
            return now
        last = now
    raise AssertionError("the transport's counters never came to rest")


def _dispatch_gains(before, dispatches):
    """The dispatch adders' gains since ``before``, once ``dispatches``
    more have completed (the watcher feeds them after its callers wake)."""
    assert _wait_until(
        lambda: _transport_counters()["dispatches"] - before["dispatches"]
        == dispatches
    )
    after = _transport_counters()
    return {k: after[k] - before[k] for k in after if k.startswith("dispatch")}


class TestWidenedBatches:
    """A batch is the FIFO prefix of the queue, whatever the buckets in
    it; its rows are stacked at the widest, under ``MAX_STACKED_WORDS``
    once buckets differ (PR 28)."""

    @pytest.mark.parametrize("buckets", [(64, 256), (64, 16384), (4096, 1024)])
    @pytest.mark.parametrize("b", [2, 3, 5, 16])
    def test_buckets_queued_together_leave_as_one_dispatch(
        self, two_method_endpoint, monkeypatch, buckets, b
    ):
        ep = two_method_endpoint
        widest = max(buckets)
        # alternate the two buckets; not every row fills its own
        sizes = [buckets[i % 2] - (i % 3) for i in range(b)]
        calls = [
            (words, (0, 7)[i % 2])
            for i, words in enumerate(_payloads(sizes, widest + b))
        ]
        before = _counters_at_rest()
        with _response_frames(ep) as frames:
            pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        assert batches == [(widest, list(range(1, b + 1)))]
        pad = 1 << (b - 1).bit_length()
        (frame,) = frames
        assert frame.shape == ((widest + 8,) if pad == 1 else (pad, widest + 8))
        narrower = sum(_bucket_words(n) < widest for n in sizes)
        assert 0 < narrower < b
        for (words, mid), pending in zip(calls, pendings):
            assert pending.error is None and pending.error_code == 0
            want = words ^ np.uint32(XOR) if mid == 7 else words
            np.testing.assert_array_equal(pending.response_words, want)
            d = pending.dispatch
            assert (d.rows, d.pad_rows, d.bucket) == (b, pad, widest)
            assert d.widened_rows == narrower
        assert _dispatch_gains(before, 1) == {
            "dispatches": 1,
            "dispatch_rows": b,
            "dispatch_pad_rows": pad,
            "dispatch_words": pad * widest,
            "dispatch_widened_rows": narrower,
        }

    @pytest.mark.parametrize(
        "queued, formed",
        [
            # the issue's case: a 4 MiB call shares a dispatch with nobody
            # of another bucket, the small ones behind it with each other
            ([1 << 20, 64, 64, 64], [(1 << 20, 1), (64, 3)]),
            ([64, 1 << 20, 64], [(64, 1), (1 << 20, 1), (64, 1)]),
            # 4 rows of 64 Ki words are the cap; a fifth call means 8 rows
            ([1 << 16] + [64] * 6, [(1 << 16, 4), (64, 3)]),
            # once buckets differ the cap holds for the widest bucket too
            ([64] + [1 << 16] * 5, [(1 << 16, 4), (1 << 16, 2)]),
            # 16 rows x 16 Ki words: the whole window of the widest size
            # of the benchmark's mix, exactly at the cap
            ([64, 1 << 14] * 8, [(1 << 14, 16)]),
            ([1 << 15, 64] * 5, [(1 << 15, 8), (1 << 15, 2)]),
        ],
    )
    def test_the_cap_ends_a_batch_and_the_order_is_kept(
        self, two_method_endpoint, monkeypatch, queued, formed
    ):
        from incubator_brpc_tpu.transport.device import MAX_STACKED_WORDS

        assert MAX_STACKED_WORDS == 1 << 18
        ep = two_method_endpoint
        calls = [(words, 0) for words in _payloads(queued, len(queued))]
        pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        batches.sort(key=lambda batch: batch[1][0])
        assert [(bucket, len(cids)) for bucket, cids in batches] == formed
        # the batches are consecutive runs of the queue: nothing overtook
        assert [c for _, cids in batches for c in cids] == list(
            range(1, len(queued) + 1)
        )
        for (words, _), pending in zip(calls, pendings):
            assert pending.error_code == 0
            np.testing.assert_array_equal(pending.response_words, words)
            d = pending.dispatch
            if d.widened_rows:
                assert d.pad_rows * d.bucket <= MAX_STACKED_WORDS

    @pytest.mark.parametrize(
        "bucket, queued, max_batch, formed",
        [
            (64, 5, 4, [4, 1]),
            (256, 16, 16, [16]),
            # one bucket stacks whatever its size, as it always did:
            # 8 rows of 64 Ki words are twice the cap
            (1 << 16, 8, 8, [8]),
        ],
    )
    def test_a_queue_of_one_bucket_forms_the_batches_it_always_did(
        self, monkeypatch, bucket, queued, max_batch, formed
    ):
        ep = DeviceEndpoint(window_size=16, max_batch=max_batch)
        sizes = [bucket - (i % 3) for i in range(queued)]
        calls = [(words, 0) for words in _payloads(sizes, bucket)]
        before = _counters_at_rest()
        pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        batches.sort(key=lambda batch: batch[1][0])
        assert [(b, len(cids)) for b, cids in batches] == [
            (bucket, n) for n in formed
        ]
        for (words, _), pending in zip(calls, pendings):
            assert pending.error_code == 0
            np.testing.assert_array_equal(pending.response_words, words)
        gained = _dispatch_gains(before, len(formed))
        assert gained["dispatch_rows"] == queued
        assert gained["dispatch_widened_rows"] == 0

    @pytest.mark.parametrize("flagged", [0, 2, 5])
    def test_a_flagged_frame_in_a_widened_batch_fails_only_its_call(
        self, two_method_endpoint, monkeypatch, flagged
    ):
        ep = two_method_endpoint
        sizes = [40, 1000, 64, 4000, 200, 7]
        calls = [
            (words, 999 if i == flagged else (0, 7)[i % 2])
            for i, words in enumerate(_payloads(sizes, flagged))
        ]
        pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        assert batches == [(4096, [1, 2, 3, 4, 5, 6])]
        for i, ((words, mid), pending) in enumerate(zip(calls, pendings)):
            assert pending.error is None
            if i == flagged:
                assert pending.error_code == 1002  # ENOMETHOD, this row only
                assert not pending.response_words.any()
            else:
                assert pending.error_code == 0, i
                want = words ^ np.uint32(XOR) if mid == 7 else words
                np.testing.assert_array_equal(pending.response_words, want)


class TestLaunch:
    """The launch of a dispatch is one call of the jitted program on the
    stacked host rows (PR 26)."""

    @pytest.mark.parametrize("bucket", [64, 4096])
    @pytest.mark.parametrize("b", [1, 2, 3, 5, 16])
    def test_every_row_of_a_batch_answers_its_own_request(
        self, two_method_endpoint, b, bucket
    ):
        ep = two_method_endpoint
        batch, sent = _stacked_batch(ep, bucket, b)
        with _response_frames(ep) as frames:
            ep._dispatch_batch(bucket, batch)
        for entry in batch:
            assert entry[4].wait(timeout=60)
        assert ep.inflight == 0
        (frame,) = frames
        assert frame.devices() == {ep.device}
        host = np.asarray(frame).reshape(-1, bucket + 8)
        assert host.shape[0] == 1 << (b - 1).bit_length()
        for i, (mid, cid, words) in enumerate(sent):
            pending = batch[i][4]
            assert pending.error is None and pending.error_code == 0, i
            want = words ^ np.uint32(XOR) if mid == 7 else words
            np.testing.assert_array_equal(pending.response_words, want)
            assert pending.response_words.dtype == np.uint32
            # the header the device wrote: this row's ids, whole
            assert cid >= 1 << 31 and int(host[i, 3]) == cid, i
            assert int(host[i, 4]) == 0 and int(host[i, 5]) == mid, i
            d = pending.dispatch
            assert (d.rows, d.bucket) == (b, bucket)
            assert 0 < d.t_stacked <= d.t_launched <= d.t_readback

    def test_the_rows_land_on_the_endpoints_device_not_the_default(self):
        import jax

        other = jax.devices()[-1]
        assert other != jax.devices()[0]  # conftest: 8 virtual devices
        ep = DeviceEndpoint(device=other, window_size=4, max_batch=4)
        for b in (1, 3):
            batch, sent = _stacked_batch(ep, 64, b, methods=(0,))
            with _response_frames(ep) as frames:
                ep._dispatch_batch(64, batch)
            for (_, _, words), entry in zip(sent, batch):
                assert entry[4].wait(timeout=60) and entry[4].error_code == 0
                np.testing.assert_array_equal(entry[4].response_words, words)
            assert [f.devices() for f in frames] == [{other}]

    def test_nothing_but_the_program_call_between_stacked_and_launched(
        self, two_method_endpoint, monkeypatch
    ):
        from incubator_brpc_tpu.transport import device

        ep = two_method_endpoint
        ep.warm(64 * 4)
        calls = []
        monkeypatch.setattr(
            device, "jax", _Counting(device.jax, ("device_put",), calls)
        )
        # the proxy does see a call (the frame's own jnp calls are the
        # service's since PR 37, traced inside the program call)
        device.jax.device_put(np.zeros(1, np.uint32))
        assert [name for name, _ in calls] == ["device_put"]
        calls.clear()
        launched = []
        for b in (1, 2, 5, 16):
            batch, _ = _stacked_batch(ep, 64, b)
            ep._dispatch_batch(64, batch)
            for entry in batch:
                assert entry[4].wait(timeout=60) and entry[4].error_code == 0
            d = batch[0][4].dispatch
            launched.append((d.t_stacked, d.t_launched))
            assert 0 < d.t_stacked <= d.t_launched
        assert calls == []
        # a cold geometry compiles inside its program call and stages nothing
        batch, _ = _stacked_batch(ep, 128, 1)
        ep._dispatch_batch(128, batch)
        assert batch[0][4].wait(timeout=60)
        assert calls == []


def _compiles(caplog):
    """What ``jax.log_compiles`` said of the endpoint's two programs (the
    harness's ``jnp.uint32(1)`` is a program of its own, once a process)."""
    return [
        r.getMessage() for r in caplog.records
        if r.name == "jax._src.interpreters.pxla"
        and r.getMessage().startswith("Compiling jit(step_")
    ]


def _harness_warm(ep, sizes, callers):
    """The benchmark's own warm-up (``benchmark/deployments/device_echo.py``:
    both programs by name, with arrays committed to the device), run on
    ``ep`` through a stand-in for the deployment."""
    import sys
    import types

    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.deployments import device_echo

    device_echo.Deployment.warm(
        types.SimpleNamespace(endpoint=ep, _jax=jax),
        {"callers": callers, "sizes": sizes},
    )


class TestWarmUpHolds:
    """A dispatch of a warmed geometry compiles nothing, whichever route
    warmed it."""

    BUCKETS = (64, 1024)
    BATCHES = (1, 2, 3, 4, 7, 8)

    def _dispatch_all(self, ep):
        for bucket in self.BUCKETS:
            for b in self.BATCHES:
                batch, sent = _stacked_batch(ep, bucket, b, methods=(0,))
                ep._dispatch_batch(bucket, batch)
                for (_, _, words), entry in zip(sent, batch):
                    assert entry[4].wait(timeout=60)
                    assert entry[4].error_code == 0
                    np.testing.assert_array_equal(entry[4].response_words, words)

    def _sizes(self, ep):
        return ep._program._cache_size(), ep._batch_program._cache_size()

    @pytest.mark.parametrize("device_index", [0, 3])
    def test_after_warm(self, caplog, device_index):
        import jax

        ep = DeviceEndpoint(
            device=jax.devices()[device_index], window_size=8, max_batch=8
        )
        with jax.log_compiles():
            for bucket in self.BUCKETS:
                ep.warm(bucket * 4)
            assert len(_compiles(caplog)) == 2 * 4  # the detector detects
            sizes = self._sizes(ep)
            assert sizes == (2, 6)
            caplog.clear()
            self._dispatch_all(ep)
        assert _compiles(caplog) == []
        assert self._sizes(ep) == sizes

    @pytest.mark.parametrize("device_index", [0, 3])
    def test_after_the_harness_route(self, caplog, device_index):
        """Committed arrays into both programs by name. The executables
        are shared with the host-array route (``in_shardings``); jit's
        fast path keeps one entry per argument kind, so ``_cache_size``
        grows by that entry on the first dispatch of a geometry, without
        a compile, and holds from there."""
        import jax

        ep = DeviceEndpoint(
            device=jax.devices()[device_index], window_size=8, max_batch=8
        )
        with jax.log_compiles():
            _harness_warm(ep, [4 * b for b in self.BUCKETS], callers=8)
            assert len(_compiles(caplog)) == 2 * 4
            warmed = self._sizes(ep)
            assert warmed == (2, 6)
            caplog.clear()
            self._dispatch_all(ep)
            assert _compiles(caplog) == []
            sizes = self._sizes(ep)
            assert sizes == (4, 12)  # one fast-path entry more a geometry
            self._dispatch_all(ep)
        assert _compiles(caplog) == []
        assert self._sizes(ep) == sizes


# -- PR 53: a dispatch's operand is built in one pass --------------------------


@contextlib.contextmanager
def _operands(ep):
    """The arrays each dispatch of ``ep`` hands its program, as they are
    (not copies: nothing writes to an operand once it is built)."""
    seen = []
    one, many = ep._program, ep._batch_program

    def row(rows, cids, mids, dispatch=None):
        seen.append(rows)
        return one(rows, cids, mids, dispatch)

    def stacked(rows, cids, mids, dispatch=None):
        seen.append(rows)
        return many(rows, cids, mids, dispatch)

    ep._program, ep._batch_program = row, stacked
    try:
        yield seen
    finally:
        ep._program, ep._batch_program = one, many


def _as_call_bytes_sees(payload: bytes) -> np.ndarray:
    return np.frombuffer(payload, dtype=np.uint8 if len(payload) % 4 else np.uint32)


def _the_parents_operand(payloads, bucket):
    """The array PR 53's parent handed the program: every call's bytes
    padded to words, copied into a zeroed row of its own bucket, and that
    row into a zeroed ``(bpad, bucket)`` array."""
    rows = np.zeros((1 << (len(payloads) - 1).bit_length(), bucket), np.uint32)
    for i, payload in enumerate(payloads):
        words = np.frombuffer(payload + b"\x00" * (-len(payload) % 4), np.uint32)
        padded = np.zeros(_bucket_words(max(1, words.size)), np.uint32)
        padded[: words.size] = words
        rows[i, : padded.size] = padded
    return rows


def _operand_counters():
    from incubator_brpc_tpu.transport import device

    return {
        name: getattr(device, "m_" + name).get_value()
        for name in (
            "dispatches", "dispatch_words", "dispatch_zeroed_words",
            "dispatch_borrowed",
        )
    }


@pytest.fixture(params=["native", "numpy"])
def stacker(request, monkeypatch):
    """Both writers of a stacked operand: ``tb_stack_rows``, and numpy where
    the library is absent."""
    from incubator_brpc_tpu import native

    if request.param == "numpy":
        monkeypatch.setattr(native, "LIB", None)
    elif native.LIB is None:
        pytest.skip("the native library could not be had here")
    return request.param


OPERAND_BYTES = [1, 3, 255, 256, 65_537, 1_048_576]


class TestOperand:
    """The array a dispatch hands its program is word for word the one the
    parent built by zeroing twice and copying twice (PR 53)."""

    @pytest.mark.parametrize("nbytes", OPERAND_BYTES)
    def test_a_call_alone(self, two_method_endpoint, stacker, nbytes):
        ep = two_method_endpoint
        payload = np.random.default_rng(nbytes).bytes(nbytes)
        with _operands(ep) as seen:
            code, out = ep.call_bytes(payload, timeout=120)
        assert code == 0 and out == payload
        (operand,) = seen
        bucket = _bucket_words(-(-nbytes // 4))
        assert operand.dtype == np.uint32 and operand.shape == (bucket,)
        np.testing.assert_array_equal(
            operand, _the_parents_operand([payload], bucket)[0])

    @pytest.mark.parametrize("nbytes", OPERAND_BYTES)
    def test_a_batch_of_three_has_a_pad_row_of_zeros(
        self, two_method_endpoint, stacker, monkeypatch, nbytes
    ):
        ep = two_method_endpoint
        rng = np.random.default_rng(nbytes + 3)
        # the second a word shorter where there is a word to spare
        payloads = [rng.bytes(n) for n in (nbytes, max(1, nbytes - 4), nbytes)]
        bucket = _bucket_words(-(-nbytes // 4))
        calls = [(_as_call_bytes_sees(p), 0) for p in payloads]
        with _operands(ep) as seen:
            pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        assert batches == [(bucket, [1, 2, 3])]
        (operand,) = seen
        assert operand.dtype == np.uint32 and operand.shape == (4, bucket)
        np.testing.assert_array_equal(
            operand, _the_parents_operand(payloads, bucket))
        assert not operand[3].any()
        for payload, pending in zip(payloads, pendings):
            assert pending.error_code == 0
            assert pending.response_words.tobytes()[: len(payload)] == payload

    @pytest.mark.parametrize(
        "sizes", [(100, 4000, 3), (4096, 1, 255, 256, 1024), (65_537, 256)])
    def test_a_batch_of_mixed_buckets(
        self, two_method_endpoint, stacker, monkeypatch, sizes
    ):
        ep = two_method_endpoint
        rng = np.random.default_rng(sum(sizes))
        payloads = [rng.bytes(n) for n in sizes]
        widest = _bucket_words(-(-max(sizes) // 4))
        calls = [(_as_call_bytes_sees(p), 0) for p in payloads]
        with _operands(ep) as seen:
            pendings, batches = _queue_then_drain(ep, calls, monkeypatch)
        assert batches == [(widest, list(range(1, len(sizes) + 1)))]
        (operand,) = seen
        np.testing.assert_array_equal(
            operand, _the_parents_operand(payloads, widest))
        for payload, pending in zip(payloads, pendings):
            assert pending.error_code == 0
            assert pending.response_words.tobytes()[: len(payload)] == payload
            assert pending.dispatch.zeroed_words == operand.size - sum(
                -(-n // 4) for n in sizes)

    @pytest.mark.parametrize("nbytes", [256, 65_536, 1_048_576])
    def test_a_call_alone_that_fills_its_row_is_handed_over_as_it_lies(
        self, two_method_endpoint, nbytes
    ):
        ep = two_method_endpoint
        payload = np.random.default_rng(nbytes).bytes(nbytes)
        with _operands(ep) as seen:
            code, out = ep.call_bytes(payload, timeout=120)
        assert code == 0 and out == payload
        (operand,) = seen
        assert np.shares_memory(operand, np.frombuffer(payload, np.uint8))
        assert not operand.flags.writeable
        assert operand.dtype == np.uint32 and operand.shape == (nbytes // 4,)

    @pytest.mark.parametrize("nbytes, dtype", [
        (252, np.uint32),  # a word short of its row
        (255, np.uint8),  # bytes that end inside a word
        (256, np.uint8),  # bytes that would fill the row, not given as words
        (260, np.uint32),  # a word over: a row of 128
    ])
    def test_a_call_that_does_not_fill_its_row_as_words_is_copied(
        self, two_method_endpoint, nbytes, dtype
    ):
        ep = two_method_endpoint
        payload = np.random.default_rng(nbytes).bytes(nbytes)
        given = np.frombuffer(payload, dtype)
        with _operands(ep) as seen:
            pending = ep.call_words(given, timeout=120)
            assert pending.wait(120) and pending.error_code == 0
        (operand,) = seen
        assert not np.shares_memory(operand, given)
        assert operand.flags.writeable  # the dispatch's own array
        np.testing.assert_array_equal(
            operand, _the_parents_operand([payload], operand.size)[0])
        assert pending.response_words.tobytes()[:nbytes] == payload

    @pytest.mark.parametrize("words", [10, 64, 100])
    def test_a_writeable_array_may_be_written_once_call_words_returned(
        self, two_method_endpoint, words
    ):
        ep = two_method_endpoint
        with ep._qlock:
            ep._draining = True  # the call waits in the queue
        given = np.arange(1, words + 1, dtype=np.uint32)
        sent = given.copy()
        with _operands(ep) as seen:
            pending = ep.call_words(given, timeout=120)
            given[:] = 0xDEADBEEF
            ep._drain()
            assert pending.wait(120) and pending.error_code == 0
        np.testing.assert_array_equal(pending.response_words, sent)
        (operand,) = seen
        assert not np.shares_memory(operand, given)
        np.testing.assert_array_equal(operand[:words], sent)
        assert not operand[words:].any()

    @pytest.mark.parametrize("how", ["strided", "unaligned"])
    def test_a_read_only_array_that_is_no_aligned_run_of_words_is_copied(
        self, two_method_endpoint, how
    ):
        ep = two_method_endpoint
        if how == "strided":
            given = np.arange(128, dtype=np.uint32)[::2]
            given.setflags(write=False)
        else:
            given = np.frombuffer(bytes(range(256)) + b"\0", np.uint8)[1:].view(np.uint32)
            assert not given.flags.aligned and not given.flags.writeable
        with _operands(ep) as seen:
            pending = ep.call_words(given, timeout=120)
            assert pending.wait(120) and pending.error_code == 0
        np.testing.assert_array_equal(pending.response_words, given)
        # it fills its row, and the row is the endpoint's copy of it
        assert seen[0].flags.aligned and not np.shares_memory(seen[0], given)
        assert pending.dispatch.borrowed == 1

    def test_words_of_another_dtype_are_cast_as_the_padded_row_cast_them(
        self, two_method_endpoint
    ):
        ep = two_method_endpoint
        given = np.arange(1, 65, dtype=np.int64)
        with _operands(ep) as seen:
            pending = ep.call_words(given, timeout=120)
            assert pending.wait(120) and pending.error_code == 0
        np.testing.assert_array_equal(pending.response_words, given)
        assert seen[0].dtype == np.uint32 and seen[0].shape == (64,)

    def test_the_two_adders_over_a_known_sequence(
        self, two_method_endpoint, stacker, monkeypatch
    ):
        ep = two_method_endpoint
        _counters_at_rest()
        before = _operand_counters()
        full, short = bytes(range(256)), bytes(range(100))
        # a call alone that fills its row: borrowed, nothing zeroed
        assert ep.call_bytes(full, timeout=120) == (0, full)
        # a call alone that does not: its tail, 64 - 25 words
        assert ep.call_bytes(short, timeout=120) == (0, short)
        # three in a dispatch: a tail of 39 words and a pad row of 64
        calls = [(_as_call_bytes_sees(p), 0) for p in (full, short, full)]
        _queue_then_drain(ep, calls, monkeypatch)
        assert _wait_until(
            lambda: _operand_counters()["dispatches"] - before["dispatches"] == 3)
        after = _operand_counters()
        assert {k: after[k] - before[k] for k in after} == {
            "dispatches": 3,
            "dispatch_words": 64 + 64 + 4 * 64,
            "dispatch_zeroed_words": 0 + 39 + (39 + 64),
            "dispatch_borrowed": 1,
        }

    def test_the_native_writer_refuses_a_call_longer_than_a_row(self):
        from incubator_brpc_tpu import native
        from incubator_brpc_tpu.transport.device import _stack_rows

        if native.LIB is None:
            pytest.skip("the native library could not be had here")
        rows = np.full((2, 64), 7, dtype=np.uint32)
        with pytest.raises(ValueError):
            _stack_rows(rows, [np.zeros(65, np.uint32)])
        with pytest.raises(ValueError):
            _stack_rows(rows, [np.zeros(1, np.uint32)] * 3)
        assert (rows == 7).all()  # nothing was written
