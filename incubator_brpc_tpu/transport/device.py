"""Device transport — the ``transport=tpu`` slot (reference analog:
src/brpc/rdma/rdma_endpoint.h:42-213 per-connection QP with send/recv
rings and credit-window flow control, block_pool.h registered-memory
blocks, rdma_completion_queue CQ delivery).

A ``DeviceEndpoint`` is the RdmaEndpoint re-thought for XLA:

- the "registered memory" is HBM itself: requests are framed into uint32
  device buffers (ops/framing), the *entire server hot path* — parse,
  verify, dispatch, handle, respond — is one fused XLA computation
  (models/tensor_echo), and only the response crosses back;
- the "credit window" bounds in-flight device dispatches
  (``window_size``, like _local_window_capacity rdma_endpoint.h:176-195):
  callers park on a butex when the window is full, completions release
  credits;
- the "completion queue" is a DeviceCompletionButex watcher
  (rdma_completion_queue delivering CQ events, here PJRT readiness);
- frames are bucketed to power-of-two payload sizes so XLA compiles one
  program per geometry and reuses it (static shapes; the block-pool
  fixed-block discipline applied to programs instead of buffers).

``DeviceEndpoint.call_bytes`` adapts the host byte world: payloads are
padded into the bucket and responses trimmed to the request's length
(handlers are shape-preserving word transforms). ``server_handler`` plugs
an endpoint into an ordinary Server method map, giving the full
host-RPC → HBM → fused-step → response path — the reference's
"flip transport=tpu and rerun the same example pair" moment (SURVEY §7
step 5).
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from incubator_brpc_tpu.bvar import Adder, LatencyRecorder
from incubator_brpc_tpu.ops import framing
from incubator_brpc_tpu.runtime.butex import Butex, ETIMEDOUT
from incubator_brpc_tpu.runtime.device_butex import DeviceCompletionButex
from incubator_brpc_tpu.utils.status import ErrorCode

MIN_BUCKET_WORDS = 64
MAX_BUCKET_WORDS = 1 << 24  # 64 MiB of uint32

device_calls = Adder(name="device_transport_calls")
device_latency = LatencyRecorder(name="device_transport_latency")


def _bucket_words(n: int) -> int:
    b = MIN_BUCKET_WORDS
    while b < n:
        b <<= 1
    if b > MAX_BUCKET_WORDS:
        raise ValueError(f"payload of {n} words exceeds max bucket")
    return b


class _PendingCall:
    __slots__ = ("ready", "response_words", "error_code", "error", "_t0")

    def __init__(self):
        self.ready = Butex(0)
        self.response_words = None
        self.error_code = 0
        self.error: Optional[BaseException] = None
        self._t0 = 0.0

    def settle(self) -> None:
        self.ready.add(1)
        self.ready.wake_all()

    def wait(self, timeout: Optional[float]) -> bool:
        while self.ready.load() == 0:
            if self.ready.wait(0, timeout=timeout) == ETIMEDOUT:
                return False
        return True


class DeviceEndpoint:
    """One device-resident service behind a credit window."""

    def __init__(
        self,
        service=None,
        device=None,
        window_size: int = 8,
        max_batch: int = 16,
    ):
        from collections import deque

        from incubator_brpc_tpu.models.tensor_echo import TensorEchoService

        self.service = service or TensorEchoService()
        self.device = device if device is not None else jax.devices()[0]
        self.window_size = window_size
        # Micro-batching: concurrent same-bucket calls stack into ONE
        # [B, width] dispatch of the vmapped step (batch sizes padded to
        # powers of two so jit compiles a handful of programs, not one
        # per B). This is the TPU-idiomatic fix for per-dispatch fixed
        # costs: 16 concurrent callers pay ~1-2 dispatches, not 16 — and
        # the stacked rows feed the MXU together. Clamped to the window:
        # at most window_size calls hold credits concurrently, so a
        # larger batch ceiling could never form.
        self.max_batch = max(1, min(max_batch, window_size))
        self._credits = Butex(window_size)
        self._cq = DeviceCompletionButex()
        self._queue = deque()  # (bucket, mid_u32, row, cid_u32, pending, n)
        self._qlock = threading.Lock()
        self._draining = False
        # frame-building fused INTO the jitted program; the batched form
        # vmaps the same fused step over stacked rows (jit's per-shape
        # cache gives one compiled program per (batch, bucket) geometry —
        # the fixed-block discipline)
        self._program = jax.jit(
            lambda padded, cid_lo, mid: self.service.step(
                framing.frame(
                    padded, (cid_lo, jnp.uint32(0)), method_id=mid
                )
            )
        )
        self._batch_program = jax.jit(
            jax.vmap(
                lambda padded, cid_lo, mid: self.service.step(
                    framing.frame(
                        padded, (cid_lo, jnp.uint32(0)), method_id=mid
                    )
                )
            )
        )

    # -- credit window (rdma_endpoint.h:176-195) ----------------------------

    def _acquire_credit(self, timeout: Optional[float]) -> bool:
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            c = self._credits.load()
            if c > 0 and self._credits.compare_exchange(c, c - 1):
                return True
            if c > 0:
                continue  # CAS race: retry
            remaining = None
            if deadline is not None:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    return False
            self._credits.wait(0, timeout=remaining)

    def _release_credit(self) -> None:
        self._credits.add(1)
        self._credits.wake(1)  # one credit frees one waiter, no herd

    @property
    def inflight(self) -> int:
        return self.window_size - self._credits.load()

    # -- call paths ---------------------------------------------------------

    def call_words(
        self,
        payload_words: np.ndarray,
        method_id: int = 0,
        correlation_id: int = 1,
        timeout: Optional[float] = 10.0,
    ) -> _PendingCall:
        """Async: frame → HBM → dispatch fused step → watch completion.
        Returns a _PendingCall the caller can wait on; the credit is held
        until the response settles (the per-WR ack discipline)."""
        pending = _PendingCall()
        if not self._acquire_credit(timeout):
            pending.error_code = ErrorCode.EOVERCROWDED
            pending.settle()
            return pending
        device_calls << 1
        pending._t0 = _time.monotonic()
        n = payload_words.shape[0]
        try:
            bucket = _bucket_words(max(1, n))
        except ValueError:
            # oversized payload: the credit MUST come back (a leak here
            # shrinks the window forever) and the caller gets the settled-
            # pending contract, not a raw exception
            self._release_credit()
            pending.error_code = ErrorCode.EREQUEST
            pending.settle()
            return pending
        padded = np.zeros(bucket, dtype=np.uint32)
        padded[:n] = payload_words
        with self._qlock:
            self._queue.append(
                (
                    bucket,
                    np.uint32(method_id),
                    padded,
                    np.uint32(correlation_id & 0xFFFFFFFF),
                    pending,
                    n,
                )
            )
            if self._draining:
                return pending  # the live drainer will pick it up
            self._draining = True
        # a DEDICATED thread, not a worker-pool fiber: handler fibers
        # block waiting on these dispatches, so a saturated pool could
        # strand the drainer behind the very callers it must unblock
        threading.Thread(
            target=self._drain, name="tbrpc-dev-batch", daemon=True
        ).start()
        return pending

    # -- the batching drainer (single-drainer, like the link's _kick) -------

    def _drain(self) -> None:
        while True:
            with self._qlock:
                if not self._queue:
                    self._draining = False
                    return
                # group the head run of SAME-BUCKET entries (shape =
                # program identity); mids/cids are per-row arguments
                bucket = self._queue[0][0]
                batch = []
                while (
                    self._queue
                    and self._queue[0][0] == bucket
                    and len(batch) < self.max_batch
                ):
                    batch.append(self._queue.popleft())
                more = bool(self._queue)
            if more:
                # staggered arrivals: submit THIS batch on its own thread
                # so the next batch's host→device submission overlaps it
                # — a single submitting thread
                # would serialize exactly the fixed costs the window
                # exists to overlap (dedicated threads for the same
                # reason as _drain itself)
                threading.Thread(
                    target=self._dispatch_batch,
                    args=(bucket, batch),
                    name="tbrpc-dev-batch-tx",
                    daemon=True,
                ).start()
            else:
                self._dispatch_batch(bucket, batch)

    def _dispatch_batch(self, bucket: int, batch: list) -> None:
        b = len(batch)
        # pad the batch to a power of two so jit compiles O(log max_batch)
        # programs per bucket; pad rows are zero frames whose (flagged-
        # garbage) response rows are simply ignored
        bpad = 1
        while bpad < b:
            bpad <<= 1
        rows = np.zeros((bpad, bucket + 0), dtype=np.uint32)
        cids = np.zeros(bpad, dtype=np.uint32)
        mids = np.zeros(bpad, dtype=np.uint32)
        for i, (_, mid, padded, cid, _p, _n) in enumerate(batch):
            rows[i] = padded
            cids[i] = cid
            mids[i] = mid
        try:
            if bpad == 1:
                response = self._program(  # single call: no vmap overhead
                    jax.device_put(jnp.asarray(rows[0]), self.device),
                    jnp.uint32(int(cids[0])),
                    jnp.uint32(int(mids[0])),
                )
            else:
                response = self._batch_program(
                    jax.device_put(jnp.asarray(rows), self.device),
                    jnp.asarray(cids),
                    jnp.asarray(mids),
                )
        except Exception as e:  # dispatch failed: settle the whole batch
            for _, _mid, _padded, _cid, pending, _n in batch:
                self._release_credit()
                pending.error = e
                pending.error_code = ErrorCode.EINTERNAL
                pending.settle()
            return

        def on_complete(arrays, error, _batch=batch, _single=(bpad == 1)):
            try:
                host = None
                if error is None:
                    host = np.asarray(jax.device_get(arrays))
            except Exception as e:  # noqa: BLE001 — fetch failed
                error, host = e, None
            for i, (_, _mid, _padded, _cid, pending, n) in enumerate(_batch):
                try:
                    if error is not None:
                        pending.error = error
                        pending.error_code = ErrorCode.EINTERNAL
                    else:
                        row = host if _single else host[i]
                        _, words, err = _parse_response(row)
                        pending.error_code = int(err)
                        pending.response_words = words[:n]
                    device_latency << (
                        _time.monotonic() - pending._t0
                    ) * 1e6
                except Exception as e:  # noqa: BLE001 — parse failed
                    pending.error = e
                    pending.error_code = ErrorCode.EINTERNAL
                    pending.response_words = None
                finally:
                    self._release_credit()
                    pending.settle()

        self._cq.watch(response, on_complete=on_complete)

    def call_bytes(
        self,
        payload: bytes,
        method_id: int = 0,
        correlation_id: int = 1,
        timeout: Optional[float] = 10.0,
    ) -> Tuple[int, bytes]:
        """Sync byte adapter: pad to words, run, trim the response to the
        request's byte length (handlers are shape-preserving)."""
        nbytes = len(payload)
        pad = (-nbytes) % 4
        words = np.frombuffer(payload + b"\x00" * pad, dtype=np.uint32)
        # ONE deadline budget across credit-wait + completion-wait
        deadline = None if timeout is None else _time.monotonic() + timeout
        pending = self.call_words(
            words, method_id=method_id, correlation_id=correlation_id,
            timeout=timeout,
        )
        remaining = None
        if deadline is not None:
            remaining = max(0.0, deadline - _time.monotonic())
        if not pending.wait(remaining):
            return ErrorCode.ERPCTIMEDOUT, b""
        if pending.error_code:
            return pending.error_code, b""
        return 0, pending.response_words.tobytes()[:nbytes]

    def warm(self, payload_bytes: int, timeout: float = 300.0) -> None:
        """Compile every (batch, bucket) geometry this payload size can hit
        — single + each power-of-two batch up to max_batch — so a timed or
        latency-sensitive workload never pays XLA compilation mid-flight.
        Batch formation depends on arrival timing, so a concurrency burst
        does NOT reliably warm the larger geometries; this does."""
        n_words = max(1, (payload_bytes + 3) // 4)
        bucket = _bucket_words(n_words)
        row = np.zeros(bucket, dtype=np.uint32)
        outs = [
            self._program(
                jax.device_put(jnp.asarray(row), self.device),
                jnp.uint32(1),
                jnp.uint32(0),
            )
        ]
        b = 2
        while b <= self.max_batch:
            rows = np.zeros((b, bucket), dtype=np.uint32)
            outs.append(
                self._batch_program(
                    jax.device_put(jnp.asarray(rows), self.device),
                    jnp.zeros(b, dtype=jnp.uint32),
                    jnp.zeros(b, dtype=jnp.uint32),
                )
            )
            b <<= 1
        jax.block_until_ready(outs)

    # -- host-plane integration --------------------------------------------

    def server_handler(self, method_id: int = 0, timeout: float = 60.0):
        """An ordinary Server handler that delegates to this endpoint: the
        request payload goes to HBM, the fused step runs, the response
        comes back — RPC in, device compute, RPC out. ``timeout`` budgets
        credit-wait + queued-batch dispatch + completion (under bursts a
        call may ride the second or third micro-batch)."""

        def handler(cntl, request: bytes) -> bytes:
            code, out = self.call_bytes(
                request,
                method_id=method_id,
                correlation_id=cntl.call_id or 1,
                timeout=timeout,
            )
            if code:
                cntl.set_failed(code, f"device call failed ({code})")
                return b""
            return out

        return handler


def _parse_response(host_frame: np.ndarray):
    """Host-side parse of a device response frame (the 8-word header layout
    of ops/framing.py, read with numpy — no second device round-trip).
    Word 7 is the error code on responses."""
    header = host_frame[: framing.HEADER_WORDS]
    payload = host_frame[framing.HEADER_WORDS :]
    return header, payload, header[7]
