"""A prefill node streaming one layer's KV blocks into a decode node's page
pool, HBM to HBM (Mooncake, arXiv:2407.00079; the configuration's file has
the shapes): ``link_stream.py``'s StreamingRPC over
``Channel(transport="tpu")``, with the messages **device arrays** that
cross by the link's lane and the sink a ``KvPagePool`` on the far chip. The
link's checks and its single-controller set-up are ``link_echo.py``'s.

One stream a caller, opened in ``channel()`` before the first transfer and
held until ``close()``. One *transfer* is one call of the harness: a
64-byte bytes message that names the pages (caller, transfer, first page,
blocks), then the transfer's blocks as consecutive ``Stream.write``s of a
``jax.Array`` each (``EAGAIN`` and ``EOVERCROWDED`` are retried), then the
decode side's receipt. The decode handler writes every batch it is handed
into the pool (``KvPagePool.write``, donated) and keeps the pool.

The harness stops a call's clock when ``call_method`` returns and then
reads ``response_payload``: that first read judges the transfer (its pages
against content regenerated on the decode chip, one scalar read back; warm
transfers also on the host, against ``references/kv_block_pool.py``) and
makes the next transfer's blocks on the prefill chip, both outside every
clock. The harness's seeded host payload is not sent: the blocks are born
on the device, and ``--seed`` enters through their content.

A program without the lane (``DeviceLink.lane_send``) or the pool cannot
run this: ``Deployment`` raises before a stream is opened.

Off the TPU the harness cuts a transfer to 4 KiB: 4 blocks of 1 KiB and a
pool of the configuration's ``rehearsal_pages``, said on a line of its own.
"""

from __future__ import annotations

import argparse
import struct
import threading
import time
from collections import deque

import numpy as np

from benchmark import manifest

_link = manifest.load_module("deployments", "link_echo.py")

OPENING = struct.Struct("<QQQQ")  # caller, transfer, first page, blocks
OPENING_BYTES = 64
COUNTS = struct.Struct("<QQ")  # a transfer's bytes and messages
WRITE_WAIT_S = 10.0  # one write parked on the window before it says EAGAIN

# flip_bit: one bit of a transfer's first block flips on the way into the
# pool; stale: a transfer's blocks are the transfer before's; reorder: its
# first two blocks land in each other's pages; host_bytes: the blocks are
# written as bytes messages (a read-back on the prefill chip, the byte
# stream, a host-to-device copy on the decode chip), which breaks
# guarantee (4) and nothing else: the A/B the lane is for
CONTROLS = ("flip_bit", "stale", "reorder", "host_bytes")


def _run_seed() -> int:
    """``--seed`` of the run this process is (``benchmark/run.py``'s own
    argument; 0 where there is none, as in a test that builds the
    deployment itself)."""
    parser = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    parser.add_argument("--seed", type=int, default=0)
    return parser.parse_known_args()[0].seed


def _host_bytes(message) -> bytes:
    """A bytes message as the handler was handed it: bytes, or an IOBuf
    under ``raw_messages``."""
    return message if isinstance(message, bytes) else message.to_bytes()


def _fmix32(x):
    import jax.numpy as jnp

    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def device_words(keys, words: int, golden: int):
    """``uint32[n, words]``: the blocks of ``keys`` (``uint32[n, 2]``), the
    reference's ``block_words`` in ``jax.numpy``."""
    import jax.numpy as jnp

    index = jnp.arange(words, dtype=jnp.uint32)
    first = _fmix32(index[None, :] * jnp.uint32(golden) + keys[:, 0:1])
    return _fmix32(first ^ keys[:, 1:2])


class _Decode:
    """The decode node's half of one stream: reads a transfer's opening
    message, writes every block it is handed into the pool's next page and,
    once it holds the transfer's blocks, sends one receipt of what it
    counted."""

    def __init__(self, deployment):
        self._deployment = deployment
        self._opening = None  # (caller, transfer, first page, blocks)
        self._got = self._nbytes = 0
        self._kept = {}  # the stale control's memory: block -> the one before

    def on_received_messages(self, stream, messages) -> None:
        d, placed = self._deployment, []
        for message in messages:
            if self._opening is None:
                self._opening = OPENING.unpack_from(_host_bytes(message))
                self._got = self._nbytes = 0
                continue
            _caller, _transfer, first_page, want = self._opening
            block, at = d.as_block(message), self._got
            if d.control == "reorder" and at < 2:
                at = 1 - at
            elif d.control == "flip_bit" and at == 0:
                block = d.flip(block)
            elif d.control == "stale":
                block, self._kept[at] = self._kept.get(at, block), block
            placed.append(((first_page + at) % d.kv.pages, block))
            self._got += 1
            self._nbytes += d.block_bytes
            if self._got == want:
                d.write_pages(placed)
                placed = []
                self._opening = None
                stream.write(COUNTS.pack(self._nbytes, self._got))
        d.write_pages(placed)

    def on_closed(self, stream) -> None:
        pass

    def on_failed(self, stream, error_code, reason) -> None:
        pass


class _Receipts:
    """The client's handler: the decode side's one message a transfer."""

    def __init__(self):
        self.counts = None
        self.arrived = threading.Event()

    def on_received_messages(self, stream, messages) -> None:
        self.counts = COUNTS.unpack(bytes(messages[-1]))
        self.arrived.set()

    def on_closed(self, stream) -> None:
        self.arrived.set()

    def on_failed(self, stream, error_code, reason) -> None:
        self.arrived.set()


class _Caller:
    """One caller's stream, its count of transfers and the blocks of its
    next one, ready on the prefill chip."""

    def __init__(self, index: int, stream, receipts):
        self.index, self.stream, self.receipts = index, stream, receipts
        self.transfers = 0
        self.blocks = None
        self.blocks_of = None  # the transfer those blocks are
        self.warm_until = None  # the harness's rule, from the first call


class _Transfer:
    """What the generator reads of one transfer. The verdict is reached
    when ``response_payload`` is first read: the clock has stopped."""

    def __init__(self, deployment, caller, request, attachment, transfer,
                 pages, warm, error=None):
        self._deployment, self._caller = deployment, caller
        self._request, self._transfer, self._pages = request, transfer, pages
        self._warm, self._error = warm, error
        self._verdict = None
        self.response_attachment = attachment  # not sent, so as it came

    def failed(self) -> bool:
        return self._error is not None

    @property
    def error_text(self) -> str:
        return self._error or ""

    @property
    def response_payload(self) -> bytes:
        if self._verdict is None:
            passed = self._error is None and self._deployment.judge(
                self._caller, self._transfer, self._pages, self._warm)
            self._verdict = self._request if passed else b""
            if self._error is None:
                self._deployment.make_blocks(self._caller)
        return self._verdict


class _Client:
    """``call_method`` of the harness is one transfer on the calling
    thread's own stream."""

    def __init__(self, deployment, callers: list):
        self._deployment, self._free = deployment, deque(callers)
        self._mine = {}
        self._lock = threading.Lock()

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        me = threading.get_ident()
        with self._lock:
            if me not in self._mine:
                self._mine[me] = self._free.popleft()
        timeout_s = (cntl.timeout_ms if cntl is not None else 60000) / 1e3
        return self._deployment.transfer(
            self._mine[me], request, attachment, timeout_s)


class Deployment(_link.Deployment):
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models.kv_page_pool import KvPagePool  # noqa: F401
        from incubator_brpc_tpu.rpc import Server, StreamOptions, stream_accept
        from incubator_brpc_tpu.transport import device_link

        if not hasattr(device_link.DeviceLink, "lane_send"):
            raise RuntimeError(
                "this program's DeviceLink has no lane: a device array "
                "cannot cross it (needs PR 39's incubator_brpc_tpu)")
        self._device_link = device_link
        self._config, self.control = config, control
        self._stream = dict(config["stream"])
        self._window = int(self._stream["max_buf_size"])
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        self._seed = _run_seed()
        self._on_tpu = jax.devices()[0].platform == "tpu"
        self._lock = threading.Lock()
        self._pool_lock = threading.Lock()
        self._next_page = 0
        self._recent = deque(maxlen=int(config["host_checked_tail_transfers"]))
        self._unequal_blocks = self._unequal_pages = self._pages_read_back = 0
        self._other_shapes = self._as_host_bytes = 0
        self._overrun = self._other_counts = 0
        self._device_messages_sent = 0
        self._lane_bytes_before = device_link.lane_bytes.get_value()
        self._link_bytes_before = device_link.link_bytes.get_value()
        self._callers, self._client = [], None
        self.kv = self.pool = None  # warm() reads their sizes off the traffic

        def open_stream(cntl, request):
            accepted = stream_accept(cntl, StreamOptions(
                handler=_Decode(self),
                raw_messages=bool(self._stream["raw_messages"])))
            if accepted is None:
                cntl.set_failed(22, "the request carries no stream")
            return b""

        handler = open_stream if spans is None else spans.wrap(open_stream)
        self.server = Server()
        self.server.add_service("StreamService", {"Open": handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self._options = dict(config["channel_options"])
        self._want = config["link"]
        self._channel = None

    # -- set-up --------------------------------------------------------------

    def warm(self, traffic: dict) -> None:
        """The callers' streams (the first ``Open`` call makes the link's
        handshake, in which its trains compile), the pool on the decode
        chip, and every program a transfer or its judge runs, each
        run once: the lane's for a block, the pool's write at every count
        of blocks a program takes, the block maker, the two comparers."""
        import jax
        import jax.numpy as jnp

        from incubator_brpc_tpu.models import kv_page_pool

        config, size = self._config, min(traffic["sizes"])
        self.block_bytes = min(int(traffic["message_bytes"]), max(4, size // 4))
        self.blocks = size // self.block_bytes
        self.words = self.block_bytes // 4
        self._traffic = traffic
        self._open_streams()  # the first call makes the link's handshake
        prefill, decode = self.link.devices
        found = jax.devices()
        if (prefill, decode) != (found[config["prefill_device"]],
                                 found[config["decode_device"]]):
            raise RuntimeError(f"the link joins {prefill} and {decode}")
        pages = int(config["pool_pages"])
        if not self._on_tpu:
            pages = int(config["rehearsal_pages"])
            print(f"REHEARSAL pool: {pages} pages of {self.block_bytes} B, "
                  f"{self.blocks} blocks a transfer on {prefill.platform}, "
                  f"not the configuration's {config['pool_pages']} pages of "
                  f"{config['block_bytes']} B", flush=True)
        self.kv = kv_page_pool.KvPagePool(pages, self.words)
        self.pool = self.kv.init_state(decode)
        # what the pool must hold, in the reference's own words
        self._expected = self._reference.Pool(pages, self.words, self._seed)
        words, golden = self.words, self._reference.GOLDEN

        def make(keys):
            return tuple(device_words(keys, words, golden))

        def unequal(pool, pages, keys):
            want = device_words(keys, words, golden)
            got = kv_page_pool.kv_page_read(pool, pages)
            return jnp.sum(jnp.any(got != want, axis=1), dtype=jnp.uint32)

        self._make = jax.jit(make)
        self._unequal = jax.jit(unequal)
        self.flip = jax.jit(lambda block: block.at[0].set(block[0] ^ jnp.uint32(1)))
        self._prefill = prefill
        self.link.warm_lane(0, (words,), np.uint32)
        zeros = [np.zeros(words, np.uint32)
                 for _ in range(int(config["max_blocks_a_write"]))]
        if self.control != "host_bytes":  # blocks come as they will be handed
            zeros = [jax.device_put(block, decode) for block in zeros]
        for k in range(1, len(zeros) + 1):
            self.write_pages([(0, block) for block in zeros[:k]])
        self.flip(zeros[0])
        pages0 = np.zeros(self.blocks, np.int32)
        keys0 = self._reference.block_keys(self._seed, 0, 0, self.blocks)
        int(self._unequal(self.pool, pages0, keys0))
        np.asarray(self.kv.read(self.pool, pages0))
        jax.block_until_ready(self._make(jax.device_put(keys0, prefill)))

    def _open_streams(self) -> None:
        """One stream a caller, opened once and held for the run."""
        from incubator_brpc_tpu.rpc import Controller, StreamOptions, stream_create

        for index in range(int(self._traffic["callers"])):
            receipts = _Receipts()
            stream = stream_create(StreamOptions(
                handler=receipts, max_buf_size=self._window,
                messages_in_batch=int(self._stream["messages_in_batch"])))
            cntl = super().channel().call_method(
                self._traffic["service"], self._traffic["method"], b"",
                request_stream=stream, cntl=Controller(timeout_ms=60000))
            if cntl.failed() or not stream.wait_connected(60):
                raise RuntimeError(f"no stream: {cntl.error_text}")
            self._callers.append(_Caller(index, stream, receipts))

    def channel(self):
        """Every caller's first transfer's blocks made before the harness
        sends anything."""
        if self._client is None:
            for caller in self._callers:
                self.make_blocks(caller)
            self._client = _Client(self, self._callers)
        return self._client

    def make_blocks(self, caller) -> None:
        """The caller's next transfer's blocks, made on the prefill chip by
        one program as so many arrays, and waited for: prefill's compute
        stands outside the transfer's clock."""
        import jax

        keys = self._reference.block_keys(
            self._seed, caller.index, caller.transfers, self.blocks)
        caller.blocks = jax.block_until_ready(
            self._make(jax.device_put(keys, self._prefill)))
        caller.blocks_of = caller.transfers

    # -- the decode side's pool ----------------------------------------------

    def as_block(self, message):
        """A block as the handler was handed it: a ``jax.Array`` on the
        decode chip, counted where it is anything else."""
        import jax

        decode = self.link.devices[1]
        if isinstance(message, jax.Array):
            shape_ok = (message.shape, message.dtype) == ((self.words,), np.uint32)
            with self._lock:
                self._other_shapes += not shape_ok
                self._as_host_bytes += message.devices() != {decode}
            return message
        data = _host_bytes(message)
        with self._lock:
            self._as_host_bytes += 1
            self._other_shapes += len(data) != self.block_bytes
        return np.frombuffer(data, np.uint32)

    def write_pages(self, placed: list) -> None:
        """``[(page, block)]`` into the pool, at most ``max_blocks_a_write``
        a program; the pool is donated and kept."""
        most = int(self._config["max_blocks_a_write"])
        for at in range(0, len(placed), most):
            group = placed[at:at + most]
            pages = np.array([page for page, _ in group], np.int32)
            with self._pool_lock:
                self.pool = self.kv.write(
                    self.pool, pages, tuple(block for _, block in group))

    # -- one transfer ----------------------------------------------------------

    def transfer(self, caller, request, attachment, timeout_s):
        """One transfer on the caller's thread; the clock is the caller's."""
        from incubator_brpc_tpu.utils.status import ErrorCode

        started = time.monotonic()
        deadline = started + timeout_s
        if caller.warm_until is None:
            caller.warm_until = started + float(self._traffic["warm_seconds"])
        number, stream = caller.transfers, caller.stream
        if caller.blocks_of != number:
            self.make_blocks(caller)  # the call before failed and was never judged
        # warm by the generator's own rule: so many calls and so long
        warm = (number < int(self._traffic["warm_calls_per_caller"])
                or started < caller.warm_until)
        caller.transfers += 1
        with self._lock:
            first_page = self._next_page
            self._next_page = (first_page + self.blocks) % self.kv.pages
            pages = self._expected.place(
                caller.index, number, first_page, self.blocks)

        def done(error=None):
            return _Transfer(self, caller, request, attachment, number, pages,
                             warm, error)

        caller.receipts.counts = None
        caller.receipts.arrived.clear()
        opening = OPENING.pack(caller.index, number, first_page, self.blocks)
        ahead_limit = self._window + self.block_bytes - 1
        overrun = device_messages = 0
        for message in [opening.ljust(OPENING_BYTES, b"\0"), *caller.blocks]:
            if not isinstance(message, bytes):
                if self.control == "host_bytes":
                    message = np.asarray(message).tobytes()
                else:
                    device_messages += 1
            while True:
                rc = stream.write(message, timeout=WRITE_WAIT_S)
                if rc == 0:
                    break
                if (rc not in (ErrorCode.EAGAIN, ErrorCode.EOVERCROWDED)
                        or time.monotonic() > deadline):
                    return done(f"write gave {rc}")
            overrun = max(overrun, stream.unconsumed_bytes - ahead_limit)
        arrived = caller.receipts.arrived.wait(max(0.0, deadline - time.monotonic()))
        counts = caller.receipts.counts
        if not arrived or counts is None:
            return done("no receipt")
        with self._lock:
            self._device_messages_sent += device_messages
            self._overrun = max(self._overrun, overrun)
            self._other_counts += counts != (
                self.blocks * self.block_bytes, self.blocks)
        return done()

    # -- after the clock -------------------------------------------------------

    def judge(self, caller, transfer: int, pages: list, warm: bool) -> bool:
        """Guarantee (1) for one transfer, its clock stopped: its pages on
        the decode chip against the content regenerated there, one scalar
        read back; a warm transfer's pages also on the host, against the
        reference."""
        keys = self._reference.block_keys(
            self._seed, caller.index, transfer, self.blocks)
        with self._pool_lock:
            unequal = int(self._unequal(
                self.pool, np.array(pages, np.int32), keys))
        with self._lock:
            self._unequal_blocks += unequal
            self._recent.append(pages)
        if warm:
            unequal += self.on_the_host(pages)
        return unequal == 0

    def on_the_host(self, pages: list) -> int:
        """A transfer's pages read back and compared byte for byte with
        what the reference says they hold; the pages that differ."""
        with self._pool_lock:
            got = np.asarray(self.kv.read(self.pool, np.array(pages, np.int32)))
        unequal = sum(
            not np.array_equal(got[b], self._expected.page(page))
            for b, page in enumerate(pages))
        with self._lock:
            self._pages_read_back += len(pages)
            self._unequal_pages += unequal
        return unequal

    def holds(self) -> list:
        for pages in list(self._recent):
            self.on_the_host(pages)
        sent = self._device_messages_sent * self.block_bytes
        lane = self._device_link.lane_bytes.get_value() - self._lane_bytes_before
        link = self._device_link.link_bytes.get_value() - self._link_bytes_before
        share = 100.0 * link / lane if lane else float("inf")
        decode = self.link.devices[1]
        peak = (decode.memory_stats() or {}).get("peak_bytes_in_use")
        low, high = self.kv.nbytes, int(1.25 * self.kv.nbytes)
        judged_here = self._on_tpu and peak is not None
        return super().holds() + [
            ("kv_blocks_not_equal_to_their_source", self._unequal_blocks, 0,
             self._unequal_blocks == 0),
            (f"kv_pages_not_equal_on_the_host_of_{self._pages_read_back}_read_back",
             self._unequal_pages, 0, self._unequal_pages == 0),
            ("kv_messages_with_other_shapes", self._other_shapes, 0,
             self._other_shapes == 0),
            ("stream_window_overrun_bytes", self._overrun,
             f"0 over max_buf_size {self._window} + a block "
             f"{self.block_bytes} - 1", self._overrun == 0),
            (f"kv_lane_bytes_other_than_the_{self._device_messages_sent}_blocks_sent",
             abs(lane - sent), 0, lane == sent),
            ("kv_byte_stream_pct_of_lane_bytes", round(share, 4),
             "< 1 on a TPU" if self._on_tpu else "not judged off the TPU",
             share < 1 or not self._on_tpu),
            ("kv_messages_handed_as_host_bytes", self._as_host_bytes, 0,
             self._as_host_bytes == 0),
            ("kv_pool_updated_where_it_lies_peak_bytes",
             peak if judged_here else "not judged off the TPU",
             f">= {low} and < {high}",
             not judged_here or low <= peak < high),
            ("kv_receipts_with_other_counts", self._other_counts, 0,
             self._other_counts == 0),
        ]

    def close(self) -> None:
        for caller in self._callers:
            caller.stream.close()
        super().close()
