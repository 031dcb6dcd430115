"""Expert exchange — a source rank's side of an expert-parallel unit: the
dispatch of a micro-batch's routed tokens to the ranks that hold their
experts, as tensor calls, and the combine of what comes back.

``models/expert_shard`` is a rank; this is who calls it. A micro-batch
``x`` is ``bf16[N, hidden]`` on the source's chip and stays there. For one
layer the router's choice (dense gate weights ``[N, experts]``, a chosen
expert's weight, 0 for the others: whatever router the caller runs) becomes
a **plan**: for each rank the indices of the tokens it is sent, in order,
and those tokens' gate weights over the experts the rank holds. A **layer
call** is then

- ``gather``: one program on the source's chip builds every rank's operand
  where the micro-batch lies: ``uint32[capacity, hidden / 2 + held]``, row
  ``t`` the token's bf16 (two a word, the even column low) and its float32
  gate weights, rows past the rank's ``T`` zero
  (``ExpertShardService.dispatch_tensor``'s layout);
- the fan-out: one ``call_method(service, method, <layer, T, hidden, held:
  16 B>, attachment=<the operand>)`` a rank, all in flight together (``done``
  callbacks), each over its own ``Channel(transport="tpu")``: the operand
  crosses that link's lane as it lies, the rank's ``DeviceEndpoint`` runs
  its step on it, the answer (an array of the operand's shape, the tokens'
  partial sums in bf16) comes back the same way;
- ``combine``: one program adds the ranks' partial sums back at the tokens'
  places, ``((0 + a0) + a1) + a2`` in float32, and rounds to bf16 once:
  ``bf16[N, hidden]``, what goes on to the next layer. A token no rank
  here was sent stays zero.

**How the two programs move a row once.** An operand's minor dimension,
``hidden / 2 + held`` (3,592 at the published widths), is no multiple of
the chip's 128 lanes, and a v5e's compiler lays such an array
**column-major** (``{0,1:T(8,128)}``: the ``capacity`` rows on the lanes;
compile either program for the chip and read the parameters' layouts). So
an operand's transpose ``[hidden / 2 + held, capacity]`` costs nothing, and
in it the packing is the chip's own: a bf16 block lies two *rows* a word,
the even row low, so the transpose of a block of token rows, ``[2c, tj]``
bf16, read as ``uint32[c, tj]`` (``pltpu.bitcast``: no data moves), *is*
columns ``2i`` and ``2i+1`` of ``tj`` tokens a word. The pair of adjacent
columns ↔ a word is therefore one transpose of a block in VMEM
(``_pack_kernel``, ``_sum_kernel``) and never an array with a minor
axis of 2, which this chip pads to 128 lanes: PR 54's programs unpacked
through ``reshape(..., hidden / 2, 2)`` and the compiler made three
``uint32[8192, 7168]`` of 235 MB a call of them, beside row gathers that
ran across the lanes of the column-major answers (8.35 ms a layer call,
5% of the HBM roofline; ``PERF.md`` §6, PR 56). Everything else is bits
moved whole: a ``-0.0`` keeps its sign, an ``inf`` or a NaN its column.
The kernels work in blocks of 128 tokens, 128 operand rows and 128 words;
shapes those do not divide (or too wide for the chip's VMEM) are served by
the plain formulation (``gather_plain``, ``combine_plain``: PR 54's
programs, the tests' second reference), bit for bit the same. The shapes
select, and nothing else does (``_in_blocks``).

Nothing of a token or an answer is ever in host memory: the host builds
three 16-byte frames. The fixed ``capacity`` keeps every program's shape,
and the lane's, independent of the router's split.

Every layer call leaves a row (``expert_exchange`` in ``bvar.feeds()``):
``device_transport_expert_exchange_call_us`` and its stages end to end
(``..._gather_us``, ``..._fanout_us``: the first sub-call sent to the last
answer in hand, ``..._combine_us``: to the combined array ready), beside
``..._rank_skew_us`` (the last answer less the first) and the adders
``..._tokens_sent``, ``..._pairs_sent`` and ``..._capacity_rows`` (rows that
crossed, padding included). docs/OBSERVABILITY.md has the table.
"""

from __future__ import annotations

import functools
import struct
import threading
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from incubator_brpc_tpu.bvar import Adder, LatencyRecorder, RecorderFeed

HEAD = struct.Struct("<4I")  # layer, tokens, hidden, experts held

STAMPS = ("entry", "first_sent", "first_answer", "last_answer", "exit")
_recorders = {
    "call": LatencyRecorder(name="device_transport_expert_exchange_call_us"),
    "gather": LatencyRecorder(name="device_transport_expert_exchange_gather_us"),
    "fanout": LatencyRecorder(name="device_transport_expert_exchange_fanout_us"),
    "combine": LatencyRecorder(name="device_transport_expert_exchange_combine_us"),
    "rank_skew": LatencyRecorder(name="device_transport_expert_exchange_rank_skew_us"),
}
# gather + fanout + combine = call: the stages meet at their stamps
_feed = RecorderFeed(
    [
        (_recorders["call"], 1e-3, ("entry", "exit")),
        (_recorders["gather"], 1e-3, ("entry", "first_sent")),
        (_recorders["fanout"], 1e-3, ("first_sent", "last_answer")),
        (_recorders["combine"], 1e-3, ("last_answer", "exit")),
        (_recorders["rank_skew"], 1e-3, ("first_answer", "last_answer")),
    ],
    stamps=STAMPS,
    name="expert_exchange",
    ring_rows=1 << 12,
    call=(("entry", "exit"),),
)
m_tokens_sent = Adder(name="device_transport_expert_exchange_tokens_sent")
m_pairs_sent = Adder(name="device_transport_expert_exchange_pairs_sent")
m_capacity_rows = Adder(name="device_transport_expert_exchange_capacity_rows")


def flush_recorders() -> None:
    """Feed the recorders now instead of within the second (tests)."""
    _feed.flush()


class CapacityExceeded(ValueError):
    """A rank would be sent more tokens than an operand holds."""


class LayerPlan(NamedTuple):
    """One (micro-batch, layer): ``index[ranks, capacity]`` int32 on the
    source's chip, a rank's token indices in order, then ``N + k`` at pad
    row ``k`` (no token); ``gates[ranks, capacity, held]`` float32 beside
    them; ``inverse[ranks, N]`` int32, the row of a rank's operand that is
    token ``t``, or ``capacity`` (no row) where the rank is not sent it; on
    the host the tokens and (token, expert) pairs a rank is sent."""

    index: jax.Array
    gates: jax.Array
    inverse: jax.Array
    tokens: tuple
    pairs: tuple


class LayerAnswer(NamedTuple):
    """What a layer call gives: the combined ``bf16[N, hidden]`` on the
    source's chip (``None`` where a sub-call failed), the sub-calls'
    controllers, in rank order, and each rank's answer as it landed."""

    y: Optional[jax.Array]
    controllers: tuple
    parts: tuple

    def failed(self) -> bool:
        return self.y is None

    @property
    def error_text(self) -> str:
        return "; ".join(
            f"rank {r}: {c.error_text}"
            for r, c in enumerate(self.controllers) if c.failed())


def plan_layer(weights, first_experts: Sequence[int], held: int, capacity: int,
               device) -> LayerPlan:
    """The plan of one layer from the router's dense gate weights
    ``[N, experts]`` (host or device; read back here: set-up, not a call's
    path): rank ``r`` holds experts ``first_experts[r]`` onwards, ``held``
    of them. ``CapacityExceeded`` where a rank's tokens outnumber
    ``capacity``."""
    weights = np.asarray(weights, np.float32)
    n = weights.shape[0]
    ranks = len(first_experts)
    index = np.tile(n + np.arange(capacity, dtype=np.int32), (ranks, 1))
    gates = np.zeros((ranks, capacity, held), np.float32)
    inverse = np.full((ranks, n), capacity, np.int32)
    tokens, pairs = [], []
    for r, first in enumerate(first_experts):
        sub = weights[:, first : first + held]
        rows = np.nonzero((sub != 0).any(axis=1))[0]
        if len(rows) > capacity:
            raise CapacityExceeded(
                f"rank {r} would be sent {len(rows)} tokens, an operand holds {capacity}")
        index[r, : len(rows)] = rows
        gates[r, : len(rows)] = sub[rows]
        inverse[r, rows] = np.arange(len(rows), dtype=np.int32)
        tokens.append(len(rows))
        pairs.append(int((sub != 0).sum()))
    return LayerPlan(
        jax.device_put(index, device), jax.device_put(gates, device),
        jax.device_put(inverse, device), tuple(tokens), tuple(pairs))


_VMEM_LIMIT_BYTES = 100 << 20  # the sum: groups as they land, the ring, rows in place
# The pack needs 7 MB at the published widths, and what its limit leaves
# decides where XLA keeps the rows it gathers for it: under 16 MiB the compiler fetches the micro-batch into VMEM
# ahead (117 MB of the chip's 128) and its row gather reads it there, 136 us
# on a v5e; at 20-40 MiB it keeps the gathered rows in VMEM instead and
# gathers from HBM, 527 us; above that neither, 647 us (PERF.md §6, PR 56:
# PR 55's chip run).
_PACK_VMEM_LIMIT_BYTES = 16 << 20
_CHUNK_WORDS = 128  # a register's lanes: the words a transpose takes at a time
_TOKENS_A_STEP = 128  # tokens a step of the sum, rows of an answer's group and of the pack's step
_SUM_COLUMNS = 512  # columns the sum adds at a time: three ranks' in registers


def _pack_bytes(hidden: int, held: int) -> int:
    """The VMEM a step of the pack holds: the bf16 rows, their flags and
    gates in, the words and gates out, two of each."""
    return 2 * _TOKENS_A_STEP * (2 * hidden + 4 + 4 * held + 4 * (hidden // 2 + held))


def _sum_scratch(ranks: int, hidden: int) -> list:
    """The sum's buffers in VMEM, ``(shape, dtype)`` each."""
    g = _TOKENS_A_STEP
    return [
        ((2, ranks, hidden // 2, g), jnp.uint32),  # groups as they land
        ((ranks, 2 * g, hidden), jnp.float32),  # the ring
        ((ranks, g, hidden), jnp.float32),  # rows in place
    ]


def _sum_bytes(ranks: int, hidden: int) -> int:
    """The VMEM a step of the sum holds: its buffers and two blocks of bf16 out."""
    return sum(
        int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        for shape, dtype in _sum_scratch(ranks, hidden)
    ) + 2 * _TOKENS_A_STEP * hidden * 2


def _in_blocks(ranks: int, tokens: int, hidden: int, capacity: int, held: int) -> bool:
    """Whether the kernels serve these shapes: their blocks divide them and
    their buffers fit the chip's VMEM (a quarter of the sum's limit left to
    the compiler). Else the plain programs."""
    return (hidden % (2 * _CHUNK_WORDS) == 0 and capacity % _TOKENS_A_STEP == 0
            and tokens % _TOKENS_A_STEP == 0
            and _pack_bytes(hidden, held) <= _PACK_VMEM_LIMIT_BYTES
            and _sum_bytes(ranks, hidden) <= 3 * _VMEM_LIMIT_BYTES // 4)


def _pack_kernel(k, rows_ref, live_ref, gates_ref, out_ref):
    """``rows[tj, hidden]`` bf16 in column order to ``out[k + held, tj]``:
    a chunk's transpose lies columns 2i and 2i+1 a word, which is the
    operand's packing, so ``pltpu.bitcast`` is the whole shuffle."""
    live = live_ref[...] != 0  # [1, tj]: the row holds a token
    c = _CHUNK_WORDS
    for at in range(0, k, c):
        x = rows_ref[:, 2 * at : 2 * (at + c)]
        out_ref[at : at + c, :] = jnp.where(
            live, pltpu.bitcast(x.T, jnp.uint32), jnp.uint32(0))
    out_ref[k:, :] = pltpu.bitcast(gates_ref[...], jnp.uint32)


def gather_plain(x, index, gates):
    """``gather`` as XLA alone writes it (PR 54's program): the rows read
    as words through a trailing axis of 2. Serves the shapes the kernels'
    blocks do not divide, and is the tests' second reference."""
    ranks, capacity = index.shape
    h = x.shape[1]
    rows = x.at[index].get(mode="fill", fill_value=0)
    words = lax.bitcast_convert_type(
        rows.reshape(ranks, capacity, h // 2, 2), jnp.uint32)
    operands = jnp.concatenate(
        [words, lax.bitcast_convert_type(gates, jnp.uint32)], axis=2)
    return tuple(operands[r] for r in range(ranks))


def gather(x, index, gates, *, interpret: bool):
    """Every rank's operand from the micro-batch ``x[N, hidden]`` bf16: a
    tuple of ``uint32[capacity, hidden / 2 + held]``, one array a rank. A
    pad row (an index past ``N``) is zero.

    A rank's token rows are gathered once (XLA's row gather, bf16 as it
    lies), and one kernel a rank writes its operand once, **transposed**:
    ``[hidden / 2 + held, capacity]`` row-major is how the chip lays a
    ``[capacity, hidden / 2 + held]`` array (module docstring), so the
    transpose handed back costs nothing and no array has a minor axis of 2.
    (``gather_plain`` where the shapes are not the kernel's.) ``interpret``:
    the kernel in Pallas's interpreter, where the programs' device is no TPU
    (``ExpertExchange`` says; on either side the bits are the same)."""
    ranks, capacity = index.shape
    tokens, hidden = x.shape
    held = gates.shape[2]
    if not _in_blocks(ranks, tokens, hidden, capacity, held):
        return gather_plain(x, index, gates)
    k, tj = hidden // 2, _TOKENS_A_STEP
    rows = x.at[jnp.minimum(index, tokens - 1)].get(mode="promise_in_bounds")
    live = (index < tokens).astype(jnp.int32).reshape(ranks, 1, capacity)
    gates = jnp.swapaxes(gates, 1, 2)  # [ranks, held, capacity]: 64 KB a rank
    return tuple(
        pl.pallas_call(
            functools.partial(_pack_kernel, k),
            out_shape=jax.ShapeDtypeStruct((k + held, capacity), jnp.uint32),
            grid=(capacity // tj,),
            in_specs=[
                pl.BlockSpec((None, tj, hidden), lambda b, r=r: (r, b, 0)),
                pl.BlockSpec((None, 1, tj), lambda b, r=r: (r, 0, b)),
                pl.BlockSpec((None, held, tj), lambda b, r=r: (r, 0, b)),
            ],
            out_specs=pl.BlockSpec((k + held, tj), lambda b: (0, b)),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_PACK_VMEM_LIMIT_BYTES),
            name="expert_exchange_pack",
            interpret=interpret,
        )(rows, live, gates).T
        for r in range(ranks))


def _sum_kernel(ranks, tb, k, index_ref, first_ref, sent_ref, *refs):
    """One block of ``tb`` tokens. A rank's rows are in token order, so the
    rows of answer ``r`` whose tokens lie in block ``b`` are the run
    ``first[r, b]`` to ``first[r, b + 1]``, and the blocks' runs follow one
    another through the answer. The answer comes in **once**, a group of
    ``g`` rows (``g`` lanes of its transpose, all ``k`` words) at a time: the
    block whose run first enters a group waits for it (a run is at most
    ``tb = g`` rows, so a block enters at most one), unpacks it into bf16
    rows in column order (the transpose of a chunk, two rows a word) and
    keeps it in float32 in a ring of two groups, which is all a run can
    reach back into; the next block's group is on its way meanwhile. Each
    row of the run is then moved to its token's place, and the ranks' rows
    are added in rank order where ``sent`` says the rank has the token."""
    answers, out_ref = refs[:ranks], refs[ranks]
    land, ring, rows, sem = refs[ranks + 1 :]
    b, nb = pl.program_id(0), pl.num_programs(0)
    capacity = index_ref.shape[0] // ranks
    hidden = out_ref.shape[1]
    g, c = tb, _CHUNK_WORDS
    cw = _SUM_COLUMNS if hidden % _SUM_COLUMNS == 0 else 2 * _CHUNK_WORDS

    def run(r, block):
        return first_ref[r * (nb + 1) + block], first_ref[r * (nb + 1) + block + 1]

    def entered(r, block):
        """``(whether the block's run enters a group, that group)``."""
        lo, hi = run(r, block)
        m = (lo + g - 1) // g  # the groups the runs before this one touched
        return (hi + g - 1) // g > m, m

    def group_copy(r, m):
        return pltpu.make_async_copy(
            answers[r].at[pl.ds(0, k), pl.ds(pl.multiple_of(m * g, g), g)],
            land.at[m % 2, r], sem.at[m % 2, r])

    def start(block):
        for r in range(ranks):
            new, m = entered(r, block)
            pl.when(new)(lambda r=r, m=m: group_copy(r, m).start())

    pl.when(b == 0)(lambda: start(0))
    for r in range(ranks):
        new, m = entered(r, b)

        @pl.when(new)
        def _(r=r, m=m):
            group_copy(r, m).wait()
            half = pl.multiple_of((m % 2) * g, g)
            for at in range(0, k, c):
                ring[r, pl.ds(half, g), 2 * at : 2 * (at + c)] = pltpu.bitcast(
                    land[m % 2, r, at : at + c, :], jnp.bfloat16).T.astype(jnp.float32)

    pl.when(b + 1 < nb)(lambda: start(b + 1))
    for r in range(ranks):
        lo, hi = run(r, b)

        def move(j, _, r=r):
            place = index_ref[r * capacity + j] - b * tb
            rows[r, pl.ds(place, 1), :] = ring[r, pl.ds(j % (2 * g), 1), :]
            return 0

        lax.fori_loop(lo, hi, move, 0)
    sent = sent_ref[...]  # [tb, ranks]
    here = [sent[:, r : r + 1] != 0 for r in range(ranks)]
    for at in range(0, hidden, cw):
        y = jnp.zeros((tb, cw), jnp.float32)
        for r in range(ranks):
            # a place no row was moved to holds what an earlier block left
            y = y + jnp.where(here[r], rows[r, :, at : at + cw], 0.0)
        out_ref[:, at : at + cw] = y.astype(jnp.bfloat16)


def combine_plain(tokens: int, hidden: int, inverse, *answers):
    """``combine`` as XLA alone writes it (PR 54's program): each token
    takes its row of each rank's answer by ``inverse`` (a gather of
    ``tokens`` rows a rank), the words unpacked through a trailing axis of
    2. Serves the shapes the kernel's blocks do not divide, and is the
    tests' second reference."""
    y = jnp.zeros((tokens, hidden), jnp.float32)
    for r, answer in enumerate(answers):
        words = answer[:, : hidden // 2].at[inverse[r]].get(
            mode="fill", fill_value=0)
        rows = lax.bitcast_convert_type(words, jnp.bfloat16)
        y = y + rows.reshape(tokens, hidden).astype(jnp.float32)
    return y.astype(jnp.bfloat16)


def combine(tokens: int, hidden: int, index, inverse, *answers, interpret: bool):
    """``bf16[tokens, hidden]``: the ranks' partial sums (``answers[r]``, an
    operand's shape, a row's first ``hidden / 2`` words its bf16) added at
    their tokens' places, ``((0 + a0) + a1) + a2`` in float32, rounded once;
    a rank that was not sent the token adds ``0.0``.

    One kernel walks the tokens a block at a time (``_sum_kernel``): every
    answer is read once as it lies, a row is moved once to where its token
    is, and nothing the size of the micro-batch is written but the sum. (On
    a v5e XLA's scatter-add of the same rows took 22 ms a micro-batch; a
    gather of 8,192 rows from each answer, 24,576 for the 5,265 that hold a
    token, with the words unpacked through a trailing axis of 2, took 3.7 ms
    and held 940 MB: ``combine_plain``, where the shapes are not the
    kernel's.)"""
    ranks, capacity = index.shape
    held = answers[0].shape[1] - hidden // 2
    if not _in_blocks(ranks, tokens, hidden, capacity, held):
        return combine_plain(tokens, hidden, inverse, *answers)
    k = hidden // 2
    tb = _TOKENS_A_STEP  # a block's run, at most tb rows, fits a group of as many
    nb = tokens // tb
    sent = inverse < capacity
    first = jnp.cumsum(
        sent.reshape(ranks, nb, tb).sum(axis=2, dtype=jnp.int32), axis=1, dtype=jnp.int32)
    first = jnp.pad(first, ((0, 0), (1, 0)))  # [ranks, nb + 1]
    return pl.pallas_call(
        functools.partial(_sum_kernel, ranks, tb, k),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec((tb, ranks), lambda b, *_: (b, 0))]
            + [pl.BlockSpec(memory_space=pltpu.HBM)] * ranks,
            out_specs=pl.BlockSpec((tb, hidden), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM(*buffer) for buffer in _sum_scratch(ranks, hidden)]
            + [pltpu.SemaphoreType.DMA((2, ranks))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="expert_exchange_sum",
        interpret=interpret,
    )(index.reshape(-1), first.reshape(-1), sent.astype(jnp.int32).T,
      *(answer.T for answer in answers))


class ExpertExchange:
    """A source rank's client: ``channels[r]`` reaches rank ``r``, which
    holds ``held`` experts from ``first_experts[r]``; micro-batches are
    ``bf16[tokens, hidden]`` on ``device`` (the channels' links' client
    device), operands hold ``capacity`` token rows."""

    def __init__(
        self, channels: Sequence, first_experts: Sequence[int], held: int,
        hidden: int, tokens: int, capacity: int, device,
        service: str = "experts", method: str = "ffn",
    ):
        if hidden % 2:
            raise ValueError("bf16 rides two a word: hidden must be even")
        self.channels, self.first_experts = list(channels), list(first_experts)
        self.held, self.hidden, self.tokens = held, hidden, tokens
        self.capacity, self.device = capacity, device
        self.service, self.method = service, method
        self.operand_shape = (capacity, hidden // 2 + held)
        on_device = SingleDeviceSharding(device)

        interpret = device.platform != "tpu"

        def expert_exchange_gather(x, index, gates):
            return gather(x, index, gates, interpret=interpret)

        def expert_exchange_combine(index, inverse, *answers):
            return combine(tokens, hidden, index, inverse, *answers, interpret=interpret)

        # named for the trace (jit_expert_exchange_gather, ..._combine):
        # benchmark/roofline_exchange.py finds their device time by the names
        self._gather = jax.jit(
            expert_exchange_gather, in_shardings=on_device, out_shardings=on_device)
        self._combine = jax.jit(
            expert_exchange_combine, in_shardings=on_device, out_shardings=on_device)

    def plan(self, weights) -> LayerPlan:
        return plan_layer(
            weights, self.first_experts, self.held, self.capacity, self.device)

    def operands(self, x, plan: LayerPlan) -> tuple:
        """The ranks' operands for ``x`` under ``plan`` (``gather``)."""
        return self._gather(x, plan.index, plan.gates)

    def combined(self, plan: LayerPlan, answers) -> jax.Array:
        """``combine`` of the ranks' answers under ``plan``."""
        return self._combine(plan.index, plan.inverse, *answers)

    def landed(self, part):
        """A rank's answer as ``combine`` takes it: the array as it landed;
        bytes, where the link under the call has no lane (one shared device)
        and both attachments crossed as host bytes, put on the device."""
        if isinstance(part, (bytes, bytearray)) and len(part) == 4 * int(
                np.prod(self.operand_shape)):
            part = jax.device_put(
                np.frombuffer(part, np.uint32).reshape(self.operand_shape),
                self.device)
        return part

    def warm(self) -> None:
        """Compile ``gather`` and ``combine`` and run each once, on zeros."""
        x = jax.device_put(
            np.zeros((self.tokens, self.hidden), jnp.bfloat16), self.device)
        plan = self.plan(np.zeros((self.tokens, self.first_experts[-1] + self.held)))
        jax.block_until_ready(
            self.combined(plan, self.operands(x, plan)))

    def call_layer(self, x, plan: LayerPlan, layer: int,
                   timeout_ms: int = 60000) -> LayerAnswer:
        """One micro-batch's one layer: gather, the sub-calls in flight
        together, combine. Returns when the combined array is ready on the
        source's chip (or a sub-call failed)."""
        from incubator_brpc_tpu.rpc import Controller

        t_entry = time.monotonic_ns()
        operands = self.operands(x, plan)
        ranks = len(self.channels)
        answered, done = [], threading.Event()

        def on_done(_cntl):
            # an append is one step of the interpreter: whoever appends the
            # last stamp sees them all
            answered.append(time.monotonic_ns())
            if len(answered) == ranks:
                done.set()

        t_first_sent = time.monotonic_ns()
        controllers = []
        for r, channel in enumerate(self.channels):
            frame = HEAD.pack(layer, plan.tokens[r], self.hidden, self.held)
            controllers.append(channel.call_method(
                self.service, self.method, frame,
                cntl=Controller(timeout_ms=timeout_ms), done=on_done,
                attachment=operands[r]))
        done.wait()
        parts = tuple(self.landed(c.response_attachment) for c in controllers)
        y = None
        if not any(c.failed() for c in controllers) and all(
            isinstance(p, jax.Array) and p.shape == self.operand_shape for p in parts
        ):
            y = jax.block_until_ready(self.combined(plan, parts))
        t_exit = time.monotonic_ns()
        if y is not None:
            _feed.rows.append((
                t_entry, t_first_sent, min(answered), max(answered), t_exit))
            m_tokens_sent << sum(plan.tokens)
            m_pairs_sent << sum(plan.pairs)
            m_capacity_rows << ranks * self.capacity
        return LayerAnswer(y, tuple(controllers), parts)
