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
  places (each token gathers its row of each answer) in float32 and rounds
  to bf16 once: ``bf16[N, hidden]``, what goes
  on to the next layer. A token no rank here was sent stays zero.

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

import struct
import threading
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
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


def gather(x, index, gates):
    """Every rank's operand from the micro-batch ``x[N, hidden]`` bf16: a
    tuple of ``uint32[capacity, hidden / 2 + held]``, one array a rank. A
    pad row (an index past ``N``) is zero."""
    ranks, capacity = index.shape
    h = x.shape[1]
    rows = x.at[index].get(mode="fill", fill_value=0)
    words = lax.bitcast_convert_type(
        rows.reshape(ranks, capacity, h // 2, 2), jnp.uint32)
    operands = jnp.concatenate(
        [words, lax.bitcast_convert_type(gates, jnp.uint32)], axis=2)
    return tuple(operands[r] for r in range(ranks))


def combine(tokens: int, hidden: int, inverse, *answers):
    """``bf16[tokens, hidden]``: the ranks' partial sums (``answers[r]``, an
    operand's shape, a row's first ``hidden / 2`` words its bf16) added at
    their tokens' places in float32, rounded once. Each token takes its row
    of each rank's answer by ``inverse`` (a gather: on a v5e a scatter-add of
    the same rows took 22 ms a micro-batch, ten times this); a rank that was
    not sent the token adds nothing."""
    y = jnp.zeros((tokens, hidden), jnp.float32)
    for r, answer in enumerate(answers):
        words = answer[:, : hidden // 2].at[inverse[r]].get(
            mode="fill", fill_value=0)
        rows = lax.bitcast_convert_type(words, jnp.bfloat16)
        y = y + rows.reshape(tokens, hidden).astype(jnp.float32)
    return y.astype(jnp.bfloat16)


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

        def expert_exchange_gather(x, index, gates):
            return gather(x, index, gates)

        def expert_exchange_combine(inverse, *answers):
            return combine(tokens, hidden, inverse, *answers)

        # named for the trace: jit_expert_exchange_gather, ..._combine
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
        return self._combine(plan.inverse, *answers)

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
            self._combine(plan.inverse, *self._gather(x, plan.index, plan.gates)))

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
