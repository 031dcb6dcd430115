"""Expert shard — one rank of an expert-parallel unit as a device-resident
RPC step.

The service ``models/tensor_echo`` and ``models/record_table`` are not:
one whose step is bound on the device. The rank holds ``experts_held``
routed experts of each of ``layers`` MoE layers, weights in HBM (bf16,
``gate`` and ``up`` ``[layers, experts_held, hidden, intermediate]`` and
``down[layers, experts_held, intermediate, hidden]``, the hidden axis
stored even columns first, then odd: ``hidden_order``), and callers send it
the tokens their router chose one of its experts for. The weights are the
``DeviceEndpoint``'s third kind of state (docs/DEVICE_PLANE.md): the step
reads them where they lie and hands no state back (``dispatch_step`` gives
``None`` in the state's place), so nothing is donated, no dispatch waits
its turn for them and a program that raises loses nothing.

On the wire (method ``FFN`` = 1; payload words, little-endian; the frame is
ops/framing's):

- words 0-3: the layer ``l`` in ``[0, layers)``, the token count ``T``,
  ``hidden``, ``experts_held`` (the shapes both ends were built for);
- ``T`` rows of ``hidden`` bf16 (two a word, the even column low);
- ``T x experts_held`` float32 gate weights, dense over the experts held
  here, 0 where the router did not send the token to that expert;

16 + T x (2 x hidden + 4 x experts_held) bytes, answered by ``T x hidden``
bf16::

    y_t = sum_e w[t, e] * down_{l,e}(silu(gate_{l,e} x_t) * up_{l,e} x_t)

products in bf16 with float32 accumulation, the gated intermediate rounded
to bf16 for the down product, the sum over experts in float32, rounded to
bf16 once. A token is served by whatever experts its weights name: any
split of a row's tokens over the experts (all on one, none on another),
no capacity, none dropped; an expert no token of the row names is not
read. Rows of one dispatch may name different layers, and a row's answer
does not depend on its bucket or on the rows beside it.

The products are one Pallas kernel (``expert_ffn``): the dispatch's (row,
expert) pairs that have a token are its work items, a grid step is one
item's slice of the intermediate, and the ``index_map`` of the three
weight operands takes the item's layer and expert from prefetched
scalars, so a layer's 705 MB are streamed through VMEM from where they
lie and never copied (a ``dynamic_slice`` of the stacked weights compiled,
for a v5e, to a 59 MB copy an expert before each product).

A bad frame, a layer out of range, shapes that are not this rank's, a
``T`` that the row cannot hold or that is not the request's own (a token
that no weight names, words past the request's end that are not zero) and
a weight that is not finite are answered ``EREQUEST``; an unknown method,
a dispatch's pad rows among them (zero payload, method 0), ``ENOMETHOD``:
both without a product.

The last four payload words of a response frame, past anything a caller
is given, say what the row cost: tokens, (token, expert) pairs, the layer
and the experts that got a token as a bit mask. ``account`` adds them up
for a dispatch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from incubator_brpc_tpu.bvar import Adder
from incubator_brpc_tpu.ops import framing

FFN = 1
ENOMETHOD, EREQUEST = 1002, 1003  # utils/status.ErrorCode's, as tensor_echo's
HEADER_WORDS = 4  # layer, tokens, hidden, experts held
TALLY_WORDS = 4  # tokens, pairs, layer, mask of the experts that got a token
GATE, UP, DOWN = 0, 1, 2  # the matrices of an expert, as the weight function names them

# fed by ``account`` from a completed dispatch's frames, on the watcher
m_tokens = Adder(name="device_transport_expert_tokens")
m_pairs = Adder(name="device_transport_expert_pairs")
# distinct layers, and distinct (layer, expert) with a token, a dispatch: what
# the step had to read of the weights
m_layers = Adder(name="device_transport_expert_layers")
m_weight_sets = Adder(name="device_transport_expert_weight_sets")


def weight_salt(seed: int, layer: int, expert: int, matrix: int) -> int:
    """What tells one matrix's content from another's: a 32-bit mix of the
    four in Python integers."""
    x = (
        seed * 0xC2B2AE3D + layer * 0x27D4EB2F + expert * 0x165667B1
        + matrix * 0x9E3779B9 + 0x85EBCA6B
    ) & 0xFFFFFFFF
    x = ((x ^ (x >> 15)) * 0x2C1B3C6D) & 0xFFFFFFFF
    x = ((x ^ (x >> 12)) * 0x297A2D39) & 0xFFFFFFFF
    return x ^ (x >> 15)


def weight_scale(fan_in: int) -> float:
    """A power of two that brings ``weight_values``' odd integers (rms
    255 / sqrt(3)) to about ``1 / sqrt(fan_in)``: activations stay O(1)."""
    return 2.0 ** -round(math.log2(255 / math.sqrt(3) * math.sqrt(fan_in)))


def weight_values(salt, rows, columns, scale: float, xp=jnp):
    """Element ``(rows, columns)`` of the matrix ``salt`` names: an integer
    mix in uint32 arithmetic, its top byte ``k`` read as the odd integer
    ``2k - 255`` times ``scale``, float32 and exact in bf16. ``xp`` is
    ``jax.numpy`` or ``numpy``, which compute it alike (the benchmark's
    reference holds its own copy)."""
    x = (
        rows.astype(xp.uint32) * xp.uint32(0x9E3779B1)
        + columns.astype(xp.uint32) * xp.uint32(0x85EBCA77)
        + xp.asarray(salt, xp.uint32)
    )
    x = (x ^ (x >> 15)) * xp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * xp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    k = (x >> 24).astype(xp.int32)
    return (2 * k - 255).astype(xp.float32) * xp.float32(scale)


def _ffn_kernel(
    rows_ref, _layers_ref, _experts_ref, n_ref,  # the work items, prefetched
    x_ref, w_ref, gate_ref, up_ref, down_ref, out_ref, acc_ref,
):
    """One grid step: item ``k``'s slice ``j`` of the intermediate.
    ``acc`` gathers a row's answer in float32 over its items and their
    slices and is rounded to bf16 once, at the row's last."""
    k, j = pl.program_id(0), pl.program_id(1)
    last_k, last_j = pl.num_programs(0) - 1, pl.num_programs(1) - 1
    active = k < n_ref[0]
    row = rows_ref[k]
    opens = (k == 0) | (rows_ref[jnp.maximum(k - 1, 0)] != row)
    closes = (k == n_ref[0] - 1) | (rows_ref[jnp.minimum(k + 1, last_k)] != row)

    @pl.when(active & opens & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        inner = (gate / (1.0 + jnp.exp(-gate)) * up).astype(jnp.bfloat16)
        acc_ref[...] += w_ref[...] * jnp.dot(
            inner, down_ref[...], preferred_element_type=jnp.float32)

    @pl.when(active & closes & (j == last_j))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# the widest slice of the intermediate a grid step takes: three weight blocks
# of hidden x 512 bf16 are 22 MB at hidden 7,168, twice that double-buffered
SLICE = 512
VMEM_LIMIT_BYTES = 100 << 20


def expert_ffn(state, x, w, item_row, item_layer, item_expert, n_items, interpret):
    """``y[b, tokens, hidden]`` bf16: for each of the first ``n_items`` work
    items ``(row, layer, expert)``, in row order, ``w * down(silu(gate x) *
    up x)`` added into the item's row; a row no item names is never
    written, and what it holds is the caller's to mask.
    ``x[b, tokens, hidden]`` bf16, ``w[items, tokens, 1]`` float32 an
    item's gate weights; items past ``n_items`` must repeat the last one's
    numbers: their steps then name the blocks the last step of the work
    named, and nothing is fetched for them."""
    gate, up, down = state
    _, tokens, h = x.shape
    inter = gate.shape[3]
    ti = min(SLICE, inter)
    if inter % ti:
        raise ValueError(f"intermediate {inter} is not a multiple of {ti}")

    slices = inter // ti

    def last(k, n):
        """Item ``k``, or the last item where ``k`` is past the list: a step
        past the work names the blocks of the last one that did any."""
        return jnp.minimum(k, jnp.maximum(n[0] - 1, 0))

    def weights(block, at):
        def index(k, j, rows, layers, experts, n):
            j = jnp.where(k < n[0], j, slices - 1)
            return (layers[k], experts[k]) + at(j)

        return pl.BlockSpec((None, None) + block, index)

    def of_row(k, j, rows, layers, experts, n):
        return rows[k], 0, 0

    return pl.pallas_call(
        _ffn_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(item_row.shape[0], slices),
            in_specs=[
                pl.BlockSpec((None, tokens, h), of_row),
                pl.BlockSpec(
                    (None, tokens, 1), lambda k, j, *refs: (last(k, refs[3]), 0, 0)),
                weights((h, ti), lambda j: (0, j)),
                weights((h, ti), lambda j: (0, j)),
                weights((ti, h), lambda j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((None, tokens, h), of_row),
            scratch_shapes=[pltpu.VMEM((tokens, h), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        name="expert_ffn",
        interpret=interpret,
    )(item_row, item_layer, item_expert, n_items, x, w, gate, up, down)


class ExpertShardService:
    """``experts_held`` routed experts, the unit's ``first_expert`` onwards,
    of ``layers`` layers, ``hidden`` wide with an ``intermediate`` of their
    own, weights from ``seed``."""

    def __init__(
        self, hidden: int, intermediate: int, experts_held: int, layers: int,
        seed: int = 0, first_expert: int = 0,
    ):
        if hidden % 2:
            raise ValueError("bf16 rides two a word: hidden must be even")
        if not 0 < experts_held <= 32:
            raise ValueError("the experts that got a token are one word's bits")
        self.hidden, self.intermediate = hidden, intermediate
        self.experts_held, self.layers = experts_held, layers
        self.seed, self.first_expert = seed, first_expert
        self.token_words = hidden // 2
        self.token_bytes = 2 * hidden + 4 * experts_held  # a row and its weights
        self.weight_bytes = 2 * 3 * layers * experts_held * hidden * intermediate
        # the hidden axis as the weights store it: a word of the wire holds
        # columns 2k (low) and 2k + 1 (high), so all the low halves and then
        # all the high ones are a row, by shifts alone, and an answer packs
        # the same way; interleaving them would be a lane shuffle a word
        self.hidden_order = np.concatenate(
            [np.arange(0, hidden, 2), np.arange(1, hidden, 2)]).astype(np.uint32)
        # off the TPU the kernel runs in Pallas's interpreter (the tests)
        self.interpret = jax.default_backend() != "tpu"

    def tokens_that_fit(self, width: int) -> int:
        """The most tokens a request in a row of ``width`` words can hold."""
        room = width - HEADER_WORDS
        return max(0, room // (self.token_words + self.experts_held))

    # -- what a DeviceEndpoint asks of its service --------------------------

    def init_state(self, device):
        """``(gate, up, down)`` on ``device``, filled where they lie an
        expert at a time."""
        h, i = self.hidden, self.intermediate
        scales = (weight_scale(h), weight_scale(h), weight_scale(i))

        def fill(state, layer, expert, salts):
            out = []
            across, inner = jnp.asarray(self.hidden_order), jnp.arange(i, dtype=jnp.uint32)
            for m, matrix in enumerate(state):
                rows, columns = (inner, across) if m == DOWN else (across, inner)
                block = weight_values(
                    salts[m], rows[:, None], columns[None, :], scales[m])
                out.append(lax.dynamic_update_slice(
                    matrix, block.astype(jnp.bfloat16)[None, None],
                    (layer, expert, 0, 0)))
            return tuple(out)

        fill = jax.jit(fill, donate_argnums=0)
        held = (self.layers, self.experts_held)
        state = jax.jit(
            lambda: tuple(
                jnp.zeros(held + shape, jnp.bfloat16)
                for shape in ((h, i), (h, i), (i, h))),
            out_shardings=SingleDeviceSharding(device),
        )()
        for layer in range(self.layers):
            for expert in range(self.experts_held):
                salts = np.asarray(
                    [weight_salt(self.seed, layer, self.first_expert + expert, m)
                     for m in (GATE, UP, DOWN)], np.uint32)
                state = fill(state, np.int32(layer), np.int32(expert), salts)
        return state

    def answer_bytes(self, method_id: int, request_bytes: int) -> int:
        tokens, rest = divmod(request_bytes - 4 * HEADER_WORDS, self.token_bytes)
        if method_id == FFN and tokens >= 1 and rest == 0:
            return tokens * 2 * self.hidden
        return request_bytes  # answered with an error, as long as it came

    # what the deployment's must-fail controls change (benchmark/deployments/
    # expert_shard.py): which tokens an expert serves, and from which layer

    def routed(self, weights):
        """The gate weights ``[b, tokens, experts_held]`` as served."""
        return weights

    def serving_layer(self, layer):
        """The layer whose weights serve a row that names ``layer``."""
        return layer

    def step(self, state, rows, cids, mids):
        """One dispatch over the whole batch: ``(state, rows[b, w], cids[b],
        mids[b]) -> (None, response frames[b, 8 + w])``. Jittable; the
        state is read and not replaced."""
        b, width = rows.shape
        cap = self.tokens_that_fit(width)
        header, payload, ok = jax.vmap(
            lambda padded, cid_lo, mid: framing.parse(
                framing.frame(padded, (cid_lo, jnp.uint32(0)), method_id=mid)
            )
        )(rows, cids, mids)
        mid = header.method_id
        is_ffn = mid == jnp.uint32(FFN)
        if cap == 0:  # a row too narrow for one token: every ffn is a bad request
            answer = jnp.zeros((b, width), jnp.uint32)
            valid = jnp.zeros(b, bool)
        else:
            answer, valid = self._serve(state, payload, ok & is_ffn, cap)
        err = jnp.where(
            ok & is_ffn,
            jnp.where(valid, jnp.uint32(0), jnp.uint32(EREQUEST)),
            jnp.where(ok, jnp.uint32(ENOMETHOD), jnp.uint32(EREQUEST)),
        )
        frames = jax.vmap(
            lambda result, lo, hi, m, e: framing.frame(
                result, (lo, hi), method_id=m,
                flags=framing.FLAG_RESPONSE, error_code=e,
            )
        )(answer, header.cid_lo, header.cid_hi, mid, err)
        return None, frames

    dispatch_step = step  # the name the endpoint calls; a batch is seen whole

    def _serve(self, state, payload, asked, cap: int):
        """The answers ``[b, width]`` of the rows that ask for the method
        and are well formed (``valid[b]``); zeros for the others."""
        b, width = payload.shape
        h, held, tw = self.hidden, self.experts_held, self.token_words
        layer, tokens = payload[:, 0], payload[:, 1]
        fits = (tokens >= 1) & (tokens <= cap)
        t = jnp.where(fits, tokens, 0).astype(jnp.int32)
        live = jnp.arange(cap)[None, :] < t[:, None]  # [b, cap]
        # the tokens, two bf16 a word, in ``hidden_order``: a bf16 is the
        # top half of the float32 of its value. Past the request's own lie
        # its weights, or nothing
        words = payload[:, HEADER_WORDS : HEADER_WORDS + cap * tw].reshape(b, cap, tw)
        x = jnp.concatenate(
            [lax.bitcast_convert_type(half, jnp.float32).astype(jnp.bfloat16)
             for half in (words << 16, words & jnp.uint32(0xFFFF0000))],
            axis=2)
        # the weights lie behind the T tokens: a row's own offset
        w = jax.vmap(
            lambda row, start: lax.dynamic_slice(row, (start,), (cap * held,))
        )(payload, HEADER_WORDS + t * tw)
        w = lax.bitcast_convert_type(w, jnp.float32).reshape(b, cap, held)
        finite = jnp.all(jnp.isfinite(w) | ~live[:, :, None], axis=(1, 2))
        w = jnp.where(live[:, :, None], w, 0.0)
        named = jnp.all(jnp.any(w != 0, axis=2) | ~live, axis=1)
        end = HEADER_WORDS + t * (tw + held)
        tail = jnp.where(jnp.arange(width)[None, :] >= end[:, None], payload, 0)
        valid = (
            asked & fits & finite & named & jnp.all(tail == 0, axis=1)
            & (layer < jnp.uint32(self.layers))
            & (payload[:, 2] == jnp.uint32(h)) & (payload[:, 3] == jnp.uint32(held))
        )
        live &= valid[:, None]
        x = jnp.where(live[:, :, None], x, jnp.bfloat16(0))
        w = self.routed(jnp.where(live[:, :, None], w, 0.0))
        at = jnp.where(valid, layer, 0).astype(jnp.int32)

        # the work: every (row, expert) with a token, in row order; the
        # tail of the list repeats the last item, which costs no fetch
        pairs = w != 0
        got = jnp.any(pairs, axis=1)  # [b, held]
        n_items = jnp.sum(got, dtype=jnp.int32)
        order = jnp.argsort(~got.reshape(-1), stable=True).astype(jnp.int32)
        item = order[jnp.minimum(jnp.arange(b * held), jnp.maximum(n_items - 1, 0))]
        item_row, item_expert = item // held, item % held
        pad = (-cap) % 16  # whole bf16 tiles of tokens
        y = expert_ffn(
            state,
            jnp.pad(x, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(w.transpose(0, 2, 1).reshape(b * held, cap)[item],
                    ((0, 0), (0, pad)))[:, :, None],
            item_row, self.serving_layer(at)[item_row], item_expert,
            n_items[None], self.interpret,
        )[:, :cap]
        low, high = (
            lax.bitcast_convert_type(half.astype(jnp.float32), jnp.uint32)
            for half in (y[:, :, :tw], y[:, :, tw:]))
        words = ((low >> 16) | high).reshape(b, cap * tw)
        tally = jnp.stack(
            [
                t.astype(jnp.uint32), jnp.sum(pairs, axis=(1, 2), dtype=jnp.uint32),
                at.astype(jnp.uint32),
                jnp.sum(got.astype(jnp.uint32) << jnp.arange(held, dtype=jnp.uint32),
                        axis=1, dtype=jnp.uint32),
            ],
            axis=1,
        )
        answer = jnp.zeros((b, width), jnp.uint32)
        answer = answer.at[:, : cap * tw].set(words)
        answer = answer.at[:, width - TALLY_WORDS :].set(tally)
        # a row that is not served was never written: whatever lies there goes
        return jnp.where(valid[:, None], answer, 0), valid

    def account(self, mids: np.ndarray, frames: np.ndarray) -> None:
        """Host side, from a completed dispatch's method ids and response
        frames: tokens and pairs served, and what the dispatch had to read
        of the weights: its distinct layers and (layer, expert) sets."""
        served = (frames[:, framing.HEADER_WORDS - 1] == 0) & (mids == FFN)
        if not served.any():
            return
        tally = frames[served, -TALLY_WORDS:].astype(np.int64)
        m_tokens << int(tally[:, 0].sum())
        m_pairs << int(tally[:, 1].sum())
        by_layer = {}
        for _t, _p, layer, mask in tally:
            by_layer[layer] = by_layer.get(layer, 0) | int(mask)
        m_layers << len(by_layer)
        m_weight_sets << sum(bin(mask).count("1") for mask in by_layer.values())
