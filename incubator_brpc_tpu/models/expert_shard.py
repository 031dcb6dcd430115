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

The products are one grouped Pallas kernel (``expert_ffn_grouped``, the
same for both kinds of operand): the dispatch's (token, expert) pairs, the
stacked rows' tokens as one list, are sorted by (layer, expert) (a stable
sort, so a group's tokens stay in order), each group's run is padded to
whole tiles, the pairs' token rows are gathered into that order, and the
kernel walks the tiles: a grid step is one tile's slice of the
intermediate, the ``index_map`` of the three weight operands takes the
tile's layer and expert from prefetched scalars, so a layer's 705 MB are
streamed through VMEM from where they lie and never copied (a
``dynamic_slice`` of the stacked weights compiled, for a v5e, to a 59 MB
copy an expert before each product); the tile's answer is summed over the
slices in float32 where it lies in VMEM and written once, weighted, and the
weighted rows are added back at their tokens' places in float32 and rounded
to bf16 once. Each pair is computed once (up to the padding of its group's
last tile); a (layer, expert) whose pairs fill one tile is read once a
program call, however many rows of the dispatch name it, and one that needs
``n`` tiles ``n`` times. A pass of the step has a static capacity, 5/4
pairs a token row and half a tile a group the rows can name: the router's
splits (1.17 pairs a token) are one pass, any other split as many passes as
its tiles need (a ``fori_loop`` over the count), so nothing of the worst
case's size is ever held.

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

**A tensor operand** (``dispatch_tensor``, what ``DeviceEndpoint`` asks of
a service for a call whose operand is a ``jax.Array``; PR 54). The request's
words are the four header words above and nothing else; the operand is one
``uint32[R, hidden / 2 + experts_held]`` array on the rank's chip, row ``t``
the token's ``hidden`` bf16 (two a word, the even column low) and then its
``experts_held`` float32 gate weights, bit for bit; rows past ``T`` are zero
and cost no product. ``R``, the operand's capacity, is whatever the caller
built it with (a production micro-batch's share: 2,048). The answer is one
array of the operand's own shape (so that a request and an answer that wait
at a link's lane together cross in one program): row ``t`` the ``hidden``
bf16 of ``y_t``, its last ``experts_held`` words zero. Same equations,
precisions and error answers as above; besides, an operand of another
shape or dtype, and rows past ``T`` that are not zero, are ``EREQUEST``.

A tile holds ``tile_for`` pairs: 384 where one operand of 2,048 rows names
one layer (256 pairs an expert and their deviation fit, so nearly every
expert is read once a call), 16 for host rows of 72 tokens, which mostly
name layers of their own.
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


# the widest slice of the intermediate a grid step takes: three weight blocks
# of hidden x 512 bf16 are 22 MB at hidden 7,168, twice that double-buffered
SLICE = 512
VMEM_LIMIT_BYTES = 100 << 20


# the most pairs a tile of the grouped product: a group's run of sorted pairs
# is padded to whole tiles. 256 pairs an expert (a rank's share of a prefill
# micro-batch of 8,192 tokens) with a deviation of 16 fit one tile of 384
# nearly always, so the expert is read once; x, the answer in float32 and
# the three weight blocks, double-buffered, are 77 MB of VMEM at hidden 7,168
TILE = 384


def tile_for(rows: int, held: int) -> int:
    """Pairs a tile where ``rows`` token rows name one layer: half as many
    again as an even split over the experts held gives one of them, in whole
    bf16 tiles of 16, ``TILE`` at most: 384 for an operand of 2,048 rows, 16
    for a host row of 72 tokens."""
    return min(TILE, -(-(3 * rows) // (2 * held * 16)) * 16)


def _grouped_kernel(
    _layers_ref, _experts_ref, n_ref,  # prefetched: a tile's layer and expert, tiles
    x_ref, w_ref, gate_ref, up_ref, down_ref, out_ref,
):
    """One grid step: tile ``k``'s slice ``j`` of the intermediate. The
    tile's answer gathers in ``out_ref`` in float32 over the slices (its
    block stays in VMEM while ``j`` runs) and leaves once."""
    k, j = pl.program_id(0), pl.program_id(1)
    active = k < n_ref[0]

    @pl.when(active & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active)
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        inner = (gate / (1.0 + jnp.exp(-gate)) * up).astype(jnp.bfloat16)
        out_ref[...] += w_ref[...] * jnp.dot(
            inner, down_ref[...], preferred_element_type=jnp.float32)


def expert_ffn_grouped(state, x, w, tile_layer, tile_expert, n_tiles, tile, interpret):
    """``out[pairs, hidden]`` float32: for each of the first ``n_tiles``
    tiles of ``tile`` rows of ``x[pairs, hidden]`` bf16 (a tile's rows are
    pairs of one expert of one layer, ``tile_expert[k]`` of
    ``tile_layer[k]``), ``w * down(silu(gate x) * up x)`` with ``w[pairs,
    1]`` float32. Tiles past ``n_tiles`` must repeat the last one's layer
    and expert: their steps name the blocks the last step of the work named,
    nothing is fetched for them and their rows of ``out`` are never written
    (the caller masks them)."""
    gate, up, down = state
    pairs, h = x.shape
    inter = gate.shape[3]
    ti = min(SLICE, inter)
    if inter % ti or pairs % tile:
        raise ValueError(f"{inter} x {pairs} is not whole slices of {ti} x {tile}")
    slices = inter // ti

    def of_tile(k, j, layers, experts, n):
        return jnp.minimum(k, jnp.maximum(n[0] - 1, 0)), 0

    def weights(block, at):
        def index(k, j, layers, experts, n):
            j = jnp.where(k < n[0], j, slices - 1)
            return (layers[k], experts[k]) + at(j)

        return pl.BlockSpec((None, None) + block, index)

    return pl.pallas_call(
        _grouped_kernel,
        out_shape=jax.ShapeDtypeStruct((pairs, h), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pairs // tile, slices),
            in_specs=[
                pl.BlockSpec((tile, h), of_tile),
                pl.BlockSpec((tile, 1), of_tile),
                weights((h, ti), lambda j: (0, j)),
                weights((h, ti), lambda j: (0, j)),
                weights((ti, h), lambda j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((tile, h), of_tile),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        name="expert_ffn_grouped",
        interpret=interpret,
    )(tile_layer, tile_expert, n_tiles, x, w, gate, up, down)


def _rows_of(words):
    """``[..., n]`` uint32 words as ``[..., 2 n]`` bf16 in ``hidden_order``:
    a bf16 is the top half of the float32 of its value, so the low halves
    and then the high ones are a row, by shifts alone."""
    return jnp.concatenate(
        [lax.bitcast_convert_type(half, jnp.float32).astype(jnp.bfloat16)
         for half in (words << 16, words & jnp.uint32(0xFFFF0000))],
        axis=-1)


def _words_of(y):
    """``[..., 2 n]`` bf16 in ``hidden_order`` packed two a word."""
    n = y.shape[-1] // 2
    low, high = (
        lax.bitcast_convert_type(half.astype(jnp.float32), jnp.uint32)
        for half in (y[..., :n], y[..., n:]))
    return (low >> 16) | high


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
        x = _rows_of(
            payload[:, HEADER_WORDS : HEADER_WORDS + cap * tw].reshape(b, cap, tw))
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

        # the products: the stacked rows' tokens are one list, a token's
        # layer its row's
        pairs = w != 0
        got = jnp.any(pairs, axis=1)  # [b, held]
        y = self._grouped_sum(
            state, x.reshape(b * cap, h), w.reshape(b * cap, held),
            jnp.repeat(self.serving_layer(at), cap), min(b, self.layers),
        ).reshape(b, cap, h)
        words = _words_of(y).reshape(b, cap * tw)
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

    # -- a tensor operand (what a DeviceEndpoint asks for a device array) -----

    def operand_words(self) -> int:
        """Words a row of a tensor operand (and of its answer): a token's
        bf16 and its gate weights over the experts held."""
        return self.token_words + self.experts_held

    def dispatch_tensor(self, state, row, operand, cid_lo, mid):
        """One call whose operand is a device array: ``(state, the request's
        words zero-padded to a row, operand, cid, mid) -> (None, answer,
        response frame)``; the module's docstring has the layout. Jittable;
        the state is read and not replaced. The frame is a row's (its last
        four payload words the tally ``account`` reads) and is all of the
        answer a host needs to see."""
        header, payload, ok = framing.parse(
            framing.frame(row, (cid_lo, jnp.uint32(0)), method_id=mid))
        asked = ok & (header.method_id == jnp.uint32(FFN))
        wide = self.operand_words()
        shaped = (
            operand.dtype == jnp.uint32 and operand.size > 0
            and operand.size % wide == 0
            and (operand.ndim == 1 or operand.shape[1:] == (wide,))
        )
        if shaped:  # host bytes come as the rows end to end
            answer, valid, tally = self._serve_tensor(
                state, payload, asked, operand.reshape(-1, wide))
            answer = answer.reshape(operand.shape)
        else:  # no tensor of this rank's: a bad request, without a product
            answer = jnp.zeros((1,), jnp.uint32)
            valid, tally = jnp.bool_(False), jnp.zeros(TALLY_WORDS, jnp.uint32)
        err = jnp.where(
            asked,
            jnp.where(valid, jnp.uint32(0), jnp.uint32(EREQUEST)),
            jnp.where(ok, jnp.uint32(ENOMETHOD), jnp.uint32(EREQUEST)),
        )
        result = jnp.zeros(payload.shape, jnp.uint32).at[-TALLY_WORDS:].set(
            jnp.where(valid, tally, 0))
        frame = framing.frame(
            result, (header.cid_lo, header.cid_hi), method_id=header.method_id,
            flags=framing.FLAG_RESPONSE, error_code=err)
        return None, answer, frame

    def _serve_tensor(self, state, payload, asked, operand):
        """``(answer[R, wide] uint32, valid, tally[4])`` for ``operand[R,
        wide]``; the answer is zero where the request is not served."""
        rows, _wide = operand.shape
        h, held, tw = self.hidden, self.experts_held, self.token_words
        layer, tokens = payload[0], payload[1]
        fits = (tokens >= 1) & (tokens <= rows)
        t = jnp.where(fits, tokens, 0).astype(jnp.int32)
        live = jnp.arange(rows) < t
        w = lax.bitcast_convert_type(operand[:, tw:], jnp.float32)
        finite = jnp.all(jnp.isfinite(w) | ~live[:, None])
        named = jnp.all(jnp.any(w != 0, axis=1) | ~live)
        tail = jnp.any(jnp.where(live[:, None], jnp.uint32(0), operand) != 0)
        valid = (
            asked & fits & finite & named & ~tail
            & (layer < jnp.uint32(self.layers))
            & (payload[2] == jnp.uint32(h)) & (payload[3] == jnp.uint32(held))
            & jnp.all(payload[HEADER_WORDS:] == 0)
        )
        live &= valid
        x = _rows_of(operand[:, :tw])
        w = self.routed(jnp.where(live[:, None], w, 0.0)[None])[0]
        at = self.serving_layer(jnp.where(valid, layer, 0).astype(jnp.int32))
        counts = jnp.sum(w != 0, axis=0, dtype=jnp.int32)  # pairs an expert
        y = self._grouped_sum(state, x, w, jnp.broadcast_to(at, (rows,)), 1)
        answer = jnp.concatenate(
            [_words_of(y), jnp.zeros((rows, held), jnp.uint32)], axis=1)
        tally = jnp.stack([
            t.astype(jnp.uint32), jnp.sum(counts).astype(jnp.uint32),
            jnp.where(valid, layer, 0),
            jnp.sum((counts > 0).astype(jnp.uint32)
                    << jnp.arange(held, dtype=jnp.uint32), dtype=jnp.uint32),
        ])
        return jnp.where(valid, answer, 0), valid, tally

    def _grouped_sum(self, state, x, w, layer_of, layers_named: int):
        """``y[rows, hidden]`` bf16: row ``t`` the sum over the experts its
        weights ``w[t]`` name (``[rows, held]`` float32, 0 where none) of the
        weighted expert of layer ``layer_of[t]`` on ``x[t]`` (bf16);
        ``layers_named`` is the most distinct layers the rows can name. The
        (token, expert) pairs are sorted by (layer, expert), a group's tokens
        in their order, each group's run padded to whole tiles, and the tiles
        walked by ``expert_ffn_grouped``; the weighted rows are added at
        their tokens' places in float32 and rounded once."""
        rows, h = x.shape
        held, groups = self.experts_held, self.layers * self.experts_held
        # stacked rows mostly name layers of their own: a group is a row's
        # pairs of one expert, or a few rows'
        tile = tile_for(rows // layers_named, held)
        # a pair's group, token-major; ``groups`` where there is no pair
        group = jnp.where(
            w != 0, layer_of[:, None] * held + jnp.arange(held), groups
        ).astype(jnp.int32).reshape(-1)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        bounds = jnp.searchsorted(
            group[order], jnp.arange(groups + 1), side="left").astype(jnp.int32)
        first_pair, counts = bounds[:-1], bounds[1:] - bounds[:-1]
        tiles = -(-counts // tile)  # a group's run in whole tiles
        n_tiles = jnp.sum(tiles)
        tiles_before = jnp.cumsum(tiles)
        first_tile = tiles_before - tiles
        weight_of = w.reshape(-1)

        # a pass takes so many tiles (a static shape: 5/4 pairs a row, and
        # half a tile a group the rows can name for its run's ragged end);
        # the router's splits, 1.17 pairs a token, are one pass, any other
        # split as many as it needs
        pass_tiles = -(-(5 * rows // 4) // tile) + -(-layers_named * held // 2)

        def one_pass(nth_pass, y):
            """Tiles ``[nth_pass * pass_tiles, ...)`` of the work, their
            weighted rows added into ``y[rows, hidden]`` float32 at the
            tokens' places."""
            k = nth_pass * pass_tiles + jnp.arange(pass_tiles)
            here = jnp.clip(n_tiles - nth_pass * pass_tiles, 0, pass_tiles)
            group_of = jnp.minimum(
                jnp.searchsorted(tiles_before, k, side="right"), groups - 1
            ).astype(jnp.int32)
            slot = jnp.arange(pass_tiles * tile)
            g = group_of[slot // tile]
            nth = (k[0] - first_tile[g]) * tile + slot  # of its group's run
            real = (slot // tile < here) & (nth < counts[g])
            pair = order[jnp.clip(first_pair[g] + nth, 0, rows * held - 1)]
            token = jnp.where(real, pair // held, rows)  # ``rows``: no token
            xs = x.at[token].get(mode="fill", fill_value=0)
            ws = jnp.where(real, weight_of[pair], 0.0)
            # tiles past the work repeat the last one's group: no fetch
            named = group_of[jnp.minimum(jnp.arange(pass_tiles), jnp.maximum(here - 1, 0))]
            out = expert_ffn_grouped(
                state, xs, ws[:, None], named // held, named % held,
                here[None], tile, self.interpret)
            # a slot of no pair names no token: whatever lies there is dropped
            return y.at[token].add(out, mode="drop")

        return lax.fori_loop(
            0, -(-n_tiles // pass_tiles), one_pass,
            jnp.zeros((rows, h), jnp.float32),
        ).astype(jnp.bfloat16)

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
