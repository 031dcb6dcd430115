"""The plain reference of DeepSeek-V3's mixture-of-experts layer and of one
expert-parallel rank's share of it: straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, no kernels, no
batching, nothing of the program imported.

**The layer** (deepseek-ai/DeepSeek-V3 ``config.json`` and the model's own
``MoEGate`` / ``DeepseekV3MoE``; ``PUBLISHED`` holds the numbers):

- the router: ``scores = sigmoid(x W^T)`` over ``n_routed_experts``; for
  the selection only, ``scores + e_score_correction_bias``; a group's score
  is the sum of its two best; the best ``topk_group`` of ``n_group`` groups
  are kept; among their experts the best ``num_experts_per_tok`` are
  chosen; a chosen expert's weight is its unbiased score, the chosen ones
  normalised to sum 1 (``norm_topk_prob``), times ``routed_scaling_factor``;
- an expert: ``down(silu(gate x) * up x)``, ``moe_intermediate_size`` wide;
- ``moe(x) = sum_e w[t, e] expert_e(x_t) + shared(x_t)``, the shared expert
  ``n_shared_experts`` times as wide.

``share(..., rank, ep)`` is the same with only that rank's experts
(``n_routed_experts / ep`` of them, the rank's first onwards) and the
shared expert left out: what one rank of an expert-parallel unit adds, and
what ``models/expert_shard`` answers. The ranks' shares and the shared
expert, counted once, add up to ``moe``.

**Departures from the published description, each on purpose:**

- weights are seeded, not the release's: ``weight_values`` is an integer
  function of ``(seed, layer, expert, matrix, row, column)`` that ``numpy``
  and ``jax.numpy`` compute alike (this file's own copy; the program fills
  its HBM from its own), odd integers in ``[-255, 255]`` times a power of
  two near ``1 / sqrt(fan_in)``, so each is exact in bf16 and activations
  stay O(1);
- ``e_score_correction_bias`` is zero (the release's is learned);
- float32 throughout (the release is fp8 with bf16 activations);
- the router's weight matrix is seeded the same way (matrix ``ROUTER`` of
  the expert one past the last routed one).

**The client's half** (``micro_batch``, ``sent_here``, ``pack_request``): from a seeded micro-batch of ``N``
tokens and the layer's seeded router, the tokens whose chosen experts
include one held by the rank, with their weights over the experts held
(0 where the router chose another), packed as the ``ffn`` request of
``models/expert_shard`` (16 B of layer, ``T``, hidden, experts held; ``T``
rows of bf16; ``T x held`` float32 weights).

``expected(request, attachment)`` is what ``benchmark/generator.py`` asks
of any reference. An answer here is judged by a tolerance, not for
equality, so the deployment's client judges it once the call's clock has
stopped and hands the generator the request's own bytes back exactly when
it passed; in the client's encoding the expected answer is the request.
"""

from __future__ import annotations

import functools
import hashlib
import math
import struct
from typing import NamedTuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

GATE, UP, DOWN, ROUTER = 0, 1, 2, 3
HEAD = struct.Struct("<4I")  # layer, tokens, hidden, experts held


class Moe(NamedTuple):
    """The sizes of one MoE layer, by their ``config.json`` names."""

    hidden_size: int = 7168
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    n_shared_experts: int = 1
    norm_topk_prob: bool = True


PUBLISHED = Moe()


def expected(request: bytes, attachment: bytes) -> tuple:
    return request, attachment


# -- seeded weights (this file's own copy of the weight function) ------------


def weight_salt(seed: int, layer: int, expert: int, matrix: int) -> int:
    x = (
        seed * 0xC2B2AE3D + layer * 0x27D4EB2F + expert * 0x165667B1
        + matrix * 0x9E3779B9 + 0x85EBCA6B
    ) & 0xFFFFFFFF
    x = ((x ^ (x >> 15)) * 0x2C1B3C6D) & 0xFFFFFFFF
    x = ((x ^ (x >> 12)) * 0x297A2D39) & 0xFFFFFFFF
    return x ^ (x >> 15)


def weight_scale(fan_in: int) -> float:
    return 2.0 ** -round(math.log2(255 / math.sqrt(3) * math.sqrt(fan_in)))


def weight_values(salt, shape: tuple, fan_in: int, xp=jnp):
    """The ``shape[0] x shape[1]`` matrix ``salt`` names, float32."""
    rows = xp.arange(shape[0], dtype=xp.uint32)[:, None]
    columns = xp.arange(shape[1], dtype=xp.uint32)[None, :]
    x = (
        rows * xp.uint32(0x9E3779B1) + columns * xp.uint32(0x85EBCA77)
        + xp.asarray(salt, xp.uint32)
    )
    x = (x ^ (x >> 15)) * xp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * xp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    k = (x >> 24).astype(xp.int32)
    return (2 * k - 255).astype(xp.float32) * xp.float32(weight_scale(fan_in))


def expert_weights(moe: Moe, seed: int, layer: int, expert: int, width: int = 0):
    """``(gate[hidden, width], up[hidden, width], down[width, hidden])`` of
    one expert, input-major; ``width`` is ``moe_intermediate_size`` unless
    given (the shared expert's)."""
    h, i = moe.hidden_size, width or moe.moe_intermediate_size
    salts = np.asarray(
        [weight_salt(seed, layer, expert, m) for m in (GATE, UP, DOWN)], np.uint32)
    return _expert_weights(salts, h, i)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _expert_weights(salts, h: int, i: int):
    return (
        weight_values(salts[GATE], (h, i), h),
        weight_values(salts[UP], (h, i), h),
        weight_values(salts[DOWN], (i, h), i),
    )


def router_weights(moe: Moe, seed: int, layer: int):
    """``W[n_routed_experts, hidden]`` of a layer's router."""
    salt = weight_salt(seed, layer, moe.n_routed_experts + 1, ROUTER)
    shape = (moe.n_routed_experts, moe.hidden_size)
    return weight_values(np.uint32(salt), shape, moe.hidden_size)


# -- the layer ----------------------------------------------------------------


def router(moe: Moe, x, w_router, bias=None):
    """Dense gate weights ``[tokens, n_routed_experts]``: a chosen expert's
    weight, 0 for the others."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ w_router.T)
    choice = scores if bias is None else scores + bias
    n = scores.shape[0]
    per_group = moe.n_routed_experts // moe.n_group
    grouped = choice.reshape(n, moe.n_group, per_group)
    group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_scores, moe.topk_group)[1]
    group_kept = jnp.zeros((n, moe.n_group), bool).at[
        jnp.arange(n)[:, None], kept].set(True)
    allowed = jnp.repeat(group_kept, per_group, axis=1)
    chosen = jax.lax.top_k(
        jnp.where(allowed, choice, 0.0), moe.num_experts_per_tok)[1]
    picked = jnp.zeros_like(scores, bool).at[
        jnp.arange(n)[:, None], chosen].set(True)
    weights = jnp.where(picked, scores, 0.0)
    if moe.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * moe.routed_scaling_factor


def expert(x, gate, up, down):
    """``down(silu(gate x) * up x)`` for ``x[tokens, hidden]``."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed(moe: Moe, seed: int, layer: int, x, weights, experts):
    """``sum_e weights[:, e] * expert_e(x)`` over ``experts``, an expert at
    a time: its weights are made, used and dropped."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in experts:
        y = y + weights[:, e : e + 1] * expert(
            x, *expert_weights(moe, seed, layer, e))
    return y


def shared(moe: Moe, seed: int, layer: int, x):
    """The shared expert, the expert one past the last routed one."""
    width = moe.n_shared_experts * moe.moe_intermediate_size
    return expert(
        x, *expert_weights(moe, seed, layer, moe.n_routed_experts, width))


def moe_layer(moe: Moe, seed: int, layer: int, x):
    """(a) The layer whole: router, routed experts, shared expert."""
    weights = router(moe, x, router_weights(moe, seed, layer))
    y = routed(moe, seed, layer, x, weights, range(moe.n_routed_experts))
    return y + shared(moe, seed, layer, x)


def held(moe: Moe, rank: int, ep: int) -> range:
    """The routed experts of ``rank`` in a unit of ``ep`` ranks."""
    each = moe.n_routed_experts // ep
    return range(rank * each, (rank + 1) * each)


def share(moe: Moe, seed: int, layer: int, x, rank: int, ep: int):
    """(b) The layer with only ``rank``'s experts and no shared expert."""
    weights = router(moe, x, router_weights(moe, seed, layer))
    return routed(moe, seed, layer, x, weights, held(moe, rank, ep))


# -- the client's half ----------------------------------------------------------


def micro_batch(payload: bytes, layer: int, attempt: int, tokens: int, hidden: int):
    """``tokens`` token rows read off a seeded payload: standard normal from
    a key that is a hash of ``(payload, layer, attempt)``, rounded to bf16
    (the activations' precision on the wire), as float32."""
    digest = hashlib.blake2b(
        payload + struct.pack("<II", layer, attempt), digest_size=8).digest()
    key = jax.random.wrap_key_data(jnp.asarray(np.frombuffer(digest, np.uint32)))
    return _normal_as_bf16(key, tokens, hidden)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal_as_bf16(key, tokens: int, hidden: int):
    x = jax.random.normal(key, (tokens, hidden), jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_routed_by = jax.jit(router, static_argnums=0)


def sent_here(moe: Moe, x, w_router, rank: int, ep: int):
    """(c) Of a micro-batch, what its router sends to ``rank``: the token
    indices, and those tokens' weights over the rank's experts."""
    mine = held(moe, rank, ep)
    weights = np.asarray(_routed_by(moe, x, w_router))[:, mine.start : mine.stop]
    rows = np.nonzero((weights != 0).any(axis=1))[0]
    return rows, weights[rows]


def pack_request(layer: int, x, weights) -> bytes:
    """The ``ffn`` request for token rows ``x`` (bf16 values) and their
    weights over the experts held."""
    tokens, hidden = x.shape
    return (
        HEAD.pack(layer, tokens, hidden, weights.shape[1])
        + np.asarray(x).astype(ml_dtypes.bfloat16).tobytes()
        + np.asarray(weights, np.float32).tobytes()
    )


def unpack_answer(answer: bytes, hidden: int) -> np.ndarray:
    """``[tokens, hidden]`` float32 of an answer's bf16."""
    y = np.frombuffer(answer, ml_dtypes.bfloat16).astype(np.float32)
    return y.reshape(-1, hidden)


def outside(answer: np.ndarray, want: np.ndarray) -> tuple:
    """How far an answer lies from the reference's, as the two numbers a
    tolerance is set on: the largest, over the tokens, of ``|y - r|_2 /
    |r|_2``, and the largest element of ``|y - r|`` over its token's
    ``rms(r)``."""
    diff = answer - want
    norm = np.sqrt((want * want).sum(axis=1))
    norm = np.where(norm > 0, norm, 1.0)
    rel_l2 = np.sqrt((diff * diff).sum(axis=1)) / norm
    over_rms = np.abs(diff).max(axis=1) / (norm / math.sqrt(want.shape[1]))
    return float(rel_l2.max()), float(over_rms.max())
