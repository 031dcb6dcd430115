"""The plain reference of what a source rank gets back from the expert ranks
it can reach: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, nothing of the program imported.

``moe_expert_share.py`` (loaded here by its path) has the layer: DeepSeek-V3's
router and an expert, with the seeded weight function. This file adds the
share of several ranks and the combine. Of an expert-parallel unit of ``ep``
ranks the source reaches ``ranks`` (the deployment: ranks 0-2 of 32, experts
0-23). For a micro-batch ``x[N, hidden]`` and a layer::

    combined_t = sum over the experts e held by ``ranks`` that the router
                 chose for token t of  w[t, e] * expert_e(x_t)

and nothing for the experts on ranks the source does not reach here (the
other 232), nor the shared expert, which runs on the caller's side: that
partial sum is what goes on to the next layer, in the program and here
alike. A token that none of those experts was chosen for stays zero. The sum
is computed an expert at a time over the whole micro-batch, from this
module's own copy of the weight function (``moe_expert_share``'s), the
weight 0 where the router chose another.

``micro_batch(seed, number, tokens, hidden)`` is the content function of the
deployment's pool: standard normal from a key of (seed, number), rounded to
bf16 (the activations' precision on the wire), as float32.

``outside`` is ``moe_expert_share.outside`` computed where the arrays lie:
``rel_l2``, the largest over the tokens of ``|y - r|_2 / |r|_2``, and
``element_over_rms``, the largest element of ``|y - r|`` over its token's
``rms(r)``; a token whose reference is zero is measured against 1.

``expected(request, attachment)`` is what ``benchmark/generator.py`` asks of
any reference: an answer here is judged by the tolerance once its clock has
stopped, and the deployment's client hands the generator the request's own
bytes back exactly when it passed.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


def _load_share():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "moe_expert_share.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_references_moe_expert_share_py", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


share = _load_share()
Moe, PUBLISHED = share.Moe, share.PUBLISHED


def expected(request: bytes, attachment: bytes) -> tuple:
    return request, attachment


def micro_batch(seed: int, number: int, tokens: int, hidden: int):
    """Micro-batch ``number`` of the run ``seed``: ``float32[tokens, hidden]``
    of bf16 values."""
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), number)
    key = jax.random.fold_in(key, seed >> 31)
    return _normal_as_bf16(key, tokens, hidden)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal_as_bf16(key, tokens: int, hidden: int):
    x = jax.random.normal(key, (tokens, hidden), jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def gate_weights(moe: Moe, seed: int, layer: int, x):
    """The published router's dense gate weights ``[N, n_routed_experts]``
    for ``x`` at ``layer``."""
    return _routed_by(moe, x, share.router_weights(moe, seed, layer))


_routed_by = jax.jit(share.router, static_argnums=0)


def experts_of(moe: Moe, ranks, ep: int) -> list:
    """The routed experts ``ranks`` of a unit of ``ep`` hold, in order."""
    return [e for rank in ranks for e in share.held(moe, rank, ep)]


@jax.jit
def _add(y, weights, e, x, gate, up, down):
    w = jax.lax.dynamic_slice_in_dim(weights, e, 1, 1)
    return y + w * share.expert(x, gate, up, down)


def combined(moe: Moe, seed: int, layer: int, x, weights, ranks, ep: int):
    """``float32[N, hidden]``: what the source holds after the layer call,
    by the router's ``weights[N, n_routed_experts]``: the experts of
    ``ranks`` an expert at a time, its weights made, used and dropped."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in experts_of(moe, ranks, ep):
        y = _add(y, weights, np.int32(e), x,
                 *share.expert_weights(moe, seed, layer, e))
    return y


@jax.jit
def outside(answer, want):
    """``(rel_l2, element_over_rms)`` of ``answer`` against ``want``, both
    ``[N, hidden]`` (the answer in any float type), where they lie."""
    want = want.astype(jnp.float32)
    diff = answer.astype(jnp.float32) - want
    norm = jnp.sqrt(jnp.sum(want * want, axis=1))
    norm = jnp.where(norm > 0, norm, 1.0)
    rel_l2 = jnp.sqrt(jnp.sum(diff * diff, axis=1)) / norm
    over_rms = jnp.max(jnp.abs(diff), axis=1) / (norm / math.sqrt(want.shape[1]))
    return jnp.stack([jnp.max(rel_l2), jnp.max(over_rms)])
