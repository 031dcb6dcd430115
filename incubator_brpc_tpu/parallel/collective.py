"""Collective lowerings of combo-channel semantics (used inside shard_map).

Each function is the device-side body of one reference combo channel
(SURVEY.md §2.5). They are thin, composable wrappers over lax collectives so
XLA schedules them on ICI; no Python control flow depends on data.
"""

from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

# Threads of one process that launch multi-device programs with a
# collective in them (the parties of a session co-hosted in one process,
# parallel/mc_dispatch.py; concurrent callers of a fused combo channel,
# rpc/combo.py) hold this across the (async) enqueue: two devices that see
# those launches in different orders each wait in a collective the other
# has not reached. Held, every device queue gets the same order. Only the
# enqueue is ordered, never the host work before it or the read-back after.
launch_order = threading.Lock()


def fanout(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """ParallelChannel broadcast side: give every replica along ``axis`` the
    full set of sub-results (reference parallel_channel.cpp CallMapper
    broadcast) — an all_gather over ICI."""
    return lax.all_gather(x, axis)


def merge(x: jnp.ndarray, axis: str, merger: str = "sum") -> jnp.ndarray:
    """ParallelChannel ResponseMerger: combine replies across ``axis``
    (reference parallel_channel.h:92-101). 'sum'|'mean'|'max'|'min'."""
    if merger == "sum":
        return lax.psum(x, axis)
    if merger == "mean":
        return lax.pmean(x, axis)
    if merger == "max":
        return lax.pmax(x, axis)
    if merger == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unknown merger {merger!r}")


def partition_exchange(x: jnp.ndarray, axis: str, split_dim: int = 0, concat_dim: int = 0) -> jnp.ndarray:
    """PartitionChannel: route slice i of every rank to rank i along ``axis``
    (reference partition_channel.cpp tag 'i/N' routing) — all_to_all."""
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True)


def ring_stream(
    x: jnp.ndarray,
    axis: str,
    step_fn: Callable[[jnp.ndarray, jnp.ndarray], tuple],
    carry_init: jnp.ndarray,
):
    """Streaming RPC over the ICI ring: pass ``x`` around the ``axis`` ring,
    folding ``step_fn(carry, received) -> (carry, send_next)`` at each hop.

    This is the credit-window tensor stream of SURVEY §2.5 ("bidirectional
    tensor stream over ICI"): the window is implicit — each hop is one
    in-flight frame per neighbor, matching RdmaEndpoint's per-WR ack scheme
    (rdma_endpoint.h:176-195) with window=1.
    """
    n = lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(state, _):
        carry, buf = state
        carry, send = step_fn(carry, buf)
        buf = lax.ppermute(send, axis, perm)
        return (carry, buf), None

    (carry, buf), _ = lax.scan(body, (carry_init, x), None, length=n)
    return carry, buf


def ring_allgather(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """All-gather built from the ring primitive (used by tests to check the
    ring against XLA's native all_gather).

    At hop k each rank holds the chunk that originated at rank (my - k) mod n.
    """
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    out = jnp.zeros((n,) + x.shape, x.dtype)

    def step_fn(carry, received):
        acc, k = carry
        src = (my - k) % n
        acc = acc.at[src].set(received)
        return (acc, k + 1), received

    (out, _), _ = ring_stream(x, axis, step_fn, (out, jnp.int32(0)))
    return out
