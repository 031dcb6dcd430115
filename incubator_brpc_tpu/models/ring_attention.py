"""Ring attention — sequence-parallel exact attention over the 'sp' mesh
axis (the long-context slot of the build brief; reference analog: the
Streaming-RPC credit window moving unbounded payloads, SURVEY §2.5 —
here the "stream" is KV blocks rotating around the ICI ring).

Design (Ring Attention with Blockwise Transformers, public recipe):
- Q stays put; each rank's K/V block makes one full trip around the ring
  via ``lax.ppermute`` (one in-flight block per neighbor — the same
  window=1 per-hop ack scheme as RdmaEndpoint's credit flow control).
- Per hop, a blockwise attention step folds into ONLINE-SOFTMAX
  accumulators (running max ``m``, normalizer ``l``, weighted sum ``o``)
  so the result is EXACT full attention without materializing the global
  (T, T) score matrix — memory per rank stays O(T_local^2 / sp).
- Causal masking uses global token positions derived from the rank index,
  so the ring result equals single-device causal attention.

Everything is jittable under shard_map with static shapes; the hop loop
is a ``lax.scan`` (compiler-friendly control flow, no Python loop over
traced values — the whole ring compiles into one XLA while-op with
collective-permute inside).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """Scores for one (Q_local, KV_block) pair + online-softmax pieces.
    q: (B, Tq, H, D), k/v: (B, Tk, H, D), mask: (Tq, Tk) additive."""
    d = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(d))
    s = s + mask[None, None, :, :]
    m = jnp.max(s, axis=-1)  # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B, H, Tq)
    o = jnp.einsum("bhts,bshd->bthd", p, v)  # (B, Tq, H, D)
    return m, l, o


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis: str = "sp",
    causal: bool = True,
    prefetch: bool = False,
) -> jnp.ndarray:
    """Exact attention with K/V ringing over ``axis``. Call inside
    shard_map with q/k/v sharded on their sequence dim; shapes per rank:
    (B, T_local, H, D). Returns (B, T_local, H, D).

    ``prefetch=True`` emits each hop's ppermute BEFORE the held block's
    attention fold (rotate-while-computing, the T3 overlap shape): the
    next KV block's transfer is independent of the fold, so the compiler
    may overlap the ring hop with the blockwise attention compute
    instead of serializing transfer-then-fold. Bit-identical output —
    the dataflow is unchanged, only the emission order moves."""
    sp = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    b, t, h, d = q.shape
    qf = q.astype(jnp.float32)

    # accumulators: running max m, normalizer l, weighted sum o
    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    o0 = jnp.zeros((b, t, h, d), jnp.float32)

    q_pos = idx * t + jnp.arange(t)  # my queries' global positions

    def block_merge(m, l, o, k_r, v_r, r):
        """Fold one held KV block into the online-softmax accumulators.
        The block currently held arrived from rank (idx - r) mod sp."""
        src = (idx - r) % sp
        kv_pos = src * t + jnp.arange(t)
        if causal:
            mask = jnp.where(kv_pos[None, :] <= q_pos[:, None], 0.0, NEG_INF)
        else:
            mask = jnp.zeros((t, t), jnp.float32)
        bm, bl, bo = _block_attn(qf, k_r.astype(jnp.float32),
                                 v_r.astype(jnp.float32), mask)
        # online-softmax merge (flash-style log-sum-exp combination)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)  # rescale old accumulators
        beta = jnp.exp(bm - m_new)  # rescale this block
        l_new = l * alpha + bl * beta
        o_new = (
            o * alpha.transpose(0, 2, 1)[..., None]
            + bo * beta.transpose(0, 2, 1)[..., None]
        )
        return m_new, l_new, o_new

    def hop(carry, r):
        m, l, o, k_r, v_r = carry
        if prefetch:
            # rotate while computing: the transfer of the held block to
            # the right neighbor starts before (independently of) the
            # fold that consumes the SAME held block locally
            k_next = lax.ppermute(k_r, axis, perm)
            v_next = lax.ppermute(v_r, axis, perm)
            m, l, o = block_merge(m, l, o, k_r, v_r, r)
        else:
            m, l, o = block_merge(m, l, o, k_r, v_r, r)
            # pass KV to the right neighbor (window=1 ring stream)
            k_next = lax.ppermute(k_r, axis, perm)
            v_next = lax.ppermute(v_r, axis, perm)
        return (m, l, o, k_next, v_next), None

    # sp-1 hops WITH a permute, then the last held block folds outside the
    # scan: the final rotation's result would be discarded, and XLA cannot
    # DCE a collective inside the while-op — this saves one full KV trip
    if sp > 1:
        (m, l, o, k_last, v_last), _ = lax.scan(
            hop, (m0, l0, o0, k, v), jnp.arange(sp - 1)
        )
    else:
        m, l, o, k_last, v_last = m0, l0, o0, k, v
    m, l, o = block_merge(m, l, o, k_last, v_last, sp - 1)
    # fully-masked rows (never for causal self-attention, where a token
    # always sees itself) would have l == 0; guard the divide anyway
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def full_attention(q, k, v, causal: bool = True):
    """Single-device reference (the spec ring_attention must match)."""
    d = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    t = q.shape[1]
    if causal:
        pos = jnp.arange(t)
        s = s + jnp.where(pos[None, :] <= pos[:, None], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(q.dtype)


def make_ring_attention_step(
    mesh: jax.sharding.Mesh, causal: bool = True, prefetch: bool = False
):
    """Jitted sharded entry: q/k/v sharded over 'sp' on the sequence dim,
    replicated elsewhere (batch could additionally shard over dp/ep —
    kept sequence-only here since this layer IS the sp showcase)."""
    spec = P(None, "sp", None, None)

    fn = jax.shard_map(
        partial(ring_attention, axis="sp", causal=causal, prefetch=prefetch),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def place(x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jitted, place
