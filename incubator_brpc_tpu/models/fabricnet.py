"""FabricNet — the flagship multi-chip workload of the fabric.

The reference proves its distribution primitives with the combo-channel
example pairs (example/parallel_echo_c++, partition_echo_c++,
streaming_echo_c++); FabricNet composes *all* of their TPU lowerings into one
training step over the fabric mesh (SURVEY.md §2.5):

- **dp/ep data fan-out + gradient merge** — ParallelChannel scatter/gather
  (parallel_channel.cpp): batch sharded over ('dp','ep'), gradients psummed
  by the shard_map transpose (the ResponseMerger with merger='sum').
- **tp partitioned service** — PartitionChannel (partition_channel.cpp):
  Megatron-style MLP whose hidden dim is sharded over 'tp'; the reply merge
  is a psum riding ICI.
- **pp pipeline** — chained streaming RPC: GPipe microbatch schedule whose
  stage handoff is a ppermute ring over 'pp' (the credit-window stream of
  stream.cpp with window=1 frame in flight per neighbor).
- **sp sequence ring** — with ``heads > 0`` (the default), EXACT causal
  ring attention over 'sp' (models/ring_attention.py: KV blocks rotate the
  ring, online-softmax accumulation — the long-context slot); with
  ``heads == 0`` the lighter ring-mean context pass built on
  parallel.collective.ring_stream.
- **ep expert exchange** — DynamicPartitionChannel
  (partition_channel.h:134): static round-robin token routing via all_to_all
  over 'ep'.

Everything is shard_map'd over the fabric Mesh, static-shaped, and
differentiable — the driver's ``dryrun_multichip`` jits the full train step.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec as P

from incubator_brpc_tpu.parallel.collective import ring_stream

# like every shard_map in this tree: the replication check stays off
_shard_map = partial(jax.shard_map, check_vma=False)


@dataclasses.dataclass(frozen=True)
class FabricNetConfig:
    d_model: int = 32
    d_ff: int = 64  # sharded over tp — must divide by mesh tp size
    d_expert: int = 32
    experts_per_rank: int = 2
    layers_per_stage: int = 1
    batch: int = 8  # global; must divide by dp*ep*microbatches
    seq: int = 16  # global; must divide by sp
    microbatches: int = 2
    heads: int = 2  # ring-attention heads; 0 = ring-mean context instead
    lr: float = 1e-2
    dtype: jnp.dtype = jnp.float32


def param_specs(heads: int) -> Dict[str, P]:
    """PartitionSpecs for the param pytree (leading 'pp' = pipeline stage)."""
    specs = {
        "w_in": P("pp", None, None, "tp"),
        "w_out": P("pp", None, "tp", None),
        "moe_w1": P("pp", "ep", None, None),
        "moe_w2": P("pp", "ep", None, None),
        "gate": P("pp", None, None),
        "head": P(),
    }
    if heads:
        # attention projections replicated across tp (sp is their axis)
        specs["wqkv"] = P("pp", None, None, None)
        specs["wo"] = P("pp", None, None)
    return specs


def batch_specs() -> Tuple[P, P]:
    x_spec = P(("dp", "ep"), "sp", None)
    return x_spec, x_spec


def init_params(cfg: FabricNetConfig, mesh: jax.sharding.Mesh, seed: int = 0):
    """Initialize the sharded param pytree directly with target shardings so
    XLA materializes each shard on its owner (no host broadcast)."""
    pp = mesh.shape["pp"]
    ep = mesh.shape["ep"]
    d, f, fe = cfg.d_model, cfg.d_ff, cfg.d_expert
    L = cfg.layers_per_stage
    E = cfg.experts_per_rank * ep
    keys = jax.random.split(jax.random.key(seed), 8)
    specs = param_specs(cfg.heads)

    def mk(key, shape, spec, scale):
        # scale is a numpy float64 scalar — multiply in the target dtype or
        # promotion silently upcasts bfloat16 params to float32
        arr = jax.random.normal(key, shape, cfg.dtype) * jnp.asarray(
            scale, cfg.dtype
        )
        return jax.device_put(arr, NamedSharding(mesh, spec))

    params = {
        "w_in": mk(keys[0], (pp, L, d, f), specs["w_in"], 1.0 / np.sqrt(d)),
        "w_out": mk(keys[1], (pp, L, f, d), specs["w_out"], 1.0 / np.sqrt(f)),
        "moe_w1": mk(keys[2], (pp, E, d, fe), specs["moe_w1"], 1.0 / np.sqrt(d)),
        "moe_w2": mk(keys[3], (pp, E, fe, d), specs["moe_w2"], 1.0 / np.sqrt(fe)),
        "gate": mk(keys[4], (pp, d, 1), specs["gate"], 1.0 / np.sqrt(d)),
        "head": mk(keys[5], (d, d), specs["head"], 1.0 / np.sqrt(d)),
    }
    if cfg.heads:
        params["wqkv"] = mk(
            keys[6], (pp, 3, d, d), specs["wqkv"], 1.0 / np.sqrt(d)
        )
        params["wo"] = mk(keys[7], (pp, d, d), specs["wo"], 1.0 / np.sqrt(d))
    return params


def _rms_norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _gelu(x: jnp.ndarray) -> jnp.ndarray:
    """dtype-preserving gelu: jax.nn.gelu's float32 internals promote
    bfloat16 activations, which would poison every downstream matmul (and
    break the pipeline scan whose carry must keep the model dtype)."""
    return jax.nn.gelu(x).astype(x.dtype)


def _mlp_tp(w_in_l, w_out_l, x):
    """Megatron MLP: hidden sharded over 'tp', reply merged with psum —
    the PartitionChannel request/merge path on ICI."""
    h = _gelu(jnp.einsum("bsd,df->bsf", x, w_in_l))
    y = jnp.einsum("bsf,fd->bsd", h, w_out_l)
    return lax.psum(y, "tp")


def _ring_context(x: jnp.ndarray) -> jnp.ndarray:
    """Sequence-parallel global context via the sp ring (streaming RPC
    lowering): fold per-shard sequence means around the ring."""
    sp = lax.axis_size("sp")
    local = jnp.mean(x, axis=1)  # (mb, d)

    def fold(acc, received):
        return acc + received, received

    total, _ = ring_stream(local, "sp", fold, jnp.zeros_like(local))
    return (total / sp)[:, None, :]


def _moe(moe_w1, moe_w2, gate_w, x):
    """Static round-robin MoE over 'ep' — DynamicPartitionChannel lowering.

    Tokens (replicated gate decides magnitude, routing is static round-robin
    by token index) are exchanged with a tiled all_to_all, processed by the
    rank-local experts, and exchanged back (all_to_all is an involution for
    equal tiles).

    Data-dependent routing at a published model's widths lives in
    ``models/expert_shard`` (one rank's share of DeepSeek-V3's routed
    experts: any split of a block's tokens over the experts held, no
    capacity, none dropped) with its reference, the published router
    included, in ``benchmark/references/moe_expert_share.py``; the exchange
    between ranks is still this one.
    """
    ep = lax.axis_size("ep")
    e_local = moe_w1.shape[0]
    mb, sl, d = x.shape
    t = mb * sl
    tokens = x.reshape(t, d)
    g = jax.nn.sigmoid(tokens @ gate_w)  # (t, 1) learned gate
    # group tokens by destination rank (token i -> rank i % ep), chunk-contiguous
    grouped = tokens.reshape(t // ep, ep, d).swapaxes(0, 1).reshape(t, d)
    routed = lax.all_to_all(grouped, "ep", split_axis=0, concat_axis=0, tiled=True)
    # rank-local expert apply: token r -> local expert r % e_local (static)
    xr = routed.reshape(t // e_local, e_local, d).swapaxes(0, 1)  # (e_local, t/e_local, d)
    h = _gelu(jnp.einsum("etd,edf->etf", xr, moe_w1))
    yr = jnp.einsum("etf,efd->etd", h, moe_w2)
    routed_out = yr.swapaxes(0, 1).reshape(t, d)
    back = lax.all_to_all(routed_out, "ep", split_axis=0, concat_axis=0, tiled=True)
    ungrouped = back.reshape(ep, t // ep, d).swapaxes(0, 1).reshape(t, d)
    return (ungrouped * g).reshape(mb, sl, d)


def _ring_attn_block(wqkv, wo, heads, x, prefetch: bool = False):
    """Causal ring attention over 'sp' (models/ring_attention.py) with
    per-stage projections — the long-context sequence-parallel block.
    ``prefetch`` emits each hop's KV rotation before the held block's
    fold (rotate-while-computing; bit-identical output)."""
    from incubator_brpc_tpu.models.ring_attention import ring_attention

    mb, sl, d = x.shape
    q = (x @ wqkv[0]).reshape(mb, sl, heads, d // heads)
    k = (x @ wqkv[1]).reshape(mb, sl, heads, d // heads)
    v = (x @ wqkv[2]).reshape(mb, sl, heads, d // heads)
    out = ring_attention(q, k, v, axis="sp", causal=True, prefetch=prefetch)
    return out.reshape(mb, sl, d) @ wo


def _stage_fn(sp_params, heads, prefetch, x):
    """One pipeline stage: L residual [tp-MLP] layers + sp sequence block
    (ring attention, or ring-mean context when heads=0) + ep MoE block.
    ``heads``/``prefetch`` are static config, threaded via partial — never
    through the (traced-array) param pytree."""
    L = sp_params["w_in"].shape[0]
    for l in range(L):
        x = x + _mlp_tp(sp_params["w_in"][l], sp_params["w_out"][l], _rms_norm(x))
    if heads:
        x = x + _ring_attn_block(
            sp_params["wqkv"], sp_params["wo"], heads, _rms_norm(x),
            prefetch=prefetch,
        )
    else:
        x = x + _ring_context(x)
    x = x + _moe(sp_params["moe_w1"], sp_params["moe_w2"], sp_params["gate"], _rms_norm(x))
    return x


def _pipeline(stage, xs):
    """GPipe over 'pp': scan of M + pp - 1 ticks; stage handoff is a
    ppermute ring (streaming-RPC frame to the right neighbor each tick)."""
    pp = lax.axis_size("pp")
    sidx = lax.axis_index("pp")
    m = xs.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    buf = jnp.zeros_like(xs[0])
    outs = jnp.zeros_like(xs)

    def tick(carry, t):
        buf, outs = carry
        inp = jnp.where(sidx == 0, xs[jnp.clip(t, 0, m - 1)], buf)
        out = stage(inp)
        ot = t - (pp - 1)
        valid = (ot >= 0) & (ot < m) & (sidx == pp - 1)
        outs = jnp.where(valid, outs.at[jnp.clip(ot, 0, m - 1)].set(out), outs)
        buf = lax.ppermute(out, "pp", perm)
        return (buf, outs), None

    (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(m + pp - 1))
    # broadcast last stage's outputs to every pp rank (replicates over pp)
    outs = lax.psum(jnp.where(sidx == pp - 1, outs, jnp.zeros_like(outs)), "pp")
    return outs


def _local_forward(
    cfg: FabricNetConfig, params, x, microbatches: int = 0,
    prefetch: bool = False,
):
    """Per-rank forward body (inside shard_map). x: (B_local, S_local, d).
    ``microbatches`` overrides the config's pipeline microbatch count (the
    overlap schedule feeds one outer slice per inner pipeline fill);
    ``prefetch`` selects the ring attention rotate-while-computing
    emission (bit-identical, see models/ring_attention.py)."""
    # squeeze this rank's pipeline-stage slice (leading pp dim is size 1 here)
    sp_params = {
        "w_in": params["w_in"][0],
        "w_out": params["w_out"][0],
        "moe_w1": params["moe_w1"][0],
        "moe_w2": params["moe_w2"][0],
        "gate": params["gate"][0],
    }
    if cfg.heads:
        sp_params["wqkv"] = params["wqkv"][0]
        sp_params["wo"] = params["wo"][0]
    bl, sl, d = x.shape
    m = microbatches or cfg.microbatches
    xs = x.reshape(m, bl // m, sl, d)
    outs = _pipeline(partial(_stage_fn, sp_params, cfg.heads, prefetch), xs)
    out = outs.reshape(bl, sl, d)
    return out @ params["head"]


def _local_loss(cfg: FabricNetConfig, params, x, y):
    out = _local_forward(cfg, params, x)
    local = jnp.mean(jnp.square(out - y))
    return lax.pmean(local, ("dp", "ep", "sp", "tp", "pp"))


_ALL_AXES = ("dp", "ep", "sp", "tp", "pp")


def _slice_local_loss(cfg: FabricNetConfig, prefetch: bool, params, x, y):
    """One microbatch slice's local loss (inside shard_map): the slice
    pipelines with a single inner microbatch — the outer schedule IS the
    microbatch loop.  ``prefetch`` selects the ring attention
    rotate-while-computing emission (bit-identical)."""
    out = _local_forward(cfg, params, x, microbatches=1, prefetch=prefetch)
    local = jnp.mean(jnp.square(out - y))
    return lax.pmean(local, _ALL_AXES)


def _microbatch_slicer(cfg: FabricNetConfig, mesh: jax.sharding.Mesh):
    """Jitted per-rank reshape (B, S, d) -> (M, B/M, S, d): each rank
    splits its LOCAL batch rows into the M schedule slices — slicing the
    global batch axis outside shard_map would gather a contiguous global
    block that lives on a subset of the dp/ep ranks instead."""
    x_spec, _ = batch_specs()
    m_slices = cfg.microbatches

    def body(x):
        bl = x.shape[0]
        return x.reshape(m_slices, bl // m_slices, *x.shape[1:])

    return jax.jit(_shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec,),
        out_specs=P(None, ("dp", "ep"), "sp", None),
    ))


def make_forward_step(cfg: FabricNetConfig, mesh: jax.sharding.Mesh):
    """Jitted sharded forward: (params, x) -> (B, S, d) output."""
    x_spec, _ = batch_specs()
    fwd = _shard_map(
        partial(_local_forward, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg.heads), x_spec),
        out_specs=x_spec,
    )
    return jax.jit(fwd)


def make_train_step(
    cfg: FabricNetConfig, mesh: jax.sharding.Mesh, schedule: str = "fused"
):
    """Jitted FULL training step (forward + backward + SGD update) with all
    five parallelism axes live. Returns the jitted step function.

    ``schedule`` selects how gradient collectives meet compute:

    - ``"fused"`` (default, the pre-overlap path unchanged): one
      value_and_grad through the shard_map boundary — the boundary
      transpose emits the gradient psums after the whole backward.
    - ``"serialized"``: the microbatch-sliced A/B baseline — slice m's
      per-leaf gradient psums are barriered before slice m+1's forward
      (compute waits for the full collective, the ~75% MFU shape).
    - ``"overlapped"``: same sliced dataflow with the barrier dropped —
      slice m's chunked psums overlap slice m+1's compute, and ring
      attention prefetches its KV rotation (T3).  Bit-identical loss and
      grads to ``"serialized"``.
    """
    x_spec, y_spec = batch_specs()
    if schedule not in ("fused", "serialized", "overlapped"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule != "fused":
        # The T3 microbatch schedule (docs/DEVICE_PLANE.md "overlap
        # scheduler"): grads accumulate per microbatch slice, each
        # slice's gradient reduction firing as per-param-leaf psums at
        # its OWN shard_map boundary transpose — the chunked collective
        # (one sub-collective per leaf, not one fused all-grads psum).
        # "serialized" pins slice m+1's forward behind slice m's psums
        # with an optimization_barrier — the compute-waits-for-full-
        # collective shape fabricnet was stuck at; "overlapped" drops
        # the barrier, so slice m's collectives are dataflow-independent
        # of slice m+1's compute and the scheduler runs them behind it.
        # The barrier is an identity — both schedules run IDENTICAL ops,
        # so loss and grads are bit-identical between them.
        overlap = schedule == "overlapped"
        m_slices = cfg.microbatches
        slice_loss = _shard_map(
            partial(_slice_local_loss, cfg, overlap),
            mesh=mesh,
            in_specs=(param_specs(cfg.heads), x_spec, y_spec),
            out_specs=P(),
        )
        grad_fn = jax.value_and_grad(slice_loss)
        slicer = _microbatch_slicer(cfg, mesh)

        def step(params, x, y):
            xs, ys = slicer(x), slicer(y)
            acc = None
            loss_acc = jnp.zeros((), dtype=jnp.float32)
            gate = None  # previous slice's reduced grads
            for m in range(m_slices):
                xm, ym = xs[m], ys[m]
                if gate is not None and not overlap:
                    # serialized: slice m's input becomes data-dependent
                    # on every gradient psum of slice m-1
                    xm, gate = lax.optimization_barrier((xm, gate))
                l_m, g_m = grad_fn(params, xm, ym)
                acc = g_m if acc is None else jax.tree_util.tree_map(
                    jnp.add, acc, g_m
                )
                loss_acc = loss_acc + l_m.astype(jnp.float32)
                gate = g_m
            inv = 1.0 / m_slices
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - cfg.lr * (g * jnp.asarray(inv, g.dtype)
                                           ).astype(p.dtype),
                params, acc,
            )
            return new_params, loss_acc * inv

        return jax.jit(step, donate_argnums=(0,))

    loss_fn = _shard_map(
        partial(_local_loss, cfg),
        mesh=mesh,
        in_specs=(param_specs(cfg.heads), x_spec, y_spec),
        out_specs=P(),
    )

    def step(params, x, y):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, x, y))(params)
        new_params = jax.tree_util.tree_map(lambda p, g: p - cfg.lr * g, params, grads)
        return new_params, loss

    return jax.jit(step, donate_argnums=(0,))


def make_batch(cfg: FabricNetConfig, mesh: jax.sharding.Mesh, seed: int = 1):
    """Random (x, y) placed with the fabric batch sharding."""
    kx, ky = jax.random.split(jax.random.key(seed))
    x_spec, y_spec = batch_specs()
    shape = (cfg.batch, cfg.seq, cfg.d_model)
    x = jax.device_put(jax.random.normal(kx, shape, cfg.dtype), NamedSharding(mesh, x_spec))
    y = jax.device_put(jax.random.normal(ky, shape, cfg.dtype), NamedSharding(mesh, y_spec))
    return x, y


def validate_config(cfg: FabricNetConfig, mesh: jax.sharding.Mesh) -> None:
    """Static divisibility checks (all shapes must be static for XLA)."""
    dp, pp, tp, sp, ep = (mesh.shape[a] for a in ("dp", "pp", "tp", "sp", "ep"))
    assert cfg.d_ff % tp == 0, "d_ff must divide by tp"
    assert cfg.batch % (dp * ep) == 0, "batch must divide by dp*ep"
    bl = cfg.batch // (dp * ep)
    assert bl % cfg.microbatches == 0, "local batch must divide microbatches"
    assert cfg.seq % sp == 0, "seq must divide by sp"
    if cfg.heads:
        assert cfg.d_model % cfg.heads == 0, "d_model must divide by heads"
    t = (bl // cfg.microbatches) * (cfg.seq // sp)
    assert t % ep == 0, "local tokens must divide by ep"
    assert t % (cfg.experts_per_rank * ep) == 0, "local tokens must divide experts"
