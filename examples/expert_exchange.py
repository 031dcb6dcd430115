#!/usr/bin/env python
"""expert_exchange — the four-chip expert step in one process: a source rank
on device 0 dispatches a micro-batch's routed tokens to three expert ranks
on devices 1-3 as unary tensor calls and combines their partial sums (the
MoE dispatch and combine of a prefill worker in an expert-parallel unit).
Each rank is ``Server(device_index=i)`` whose ``ffn`` method is
``DeviceEndpoint(device=i).server_handler`` over ``ExpertShardService``; the
source holds three ``Channel(transport="tpu")`` and an ``ExpertExchange``.
A token block crosses each link's lane as a ``jax.Array``, the rank's
endpoint runs its step on it where it landed, the answer comes back the same
way: no token and no answer is ever in host memory.
At a small size (hidden 64, 32 experts in 4 groups, top-4, 4 ranks of 8 of
which three are served, 3 layers; off a TPU on four forced host devices);
``benchmark/configs/expert_exchange_dsv3_ep32.json`` is the same unit at
DeepSeek-V3's published widths.
Run: python examples/expert_exchange.py
"""

import os
import sys

sys.path.insert(0, ".")
if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu"):
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from benchmark import manifest  # noqa: E402
from incubator_brpc_tpu.models.expert_exchange import ExpertExchange  # noqa: E402
from incubator_brpc_tpu.models.expert_shard import FFN, ExpertShardService  # noqa: E402
from incubator_brpc_tpu.rpc import (  # noqa: E402
    Channel, ChannelOptions, Controller, Server, ServerOptions)
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402

LAYERS, RANKS, TOKENS, CAPACITY, SEED = 3, 4, 64, 48, 7


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = manifest.load_module("references", "moe_expert_exchange.py")
    moe = ref.Moe(hidden_size=64, moe_intermediate_size=32, n_routed_experts=32,
                  num_experts_per_tok=4, n_group=4, topk_group=2)
    held = moe.n_routed_experts // RANKS
    devices = jax.devices()
    assert len(devices) >= 4, "a source rank and three expert ranks: four devices"

    # the ranks: eight experts of three layers each, a device each
    servers, channels = [], []
    for i in (1, 2, 3):
        endpoint = DeviceEndpoint(
            service=ExpertShardService(
                moe.hidden_size, moe.moe_intermediate_size, held, LAYERS,
                seed=SEED, first_expert=(i - 1) * held),
            device=devices[i], window_size=2)
        server = Server(ServerOptions(device_index=i))
        server.add_service("experts", {"ffn": endpoint.server_handler(method_id=FFN)})
        assert server.start(0)
        channel = Channel()
        assert channel.init(
            f"127.0.0.1:{server.port}",
            options=ChannelOptions(transport="tpu", timeout_ms=120000,
                                   link_controller="single"))
        # the handshake builds the link; an empty request is answered EREQUEST
        channel.call_method("experts", "ffn", b"", cntl=Controller(timeout_ms=120000))
        servers.append(server)
        channels.append(channel)
        print("rank", i - 1, "holds experts", list(ref.share.held(moe, i - 1, RANKS)),
              "on", devices[i], "link", channel._device_sock.link.geometry)

    # the source rank: one micro-batch on device 0, one layer call a layer
    exchange = ExpertExchange(
        channels, [0, held, 2 * held], held, moe.hidden_size, TOKENS, CAPACITY,
        devices[0])
    x = jax.device_put(ref.micro_batch(SEED, 0, TOKENS, moe.hidden_size), devices[0])
    for layer in range(LAYERS):
        weights = ref.gate_weights(moe, SEED, layer, x)  # the published router
        plan = exchange.plan(weights)
        # generous timeout: the first call of a shape compiles the programs
        answer = exchange.call_layer(
            x.astype(jnp.bfloat16), plan, layer, timeout_ms=120000)
        assert not answer.failed(), answer.error_text
        want = ref.combined(moe, SEED, layer, x, weights, [0, 1, 2], RANKS)
        rel_l2, over_rms = (float(v) for v in np.asarray(ref.outside(answer.y, want)))
        assert rel_l2 < 0.01 and over_rms < 0.05, (rel_l2, over_rms)
        print(f"layer {layer}: {plan.tokens} of {TOKENS} tokens sent to the three "
              f"ranks, {sum(plan.pairs)} (token, expert) pairs; the combined sum on "
              f"{next(iter(answer.y.devices()))} is within rel_l2 {rel_l2:.4f}, "
              f"element_over_rms {over_rms:.4f} of the float32 reference")
    for server in servers:
        server.stop()


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)  # the links' daemon threads never join
