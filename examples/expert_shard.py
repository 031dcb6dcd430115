#!/usr/bin/env python
"""expert_shard — one rank of an expert-parallel unit as an RPC server, and
a source rank as its client (the MoE dispatch of a serving unit: a worker
calls the rank that holds eight of a layer's experts with the tokens its
router sent there and waits for the partial sum). The server holds the
rank's expert weights in HBM behind a ``DeviceEndpoint``; the client routes
a micro-batch through the published router, sends each layer's share as one
``ffn`` call and checks the answer against the plain reference.
At a small size (hidden 64, 32 experts in 4 groups, top-4, 4 ranks of 8,
3 layers); ``benchmark/configs/expert_shard_dsv3_ep32.json`` is the same
pair at DeepSeek-V3's published widths.
Run: python examples/expert_shard.py
"""

import sys

sys.path.insert(0, ".")

from benchmark import manifest  # noqa: E402
from incubator_brpc_tpu.models.expert_shard import FFN, ExpertShardService  # noqa: E402
from incubator_brpc_tpu.rpc import Channel, Controller, Server  # noqa: E402
from incubator_brpc_tpu.transport.device import DeviceEndpoint  # noqa: E402

LAYERS, RANKS, RANK, SEED = 3, 4, 1, 7


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    ref = manifest.load_module("references", "moe_expert_share.py")
    moe = ref.Moe(hidden_size=64, moe_intermediate_size=32, n_routed_experts=32,
                  num_experts_per_tok=4, n_group=4, topk_group=2)
    held = moe.n_routed_experts // RANKS

    # the server: rank 1's eight experts of three layers, on the device
    endpoint = DeviceEndpoint(
        service=ExpertShardService(
            moe.hidden_size, moe.moe_intermediate_size, held, LAYERS,
            seed=SEED, first_expert=RANK * held),
        window_size=8)
    server = Server()
    server.add_service("experts", {"ffn": endpoint.server_handler(method_id=FFN)})
    assert server.start(0)
    print("rank", RANK, "of", RANKS, "holds experts",
          list(ref.held(moe, RANK, RANKS)), "on", endpoint.device)

    # the client: a source rank with one micro-batch of 48 tokens
    channel = Channel()
    assert channel.init(f"127.0.0.1:{server.port}")
    for layer in range(LAYERS):
        x = ref.micro_batch(b"examples/expert_shard", layer, 0, 48, moe.hidden_size)
        rows, weights = ref.sent_here(
            moe, x, ref.router_weights(moe, SEED, layer), RANK, RANKS)
        # generous timeout: the first call of a size compiles the device program
        cntl = channel.call_method(
            "experts", "ffn", ref.pack_request(layer, x[jnp.asarray(rows)], weights),
            cntl=Controller(timeout_ms=120000))
        assert cntl.ok(), cntl.error_text
        answer = ref.unpack_answer(cntl.response_payload, moe.hidden_size)
        want = np.asarray(ref.share(moe, SEED, layer, x, RANK, RANKS))[rows]
        rel_l2, _ = ref.outside(answer, want)
        assert rel_l2 < 0.01, rel_l2
        print(f"layer {layer}: {len(rows)} of 48 tokens sent here, "
              f"{int((weights != 0).sum())} (token, expert) pairs; the partial "
              f"sum is within {rel_l2:.4f} of the float32 reference")
    server.stop()


if __name__ == "__main__":
    main()
