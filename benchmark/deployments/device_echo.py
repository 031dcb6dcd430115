"""One server that holds the chip: ``Server()`` with one service whose
method is ``DeviceEndpoint(...).server_handler()`` over
``TensorEchoService`` — host RPC in, frame to HBM, the fused step (parse,
verify the checksum, dispatch, respond) on the device, response out.
The set-up is ``chip_smoke.py``'s ``device_rpc`` phase; what is timed is
not its code path."""

from __future__ import annotations


def _flip_bit(words):
    """Control: the answer is altered where it is produced. One bit of
    every response flips, in the first or the second word by the second
    word's parity."""
    where = words[1] & 1
    return words.at[where].set(words[where] ^ 1)


def _stale(handler):
    """Control: a call is answered with the previous call's response
    where the lengths agree — acknowledged, and not its own bytes."""
    last = {}

    def stale(cntl, request):
        out = handler(cntl, request)
        previous = last.get(len(out), out)
        last[len(out)] = out
        return previous

    return stale


CONTROLS = ("flip_bit", "stale")


class Deployment:
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models.tensor_echo import TensorEchoService
        from incubator_brpc_tpu.rpc import Server
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        service = TensorEchoService()
        method_id = int(config["method_id"])
        if control == "flip_bit":
            method_id = 1
            service.add_method(method_id, _flip_bit)
        self.endpoint = DeviceEndpoint(service=service, **config["endpoint"])
        handler = self.endpoint.server_handler(method_id=method_id)
        if control == "stale":
            handler = _stale(handler)
        if spans is not None:
            handler = spans.wrap(handler)
        self.server = Server()
        self.server.add_service("tensor", {"echo": handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self.devices = [self.endpoint.device]
        self._jax = jax

    def warm(self, traffic: dict) -> None:
        """Compile the (batch, bucket) programs this mix can form and no
        others: a batch holds at most one call per caller. Follows
        ``DeviceEndpoint._dispatch_batch`` (and ``warm``, which always
        compiles every batch up to ``max_batch``)."""
        import jax.numpy as jnp
        import numpy as np

        from incubator_brpc_tpu.transport.device import _bucket_words

        ep, jax = self.endpoint, self._jax
        top = min(int(traffic["callers"]), ep.max_batch)
        outs = []
        for size in sorted(set(traffic["sizes"])):
            bucket = _bucket_words(max(1, (size + 3) // 4))
            outs.append(
                ep._program(
                    jax.device_put(
                        jnp.asarray(np.zeros(bucket, np.uint32)), ep.device
                    ),
                    jnp.uint32(1),
                    jnp.uint32(0),
                )
            )
            batch = 2
            while batch < 2 * top:
                outs.append(
                    ep._batch_program(
                        jax.device_put(
                            jnp.asarray(np.zeros((batch, bucket), np.uint32)),
                            ep.device,
                        ),
                        jnp.asarray(np.zeros(batch, np.uint32)),
                        jnp.asarray(np.zeros(batch, np.uint32)),
                    )
                )
                batch <<= 1
        jax.block_until_ready(outs)

    def holds(self) -> list:
        """``(what, value, limit, held)`` for each guarantee that is read
        from the deployment and not from the responses."""
        return []

    def close(self) -> None:
        self.server.stop()
        self.server.join(timeout=10)
