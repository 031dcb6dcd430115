"""Tensor echo — the echo_c++ example as a device-resident RPC step.

Reference: example/echo_c++ (EchoService::Echo returns the request string,
optionally with attachment) driven through the client call stack of
SURVEY.md §3.1. Here the whole server-side hot path — parse, verify,
dispatch, handle, respond (baidu_rpc_protocol.cpp:307-503 ProcessRpcRequest →
SendRpcResponse) — is one fused XLA computation over an HBM-resident frame:
no host round-trip per request.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from incubator_brpc_tpu.ops import framing


def _echo_handler(payload: jnp.ndarray) -> jnp.ndarray:
    return payload


class TensorEchoService:
    """Registry of method_id -> jittable handler, mirroring Server's
    _method_map of MethodProperty (reference server.cpp:1209) at device level.

    Handlers must be shape-preserving uint32->uint32 transforms (static
    shapes; XLA traces each handler once per payload geometry). Behind a
    ``DeviceEndpoint`` a handler sees its payload zero-padded to a width
    the endpoint chooses (the call's bucket, or a wider one it shares a
    dispatch with), and the caller is given the first ``n`` words of the
    answer, ``n`` the words it sent: those must not depend on that width.
    """

    def __init__(self) -> None:
        self._methods: Dict[int, Callable[[jnp.ndarray], jnp.ndarray]] = {}
        self.add_method(0, _echo_handler)

    def add_method(self, method_id: int, handler: Callable[[jnp.ndarray], jnp.ndarray]) -> None:
        if method_id in self._methods:
            raise ValueError(f"method {method_id} already registered")
        self._methods[method_id] = handler

    def step(self, framed: jnp.ndarray) -> jnp.ndarray:
        """One server step: parse + verify + dispatch + respond. Jittable.

        Bad frames (magic/checksum mismatch) produce a response frame with
        error_code=EREQUEST and zeroed payload — branch-free, like the
        reference parse returning an error response rather than crashing.
        """
        header, payload, ok = framing.parse(framed)
        # dispatch: method ids may be sparse, so map id -> dense branch index
        # (the reference's FlatMap lookup, server.cpp:1209, becomes an
        # equality-select + lax.switch branch table). Unknown ids produce an
        # ENOMETHOD error frame, mirroring ProcessRpcRequest's lookup failure
        # path (baidu_rpc_protocol.cpp:423-440).
        keys = sorted(self._methods)
        handlers = [self._methods[k] for k in keys]
        mid = header.method_id
        known = jnp.zeros((), bool)
        branch = jnp.zeros((), jnp.int32)
        for i, k in enumerate(keys):
            hit = mid == jnp.uint32(k)
            known = known | hit
            branch = jnp.where(hit, jnp.int32(i), branch)
        if len(handlers) == 1:
            result = handlers[0](payload)
        else:
            result = jax.lax.switch(branch, handlers, payload)
        ok_all = ok & known
        result = jnp.where(ok_all, result, jnp.zeros_like(result))
        err = jnp.where(
            ok,
            jnp.where(known, jnp.uint32(0), jnp.uint32(1002)),  # ENOMETHOD
            jnp.uint32(1003),  # EREQUEST
        )
        return framing.frame(
            result,
            header.correlation_id,
            method_id=header.method_id,
            flags=framing.FLAG_RESPONSE,
            error_code=err,
        )


def make_echo_step(
    payload_words: int = 256,
    service: Optional[TensorEchoService] = None,
):
    """Returns (jitted step fn, example framed request) for a given payload
    geometry — used by chip_smoke.py and __graft_entry__.entry()."""
    service = service or TensorEchoService()
    step = jax.jit(service.step)
    payload = jnp.arange(payload_words, dtype=jnp.uint32)
    request = framing.frame(payload, correlation_id=1, method_id=0)
    return step, request
