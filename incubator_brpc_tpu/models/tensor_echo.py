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

    Handlers are shape-preserving uint32->uint32 transforms (static
    shapes; XLA traces each handler once per payload geometry). Behind a
    ``DeviceEndpoint`` this is the service that keeps no state
    (``init_state`` gives ``None``), whose step is row-wise (a dispatch's
    rows know nothing of each other: ``dispatch_step`` vmaps ``step``)
    and whose answer is as long as its request (``answer_bytes``). A
    handler sees its payload zero-padded to a width the endpoint chooses
    (the call's bucket, or a wider one it shares a dispatch with), and
    the caller is given the answer cut at the request's length: that part
    must not depend on the width. ``models/record_table`` is the service
    that keeps state, sees its batch whole and answers 1,000 B to 8.
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

    # -- what a DeviceEndpoint asks of its service --------------------------

    def init_state(self, device) -> None:
        """Nothing lives on the device between calls."""
        return None

    def answer_bytes(self, method_id: int, request_bytes: int) -> int:
        return request_bytes

    def dispatch_step(self, state, rows, cids, mids):
        """One dispatch: every row framed and stepped on its own. A row
        alone is stepped as the 1-D program it always was: vmapped over a
        batch of one, XLA lays the frame out as ``[1, n]`` and assembles
        it by update-slices (compiled for a v5e at 1 Mi words, PR 37)."""

        def row(padded, cid_lo, mid):
            return self.step(
                framing.frame(padded, (cid_lo, jnp.uint32(0)), method_id=mid)
            )

        if rows.shape[0] == 1:
            return state, row(rows[0], cids[0], mids[0])[None]
        return state, jax.vmap(row)(rows, cids, mids)

    def account(self, mids, frames) -> None:
        """No counter of its own."""


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
