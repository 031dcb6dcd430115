"""Pipelined cross-process collective sessions — RPC-scheduled, ICI-run.

The combo-channel fusion (rpc/combo.py + parallel/collective.py) collapses
a ParallelChannel call into ONE shard_map dispatch — but only inside one
controller, where the client stages every party's operand itself. Across
controllers a one-shot fused call cannot win: the client cannot place
bytes on non-addressable devices, so the operands would ride the host
plane anyway (docs/DEVICE_PLANE.md). What DOES win across processes is
the PIPELINED shape: schedule once over the host plane, then run K
lockstep collective steps whose operands never leave the devices — the
steady-state of the reference's "RDMA for tensor traffic" story, and of
every real multi-host training loop.

A session is proposed as a plain RPC to every server
(``_tpu_transport.collective``): {parties (global device ids), your
party index, steps, width, seed}. Each party — client included — then
runs the IDENTICAL jitted program: K chained ``shard_map`` steps over
``Mesh(parties, ("party",))`` where each step exchanges shards with a
collective (``pmean`` here: every party's operand converges to the
global mean, which makes convergence a checkable invariant). Lockstep
needs no per-step coordination: the step count was agreed up front, the
chain is data-dependent, and XLA pipelines the K dispatches.

Deployment contract: every party is one process of a ``jax.distributed``
group (the mc_link deployment); the session only needs the group — no
device link is required, though sessions and links share the group
freely (mc_worker's fabric client runs both).
"""

from __future__ import annotations

import json
import logging
import time
from typing import List, Tuple

import numpy as np

from incubator_brpc_tpu.bvar import Adder, LatencyRecorder

logger = logging.getLogger(__name__)

COLLECTIVE_METHOD = "collective"

# How long propose_collective watches freshly-dispatched RUN proposals for
# an instant bounce (admission ELIMIT from an overlapping session, a server
# mid-stop) before entering its own session. The accept pre-ack already
# covers validation rejections, so this only needs to span a local RPC
# round trip — 10x under the old fixed 0.5 s grace window.
_REJECT_WATCH_S = 0.05
# A run proposal refused with ELIMIT is sent again after each of these waits,
# once each: the refusal may be the proposer's own accept, whose admission
# slot the server frees only after writing the ack (propose_collective). The
# waits are a descheduled server thread's, not a session's: a proposal still
# refused after them (three sent in all, ~0.3 s) is rejected.
_OWN_ACCEPT_RESEND_S = (0.05, 0.2)

# session-level observability (ISSUE: the collective plane was blind):
# every run_collective_session — proposer and server parties alike —
# counts here and, when rpcz samples it, leaves one span in the proposing
# RPC's trace carrying step count / operand width / participant set
collective_sessions = Adder(name="mc_collective_sessions")
collective_steps = Adder(name="mc_collective_steps")
collective_errors = Adder(name="mc_collective_errors")
collective_session_us = LatencyRecorder(name="mc_collective_session_us")


def _start_session_span(
    party_ids: List[int],
    own_index: int,
    steps: int,
    width: int,
    trace_id: int = 0,
    parent_span_id: int = 0,
):
    from incubator_brpc_tpu.builtin.rpcz import (
        SPAN_TYPE_COLLECTIVE,
        start_custom_span,
    )

    span = start_custom_span(
        SPAN_TYPE_COLLECTIVE,
        "_tpu_transport",
        COLLECTIVE_METHOD,
        trace_id=trace_id,
        parent_span_id=parent_span_id,
    )
    if span is not None:
        span.annotate(
            f"steps={steps} width={width} index={own_index} "
            f"parties={party_ids}"
        )
    return span


def _end_session_span(span, error_code: int = 0) -> None:
    from incubator_brpc_tpu.builtin.rpcz import end_custom_span

    end_custom_span(span, error_code=error_code)


def _run_observed_session(span, party_ids, own_index, steps, width, seed):
    """run_collective_session under span/counter bookkeeping: a raise
    counts one error and closes the span with EINTERNAL (shared by the
    handler and proposer parties); the SUCCESS close stays with the
    caller, which may have more to do before the span ends."""
    try:
        return run_collective_session(party_ids, own_index, steps, width, seed)
    except Exception:
        collective_errors << 1
        from incubator_brpc_tpu.utils.status import ErrorCode

        _end_session_span(span, error_code=ErrorCode.EINTERNAL)
        raise


def _devices_by_id(ids: List[int]):
    from incubator_brpc_tpu.parallel.mc_dispatch import (
        _devices_by_id as _impl,
    )

    return _impl(ids)


# -- pmean as ONE registered method on the collective method plane -------------
#
# The session machinery itself lives in parallel/mc_dispatch.py and is
# kernel-agnostic: a session names a registered device method and every
# party fingerprint-validates it before entering lockstep. pmean — the
# original canned demo — survives as just one such method: the kernel
# below reinterprets the row bytes as float32, pmeans over the party
# axis, and writes the bytes back. It is width-independent (geometry is
# the DeviceMethod's), so one source mints a DeviceMethod per requested
# width via the resolver — identical fingerprints in every process that
# imports this module.

PMEAN_SERVICE = "_collective"
PMEAN_METHOD = "pmean"


def _pmean_bytes_kernel(data, n):
    import jax
    import jax.numpy as jnp

    f = jax.lax.bitcast_convert_type(data.reshape(-1, 4), jnp.float32)
    m = jax.lax.pmean(f, "par")
    return jax.lax.bitcast_convert_type(m, jnp.uint8).reshape(-1), n


_pmean_dms: dict = {}
_pmean_lock = __import__("threading").Lock()


def _pmean_dm(width_bytes: int):
    from incubator_brpc_tpu.parallel import quantized as _quantized
    from incubator_brpc_tpu.rpc.device_method import DeviceMethod

    with _pmean_lock:
        dm = _pmean_dms.get(width_bytes)
        if dm is None:
            # chunkable: pmean is elementwise along the width (psum of a
            # slice IS the slice of the psum) and passes n through — the
            # chunk-safety contract verbatim (the declaration is a
            # capability, not kernel identity: fingerprints unchanged)
            dm = DeviceMethod(
                _pmean_bytes_kernel, width=width_bytes, chunkable=True
            )
            # the quantize= session knob resolves through these variants
            # (block-aligned widths only; others reject pre-lockstep)
            _quantized.attach_pmean_variants(dm, width_bytes)
            _pmean_dms[width_bytes] = dm
        return dm


def _resolve_pmean(service: str, method: str, width):
    """mc_dispatch method resolver: mints the pmean DeviceMethod for any
    float32-aligned width, so sessions of arbitrary geometry resolve the
    same fingerprint everywhere without a Server registration."""
    if (
        service == PMEAN_SERVICE
        and method == PMEAN_METHOD
        and isinstance(width, int)
        and width > 0
        and width % 4 == 0
    ):
        return _pmean_dm(width)
    return None


def _install_resolver() -> None:
    from incubator_brpc_tpu.parallel import mc_dispatch

    mc_dispatch.register_method_resolver(_resolve_pmean)


_install_resolver()


def run_collective_session(
    party_ids: List[int],
    own_index: int,
    steps: int,
    width: int,
    seed: int,
) -> Tuple[np.ndarray, float]:
    """Run this party's half of the session; returns (final own shard,
    elapsed seconds). Every party calls this with identical arguments
    except ``own_index`` — the programs must match or the collectives
    cannot rendezvous. Since the collective method plane landed this is a
    thin float32 veneer over ``mc_dispatch.run_dispatch_session`` with
    the registered pmean method: one step pulls every party toward the
    global mean, the invariant each party verifies independently."""
    from incubator_brpc_tpu.parallel.mc_dispatch import run_dispatch_session

    dm = _pmean_dm(4 * width)
    # every party's operand derives from the seed, so each side can stage
    # whatever shards it addresses without communication (exactly its own
    # row in the mc deployment; all rows in a single-controller run)
    operands = [
        _party_operand(seed, i, width).tobytes()
        for i in range(len(party_ids))
    ]
    own_row, own_n, elapsed = run_dispatch_session(
        party_ids, own_index, dm, operands, steps,
        service=PMEAN_SERVICE, method=PMEAN_METHOD,
    )
    own = np.frombuffer(
        bytes(np.asarray(own_row[:own_n], dtype=np.uint8)), dtype=np.float32
    ).copy()
    collective_sessions << 1
    collective_steps << steps
    collective_session_us << elapsed * 1e6
    return own, elapsed


def _party_operand(seed: int, index: int, width: int) -> np.ndarray:
    rng = np.random.default_rng(seed + index)
    return rng.standard_normal(width).astype(np.float32)


def expected_mean(seed: int, nparties: int, width: int) -> np.ndarray:
    return np.mean(
        [_party_operand(seed, i, width) for i in range(nparties)], axis=0
    )


def make_collective_handler(server):
    """Server half: accept a session proposal, run our party's program on
    a worker fiber, answer with the final shard's checksum once the chain
    drains (the response doubles as the completion barrier the client
    collects)."""

    def collective(cntl, request: bytes) -> bytes:
        try:
            req = json.loads(request.decode())
            party_ids = [int(i) for i in req["parties"]]
            own_index = int(req["index"])
            steps = int(req["steps"])
            width = int(req["width"])
            seed = int(req["seed"])
        except (ValueError, KeyError, TypeError) as e:
            from incubator_brpc_tpu.utils.status import ErrorCode

            cntl.set_failed(ErrorCode.EREQUEST, f"bad collective proposal: {e}")
            return b""
        if not (0 < steps <= 100_000 and 0 < width <= (1 << 20)):
            from incubator_brpc_tpu.utils.status import ErrorCode

            cntl.set_failed(
                ErrorCode.EREQUEST, "collective proposal out of bounds"
            )
            return b""
        if req.get("phase") == "accept":
            # Accept pre-ack (ADVICE r5): the proposer waits for every
            # party's explicit accept BEFORE entering its own session,
            # instead of burning a fixed grace window. Validation beyond
            # the bounds above: every named device must be addressable in
            # this process's global view, or the session could never
            # rendezvous. Nothing is run or reserved here.
            try:
                _devices_by_id(party_ids)
            except ValueError as e:
                from incubator_brpc_tpu.utils.status import ErrorCode

                cntl.set_failed(ErrorCode.EREQUEST, str(e))
                return b""
            return json.dumps({"accept": True, "index": own_index}).encode()
        # the session span lands in the PROPOSING client's trace: the
        # trace/span ids arrived in the request meta (baidu_std-style
        # Dapper propagation) and are already on the controller
        span = _start_session_span(
            party_ids, own_index, steps, width,
            trace_id=cntl.trace_id, parent_span_id=cntl.span_id,
        )
        # Liveness: a party that never joins stalls the rendezvous until
        # the collective backend's own timeout errors the chain (gloo on
        # the CPU fabric; the coordination service reports dead PROCESSES
        # group-wide) — the raise lands here and answers EINTERNAL. A
        # live-but-declining peer is caught on the client by the accept
        # pre-ack phase in propose_collective.
        own, elapsed = _run_observed_session(
            span, party_ids, own_index, steps, width, seed
        )
        _end_session_span(span)
        return json.dumps(
            {
                "checksum": float(np.sum(own, dtype=np.float64)),
                "elapsed_s": elapsed,
                "steps": steps,
            }
        ).encode()

    return collective


def propose_collective(
    channels,
    party_ids: List[int],
    client_index: int,
    steps: int,
    width: int,
    seed: int,
    timeout_ms: float = 120000,
):
    """Client half: propose the session to every server (async — they
    must all start dispatching, the collective needs every party), run
    our own party's program, then collect completions. Returns
    {"own": shard, "elapsed_s": s, "server_checksums": [...]}.

    ``channels[i]`` is an initialized host channel to the server playing
    party ``server_indexes[i]``; party indexes are assigned positionally:
    servers take every index except ``client_index``."""
    import threading

    from incubator_brpc_tpu.rpc.controller import Controller
    from incubator_brpc_tpu.transport.device_link import HANDSHAKE_SERVICE
    from incubator_brpc_tpu.utils.status import ErrorCode

    server_indexes = [i for i in range(len(party_ids)) if i != client_index]
    if len(server_indexes) != len(channels):
        raise ValueError("one channel per server party required")

    def proposal(idx: int, phase: str = "") -> bytes:
        d = {
            "parties": party_ids,
            "index": idx,
            "steps": steps,
            "width": width,
            "seed": seed,
        }
        if phase:
            d["phase"] = phase
        return json.dumps(d).encode()

    # Phase 1 — explicit accept pre-ack from EVERY server (replaces the
    # old fixed 0.5 s grace window, ADVICE r5): each party validates the
    # proposal (fields, bounds, device visibility) and answers
    # immediately, without running anything. A rejection surfaces here,
    # BEFORE we enter our own session whose collective would wait on a
    # party that never joins — and a clean accept set lets us proceed the
    # moment the last ack lands instead of always burning 500 ms.
    accepts = []
    for ch, idx in zip(channels, server_indexes):
        cntl = Controller(timeout_ms=timeout_ms)
        ev = threading.Event()
        ch.call_method(
            HANDSHAKE_SERVICE,
            COLLECTIVE_METHOD,
            proposal(idx, phase="accept"),
            cntl=cntl,
            done=lambda c, _ev=ev: _ev.set(),
        )
        accepts.append((cntl, ev))
    accept_deadline = time.monotonic() + timeout_ms / 1000.0
    for cntl, ev in accepts:
        if not ev.wait(max(0.0, accept_deadline - time.monotonic())):
            raise TimeoutError("collective peer never acknowledged proposal")
        if cntl.failed():
            raise RuntimeError(
                f"collective proposal rejected: {cntl.error_text}"
            )

    # Phase 2 — the run proposals (async: every party must be dispatching
    # before any can finish; a sync proposal to server A would deadlock —
    # A's collective blocks on parties that were never told to start).
    # Mid-session process death stays the backend's liveness domain (the
    # coordination service / gloo timeout errors the chain group-wide).
    def propose_run(ch, idx):
        cntl = Controller(timeout_ms=timeout_ms)
        ev = threading.Event()
        ch.call_method(
            HANDSHAKE_SERVICE,
            COLLECTIVE_METHOD,
            proposal(idx),
            cntl=cntl,
            done=lambda c, _ev=ev: _ev.set(),
        )
        return cntl, ev

    pending = [
        propose_run(ch, idx) for ch, idx in zip(channels, server_indexes)
    ]
    # Short rejection watch before committing to our own session: the
    # accept phase reserves nothing, so a run proposal can still bounce
    # instantly (admission ELIMIT from an overlapping session, a server
    # mid-stop). A completed failure here means a party that will never
    # join — surface it now rather than waiting out the collective
    # backend's timeout. Bounded at _REJECT_WATCH_S (one local RPC round
    # trip), not the old always-burned 0.5 s.
    # One ELIMIT is not an overlapping session: a server frees a call's
    # admission slot AFTER it has written the response (Server._finish),
    # so a run proposal that follows our own accept's ack closely can find
    # that accept still counted against collective_max_concurrency. Such a
    # proposal is sent again after each wait of _OWN_ACCEPT_RESEND_S; a
    # session that really overlaps holds its slot for its whole chain and
    # is still refused after the last.
    resent = [0] * len(pending)
    watch_deadline = time.monotonic() + _REJECT_WATCH_S
    while time.monotonic() < watch_deadline:
        for i, (cntl, ev) in enumerate(pending):
            if not (ev.is_set() and cntl.failed()):
                continue
            if (
                cntl.error_code != ErrorCode.ELIMIT
                or resent[i] == len(_OWN_ACCEPT_RESEND_S)
            ):
                raise RuntimeError(
                    f"collective proposal rejected: {cntl.error_text}"
                )
            time.sleep(_OWN_ACCEPT_RESEND_S[resent[i]])
            resent[i] += 1
            pending[i] = propose_run(channels[i], server_indexes[i])
            watch_deadline = time.monotonic() + _REJECT_WATCH_S
        time.sleep(0.005)
    span = _start_session_span(party_ids, client_index, steps, width)
    own, elapsed = _run_observed_session(
        span, party_ids, client_index, steps, width, seed
    )
    _end_session_span(span)
    checksums = []
    deadline = time.monotonic() + timeout_ms / 1000.0  # shared, not per-peer
    for cntl, ev in pending:
        if not ev.wait(max(0.0, deadline - time.monotonic())):
            raise TimeoutError("collective peer never completed")
        if cntl.failed():
            raise RuntimeError(f"collective peer failed: {cntl.error_text}")
        checksums.append(
            json.loads(cntl.response_payload.decode())["checksum"]
        )
    return {"own": own, "elapsed_s": elapsed, "server_checksums": checksums}
