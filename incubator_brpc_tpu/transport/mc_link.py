"""Multi-controller device links — the device plane across PROCESSES.

The single-controller ``DeviceLink`` (transport/device_link.py) holds both
halves of the QP in one process: one drive fiber fills both parties' slots
and dispatches the exchange step. The reference transport this plane
re-thinks connects *separate hosts*: the RDMA handshake crosses the TCP
socket between two machines and each side runs its own send/recv rings
(/root/reference/src/brpc/rdma/rdma_endpoint.h:42-213, per-host device init
/root/reference/src/brpc/rdma/rdma_helper.cpp). This module is that
deployment for XLA's multi-controller model:

- **One process per party.** Each process owns ONE side of the link: its
  own device (``jax.local_devices()``), its own outbound queue, its own
  DeviceSocket and messenger. The peer's device is visible in
  ``jax.devices()`` through ``jax.distributed`` but not addressable.
- **The data plane is lockstep SPMD.** Both processes jit the SAME
  exchange step (``shard_map``/``ppermute`` over ``Mesh([dev_c, dev_s])``)
  and dispatch it the SAME number of times in the SAME order — the
  multi-controller contract. Each dispatch contributes only the local
  shard (``make_array_from_single_device_arrays`` with one row); XLA's
  collective moves both rows across ICI (gloo on the CPU test fabric).
- **The control plane rides the host socket.** Step *scheduling* — how
  many exchange steps both sides agree to dispatch — flows as tiny JSON
  messages on a full-duplex streaming-RPC channel (rpc/stream.py) opened
  by the same handshake RPC that proposes the link: the reference's
  rdmacm-over-TCP split (control on TCP, data on the device fabric),
  socket.cpp:1692-1704. Each side announces ``want`` = the step count its
  backlog needs; both sides run ``target = max(all wants)`` — a monotone
  join that needs no consensus round.
- **Credit: the collective IS the window.** The single-controller wire-ack
  mode gates dispatch on acks carried in received slot headers — the only
  signal an *independently dispatching* sender has. Under lockstep SPMD
  the same gate can deadlock: both sides can stall waiting for fresher
  acks that only future (never-dispatched) rows would carry. Here each
  side instead gates on its OWN undrained completions
  (``seq - delivered < window``): a receiver that stops draining stops
  dispatching, which stalls the peer's collectives at exactly ``window``
  steps of pipeline — backpressure propagates through the data plane
  itself, no ack round trip. The cumulative-delivered count still rides
  slot words 3+5 (the piggybacked imm-data ack,
  rdma_endpoint.h:176-195) as the cross-host drain telemetry: tests
  assert it advances, /status surfaces it, and a peer whose acks freeze
  while completions stall is failed by the wedge timer.
- **Shutdown is a two-message dance.** Either side freezes its wants and
  sends ``close_req(target)``; the peer freezes, computes
  ``final = max(targets)`` and answers ``close_ack(final)``. Stream
  ordering makes ``final`` identical on both sides (every want precedes
  its sender's close_req), so both dispatch exactly ``final`` steps and
  tear down — no half-joined collective.
"""

from __future__ import annotations

import json
import logging
import threading
from typing import Callable, Dict, List, Optional

import numpy as np

from incubator_brpc_tpu.bvar import Adder, RecorderFeed
from incubator_brpc_tpu.transport.device_link import (
    HANDSHAKE_SERVICE,
    HANDSHAKE_METHOD,
    DeviceLink,
    DeviceSocket,
    link_slots,
    link_steps,
)
from incubator_brpc_tpu.utils.status import ErrorCode

logger = logging.getLogger(__name__)

mc_ctrl_msgs = Adder(name="mc_link_control_msgs")
# completion waits that made no progress (peer slow / not yet dispatching):
# each tick is one bounded 1 s retry before the wedge timer would fire
mc_stall_retries = Adder(name="mc_link_stall_retries")
mc_wedge_failures = Adder(name="mc_link_wedge_failures")


class MultiControllerLink(DeviceLink):
    """One side of a two-process device link (see module docstring).

    ``own_side``: 0 = client (the handshake proposer), 1 = server.
    ``control_send``: ships one small dict to the peer's ``on_control``
    (the streaming-RPC control plane). ``devices`` are the two GLOBAL
    devices in link order [client, server]; exactly ``devices[own_side]``
    must be addressable from this process.

    No lane yet (``carries_arrays``): the two halves of a lane program
    would have to be dispatched in lockstep by two processes, which the
    step budget below does not count. ``Stream.write`` of a device array
    over this link is refused (``EINVAL``); ROADMAP.md D10.
    """

    carries_arrays = False

    def __init__(
        self,
        own_side: int,
        devices: List,
        slot_words: int = 16384,
        window: int = 8,
        control_send: Optional[Callable[[dict], None]] = None,
        wedge_timeout: float = 120.0,
    ):
        self.own_side = own_side
        self._control_send_fn = control_send
        self._target = 0  # steps both sides agreed to dispatch
        self._final_target: Optional[int] = None  # set by the close dance
        self._frozen = False  # close dance started: wants stop growing
        self._finished = False
        self._ctrl_close: Optional[Callable[[], None]] = None
        self.wedge_timeout = wedge_timeout
        super().__init__(
            devices,
            slot_words=slot_words,
            window=window,
            host_loopback=False,
            ack_mode="wire",
        )
        if self._step is None or self._mesh is None:
            raise ValueError(
                "multi-controller link needs two distinct global devices"
            )

    # -- control plane -------------------------------------------------------

    def _send_ctrl(self, msg: dict) -> None:
        fn = self._control_send_fn
        if fn is None:
            return
        try:
            fn(msg)
            mc_ctrl_msgs << 1
        except Exception:
            logger.exception("mc link control send failed")
            self.fail("control plane send failed")

    def on_control(self, msg: dict) -> None:
        """Peer control message (runs on the control stream's consumer
        fiber — ordered, one at a time)."""
        op = msg.get("op")
        if op == "want":
            with self._lock:
                if not self._frozen and not self._closed:
                    self._target = max(self._target, int(msg["n"]))
            self._kick()
        elif op == "close_req":
            with self._lock:
                self._frozen = True
                self._send_blocked = True  # refuse post-freeze queues
                # our own backlog queued before this freeze still needs
                # steps — fold it into the final count (a send() that
                # returned 0 must reach the wire; the peer learns the
                # raised final from the close_ack)
                need = (
                    self._out_nbytes[self.own_side] + self._slot_bytes - 1
                ) // self._slot_bytes
                if self._close_pending[self.own_side]:
                    need = max(need, 1)
                final = max(
                    self._target, int(msg["target"]), self._seq + need
                )
                self._target = final
                self._final_target = final
            self._send_ctrl({"op": "close_ack", "target": final})
            self._kick()
        elif op == "close_ack":
            with self._lock:
                final = int(msg["target"])
                self._target = max(self._target, final)
                self._final_target = final
            self._kick()
        else:
            logger.warning("mc link: unknown control op %r", op)

    def _propagate_want(self) -> None:
        """After queuing bytes: if the backlog needs steps beyond the
        current target, raise it locally and announce to the peer. The
        target only ever grows (monotone max both sides converge on)."""
        with self._lock:
            if self._closed or self._frozen:
                return
            need = (
                self._out_nbytes[self.own_side] + self._slot_bytes - 1
            ) // self._slot_bytes
            if self._close_pending[self.own_side]:
                need = max(need, 1)
            want = self._seq + need
            if want <= self._target:
                return
            self._target = want
        self._send_ctrl({"op": "want", "n": want})
        self._kick()

    # -- send / close --------------------------------------------------------

    def send(self, side: int, data, timeout: Optional[float] = 10.0) -> int:
        assert side == self.own_side, "mc link only sends from its own side"
        rc = super().send(side, data, timeout=timeout)
        if rc == 0:
            self._propagate_want()
        return rc

    def close(self, side: int) -> None:
        with self._lock:
            if self._closed or self._frozen:
                return
            self._close_pending[self.own_side] = True
            self._frozen = True
            self._send_blocked = True  # refuse post-freeze queues
            need = (
                self._out_nbytes[self.own_side] + self._slot_bytes - 1
            ) // self._slot_bytes
            self._target = max(self._target, self._seq + max(need, 1))
            t = self._target
        self._send_ctrl({"op": "close_req", "target": t})
        self._kick()

    # -- the lockstep drive loop --------------------------------------------

    def _warm_step(self) -> None:
        """Nothing to warm: an exchange here is a collective, dispatched
        only once both hosts have agreed to it, and every agreed step has
        the one shape (one slot a side), which the first of them compiles."""

    def _make_local_slots(self, train: np.ndarray):
        import jax

        shard = jax.device_put(train[None], self.devices[self.own_side])
        return jax.make_array_from_single_device_arrays(
            (2,) + train.shape, self._sharding, [shard]
        )

    def _drive(self) -> None:
        import time as _time

        stall_since: Optional[float] = None
        while True:
            with self._lock:
                if self._closed:
                    self._driving = False
                    return
                if (
                    self._final_target is not None
                    and self._seq >= self._final_target
                    and self._inflight == 0
                ):
                    self._driving = False
                    finish = True
                else:
                    finish = False
                    if self._seq >= self._target:
                        # nothing agreed to dispatch; on_control/send kick
                        # the drive again when the target grows
                        self._driving = False
                        return
                    if self._inflight >= self.window:
                        # own-delivery credit (see module docstring): wait
                        # for a completion; delivery releases the credit
                        need = self._cq.load() + 1
                    else:
                        need = None
                        # one slot a step, whatever the backlog: both
                        # hosts must dispatch the same shape, and the
                        # agreed budget (target) counts steps
                        row = np.empty((1, self._width), dtype=np.uint32)
                        self._fill_train_locked(self.own_side, 1, row)
                        # the step's timeline feeds the per-link
                        # recorders exactly like the base _drive's; this
                        # link never cuts a train (_train_len_locked), so it
                        # never records what one is cut from
                        seq, step = self._take_seq_locked(1, RecorderFeed.MISSING)
            if finish:
                self._finish_close()
                return
            if need is not None:
                before = self._cq.load()
                self._cq.wait_for(need, timeout=1.0)
                if self._cq.load() == before:
                    # no completion progress: the peer may have stopped
                    # dispatching (died mid-burst). Gloo/XLA eventually
                    # error the half-joined collective; this timer bounds
                    # the wait even if the backend blocks silently.
                    mc_stall_retries << 1
                    now = _time.monotonic()
                    if stall_since is None:
                        stall_since = now
                    elif now - stall_since > self.wedge_timeout:
                        mc_wedge_failures << 1
                        self.fail(
                            "device plane wedged (peer not dispatching)"
                        )
                        with self._lock:
                            self._driving = False
                        return
                else:
                    stall_since = None
                continue
            stall_since = None
            try:
                out = self._step(self._make_local_slots(row))
            except Exception:
                logger.exception("mc link step dispatch failed")
                self._dispatch_failed(1)
                return
            step.launched()
            link_steps << 1
            link_slots << 1
            self._cq.watch(
                out,
                on_complete=lambda arrays, error, _seq=seq, _step=step: (
                    self._on_step_done(_seq, arrays, error, 1, _step)
                ),
                stamps=step.watcher,
            )

    def _finish_close(self) -> None:
        """Both sides dispatched exactly ``final_target`` steps and every
        delivery drained: the link is done. Quiet teardown — no fail()
        cascade into the peer (it finishes its own count)."""
        with self._lock:
            if self._finished or self._closed:
                return
            self._finished = True
            self._closed = True
        self._retire_metrics()  # clean close never reaches fail()
        sock = self.socks[self.own_side]
        if sock is not None:
            sock.set_failed(ErrorCode.ECLOSE, "device link closed")
        self._wbutex.add(1)
        self._wbutex.wake_all()
        self._close_ctrl()

    def fail(self, reason: str) -> None:
        super().fail(reason)
        # a dead link must not leave its control stream (and this link,
        # captured by the stream handler) attached to the shared TCP
        # connection forever
        self._close_ctrl()

    def _close_ctrl(self) -> None:
        fn, self._ctrl_close = self._ctrl_close, None
        if fn is not None:
            try:
                fn()
            except Exception:
                logger.exception("mc link control stream close raised")

    @property
    def peer_ack(self) -> int:
        """Cumulative frames the peer reported delivered (slot words 3+5) —
        the cross-host drain telemetry."""
        with self._lock:
            return self._peer_ack


# -- control stream plumbing ---------------------------------------------------


class _ControlHandler:
    """StreamHandler for the link's control plane. Messages are one JSON
    dict per stream message; they run on the stream's ordered consumer
    fiber, which is exactly the delivery order the close dance needs."""

    def __init__(self) -> None:
        self.link: Optional[MultiControllerLink] = None

    def on_received_messages(self, stream, messages: List[bytes]) -> None:
        link = self.link
        if link is None:
            return
        for m in messages:
            try:
                msg = json.loads(m.decode())
            except ValueError:
                logger.warning("mc link: undecodable control message")
                continue
            link.on_control(msg)

    def on_closed(self, stream) -> None:
        link = self.link
        if link is None:
            return
        # a clean shutdown closes the stream after the final step; only an
        # unexpected close (peer died) fails the link
        if link._final_target is None and not link._closed:
            link.fail("control stream closed by peer")

    def on_failed(self, stream, error_code: int, reason: str) -> None:
        link = self.link
        if link is not None and not link._closed:
            link.fail(f"control stream failed: {reason}")


def _stream_sender(stream) -> Callable[[dict], None]:
    def send(msg: dict) -> None:
        rc = stream.write(json.dumps(msg).encode(), timeout=10.0)
        if rc != 0:
            raise ConnectionError(f"control stream write failed: {rc}")

    return send


def _device_by_global_id(global_id: int):
    import jax

    for d in jax.devices():
        if d.id == global_id:
            return d
    raise ValueError(
        f"device id {global_id} not in this process's global view "
        f"(is jax.distributed initialized on both hosts?)"
    )


# -- establishment -------------------------------------------------------------


def accept_mc_handshake(server, cntl, req: dict) -> bytes:
    """Server half, called from the ``_tpu_transport.handshake`` handler
    when the proposal carries ``controller='multi'``. Accepts the control
    stream riding the same RPC, builds this process's link half over its
    own local device, and answers with the global device id so the client
    constructs the IDENTICAL mesh."""
    import jax

    from incubator_brpc_tpu.rpc.stream import StreamOptions, stream_accept

    handler = _ControlHandler()
    ctrl = stream_accept(cntl, StreamOptions(handler=handler))
    if ctrl is None:
        cntl.set_failed(
            ErrorCode.EREQUEST,
            "multi-controller handshake needs a control stream",
        )
        return b""
    try:
        client_dev = _device_by_global_id(int(req["client_device"]))
        slot_words = int(req.get("slot_words", 16384))
        window = int(req.get("window", 8))
    except (KeyError, ValueError, TypeError) as e:
        cntl.set_failed(ErrorCode.EREQUEST, f"bad mc handshake: {e}")
        return b""
    local = jax.local_devices()
    idx = server.options.device_index or 0
    server_dev = local[idx % len(local)]
    if server_dev.id == client_dev.id:
        cntl.set_failed(
            ErrorCode.EREQUEST,
            "client and server proposed the same device — a multi-"
            "controller link needs one device per process",
        )
        return b""
    link = MultiControllerLink(
        own_side=1,
        devices=[client_dev, server_dev],
        slot_words=slot_words,
        window=window,
        control_send=_stream_sender(ctrl),
    )
    link._ctrl_close = ctrl.close
    handler.link = link
    ds = DeviceSocket(
        link,
        side=1,
        messenger=server._messenger,
        context={"server": server},
    )
    # fingerprint consumption is symmetric: the client's advertised
    # device methods land on the server-side socket too, so EITHER end
    # can validate a (service, method) session proposal or a collective
    # lowering against what its peer actually registered
    ds.device_methods = dict(req.get("device_methods") or {})
    server._device_socks.append(ds)

    def _forget(sock, _server=server):
        try:
            _server._device_socks.remove(sock)
        except ValueError:
            pass
        sock.recycle()

    # fabriclint: allow(lifecycle-callback) self-pruning hook: drops the dead DeviceSocket from server._device_socks and recycles it — the server fails every device sock at stop, firing it
    ds.on_failed.append(_forget)
    return json.dumps(
        {
            "device": server_dev.id,
            "slot_words": slot_words,
            "window": window,
            "device_methods": {
                full: dm.fingerprint()
                for full, dm in getattr(server, "_device_methods", {}).items()
            },
        }
    ).encode()


def establish_mc_link(
    channel,
    device_index: int = 0,
    slot_words: int = 16384,
    window: int = 8,
    timeout_ms: float = 60000,
) -> DeviceSocket:
    """Client half: open the control stream, propose over the host socket
    (``device_index`` indexes this process's LOCAL devices), build side 0
    over the agreed global device pair. The returned DeviceSocket rides
    RPC frames over the lockstep SPMD exchange."""
    import jax

    from incubator_brpc_tpu.rpc import channel as channel_mod
    from incubator_brpc_tpu.rpc.controller import Controller
    from incubator_brpc_tpu.rpc.stream import StreamOptions, stream_create

    local = jax.local_devices()
    client_dev = local[device_index % len(local)]
    handler = _ControlHandler()
    ctrl = stream_create(StreamOptions(handler=handler))
    from incubator_brpc_tpu.rpc.device_method import registry_fingerprints

    payload = json.dumps(
        {
            "controller": "multi",
            "cookie": "",
            "client_device": client_dev.id,
            "slot_words": slot_words,
            "window": window,
            # symmetric advertisement (see accept_mc_handshake): the
            # collective method plane validates proposals against these
            "device_methods": registry_fingerprints(),
        }
    ).encode()
    cntl = Controller(timeout_ms=timeout_ms)
    cntl._force_host = True
    cntl = channel.call_method(
        HANDSHAKE_SERVICE,
        HANDSHAKE_METHOD,
        payload,
        cntl=cntl,
        request_stream=ctrl,
    )
    if cntl.failed():
        ctrl.close()
        raise ConnectionError(
            f"multi-controller handshake failed: {cntl.error_text}"
        )
    try:
        resp = json.loads(cntl.response_payload.decode())
        server_dev = _device_by_global_id(int(resp["device"]))
        link = MultiControllerLink(
            own_side=0,
            devices=[client_dev, server_dev],
            slot_words=int(resp.get("slot_words", slot_words)),
            window=int(resp.get("window", window)),
            control_send=_stream_sender(ctrl),
        )
    except Exception:
        # the server already built its half: closing the control stream
        # is what tells it to fail that half instead of wedging until
        # its wedge timer fires
        ctrl.close()
        raise
    link._ctrl_close = ctrl.close
    handler.link = link
    ds = DeviceSocket(link, side=0, messenger=channel_mod._client_messenger)
    ds.device_methods = resp.get("device_methods", {})
    return ds
