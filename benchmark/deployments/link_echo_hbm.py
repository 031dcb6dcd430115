"""``link_echo.py``'s echo with the attachment in the device's own memory
(upstream's ``rdma_performance`` as it runs with ``use_rdma=true``): a unary
call over ``Channel(transport="tpu")`` whose request attachment is a
``jax.Array`` on the client's chip. The handler reads a ``jax.Array`` on its
own chip and answers with it; the caller reads a ``jax.Array`` on its chip.
Both cross by the link's lane, one program each way, their frames the
programs' tags (``docs/DEVICE_PLANE.md``, "A unary call carries a tensor").
The link's checks and its single-controller set-up are ``link_echo.py``'s.

One call of the harness is one such call on the calling thread's own
``_Caller``: its tensor was made on the client's chip, by one program, when
the caller's previous call was judged. The harness stops a call's clock
when ``call_method`` returns and then reads ``response_payload``: that
first read judges the answer (against the call's content regenerated on the
client's chip, one scalar read back; a warm call's also on the host, against
``references/tensor_echo_identity.py``) and makes the caller's next tensor,
both outside every clock. The harness's seeded host payload is not sent:
``--seed`` enters through the tensors' content.

A program without the capability (``device_link.array_carrier``,
``DeviceSocket.write_device_message``) cannot run this: ``Deployment``
raises before its first call.

Off the TPU the harness cuts the tensor to 4 KiB.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from benchmark import manifest

_link = manifest.load_module("deployments", "link_echo.py")
_kv = manifest.load_module("deployments", "kv_block_stream.py")

SWAP_WAIT_S = 0.05  # how long the swap control keeps an answer for a partner

# flip_bit: one bit of the answer's first word flips on the server's chip;
# stale: a call is answered with the call before's tensor; swap: two calls
# in flight together (two callers') get each other's answer; host_bytes:
# the same calls with bytes attachments (the caller reads its tensor back,
# the bytes ride the link's trains, the caller puts the answer on its chip
# again), which breaks guarantee (2) and nothing else: the A/B the cell is for
CONTROLS = ("flip_bit", "stale", "swap", "host_bytes")


class _Caller:
    """One caller's count of calls, its next call's tensor, ready on the
    client's chip, and its last answers, kept for the host's comparison."""

    def __init__(self, index: int, keep: int):
        self.index = index
        self.calls = 0
        self.tensor = None
        self.tensor_of = None  # the call that tensor is
        self.warm_until = None  # the harness's rule, from the first call
        self.recent = deque(maxlen=keep)  # (call, answer)


class _Call:
    """What the generator reads of one call. The verdict is reached when
    ``response_payload`` is first read: the clock has stopped."""

    def __init__(self, deployment, caller, cntl, request, attachment, number,
                 answer, warm):
        self._deployment, self._caller, self._cntl = deployment, caller, cntl
        self._request, self._number = request, number
        self._answer, self._warm = answer, warm
        self._verdict = None
        self.response_attachment = attachment  # not sent, so as it came

    def failed(self) -> bool:
        return self._cntl.failed()

    @property
    def error_text(self) -> str:
        return self._cntl.error_text

    @property
    def response_payload(self) -> bytes:
        if self._verdict is None:
            passed = (
                not self._cntl.failed()
                and self._cntl.response_payload == self._request
                and self._deployment.judge(
                    self._caller, self._number, self._answer, self._warm)
            )
            self._verdict = self._request if passed else b""
            if not self._cntl.failed():
                self._deployment.make_tensor(self._caller)
        return self._verdict


class _Client:
    """``call_method`` of the harness is one call on the calling thread's
    own caller, over the one channel."""

    def __init__(self, deployment, channel, callers: list):
        self._deployment, self._channel = deployment, channel
        self._free, self._mine = deque(callers), {}
        self._lock = threading.Lock()

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        me = threading.get_ident()
        with self._lock:
            if me not in self._mine:
                self._mine[me] = self._free.popleft()
        return self._deployment.call(
            self._channel, self._mine[me], service, method, request,
            attachment, cntl)


class Deployment(_link.Deployment):
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.rpc import Server
        from incubator_brpc_tpu.transport import device_link

        if not (hasattr(device_link, "array_carrier")
                and hasattr(device_link.DeviceSocket, "write_device_message")):
            raise RuntimeError(
                "this program's unary calls carry host bytes only: "
                "call_method(attachment=<jax.Array>) cannot cross the lane "
                "(needs PR 44's incubator_brpc_tpu)")
        self._device_link = device_link
        self._config, self.control = config, control
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        self._seed = _kv._run_seed()
        self._on_tpu = jax.devices()[0].platform == "tpu"
        self._lock = threading.Lock()
        self._not_device_arrays = self._unequal_on_device = 0
        self._unequal_on_host = self._read_back = self._completed = 0
        self._kept = None  # the stale control's memory: the call before's
        self._partner = None  # the swap control's: [tensor, event, the partner's]
        self._lane_bytes_before = device_link.lane_bytes.get_value()
        self._link_bytes_before = device_link.link_bytes.get_value()
        self._callers, self._client = [], None

        handler = self._echo if spans is None else spans.wrap(self._echo)
        self.server = Server()
        self.server.add_service("EchoService", {"Echo": handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self._options = dict(config["channel_options"])
        self._want = config["link"]
        self._channel = None

    # -- the server's handler --------------------------------------------------

    def _echo(self, cntl, request):
        """``response_attachment = request_attachment``, counted where what
        it read was no ``jax.Array`` of the call's shape on its own chip."""
        import jax

        tensor = cntl.request_attachment
        ok = (
            isinstance(tensor, jax.Array)
            and tensor.devices() == {self.link.devices[1]}
            and (tensor.shape, tensor.dtype) == ((self.words,), np.uint32)
        )
        if not ok:
            with self._lock:
                self._not_device_arrays += 1
        elif self.control == "flip_bit":
            tensor = self.flip(tensor)
        elif self.control == "stale":
            with self._lock:
                tensor, self._kept = (
                    tensor if self._kept is None else self._kept), tensor
        elif self.control == "swap":
            tensor = self._swapped(tensor)
        cntl.response_attachment = tensor
        return request

    def _swapped(self, tensor):
        """The swap control: the answer of another call in flight now, which
        gets this one's; a call that finds no partner in ``SWAP_WAIT_S``
        keeps its own."""
        with self._lock:
            waiting, self._partner = self._partner, None
            if waiting is None:
                mine = self._partner = [tensor, threading.Event(), None]
        if waiting is not None:
            waiting[2] = tensor
            waiting[1].set()
            return waiting[0]
        if mine[1].wait(SWAP_WAIT_S):
            return mine[2]
        with self._lock:
            if self._partner is mine:
                self._partner = None
                return tensor  # nobody came
        mine[1].wait()  # a partner took it as the wait ran out
        return mine[2]

    # -- set-up ----------------------------------------------------------------

    def warm(self, traffic: dict) -> None:
        """The handshake (the link's trains compile in it), the lane's
        program for the tensor each way, and every program a call's maker
        or judge runs, each run once."""
        import jax
        import jax.numpy as jnp

        self._traffic = traffic
        self.nbytes = min(traffic["sizes"])
        self.words = self.nbytes // 4
        want = self._config["attachment"]
        if self._on_tpu and (self.words, "uint32") != (want["words"], want["dtype"]):
            raise RuntimeError(f"the traffic's tensor is not {want}")
        if not self._on_tpu:
            print(f"REHEARSAL tensor: uint32[{self.words}] on "
                  f"{jax.devices()[0].platform}, not the configuration's "
                  f"uint32[{want['words']}]", flush=True)
        channel = super().channel()
        warm = channel.call_method(
            traffic["service"], traffic["method"], b"ping", attachment=b"warm")
        if warm.failed():
            raise RuntimeError(f"the handshake's call failed: {warm.error_text}")
        client, server = self.link.devices
        found = jax.devices()
        if (client, server) != (found[0], found[1]):
            raise RuntimeError(f"the link joins {client} and {server}")
        self._client_device = client
        words, reference = self.words, self._reference
        self._make = jax.jit(lambda key: reference.device_words(key, words))
        self._unequal = jax.jit(lambda tensor, key: jnp.sum(
            tensor != reference.device_words(key, words), dtype=jnp.uint32))
        self.flip = jax.jit(lambda t: t.at[0].set(t[0] ^ jnp.uint32(1)))
        for side in (0, 1):
            self.link.warm_lane(side, (words,), np.uint32)
        key = jax.device_put(reference.tensor_key(self._seed, 0, 0), client)
        made = jax.block_until_ready(self._make(key))
        int(self._unequal(made, key))
        np.asarray(made)
        jax.block_until_ready(self.flip(jax.device_put(np.asarray(made), server)))
        self._callers = [
            _Caller(index, int(self._config["host_checked_tail_calls"]))
            for index in range(int(traffic["callers"]))
        ]
        with self._lock:  # the handshake's call carried bytes: not a call of the run
            self._not_device_arrays = 0

    def channel(self):
        """Every caller's first tensor made before the harness sends
        anything."""
        if self._client is None:
            for caller in self._callers:
                self.make_tensor(caller)
            self._client = _Client(self, super().channel(), self._callers)
        return self._client

    def _key(self, caller, call: int):
        import jax

        return jax.device_put(
            self._reference.tensor_key(self._seed, caller.index, call),
            self._client_device)

    def make_tensor(self, caller) -> None:
        """The caller's next call's tensor, made on the client's chip by one
        program and waited for: the caller's compute stands outside the
        call's clock."""
        import jax

        caller.tensor = jax.block_until_ready(
            self._make(self._key(caller, caller.calls)))
        caller.tensor_of = caller.calls

    # -- one call --------------------------------------------------------------

    def call(self, channel, caller, service, method, request, attachment, cntl):
        """One call on the caller's thread; the clock is the caller's."""
        import jax

        started = time.monotonic()
        if caller.warm_until is None:
            caller.warm_until = started + float(self._traffic["warm_seconds"])
        number = caller.calls
        if caller.tensor_of != number:
            self.make_tensor(caller)  # the call before failed and was never judged
        # warm by the generator's own rule: so many calls and so long
        warm = (number < int(self._traffic["warm_calls_per_caller"])
                or started < caller.warm_until)
        caller.calls += 1
        tensor = caller.tensor
        if self.control == "host_bytes":
            tensor = np.asarray(tensor).tobytes()
        done = channel.call_method(
            service, method, request, attachment=tensor, cntl=cntl)
        answer = done.response_attachment
        if not done.failed():
            on_chip = (isinstance(answer, jax.Array)
                       and answer.devices() == {self._client_device})
            if not on_chip and isinstance(answer, bytes):
                # what a caller of a bytes call does to hold a tensor again
                answer = jax.device_put(
                    np.frombuffer(answer, np.uint32), self._client_device)
            with self._lock:
                self._completed += 1
                self._not_device_arrays += not on_chip
        return _Call(self, caller, done, request, attachment, number, answer, warm)

    # -- after the clock -------------------------------------------------------

    def judge(self, caller, call: int, answer, warm: bool) -> bool:
        """Guarantee (1) for one call, its clock stopped: the answer on the
        client's chip against the content regenerated there, one scalar
        read back; a warm call's also on the host, against the reference."""
        import jax

        if not isinstance(answer, jax.Array) or answer.shape != (self.words,):
            with self._lock:
                self._unequal_on_device += 1
            return False
        unequal = int(self._unequal(answer, self._key(caller, call))) != 0
        with self._lock:
            self._unequal_on_device += unequal
        caller.recent.append((call, answer))
        if warm:
            unequal += self.on_the_host(caller, call, answer)
        return not unequal

    def on_the_host(self, caller, call: int, answer) -> int:
        """An answer read back and compared word for word with what the
        reference says the call's tensor holds: 1 where they differ."""
        want = self._reference.content(self._seed, caller.index, call, self.words)
        unequal = int(not np.array_equal(np.asarray(answer), want))
        with self._lock:
            self._read_back += 1
            self._unequal_on_host += unequal
        return unequal

    def holds(self) -> list:
        for caller in self._callers:
            for call, answer in list(caller.recent):
                self.on_the_host(caller, call, answer)
        calls = self._completed
        lane = self._device_link.lane_bytes.get_value() - self._lane_bytes_before
        link = self._device_link.link_bytes.get_value() - self._link_bytes_before
        short = 2 * self.nbytes * calls - lane
        a_call = link / calls if calls else float("inf")
        limit = int(self._config["byte_stream_bytes_a_call_limit"])
        return super().holds() + [
            ("tensors_not_equal_to_their_call", self._unequal_on_device, 0,
             self._unequal_on_device == 0),
            (f"tensors_not_equal_on_the_host_of_{self._read_back}_read_back",
             self._unequal_on_host, 0, self._unequal_on_host == 0),
            ("attachments_not_device_arrays", self._not_device_arrays, 0,
             self._not_device_arrays == 0),
            (f"lane_bytes_short_of_2_x_{self.nbytes}_x_{calls}_calls", short, 0,
             short == 0),
            ("payload_bytes_on_the_byte_stream", round(a_call, 1),
             f"<= {limit} a call", a_call <= limit),
        ]
