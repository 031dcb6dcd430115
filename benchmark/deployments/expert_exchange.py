"""The four-chip expert step: a source rank on ``TPU_0`` and three expert
ranks on ``TPU_1``-``TPU_3``, one single-controller process (as
``link_echo_hbm.py`` and ``partition_echo.py``). Each rank is
``Server(device_index=i)`` with a service ``experts`` of one method, ``ffn`` =
``DeviceEndpoint(device=TPU_i).server_handler(method_id=1)`` over
``ExpertShardService(first_expert=8 * (i - 1))``: ``expert_shard.py``'s
server three times, a chip each. The source holds three
``Channel(transport="tpu")`` and an ``ExpertExchange``
(``incubator_brpc_tpu/models/expert_exchange.py``): a layer call gathers each
rank's token rows where the micro-batch lies, sends three unary tensor calls
in flight together (the operand a ``jax.Array`` on ``TPU_0``, over the
link's lane; the rank's handler reads a ``jax.Array`` on its own chip, the
endpoint runs the step on it, the answer goes back as
``cntl.response_attachment``), and combines the partial sums on ``TPU_0``.

One call of the harness is one micro-batch's one layer on the calling
thread's own caller (micro-batch ``caller % pool``, layers 0 to the last and
round again, callers starting a quarter of the way apart). The harness stops
the clock when ``call_method`` returns, which is when the combined array is
ready on ``TPU_0``; ``response_payload``, first read after that, judges the
answer on ``TPU_0`` against the reference's for that (micro-batch, layer),
two numbers read back; each caller's first calls and, after the window, its
last ones are also compared on the host. The harness's seeded payload is not
sent: ``--seed`` enters through the micro-batches' content.

Set-up makes the pool on ``TPU_0`` from the seed, routes every (micro-batch,
layer) with the published router (the reference's), plans it, and computes
the reference's combined answers there, an expert at a time.

On a platform that is not a TPU the deployment takes the configuration's
``rehearsal`` sizes and says so on a line of its own. A program without the
tensor operand (``DeviceEndpoint.call_tensor``, ``models/expert_exchange``)
cannot run this: ``Deployment`` raises before its first call.
"""

from __future__ import annotations

import threading
from collections import deque

import numpy as np

from benchmark import manifest

_shard = manifest.load_module("deployments", "expert_shard.py")
_kv = manifest.load_module("deployments", "kv_block_stream.py")

SERVICE, METHOD, FFN_ID = "experts", "ffn", 1

# flip_bit: one bit of a rank's answer flips on its chip; stale: a rank
# answers a call with its previous answer; drop_tokens, wrong_layer,
# low_precision: expert_shard.py's, in the ranks' step; swap: two ranks'
# answers change places before the combine; host_bytes: the same calls with
# bytes attachments (the source reads each operand back, the bytes ride the
# links' trains, each endpoint puts them on its chip and reads the answer
# back, the source puts the answers on its chip again), which breaks
# guarantee (3) and nothing else: the A/B by hand. Its sub-calls go one at a
# time: a request off a link's byte stream is handled on the link's
# deliverer, a completion watcher, and handlers that wait there for their
# device completions, several at once, leave no watcher to bring them
# (PERF.md section 7)
CONTROLS = (
    "flip_bit", "stale", "drop_tokens", "wrong_layer", "low_precision",
    "swap", "host_bytes",
)


def _control_service(base, control):
    import jax.numpy as jnp

    class FlipBit(base):
        """A served answer's first bf16 has its top exponent bit flipped."""

        def dispatch_tensor(self, state, row, operand, cid_lo, mid):
            state, answer, frame = super().dispatch_tensor(
                state, row, operand, cid_lo, mid)
            first = (0,) * answer.ndim
            flipped = answer[first] ^ (jnp.uint32(0x4000) * (frame[7] == 0))
            return state, answer.at[first].set(flipped), frame

    if control == "flip_bit":
        return FlipBit
    return _shard._control_service(base, control)


class _HostBytes:
    """Control: a channel that sends a call's tensor as host bytes, and
    makes its calls one after another, each to its end."""

    _turn = threading.Lock()

    def __init__(self, channel):
        self._channel = channel

    def call_method(self, service, method, request, cntl, done, attachment):
        with self._turn:
            cntl = self._channel.call_method(
                service, method, request, cntl=cntl,
                attachment=np.asarray(attachment).tobytes())
        done(cntl)
        return cntl


def _control_exchange(base, control):
    """The source's client with a control's change: ``swap``, ``host_bytes``."""

    class Exchange(base):
        def __init__(self, channels, *args, **kwargs):
            if control == "host_bytes":
                channels = [_HostBytes(channel) for channel in channels]
            super().__init__(channels, *args, **kwargs)

        def combined(self, plan, answers):
            if control == "swap":
                answers = (answers[1], answers[0]) + tuple(answers[2:])
            return super().combined(plan, answers)

    return Exchange


def _stale(handler):
    """Control: a call is answered with the rank's previous answer."""
    last = [None]

    def stale(cntl, request):
        out = handler(cntl, request)
        fresh = cntl.response_attachment
        if not cntl.failed():
            cntl.response_attachment = fresh if last[0] is None else last[0]
            last[0] = fresh
        return out

    return stale


class _Caller:
    def __init__(self, index: int, batch: int, layer: int, keep: int):
        self.index, self.batch, self.layer = index, batch, layer
        self.calls = 0
        self.recent = deque(maxlen=keep)  # (batch, layer, y)


class _Answer:
    """What the generator reads of one call. The verdict is reached when
    ``response_payload`` is first read: the clock has stopped."""

    response_attachment = b""

    def __init__(self, deployment, caller, payload, batch, layer, answer, host):
        self._deployment, self._caller, self._payload = deployment, caller, payload
        self._batch, self._layer, self._answer = batch, layer, answer
        self._host, self._verdict = host, None

    def failed(self) -> bool:
        return self._answer.failed()

    @property
    def error_text(self) -> str:
        return self._answer.error_text

    @property
    def response_payload(self) -> bytes:
        if self._verdict is None:
            passed = not self._answer.failed() and self._deployment.judge(
                self._caller, self._batch, self._layer, self._answer.y, self._host)
            self._verdict = self._payload if passed else b""
        return self._verdict


class _Client:
    """``call_method`` of the harness is one layer call on the calling
    thread's own caller."""

    def __init__(self, deployment, callers: list):
        self._deployment = deployment
        self._free, self._mine = deque(callers), {}
        self._lock = threading.Lock()

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        me = threading.get_ident()
        with self._lock:
            if me not in self._mine:
                self._mine[me] = self._free.popleft()
        timeout_ms = cntl.timeout_ms if cntl is not None else 60000
        return self._deployment.call(self._mine[me], request, timeout_ms)


class Deployment:
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models import expert_shard
        from incubator_brpc_tpu.rpc import Server, ServerOptions
        from incubator_brpc_tpu.transport import device, device_link
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        try:
            from incubator_brpc_tpu.models import expert_exchange
        except ImportError:
            expert_exchange = None
        if expert_exchange is None or not hasattr(DeviceEndpoint, "call_tensor"):
            raise RuntimeError(
                "this program's DeviceEndpoint takes host words only: a unary "
                "call's jax.Array attachment cannot reach a device method "
                "(needs PR 54's incubator_brpc_tpu)")
        self._exchange_module, self._device, self._device_link = (
            expert_exchange, device, device_link)
        self._config, self.control = config, control
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        found = jax.devices()
        self._on_tpu = found[0].platform == "tpu"
        unit = config["unit"]
        sizes = {
            "moe": {k: config[k] for k in self._reference.Moe._fields},
            "layers": int(config["num_hidden_layers"]),
            "tokens": int(config["micro_batch_tokens"]),
            "capacity": int(config["operand"]["capacity_rows"]),
            "pool": int(config["micro_batch_pool"]),
            "expert_parallel": int(unit["expert_parallel"]),
            "tolerance": config["tolerance"],
        }
        sizes["moe"]["n_routed_experts"] = int(unit["router_outputs"])
        if not self._on_tpu:
            small = config["rehearsal"]
            sizes["moe"].update(small["moe"])
            sizes.update({k: v for k, v in small.items() if k != "moe"})
            print(f"REHEARSAL unit: {small} on {found[0].platform}, not the "
                  f"configuration's", flush=True)
        self._sizes = sizes
        moe = sizes["moe"]
        self.moe = self._reference.Moe(**moe)
        self.ranks = list(unit["ranks"])
        self.held = moe["n_routed_experts"] // sizes["expert_parallel"]
        if len(found) < 1 + len(self.ranks):
            raise RuntimeError(f"{len(self.ranks)} ranks and a source need "
                               f"{1 + len(self.ranks)} devices")
        self._seed = _kv._run_seed()
        self._weight_seed = int(config["weight_seed"])
        self.source = found[0]
        self.endpoints, self.servers, self._services = [], [], []
        for i, rank in enumerate(self.ranks, start=1):
            service = _control_service(expert_shard.ExpertShardService, control)(
                moe["hidden_size"], moe["moe_intermediate_size"], self.held,
                sizes["layers"], seed=self._weight_seed,
                first_expert=rank * self.held)
            endpoint = DeviceEndpoint(
                service=service, device=found[i], **config["endpoint"])
            handler = endpoint.server_handler(method_id=FFN_ID)
            if control == "stale":
                handler = _stale(handler)
            if spans is not None:
                handler = spans.wrap(handler)
            server = Server(ServerOptions(device_index=i))
            server.add_service(SERVICE, {METHOD: handler})
            if not server.start(0):
                raise RuntimeError(f"rank {rank} did not start")
            self._services.append(service)
            self.endpoints.append(endpoint)
            self.servers.append(server)
        self.port = self.servers[0].port
        self.devices = list(found[: 1 + len(self.ranks)])
        self._lock = threading.Lock()
        self._channels, self._client, self.exchange = [], None, None
        self._callers = []
        # of the calls judged: how many, what they sent, how many lay
        # outside, the farthest, and the same on the host
        self.calls = self.tokens = self.pairs = self.outside = 0
        self.worst = [0.0, 0.0]
        self.host_checked = self.host_outside = 0
        self.redrawn = 0

    # -- set-up ----------------------------------------------------------------

    def _open_channels(self) -> None:
        from incubator_brpc_tpu.rpc import Channel, ChannelOptions, Controller

        for server in self.servers:
            channel = Channel()
            if not channel.init(f"127.0.0.1:{server.port}",
                                options=ChannelOptions(**self._config["channel_options"])):
                raise RuntimeError("cannot reach a rank")
            # the handshake (the link's trains compile in it); what the rank
            # says of an empty request does not matter
            channel.call_method(SERVICE, METHOD, b"", cntl=Controller(timeout_ms=60000))
            if channel._device_sock is None:
                raise RuntimeError("the channel has no device link")
            self._channels.append(channel)

    @property
    def links(self) -> list:
        return [channel._device_sock.link for channel in self._channels]

    def warm(self, traffic: dict) -> None:
        """The three handshakes, the lanes' program for the operand's shape,
        each rank's step for it, ``gather`` and ``combine``, the judge; then
        the pool, its plans and the reference's answers."""
        import jax
        import jax.numpy as jnp

        sizes, moe, ref = self._sizes, self.moe, self._reference
        self._traffic = traffic
        self._open_channels()
        exchange, control = self._exchange_module, self.control
        self.exchange = _control_exchange(exchange.ExpertExchange, control)(
            self._channels, [rank * self.held for rank in self.ranks], self.held,
            moe.hidden_size, sizes["tokens"], sizes["capacity"], self.source,
            service=SERVICE, method=METHOD)
        shape = self.exchange.operand_shape
        want = self._config["operand"]
        if self._on_tpu and list(shape) != [want["capacity_rows"], want["row_words"]]:
            raise RuntimeError(f"the operand is {shape}, not the configuration's")
        for link in self.links:
            if not link.has_lane:
                raise RuntimeError(f"a link has no lane: {link.geometry}")
            link.warm_lane(0, shape, np.uint32)
        for endpoint in self.endpoints:
            endpoint.warm_tensor(shape)
            if control == "host_bytes":  # the bytes come as the rows end to end
                endpoint.warm_tensor((shape[0] * shape[1],))
        self.exchange.warm()
        # the pool: micro-batches on the source's chip, each layer's plan,
        # the reference's combined answers
        self.batches, self.plans, self.wants = [], [], []
        for number in range(sizes["pool"]):
            for attempt in range(64):
                x = jax.device_put(ref.micro_batch(
                    self._seed, number + attempt * sizes["pool"],
                    sizes["tokens"], moe.hidden_size), self.source)
                try:
                    routed = [ref.gate_weights(moe, self._weight_seed, layer, x)
                              for layer in range(sizes["layers"])]
                    plans = [self.exchange.plan(w) for w in routed]
                    break
                except exchange.CapacityExceeded:
                    self.redrawn += 1
            else:
                raise RuntimeError("no micro-batch within the capacity in 64 draws")
            self.batches.append(x.astype(jnp.bfloat16))
            self.plans.append(plans)
            self.wants.append([
                ref.combined(moe, self._weight_seed, layer, x, routed[layer],
                             self.ranks, sizes["expert_parallel"])
                for layer in range(sizes["layers"])])
        jax.block_until_ready(self.wants)
        np.asarray(ref.outside(self.batches[0], self.wants[0][0]))  # the judge
        callers = int(traffic["callers"])
        self._callers = [
            _Caller(c, c % sizes["pool"], (c * sizes["layers"]) // callers,
                    int(self._config["host_checked_tail_calls"]))
            for c in range(callers)]
        self._before = {
            "operands": self._device.m_device_operands.get_value(),
            "fallbacks": self._device.m_device_operand_fallbacks.get_value(),
            "link_bytes": self._device_link.link_bytes.get_value(),
            "served": _shard._served(0, 0),
        }
        self._sub_calls = 0

    def channel(self):
        if self._client is None:
            self._client = _Client(self, self._callers)
        return self._client

    # -- one call --------------------------------------------------------------

    def call(self, caller, payload: bytes, timeout_ms: int):
        batch, layer = caller.batch, caller.layer
        caller.layer = (layer + 1) % self._sizes["layers"]
        host = caller.calls < int(self._traffic["warm_calls_per_caller"])
        caller.calls += 1
        answer = self.exchange.call_layer(
            self.batches[batch], self.plans[batch][layer], layer,
            timeout_ms=timeout_ms)
        with self._lock:
            self._sub_calls += len(self.ranks)
        return _Answer(self, caller, payload, batch, layer, answer, host)

    # -- after the clock -------------------------------------------------------

    def judge(self, caller, batch: int, layer: int, y, host: bool) -> bool:
        """Guarantee (1) for one call, its clock stopped: the combined answer
        on the source's chip against the reference's, two numbers read back;
        a caller's first calls also on the host."""
        tolerance = self._sizes["tolerance"]
        far = [float(v) for v in np.asarray(
            self._reference.outside(y, self.wants[batch][layer]))]
        passed = (far[0] <= tolerance["rel_l2"]
                  and far[1] <= tolerance["element_over_rms"])
        plan = self.plans[batch][layer]
        with self._lock:
            self.calls += 1
            self.tokens += sum(plan.tokens)
            self.pairs += sum(plan.pairs)
            self.outside += not passed
            self.worst = [max(a, b) for a, b in zip(self.worst, far)]
        if caller is not None:
            caller.recent.append((batch, layer, y))
        if host:
            passed &= self.on_the_host(batch, layer, y)
        return passed

    def on_the_host(self, batch: int, layer: int, y) -> bool:
        """The answer and the reference's read back and compared in numpy."""
        tolerance = self._sizes["tolerance"]
        far = self._reference.share.outside(
            np.asarray(y).astype(np.float32), np.asarray(self.wants[batch][layer]))
        passed = (far[0] <= tolerance["rel_l2"]
                  and far[1] <= tolerance["element_over_rms"])
        with self._lock:
            self.host_checked += 1
            self.host_outside += not passed
        return passed

    def malformed(self) -> tuple:
        """Guarantee (5), with nothing in flight: operands that must fail
        (another shape, another dtype, ``T`` over the capacity, a layer out
        of range, a weight that is not finite, a token no weight names), then
        a good call that must still be answered as before.
        ``(sent, answered or failed another way, the good one outside)``."""
        import jax
        import jax.numpy as jnp

        from incubator_brpc_tpu.rpc import Controller
        from incubator_brpc_tpu.utils.status import ErrorCode

        exchange, head = self.exchange, self._exchange_module.HEAD
        plan, x = self.plans[0][0], self.batches[0]
        good = exchange._gather(x, plan.index, plan.gates)[0]
        tokens, hidden, held = plan.tokens[0], self.moe.hidden_size, self.held
        rows, wide = good.shape
        nan = jnp.uint32(0x7FC00000)
        bad = [
            (head.pack(0, tokens, hidden, held), good[:, :-1]),
            (head.pack(0, tokens, hidden, held), good.astype(jnp.int32)),
            (head.pack(0, rows + 1, hidden, held), good),
            (head.pack(self._sizes["layers"], tokens, hidden, held), good),
            (head.pack(0, tokens, hidden, held), good.at[0, wide - 1].set(nan)),
            (head.pack(0, tokens, hidden, held),
             good.at[0, wide - held :].set(jnp.uint32(0))),
        ]
        wrong = 0
        for frame, operand in bad:
            cntl = self._channels[0].call_method(
                SERVICE, METHOD, frame, attachment=jax.block_until_ready(operand),
                cntl=Controller(timeout_ms=60000))
            wrong += not (cntl.failed() and cntl.error_code == ErrorCode.EREQUEST)
        with self._lock:
            self._sub_calls += len(bad)
        after = exchange.call_layer(x, plan, 0)
        with self._lock:
            self._sub_calls += len(self.ranks)
        touched = after.failed() or not self.judge(None, 0, 0, after.y, True)
        return len(bad), wrong, int(touched)

    def holds(self) -> list:
        for caller in self._callers:
            for batch, layer, y in list(caller.recent):
                self.on_the_host(batch, layer, y)
        sent, answered, touched = self.malformed()
        tolerance = self._sizes["tolerance"]
        plans = [p for plans in self.plans for p in plans]
        sent_a_rank = np.asarray([t for p in plans for t in p.tokens])
        print(f"pool: {len(self.batches)} micro-batches of {self._sizes['tokens']} "
              f"tokens, {len(plans)} plans; tokens a rank mean "
              f"{sent_a_rank.mean():.1f} least {sent_a_rank.min()} most "
              f"{sent_a_rank.max()} of a capacity of {self._sizes['capacity']}, "
              f"pairs a token {sum(sum(p.pairs) for p in plans) / sent_a_rank.sum():.4f}, "
              f"{self.redrawn} micro-batches drawn again; farthest answer: rel_l2 "
              f"{self.worst[0]:.6f}, element_over_rms {self.worst[1]:.6f}",
              flush=True)
        before = self._before
        served = _shard._served(self.tokens + before["served"][0],
                                self.pairs + before["served"][1])
        served = (served[0] - before["served"][0], served[1] - before["served"][1])
        operands = self._device.m_device_operands.get_value() - before["operands"]
        fallbacks = (self._device.m_device_operand_fallbacks.get_value()
                     - before["fallbacks"])
        on_stream = self._device_link.link_bytes.get_value() - before["link_bytes"]
        sub_calls = max(self._sub_calls, 1)
        a_call = on_stream / sub_calls
        limit = int(self._config["byte_stream_bytes_a_sub_call_limit"])
        links = self.links
        want = self._config["link"]
        pairs_of = [tuple(d.id for d in link.devices) for link in links]
        distinct = len({ids[1] for ids in pairs_of} - {self.source.id})
        geometries = sorted({link.geometry for link in links})
        low = self._services[0].weight_bytes
        high = int(1.25 * low)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices[1:]]
        held = [
            ("answers_outside_tolerance", self.outside,
             f"0 of {self.calls} judged; rel_l2 <= {tolerance['rel_l2']}, "
             f"element_over_rms <= {tolerance['element_over_rms']}",
             self.outside == 0),
            (f"answers_outside_on_the_host_of_{self.host_checked}_read_back",
             self.host_outside, 0, self.host_outside == 0),
            ("tokens_sent_and_not_served", max(0, self.tokens - served[0]), 0,
             self.tokens <= served[0]),
            ("token_expert_pairs_sent_and_not_served",
             max(0, self.pairs - served[1]), 0, self.pairs <= served[1]),
            ("calls_into_the_endpoints_without_a_device_operand",
             self._sub_calls - operands, f"0 of {self._sub_calls} sub-calls",
             operands == self._sub_calls),
            ("device_operand_fallbacks", fallbacks, 0, fallbacks == 0),
            ("payload_bytes_on_the_byte_stream", round(a_call, 1),
             f"<= {limit} a sub-call", a_call <= limit),
        ]
        for rank, peak in zip(self.ranks, peaks):
            held.append((
                f"weights_read_where_they_lie_peak_bytes_rank_{rank}",
                peak if peak is not None else "not reported on this platform",
                f">= {low} and < {high}", peak is None or low <= peak < high))
        held += [
            (f"malformed_operands_of_{sent}_not_failed_EREQUEST", answered, 0,
             answered == 0),
            ("answers_outside_after_the_malformed", touched, 0, touched == 0),
            ("exchange_distinct_rank_devices", distinct, want["devices"],
             distinct == want["devices"] == len(links)),
            ("exchange_geometry", ",".join(geometries), want["geometry"],
             geometries == [want["geometry"]]),
        ]
        return held

    def close(self) -> None:
        for server in self.servers:
            server.stop()
        for server in self.servers:
            server.join(timeout=10)
