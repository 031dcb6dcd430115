"""One server that holds the chip and one rank's expert weights on it:
``Server()`` with a service ``experts`` of one method, ``ffn`` =
``DeviceEndpoint(...).server_handler(method_id=1)`` over
``ExpertShardService`` — ``device_echo.py`` with the service changed: host
RPC in, frame to HBM, the step (parse, verify, the experts' products on the
weights where they lie) on the device, response out.

The generator is ``in_process`` for the record table's reason: a request is
formed (a micro-batch routed, the tokens sent here packed) and an answer is
judged by a tolerance, neither of which the harness's own-process generator
does. ``channel()`` returns an adapter: ``call_method`` reads the
micro-batch off the generator's seeded payload, sends that micro-batch's
next layer as the real ``ffn`` call over a ``Channel()`` to
``127.0.0.1:port`` (the callers are threads of the server's process), and
returns what the generator reads: ``failed()``, and a ``response_payload``
that equals the reference's ``expected`` exactly when the answer lay within
the tolerance of the reference's, judged when it is first read, the call's
clock stopped.

A payload is a micro-batch: the first call that shows one builds it, in
set-up (every payload of a caller's pool is seen within its first untimed
calls): for each layer held the tokens are drawn from a hash of (payload,
layer), routed by the published router, and the rank's share is packed as
the request; the reference's float32 answer to each request is computed on
the chip, an expert at a time from the reference's own weight function,
for every micro-batch that waits to be built (callers that arrive
together are built together).

On a platform that is not a TPU the deployment takes the configuration's
``rehearsal`` sizes and says so on a line of its own.
"""

from __future__ import annotations

import struct
import threading

import numpy as np

from benchmark import manifest

SERVICE, METHOD = "experts", "ffn"
FFN_ID = 1  # models/expert_shard.py's method id


def _control_service(base, control):
    """The service with one guarantee broken under the timed path."""
    import jax
    import jax.numpy as jnp

    class FlipBit(base):
        """An answer's first bf16 has its top exponent bit flipped."""

        def dispatch_step(self, state, rows, cids, mids):
            _, frames = self.step(state, rows, cids, mids)
            flipped = frames[:, 8] ^ (jnp.uint32(0x4000) * (mids == FFN_ID))
            return None, frames.at[:, 8].set(flipped)

    class DropTokens(base):
        """A capacity: an expert serves its first 8 tokens of a row only."""

        def routed(self, weights):
            nth = jnp.cumsum(weights != 0, axis=1)
            return jnp.where(nth <= 8, weights, 0.0)

    class WrongLayer(base):
        """A row is served from the next layer's weights."""

        def serving_layer(self, layer):
            return (layer + 1) % self.layers

    class LowPrecision(base):
        """The weights are rounded to the three mantissa bits an 8-bit
        float (e4m3) keeps, where they lie, before any product."""

        def init_state(self, device):
            def rounded(w):
                bits = jax.lax.bitcast_convert_type(w, jnp.uint16)
                bits = (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0)
                return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)

            rounded = jax.jit(rounded, donate_argnums=0)
            return tuple(rounded(w) for w in super().init_state(device))

    return {
        "flip_bit": FlipBit, "drop_tokens": DropTokens,
        "wrong_layer": WrongLayer, "low_precision": LowPrecision,
    }.get(control, base)


def _stale(handler):
    """Control: a call is answered with the previous answer of its length:
    acknowledged, and another request's."""
    last = {}

    def stale(cntl, request):
        out = handler(cntl, request)
        previous = last.get(len(out), out)
        last[len(out)] = out
        return previous

    return stale


CONTROLS = ("flip_bit", "stale", "drop_tokens", "wrong_layer", "low_precision")


class _MicroBatch:
    """One payload's micro-batch: per layer held the request and the
    reference's answer to it; the layer its next call sends."""

    def __init__(self):
        self.ready = threading.Event()
        self.error = None
        self.requests, self.wants, self.pairs = [], [], []
        self.turn = 0


class _Answer:
    """What the generator reads of one call. The verdict is reached when
    ``response_payload`` is first read: the clock has stopped."""

    response_attachment = b""

    def __init__(self, client, payload, want, pairs, cntl):
        self._client, self._payload = client, payload
        self._want, self._pairs = want, pairs
        self._cntl, self._verdict = cntl, None

    def failed(self) -> bool:
        return self._cntl.failed()

    @property
    def error_text(self) -> str:
        return self._cntl.error_text

    @property
    def response_payload(self) -> bytes:
        if self._verdict is None:
            answer = self._cntl.response_payload
            passed = self._client.judge(answer, self._want, self._pairs)
            self._verdict = self._payload if passed else answer
        return self._verdict


class _Client:
    """A source rank's side of the unit: ``call_method`` of the harness is
    one micro-batch's dispatch to this rank for its next layer."""

    def __init__(self, channel, reference, sizes: dict, config: dict):
        self._channel, self._ref, self._sizes = channel, reference, sizes
        self.moe = reference.Moe(**sizes["moe"])
        self._seed = int(config["weight_seed"])
        self._ep, self._rank = int(sizes["expert_parallel"]), int(sizes["rank"])
        self._tolerance = sizes["tolerance"]
        self._batches, self._pending = {}, []
        self._lock, self._building = threading.Lock(), threading.Lock()
        self._routers = None
        # of the calls judged: how many, the tokens and (token, expert)
        # pairs they sent, how many lay outside, and the farthest
        self.calls = self.tokens = self.pairs = self.outside = 0
        self.worst = [0.0, 0.0]
        self.pool_tokens, self.pool_pairs, self.redrawn = [], [], 0

    # -- the harness's side ----------------------------------------------------

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        from incubator_brpc_tpu.rpc import Controller

        batch = self._batch(request)
        layer = batch.turn
        batch.turn = (layer + 1) % len(batch.requests)  # one caller a payload
        timeout_ms = cntl.timeout_ms if cntl is not None else 60000
        answer = self._channel.call_method(
            service, method, batch.requests[layer],
            cntl=Controller(timeout_ms=timeout_ms))
        return _Answer(
            self, request, batch.wants[layer], batch.pairs[layer], answer)

    def judge(self, answer: bytes, want: np.ndarray, pairs: int) -> bool:
        """Guarantee (1), once the call's clock has stopped."""
        ref, hidden = self._ref, want.shape[1]
        passed = len(answer) == 2 * want.size
        far = (float("inf"),) * 2
        if passed:
            far = ref.outside(ref.unpack_answer(answer, hidden), want)
            passed = (far[0] <= self._tolerance["rel_l2"]
                      and far[1] <= self._tolerance["element_over_rms"])
        with self._lock:
            self.calls += 1
            self.tokens += want.shape[0]
            self.pairs += pairs
            self.outside += not passed
            self.worst = [max(a, b) for a, b in zip(self.worst, far)]
        return passed

    # -- a micro-batch, built the first time its payload shows ------------------

    def _batch(self, payload: bytes) -> _MicroBatch:
        with self._lock:
            batch = self._batches.get(payload)
            if batch is None:
                batch = self._batches[payload] = _MicroBatch()
                self._pending.append((payload, batch))
        if not batch.ready.is_set():
            with self._building:  # whoever comes first builds all that wait
                with self._lock:
                    todo, self._pending = self._pending, []
                if todo:
                    self._build(todo)
            batch.ready.wait()
        if batch.error is not None:
            raise RuntimeError("a micro-batch could not be built") from batch.error
        return batch

    def _build(self, todo: list) -> None:
        try:
            for layer in range(self._sizes["layers"]):
                self._build_layer(layer, todo)
        except BaseException as e:  # noqa: BLE001 — every waiter is told
            for _payload, batch in todo:
                batch.error = e
            raise
        finally:
            for _payload, batch in todo:
                batch.ready.set()

    def _build_layer(self, layer: int, todo: list) -> None:
        """Layer ``layer`` of every micro-batch in ``todo``: the requests,
        then the reference's answers, an expert at a time."""
        import jax
        import jax.numpy as jnp

        ref, moe, sizes = self._ref, self.moe, self._sizes
        mine = ref.held(moe, self._rank, self._ep)
        if self._routers is None:
            self._routers = [
                ref.router_weights(moe, self._seed, at)
                for at in range(sizes["layers"])]
            # the reference's expert, weighted into a request's answer
            self._add = jax.jit(lambda y, w, e, x, *block: y + jax.lax.
                                dynamic_slice_in_dim(w, e, 1, 1) * ref.expert(x, *block))
            self._take = jax.jit(lambda x, at, n: jnp.where(
                jnp.arange(at.shape[0])[:, None] < n, x[at], 0.0))
        least, most = sizes["tokens_sent"]
        shown, weights, tokens = [], [], []
        for payload, batch in todo:
            for attempt in range(64):
                x = ref.micro_batch(
                    payload, layer, attempt, sizes["tokens"], moe.hidden_size)
                rows, w = ref.sent_here(
                    moe, x, self._routers[layer], self._rank, self._ep)
                if least <= len(rows) <= most:
                    break
                self.redrawn += 1
            else:
                raise RuntimeError("no micro-batch of the stated size in 64 draws")
            # every block of the reference runs at one shape: the rows sent,
            # then rows of zeros up to the most a request may hold
            at = np.zeros(most, np.int32)
            at[: len(rows)] = rows
            x = self._take(x, at, np.int32(len(rows)))
            w = np.pad(w, ((0, most - len(rows)), (0, 0)))
            batch.requests.append(
                ref.pack_request(layer, np.asarray(x)[: len(rows)], w[: len(rows)]))
            batch.pairs.append(int((w != 0).sum()))
            self.pool_tokens.append(len(rows))
            self.pool_pairs.append(batch.pairs[-1])
            shown.append(x)
            weights.append(jnp.asarray(w))
            tokens.append(len(rows))
        answers = [jnp.zeros(x.shape, jnp.float32) for x in shown]
        for e, expert in enumerate(mine):
            block = ref.expert_weights(moe, self._seed, layer, expert)
            for i, x in enumerate(shown):
                answers[i] = self._add(answers[i], weights[i], np.int32(e), x, *block)
        for (_payload, batch), y, t in zip(todo, answers, tokens):
            batch.wants.append(np.asarray(y)[:t])

    # -- what holds() asks -------------------------------------------------------

    def malformed(self) -> tuple:
        """Guarantee (5), with nothing in flight: four requests that must
        fail (a layer out of range, a token more than was sent, a weight
        that is not finite, the last token's weights cut off), then a
        good one that must still be answered as before.
        ``(sent, answered or failed another way, the good one outside)``."""
        from incubator_brpc_tpu.rpc import Controller
        from incubator_brpc_tpu.utils.status import ErrorCode

        batch = next(iter(self._batches.values()))
        good = batch.requests[0]
        head = struct.Struct("<4I")
        layer, tokens, hidden, held = head.unpack_from(good)
        body = good[head.size :]
        nan = struct.pack("<f", float("nan"))
        bad = [
            head.pack(self._sizes["layers"], tokens, hidden, held) + body,
            head.pack(layer, tokens + 1, hidden, held) + body,
            head.pack(layer, tokens, hidden, held) + body[:-4] + nan,
            good[: -4 * held],
        ]
        wrong = 0
        for request in bad:
            cntl = self._channel.call_method(
                SERVICE, METHOD, request, cntl=Controller(timeout_ms=60000))
            wrong += not (cntl.failed() and cntl.error_code == ErrorCode.EREQUEST)
        cntl = self._channel.call_method(
            SERVICE, METHOD, good, cntl=Controller(timeout_ms=60000))
        touched = cntl.failed() or not self.judge(
            cntl.response_payload, batch.wants[0], batch.pairs[0])
        return len(bad), wrong, int(touched)


class Deployment:
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models.expert_shard import ExpertShardService
        from incubator_brpc_tpu.rpc import Server
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        device = jax.devices()[0]
        moe_keys = manifest.load_module(
            "references", config["reference"] + ".py").Moe._fields
        sizes = {
            "moe": {k: config[k] for k in moe_keys},
            "layers": int(config["num_hidden_layers"]),
            "tokens": int(config["micro_batch_tokens"]),
            "tokens_sent": [config["tokens_sent"]["least"],
                            config["tokens_sent"]["most"]],
            "expert_parallel": int(config["unit"]["expert_parallel"]),
            "rank": int(config["unit"]["rank"]),
            "tolerance": config["tolerance"],
        }
        # the router's outputs are the unit's, the experts held the rank's
        sizes["moe"]["n_routed_experts"] = int(config["unit"]["router_outputs"])
        if device.platform != "tpu":
            small = config["rehearsal"]
            sizes["moe"].update(small["moe"])
            sizes.update({k: v for k, v in small.items() if k != "moe"})
            print(f"REHEARSAL unit: {small} on {device.platform}, not the "
                  f"configuration's", flush=True)
        moe = sizes["moe"]
        held = moe["n_routed_experts"] // sizes["expert_parallel"]
        service = _control_service(ExpertShardService, control)(
            moe["hidden_size"], moe["moe_intermediate_size"], held,
            sizes["layers"], seed=int(config["weight_seed"]),
            first_expert=sizes["rank"] * held)
        self._sizes, self._config, self._service = sizes, config, service
        self.endpoint = DeviceEndpoint(
            service=service, device=device, **config["endpoint"])
        handler = self.endpoint.server_handler(method_id=FFN_ID)
        if control == "stale":
            handler = _stale(handler)
        if spans is not None:
            handler = spans.wrap(handler)
        self.server = Server()
        self.server.add_service(SERVICE, {METHOD: handler})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self.devices = [device]
        self._options = dict(config["channel_options"])
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        self._client = None

    def warm(self, traffic: dict) -> None:
        """Every (batch, bucket) program the callers can form, through the
        endpoint's own warm: the bucket of each request size the stated
        range of tokens sent gives (one, at the configuration's sizes),
        alone and in every batch up to ``max_batch``."""
        from incubator_brpc_tpu.transport.device import _bucket_words

        service, warmed = self._service, set()
        least, most = self._sizes["tokens_sent"]
        for tokens in range(least, most + 1):
            nbytes = 16 + tokens * service.token_bytes
            bucket = _bucket_words(-(-nbytes // 4))
            if bucket not in warmed:
                warmed.add(bucket)
                self.endpoint.warm(nbytes, method_id=FFN_ID)
        print(f"warmed buckets of {sorted(warmed)} words", flush=True)

    def channel(self):
        from incubator_brpc_tpu.rpc import Channel, ChannelOptions

        if self._client is None:
            channel = Channel()
            if not channel.init(f"127.0.0.1:{self.port}",
                                options=ChannelOptions(**self._options)):
                raise RuntimeError("cannot reach the server")
            self._client = _Client(
                channel, self._reference, self._sizes, self._config)
        return self._client

    def holds(self) -> list:
        """``(what, value, limit, held)``: guarantee (1)'s count beside the
        generator's own (its (2) is part of it), then (3), (4) and (5)."""
        c = self.channel()
        sent, answered, touched = c.malformed()
        tolerance = self._sizes["tolerance"]
        pool, pairs = np.asarray(c.pool_tokens), np.asarray(c.pool_pairs)
        print(f"pool: {len(pool)} requests, tokens sent mean {pool.mean():.2f} "
              f"least {pool.min()} most {pool.max()}, pairs a token "
              f"{pairs.sum() / pool.sum():.4f}, {c.redrawn} micro-batches "
              f"drawn again; farthest answer: rel_l2 {c.worst[0]:.6f}, "
              f"element_over_rms {c.worst[1]:.6f}", flush=True)
        served = _served(c.tokens, c.pairs)
        mean_tokens = c.tokens / max(c.calls, 1)
        mean_pairs = c.pairs / max(c.tokens, 1)
        pool_pairs = pairs.sum() / pool.sum()
        stats = self.devices[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        low = self._service.weight_bytes
        high = int(1.25 * low)
        return [
            ("answers_outside_tolerance", c.outside,
             f"0 of {c.calls} judged; rel_l2 <= {tolerance['rel_l2']}, "
             f"element_over_rms <= {tolerance['element_over_rms']}",
             c.outside == 0),
            ("weights_read_where_they_lie_peak_bytes",
             peak if peak is not None else "not reported on this platform",
             f">= {low} and < {high}", peak is None or low <= peak < high),
            ("tokens_sent_and_not_served", max(0, c.tokens - served[0]), 0,
             c.tokens <= served[0]),
            ("token_expert_pairs_sent_and_not_served",
             max(0, c.pairs - served[1]), 0, c.pairs <= served[1]),
            ("tokens_a_call", round(mean_tokens, 3),
             f"{pool.min()} to {pool.max()}, the pool's",
             pool.min() <= mean_tokens <= pool.max()),
            ("pairs_a_token", round(mean_pairs, 4),
             f"within 10% of the pool's {pool_pairs:.4f}",
             abs(mean_pairs / pool_pairs - 1) <= 0.1),
            (f"malformed_requests_of_{sent}_not_failed_EREQUEST", answered, 0,
             answered == 0),
            ("answers_outside_after_the_malformed", touched, 0, touched == 0),
        ]

    def close(self) -> None:
        self.server.stop()
        self.server.join(timeout=10)


def _served(tokens: int, pairs: int) -> tuple:
    """``(tokens, pairs)`` the service counted since the process began
    (``account``, a completion watcher's, runs after the callers wake: the
    last dispatch's count is given two seconds to land)."""
    import time

    from incubator_brpc_tpu.models import expert_shard

    deadline = time.monotonic() + 2.0
    while True:
        served = (expert_shard.m_tokens.get_value(),
                  expert_shard.m_pairs.get_value())
        if served >= (tokens, pairs) or time.monotonic() > deadline:
            return served
        time.sleep(0.01)
