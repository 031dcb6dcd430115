"""One server that holds the chip and a record table on it: ``Server()``
with a service of two methods, ``read`` and ``update``, each
``DeviceEndpoint(...).server_handler(method_id=...)`` of **one** endpoint
over ``RecordTableService`` — ``device_echo.py`` with the service changed:
host RPC in, frame to HBM, the step (parse, verify, gather, apply the
updates where the table lies) on the device, response out.

The generator is ``in_process``: the harness's own-process generator sends
its random bytes as the request over a bare ``Channel`` and compares with a
reference that knows no history. A keyed store needs a client that forms
operations and a reference that remembers, so ``channel()`` returns an
adapter: ``call_method`` reads the operation off the payload
(``references/ycsb_record_store.py``), makes the real call over a
``Channel()`` to ``127.0.0.1:port`` (the host plane is on the path as in
``echo_256b_c16``; the callers are threads of the server's process), and
returns what the generator reads: ``failed()``, and a ``response_payload``
that equals the reference's ``expected`` exactly when the answer passed the
register check, judged when it is first read, the call's clock stopped.

On a platform that is not a TPU the table holds the configuration's
``rehearsal_records`` and the deployment says so on a line of its own.
"""

from __future__ import annotations

import threading
import time

from benchmark import manifest

SERVICE = "ycsb"
READ_ID, UPDATE_ID = 1, 2  # models/record_table.py's method ids


def _flip_bit_service(base):
    """Control: the step answers a read with one bit wrong (the lowest of
    the record's first word)."""

    class FlipBit(base):
        def dispatch_step(self, table, rows, cids, mids):
            import jax.numpy as jnp

            table, frames = self.step(table, rows, cids, mids)
            flipped = frames[:, 8] ^ (mids == READ_ID).astype(jnp.uint32)
            return table, frames.at[:, 8].set(flipped)

    return FlipBit


def _stale(handler):
    """Control: a read is answered with the record the read before it was
    given — acknowledged, and not its own."""
    last = {}

    def stale(cntl, request):
        out = handler(cntl, request)
        previous = last.get(len(out), out)
        last[len(out)] = out
        return previous

    return stale


CONTROLS = ("flip_bit", "stale")


class _Answer:
    """What the generator reads of one operation. The verdict is reached
    when ``response_payload`` is first read: the clock has stopped."""

    response_attachment = b""

    def __init__(self, client, payload, parts, cntl, sent, answered):
        self._client, self._payload, self._parts = client, payload, parts
        self._cntl, self._sent, self._answered = cntl, sent, answered
        self._verdict = None

    def failed(self) -> bool:
        return self._cntl.failed()

    @property
    def error_text(self) -> str:
        return self._cntl.error_text

    @property
    def response_payload(self) -> bytes:
        if self._verdict is None:
            answer = self._cntl.response_payload
            passed = self._client.judge(
                self._parts, answer, self._sent, self._answered)
            self._verdict = self._payload if passed else answer
        return self._verdict


class _Client:
    """The YCSB client: ``call_method`` of the harness is one operation."""

    def __init__(self, channel, reference, records: int, table_seed: int):
        self._channel, self._ref = channel, reference
        self._workload = reference.Workload(records)
        self.register = reference.Register(table_seed)
        self._lock = threading.Lock()
        self.reads = self.updates = 0
        self.wrong_fields = self.wrong_reads = self.wrong_statuses = 0

    def call_method(self, service, method, request, attachment=b"", cntl=None):
        from incubator_brpc_tpu.rpc import Controller

        ref, parts = self._ref, self._workload.parts(request)
        kind, key, field, value = parts
        timeout_ms = cntl.timeout_ms if cntl is not None else 60000
        sent = time.monotonic_ns()
        if kind == ref.READ:
            wire, entry = ref.KEY.pack(key), None
        else:
            wire = ref.UPDATE_HEAD.pack(key, field) + value
            entry = self.register.sent(key, field, value, sent)
        answer = self._channel.call_method(
            service, kind, wire, cntl=Controller(timeout_ms=timeout_ms))
        answered = time.monotonic_ns()
        if entry is not None and not answer.failed():
            self.register.acknowledged(entry, answered)
        return _Answer(self, request, parts, answer, sent, answered)

    def judge(self, parts, answer: bytes, sent: int, answered: int) -> bool:
        kind, key, _field, _value = parts
        if kind == self._ref.READ:
            wrong = self.register.wrong_fields(key, answer, sent, answered)
            with self._lock:
                self.reads += 1
                self.wrong_fields += wrong
                self.wrong_reads += bool(wrong)
            return not wrong
        ok = answer == self._ref.STATUS_OK
        with self._lock:
            self.updates += 1
            self.wrong_statuses += not ok
        return ok

    def read_back(self) -> tuple:
        """Guarantee (2): with nothing in flight, every record an update was
        sent to, read through the served path. ``(fields updated, fields of
        those records that show a value no acknowledged update left
        there)``."""
        from incubator_brpc_tpu.rpc import Controller

        ref, fields, lost = self._ref, 0, 0
        for key, touched in sorted(self.register.updated().items()):
            sent = time.monotonic_ns()
            cntl = self._channel.call_method(
                SERVICE, ref.READ, ref.KEY.pack(key),
                cntl=Controller(timeout_ms=60000))
            record = b"" if cntl.failed() else cntl.response_payload
            fields += len(touched)
            lost += self.register.wrong_fields(
                key, record, sent, time.monotonic_ns())
        return fields, lost


class Deployment:
    def __init__(self, config: dict, control, spans):
        import jax

        from incubator_brpc_tpu.models.record_table import RecordTableService
        from incubator_brpc_tpu.rpc import Server
        from incubator_brpc_tpu.transport.device import DeviceEndpoint

        device = jax.devices()[0]
        self.records = int(config["recordcount"])
        if device.platform != "tpu":
            self.records = int(config["rehearsal_records"])
            print(f"REHEARSAL table: {self.records} records on "
                  f"{device.platform}, not the configuration's "
                  f"{config['recordcount']}", flush=True)
        self._table_seed = int(config["table_seed"])
        service_type = RecordTableService
        if control == "flip_bit":
            service_type = _flip_bit_service(RecordTableService)
        service = service_type(
            self.records, fields=int(config["fieldcount"]),
            field_words=int(config["fieldlength"]) // 4,
            row_words=int(config["row_words"]), seed=self._table_seed)
        self.table_bytes = 4 * self.records * int(config["row_words"])
        self.endpoint = DeviceEndpoint(
            service=service, device=device, **config["endpoint"])
        read = self.endpoint.server_handler(method_id=READ_ID)
        update = self.endpoint.server_handler(method_id=UPDATE_ID)
        if control == "stale":
            read = _stale(read)
        if spans is not None:
            read, update = spans.wrap(read), spans.wrap(update)
        self.server = Server()
        self.server.add_service(SERVICE, {"read": read, "update": update})
        if not self.server.start(0):
            raise RuntimeError("the server did not start")
        self.port = self.server.port
        self.devices = [device]
        self._options = dict(config["channel_options"])
        self._reference = manifest.load_module(
            "references", config["reference"] + ".py")
        self._update_share = float(config["updateproportion"])
        self._client = None

    def warm(self, traffic: dict) -> None:
        """Every (batch, bucket) program the callers can form, through the
        endpoint's own warm: a read's bucket (8 B in, a record out) and an
        update's, each alone and in every batch up to ``max_batch``."""
        ref = self._reference
        self.endpoint.warm(ref.KEY.size, method_id=READ_ID)
        self.endpoint.warm(
            ref.UPDATE_HEAD.size + ref.FIELDLENGTH, method_id=UPDATE_ID)

    def channel(self):
        from incubator_brpc_tpu.rpc import Channel, ChannelOptions

        if self._client is None:
            channel = Channel()
            if not channel.init(f"127.0.0.1:{self.port}",
                                options=ChannelOptions(**self._options)):
                raise RuntimeError("cannot reach the server")
            self._client = _Client(
                channel, self._reference, self.records, self._table_seed)
        return self._client

    def holds(self) -> list:
        """``(what, value, limit, held)``: guarantee (1)'s counts beside the
        generator's own, then (2), (4) and (5)."""
        c = self.channel()
        fields, lost = c.read_back()
        calls = c.reads + c.updates
        share = c.updates / calls if calls else 0.0
        want = self._update_share
        # the source's 4-6%; a short run (a rehearsal) gets four standard
        # deviations of its own count
        room = max(0.01, 4 * (want * (1 - want) / max(calls, 1)) ** 0.5)
        stats = self.devices[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        low, high = self.table_bytes, int(1.25 * self.table_bytes)
        return [
            ("reads_with_a_field_no_update_left_there", c.wrong_reads, 0,
             c.wrong_reads == 0),
            ("fields_torn_or_of_no_update", c.wrong_fields, 0, c.wrong_fields == 0),
            ("updates_with_another_status", c.wrong_statuses, 0,
             c.wrong_statuses == 0),
            (f"acknowledged_updates_lost_of_{fields}_fields_read_back", lost, 0,
             lost == 0),
            ("table_updated_where_it_lies_peak_bytes",
             peak if peak is not None else "not reported on this platform",
             f">= {low} and < {high}",
             peak is None or low <= peak < high),
            ("update_share_of_calls", round(share, 5),
             f"{want - room:.4f} to {want + room:.4f} over {calls} calls",
             abs(share - want) <= room),
        ]

    def close(self) -> None:
        self.server.stop()
        self.server.join(timeout=10)
