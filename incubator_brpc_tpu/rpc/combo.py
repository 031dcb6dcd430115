"""Combo channels — ParallelChannel / SelectiveChannel / PartitionChannel
(reference src/brpc/parallel_channel.{h,cpp}, selective_channel.{h,cpp},
partition_channel.{h,cpp}).

These compose ordinary Channels on the host RPC plane. When every party
sits on one device mesh, the same fan-out/merge and partition-exchange
semantics lower to XLA collectives instead (parallel/collective.py — the
SURVEY §2.5 ICI fast path); the classes here are the general
point-to-point form.

Kept semantics:
- ParallelChannel: CallMapper maps (channel_index, request) → SubCall
  (broadcast / rewritten / skipped, parallel_channel.h:36-101); sub-calls
  run concurrently; the parent fails once ``nfailed >= fail_limit``
  (default: all non-skipped must fail, parallel_channel.cpp:625-627);
  successful responses merge in channel-index order via ResponseMerger.
- SelectiveChannel: sub-channels are schedulable units behind an internal
  LB; retries go to *different* sub-channels (selective_channel.cpp, the
  `_sender` hook controller.cpp:956-964).
- PartitionChannel: one naming service splits into per-partition
  sub-channels via a PartitionParser reading "N/M" server tags
  (partition_channel.h:44-50); the call fans out like ParallelChannel.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from incubator_brpc_tpu.bvar import (
    CPU_CLOCK_EVERY,
    Adder,
    LatencyRecorder,
    RecorderFeed,
    clocks,
)
from incubator_brpc_tpu.rpc.channel import Channel, ChannelOptions
from incubator_brpc_tpu.rpc.controller import HOST_BYTES, RETRIABLE, Controller
from incubator_brpc_tpu.utils.endpoint import EndPoint
from incubator_brpc_tpu.utils.status import ErrorCode, berror

logger = logging.getLogger(__name__)

# the stages of a fused call, in order: their means add up to the call's
# but for the CallMapper's own time. One row a fused call waits in the
# feed for bvar's 1 Hz sampler, as rpc/stream.py's do
FUSED_STAGES = (
    "resolve", "pack", "put", "launch_wait", "launch", "gather", "merge",
)
# the stages that run on the caller's processor (launch_wait parks for the
# launch order, resolve is not timed twice): <stage>_cpu_us beside <stage>_us.
# One fused call in bvar.CPU_CLOCK_EVERY carries the CPU stamps, the others -1
FUSED_CPU_STAGES = ("pack", "put", "launch", "gather", "merge")
_fused_calls = itertools.count()
# A fused call's row: call_method entered, the stamp that opens each stage
# (time.monotonic_ns()), the merge's end and the call's, then the caller's
# CPU clock (time.thread_time_ns()) at the same stamps from resolve to
# merged: every stamp is the caller's own thread's.
_FUSED_WALL = ("call",) + FUSED_STAGES + ("merged", "end")
FUSED_STAMPS = _FUSED_WALL + tuple(
    what + "_cpu" for what in FUSED_STAGES + ("merged",)
)
# a stage lies between the stamp of its name and the next one
_FUSED_SPAN = dict(zip(FUSED_STAGES, zip(FUSED_STAGES, _FUSED_WALL[2:])))


class _ComboVars:
    """The collective lowering's recorders and adders, under the device
    link's prefix (docs/OBSERVABILITY.md). A call counts in exactly one of
    ``fused`` (one shard_map dispatch), ``mc_lowered`` (a session of the
    collective method plane) and ``host_fanout``."""

    def __init__(self, prefix: str = "device_link_combo"):
        def recorder(what: str) -> LatencyRecorder:
            return LatencyRecorder(name=f"{prefix}_{what}_us")

        # the last 16 Ki rows stay (30 s of the partitioned cell, twice over)
        self.calls = RecorderFeed(
            [(recorder("call"), 1e-3, ("call", "end"))]
            + [(recorder(s), 1e-3, _FUSED_SPAN[s]) for s in FUSED_STAGES]
            + [
                (recorder(s + "_cpu"), 1e-3,
                 tuple(at + "_cpu" for at in _FUSED_SPAN[s]))
                for s in FUSED_CPU_STAGES
            ],
            stamps=FUSED_STAMPS,
            name=f"{prefix}_calls",
            ring_rows=1 << 14,
            worker=tuple(_FUSED_SPAN[s] for s in FUSED_STAGES if s != "launch_wait"),
            call=(("call", "end"),),
        )
        self.fused = Adder(name=f"{prefix}_fused")
        # fused calls whose answer was one join of the gathered rows
        self.joined = Adder(name=f"{prefix}_joined")
        self.host_fanout = Adder(name=f"{prefix}_host_fanout")
        self.mc_lowered = Adder(name=f"{prefix}_mc_lowered")
        self.rows = Adder(name=f"{prefix}_rows")  # sub-requests a fused program ran
        self.bytes = Adder(name=f"{prefix}_bytes")  # request bytes scattered in them


COMBO_VARS = _ComboVars()


class _FusedCall:
    """What one lowered call leaves behind: the lowering taken, its
    partitions and the stamps its stages are cut at, on both clocks
    (``stamps`` wall from ``call_method``'s entry, ``cpu`` the caller's
    CPU clock from the first ``stamp()``, -1 in a call that is not
    ``timed``)."""

    __slots__ = (
        "service", "method", "lowering", "devices", "nbytes", "stamps", "cpu",
        "timed", "joined",
    )

    def __init__(self, service: str, method: str, t_call: int):
        self.service, self.method = service, method
        self.lowering = ""
        self.devices: list = []
        self.nbytes = 0
        self.joined = False  # the answer was one join (merge_responses)
        self.stamps = [t_call]
        self.cpu: list = []
        self.timed = next(_fused_calls) % CPU_CLOCK_EVERY == 0

    def stamp(self) -> None:
        wall, cpu = clocks(self.timed)
        self.stamps.append(wall)
        self.cpu.append(cpu)

    def record(self) -> None:
        """The call is over: the adders, one row for the sampler and, under
        rpcz, the call's one span."""
        from incubator_brpc_tpu.builtin.rpcz import (
            SPAN_TYPE_COLLECTIVE,
            end_custom_span,
            start_custom_span,
        )

        end = time.monotonic_ns()
        row = None
        if self.lowering == "fused":
            row = (*self.stamps, end, *self.cpu)
            COMBO_VARS.calls.rows.append(row)
            COMBO_VARS.fused << 1
            if self.joined:
                COMBO_VARS.joined << 1
            COMBO_VARS.rows << len(self.devices)
            COMBO_VARS.bytes << self.nbytes
        else:
            COMBO_VARS.mc_lowered << 1
        span = start_custom_span(SPAN_TYPE_COLLECTIVE, self.service, self.method)
        if span is None:
            return
        span.start_real_us -= (end - self.stamps[0]) // 1000
        note = (
            f"lowering={self.lowering} partitions={len(self.devices)} "
            f"devices={[getattr(d, 'id', None) for d in self.devices]} "
            f"request_bytes={self.nbytes}"
        )
        if row is not None:
            stages = COMBO_VARS.calls.read(row)[1 : 1 + len(FUSED_STAGES)]
            note += " " + " ".join(
                f"{what}_us={ns / 1e3:.0f}" for what, ns in zip(FUSED_STAGES, stages)
            )
        span.annotate(note)
        end_custom_span(span)


# -- ParallelChannel ---------------------------------------------------------


class SubCall:
    """What a CallMapper returns per sub-channel (parallel_channel.h:36)."""

    __slots__ = ("service", "method", "request", "skipped")

    def __init__(
        self,
        service: Optional[str] = None,
        method: Optional[str] = None,
        request: Optional[bytes] = None,
        skipped: bool = False,
    ):
        self.service = service
        self.method = method
        self.request = request
        self.skipped = skipped

    @classmethod
    def skip(cls) -> "SubCall":
        return cls(skipped=True)


class CallMapper:
    """Default: broadcast the original request to every sub-channel."""

    def map(
        self, channel_index: int, nchannels: int, service: str, method: str,
        request: bytes,
    ) -> SubCall:
        return SubCall()


class ResponseMerger:
    """Incremental merge in channel-index order (parallel_channel.h:103).
    Default: concatenate payload bytes.

    A subclass's ``merge`` (or one set on an instance) is called once a
    successful sub-call, in channel order, with ``bytes``. Where every
    merger of a call is this class's own, ``merge`` is not called: the
    answers are joined at once (``merge_responses``), which gives the same
    bytes and copies each once."""

    def merge(self, merged: bytes, sub_response: bytes) -> bytes:
        return merged + sub_response


def merge_responses(mergers, answers) -> Tuple[bytes, bool]:
    """The merged answer of one call, every lowering's: ``mergers`` and
    their sub-calls' ``answers`` in channel order. Where every merger's
    ``merge`` is ``ResponseMerger.merge`` itself, one join writes each
    answer byte once (an answer may be any buffer, a view of the gathered
    rows too); else each merger is called in turn with ``bytes``. Returns
    the bytes and whether they were joined."""
    if all(
        getattr(m.merge, "__func__", None) is ResponseMerger.merge for m in mergers
    ):
        return b"".join(answers), True
    merged = b""
    for merger, answer in zip(mergers, answers):
        merged = merger.merge(
            merged, answer if isinstance(answer, bytes) else bytes(answer)
        )
    return merged, False


class ParallelChannel:
    """Scatter/gather across sub-channels (parallel_channel.cpp).

    When every non-skipped sub-channel rides a device link (transport=
    'tpu') to a DISTINCT mesh device and the target method is a registered
    device method (rpc/device_method.py), the whole scatter → execute →
    gather fuses into ONE shard_map dispatch: each server device runs the
    method kernel on its sub-request shard and an all-gather
    (parallel/collective.fanout) returns every response in a single
    collective — the SURVEY §2.5 lowering of this row ("ParallelChannel
    fan-out/merge → all-gather across pod replicas"; BASELINE configs
    #3/#4). When the sub-channels resolve to MULTI-CONTROLLER links the
    single dispatch is impossible (operand bytes cannot be placed on
    non-addressable devices), so the call lowers through the collective
    method plane instead: a 1-step N-party session of the same kernel,
    scheduled over the host plane (parallel/mc_dispatch.py) — one API,
    the transport picks the lowering. Every path runs the same jitted
    kernel over the same "par" axis and merges through one function
    (``merge_responses``: a merger's ``merge`` is called once a successful
    sub-call, in channel order, with ``bytes``; where every merger is the
    default ``ResponseMerger`` the answers are joined at once instead), so
    fused, mc-lowered and host fan-out produce byte-identical merged
    responses. A precondition that does not hold chooses the host path —
    a choice from observed geometry; once the device path is chosen, a
    failure of its program fails the call with the program's text (a
    fan-out that quietly succeeded instead would hide a device plane that
    cannot run)."""

    def __init__(self, fail_limit: int = -1, fuse_device_calls: bool = True):
        self.fail_limit = fail_limit
        self.fuse_device_calls = fuse_device_calls
        self._subs: List[Tuple[Channel, CallMapper, ResponseMerger]] = []
        self._fused_cache: dict = {}  # (dm id, devices) -> compiled dispatch

    def add_channel(
        self,
        channel: Channel,
        call_mapper: Optional[CallMapper] = None,
        response_merger: Optional[ResponseMerger] = None,
    ) -> None:
        self._subs.append(
            (channel, call_mapper or CallMapper(), response_merger or ResponseMerger())
        )

    @property
    def channel_count(self) -> int:
        return len(self._subs)

    def call_method(
        self,
        service: str,
        method: str,
        request: bytes,
        cntl: Optional[Controller] = None,
        done: Optional[Callable[[Controller], None]] = None,
        attachment: bytes = b"",
        request_stream=None,
    ) -> Controller:
        """What ``Channel.call_method`` takes. The attachment goes to every
        non-skipped sub-call as it is (parallel_channel.cpp appends the
        parent's to each sub-controller's) and the sub-calls' response
        attachments come back joined in channel order; a call that carries
        one does not fuse, for a device kernel sees request bytes only. A
        stream rides one connection, which a combo channel has not."""
        t_call = time.monotonic_ns()
        if cntl is None:
            cntl = Controller()
        nchan = len(self._subs)
        refused = (
            "ParallelChannel has no sub channels" if nchan == 0
            else "a combo channel carries no stream" if request_stream is not None
            # ROADMAP.md, Reach: a device array through a combo channel
            else "a combo channel's attachment is host bytes"
            if not isinstance(attachment, HOST_BYTES)
            else None
        )
        if refused:
            cntl.set_failed(ErrorCode.EINVAL, refused)
            if done:
                done(cntl)
            return cntl

        plan: List[Optional[Tuple[Channel, ResponseMerger, SubCall]]] = []
        for i, (ch, mapper, merger) in enumerate(self._subs):
            sub = mapper.map(i, nchan, service, method, request)
            plan.append(None if sub.skipped else (ch, merger, sub))
        ndone = sum(1 for p in plan if p is not None)
        if ndone == 0:
            cntl.set_failed(ErrorCode.EREQUEST, "all sub calls skipped")
            if done:
                done(cntl)
            return cntl
        if self.fuse_device_calls and ndone >= 2 and not attachment:
            call = _FusedCall(service, method, t_call)
            try:
                fused = self._maybe_fused_device_call(
                    service, method, request, plan, cntl, call
                )
            except Exception as e:
                logger.exception("fused collective dispatch failed")
                cntl.set_failed(
                    getattr(e, "error_code", ErrorCode.EINTERNAL),
                    f"fused collective dispatch failed: {e!r}",
                )
                if done is not None:
                    done(cntl)
                return cntl
            if fused is not None:
                cntl.response_payload = fused
                cntl.collective_fused = True
                call.record()
                if done is not None:
                    done(cntl)
                return cntl
        COMBO_VARS.host_fanout << 1

        # 1 <= fail_limit <= ndone (parallel_channel.cpp:625-637)
        fail_limit = self.fail_limit
        if fail_limit < 0:
            fail_limit = ndone
        fail_limit = max(1, min(fail_limit, ndone))

        state = {
            "remaining": ndone,
            "nfailed": 0,
            "first_error": (0, ""),
            "finished": False,
        }
        lock = threading.Lock()
        all_done = threading.Event()
        sub_cntls: List[Optional[Controller]] = [None] * nchan

        def finish() -> None:
            if state["nfailed"] >= fail_limit:
                code, text = state["first_error"]
                cntl.set_failed(
                    code or ErrorCode.EINTERNAL,
                    f"{state['nfailed']}/{ndone} sub calls failed "
                    f"(fail_limit={fail_limit}): {text}",
                )
            else:
                served = [
                    (p[1], sc) for p, sc in zip(plan, sub_cntls)
                    if p is not None and sc is not None and sc.ok()
                ]
                cntl.response_payload, _ = merge_responses(
                    [merger for merger, _sc in served],
                    [sc.response_payload for _merger, sc in served],
                )
                cntl.response_attachment = b"".join(
                    sc.response_attachment for _merger, sc in served
                )
            all_done.set()
            if done is not None:
                done(cntl)

        def sub_done(i: int, sc: Controller) -> None:
            with lock:
                sub_cntls[i] = sc
                if sc.failed():
                    state["nfailed"] += 1
                    if state["first_error"][0] == 0:
                        state["first_error"] = (sc.error_code, sc.error_text)
                state["remaining"] -= 1
                # early finish once the verdict is decided either way
                # (parallel_channel.cpp:221-224 cancels the rest; our
                # remaining sub-calls just complete into a dead closure)
                decided = (
                    state["remaining"] == 0 or state["nfailed"] >= fail_limit
                )
                if not decided or state["finished"]:
                    return
                state["finished"] = True
            finish()

        for i, p in enumerate(plan):
            if p is None:
                continue
            ch, _, sub = p
            sc = Controller(
                timeout_ms=cntl.timeout_ms,
                max_retry=cntl.max_retry,
                backup_request_ms=cntl.backup_request_ms,
            )
            sc.compress_type = cntl.compress_type
            sc.log_id = cntl.log_id
            ch.call_method(
                sub.service or service,
                sub.method or method,
                request if sub.request is None else sub.request,
                cntl=sc,
                done=(lambda c, _i=i: sub_done(_i, c)),
                attachment=attachment,
            )
        if done is None:
            all_done.wait()
        return cntl

    call = call_method

    # -- the ICI collective lowering (SURVEY §2.5; BASELINE #3/#4) -----------

    def _maybe_fused_device_call(
        self, service, method, request, plan, cntl, call: "_FusedCall"
    ) -> Optional[bytes]:
        """One shard_map dispatch over the sub-channels' server devices, or
        None when the preconditions don't hold (host fan-out runs instead).
        Raises what the device program raised once it was chosen.

        Preconditions: the method has a registered device kernel; every
        non-skipped sub-channel uses transport='tpu' and resolves a live
        device link; the links' server devices are pairwise distinct (they
        form the mesh axis); every sub-request fits the kernel row width.
        ``call`` takes the lowering chosen and the stamps of its stages.
        """
        from incubator_brpc_tpu.rpc.device_method import lookup_device_method

        call.stamp()  # resolve: LB picks and fingerprint checks
        dm = lookup_device_method(service, method)
        if dm is None:
            return None
        full = f"{service}.{method}"
        fp = dm.fingerprint()
        subs = [(i, p) for i, p in enumerate(plan) if p is not None]
        mergers = [merger for _i, (_ch, merger, _sub) in subs]
        requests: List[bytes] = []
        devices = []
        probed: List[tuple] = []  # (channel, device socket) picks to settle

        def _settle_probes() -> None:
            # release LB picks that never became an RPC (la charges
            # in-flight on select; an un-settled probe would depress the
            # peer's weight forever) — no latency sample is recorded
            for pch, pds in probed:
                if pch._lb is not None:
                    pch._lb.settle(pds)

        links = []
        for _i, (ch, _merger, sub) in subs:
            if sub.service is not None or sub.method is not None:
                # a mapper that redirects a sub-call to a different method
                # must run on the host path (the fused program compiles ONE
                # kernel for the whole axis)
                _settle_probes()
                return None
            if getattr(ch._options, "transport", "") != "tpu":
                _settle_probes()
                return None
            req = request if sub.request is None else sub.request
            if len(req) > dm.width:
                _settle_probes()
                return None
            requests.append(req)
            try:
                ds = ch._pick_socket(Controller(timeout_ms=cntl.timeout_ms))
            except Exception:
                _settle_probes()
                return None  # cannot resolve a link: host path arbitrates
            probed.append((ch, ds))
            link = getattr(ds, "link", None)
            if link is None or link._mesh is None:
                _settle_probes()
                return None  # not a device link (or loopback geometry)
            if getattr(ds, "device_methods", {}).get(full) != fp:
                # the peer did not advertise THIS kernel under this name —
                # fusing would run a kernel the server never registered
                _settle_probes()
                return None
            devices.append(link.devices[1])
            links.append(link)
        ids = [getattr(d, "id", None) for d in devices]
        if len(set(ids)) != len(ids):
            _settle_probes()
            return None  # shared devices cannot form the collective axis
        # multi-controller sub-links cannot take the single-dispatch fuse
        # (this process cannot place operand bytes on non-addressable
        # devices) — they lower through the collective method plane
        # instead: one 1-step N-party session of the SAME kernel over the
        # same axis, scheduled over the host plane (parallel/mc_dispatch)
        call.devices, call.nbytes = devices, sum(len(r) for r in requests)
        mc = [getattr(lk, "own_side", None) is not None for lk in links]
        if any(mc):
            if not all(mc):
                _settle_probes()
                return None  # mixed planes cannot form one party axis
            call.lowering = "mc_lowered"
            t0 = time.perf_counter()
            try:
                from incubator_brpc_tpu.parallel import mc_dispatch

                outs = mc_dispatch.lower_parallel_call(
                    [ch for _i, (ch, _m, _s) in subs],
                    devices,
                    service,
                    method,
                    requests,
                    timeout_ms=cntl.timeout_ms,
                )
            except Exception:
                _settle_probes()
                raise
            latency_us = (time.perf_counter() - t0) * 1e6
            for pch, pds in probed:
                if pch._lb is not None:
                    pch._lb.feedback(pds, latency_us, 0)
            return merge_responses(mergers, outs)[0]
        call.lowering = "fused"
        call.stamp()  # pack
        try:
            rows_out, ns_out = self._fused_dispatch(dm, devices, requests, call)
        except Exception:
            _settle_probes()
            raise
        # the servers DID serve this dispatch: settle each LB pick with the
        # real fused latency (the host path's per-sub feedback analog)
        latency_us = (call.stamps[-1] - call.stamps[2]) / 1e3
        for pch, pds in probed:
            if pch._lb is not None:
                pch._lb.feedback(pds, latency_us, 0)
        # each partition's answer is a view of its gathered row: the default
        # mergers' join is the one copy, a user's merger gets bytes of it
        merged, call.joined = merge_responses(
            mergers, [row[:n] for row, n in zip(rows_out, ns_out.tolist())]
        )
        call.stamp()  # the merge is over
        return merged

    def _fused_dispatch(self, dm, devices, requests: List[bytes], call: "_FusedCall"):
        """Pack, put, launch and gather; one stamp of ``call`` closes each
        stage. The operands are staged once: ``pack`` writes every request
        into its row of one ``(n, width)`` host buffer made for the call
        (``DeviceMethod.pack_into``: no zero-fill but a short row's tail),
        ``put`` hands the rows and the ``n``s to the runtime in one
        ``device_put`` under the mesh's sharding. Only the enqueue of the
        program is ordered across threads (``collective.launch_order``):
        the host packing before it and the read-back after it run beside
        other callers'."""
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from incubator_brpc_tpu.parallel import collective

        n = len(devices)
        key = (
            dm.fingerprint(),
            tuple(getattr(d, "id", i) for i, d in enumerate(devices)),
        )
        cached = self._fused_cache.get(key)
        if cached is not None and cached[3] is not dm:
            cached = None  # same name re-registered with a new DeviceMethod
        if cached is None:
            mesh = Mesh(np.asarray(devices), ("par",))
            data_sh = NamedSharding(mesh, P("par"))

            # named for the device trace: the program is jit_combo_fused
            def combo_fused(data, ns):
                # per-partition service execution on this shard's device...
                out, m = dm.kernel(data[0], ns[0])
                # ...then ONE all-gather returns every response everywhere
                # (parallel/collective.fanout — the ParallelChannel merge
                # side lowered to the ICI collective)
                return collective.fanout(out, "par"), collective.fanout(m, "par")

            # the all_gather makes outputs replicated, which the static
            # replication check cannot always infer — turn it off
            wrapped = jax.shard_map(
                combo_fused, mesh=mesh, in_specs=(P("par"), P("par")),
                out_specs=(P(), P()), check_vma=False,
            )
            fused = jax.jit(wrapped)
            cached = (fused, data_sh, mesh, dm)
            self._fused_cache[key] = cached
        fused, data_sh, mesh, _ = cached
        # one host buffer a call, each request byte written once; the arrays
        # made from it keep it alive while the runtime still reads it
        rows = np.empty((n, dm.width), dtype=np.uint8)
        ns = np.empty(n, dtype=np.int32)
        for i, r in enumerate(requests):
            ns[i] = dm.pack_into(rows[i], r)
        call.stamp()  # put: one hand-over, the runtime cuts the rows itself
        data, ns_sharded = jax.device_put((rows, ns), data_sh)
        call.stamp()  # launch_wait
        with collective.launch_order:
            call.stamp()  # launch: the program call until it returned
            g, gm = fused(data, ns_sharded)
        call.stamp()  # gather: the gathered rows on the host
        # uint8 rows, as DeviceMethod.unpack reads a row
        out = np.asarray(g, dtype=np.uint8), np.asarray(gm)
        call.stamp()  # merge: LB feedback, then the answers' one join or the mergers
        return out


# -- SelectiveChannel --------------------------------------------------------


class SelectiveChannel:
    """Replica-set chooser: each sub-channel is a schedulable unit; retries
    move to a different sub-channel (selective_channel.cpp). Like the
    reference — which wraps sub-channels in fake SocketIds and feeds them
    to an embedded LoadBalancer — the scheduler here IS a real LB from the
    registry (rr/random/wrr/la) over per-sub pseudo-endpoints reading
    through DoublyBufferedData snapshots, with latency/error feedback
    after every attempt, so ``lb_name="la"`` gives locality-aware replica
    selection across clusters.

    Health integrates the way the reference's fake Sockets do (a failed
    sub-channel's SocketId is excluded by the LB until its health check
    revives it, selective_channel.cpp + the Socket health-check loop):
    ``health_check_fails`` consecutive transport-class failures take the
    sub OUT of the LB's candidate set; after an exponentially backed-off
    interval the sub is revived in place — the next real call is the
    probe (Socket revives in place the same way), success resets it,
    failure re-downs it with a doubled interval."""

    # errors that indict the REPLICA (transport/overload), not the request
    _HEALTH_ERRORS = frozenset(
        {
            ErrorCode.EFAILEDSOCKET,
            ErrorCode.EHOSTDOWN,
            ErrorCode.ERPCTIMEDOUT,
            ErrorCode.EOVERCROWDED,
            ErrorCode.ECLOSE,
        }
    )

    def __init__(
        self,
        max_retry: int = 3,
        lb_name: str = "rr",
        health_check_fails: int = 2,
        health_check_interval_s: float = 1.0,
    ):
        from incubator_brpc_tpu.lb import create_load_balancer

        self.max_retry = max_retry
        self.health_check_fails = health_check_fails
        self.health_check_interval_s = health_check_interval_s
        self._subs: List[Channel] = []
        self._eps: List[EndPoint] = []  # pseudo endpoint per sub-channel
        self._fail_streak: List[int] = []
        self._down_until: List[float] = []  # 0 = healthy
        self._backoff: List[float] = []
        self._lb = create_load_balancer(lb_name)
        self._lock = threading.Lock()

    def add_channel(self, channel: Channel) -> int:
        with self._lock:
            idx = len(self._subs)
            self._subs.append(channel)
            ep = EndPoint(ip="subchannel", port=idx)
            self._eps.append(ep)
            self._fail_streak.append(0)
            self._down_until.append(0.0)
            self._backoff.append(self.health_check_interval_s)
        self._lb.add_server(ep)
        return idx

    @property
    def channel_count(self) -> int:
        return len(self._subs)

    def _pick(self, excluded: set) -> Optional[int]:
        import time as _time

        now = _time.monotonic()
        with self._lock:
            excluded_eps = {self._eps[i] for i in excluded if i < len(self._eps)}
            # downed subs stay out of the candidate set until their
            # revive time — then they rejoin and the next call probes them
            for i, until in enumerate(self._down_until):
                if until > now:
                    excluded_eps.add(self._eps[i])
        ep = self._lb.select(excluded=excluded_eps)
        if ep is None and excluded_eps:
            # every replica is either excluded or down: rather than fail
            # the call outright, probe the least-recently-downed sub not
            # excluded by THIS call (the reference likewise degrades to
            # trying an unhealthy node when nothing healthy remains)
            with self._lock:
                candidates = [
                    (self._down_until[i], i)
                    for i in range(len(self._subs))
                    if i not in excluded
                ]
            if candidates:
                return min(candidates)[1]
        return ep.port if ep is not None else None

    def _feedback(
        self,
        index: int,
        latency_us: float,
        error_code: int,
        budget_starved: bool = False,
    ) -> None:
        """``budget_starved``: the attempt ran on the dregs of the shared
        per-call deadline (an earlier slow replica ate it); its timeout
        indicts the BUDGET, not this replica — feed the LB but leave the
        health streak alone."""
        import time as _time

        with self._lock:
            if index >= len(self._eps):
                return
            ep = self._eps[index]
            if error_code in self._HEALTH_ERRORS:
                if not (
                    budget_starved and error_code == ErrorCode.ERPCTIMEDOUT
                ):
                    self._fail_streak[index] += 1
                    if self._fail_streak[index] >= self.health_check_fails:
                        # down: excluded from _pick until the backed-off
                        # revive time, then probed in place
                        self._down_until[index] = (
                            _time.monotonic() + self._backoff[index]
                        )
                        self._backoff[index] = min(
                            self._backoff[index] * 2, 30.0
                        )
            else:
                # a completed response — success OR application error —
                # proves the replica reachable: 'consecutive' means what
                # it says, so the streak resets and a downed replica whose
                # probe got through revives
                self._fail_streak[index] = 0
                self._down_until[index] = 0.0
                self._backoff[index] = self.health_check_interval_s
        self._lb.feedback(ep, latency_us, error_code)

    def health(self) -> List[dict]:
        """Introspection: per-sub health (mirrors /connections for subs)."""
        import time as _time

        now = _time.monotonic()
        with self._lock:
            return [
                {
                    "index": i,
                    "down": self._down_until[i] > now,
                    "fail_streak": self._fail_streak[i],
                    "revive_in_s": max(0.0, self._down_until[i] - now),
                }
                for i in range(len(self._subs))
            ]

    def call_method(
        self,
        service: str,
        method: str,
        request: bytes,
        cntl: Optional[Controller] = None,
        done: Optional[Callable[[Controller], None]] = None,
        attachment: bytes = b"",
    ) -> Controller:
        if cntl is None:
            cntl = Controller(max_retry=self.max_retry)
        if not self._subs:
            cntl.set_failed(ErrorCode.EINVAL, "SelectiveChannel has no sub channels")
            if done:
                done(cntl)
            return cntl
        if done is not None:
            # honor the async contract: the retry loop joins sub-calls, so it
            # runs on a worker fiber and the caller returns immediately
            from incubator_brpc_tpu.runtime.worker_pool import global_worker_pool

            global_worker_pool().spawn(
                self._call_blocking, service, method, request, cntl, done,
                attachment,
            )
            return cntl
        return self._call_blocking(service, method, request, cntl, None, attachment)

    def _call_blocking(
        self,
        service: str,
        method: str,
        request: bytes,
        cntl: Controller,
        done: Optional[Callable[[Controller], None]],
        attachment: bytes = b"",
    ) -> Controller:
        import time as _time

        excluded: set = set()
        # the per-call retry knob wins (Controller.max_retry, as Channel
        # honors it); the whole call shares ONE deadline — each attempt gets
        # the remaining budget, not a fresh timeout (controller.cpp deadline)
        attempts = 1 + max(0, cntl.max_retry)
        deadline = None
        if cntl.timeout_ms is not None and cntl.timeout_ms > 0:
            deadline = _time.monotonic() + cntl.timeout_ms / 1000.0
        last: Optional[Controller] = None
        for attempt_no in range(attempts):
            remaining_ms = cntl.timeout_ms
            if deadline is not None:
                remaining_ms = (deadline - _time.monotonic()) * 1000.0
                if remaining_ms <= 0:
                    if last is None:
                        cntl.set_failed(
                            ErrorCode.ERPCTIMEDOUT, berror(ErrorCode.ERPCTIMEDOUT)
                        )
                        if done:
                            done(cntl)
                        return cntl
                    break
            i = self._pick(excluded)
            if i is None:
                break
            sub = self._subs[i]
            sc = Controller(
                timeout_ms=remaining_ms,
                max_retry=0,  # retry here moves channels, not servers
                backup_request_ms=cntl.backup_request_ms,
            )
            sc.compress_type = cntl.compress_type
            sc.log_id = cntl.log_id
            sub.call_method(service, method, request, cntl=sc, attachment=attachment)
            last = sc
            # only a LATER attempt can be budget-starved: the first one
            # had the whole deadline, so its timeout indicts the replica
            starved = (
                attempt_no > 0
                and cntl.timeout_ms is not None
                and cntl.timeout_ms > 0
                and remaining_ms is not None
                and remaining_ms < max(50.0, 0.2 * cntl.timeout_ms)
            )
            self._feedback(
                i, sc.latency_us, sc.error_code, budget_starved=starved
            )
            if sc.ok():
                cntl.response_payload = sc.response_payload
                cntl.response_attachment = sc.response_attachment
                cntl.remote_side = sc.remote_side
                if done:
                    done(cntl)
                return cntl
            excluded.add(i)
            if sc.error_code not in RETRIABLE and sc.error_code != ErrorCode.ERPCTIMEDOUT:
                break  # application error: switching replicas won't help
        if last is not None:
            cntl.set_failed(last.error_code, f"all replicas failed: {last.error_text}")
        else:
            cntl.set_failed(ErrorCode.EINTERNAL, "no selectable sub channel")
        if done:
            done(cntl)
        return cntl

    call = call_method


# -- PartitionChannel --------------------------------------------------------


class PartitionParser:
    """Parse a server tag into (partition_index, partition_count) or None if
    the tag doesn't belong to this scheme (partition_channel.h:44-50 parses
    "N/M")."""

    def parse(self, tag: str) -> Optional[Tuple[int, int]]:
        try:
            n, m = tag.split("/", 1)
            idx, cnt = int(n), int(m)
        except (ValueError, AttributeError):
            return None
        if 0 <= idx < cnt:
            return idx, cnt
        return None


def _build_partition_channels(
    ns_thread,
    parser: "PartitionParser",
    partition_count: int,
    lb_name: str,
    options: Optional[ChannelOptions],
):
    """Per-partition filtered LB views over ONE shared naming watcher
    (partition_channel.cpp builds sub-channels the same way) — shared by
    PartitionChannel and DynamicPartitionChannel so the construction (and
    its error handling) cannot drift. Returns (channels, lbs) or None if a
    sub-channel failed to init. The client socket map carries the response
    messenger."""
    from incubator_brpc_tpu.lb import LoadBalancerWithNaming
    from incubator_brpc_tpu.rpc.channel import _client_socket_map

    # sub-channel sockets must honor the caller's TLS config — the LB dials
    # main sockets itself, so the context + the ssl-partitioned key tag
    # have to reach it here (a Channel.init target gets this from
    # _conn_kwargs/_auth_key_tag)
    conn_kwargs: dict = {}
    key_tag = ""
    if options is not None and options.ssl_context is not None:
        conn_kwargs = {
            "ssl_context": options.ssl_context,
            "ssl_server_hostname": options.ssl_server_hostname,
        }
        key_tag = f"|ssl-{id(options.ssl_context):x}"

    channels, lbs = [], []
    for part in range(partition_count):
        def _filter(ep, _part=part):
            return parser.parse(getattr(ep, "tag", "") or "") == (
                _part,
                partition_count,
            )

        lb = LoadBalancerWithNaming(
            lb_name=lb_name,
            socket_map=_client_socket_map,
            ns_thread=ns_thread,
            server_filter=_filter,
            key_tag=key_tag,
            conn_kwargs=conn_kwargs,
        )
        ch = Channel()
        if not ch.init_with_lb(lb, options=options):
            return None
        channels.append(ch)
        lbs.append(lb)
    return channels, lbs


class PartitionChannel(ParallelChannel):
    """One naming service, M partitions, one sub-channel per partition
    (partition_channel.cpp). Servers publish tags ("0/3", "1/3", ...) next
    to their address in the naming source; each sub-channel only sees its
    partition's servers."""

    def __init__(self, fail_limit: int = -1):
        super().__init__(fail_limit=fail_limit)
        self.partition_count = 0
        self._ns_thread = None

    def init(
        self,
        naming_url: str,
        partition_count: int,
        lb_name: str = "rr",
        parser: Optional[PartitionParser] = None,
        options: Optional[ChannelOptions] = None,
        call_mapper: Optional[CallMapper] = None,
        response_merger: Optional[ResponseMerger] = None,
    ) -> bool:
        from incubator_brpc_tpu.naming import NamingServiceThread

        parser = parser or PartitionParser()
        self.partition_count = partition_count
        self._ns_thread = NamingServiceThread(naming_url)
        if not self._ns_thread.start():
            return False
        built = _build_partition_channels(
            self._ns_thread, parser, partition_count, lb_name, options
        )
        if built is None:
            return False
        for ch in built[0]:
            self.add_channel(ch, call_mapper, response_merger)
        return True

    def stop(self) -> None:
        if self._ns_thread is not None:
            self._ns_thread.stop()




class DynamicPartitionChannel:
    """Mixed partitioning schemes behind one naming service, traffic
    weighted by per-scheme capacity (reference partition_channel.h:134 +
    policy/dynpart_load_balancer.cpp: servers tagged "0/3" and "0/4"
    coexist while a fleet re-partitions; each call picks ONE scheme with
    probability proportional to live-servers/partition-count — full replica
    sets attract more traffic — then fans out across that scheme's
    partitions like an ordinary PartitionChannel)."""

    def __init__(self, fail_limit: int = -1):
        self.fail_limit = fail_limit
        self._ns_thread = None
        self._parser: Optional[PartitionParser] = None
        self._lb_name = "rr"
        self._options: Optional[ChannelOptions] = None
        self._lock = threading.Lock()
        # scheme M -> (ParallelChannel, [per-partition LBs for weighting])
        self._schemes = {}
        self._rng_state = 0x9E3779B97F4A7C15

    def init(
        self,
        naming_url: str,
        lb_name: str = "rr",
        parser: Optional[PartitionParser] = None,
        options: Optional[ChannelOptions] = None,
    ) -> bool:
        from incubator_brpc_tpu.naming import NamingServiceThread

        self._parser = parser or PartitionParser()
        self._lb_name = lb_name
        self._options = options
        self._ns_thread = NamingServiceThread(naming_url)
        if not self._ns_thread.start():
            return False
        # observe to DISCOVER schemes; the per-partition filtered LBs do
        # their own add/remove through the same thread
        self._ns_thread.add_observer(self)
        return True

    def stop(self) -> None:
        if self._ns_thread is not None:
            # detach before stop — observer symmetry with init(): were the
            # watcher ever shared, a stopped channel must not keep
            # receiving (and acting on) scheme churn
            self._ns_thread.remove_observer(self)
            self._ns_thread.stop()

    # NamingServiceThread observer: build a scheme on first sighting
    def add_server(self, ep) -> None:
        parsed = self._parser.parse(getattr(ep, "tag", "") or "")
        if parsed is None:
            return
        _, count = parsed
        # the whole check+build is under the lock: two concurrent observer
        # callbacks discovering the same scheme must not both build it (the
        # loser's LBs would stay registered on the naming thread forever).
        # No inversion risk: the naming thread never holds its own lock
        # while calling observers.
        with self._lock:
            if count in self._schemes:
                return
            built = _build_partition_channels(
                self._ns_thread, self._parser, count, self._lb_name, self._options
            )
            if built is None:
                logger.warning("scheme /%d failed to build; skipped", count)
                return
            channels, lbs = built
            pc = ParallelChannel(fail_limit=self.fail_limit)
            for ch in channels:
                pc.add_channel(ch)
            self._schemes[count] = (pc, lbs)

    def remove_server(self, ep) -> None:
        pass  # the filtered LBs see the removal themselves

    def _pick_scheme(self):
        with self._lock:
            schemes = list(self._schemes.values())
        weighted = []
        for pc, lbs in schemes:
            nservers = sum(len(lb.servers()) for lb in lbs)
            if nservers > 0:
                weighted.append((nservers / pc.channel_count, pc))
        if not weighted:
            return None
        # xorshift-weighted pick (no global random state)
        self._rng_state ^= (self._rng_state << 13) & 0xFFFFFFFFFFFFFFFF
        self._rng_state ^= self._rng_state >> 7
        self._rng_state ^= (self._rng_state << 17) & 0xFFFFFFFFFFFFFFFF
        total = sum(w for w, _ in weighted)
        x = (self._rng_state / 2**64) * total
        for w, pc in weighted:
            x -= w
            if x <= 0:
                return pc
        return weighted[-1][1]

    def call_method(
        self,
        service: str,
        method: str,
        request: bytes,
        cntl: Optional[Controller] = None,
        done: Optional[Callable[[Controller], None]] = None,
        attachment: bytes = b"",
        request_stream=None,
    ) -> Controller:
        pc = self._pick_scheme()
        if pc is None:
            if cntl is None:
                cntl = Controller()
            cntl.set_failed(ErrorCode.EINTERNAL, "no partitioning scheme has servers")
            if done:
                done(cntl)
            return cntl
        return pc.call_method(
            service, method, request, cntl=cntl, done=done,
            attachment=attachment, request_stream=request_stream,
        )

    call = call_method
