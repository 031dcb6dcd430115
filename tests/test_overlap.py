"""The compute/communication overlap scheduler (ISSUE 13, T3):
chunked collective steps + double-buffered sessions in
parallel/mc_dispatch.py, the fabricnet microbatch overlap schedule, and
the rpcz proof-of-overlap plane.

Gates encoded here (the acceptance criteria):

- every overlap schedule is BYTE-identical to the serialized one (and to
  the integer session model);
- ``chunks=1, double_buffer=False`` degenerates to the exact pre-overlap
  code path (observable: the chunk bvar never moves);
- a party death mid-step with half a step's chunks acked aborts cleanly
  and ``propose_with_recovery`` heals with the resume point at a STEP
  boundary — never a torn chunk;
- the per-step watchdog stamps per-chunk progress and an abort reason
  names step+chunk;
- an overlapped session's rpcz trace shows chunk collective spans
  time-overlapping the NEXT step's compute span — asserted numerically,
  not eyeballed.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from incubator_brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Server,
    ServerOptions,
    device_method,
)
from incubator_brpc_tpu.transport.mc_worker import (
    SESSION_WIDTH,
    _scale_psum_kernel,
    session_expected,
)


@pytest.fixture
def registered_chunkable():
    """("dsvc", "scale") registered CHUNK-SAFE in this process's registry
    (psum + elementwise scale treats every width slice alike and passes
    n through — the chunk-safety contract)."""
    from incubator_brpc_tpu.rpc.device_method import (
        DeviceMethod,
        lookup_device_method,
        register_device_method,
    )

    dm = DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH, chunkable=True)
    prev = lookup_device_method("dsvc", "scale")
    register_device_method("dsvc", "scale", dm)
    yield dm
    if prev is not None:
        register_device_method("dsvc", "scale", prev)


def _servers(n, chunkable=True, start_index=1, inline=True):
    servers = []
    for i in range(n):
        s = Server(
            ServerOptions(
                device_index=start_index + i,
                usercode_inline=inline,
                enable_collective_service=True,
                collective_max_concurrency=0,
            )
        )
        s.add_service(
            "dsvc",
            {"scale": device_method(
                _scale_psum_kernel, width=SESSION_WIDTH, chunkable=chunkable
            )},
        )
        assert s.start(0)
        servers.append(s)
    return servers


def _channels(servers):
    chans = []
    for s in servers:
        ch = Channel()
        assert ch.init(f"127.0.0.1:{s.port}")
        chans.append(ch)
    return chans


def _stop(servers):
    for s in servers:
        s.stop()
        s.join(timeout=5)


class TestChunkedSessions:
    """Chunked + double-buffered schedules vs the integer session model
    and the degenerate path."""

    @pytest.mark.parametrize(
        "chunks,double_buffer",
        [(1, False), (4, False), (4, True), (1, True), (8, True)],
    )
    def test_every_schedule_matches_integer_model(
        self, registered_chunkable, chunks, double_buffer
    ):
        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch

        servers = _servers(2)
        try:
            chans = _channels(servers)
            party_ids = [jax.devices()[1].id, jax.devices()[2].id]
            operands = [bytes(range(40)), bytes(range(100, 180))]
            out = propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=3, proposer_index=None, timeout_ms=60000,
                chunks=chunks, double_buffer=double_buffer,
            )
            assert out["final_steps"] == 3
            assert out["results"] == session_expected(operands, 3)
        finally:
            _stop(servers)

    def test_degenerate_path_is_the_pre_overlap_code(
        self, registered_chunkable
    ):
        """chunks=1 + double_buffer=False must run the exact unchunked
        chain: the chunk bvar (counted once per chunked session) stays
        untouched, while any chunked schedule moves it."""
        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import (
            dispatch_chunks,
            propose_dispatch,
        )

        servers = _servers(2)
        try:
            chans = _channels(servers)
            party_ids = [jax.devices()[1].id, jax.devices()[2].id]
            operands = [b"\x05" * 16, b"\x09" * 24]
            before = dispatch_chunks.get_value()
            propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=2, proposer_index=None, timeout_ms=60000,
            )
            assert dispatch_chunks.get_value() == before, (
                "the default schedule dispatched chunk sub-collectives"
            )
            propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=2, proposer_index=None, timeout_ms=60000,
                chunks=2,
            )
            # 2 parties x 2 steps x 2 chunks
            assert dispatch_chunks.get_value() == before + 8
        finally:
            _stop(servers)

    def test_overlap_ratio_gauge_reads(self, registered_chunkable):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            overlap_ratio_gauge,
        )

        assert 0.0 <= overlap_ratio_gauge.get_value() <= 1.0

    def test_proposer_rejects_unchunkable_kernel(self):
        """A method registered without chunkable=True cannot run chunked
        — the proposer validates against its own registry before any
        fan-out (a silently mis-chunked kernel would diverge, not
        fail)."""
        from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )

        register_device_method(
            "dsvc", "plain_scale",
            DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH),
        )
        # channels are never dialed: the validation rejects first
        with pytest.raises(ValueError, match="chunk"):
            propose_dispatch(
                [None], [0, 1], "dsvc", "plain_scale", [b"a", b"b"],
                steps=1, proposer_index=0, chunks=2,
            )

    def test_proposer_rejects_bad_chunk_geometry(
        self, registered_chunkable
    ):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            MAX_CHUNKS,
            propose_dispatch,
        )

        # channels are never dialed: the validation rejects first
        with pytest.raises(ValueError, match="divide"):
            propose_dispatch(
                [None], [0, 1], "dsvc", "scale", [b"a", b"b"],
                steps=1, proposer_index=0, chunks=3,  # 3 ∤ SESSION_WIDTH
            )
        with pytest.raises(ValueError, match="chunks"):
            propose_dispatch(
                [None], [0, 1], "dsvc", "scale", [b"a", b"b"],
                steps=1, proposer_index=0, chunks=MAX_CHUNKS + 1,
            )

    def test_party_without_chunkable_registration_rejects(self):
        """Chunk-safety is validated by EVERY party against its LOCAL
        registry, like the fingerprint: a server whose registration
        lacks the declaration cleanly rejects the run proposal before
        any lockstep entry (the fingerprint matches — chunkability is a
        capability, not part of the kernel's identity)."""
        import base64
        import json

        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import dispatch_rejects
        from incubator_brpc_tpu.rpc import Controller
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod
        from incubator_brpc_tpu.utils.status import ErrorCode

        servers = _servers(1, chunkable=False)
        try:
            (ch,) = _channels(servers)
            parties = [jax.devices()[1].id, jax.devices()[2].id]
            fp = DeviceMethod(
                _scale_psum_kernel, width=SESSION_WIDTH
            ).fingerprint()
            before = dispatch_rejects.get_value()
            run = {
                "parties": parties,
                "index": 0,
                "steps": 1,
                "width": SESSION_WIDTH,
                "service": "dsvc",
                "method": "scale",
                "fingerprint": fp,
                "operands": [
                    base64.b64encode(b"\x01" * 8).decode(),
                    base64.b64encode(b"\x02" * 8).decode(),
                ],
                "chunks": 2,
            }
            cntl = Controller(timeout_ms=30000)
            ch.call_method(
                "_tpu_transport", "collective_dispatch",
                json.dumps(run).encode(), cntl=cntl,
            )
            assert cntl.failed()
            assert cntl.error_code == ErrorCode.EREQUEST
            assert "chunkable" in cntl.error_text
            assert dispatch_rejects.get_value() == before + 1
        finally:
            _stop(servers)


class TestOverlapRpczProof:
    """The acceptance criterion: chunk collective spans of an overlapped
    session TIME-OVERLAP the next step's compute span — asserted on the
    sampled spans, with the serialized schedule as the control."""

    @pytest.fixture
    def rpcz_on(self, tuned_flags):
        tuned_flags("enable_rpcz", True)
        tuned_flags("rpcz_samples_per_second", 1_000_000)
        from incubator_brpc_tpu.builtin.rpcz import span_store

        yield span_store

    def _run_session(self, double_buffer, steps=4, pace_s=0.0):
        import jax

        from incubator_brpc_tpu.parallel import mc_dispatch

        servers = _servers(2)
        try:
            chans = _channels(servers)
            party_ids = [jax.devices()[1].id, jax.devices()[2].id]
            if pace_s:
                mc_dispatch.set_step_hook(
                    lambda s, i, c: time.sleep(pace_s)
                )
            out = mc_dispatch.propose_dispatch(
                chans, party_ids, "dsvc", "scale",
                [bytes(range(40)), bytes(range(100, 180))],
                steps=steps, proposer_index=None, timeout_ms=60000,
                chunks=4, double_buffer=double_buffer,
            )
            assert out["results"] == session_expected(
                [bytes(range(40)), bytes(range(100, 180))], steps
            )
        finally:
            mc_dispatch.set_step_hook(None)
            _stop(servers)

    @staticmethod
    def _session_spans(store):
        return [
            sp for sp in store.recent(limit=10000)
            if any(
                t.startswith(("chunk=", "compute step="))
                for _off, t in sp.annotations
            )
        ]

    def test_double_buffered_chunks_overlap_next_compute(
        self, registered_chunkable, rpcz_on
    ):
        from incubator_brpc_tpu.builtin.rpcz import (
            _CHUNK_ANN_RE,
            _COMPUTE_ANN_RE,
            overlap_report,
        )

        rpcz_on.clear()
        self._run_session(double_buffer=True)
        spans = self._session_spans(rpcz_on)
        assert spans, "no overlap-session spans sampled"

        # the numeric assertion: at least one chunk span of step k whose
        # [start, end] interval intersects the SAME party chain's step
        # k+1 compute span (chunk spans parent to their step's compute
        # span; step spans share a per-party session parent — cross-
        # party skew must not count as overlap)
        by_id = {sp.span_id: sp for sp in spans}
        computes = {}
        for sp in spans:
            for _off, t in sp.annotations:
                m = _COMPUTE_ANN_RE.match(t)
                if m:
                    computes[(sp.parent_span_id, int(m.group(1)))] = (
                        sp.start_real_us,
                        sp.start_real_us + sp.latency_us,
                    )
        overlapped = 0
        for sp in spans:
            for _off, t in sp.annotations:
                m = _CHUNK_ANN_RE.match(t)
                if not m:
                    continue
                step = int(m.group(3))
                parent = by_id.get(sp.parent_span_id)
                party = (
                    parent.parent_span_id if parent is not None else 0
                )
                cs, ce = (
                    sp.start_real_us, sp.start_real_us + sp.latency_us
                )
                nxt = computes.get((party, step + 1))
                if nxt and min(ce, nxt[1]) - max(cs, nxt[0]) > 0:
                    overlapped += 1
        assert overlapped > 0, (
            "no chunk collective span time-overlaps the next step's "
            "compute span — the schedule serialized"
        )
        # and the operator view agrees
        report = overlap_report(spans)
        assert report and report[-1].endswith("OVERLAPPED")

    def test_serialized_schedule_reads_serialized(
        self, registered_chunkable, rpcz_on
    ):
        """The control: with the per-step ack barrier, every chunk span
        closes before the next compute span begins — the report calls
        the regression out."""
        from incubator_brpc_tpu.builtin.rpcz import overlap_report

        rpcz_on.clear()
        self._run_session(double_buffer=False)
        spans = self._session_spans(rpcz_on)
        assert spans
        report = overlap_report(spans)
        assert report and report[-1].endswith("SERIALIZED")

    def test_overlap_report_unit(self):
        """Deterministic synthetic spans: one overlapped, one serialized
        — the report lines and verdict are exact."""
        from incubator_brpc_tpu.builtin.rpcz import Span, overlap_report

        def mk(start, lat, ann):
            sp = Span(start_real_us=start, latency_us=lat)
            sp.annotations.append((0.0, ann))
            return sp

        base = [
            mk(1000, 100, "compute step=0/2 chunks=2 schedule=double_buffer"),
            mk(1200, 100, "compute step=1/2 chunks=2 schedule=double_buffer"),
        ]
        overlapped = base + [
            # chunk of step 0 closing inside step 1's window
            mk(1050, 200, "chunk=0/2 step=0"),
        ]
        report = overlap_report(overlapped)
        assert any("overlapped" in line for line in report)
        assert report[-1].endswith("OVERLAPPED")
        serialized = base + [
            mk(1050, 100, "chunk=0/2 step=0"),  # closes at 1150 < 1200
        ]
        report = overlap_report(serialized)
        assert any("serialized" in line for line in report)
        assert report[-1].endswith("SERIALIZED")
        assert overlap_report([mk(0, 1, "plain annotation")]) == []

    def test_rpc_view_trace_tree_appends_overlap_report(
        self, registered_chunkable, rpcz_on
    ):
        """The operator pipe end to end: scrape a live server's /rpcz
        trace and the trace-tree rendering carries the verdict line."""
        from incubator_brpc_tpu.builtin.rpcz import overlap_report
        from tools.rpc_view import scrape_rpcz

        rpcz_on.clear()
        self._run_session(double_buffer=True)
        spans = self._session_spans(rpcz_on)
        trace_ids = {sp.trace_id for sp in spans}
        assert trace_ids
        srv = Server(ServerOptions())
        assert srv.start(0)
        try:
            tid = trace_ids.pop()
            scraped = scrape_rpcz(
                f"127.0.0.1:{srv.port}", trace_id=f"{tid:x}"
            )
            assert scraped, "live /rpcz scrape returned no spans"
            report = overlap_report(scraped)
            assert report, "scraped trace carries no chunk annotations"
        finally:
            srv.stop()
            srv.join(timeout=5)


class TestChunkedWatchdog:
    """The satellite fix: a chunked step is C progress stamps, and an
    abort reason names step+chunk — a stalled last chunk is attributed
    to ITS step, not misread as the next one hanging."""

    def test_watchdog_abort_names_step_and_chunk(
        self, registered_chunkable
    ):
        import jax

        from incubator_brpc_tpu.parallel import mc_dispatch

        servers = _servers(3)
        try:
            chans = _channels(servers)
            party_ids = [d.id for d in jax.devices()[1:4]]
            operands = [bytes([i + 1]) * 8 for i in range(3)]
            before = mc_dispatch.dispatch_aborts.get_value()

            STALL_S = 2.5

            def hook(step, idx, chunk):
                if idx == 1 and step == 2 and chunk == 1:
                    time.sleep(STALL_S)  # wedged inside step 2 chunk 1

            mc_dispatch.set_step_hook(hook)
            t0 = time.monotonic()
            with pytest.raises(mc_dispatch.SessionAborted) as exc:
                # the deadline must sit well under STALL_S (the watchdog,
                # not the session deadline, is what fires) but above a
                # loaded host's first-dispatch window — compile time
                # charges against step 0's budget, and a too-tight value
                # aborts at "step 0" before the seeded stall is reached
                mc_dispatch.propose_dispatch(
                    chans, party_ids, "dsvc", "scale", operands,
                    steps=30, proposer_index=None, timeout_ms=60000,
                    session_deadline_ms=30000, step_deadline_ms=600,
                    chunks=2, double_buffer=True,
                )
            elapsed = time.monotonic() - t0
            mc_dispatch.set_step_hook(None)
            # the watchdog (not the 30 s session deadline) fired, and
            # the blame names the torn step AND chunk
            assert elapsed < STALL_S + 4.0
            msg = str(exc.value)
            assert "step deadline" in msg
            assert "step 2 chunk 1/2" in msg, msg
            assert mc_dispatch.dispatch_aborts.get_value() > before
        finally:
            mc_dispatch.set_step_hook(None)
            _stop(servers)


class TestOverlapChaosDrill:
    """Party death mid-step with half the chunks acked: the session
    aborts cleanly and propose_with_recovery heals with the resume point
    at a STEP boundary — never a torn chunk."""

    DEADLINE_MS = 6000
    STEPS = 60

    def test_death_mid_chunked_step_heals_at_step_boundary(
        self, registered_chunkable, tuned_flags
    ):
        import jax

        from incubator_brpc_tpu.parallel import mc_dispatch

        if len(jax.devices()) < 5:
            pytest.skip("needs a 5+ device mesh (3 parties + spare)")
        # worker-pool servers (not inline): the resume barrier's census
        # RPCs must be servable while the party chains hold their
        # handler threads
        servers = _servers(4, inline=False)  # 3 parties + 1 spare
        channels = []
        try:
            for s in servers:
                ch = Channel()
                assert ch.init(
                    f"list://127.0.0.1:{s.port}", lb_name="rr",
                    options=ChannelOptions(max_retry=1, timeout_ms=10000),
                )
                channels.append(ch)
            party_ids = [d.id for d in jax.devices()[1:4]]
            spare_dev = jax.devices()[4].id
            operands = [bytes([i + 1]) * 8 for i in range(3)]

            # pace every CHUNK, and trigger the kill on PROGRESS (step
            # 12, mid-step at chunk 2 — half the step's chunks already
            # dispatched: a torn step), not wall time: jit compilation
            # of the chunked programs would otherwise eat a fixed timer
            # budget before any checkpoint exists
            kill_now = threading.Event()

            def hook(step, idx, chunk):
                if step >= 12 and chunk >= 2:
                    kill_now.set()
                time.sleep(0.008)

            def killer_body():
                if kill_now.wait(timeout=30):
                    servers[0].stop()
                    servers[0].join(timeout=3)

            mc_dispatch.set_step_hook(hook)
            killer = threading.Thread(target=killer_body, daemon=True)
            killer.start()
            try:
                out = mc_dispatch.propose_with_recovery(
                    channels[:3], party_ids, "dsvc", "scale", operands,
                    steps=self.STEPS, proposer_index=None,
                    timeout_ms=60000,
                    session_deadline_ms=self.DEADLINE_MS,
                    spares=[(channels[3], spare_dev)],
                    checkpoint_every=2,
                    chunks=4, double_buffer=True,
                )
            finally:
                kill_now.set()
                mc_dispatch.set_step_hook(None)
                killer.join(timeout=5)

            assert out["dead_party_ids"] == [party_ids[0]]
            assert out["replaced_party_ids"] == [spare_dev]
            # the resume point is a WHOLE checkpointed step — chunks
            # re-concat before entering the ring, so a torn chunk can
            # never be elected
            assert out["resumed_from"] is not None
            assert out["resumed_from"] > 0
            assert out["resumed_from"] % 2 == 0
            assert out["final_steps"] == self.STEPS
            want = session_expected(operands, self.STEPS)
            for i, (got, exp) in enumerate(zip(out["results"], want)):
                assert got == exp, f"slot {i} diverged after resume"
        finally:
            for ch in channels:
                if ch._lb is not None:
                    ch._lb.stop()
            _stop(servers)
