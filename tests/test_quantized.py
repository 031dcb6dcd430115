"""Quantized + topology-aware collectives (parallel/quantized.py + the
mc_dispatch scheduler extensions).

Three tiers:
- pure-numpy quantizer units (round-trip exactness, error bounds,
  chunk-split identity, fingerprint stability) — no devices needed;
- in-process sessions on the virtual 8-device mesh: the quantize= knob
  end to end (accept validation, wire accounting, bvars, overlap
  composition), against the exact session and the numpy model;
- topology-aware scheduling units (synthetic skewed link telemetry) and
  the DeviceLinkMap.link_profile() accessor over a real loopback link.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from incubator_brpc_tpu.parallel import quantized as Q

WIDTH = 256  # 64 floats = 2 default blocks — small enough to jit fast


def _rows(n, nfloats, seed=5, scale=3.0):
    rng = np.random.default_rng(seed)
    return [
        (rng.standard_normal(nfloats) * scale * (i + 1)).astype(np.float32)
        for i in range(n)
    ]


class TestQuantizerMath:
    """The numpy twin: the arithmetic contract everything else rides."""

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    @pytest.mark.parametrize("width,block", [(128, 32), (512, 32), (512, 64), (4096, 32), (256, 8)])
    def test_round_trip_error_inside_bound(self, mode, width, block):
        (x,) = _rows(1, width // 4)
        q, e = Q.np_quantize(x, mode, block)
        v = Q.np_dequantize(q, e, mode, block)
        bound = Q.pmean_error_bound([x], 1, mode, block)
        assert float(np.abs(v - x).max()) <= bound

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_round_trip_is_idempotent(self, mode):
        """dequantize∘quantize is a projection: applying it twice yields
        the identical BYTES — the property quantized checkpoint rings
        need for byte-identical resume (power-of-two scales make the
        scaling arithmetic exact)."""
        (x,) = _rows(1, WIDTH // 4, seed=9, scale=40.0)
        v1 = Q.np_dequantize(*Q.np_quantize(x, mode), mode)
        v2 = Q.np_dequantize(*Q.np_quantize(v1, mode), mode)
        assert v1.tobytes() == v2.tobytes()

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_zero_and_uniform_blocks(self, mode):
        x = np.zeros(64, np.float32)
        v = Q.np_dequantize(*Q.np_quantize(x, mode), mode)
        assert v.tobytes() == x.tobytes()
        x = np.full(64, 7.5, np.float32)
        v = Q.np_dequantize(*Q.np_quantize(x, mode), mode)
        assert float(np.abs(v - x).max()) <= Q.pmean_error_bound([x], 1, mode)

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_chunk_split_identity(self, mode):
        """Block-aligned chunking is exact: quantizing each chunk
        separately produces the same dequantized bytes as slicing the
        full-width quantization — the chunk-safety declaration the
        overlap scheduler relies on."""
        (x,) = _rows(1, 128, seed=3)
        block = 32
        full = Q.np_dequantize(*Q.np_quantize(x, mode, block), mode, block)
        for chunks in (2, 4):
            cw = 128 // chunks
            assert cw % block == 0
            parts = [
                Q.np_dequantize(
                    *Q.np_quantize(x[j * cw:(j + 1) * cw], mode, block),
                    mode, block,
                )
                for j in range(chunks)
            ]
            assert np.concatenate(parts).tobytes() == full.tobytes()

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_jax_twin_agrees_bitwise_over_the_exponent_range(self, mode):
        """The jitted quantizer and the numpy twin produce the same BYTES
        for magnitudes across the float32 range, for an all-zero block,
        and for blocks whose amax is exactly qmax·2^k (the boundary the
        exponent rule turns on) — and the exponent is, exactly, the
        smallest e with amax <= qmax·2^e.  The arithmetic is built to be
        exact on any backend: no division, no exp2()."""
        import jax

        rng = np.random.default_rng(21)
        n, block, qmax = 4096, Q.DEFAULT_BLOCK, Q.qmax_for(mode)
        x = (
            rng.standard_normal(n) * np.exp2(rng.integers(-100, 100, n))
        ).astype(np.float32)
        x[:block] = 0.0
        for j, k in enumerate((-90, -1, 0, 5, 60), start=1):
            x[j * block:(j + 1) * block] = 0.0
            x[j * block] = np.float32(qmax) * np.float32(2.0) ** k
        q, e = jax.jit(
            lambda v: Q._jq_quantize(v[None, :], mode, block)
        )(x)
        nq, ne = Q.np_quantize(x, mode, block)
        assert np.asarray(e[0]).tobytes() == ne.tobytes()
        assert np.asarray(q[0]).tobytes() == nq.tobytes()
        back = jax.jit(
            lambda a, b: Q._jq_dequantize(a, b, mode, block)
        )(q, e)
        assert (
            np.asarray(back[0]).tobytes()
            == Q.np_dequantize(nq, ne, mode, block).tobytes()
        )
        # float64 holds amax and qmax·2^e exactly: the rule, checked
        amax = np.abs(x.reshape(-1, block)).max(axis=1).astype(np.float64)
        ef = ne.astype(np.float64)
        live = amax > 0
        assert (amax <= qmax * np.exp2(ef))[live].all()
        assert (amax > qmax * np.exp2(ef - 1))[live].all()

    def test_wire_bytes_and_support(self):
        assert Q.wire_bytes(512, "none") == 512
        assert Q.wire_bytes(512, "int8") == 128 + 4  # values + exponents
        assert Q.wire_bytes(512, "int4") == 64 + 4
        assert Q.wire_bytes(512, "int8") / 512 < 0.3
        assert Q.wire_bytes(512, "int4") / 512 < 0.15
        assert not Q.supports(100, "int8")   # 25 floats: no whole block
        assert not Q.supports(514, "int8")   # not float32-aligned
        assert not Q.supports(512, "int4", block=31)  # odd int4 block
        with pytest.raises(ValueError):
            Q.wire_bytes(100, "int8")

    def test_quantized_pmean_model_error_bound(self):
        rows = _rows(3, 64, seed=12)
        for mode in ("int8", "int4"):
            exact = np.mean(np.stack(rows), axis=0, dtype=np.float32)
            for steps in (1, 4):
                got = Q.np_quantized_pmean(rows, steps, mode)
                bound = Q.pmean_error_bound(rows, steps, mode)
                assert float(np.abs(got - exact).max()) <= bound


class TestVariantRegistry:
    """DeviceMethod variants: fingerprints, geometry, the quantized()
    resolution the session knob rides."""

    def test_fingerprint_stability_and_distinctness(self):
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod

        # two INDEPENDENT mints of the same parametrization (bypassing
        # the cache — what two separate processes do) agree
        a = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 32), width=WIDTH
        )
        b = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 32), width=WIDTH
        )
        assert a.fingerprint() == b.fingerprint()
        # mode, block and width all enter the identity
        c = DeviceMethod(
            Q._make_quantized_pmean_kernel("int4", 32), width=WIDTH
        )
        d = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 16), width=WIDTH
        )
        e = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 32), width=2 * WIDTH
        )
        fps = {x.fingerprint() for x in (a, c, d, e)}
        assert len(fps) == 4

    def test_quantized_resolution(self):
        from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm

        dm = _pmean_dm(WIDTH)
        assert dm.quantized("none") is dm
        assert dm.quantized("") is dm
        v8 = dm.quantized("int8")
        assert v8 is not None and v8.quant_mode == "int8"
        assert v8.chunkable and v8.chunk_align == 4 * Q.DEFAULT_BLOCK
        assert v8.wire_bytes() == Q.wire_bytes(WIDTH, "int8")
        assert v8.quantized("int8") is v8  # a variant resolves itself
        # unaligned width: no variant minted — the knob rejects cleanly
        odd = _pmean_dm(68)  # 17 floats: no whole default block
        assert odd.quantized("int8") is None

    def test_variant_cache_is_shared(self):
        assert Q.quantized_pmean_dm(WIDTH, "int8") is Q.quantized_pmean_dm(
            WIDTH, "int8"
        )


class TestQuantizedSessions:
    """The quantize= knob end to end on the virtual mesh."""

    @pytest.fixture
    def pmean_registered(self):
        from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
        from incubator_brpc_tpu.rpc.device_method import (
            lookup_device_method,
            register_device_method,
            unregister_device_method,
        )

        dm = _pmean_dm(WIDTH)
        prev = lookup_device_method("_collective", "pmean")
        register_device_method("_collective", "pmean", dm)
        yield dm
        # restore EXACTLY: a leaked registration would shadow the
        # width-minting pmean resolver for every other suite
        if prev is not None:
            register_device_method("_collective", "pmean", prev)
        else:
            unregister_device_method("_collective", "pmean")

    def _run(self, dm, rows, steps, **kw):
        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import (
            run_dispatch_session,
        )

        party_ids = [d.id for d in jax.devices()[:3]]
        ops = [r.tobytes() for r in rows]
        row, n, _ = run_dispatch_session(
            party_ids, 0, dm, ops, steps,
            service="_collective", method="pmean", **kw,
        )
        return np.frombuffer(
            bytes(np.asarray(row[:n], np.uint8)), np.float32
        )

    @pytest.mark.parametrize("mode", ["int8", "int4"])
    def test_session_matches_model_and_bound(self, pmean_registered, mode):
        rows = _rows(3, WIDTH // 4, seed=7)
        steps = 2
        exact = self._run(pmean_registered, rows, steps)
        got = self._run(pmean_registered, rows, steps, quantize=mode)
        bound = Q.pmean_error_bound(rows, steps, mode)
        assert float(np.abs(got - exact).max()) <= bound
        model = Q.np_quantized_pmean(rows, steps, mode)
        # XLA may re-associate the party sum: tolerance, not bytes
        assert np.allclose(got, model, atol=1e-5)

    def test_determinism_across_repeat_runs(self, pmean_registered):
        """The quantized chain is bit-deterministic run to run — the
        property resume byte-identity (and every party computing the
        identical mean) rides on."""
        rows = _rows(3, WIDTH // 4, seed=8)
        a = self._run(pmean_registered, rows, 2, quantize="int8")
        b = self._run(pmean_registered, rows, 2, quantize="int8")
        assert a.tobytes() == b.tobytes()

    def test_all_parties_converge_to_identical_bytes(self, pmean_registered):
        """Determinism ACROSS PARTIES: after step 1 of a quantized pmean
        every party holds the same mean, and because the quantized
        arithmetic is deterministic (round-half-even, power-of-two
        scales, one shared jitted program) their final rows are
        byte-identical — the property the lockstep contract needs."""
        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
        from incubator_brpc_tpu.rpc import Channel, Server, ServerOptions

        servers = []
        for i in range(2):
            s = Server(
                ServerOptions(
                    device_index=i + 1,
                    usercode_inline=True,
                    enable_collective_service=True,
                    collective_max_concurrency=0,
                )
            )
            assert s.start(0)
            servers.append(s)
        try:
            chans = []
            for s in servers:
                ch = Channel()
                assert ch.init(f"127.0.0.1:{s.port}")
                chans.append(ch)
            party_ids = [jax.devices()[1].id, jax.devices()[2].id]
            rows = _rows(2, WIDTH // 4, seed=17)
            out = propose_dispatch(
                chans, party_ids, "_collective", "pmean",
                [r.tobytes() for r in rows],
                steps=2, proposer_index=None, timeout_ms=60000,
                quantize="int8",
            )
            assert out["results"][0] == out["results"][1]
            assert out["quantize"] == "int8"
            assert out["wire_bytes"] == Q.wire_bytes(WIDTH, "int8") * 2 * 2
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)

    def test_overlap_composes_byte_identically(self, pmean_registered):
        """chunks>1 + double_buffer + quantize: the overlap schedule and
        any chunk_order permutation leave the bytes unchanged."""
        rows = _rows(3, WIDTH // 4, seed=10)
        base = self._run(pmean_registered, rows, 2, quantize="int8")
        chunked = self._run(
            pmean_registered, rows, 2, quantize="int8",
            chunks=2, double_buffer=True,
        )
        assert chunked.tobytes() == base.tobytes()
        routed = self._run(
            pmean_registered, rows, 2, quantize="int8",
            chunks=2, double_buffer=True, chunk_order=[1, 0],
        )
        assert routed.tobytes() == base.tobytes()

    def test_misaligned_chunks_reject_pre_lockstep(self, pmean_registered):
        """A chunk split that would cut a scale block in half is refused
        at admission (chunk_align), before any dispatch."""
        from incubator_brpc_tpu.parallel.mc_dispatch import _validate_chunks

        v8 = pmean_registered.quantized("int8")
        # WIDTH=256 -> 2 blocks of 32 floats; chunks=4 would cut blocks
        with pytest.raises(ValueError, match="block alignment"):
            _validate_chunks(v8, 4, "_collective", "pmean")

    def test_misdeclared_nonchunkable_variant_rejects(self):
        """A quantized variant registered WITHOUT the chunk-safety
        declaration rejects a chunked session cleanly pre-lockstep —
        at the proposer seam and at the handler seam alike."""
        from incubator_brpc_tpu.parallel.mc_dispatch import _validate_chunks
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod

        base = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 32),
            width=WIDTH, chunkable=True,
        )
        bad = DeviceMethod(
            Q._make_quantized_pmean_kernel("int8", 32),
            width=WIDTH, chunkable=False,
        )
        bad.quant_mode = "int8"
        base.quant_variants["int8"] = bad
        with pytest.raises(ValueError, match="not registered chunkable"):
            _validate_chunks(base.quantized("int8"), 2, "svc", "m")

    def test_quantized_bvars_and_wire_accounting(self, pmean_registered):
        from incubator_brpc_tpu.parallel import mc_dispatch as M

        rows = _rows(3, WIDTH // 4, seed=13)
        q0 = M.dispatch_quantized_sessions.get_value()
        s0 = M.dispatch_bytes_saved.get_value()
        self._run(pmean_registered, rows, 2, quantize="int8")
        assert M.dispatch_quantized_sessions.get_value() == q0 + 1
        expect_saved = (WIDTH - Q.wire_bytes(WIDTH, "int8")) * 3 * 2
        assert M.dispatch_bytes_saved.get_value() - s0 == expect_saved

    def test_quantized_checkpoint_ring_shrinks_and_resumes(
        self, pmean_registered
    ):
        """The ring entry of a quantized session costs the WIRE bytes,
        not width float32 bytes — and a replay restored from it is
        byte-identical to the uninterrupted chain (idempotent
        round-trip)."""
        import jax

        from incubator_brpc_tpu.parallel import mc_dispatch as M

        rows = _rows(3, WIDTH // 4, seed=14)
        party_ids = [d.id for d in jax.devices()[:3]]
        ops = [r.tobytes() for r in rows]
        sid = "quantized-ring-unit"
        full = self._run(
            pmean_registered, rows, 4, quantize="int8",
            session_id=sid, checkpoint_every=2,
        )
        ring = M._checkpoint_lookup(sid, 0)
        assert ring is not None and ring.watermark() >= 2
        n_addr = 3  # single controller: every party shard is local
        assert ring.entry_bytes == n_addr * (
            Q.wire_bytes(WIDTH, "int8") + 4
        )
        entry = ring.get(2)
        assert entry is not None and isinstance(entry[0], M._QuantCk)
        # resume from step 2: replay only steps 3..4, byte-identical
        v8 = pmean_registered.quantized("int8")
        row, n, _ = M.run_dispatch_session(
            party_ids, 0, v8, ops, 4,
            service="_collective", method="pmean",
            session_id=sid, resume_from=2, checkpoint_every=2,
        )
        resumed = np.frombuffer(
            bytes(np.asarray(row[:n], np.uint8)), np.float32
        )
        assert resumed.tobytes() == full.tobytes()
        M.release_checkpoints(sid)

    def test_reshard_rows_dequantize_to_full_width(self, pmean_registered):
        """checkpoint_fetch of a quantized ring ships FULL-WIDTH rows:
        the reshard wire format never forks on representation."""
        from incubator_brpc_tpu.parallel import mc_dispatch as M

        rows = _rows(3, WIDTH // 4, seed=15)
        sid = "quantized-reshard-unit"
        self._run(
            pmean_registered, rows, 2, quantize="int8",
            session_id=sid, checkpoint_every=2,
        )
        fetched = M.checkpoint_fetch(sid, 2, [0, 1, 2])
        assert sorted(fetched) == [0, 1, 2]
        import base64

        for slot, info in fetched.items():
            raw = base64.b64decode(info["row"])
            assert len(raw) == WIDTH
            # the shipped row is the dequantized state: finite floats
            assert np.isfinite(np.frombuffer(raw, np.float32)).all()
        M.release_checkpoints(sid)


class TestQuantizedProposals:
    """The rpc-plane seams: accept validation and the session-uniform
    stamp."""

    @pytest.fixture
    def server_and_channel(self):
        from incubator_brpc_tpu.rpc import (
            Channel,
            Server,
            ServerOptions,
        )

        s = Server(
            ServerOptions(
                device_index=1,
                usercode_inline=True,
                enable_collective_service=True,
                collective_max_concurrency=0,
            )
        )
        assert s.start(0)
        ch = Channel()
        assert ch.init(f"127.0.0.1:{s.port}")
        yield s, ch
        s.stop()
        s.join(timeout=5)

    def _proposal(self, width, fingerprint, parties, **over):
        d = {
            "parties": parties,
            "index": 1,
            "steps": 2,
            "width": width,
            "service": "_collective",
            "method": "pmean",
            "fingerprint": fingerprint,
            "phase": "accept",
        }
        d.update(over)
        return json.dumps(d).encode()

    def test_accept_validates_quantized_fingerprint(self, server_and_channel):
        import jax

        from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
        from incubator_brpc_tpu.rpc import Controller
        from incubator_brpc_tpu.utils.status import ErrorCode

        _s, ch = server_and_channel
        parties = [d.id for d in jax.devices()[:3]]
        v8 = _pmean_dm(WIDTH).quantized("int8")

        ok = ch.call_method(
            "_tpu_transport", "collective_dispatch",
            self._proposal(WIDTH, v8.fingerprint(), parties, quantize="int8"),
            cntl=Controller(timeout_ms=30000),
        )
        assert ok.ok(), ok.error_text

        # the EXACT kernel's fingerprint under quantize=int8 is a
        # divergence: the party resolves the variant and must reject
        wrong = ch.call_method(
            "_tpu_transport", "collective_dispatch",
            self._proposal(
                WIDTH, _pmean_dm(WIDTH).fingerprint(), parties,
                quantize="int8",
            ),
            cntl=Controller(timeout_ms=30000),
        )
        assert wrong.failed()
        assert wrong.error_code == ErrorCode.EREQUEST
        assert "fingerprint mismatch" in wrong.error_text

        # a method with NO quantized variant: clean pre-lockstep reject
        from incubator_brpc_tpu.parallel.mc_collective import (
            _pmean_bytes_kernel,
        )
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )

        plain = DeviceMethod(_pmean_bytes_kernel, width=WIDTH)
        register_device_method("qsvc", "plain", plain)
        try:
            odd = ch.call_method(
                "_tpu_transport", "collective_dispatch",
                self._proposal(
                    WIDTH, plain.fingerprint(), parties, quantize="int8",
                    service="qsvc", method="plain",
                ),
                cntl=Controller(timeout_ms=30000),
            )
            assert odd.failed()
            assert "no int8 quantized variant" in odd.error_text
        finally:
            from incubator_brpc_tpu.rpc.device_method import (
                unregister_device_method,
            )

            unregister_device_method("qsvc", "plain")

        # unknown quantize mode
        bad = ch.call_method(
            "_tpu_transport", "collective_dispatch",
            self._proposal(
                WIDTH, v8.fingerprint(), parties, quantize="fp8"
            ),
            cntl=Controller(timeout_ms=30000),
        )
        assert bad.failed()
        assert "unknown quantize mode" in bad.error_text

    def test_bad_chunk_order_rejects(self, server_and_channel):
        import jax

        from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
        from incubator_brpc_tpu.rpc import Controller
        from incubator_brpc_tpu.utils.status import ErrorCode

        _s, ch = server_and_channel
        parties = [d.id for d in jax.devices()[:2]]
        v8 = _pmean_dm(WIDTH).quantized("int8")
        run = ch.call_method(
            "_tpu_transport", "collective_dispatch",
            self._proposal(
                WIDTH, v8.fingerprint(), parties, quantize="int8",
                phase=None, chunks=2, chunk_order=[0, 0],
                operands=["", ""],
            ),
            cntl=Controller(timeout_ms=30000),
        )
        assert run.failed()
        assert run.error_code == ErrorCode.EREQUEST
        assert "chunk_order" in run.error_text


class TestTopologySchedule:
    """TASP ordering: synthetic skewed telemetry in, audited order out."""

    def test_slowest_measured_link_first(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            schedule_session_order,
        )

        prof = {
            10: {"gbps": 0.1, "rtt_us": 900.0},   # slowest
            11: {"gbps": 5.0, "rtt_us": 10.0},    # fastest
            12: {"gbps": 1.0, "rtt_us": 80.0},
        }
        order, chunk_order, note = schedule_session_order(
            [11, 12, 10], prof, chunks=6
        )
        # slowest first: pid 10 (index 2), then pid 12 (1), then pid 11
        assert order == [2, 1, 0]
        # slice j is route-LABELED to party j % 3: slices labeled to
        # the slowest party (index 2) dispatch first
        assert chunk_order == [2, 5, 1, 4, 0, 3]
        assert "link_order=[2, 1, 0]" in note
        assert "profile_gbps" in note

    def test_rtt_breaks_bandwidth_ties(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            schedule_session_order,
        )

        prof = {
            20: {"gbps": 1.0, "rtt_us": 500.0},  # slower: higher rtt
            21: {"gbps": 1.0, "rtt_us": 5.0},
        }
        order, _c, _n = schedule_session_order([21, 20], prof)
        assert order == [1, 0]

    def test_unmeasured_parties_keep_mesh_order_at_tail(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            schedule_session_order,
        )

        prof = {31: {"gbps": 0.5, "rtt_us": 100.0}}
        order, _c, _n = schedule_session_order([30, 31, 32, 33], prof)
        assert order == [1, 0, 2, 3]

    def test_no_telemetry_is_mesh_order(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import (
            schedule_session_order,
        )

        order, chunk_order, note = schedule_session_order(
            [1, 2, 3], {}, chunks=4
        )
        assert order == [0, 1, 2]
        assert chunk_order == [0, 1, 2, 3]
        assert note == ""

    def test_propose_dispatch_orders_by_synthetic_profile(self):
        """The acceptance check: a session proposed under skewed link
        telemetry demonstrably fans out slowest-first and front-loads
        that party's chunk slices — visible in the result's audit
        fields (the same strings the rpcz span records)."""
        import jax

        from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
        from incubator_brpc_tpu.rpc import (
            Channel,
            Server,
            ServerOptions,
            device_method,
        )
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
            session_expected,
        )
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )

        register_device_method(
            "dsvc", "scale",
            DeviceMethod(
                _scale_psum_kernel, width=SESSION_WIDTH, chunkable=True
            ),
        )
        servers = []
        for i in range(2):
            s = Server(
                ServerOptions(
                    device_index=i + 1,
                    usercode_inline=True,
                    enable_collective_service=True,
                    collective_max_concurrency=0,
                )
            )
            s.add_service(
                "dsvc",
                {"scale": device_method(
                    _scale_psum_kernel, width=SESSION_WIDTH, chunkable=True
                )},
            )
            assert s.start(0)
            servers.append(s)
        try:
            chans = []
            for s in servers:
                ch = Channel()
                assert ch.init(f"127.0.0.1:{s.port}")
                chans.append(ch)
            party_ids = [jax.devices()[1].id, jax.devices()[2].id]
            # party 1 (second in mesh order) measures SLOWEST
            prof = {
                party_ids[0]: {"gbps": 4.0, "rtt_us": 10.0},
                party_ids[1]: {"gbps": 0.05, "rtt_us": 2000.0},
            }
            operands = [bytes(range(40)), bytes(range(80, 160))]
            out = propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=2, proposer_index=None, timeout_ms=60000,
                chunks=4, double_buffer=True, link_profile=prof,
            )
            assert out["results"] == session_expected(operands, 2)
            assert out["link_order"] == [1, 0]
            # slice j's owner is j % 2: party 1 owns slices 1 and 3
            assert out["chunk_order"] == [1, 3, 0, 2]
        finally:
            for s in servers:
                s.stop()
                s.join(timeout=5)


class TestLinkProfileAccessor:
    """DeviceLinkMap.link_profile(): the PR 1 recorders, structured."""

    def test_live_link_profile(self):
        from incubator_brpc_tpu.rpc import (
            Channel,
            ChannelOptions,
            Server,
            ServerOptions,
        )
        from incubator_brpc_tpu.transport import device_link as DL

        s = Server(ServerOptions(device_index=1))
        s.add_service("EchoService", {"Echo": lambda cntl, req: req})
        assert s.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{s.port}",
                options=ChannelOptions(transport="tpu", timeout_ms=60000),
            )
            for _ in range(3):
                c = ch.call_method("EchoService", "Echo", b"y" * 1500)
                assert c.ok(), c.error_text
            prof = DL.link_profile()
            assert prof, "no live link in the profile"
            peer_id = ch._device_sock.link.devices[1].id
            assert peer_id in prof
            entry = prof[peer_id]
            for key in (
                "rtt_us", "rtt_p99_us", "steps", "out_bytes_s",
                "in_bytes_s", "out_bytes", "in_bytes", "gbps", "link_id",
            ):
                assert key in entry
            assert entry["steps"] > 0
            assert entry["rtt_us"] > 0
            assert entry["out_bytes"] > 0 and entry["in_bytes"] > 0
        finally:
            s.stop()
            s.join(timeout=5)

    def test_rpc_view_links_table(self):
        """The scrape-side rendering groups per-link series into rows."""
        import sys

        sys.path.insert(0, "tools")
        from tools.rpc_view import links_table

        values = {
            'device_link_3_step_rtt_us{quantile="0.99"}': 450.0,
            "device_link_3_step_rtt_us_sum": 1000.0,
            "device_link_3_step_rtt_us_count": 10.0,
            "device_link_3_out_bytes_second": 2.0e6,
            "device_link_3_in_bytes_second": 1.0e6,
            "device_link_7_step_rtt_us_sum": 90.0,
            "device_link_7_step_rtt_us_count": 3.0,
            "unrelated_metric": 1.0,
        }
        rows = links_table(values)
        assert len(rows) == 2
        assert rows[0].startswith("device_link_3:")
        assert "rtt=100.0us" in rows[0]
        assert "p99=450.0us" in rows[0]
        assert "gbps=0.003000" in rows[0]
        assert rows[1].startswith("device_link_7:")
        assert "rtt=30.0us" in rows[1]
