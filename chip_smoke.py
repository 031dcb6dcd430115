#!/usr/bin/env python3
"""chip_smoke.py — the transport=tpu path, once, on the chip.

Drives the fabric's main path through the entry points a user calls
(Server / Channel / DeviceEndpoint / DeviceLink / fabricnet /
``__graft_entry__``), at the sizes of upstream's payload sweep, and
checks what comes back by the repo's own means: bytes out == bytes in, a
numpy twin, an integer model, a plain reference. One process touches JAX and runs every in-process phase; the
parent that launched it stays off JAX so that, on a four-chip host, it
can afterwards hand one chip to each ``mc_worker`` process.

    python3 chip_smoke.py                    # needs a TPU; exits non-zero without
    python3 chip_smoke.py --rehearse-on-cpu  # tiny sizes on 8 virtual CPU devices;
                                             # proves nothing about the chip

Last line of stdout on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import faulthandler
import hashlib
import json
import os
import subprocess
import sys
import threading
import time
import traceback
from types import SimpleNamespace

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included
# One phase; past its cap a phase is hung, not slow. Cold, the slowest
# (fabricnet, three schedules) took 88 s on a v5e chip (PERF.md).
PHASE_CAP_S = 300.0
RESULT_TAG = "CHIP_SMOKE_RESULT "

# Sizes. REAL follows upstream's payload sweep (docs/cn/benchmark.md:94-110
# via BASELINE.md); a size is cut only where the time limit forces it, and
# each cut is listed in CUTS and printed.
REAL = SimpleNamespace(
    rpc_payloads=[64, 4 << 10, 32 << 10, 1 << 20, 32 << 20],
    rpc_callers=16,
    rpc_calls=8,
    rpc_burst_payload=256,
    echo_words=64 * 1024 * 1024,  # a 256 MiB frame: the fused step at a size no RPC reaches
    echo_iters=3,
    link_echo_bytes=1 << 20,
    link_slot_words=256 * 1024,
    link_window=8,
    link_chunk=1 << 20,
    link_total=64 << 20,
    # the streaming deployment's transfer (benchmark cell link_stream_ici)
    stream_total=32 << 20,
    stream_message=1 << 20,
    stream_window=2 << 20,
    stream_slot_words=16384,
    quant_floats=(1 << 20) // 4,  # one MAX_WIDTH session row
    fabricnet=dict(
        d_model=2048, d_ff=8192, d_expert=2048, experts_per_rank=2,
        layers_per_stage=4, batch=4, seq=1024,
    ),
    train_steps=3,
    # -- four chips ----------------------------------------------------------
    fused_width=4096,
    session_width=1 << 20,  # mc_dispatch.MAX_WIDTH
    session_steps=4,
    fabricnet4=dict(
        d_model=2048, d_ff=8192, d_expert=2048, experts_per_rank=2,
        layers_per_stage=1, batch=8, seq=1024,
    ),
    ring=dict(b=1, t=4096, h=8, d=128),
)
CUTS = [
    "fabricnet on the 4-device meshes: layers_per_stage 4 -> 1 (three "
    "meshes to compile inside the time limit; widths unchanged)",
]
REHEARSAL = SimpleNamespace(
    rpc_payloads=[64, 4 << 10, 32 << 10],
    rpc_callers=4,
    rpc_calls=2,
    rpc_burst_payload=256,
    echo_words=64 * 1024,
    echo_iters=2,
    link_echo_bytes=8 << 10,
    link_slot_words=1024,
    link_window=4,
    link_chunk=4 << 10,
    link_total=256 << 10,
    stream_total=64 << 10,
    stream_message=4 << 10,
    stream_window=8 << 10,
    stream_slot_words=1024,
    quant_floats=1024,
    fabricnet=dict(
        d_model=32, d_ff=64, d_expert=32, experts_per_rank=2,
        layers_per_stage=1, batch=4, seq=16,
    ),
    train_steps=2,
    fused_width=256,
    session_width=512,
    session_steps=2,
    fabricnet4=dict(
        d_model=16, d_ff=32, d_expert=16, experts_per_rank=2,
        layers_per_stage=1, batch=8, seq=16,
    ),
    ring=dict(b=1, t=32, h=2, d=16),
)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- phases (each returns a short string of facts; raises on failure) ---------


def phase_device_rpc(S) -> str:
    """Host RPC -> HBM -> fused step -> response, over the payload sweep."""
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.models.tensor_echo import TensorEchoService
    from incubator_brpc_tpu.rpc import Channel, Controller, Server
    from incubator_brpc_tpu.transport.device import DeviceEndpoint
    from incubator_brpc_tpu.utils.status import ErrorCode

    mask = 0xA5A5A5A5
    service = TensorEchoService()
    service.add_method(1, lambda words: words ^ jnp.uint32(mask))
    ep = DeviceEndpoint(service=service, window_size=16)
    server = Server()
    server.add_service(
        "tensor",
        {
            "echo": ep.server_handler(),
            "mask": ep.server_handler(method_id=1),
            "nosuch": ep.server_handler(method_id=7),  # never registered
        },
    )
    assert server.start(0)
    try:
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        rng = np.random.default_rng(22)

        def call(method, payload):
            return ch.call_method(
                "tensor", method, payload, cntl=Controller(timeout_ms=300000)
            )

        for n in S.rpc_payloads:
            payload = rng.bytes(n)
            c = call("echo", payload)
            assert c.ok(), f"{n} B echo failed: {c.error_text}"
            assert c.response_payload == payload, f"{n} B echo corrupt"
        payload = rng.bytes(4 << 10)
        c = call("mask", payload)
        assert c.ok(), c.error_text
        want = (np.frombuffer(payload, np.uint32) ^ np.uint32(mask)).tobytes()
        assert c.response_payload == want, "method 1 diverged from numpy"
        c = call("nosuch", payload)
        assert c.failed() and c.error_code == ErrorCode.ENOMETHOD, (
            c.error_code, c.error_text,
        )
        # every (batch, bucket) program the burst can form, compiled first
        ep.warm(S.rpc_burst_payload)
        bad = []

        def worker(seed):
            r = np.random.default_rng(seed)
            for _ in range(S.rpc_calls):
                p = r.bytes(S.rpc_burst_payload)
                c = call("echo", p)
                if c.failed() or c.response_payload != p:
                    bad.append((c.error_code, c.error_text))

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(S.rpc_callers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "burst hung"
        assert not bad, f"{len(bad)} burst calls failed: {bad[:3]}"
    finally:
        server.stop()
        server.join(timeout=10)
    return (
        f"payloads={S.rpc_payloads} echo byte-equal; method 1 == numpy; "
        f"method 7 -> ENOMETHOD; burst {S.rpc_callers}x{S.rpc_calls} "
        f"@{S.rpc_burst_payload}B after warm() ok; device={ep.device}"
    )


def phase_fused_echo(S) -> str:
    """The fused parse/verify/dispatch/respond step over one big frame."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.models.tensor_echo import make_echo_step
    from incubator_brpc_tpu.ops import framing

    step, request = make_echo_step(payload_words=S.echo_words)
    out = None
    for _ in range(S.echo_iters):
        out = step(request)
    jax.block_until_ready(out)
    header = np.asarray(out[: framing.HEADER_WORDS])
    same = bool(
        jnp.array_equal(
            out[framing.HEADER_WORDS :], request[framing.HEADER_WORDS :]
        )
    )
    assert out.shape == request.shape and out.dtype == jnp.uint32
    assert int(header[0]) == framing.MAGIC, hex(int(header[0]))
    assert int(header[1]) == S.echo_words
    assert int(header[2]) == framing.FLAG_RESPONSE
    assert int(header[7]) == 0, f"error code {int(header[7])}"
    assert same, "payload out != payload in"
    return (
        f"frame={(S.echo_words + framing.HEADER_WORDS) * 4} B x"
        f"{S.echo_iters} iterations, payload out == payload in"
    )


def _stream_link(link, S) -> int:
    """Window-saturated byte stream through one link; the sink hashes
    what arrives in arrival order, so a reordered or corrupted chunk
    changes the digest. Returns the slots the link dispatched."""
    import numpy as np

    from incubator_brpc_tpu.transport.device_link import DeviceSocket

    class Sink:
        def __init__(self):
            self.nbytes = 0
            self.digest = hashlib.blake2b()

        def process(self, sock):
            n = len(sock._read_buf)
            self.digest.update(sock._read_buf.to_bytes(n))
            sock._read_buf.popn(n)
            self.nbytes += n

    DeviceSocket(link, side=0, messenger=Sink())
    sink = Sink()
    DeviceSocket(link, side=1, messenger=sink)
    data = np.random.default_rng(5).bytes(S.link_total)
    try:
        for off in range(0, S.link_total, S.link_chunk):
            rc = link.send(0, data[off : off + S.link_chunk], timeout=120)
            assert rc == 0, f"link send rc={rc}"
        deadline = time.monotonic() + 300
        while sink.nbytes < S.link_total and time.monotonic() < deadline:
            time.sleep(0.002)
        assert sink.nbytes == S.link_total, (
            f"link delivered {sink.nbytes} of {S.link_total} B"
        )
        assert sink.digest.digest() == hashlib.blake2b(data).digest(), (
            "bytes out != bytes in"
        )
        return int(link._seq)
    finally:
        link.fail("smoke done")


def phase_link(S) -> str:
    """transport=tpu through its normal entry point, then the jitted
    on-device swap at the link geometry of ``S``."""
    import jax

    import __graft_entry__ as ge
    from incubator_brpc_tpu.transport.device_link import DeviceLink

    echo = ge.link_leg(os.urandom(S.link_echo_bytes))
    if echo["geometry"] == "host-swap":
        chosen = (
            f"Channel(transport='tpu') echo took the HOST SWAP on "
            f"{echo['devices']} (one shared device): not device work"
        )
    else:
        chosen = (
            f"Channel(transport='tpu') echo took {echo['geometry']} on "
            f"{echo['devices']}"
        )
    dev = jax.devices()[0]
    slots = {}
    for ack_mode in ("local", "wire"):
        link = DeviceLink(
            [dev, dev], slot_words=S.link_slot_words, window=S.link_window,
            host_loopback=False, ack_mode=ack_mode,
        )
        assert link.geometry == "device-swap", link.geometry
        slots[ack_mode] = _stream_link(link, S)
        assert slots[ack_mode] * S.link_slot_words * 4 >= S.link_total
    return (
        f"{chosen}; DeviceLink([dev, dev], host_loopback=False) device-swap: "
        f"{S.link_total} B in {S.link_chunk} B sends, slot_words="
        f"{S.link_slot_words} window={S.link_window}, in order, "
        f"slots local={slots['local']} wire={slots['wire']}"
    )


def phase_quantized_twins(S) -> str:
    """The exactness the quantized sessions rest on: the jitted
    quantize/dequantize (frexp, a mantissa compare, a power of two built
    from its exponent bits, the int4 shifts) must agree BITWISE with the
    numpy twin."""
    import jax
    import numpy as np

    from incubator_brpc_tpu.parallel import quantized as Q

    rng = np.random.default_rng(9)
    x = (
        rng.standard_normal(S.quant_floats)
        * np.exp2(rng.integers(-20, 20, S.quant_floats))
    ).astype(np.float32)
    x[: Q.DEFAULT_BLOCK] = 0.0  # an all-zero block
    x[Q.DEFAULT_BLOCK] = 127.0  # amax/qmax exactly a power of two
    for mode in ("int8", "int4"):
        q, e = jax.jit(
            lambda v, _m=mode: Q._jq_quantize(v[None, :], _m, Q.DEFAULT_BLOCK)
        )(x)
        nq, ne = Q.np_quantize(x, mode)
        assert np.asarray(e[0]).tobytes() == ne.tobytes(), f"{mode} exponents"
        assert np.asarray(q[0]).tobytes() == nq.tobytes(), f"{mode} values"
        back = jax.jit(
            lambda a, b, _m=mode: Q._jq_dequantize(a, b, _m, Q.DEFAULT_BLOCK)
        )(q, e)
        want = Q.np_dequantize(nq, ne, mode)
        assert np.asarray(back[0]).tobytes() == want.tobytes(), (
            f"{mode} dequantize"
        )
    return f"{S.quant_floats} floats, int8 and int4: device bytes == numpy twin"


def phase_fabricnet(S) -> str:
    """Train steps on a 1-device fabric mesh; the serialized and
    overlapped schedules must stay bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_brpc_tpu.models import fabricnet
    from incubator_brpc_tpu.parallel.mesh import make_fabric_mesh

    mesh = make_fabric_mesh(n_devices=1, devices=jax.devices()[:1])
    cfg = fabricnet.FabricNetConfig(
        microbatches=2, dtype=jnp.bfloat16, **S.fabricnet
    )
    fabricnet.validate_config(cfg, mesh)
    x, y = fabricnet.make_batch(cfg, mesh)
    losses = {}
    for schedule in ("fused", "serialized", "overlapped"):
        params = fabricnet.init_params(cfg, mesh)
        step = fabricnet.make_train_step(cfg, mesh, schedule=schedule)
        trace = []
        for _ in range(S.train_steps):
            params, loss = step(params, x, y)
            trace.append(np.asarray(loss))
        assert all(np.isfinite(l) for l in trace), (schedule, trace)
        losses[schedule] = np.stack(trace)
        del params
    assert (
        losses["serialized"].tobytes() == losses["overlapped"].tobytes()
    ), "overlapped schedule diverged from serialized"
    return (
        f"{S.fabricnet} bf16, {S.train_steps} steps x 3 schedules, losses "
        f"finite (fused {[float(l) for l in losses['fused']]}), "
        "serialized == overlapped bitwise"
    )


def phase_entry(S) -> str:
    import jax
    import numpy as np

    import __graft_entry__ as ge

    fn, args = ge.entry()
    out, echo = jax.jit(fn)(*args)
    jax.block_until_ready((out, echo))
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert int(np.asarray(echo)[7]) == 0
    return "__graft_entry__.entry() jitted once, finite, echo error code 0"


# -- four chips: one process driving four devices ------------------------------


def phase_link_ici(S) -> str:
    import __graft_entry__ as ge

    seen = []
    for ack_mode in ("local", "wire"):
        facts = ge.link_leg(
            os.urandom(S.link_echo_bytes), link_ack_mode=ack_mode
        )
        assert facts["geometry"] == "ppermute", facts
        assert facts["ack_mode"] == ack_mode, facts
        assert len(set(facts["devices"])) == 2, facts
        seen.append(f"{ack_mode}: {facts['slots']} slots")
    return (
        f"Channel(transport='tpu') {S.link_echo_bytes} B echo, ppermute "
        f"step between {facts['devices']}; {', '.join(seen)}"
    )


def phase_stream_ici(S) -> str:
    """StreamingRPC over the four-chip link: the streaming deployment's
    transfer (benchmark cell link_stream_ici) through stream_create and
    stream_accept, bytes, boundaries and the window compared in the leg."""
    import __graft_entry__ as ge
    import numpy as np

    data = np.random.default_rng(7).bytes(S.stream_total)
    facts = ge.stream_leg(
        data, S.stream_message, max_buf_size=S.stream_window,
        link_slot_words=S.stream_slot_words,
    )
    assert facts["geometry"] == "ppermute", facts
    assert len(set(facts["devices"])) == 2, facts
    return (
        f"{S.stream_total} B in {facts['messages']} messages of "
        f"{S.stream_message} B under a {S.stream_window} B window over "
        f"ppermute {facts['devices']}: bytes, boundaries and order kept, at "
        f"most {facts['ahead']} B ahead, {facts['slots']} slots, "
        f"{S.stream_total / facts['seconds'] / 1e9:.4f} GB/s"
    )


def phase_combo(S) -> str:
    import __graft_entry__ as ge

    request = os.urandom(S.fused_width - 96)
    fused = ge.fused_leg([1, 2, 3], request, width=S.fused_width)
    peers = ge.star_leg([1, 2, 3])
    return (
        f"ParallelChannel over device_method servers on devices 1-3: "
        f"collective_fused={fused['collective_fused']}, width "
        f"{S.fused_width}, {fused['bytes']} B == host fan-out; "
        f"PartitionChannel star peers={peers}"
    )


def phase_sessions(S) -> str:
    """In-process collective sessions over four parties: the proposer is
    party 0, three servers hold devices 1-3."""
    import jax
    import numpy as np

    from incubator_brpc_tpu.parallel import quantized as Q
    from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
    from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
    from incubator_brpc_tpu.rpc import (
        Channel,
        Server,
        ServerOptions,
        device_method,
    )
    from incubator_brpc_tpu.rpc.device_method import (
        DeviceMethod,
        register_device_method,
    )
    from incubator_brpc_tpu.transport.mc_worker import (
        _scale_psum_kernel,
        session_expected,
    )

    width, steps = S.session_width, S.session_steps
    party_ids = [d.id for d in jax.devices()[:4]]
    # the proposer validates against its LOCAL registry like every party
    register_device_method(
        "dsvc", "scale",
        DeviceMethod(_scale_psum_kernel, width=width, chunkable=True),
    )
    register_device_method("_collective", "pmean", _pmean_dm(width))
    servers = []
    try:
        for dev in (1, 2, 3):
            s = Server(
                ServerOptions(
                    device_index=dev,
                    usercode_inline=True,
                    enable_collective_service=True,
                )
            )
            s.add_service(
                "dsvc",
                {
                    "scale": device_method(
                        _scale_psum_kernel, width=width, chunkable=True
                    )
                },
            )
            assert s.start(0)
            servers.append(s)
        chans = []
        for s in servers:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{s.port}")
            chans.append(ch)
        rng = np.random.default_rng(3)
        operands = [rng.bytes(width - 8 * i) for i in range(4)]
        for sched in ({}, {"chunks": 4, "double_buffer": True}):
            out = propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=steps, proposer_index=0, timeout_ms=300000, **sched,
            )
            want = session_expected(operands, out["final_steps"], width=width)
            assert out["results"] == want, (
                f"session {sched or 'exact'} diverged from the integer model"
            )
        rows = [
            (rng.standard_normal(width // 4) * (1.0 + i)).astype(np.float32)
            for i in range(4)
        ]
        fops = [r.tobytes() for r in rows]
        exact = propose_dispatch(
            chans, party_ids, "_collective", "pmean", fops,
            steps=steps, proposer_index=0, timeout_ms=300000,
        )
        quant = propose_dispatch(
            chans, party_ids, "_collective", "pmean", fops,
            steps=steps, proposer_index=0, timeout_ms=300000,
            quantize="int8",
        )
        assert len(set(quant["results"])) == 1, "parties hold different bytes"
        got = np.frombuffer(quant["results"][0], np.float32)
        ref = np.frombuffer(exact["results"][0], np.float32)
        err = float(np.abs(got - ref).max())
        bound = Q.pmean_error_bound(rows, quant["final_steps"], "int8")
        assert err <= bound, (err, bound)
        model = Q.np_quantized_pmean(rows, quant["final_steps"], "int8")
        # XLA may re-associate the party sum: tolerance, not bytes
        assert np.allclose(got, model, atol=1e-5), float(
            np.abs(got - model).max()
        )
        ratio = quant["wire_bytes"] / exact["wire_bytes"]
    finally:
        for s in servers:
            s.stop()
            s.join(timeout=10)
    return (
        f"4 parties, width {width}, {steps} steps: dsvc.scale exact and "
        f"chunks=4+double_buffer == integer model; pmean int8 == numpy "
        f"model, all parties byte-identical, max err {err:.3g} <= bound "
        f"{bound:.3g}, wire ratio {ratio:.4f}"
    )


def phase_fabricnet4(S) -> str:
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as ge
    from incubator_brpc_tpu.parallel.mesh import covering_axis_sizes

    devices = jax.devices()[:4]
    seen = []
    for sizes in covering_axis_sizes(4):
        losses = ge.fabricnet_leg(
            devices, sizes, steps=2, dtype=jnp.bfloat16, **S.fabricnet4
        )
        live = {a: n for a, n in sizes.items() if n > 1}
        seen.append(f"{live} losses {[round(l, 4) for l in losses]}")
    return f"{S.fabricnet4} bf16, 2 train steps per mesh: " + "; ".join(seen)


def phase_ring_attention(S) -> str:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from incubator_brpc_tpu.models.ring_attention import (
        full_attention,
        make_ring_attention_step,
    )

    mesh = Mesh(np.array(jax.devices()[:4]), axis_names=("sp",))
    r = S.ring
    shape = (r["b"], r["t"], r["h"], r["d"])
    q, k, v = (
        jax.random.normal(key, shape, jax.numpy.float32)
        for key in jax.random.split(jax.random.key(0), 3)
    )
    # the comparison is of two summation orders: hold both to full f32
    # products, or bf16 passes on the MXU swamp the difference
    with jax.default_matmul_precision("highest"):
        step, place = make_ring_attention_step(mesh, causal=True)
        out = np.asarray(step(place(q), place(k), place(v)))
        want = np.asarray(full_attention(q, k, v, causal=True))
    err = float(np.abs(out - want).max())
    assert np.allclose(out, want, rtol=1e-4, atol=1e-4), err
    return f"sp=4 causal {shape} f32 vs full attention, max err {err:.3g}"


ONE_CHIP = [
    ("device_rpc", phase_device_rpc),
    ("fused_echo", phase_fused_echo),
    ("device_link", phase_link),
    ("quantized_twins", phase_quantized_twins),
    ("fabricnet", phase_fabricnet),
    ("graft_entry", phase_entry),
]
FOUR_CHIPS = [
    ("link_ici", phase_link_ici),
    ("stream_ici", phase_stream_ici),
    ("combo_collective", phase_combo),
    ("fabricnet_4dev", phase_fabricnet4),
    ("ring_attention", phase_ring_attention),
    ("sessions_4party", phase_sessions),
]


def run_phase(name: str, fn, S, t_end: float) -> bool:
    """One line per phase. A phase that outlives the budget dumps every
    thread's stack and exits the process: a hang is a failure, not a
    wait for the tool's own limit."""
    left = min(PHASE_CAP_S, t_end - time.monotonic())
    if left <= 5:
        say(f"PHASE {name} FAIL: no time left in the {BUDGET_S:.0f} s budget")
        return False
    faulthandler.dump_traceback_later(left, exit=True)
    t0 = time.monotonic()
    try:
        facts = fn(S)
    except BaseException:  # noqa: BLE001 — report, keep running the rest
        say(f"PHASE {name} FAIL after {time.monotonic() - t0:.1f} s")
        traceback.print_exc(file=sys.stdout)
        sys.stdout.flush()
        return False
    finally:
        faulthandler.cancel_dump_traceback_later()
    say(f"PHASE {name} PASS {time.monotonic() - t0:.1f} s: {facts}")
    return True


def build_native() -> None:
    """The native library from src/ as git has it: rebuilt here, so
    nothing an earlier run left under the ignored build path is used."""
    r = subprocess.run(
        ["make", "-B", "-C", os.path.join(REPO, "src")],
        capture_output=True, text=True, timeout=300,
    )
    if r.returncode != 0:
        raise SystemExit(f"native build failed:\n{r.stdout}\n{r.stderr}")
    from incubator_brpc_tpu import native

    if not native.NATIVE_AVAILABLE:
        raise SystemExit("libtbutil.so built but did not load")


def worker(rehearse: bool, t_end: float) -> int:
    """The one process that touches JAX."""
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(
        f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu_version} python {sys.version.split()[0]}"
    )
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    say(
        f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']}"
    )
    if rehearse:
        say(
            "REHEARSAL on the CPU at tiny sizes: checks control flow only "
            "and proves nothing about the chip"
        )
    elif device["platform"] != "tpu":
        say(f"chip_smoke needs a TPU; JAX found platform={dev.platform}")
        return 2
    S = REHEARSAL if rehearse else REAL
    if not rehearse:
        for cut in CUTS:
            say(f"CUT: {cut}")

    say(
        "compile cache: "
        + os.environ.get("JAX_COMPILATION_CACHE_DIR", "off (rehearsal)")
    )
    t0 = time.monotonic()
    build_native()
    say(f"native plane built from src/ and loaded ({time.monotonic() - t0:.1f} s)")

    results = {}
    for name, fn in ONE_CHIP:
        results[name] = run_phase(name, fn, S, t_end)
    if device["count"] >= 4:
        say(f"{device['count']} devices: running the four-chip phases")
        for name, fn in FOUR_CHIPS:
            results[name] = run_phase(name, fn, S, t_end)
    else:
        say(
            f"{device['count']} device(s): the four-chip phases "
            f"({', '.join(n for n, _ in FOUR_CHIPS)}, multi_controller) "
            "did not run"
        )
    failed = [n for n, ok in results.items() if not ok]
    say(
        RESULT_TAG
        + json.dumps({"device": device, "failed": failed, "ran": list(results)})
    )
    return 1 if failed else 0


def check_fabric_stats(fstats: dict, platform: str) -> None:
    """What the fabric client reported must be the deployment asked for:
    three live links, the session and the lowering run, and every link
    device on ``platform`` as the WORKERS found it. A group that formed
    on another platform is not a pass."""
    links = fstats["links"]
    assert len(links) == 3 and all(l["peer_ack"] > 0 for l in links)
    assert fstats["collective"] and fstats["mc_lowered"]
    found = {p for l in links for p in l["platforms"]}
    assert found == {platform}, (
        f"asked for one {platform} device per process, the workers found "
        f"{sorted(found)}: {[l['devices'] for l in links]}"
    )


def multi_controller(platform: str, t_end: float) -> bool:
    """The deployment the README names: ``link_controller="multi"``, one
    device per process. Runs in the LAUNCHER, which never touched JAX, so
    each mc_worker child can be handed its own chip."""
    from incubator_brpc_tpu.transport import mc_worker

    assert "jax" not in sys.modules, "the launcher must stay off JAX"
    t0 = time.monotonic()
    budget = max(30.0, min(PHASE_CAP_S, t_end - t0))
    try:
        fstats, _ = mc_worker.orchestrate_fabric(
            n_servers=3, platform=platform, timeout=budget,
            extra=(
                "--n-rpcs", "4", "--collective-steps", "8",
                "--mc-lowering-check",
            ),
        )
        check_fabric_stats(fstats, platform)
    except BaseException as e:  # noqa: BLE001
        say(f"PHASE multi_controller FAIL after {time.monotonic() - t0:.1f} s")
        say(str(e)[-6000:])
        return False
    say(
        f"PHASE multi_controller PASS {time.monotonic() - t0:.1f} s: 4 "
        f"processes, one device each, platforms as the workers report them "
        f"{[l['platforms'] for l in fstats['links']]}; PartitionChannel star "
        f"over 3 cross-process links "
        f"{[l['devices'] for l in fstats['links']]}, 4-party "
        f"pmean session {fstats['collective']}, ParallelChannel lowered "
        f"through the method plane {fstats['mc_lowered']}"
    )
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="tiny sizes on 8 virtual CPU devices; proves nothing about "
        "the chip",
    )
    ap.add_argument("--worker", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker is not None:
        return worker(args.rehearse_on_cpu, args.worker)

    # The launcher: stays off JAX, runs the worker as the one process
    # that holds the chip, relays its lines, and only when that process
    # has exited hands chips to the multi-controller children.
    t_end = time.monotonic() + BUDGET_S
    if not args.rehearse_on_cpu:
        # exported through the environment: the worker and, later, every
        # mc_worker child inherit the one decision
        from incubator_brpc_tpu.utils import compile_cache

        compile_cache.configure()
    env = dict(os.environ)
    if args.rehearse_on_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    argv = [sys.executable, os.path.abspath(__file__), "--worker", str(t_end)]
    if args.rehearse_on_cpu:
        argv.append("--rehearse-on-cpu")
    child = subprocess.Popen(
        argv, cwd=REPO, env=env, stdout=subprocess.PIPE, text=True
    )
    result, rc = None, 1
    try:
        for line in child.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if result is None:
        say(f"chip_smoke: the worker exited {rc} without a result")
        return rc or 1
    failed = list(result["failed"])
    if result["device"]["count"] >= 4:
        platform = "cpu" if args.rehearse_on_cpu else "tpu"
        if not multi_controller(platform, t_end):
            failed.append("multi_controller")
    say(f"phases run: {result['ran']}; failed: {failed or 'none'}")
    if failed:
        return 1
    if args.rehearse_on_cpu:
        say("rehearsal passed; this is not a chip result")
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": result["device"]}))
        return 0
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
