"""Multi-process harness for the multi-controller device plane.

Runnable as ``python -m incubator_brpc_tpu.transport.mc_worker <role> ...``.
Process 0 is an RPC server (and the jax.distributed coordinator); the
last process is the client; each owns ONE local device and the N of them
form an N-device global mesh. Two shapes:

- the two-process PAIR (1 server + 1 client): one link, lockstep SPMD
  exchange (transport/mc_link.py) — the reference RDMA transport's
  deployment (/root/reference/src/brpc/rdma/rdma_endpoint.h:42-213,
  per-host init rdma_helper.cpp);
- the three-process FABRIC (2 servers + 1 fabric-client): a
  PartitionChannel fans one call out over TWO cross-process links — the
  client device holds a star of links, each a 2-device sub-mesh of the
  global group running its own lockstep schedule. The N-party star of
  the single-controller DeviceLinkMap, spanning real processes.

A worker takes its platform from its environment and never overrides it;
the LAUNCHER hands each child its device there (``child_env``):
``platform="cpu"`` (the default, what tests/test_mc_link.py and
``__graft_entry__.dryrun_multiprocess`` ask for) is a CPU-mesh harness —
one virtual CPU device per process, Gloo collectives;
``platform="tpu"`` gives each process one chip of the host. A launcher
of chip-owning children must itself stay off JAX: a parent that touched
the backend holds the chips its children need.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time


# -- the collective-method-plane test kernels ---------------------------------
#
# Module-level so every worker process minting a DeviceMethod from them
# resolves the SAME fingerprint (module.qualname + source + geometry) —
# the property the session accept phase validates. Integer arithmetic
# end-to-end, so results are bit-exact across planes and processes.

SESSION_WIDTH = 512


def _scale_psum_kernel(data, n):
    """psum + elementwise — a user kernel that actually exercises the
    party axis (axis name 'par', shared by the fused single-controller
    dispatch and the mc session plane)."""
    import jax.numpy as jnp
    from jax import lax

    x = data.astype(jnp.int32)
    s = lax.psum(x, "par")
    return ((3 * s + x) % 256).astype(jnp.uint8), n


def _scale_psum_kernel_wrong(data, n):
    """Same name, different body — the divergence the fingerprint check
    must reject before any party enters lockstep."""
    import jax.numpy as jnp
    from jax import lax

    x = data.astype(jnp.int32)
    s = lax.psum(x, "par")
    return ((5 * s + x) % 256).astype(jnp.uint8), n


def session_expected(operands, steps: int, width: int = SESSION_WIDTH):
    """Host-side model of the K-step _scale_psum_kernel chain: exact
    integer arithmetic, so every party's device result must match these
    bytes bit-for-bit."""
    import numpy as np

    rows, ns = [], []
    for op in operands:
        row = np.zeros(width, np.int64)
        row[: len(op)] = np.frombuffer(op, np.uint8)
        rows.append(row)
        ns.append(len(op))
    x = np.stack(rows)
    for _ in range(steps):
        s = x.sum(axis=0)
        x = (3 * s[None, :] + x) % 256
    return [bytes(x[i, : ns[i]].astype(np.uint8)) for i in range(len(rows))]


# How long the CPU runtime lets a collective wait for a participant.
# XLA's default is half an hour; a party that dies between dispatches
# leaves the survivors' next collective waiting exactly that long, and
# the healed session queues behind it on the same device. The session
# plane bounds how long the HOSTS wait (mc_dispatch._await_or_abort);
# this bounds how long the DEVICE stays parked.
CPU_COLLECTIVE_TIMEOUT_S = 20

# One chip per process on the chip machine: four processes over the whole
# 2x2 host, the only layout that formed a group there (PERF.md
# "Bring-up"): two-process groups over two of its chips did not.
_TPU_NPROCS = 4
_TPU_PROCESS_BOUNDS = "2,2,1"
_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def child_env(platform: str, proc_id: int, nprocs: int, tpu_ports=()) -> dict:
    """The environment that hands worker ``proc_id`` of ``nprocs`` its
    one device. ``tpu_ports``: one free port per process for libtpu's own
    mesh rendezvous (``platform="tpu"`` only)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        # replace, don't append: the parent may carry an 8-device flag
        # from tests/conftest.py and XLA keeps the first occurrence
        flags = re.sub(
            r"--xla_(force_host_platform_device_count|"
            r"cpu_collective_timeout_seconds)=\d+",
            "",
            env.get("XLA_FLAGS", ""),
        )
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=1 "
            f"--xla_cpu_collective_timeout_seconds={CPU_COLLECTIVE_TIMEOUT_S}"
        ).strip()
    elif platform == "tpu":
        if nprocs != _TPU_NPROCS:
            raise ValueError(
                f"no one-chip-per-process TPU layout for {nprocs} processes "
                f"(only {_TPU_NPROCS} formed a group)"
            )
        # tpu and nothing after it: with the variable unset (or a cpu
        # second choice) a failed libtpu start is logged and the worker
        # comes up on the CPU, where a four-process group forms just as
        # well.  Named alone, the failure raises.
        env["JAX_PLATFORMS"] = "tpu"
        env.update(
            TPU_VISIBLE_CHIPS=str(proc_id),
            TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_BOUNDS=_TPU_PROCESS_BOUNDS,
            TPU_PROCESS_ADDRESSES=",".join(
                f"localhost:{p}" for p in tpu_ports
            ),
            TPU_PROCESS_PORT=str(tpu_ports[proc_id]),
            CLOUD_TPU_TASK_ID=str(proc_id),
            ALLOW_MULTIPLE_LIBTPU_LOAD="1",
        )
    else:
        raise ValueError(f"unknown platform {platform!r}")
    return env


def _init_distributed(coord_port: int, process_id: int, nprocs: int = 2) -> None:
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{coord_port}",
        num_processes=nprocs,
        process_id=process_id,
    )
    assert len(jax.devices()) == nprocs, (
        f"expected a {nprocs}-device global mesh, got {jax.devices()}"
    )
    assert len(jax.local_devices()) == 1, jax.local_devices()
    # the launcher named the platform (child_env); a group on another one
    # is a different deployment, not this one degraded
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    found = jax.local_devices()[0].platform
    assert asked in ("", found), (
        f"launcher asked for platform {asked!r}, this worker came up on "
        f"{found!r}"
    )


def run_server(args) -> int:
    _init_distributed(args.coord_port, args.proc_id, args.nprocs)
    import threading

    from incubator_brpc_tpu.rpc import Server, ServerOptions

    # Exit is COORDINATED, not parent-driven: XLA's coordination service
    # runs a cluster-wide shutdown barrier at interpreter exit (jax's
    # atexit), so a worker that exits alone blocks in that barrier until
    # the peer exits too. The client tells us it is done (a plain TCP
    # RPC), we stop, and both processes reach the barrier together.
    quit_ev = threading.Event()

    def _quit(cntl, req: bytes) -> bytes:
        quit_ev.set()
        return b"bye"

    server = Server(ServerOptions(device_index=0))
    served = [0]

    def _echo(cntl, req: bytes) -> bytes:
        served[0] += 1
        if args.die_after_rpcs and served[0] > args.die_after_rpcs:
            # fault injection: the host vanishes mid-request — no
            # response, no close dance, no clean exit (os._exit skips
            # atexit, so not even the coordination service says goodbye)
            print("SERVER_DYING", flush=True)
            os._exit(42)
        return b"echo:" + req

    server.add_service("EchoService", {"Echo": _echo})
    pid = args.proc_id
    server.add_service(
        "part", {"get": lambda cntl, req: b"p%d:" % pid + req}
    )
    # a user-registered device method for the collective method plane:
    # sessions name ("dsvc", "scale") and every party fingerprint-checks
    # it; --wrong-kernel swaps the body to prove the mismatch reject
    from incubator_brpc_tpu.rpc import device_method as _device_method

    kernel = (
        _scale_psum_kernel_wrong if args.wrong_kernel else _scale_psum_kernel
    )
    server.add_service(
        "dsvc",
        # chunkable: psum + elementwise treats every width slice alike
        # and passes n through — chunked overlap sessions are admitted
        {"scale": _device_method(kernel, width=SESSION_WIDTH, chunkable=True)},
    )
    if args.chaos_kill_at_step >= 0:
        # the deterministic chaos drill: this party "dies" at EXACTLY
        # step K of its first session — the RPC server stops (conns
        # fail, so the proposer classifies a connectivity death) while
        # the PROCESS stays alive (the jax.distributed group and the
        # device plane survive, so the healed session can still run).
        # The local session is aborted too so this handler unwedges now,
        # not at its deadline.  Admin.Quit cannot reach a stopped server,
        # so the dead party heads for the exit barrier by itself and
        # waits there for the others.
        from incubator_brpc_tpu.parallel import mc_dispatch as _mcd

        chaos_fired = threading.Event()

        def _chaos_die() -> None:
            print("SERVER_DYING", flush=True)
            server.stop()
            _mcd.abort_sessions_for_owner(
                server, "chaos drill killed this party"
            )
            quit_ev.set()

        def _chaos_hook(step: int, own_index: int) -> None:
            if step >= args.chaos_kill_at_step and not chaos_fired.is_set():
                chaos_fired.set()
                threading.Thread(target=_chaos_die, daemon=True).start()
                # park until the stop lands so no further step of the
                # doomed chain dispatches past the kill point
                time.sleep(0.2)

        _mcd.set_step_hook(_chaos_hook)
    import jax

    # which global device this process owns: the runtime assigns it (on a
    # TPU host process i does not own device i), so clients ask
    own_dev = str(jax.local_devices()[0].id).encode()
    server.add_service(
        "Admin", {"Quit": _quit, "Device": lambda cntl, req: own_dev}
    )
    assert server.start(args.rpc_port)
    print(f"SERVER_READY port={server.port}", flush=True)
    # parent closing our stdin is the fallback exit path (client crashed)
    threading.Thread(
        target=lambda: (sys.stdin.read(), quit_ev.set()), daemon=True
    ).start()
    quit_ev.wait()
    server.stop()
    server.join(timeout=10)
    print("SERVER_DONE", flush=True)
    return 0


def run_client(args) -> int:
    _init_distributed(args.coord_port, args.proc_id, args.nprocs)
    from incubator_brpc_tpu.rpc import Channel, ChannelOptions, Controller

    ch = Channel()
    assert ch.init(
        f"127.0.0.1:{args.rpc_port}",
        options=ChannelOptions(
            transport="tpu",
            link_controller="multi",
            timeout_ms=60000,
            link_slot_words=args.slot_words,
            link_window=args.window,
        ),
    )
    # jax.distributed's init barrier ran, but the peer may not have bound
    # its RPC port yet — retry the first call until the server is up
    # (a refused bootstrap surfaces as a failed controller, not a raise)
    deadline = time.monotonic() + 60.0
    while True:
        first = ch.call_method(
            "EchoService", "Echo", b"hello",
            cntl=Controller(timeout_ms=60000),
        )
        if first.ok():
            break
        if time.monotonic() > deadline:
            print(f"CLIENT_FAIL connect: {first.error_text}", flush=True)
            return 1
        time.sleep(0.2)
    assert first.response_payload == b"echo:hello"

    if args.expect_peer_death:
        return _run_client_peer_death(args, ch)
    for i in range(args.n_rpcs):
        body = bytes((i + j) % 256 for j in range(args.payload))
        req = f"m{i}:".encode() + body
        cntl = ch.call_method(
            "EchoService", "Echo", req, cntl=Controller(timeout_ms=60000)
        )
        assert cntl.ok(), f"echo {i} failed: {cntl.error_text}"
        assert cntl.response_payload == b"echo:" + req, f"echo {i} corrupt"

    link = ch._device_sock.link
    stats = {
        "n_rpcs": args.n_rpcs,
        "payload": args.payload,
        "steps": int(link._seq),
        "peer_ack": int(link.peer_ack),
        "devices": [str(d) for d in link.devices],
        "platforms": [d.platform for d in link.devices],
        "window": link.window,
        "slot_words": link.slot_words,
    }
    # the cross-host drain signal must actually flow: the peer's
    # cumulative-delivered count rides slot words 3+5 back to us
    assert stats["peer_ack"] > 0, "wire acks never advanced"
    assert stats["steps"] >= args.n_rpcs, "fewer steps than RPCs?"
    # clean shutdown: the close dance agrees on a final step count, both
    # sides dispatch exactly that many, and the link quiesces
    ch._device_sock.recycle()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        with link._lock:
            done = link._closed and link._inflight == 0
        if done:
            break
        time.sleep(0.05)
    assert link._closed, "close dance did not finish"
    stats["final_target"] = link._final_target
    print("CLIENT_OK " + json.dumps(stats), flush=True)
    # release the peer so both processes reach the coordination service's
    # exit barrier together (see run_server)
    _quit_servers([args.rpc_port])
    return 0


def run_fabric_client(args) -> int:
    """Three-process fabric: this client holds TWO multi-controller links
    (one per server process) and a PartitionChannel splits each call
    across them — the N-party star over real processes."""
    _init_distributed(args.coord_port, args.proc_id, args.nprocs)
    from incubator_brpc_tpu.rpc import (
        Channel,
        ChannelOptions,
        Controller,
        PartitionChannel,
    )

    ports = [int(p) for p in args.rpc_ports.split(",")]
    n = len(ports)
    url = "list://" + ",".join(
        f"127.0.0.1:{p} {i}/{n}" for i, p in enumerate(ports)
    )
    pc = PartitionChannel()
    assert pc.init(
        url,
        partition_count=n,
        options=ChannelOptions(
            transport="tpu",
            link_controller="multi",
            timeout_ms=60000,
            link_slot_words=args.slot_words,
            link_window=args.window,
        ),
    )
    expected = b"".join(f"p{i}:X".encode() for i in range(n))
    deadline = time.monotonic() + 90.0
    while True:
        cntl = pc.call_method(
            "part", "get", b"X", cntl=Controller(timeout_ms=60000)
        )
        if cntl.ok() and cntl.response_payload == expected:
            break
        if time.monotonic() > deadline:
            print(f"CLIENT_FAIL fabric: {cntl.error_text}", flush=True)
            return 1
        time.sleep(0.3)
    for i in range(args.n_rpcs):
        body = b"%04d" % i
        cntl = pc.call_method(
            "part", "get", body, cntl=Controller(timeout_ms=60000)
        )
        assert cntl.ok(), f"fabric rpc {i}: {cntl.error_text}"
        want = b"".join(b"p%d:" % j + body for j in range(n))
        assert cntl.response_payload == want, f"fabric rpc {i} merged wrong"
    # pipelined cross-process collective session (mc_collective): all
    # three parties run K lockstep pmean steps, operands device-resident
    # across the chain; every party must converge to the global mean
    coll = None
    if args.collective_steps > 0:
        import jax

        import numpy as _np

        from incubator_brpc_tpu.parallel.mc_collective import (
            expected_mean,
            propose_collective,
        )

        party_ids = sorted(d.id for d in jax.devices())
        client_dev = jax.local_devices()[0].id
        client_index = party_ids.index(client_dev)
        connected = _connect_all(ports)
        if connected is None:
            return 1
        out = propose_collective(
            _remote_channels(*connected, party_ids, client_index),
            party_ids, client_index,
            steps=args.collective_steps, width=256, seed=7,
        )
        want = expected_mean(7, len(party_ids), 256)
        assert _np.allclose(out["own"], want, atol=1e-5), "no convergence"
        want_sum = float(_np.sum(want, dtype=_np.float64))
        for cs in out["server_checksums"]:
            assert abs(cs - want_sum) < 1e-3, (cs, want_sum)
        coll = {
            "steps": args.collective_steps,
            "per_step_ms": out["elapsed_s"] / args.collective_steps * 1e3,
            "parties": len(party_ids),
        }

    # ParallelChannel lowering THROUGH the collective method plane: the
    # sub-channels resolve to multi-controller links, so the fused path
    # cannot single-dispatch — it schedules a 1-step N-party session of
    # the registered kernel instead (rpc/combo.py -> parallel/mc_dispatch)
    mc_low = None
    if args.mc_lowering_check:
        import numpy as _np2

        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )

        # the PROPOSER validates against its local registry too
        register_device_method(
            "dsvc", "scale",
            DeviceMethod(
                _scale_psum_kernel, width=SESSION_WIDTH, chunkable=True
            ),
        )
        req = bytes(range(48))
        cntl = pc.call_method(
            "dsvc", "scale", req, cntl=Controller(timeout_ms=60000)
        )
        assert cntl.ok(), f"mc-lowered call failed: {cntl.error_text}"
        assert getattr(cntl, "collective_fused", False), (
            "mc lowering not taken (fell back to host fan-out)"
        )
        want = b"".join(session_expected([req] * n, steps=1))
        assert cntl.response_payload == want, "mc-lowered merge diverged"
        mc_low = {"bytes": len(cntl.response_payload), "parties": n}

    links = [sub[0]._device_sock.link for sub in pc._subs]
    stats = {
        "n_rpcs": args.n_rpcs,
        "collective": coll,
        "mc_lowered": mc_low,
        "links": [
            {
                "devices": [str(d) for d in lk.devices],
                "platforms": [d.platform for d in lk.devices],
                "steps": int(lk._seq),
                "peer_ack": int(lk.peer_ack),
            }
            for lk in links
        ],
    }
    # one client device, two distinct peer devices: the star
    assert len({l["devices"][0] for l in stats["links"]}) == 1
    assert len({l["devices"][1] for l in stats["links"]}) == len(ports)
    assert all(l["peer_ack"] > 0 for l in stats["links"])
    pc.stop()
    for sub in pc._subs:
        sub[0]._device_sock.recycle()

    def _settled(lk):
        with lk._lock:
            return lk._closed and lk._inflight == 0

    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        if all(_settled(lk) for lk in links):
            break
        time.sleep(0.05)
    assert all(_settled(lk) for lk in links), "a link's close dance hung"
    print("CLIENT_OK " + json.dumps(stats), flush=True)
    _quit_servers(ports)
    return 0


def _connect_all(ports, deadline_s: float = 90.0):
    """One warm host channel per server port, retrying the first call
    until each server has bound (jax.distributed's init barrier ran, but
    RPC ports come up independently). Returns ``(channels, device_ids)``
    — the global device each server reports it owns — or None after
    printing CLIENT_FAIL."""
    from incubator_brpc_tpu.rpc import Channel, Controller

    chans, devs = [], []
    deadline = time.monotonic() + deadline_s
    for p in ports:
        hc = Channel()
        assert hc.init(f"127.0.0.1:{p}")
        while True:
            c = hc.call_method(
                "Admin", "Device", b"", cntl=Controller(timeout_ms=60000)
            )
            if c.ok():
                break
            if time.monotonic() > deadline:
                print(f"CLIENT_FAIL connect {p}: {c.error_text}", flush=True)
                return None
            time.sleep(0.2)
        chans.append(hc)
        devs.append(int(c.response_payload))
    return chans, devs


def _remote_channels(chans, devs, party_ids, own_index):
    """``chans`` reordered to the positional contract of the proposers:
    one channel per party index except ``own_index``, in index order."""
    by_dev = dict(zip(devs, chans))
    return [
        by_dev[pid] for i, pid in enumerate(party_ids) if i != own_index
    ]


def _quit_servers(ports) -> None:
    """Release every server so all processes reach the coordination
    service's exit barrier together (see run_server) — the one shutdown
    protocol, shared by every client role."""
    from incubator_brpc_tpu.rpc import Channel, Controller

    for p in ports:
        host = Channel()
        assert host.init(f"127.0.0.1:{p}")
        host.call_method("Admin", "Quit", b"", cntl=Controller(timeout_ms=10000))


def run_session_client(args) -> int:
    """N-party collective-method-plane client: propose a K-step session of
    the user-registered ("dsvc", "scale") kernel to every server process
    (plain host channels — no device links needed: the session IS the
    data plane), run our own party's chain, and verify every party's
    result bit-for-bit against the host-side integer model."""
    _init_distributed(args.coord_port, args.proc_id, args.nprocs)
    import jax

    from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
    from incubator_brpc_tpu.rpc.device_method import (
        DeviceMethod,
        register_device_method,
    )

    # the proposer validates (service, method) against its LOCAL registry
    # exactly like every accepting party
    register_device_method(
        "dsvc", "scale",
        DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH, chunkable=True),
    )
    ports = [int(p) for p in args.rpc_ports.split(",")]
    # positions in ``ports`` of the servers that start as spares
    spare_procs = set(
        int(p) for p in args.spare_procs.split(",") if p != ""
    )
    connected = _connect_all(ports)
    if connected is None:
        return 1
    all_chans, all_devs = connected
    # spare parties stand OUTSIDE the initial session: their devices are
    # excluded from the party set and their channels form the standby
    # pool the elastic recovery path heals dead slots from
    spares = [
        (ch, dev)
        for i, (ch, dev) in enumerate(zip(all_chans, all_devs))
        if i in spare_procs
    ]
    spare_dev_ids = {dev for _ch, dev in spares}
    party_ids = sorted(
        d.id for d in jax.devices() if d.id not in spare_dev_ids
    )
    client_index = party_ids.index(jax.local_devices()[0].id)
    n = len(party_ids)
    assert len(ports) == n - 1 + len(spare_procs)
    chans = _remote_channels(all_chans, all_devs, party_ids, client_index)
    # per-party operands with DIFFERENT lengths: proves both the operand
    # routing and the n-passthrough across the chain
    operands = [
        bytes((7 * i + j) % 256 for j in range(64 + 8 * i)) for i in range(n)
    ]
    steps = args.collective_steps or 4
    if args.quantize != "none":
        if args.expect_resume:
            # the quantized elastic drill lives in-process
            # (tests/test_robustness.py); this role is the wire-ratio /
            # error-bound A/B — refuse the combination loudly instead of
            # silently ignoring one flag
            print(
                "CLIENT_FAIL --quantize with --expect-resume is not a "
                "supported role combination",
                flush=True,
            )
            return 1
        return _run_session_client_quantized(
            args, chans, party_ids, client_index, steps, ports
        )
    if args.expect_resume:
        return _run_session_client_resume(
            args, chans, spares, party_ids, client_index, operands, steps,
            ports,
        )
    if args.expect_reject:
        # one server registered a different body under the same name: the
        # accept phase must reject CLEANLY, before any lockstep entry
        try:
            propose_dispatch(
                chans, party_ids, "dsvc", "scale", operands,
                steps=steps, proposer_index=client_index, timeout_ms=60000,
            )
        except RuntimeError as e:
            assert "fingerprint mismatch" in str(e), e
            print(
                "CLIENT_OK " + json.dumps({"rejected": True, "parties": n}),
                flush=True,
            )
            _quit_servers(ports)
            return 0
        print("CLIENT_FAIL mismatch was not rejected", flush=True)
        return 1
    out = propose_dispatch(
        chans, party_ids, "dsvc", "scale", operands,
        steps=steps, proposer_index=client_index, timeout_ms=120000,
        chunks=args.chunks, double_buffer=args.double_buffer,
    )
    want = session_expected(operands, out["final_steps"])
    for i, (got, exp) in enumerate(zip(out["results"], want)):
        assert got == exp, f"party {i} diverged from the integer model"
    stats = {
        "parties": n,
        "steps": out["final_steps"],
        "per_step_ms": out["elapsed_s"] / out["final_steps"] * 1e3,
        "method": "dsvc.scale",
        "chunks": args.chunks,
        "double_buffer": bool(args.double_buffer),
    }
    print("CLIENT_OK " + json.dumps(stats), flush=True)
    _quit_servers(ports)
    return 0


def _run_session_client_quantized(
    args, chans, party_ids, client_index, steps, ports
) -> int:
    """Quantized-collective gate half (--quantize int8|int4): run the
    SAME float32 operands through an EXACT pmean session and a QUANTIZED
    one (interleaved on one fabric), then report the two numbers the
    dryrun gate asserts — bytes-on-wire ratio (quantized / exact, ~0.26x
    for int8, ~0.13x for int4) and the max |quantized - exact| error,
    which must sit inside the documented bound
    (parallel/quantized.pmean_error_bound)."""
    import numpy as np

    from incubator_brpc_tpu.parallel import quantized as _q
    from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
    from incubator_brpc_tpu.parallel.mc_dispatch import propose_dispatch
    from incubator_brpc_tpu.rpc.device_method import register_device_method

    n = len(party_ids)
    width = SESSION_WIDTH  # 512 B = 128 floats = 4 blocks of 32
    # the proposer resolves (service, method) in its own registry first
    register_device_method("_collective", "pmean", _pmean_dm(width))
    rng = np.random.default_rng(1234)
    rows = [
        (rng.standard_normal(width // 4) * (1.0 + i)).astype(np.float32)
        for i in range(n)
    ]
    operands = [r.tobytes() for r in rows]
    # the overlap schedule rides along when asked (the quantized pmean
    # variants are chunkable; width 512 block-aligns chunks 1/2/4):
    # both arms run the SAME schedule so the A/B isolates quantization
    sched = dict(chunks=args.chunks, double_buffer=args.double_buffer)
    exact = propose_dispatch(
        chans, party_ids, "_collective", "pmean", operands,
        steps=steps, proposer_index=client_index, timeout_ms=120000,
        **sched,
    )
    quant = propose_dispatch(
        chans, party_ids, "_collective", "pmean", operands,
        steps=steps, proposer_index=client_index, timeout_ms=120000,
        quantize=args.quantize, **sched,
    )
    assert quant["final_steps"] == exact["final_steps"]
    bound = _q.pmean_error_bound(rows, exact["final_steps"], args.quantize)
    max_err = 0.0
    for got, ref in zip(quant["results"], exact["results"]):
        qv = np.frombuffer(got, dtype=np.float32)
        ev = np.frombuffer(ref, dtype=np.float32)
        max_err = max(max_err, float(np.abs(qv - ev).max()))
    ratio = quant["wire_bytes"] / exact["wire_bytes"]
    if max_err > bound:
        print(
            f"CLIENT_FAIL quantized error {max_err} above bound {bound}",
            flush=True,
        )
        return 1
    stats = {
        "parties": n,
        "steps": quant["final_steps"],
        "quantize": args.quantize,
        "chunks": args.chunks,
        "double_buffer": bool(args.double_buffer),
        "wire_bytes_exact": exact["wire_bytes"],
        "wire_bytes_quantized": quant["wire_bytes"],
        "wire_ratio": ratio,
        "max_error": max_err,
        "error_bound": bound,
        "method": "_collective.pmean",
    }
    print("CLIENT_OK " + json.dumps(stats), flush=True)
    _quit_servers(ports)
    return 0


def _run_session_client_resume(
    args, chans, spares, party_ids, client_index, operands, steps, ports
) -> int:
    """Chaos-drill client half: one party dies at exactly step K
    (``--chaos-kill-at-step`` on its server); the session must HEAL —
    resume barrier over the survivors, a replacement party filling the
    dead slot, replay from the agreed resume point — and the merged
    result must be byte-identical to an undisturbed run of the same
    operands.  On a TRUE multi-controller fabric the dead party's
    checkpoint ring died with its RPC plane, so the reshard can be
    unreachable and the heal legitimately lands as a full restart over
    the replaced set (``resumed_from`` None): the drill asserts the
    HEAL, and reports the resume point it achieved."""
    from incubator_brpc_tpu.parallel.mc_dispatch import propose_with_recovery

    ckpt = args.checkpoint_every or 2
    out = propose_with_recovery(
        chans, party_ids, "dsvc", "scale", operands,
        steps=steps, proposer_index=client_index, timeout_ms=120000,
        session_deadline_ms=60000, max_reproposals=1,
        spares=spares, checkpoint_every=ckpt,
    )
    want = session_expected(operands, out["final_steps"])
    identical = all(
        got == exp for got, exp in zip(out["results"], want)
    )
    if not identical:
        print("CLIENT_FAIL resumed merge diverged from the model", flush=True)
        return 1
    if not out["replaced_party_ids"]:
        print(
            f"CLIENT_FAIL no heal: replaced={out['replaced_party_ids']} "
            f"resumed_from={out['resumed_from']}",
            flush=True,
        )
        return 1
    stats = {
        "parties": len(party_ids),
        "steps": out["final_steps"],
        # None on a fabric where the dead ring was unreachable (full
        # restart over the replaced set); an int = true checkpoint resume
        "resumed_from": out["resumed_from"],
        "dead_party_ids": out["dead_party_ids"],
        "replaced_party_ids": out["replaced_party_ids"],
        "byte_identical": True,
        "method": "dsvc.scale",
    }
    print("CLIENT_OK " + json.dumps(stats), flush=True)
    _quit_servers(ports)
    return 0


def _free_ports(n: int):
    import socket

    holders, ports = [], []
    for _ in range(n):
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        ports.append(sk.getsockname()[1])
        holders.append(sk)
    for sk in holders:
        sk.close()
    return ports


def _argv_int(argv, flag: str, default: int) -> int:
    argv = list(argv)
    return int(argv[argv.index(flag) + 1]) if flag in argv else default


def _orchestrate(
    specs, label: str, timeout: float, servers_may_die=False,
    platform: str = "cpu",
):
    """Shared parent-side runner: spawn every (name, role, argv) worker
    with the environment that hands it its device (``child_env``),
    collect outputs (client LAST in ``specs`` is the one whose CLIENT_OK
    carries the stats), assert success, return (stats, transcript). The
    exit is worker-coordinated (Admin.Quit + the coordination service's
    barrier); communicate() closing stdin is the fallback when the
    client crashed early."""
    import subprocess

    nprocs = _argv_int(specs[-1][2], "--nprocs", 2)
    tpu_ports = _free_ports(nprocs) if platform == "tpu" else ()
    procs = []
    for name, role, argv in specs:
        # pair convention (main()): the server coordinates, the client
        # is last
        proc_id = _argv_int(
            argv, "--proc-id", 0 if role == "server" else nprocs - 1
        )
        procs.append(
            (
                name,
                subprocess.Popen(
                    [
                        sys.executable, "-m",
                        "incubator_brpc_tpu.transport.mc_worker", role,
                        *argv,
                    ],
                    cwd=_REPO,
                    env=child_env(platform, proc_id, nprocs, tpu_ports),
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True,
                ),
            )
        )
    client_name, client = procs[-1]
    outs = {}
    try:
        outs[client_name], _ = client.communicate(timeout=timeout)
        # a failed client released nobody: don't wait on its servers
        grace = 30.0 if client.returncode == 0 else 3.0
        for name, proc in procs[:-1]:
            outs[name], _ = proc.communicate(timeout=grace)
    except subprocess.TimeoutExpired:
        for name, proc in procs:
            proc.kill()
        for name, proc in procs:
            if name not in outs:
                outs[name] = (proc.communicate()[0] or "") + " [KILLED]"
        raise AssertionError(
            f"{label} timed out (client rc={client.returncode})\n"
            + "".join(f"-- {n} --\n{o}\n" for n, o in outs.items())
        )
    transcript = "".join(f"-- {n} --\n{o}\n" for n, o in outs.items())
    assert client.returncode == 0 and "CLIENT_OK" in outs[client_name], (
        f"{label} client failed rc={client.returncode}\n{transcript}"
    )
    if not servers_may_die:
        for name, proc in procs[:-1]:
            assert proc.returncode == 0 and "SERVER_DONE" in outs[name], (
                f"{label} {name} failed rc={proc.returncode}\n{transcript}"
            )
    stats = json.loads(
        outs[client_name].split("CLIENT_OK", 1)[1].strip().splitlines()[0]
    )
    return stats, transcript


def _run_client_peer_death(args, ch) -> int:
    """Fault-injection client half: the peer dies mid-traffic. The link
    must FAIL (fast, via the host socket under the control stream — not a
    2-minute wedge), failing the in-flight RPC, and the dead link must
    not poison the process."""
    from incubator_brpc_tpu.rpc import Controller

    ok_count = 0
    failed_at = None
    for i in range(args.n_rpcs):
        cntl = ch.call_method(
            "EchoService", "Echo", b"f%03d" % i,
            cntl=Controller(timeout_ms=30000, max_retry=0),
        )
        if cntl.ok():
            ok_count += 1
        else:
            failed_at = (i, cntl.error_code, cntl.error_text)
            break
    assert failed_at is not None, "peer died but no RPC ever failed"
    link = ch._device_sock.link
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        with link._lock:
            if link._closed:
                break
        time.sleep(0.05)
    with link._lock:
        closed = link._closed
    assert closed, "link did not fail after peer death"
    from incubator_brpc_tpu.transport.sock import CONNECTED

    assert ch._device_sock.state != CONNECTED
    print(
        "CLIENT_OK "
        + json.dumps(
            {
                "ok_before_death": ok_count,
                "failed_at": failed_at[0],
                "error_code": failed_at[1],
            }
        ),
        flush=True,
    )
    # the peer is dead: the coordination service's exit barrier can never
    # complete, so skip atexit — the CLEAN exit path is covered by the
    # non-fault tests
    sys.stdout.flush()
    os._exit(0)


def orchestrate_pair(extra=(), timeout: float = 240.0):
    """Spawn the server+client pair as real OS processes and collect the
    client's link stats (used by tests/test_mc_link.py and
    ``dryrun_multiprocess``). Returns ``(stats, client_out, server_out)``."""
    coord, rpc = _free_ports(2)
    base = ("--coord-port", str(coord), "--rpc-port", str(rpc))
    stats, transcript = _orchestrate(
        [
            ("server", "server", base),
            ("client", "client", (*base, *extra)),
        ],
        label="two-process pair",
        timeout=timeout,
    )
    return stats, transcript, transcript


def orchestrate_peer_death(die_after: int = 3, timeout: float = 240.0):
    """Fault-injection pair: the SERVER process dies mid-traffic (os._exit
    inside a handler). The client must observe a fast, clean link failure.
    The client doubles as the jax.distributed coordinator here so the
    coordination service survives the death it is reporting on."""
    coord, rpc = _free_ports(2)
    specs = [
        (
            "server",
            "server",
            (
                "--coord-port", str(coord), "--rpc-port", str(rpc),
                "--proc-id", "1",
                "--die-after-rpcs", str(die_after),
            ),
        ),
        (
            "client",
            "client",
            (
                "--coord-port", str(coord), "--rpc-port", str(rpc),
                "--proc-id", "0",
                "--n-rpcs", str(die_after + 20),
                "--expect-peer-death",
            ),
        ),
    ]
    return _orchestrate(
        specs, label="peer-death pair", timeout=timeout, servers_may_die=True
    )


def run_probe(args) -> int:
    """Capability probe body: join the group, run ONE 2-device collective,
    report. Everything the mc plane needs, nothing it doesn't — fails in
    seconds on backends that cannot run multi-process computations."""
    _init_distributed(args.coord_port, args.proc_id, args.nprocs)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = sorted(jax.devices(), key=lambda d: d.id)
    mesh = Mesh(np.asarray(devices), ("p",))
    sharding = NamedSharding(mesh, P("p"))
    own = jax.local_devices()[0]
    idx = [d.id for d in devices].index(own.id)
    fn = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.psum(x, "p"),
            mesh=mesh, in_specs=P("p"), out_specs=P("p"), check_vma=False,
        ),
        out_shardings=sharding,
    )
    shard = jax.device_put(jnp.asarray([[float(idx + 1)]]), own)
    x = jax.make_array_from_single_device_arrays(
        (len(devices), 1), sharding, [shard]
    )
    out = fn(x)
    for s in out.addressable_shards:
        total = float(np.asarray(s.data).reshape(-1)[0])
        expect = sum(range(1, len(devices) + 1))
        assert total == expect, (total, expect)
    print(
        f"PROBE_OK platform={own.platform} "
        f"devices={[str(d) for d in devices]}",
        flush=True,
    )
    return 0


_mp_capable: dict = {}


def multiprocess_capable(timeout: float = 120.0) -> bool:
    """Fast module-scoped capability gate: can this jax backend run a
    cross-process collective at all? One tiny 2-process psum decides (a
    backend without multi-process computations fails it in seconds);
    cached process-wide so every suite pays at most one probe."""
    if "ok" not in _mp_capable:
        import subprocess

        coord = _free_ports(1)[0]
        procs = [
            subprocess.Popen(
                [
                    sys.executable, "-m",
                    "incubator_brpc_tpu.transport.mc_worker", "probe",
                    "--coord-port", str(coord), "--nprocs", "2",
                    "--proc-id", str(i),
                ],
                cwd=_REPO, env=child_env("cpu", i, 2),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for i in range(2)
        ]
        ok = True
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                out = ""
            ok = ok and p.returncode == 0 and "PROBE_OK" in (out or "")
        _mp_capable["ok"] = ok
    return _mp_capable["ok"]


def orchestrate_session(
    n_parties: int = 3,
    steps: int = 4,
    wrong_kernel: bool = False,
    timeout: float = 300.0,
    chunks: int = 1,
    double_buffer: bool = False,
    quantize: str = "none",
):
    """Spawn ``n_parties - 1`` server processes + one session client (all
    one jax.distributed group) and run an N-party collective-method-plane
    session of the user kernel. ``wrong_kernel`` arms ONE server with a
    same-name/different-body kernel so the fingerprint reject path is
    what the run proves. ``chunks``/``double_buffer`` run the session on
    the overlap schedule (chunked sub-collectives, two step slots in
    flight) — byte-identity against the integer model still gates.
    ``quantize`` switches the client to the quantized-pmean A/B role:
    one exact and one quantized session over the same float operands,
    reporting the wire-bytes ratio and the max error vs the documented
    bound (the dryrun quantized gate).  Returns the client's stats."""
    ports = _free_ports(n_parties)
    coord, rpc_ports = ports[0], ports[1:]
    specs = []
    for i in range(n_parties - 1):
        argv = [
            "--coord-port", str(coord), "--nprocs", str(n_parties),
            "--proc-id", str(i), "--rpc-port", str(rpc_ports[i]),
        ]
        if wrong_kernel and i == 0:
            argv.append("--wrong-kernel")
        specs.append((f"server{i}", "server", tuple(argv)))
    client = [
        "--coord-port", str(coord), "--nprocs", str(n_parties),
        "--proc-id", str(n_parties - 1),
        "--rpc-ports", ",".join(map(str, rpc_ports)),
        "--collective-steps", str(steps),
        "--chunks", str(chunks),
        "--quantize", quantize,
    ]
    if double_buffer:
        client.append("--double-buffer")
    if wrong_kernel:
        client.append("--expect-reject")
    specs.append(("session-client", "session-client", tuple(client)))
    return _orchestrate(
        specs, label=f"{n_parties}-party session", timeout=timeout
    )


def orchestrate_chaos_session(
    n_parties: int = 3,
    steps: int = 8,
    kill_at: int = 3,
    checkpoint_every: int = 2,
    timeout: float = 300.0,
):
    """The scriptable chaos drill: ``n_parties - 1`` party servers + ONE
    spare server + the session client, all one jax.distributed group.
    Server 0 is armed with ``--chaos-kill-at-step kill_at`` so exactly
    one party dies at step K of the session; the client runs
    ``propose_with_recovery`` with the spare in its standby pool and
    asserts the session HEALS: replacement joins, resume point agreed
    over the survivors' checkpoints, and the merged result byte-identical
    to an undisturbed run.  Returns the client's stats (resumed_from,
    replaced_party_ids, byte_identical)."""
    n_servers = n_parties  # n_parties - 1 party servers + 1 spare
    ports = _free_ports(n_servers + 1)
    coord, rpc_ports = ports[0], ports[1:]
    nprocs = n_servers + 1
    spare_proc = n_servers - 1  # the LAST server process is the spare
    specs = []
    for i in range(n_servers):
        argv = [
            "--coord-port", str(coord), "--nprocs", str(nprocs),
            "--proc-id", str(i), "--rpc-port", str(rpc_ports[i]),
        ]
        if i == 0:
            argv += ["--chaos-kill-at-step", str(kill_at)]
        specs.append((f"server{i}", "server", tuple(argv)))
    client = [
        "--coord-port", str(coord), "--nprocs", str(nprocs),
        "--proc-id", str(nprocs - 1),
        "--rpc-ports", ",".join(map(str, rpc_ports)),
        "--collective-steps", str(steps),
        "--spare-procs", str(spare_proc),
        "--expect-resume",
        "--checkpoint-every", str(checkpoint_every),
    ]
    specs.append(("session-client", "session-client", tuple(client)))
    return _orchestrate(
        specs,
        label=f"chaos session (kill party 0 at step {kill_at})",
        timeout=timeout,
        servers_may_die=True,
    )


def orchestrate_fabric(
    n_servers: int = 2, extra=(), timeout: float = 300.0,
    platform: str = "cpu",
):
    """Spawn ``n_servers`` server processes + one fabric client (all in one
    jax.distributed group) and return the client's per-link stats."""
    ports = _free_ports(n_servers + 1)
    coord, rpc_ports = ports[0], ports[1:]
    nprocs = n_servers + 1
    specs = [
        (
            f"server{i}",
            "server",
            (
                "--coord-port", str(coord), "--nprocs", str(nprocs),
                "--proc-id", str(i), "--rpc-port", str(rpc_ports[i]),
            ),
        )
        for i in range(n_servers)
    ]
    specs.append(
        (
            "fabric-client",
            "fabric-client",
            (
                "--coord-port", str(coord), "--nprocs", str(nprocs),
                "--proc-id", str(n_servers),
                "--rpc-ports", ",".join(map(str, rpc_ports)), *extra,
            ),
        )
    )
    return _orchestrate(
        specs, label="fabric", timeout=timeout, platform=platform
    )


def main(argv=None) -> int:
    # SIGUSR1 dumps all thread stacks — the pair runs under an orchestration
    # harness (pytest / dryrun), and a wedged worker must be diagnosable
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1)
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "role",
        choices=[
            "server", "client", "fabric-client", "session-client", "probe",
        ],
    )
    ap.add_argument("--coord-port", type=int, required=True)
    ap.add_argument("--rpc-port", type=int, default=0)
    ap.add_argument("--rpc-ports", type=str, default="")  # fabric client
    ap.add_argument("--nprocs", type=int, default=2)
    ap.add_argument("--proc-id", type=int, default=-1)  # -1: by role
    ap.add_argument("--n-rpcs", type=int, default=8)
    ap.add_argument("--payload", type=int, default=3000)
    ap.add_argument("--slot-words", type=int, default=256)
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--collective-steps", type=int, default=0)  # fabric
    ap.add_argument("--die-after-rpcs", type=int, default=0)  # server fault
    ap.add_argument("--expect-peer-death", action="store_true")  # client
    # collective method plane (parallel/mc_dispatch):
    ap.add_argument("--wrong-kernel", action="store_true")  # server
    ap.add_argument("--expect-reject", action="store_true")  # session client
    ap.add_argument("--mc-lowering-check", action="store_true")  # fabric
    # elastic sessions (checkpoint/resume + party replacement):
    ap.add_argument("--chaos-kill-at-step", type=int, default=-1)  # server
    ap.add_argument("--spare-procs", type=str, default="")  # session client
    ap.add_argument("--expect-resume", action="store_true")  # session client
    ap.add_argument("--checkpoint-every", type=int, default=0)  # client
    ap.add_argument("--chunks", type=int, default=1)  # session client
    ap.add_argument("--double-buffer", action="store_true")  # session client
    # quantized collectives (parallel/quantized): exact vs int8/int4 A/B
    ap.add_argument(
        "--quantize", choices=["none", "int8", "int4"], default="none"
    )  # session client
    args = ap.parse_args(argv)
    if args.proc_id < 0:
        # pair convention: server is the coordinator, client is last
        args.proc_id = 0 if args.role == "server" else args.nprocs - 1
    role = {
        "server": run_server,
        "client": run_client,
        "fabric-client": run_fabric_client,
        "session-client": run_session_client,
        "probe": run_probe,
    }[args.role]
    try:
        rc = role(args)
    except BaseException:  # noqa: BLE001 — report, then leave at once
        import traceback

        traceback.print_exc()
        rc = 1
    if rc:
        # a failed worker must not park in the coordination service's
        # exit barrier waiting for peers nobody will release
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
