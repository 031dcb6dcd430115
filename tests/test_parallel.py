"""Mesh + collective-lowering tests on the virtual 8-device CPU mesh —
the reference's 'many servers as many local sockets' trick (SURVEY.md §4)
mapped to 'many chips as many virtual devices'."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from incubator_brpc_tpu.parallel import (
    default_axis_sizes,
    make_fabric_mesh,
    fanout,
    merge,
    partition_exchange,
    ring_allgather,
)


def test_default_axis_sizes():
    assert default_axis_sizes(1) == {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    s8 = default_axis_sizes(8)
    assert s8["dp"] == 2 and s8["tp"] == 2 and s8["pp"] == 2
    assert np.prod(list(s8.values())) == 8
    s32 = default_axis_sizes(32)
    assert all(v == 2 for v in s32.values())
    assert np.prod(list(default_axis_sizes(6).values())) == 6


def test_make_fabric_mesh():
    mesh = make_fabric_mesh(8)
    assert mesh.axis_names == ("dp", "pp", "tp", "sp", "ep")
    assert np.prod(list(mesh.shape.values())) == 8


@pytest.fixture
def flat_mesh():
    """One-axis view for collective semantics tests: all 8 devices on dp."""
    return make_fabric_mesh(8, axis_sizes={"dp": 8, "pp": 1, "tp": 1, "sp": 1, "ep": 1})


def _smap(mesh, fn, in_spec, out_spec):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec, check_vma=False)
    )


def test_merge_psum(flat_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    f = _smap(flat_mesh, partial(merge, axis="dp", merger="sum"), P("dp"), P())
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full((1,), 28.0))


def test_fanout_allgather(flat_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    # all_gather result is identical on every rank -> replicated out_spec
    f = _smap(flat_mesh, partial(fanout, axis="dp"), P("dp"), P(None, None))
    out = f(x)
    assert out.shape == (8, 1)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.arange(8.0))


def test_partition_exchange_is_transpose(flat_mesh):
    # 8 ranks each hold one row; all_to_all over columns == distributed transpose
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    f = _smap(
        flat_mesh,
        partial(partition_exchange, axis="dp", split_dim=1, concat_dim=1),
        P("dp", None),
        P("dp", None),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.arange(64.0).reshape(8, 8).T)


def test_ring_allgather_matches_native(flat_mesh):
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)

    def body(xl):
        return ring_allgather(xl.reshape(2), "dp")

    # every rank ends with the full (8, 2) table -> replicated
    f = _smap(flat_mesh, body, P("dp", None), P(None, None))
    out = np.asarray(f(x))
    assert out.shape == (8, 2)
    np.testing.assert_allclose(out, np.arange(16.0).reshape(8, 2))


class TestCollectiveAcceptPreAck:
    """propose_collective's two-phase shape (ADVICE r5): every server
    answers an explicit accept pre-ack before any party enters its
    session — no fixed grace window, rejections surface immediately."""

    def _server(self):
        from incubator_brpc_tpu.rpc import Server, ServerOptions

        srv = Server(ServerOptions(enable_collective_service=True))
        assert srv.start(0)
        return srv

    def test_accept_phase_validates_without_running(self, monkeypatch):
        import json as _json

        from incubator_brpc_tpu.parallel import mc_collective
        from incubator_brpc_tpu.rpc import Channel

        def _boom(*a, **kw):  # the accept phase must never run a session
            raise AssertionError("accept phase ran the session")

        monkeypatch.setattr(mc_collective, "run_collective_session", _boom)
        srv = self._server()
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            payload = _json.dumps(
                {
                    "parties": [0, 1],
                    "index": 1,
                    "steps": 3,
                    "width": 4,
                    "seed": 7,
                    "phase": "accept",
                }
            ).encode()
            cntl = ch.call_method("_tpu_transport", "collective", payload)
            assert cntl.ok(), cntl.error_text
            ack = _json.loads(cntl.response_payload.decode())
            assert ack == {"accept": True, "index": 1}
            # and a bad proposal is REJECTED at the accept phase
            bad = _json.dumps(
                {
                    "parties": [0, 1],
                    "index": 1,
                    "steps": 0,  # out of bounds
                    "width": 4,
                    "seed": 7,
                    "phase": "accept",
                }
            ).encode()
            cntl = ch.call_method("_tpu_transport", "collective", bad)
            assert cntl.failed()
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_propose_runs_without_grace_window(self, monkeypatch):
        import time as _time

        from incubator_brpc_tpu.parallel import mc_collective
        from incubator_brpc_tpu.rpc import Channel

        calls = []

        def _stub(parties, idx, steps, width, seed):
            calls.append(idx)
            return np.zeros(width, np.float32), 0.001

        monkeypatch.setattr(mc_collective, "run_collective_session", _stub)
        srv = self._server()
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            t0 = _time.monotonic()
            out = mc_collective.propose_collective(
                [ch], [0, 1], client_index=0, steps=3, width=4, seed=7,
                timeout_ms=30000,
            )
            elapsed = _time.monotonic() - t0
            assert len(out["server_checksums"]) == 1
            # client (index 0) and server party (index 1) both ran
            assert sorted(calls) == [0, 1]
            # the old fixed 0.5 s grace window is gone: the only fixed
            # pause left is the short rejection watch (structural check —
            # a tight wall-clock bound here would flake on loaded CI),
            # plus a generous sanity ceiling on the whole stubbed round
            assert mc_collective._REJECT_WATCH_S <= 0.1
            assert elapsed < 5.0, f"proposal round unexpectedly slow: {elapsed}"
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_a_run_proposal_refused_for_its_own_accept_is_sent_again(
        self, monkeypatch
    ):
        """A server frees a call's admission slot after it has written the
        response, so the run proposal that follows the accept's ack can be
        refused (ELIMIT at collective_max_concurrency=1) for the proposer's
        own accept: tests/test_mc_link.py::test_three_process_collective_session
        failed so beside five other workers (PR 55's last run). The slot
        is held here 150 ms past the ack: the second resend finds it free.
        An overlapping session, which keeps its slot, is still refused, after
        three proposals and no more."""
        import time as _time

        from incubator_brpc_tpu.parallel import mc_collective
        from incubator_brpc_tpu.rpc import Channel

        monkeypatch.setattr(
            mc_collective, "run_collective_session",
            lambda parties, idx, steps, width, seed: (
                np.zeros(width, np.float32), 0.001))
        srv = self._server()
        release, held = srv._release, []

        def late_release(status, cntl):
            if not held:  # the accept: the first call this server answers
                held.append(status)
                _time.sleep(0.15)
            release(status, cntl)

        monkeypatch.setattr(srv, "_release", late_release)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            out = mc_collective.propose_collective(
                [ch], [0, 1], client_index=0, steps=3, width=4, seed=7,
                timeout_ms=30000)
            assert len(out["server_checksums"]) == 1 and held
            # a slot that stays taken: refused, after the bounded resends
            monkeypatch.setattr(srv, "_release", lambda *call: held.append(call))
            sent, call_method = [], ch.call_method
            monkeypatch.setattr(
                ch, "call_method",
                lambda *a, **kw: sent.append(a[2]) or call_method(*a, **kw))
            t0 = _time.monotonic()
            with pytest.raises(RuntimeError, match="max_concurrency"):
                mc_collective.propose_collective(
                    [ch], [0, 1], client_index=0, steps=3, width=4, seed=7,
                    timeout_ms=30000)
            assert _time.monotonic() - t0 < 1.0
            assert len(sent) == 4  # the accept, a run proposal and two resends
        finally:
            for call in held[1:]:
                release(*call)
            srv.stop()
            srv.join(timeout=5)
