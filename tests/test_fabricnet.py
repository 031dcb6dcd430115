"""FabricNet end-to-end: full train step over the 8-device virtual mesh,
plus single-device equivalence (sharded forward == unsharded math)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_brpc_tpu.models import fabricnet
from incubator_brpc_tpu.parallel.mesh import default_axis_sizes, make_fabric_mesh


def _setup(n_devices, axis_sizes=None, **cfg_kw):
    mesh = make_fabric_mesh(n_devices, axis_sizes=axis_sizes)
    sizes = dict(mesh.shape)
    defaults = dict(
        d_model=16,
        d_ff=32,
        d_expert=16,
        experts_per_rank=2,
        batch=max(8, sizes["dp"] * sizes["ep"] * 4),
        seq=max(8, sizes["sp"] * 8),
        microbatches=2,
    )
    defaults.update(cfg_kw)
    cfg = fabricnet.FabricNetConfig(**defaults)
    fabricnet.validate_config(cfg, mesh)
    params = fabricnet.init_params(cfg, mesh)
    x, y = fabricnet.make_batch(cfg, mesh)
    return cfg, mesh, params, x, y


def test_forward_shapes_single_device():
    cfg, mesh, params, x, _ = _setup(1)
    out = fabricnet.make_forward_step(cfg, mesh)(params, x)
    assert out.shape == (cfg.batch, cfg.seq, cfg.d_model)
    assert np.isfinite(np.asarray(out)).all()


def test_train_step_decreases_loss_8dev():
    cfg, mesh, params, x, y = _setup(8)
    step = fabricnet.make_train_step(cfg, mesh)
    losses = []
    for _ in range(8):
        params, loss = step(params, x, y)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def test_sharded_forward_matches_single_device():
    """The 8-way sharded forward must compute the same function as the
    1-device mesh (collective lowerings preserve semantics)."""
    cfg, mesh1, params1, x1, _ = _setup(1, batch=8, seq=8)
    out1 = fabricnet.make_forward_step(cfg, mesh1)(params1, x1)

    # pp/ep stay 1 so param shapes match the 1-device init; shard dp/tp/sp
    mesh8 = make_fabric_mesh(
        8, axis_sizes={"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}
    )
    fabricnet.validate_config(cfg, mesh8)
    # move identical params/batch onto the 8-device mesh shardings
    from jax.sharding import NamedSharding

    specs = fabricnet.param_specs(cfg.heads)
    params8 = {
        k: jax.device_put(np.asarray(v), NamedSharding(mesh8, specs[k]))
        for k, v in params1.items()
    }
    x8 = jax.device_put(np.asarray(x1), NamedSharding(mesh8, fabricnet.batch_specs()[0]))
    out8 = fabricnet.make_forward_step(cfg, mesh8)(params8, x8)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out8), rtol=2e-4, atol=2e-5)


def test_heads_zero_ring_mean_path():
    """The heads=0 fallback (ring-mean context instead of ring attention)
    must keep training — otherwise the branch rots untested."""
    import jax

    from incubator_brpc_tpu.parallel.mesh import make_fabric_mesh

    mesh = make_fabric_mesh(
        8, axis_sizes={"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}
    )
    cfg = fabricnet.FabricNetConfig(heads=0)
    fabricnet.validate_config(cfg, mesh)
    params = fabricnet.init_params(cfg, mesh)
    assert "wqkv" not in params
    x, y = fabricnet.make_batch(cfg, mesh)
    step = fabricnet.make_train_step(cfg, mesh)
    params, l0 = step(params, x, y)
    for _ in range(5):
        params, loss = step(params, x, y)
    assert float(loss) < float(l0)


class TestOverlapSchedule:
    """The T3 microbatch overlap schedule (ISSUE 13): serialized and
    overlapped are the SAME sliced dataflow differing only in the
    optimization_barrier, so loss AND updated params must match
    BITWISE; both must agree with the fused (pre-overlap) path to
    float rounding."""

    CONFIGS = [
        # (axis_sizes, cfg overrides) — two genuinely different fabrics:
        # the pp=2/dp=2/tp=2 default spread, and a dp/tp/sp mesh with the
        # ring-attention sequence axis live
        (None, {}),
        ({"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}, {}),
    ]

    @pytest.mark.parametrize("axis_sizes,cfg_kw", CONFIGS)
    def test_overlapped_bit_identical_to_serialized(
        self, axis_sizes, cfg_kw
    ):
        cfg, mesh, params, x, y = _setup(8, axis_sizes, **cfg_kw)
        ser = fabricnet.make_train_step(cfg, mesh, schedule="serialized")
        ovl = fabricnet.make_train_step(cfg, mesh, schedule="overlapped")

        def run(step):
            p = jax.tree_util.tree_map(lambda a: a.copy(), params)
            p2, loss = step(p, x, y)
            return p2, np.asarray(loss)

        ps, ls = run(ser)
        po, lo = run(ovl)
        assert ls.tobytes() == lo.tobytes(), "loss diverged"
        for k in ps:
            assert (
                np.asarray(ps[k]).tobytes() == np.asarray(po[k]).tobytes()
            ), f"param {k} diverged between schedules"

    @pytest.mark.parametrize("axis_sizes,cfg_kw", CONFIGS)
    def test_sliced_schedule_matches_fused_grads(
        self, axis_sizes, cfg_kw
    ):
        """The sliced schedule's accumulated per-leaf psums compute the
        same gradients as the fused boundary transpose — only summation
        order differs (float rounding, not math)."""
        cfg, mesh, params, x, y = _setup(8, axis_sizes, **cfg_kw)
        fused = fabricnet.make_train_step(cfg, mesh)
        ovl = fabricnet.make_train_step(cfg, mesh, schedule="overlapped")

        def run(step):
            p = jax.tree_util.tree_map(lambda a: a.copy(), params)
            p2, loss = step(p, x, y)
            return p2, float(loss)

        pf, lf = run(fused)
        po, lo = run(ovl)
        assert abs(lf - lo) < 1e-6
        for k in pf:
            np.testing.assert_allclose(
                np.asarray(pf[k]), np.asarray(po[k]),
                rtol=2e-4, atol=2e-5, err_msg=f"param {k}",
            )

    def test_overlapped_schedule_trains(self):
        cfg, mesh, params, x, y = _setup(8)
        step = fabricnet.make_train_step(cfg, mesh, schedule="overlapped")
        losses = []
        for _ in range(6):
            params, loss = step(params, x, y)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], f"loss did not decrease: {losses}"

    def test_unknown_schedule_rejected(self):
        cfg, mesh, _p, _x, _y = _setup(1)
        with pytest.raises(ValueError, match="schedule"):
            fabricnet.make_train_step(cfg, mesh, schedule="eager")


def test_graft_entry_dryrun():
    import __graft_entry__ as ge

    facts = ge.dryrun_multichip(8)
    assert facts["fused_collective"] is True
    assert len(facts["nparty_fabric_peers"]) == 7  # servers on devices 1-7
    assert len(facts["meshes"]) == 2


def test_graft_entry_dryrun_on_four_devices():
    """A four-device host is the deployment: the N-party star and the
    fused collective must RUN there (client on device 0, servers on 1-3),
    not vanish behind a device-count gate, and every fabric axis must be
    live in one of the meshes."""
    import __graft_entry__ as ge

    facts = ge.dryrun_multichip(4)
    assert facts["fused_collective"] is True
    assert len(facts["nparty_fabric_peers"]) == 3
    assert len(set(facts["device_link"])) == 2
    for axis in ("dp", "pp", "tp", "sp", "ep"):
        assert any(m[axis] >= 2 for m in facts["meshes"]), axis


def test_graft_entry_dryrun_takes_no_fewer_devices_than_asked():
    """No fallback: asking for more devices than the process has is an
    error, never a re-initialisation on something else."""
    import pytest

    import __graft_entry__ as ge

    with pytest.raises(RuntimeError, match="found 8 cpu"):
        ge.dryrun_multichip(16)


def test_graft_entry_dryrun_multiprocess():
    # the multi-controller gate needs real cross-process collectives;
    # probe that capability in seconds instead of letting the pair burn
    # its whole handshake deadline on a backend without it
    import pytest

    from incubator_brpc_tpu.transport.mc_worker import multiprocess_capable

    if not multiprocess_capable():
        pytest.skip("jax backend cannot run multi-process computations")
    import __graft_entry__ as ge

    facts = ge.dryrun_multiprocess()
    assert facts["chaos_resume"]["byte_identical"]


def test_graft_entry_single():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out, echo_resp = jax.jit(fn)(*args) if callable(fn) else (None, None)
    assert np.isfinite(np.asarray(out)).all()
    assert echo_resp.dtype == jnp.uint32
