"""What the chip bring-up added, as far as a CPU can check it: the smoke
refuses to run off a TPU, the compile cache is placed
by one rule, and the launcher — not the worker — decides a child's
platform. Each case runs in a child process: the subjects set process
environment and ``jax.config`` and must not leak into the suite (a
configured cache would fill the checkout during tier-1)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env_over=None, drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_over or {})
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _cache_state():
    from incubator_brpc_tpu.utils.compile_cache import DEFAULT_DIR

    return sorted(os.listdir(DEFAULT_DIR)) if os.path.isdir(DEFAULT_DIR) else None


class TestRefusesOffTheChip:
    def test_chip_smoke_exits_non_zero_without_a_result(self):
        before = _cache_state()
        r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        assert "platform=cpu" in r.stdout
        assert "chip_smoke needs a TPU" in r.stdout
        assert '"ok"' not in r.stdout and "PHASE" not in r.stdout
        assert _cache_state() == before  # refused before compiling anything


_CONFIGURE = (
    "import json, os, sys\n"
    "from incubator_brpc_tpu.utils import compile_cache as cc\n"
    "path = cc.configure()\n"
    "off_jax = 'jax' not in sys.modules\n"
    "import jax\n"
    "print(json.dumps({'path': path, 'default': cc.DEFAULT_DIR,\n"
    "    'off_jax': off_jax, 'env': os.environ[cc.ENV_DIR],\n"
    "    'jax_dir': jax.config.jax_compilation_cache_dir,\n"
    "    'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
)


class TestCompileCacheRule:
    def _configure(self, env_over=None, drop=()):
        r = _run(["-c", _CONFIGURE], env_over, drop)
        assert r.returncode == 0, r.stderr
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_env_set_means_that_directory_and_no_other(self, tmp_path):
        want = str(tmp_path / "placed-from-outside")
        got = self._configure({"JAX_COMPILATION_CACHE_DIR": want})
        assert got["path"] == got["env"] == got["jax_dir"] == want
        assert got["off_jax"], "configure() must not import jax"
        assert got["min_secs"] == 0.0
        assert not os.path.exists(want)  # nothing is made before a compile

    def test_unset_means_the_fixed_path_in_the_checkout(self):
        a = self._configure(drop=("JAX_COMPILATION_CACHE_DIR",))
        b = self._configure(drop=("JAX_COMPILATION_CACHE_DIR",))
        fixed = os.path.join(REPO, ".jax_cache")
        # the same path from two processes: no pid, time or temp name in it
        assert a["path"] == b["path"] == a["default"] == fixed
        assert a["env"] == a["jax_dir"] == fixed  # children inherit it
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", ".jax_cache/x"], cwd=REPO
        )
        if os.path.isdir(os.path.join(REPO, ".git")):
            assert ignored.returncode == 0, ".jax_cache/ must be git-ignored"

    def test_the_test_suite_itself_leaves_the_cache_off(self):
        import jax

        if "JAX_COMPILATION_CACHE_DIR" in os.environ:
            pytest.skip("a cache was placed from outside")
        assert jax.config.jax_compilation_cache_dir is None


class TestLauncherHandsOutTheDevice:
    def test_cpu_children_get_one_device_and_a_collective_timeout(
        self, monkeypatch
    ):
        from incubator_brpc_tpu.transport import mc_worker

        monkeypatch.setenv(
            "XLA_FLAGS",
            "--xla_force_host_platform_device_count=8 --xla_foo=1",
        )
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        env = mc_worker.child_env("cpu", 1, 3)
        assert env["JAX_PLATFORMS"] == "cpu"
        flags = env["XLA_FLAGS"].split()
        assert "--xla_foo=1" in flags
        assert [f for f in flags if "device_count" in f] == [
            "--xla_force_host_platform_device_count=1"
        ]
        assert (
            "--xla_cpu_collective_timeout_seconds="
            f"{mc_worker.CPU_COLLECTIVE_TIMEOUT_S}" in flags
        )

    def test_tpu_children_get_one_chip_each(self, monkeypatch):
        from incubator_brpc_tpu.transport import mc_worker

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        ports = (7001, 7002, 7003, 7004)
        envs = [mc_worker.child_env("tpu", i, 4, ports) for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
        for i, e in enumerate(envs):
            # named alone: a failed libtpu start raises instead of
            # leaving the worker on the CPU
            assert e["JAX_PLATFORMS"] == "tpu"
            assert e["CLOUD_TPU_TASK_ID"] == str(i)
            assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_ADDRESSES"] == ",".join(
                f"localhost:{p}" for p in ports
            )
        with pytest.raises(ValueError, match="no one-chip-per-process"):
            mc_worker.child_env("tpu", 0, 2, ports[:2])
        with pytest.raises(ValueError, match="unknown platform"):
            mc_worker.child_env("gpu", 0, 2)

    def test_a_tpu_group_never_forms_on_the_cpu(self):
        # no chip here: the four-process launch must fail with the
        # backend's own text, not come up as a CPU group and pass
        from incubator_brpc_tpu.transport import mc_worker

        with pytest.raises(AssertionError, match="initialize backend 'tpu'"):
            mc_worker.orchestrate_fabric(
                n_servers=3, platform="tpu", timeout=90,
                extra=("--n-rpcs", "1"),
            )

    def test_the_smoke_checks_the_platform_the_workers_found(self):
        sys.path.insert(0, REPO)
        try:
            import chip_smoke
        finally:
            sys.path.remove(REPO)

        def stats(platform):
            return {
                "collective": {"steps": 8}, "mc_lowered": {"bytes": 144},
                "links": [
                    {"devices": ["d0", f"d{i}"], "peer_ack": 4,
                     "platforms": [platform, platform]}
                    for i in (1, 2, 3)
                ],
            }

        chip_smoke.check_fabric_stats(stats("tpu"), "tpu")
        with pytest.raises(AssertionError, match=r"workers found \['cpu'\]"):
            chip_smoke.check_fabric_stats(stats("cpu"), "tpu")

    def test_a_worker_never_chooses_its_own_platform(self):
        src = open(
            os.path.join(
                REPO, "incubator_brpc_tpu", "transport", "mc_worker.py"
            )
        ).read()
        assert "jax_platforms" not in src
