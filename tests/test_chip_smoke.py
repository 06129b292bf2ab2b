"""What must hold WITHOUT a chip: the smoke and the benchmark refuse the CPU,
nothing stands in for a device that is not there, importing the package
creates no backend (a chip belongs to one process), the launcher refuses
several processes on a host that has an accelerator, and the compile cache
lives where the environment says or at one fixed place in the checkout."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from paddle_tpu.distributed.fleet.topology import _pick_devices
from paddle_tpu.distributed.launch.controller import (LaunchConfig,
                                                      NodeController)
from paddle_tpu.framework.place import CPUPlace, TPUPlace
from paddle_tpu.models.llama import ParallelConfig, make_mesh
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_on_cpu(*argv, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p, time.monotonic() - t0


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one-chip", "four-chips"])
def test_chip_smoke_fails_fast_without_an_accelerator(args):
    p, seconds = run_on_cpu("chip_smoke.py", *args)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert seconds < 60, f"took {seconds:.0f} s to find no chip"


def test_bench_refuses_to_measure_the_cpu():
    p, _ = run_on_cpu("bench.py")
    assert p.returncode != 0
    assert "nothing is written from a CPU run" in p.stderr
    assert '"metric"' not in p.stdout


def test_importing_the_package_creates_no_backend():
    """A process that has created a backend holds the chip; importing the
    package, the engine, the model and the launcher must not."""
    p, _ = run_on_cpu("-c", (
        "import jax, paddle_tpu, paddle_tpu.inference, "
        "paddle_tpu.models.llama, paddle_tpu.distributed.launch, "
        "paddle_tpu.utils.compile_cache as cc\n"
        "cc.enable_compile_cache()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND')"))
    assert p.returncode == 0 and "NO_BACKEND" in p.stdout, p.stderr[-2000:]


def test_a_place_without_its_device_raises():
    assert CPUPlace(0).jax_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="no 'tpu' backend"):
        TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="only 8 'cpu' device"):
        CPUPlace(8).jax_device()


def test_a_mesh_takes_the_default_backends_devices_or_raises():
    assert _pick_devices(8) == jax.devices()
    with pytest.raises(ValueError, match="default backend .cpu. has 8"):
        _pick_devices(9)
    with pytest.raises(ValueError, match="need 16 devices"):
        make_mesh(ParallelConfig(mp=16))
    assert make_mesh(ParallelConfig(dp=2, mp=4)).devices.size == 8


@pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
def test_launcher_refuses_two_processes_on_an_accelerator_host(
        monkeypatch, platforms):
    """Children inherit the environment and nothing partitions the chips:
    two of them would both take every chip, and hang."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    envs = {} if platforms is None else {"JAX_PLATFORMS": platforms}
    two = NodeController(LaunchConfig(script="x.py", nproc_per_node=2,
                                      envs=envs))
    with pytest.raises(RuntimeError, match="One process drives all local"):
        two.run()
    assert two.server is None and not two.procs      # nothing was started
    for ok in (LaunchConfig(script="x.py", nproc_per_node=1, envs=envs),
               LaunchConfig(script="x.py", nproc_per_node=2,
                            envs={"JAX_PLATFORMS": "cpu"})):
        NodeController(ok)._check_one_process_per_host()


def test_compile_cache_is_where_the_environment_says(monkeypatch, tmp_path):
    configured = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing was set in code
    assert jax.config.jax_compilation_cache_dir == configured


def test_compile_cache_defaults_to_one_place_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
