"""The single homes the chip bring-up made: kernel backend selection
(tpudist.ops.backend), device peaks (tpudist.telemetry.flops), compile
cache placement (tpudist.utils.cache) and the launcher's one-process-per-
chip rule — each decides in one place, and refuses what it cannot know."""

import os
import subprocess
import sys

import jax
import pytest

from tpudist import launch
from tpudist.ops import backend
from tpudist.telemetry import flops
from tpudist.utils import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- kernel backend ----------------------------------------------------------


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_backend_interprets_on_cpu_compiles_on_tpu(monkeypatch, platform,
                                                  want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert backend.interpret() is want


def test_backend_refuses_unknown_platform(monkeypatch):
    """A platform that is merely "not tpu" must not interpret silently."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        backend.interpret()


# -- device peaks ------------------------------------------------------------


def test_device_peaks_v5e_row():
    peak, hbm, source = flops.device_peaks("TPU v5 lite")
    assert (peak, hbm) == (197e12, 819e9)
    assert "TPU v5e" in source


def test_device_peaks_unknown_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        flops.device_peaks("TPU v9")
    # the default reads the running device: the CPU has no row either, so
    # an MFU without an explicit peak is an error here, not a v5e share
    with pytest.raises(KeyError, match="cpu"):
        flops.mfu(1e12, 1.0)


# -- compile cache placement -------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore the process's cache directory config after a placement test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_placed_from_outside_leaves_config_alone(monkeypatch,
                                                       cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert cache.place_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                         cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert cache.place_compile_cache() == want
    assert cache.place_compile_cache() == want  # identical across calls
    assert jax.config.jax_compilation_cache_dir == want
    # ... and across processes: no pid, time, temp name or host hash in it
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tpudist.utils.cache import place_compile_cache; "
         "print(place_compile_cache())"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want


# -- launcher ----------------------------------------------------------------


def test_launcher_refuses_two_processes_on_real_chips(capsys):
    """Without --emulate-devices every child would open the local chips;
    a chip belongs to one process, so the launcher refuses up front."""
    with pytest.raises(SystemExit) as ei:
        launch.main(["--nproc_per_node=2", "child.py"])
    assert ei.value.code == 2
    err = capsys.readouterr().err
    assert "--emulate-devices" in err and "--nnode" in err
