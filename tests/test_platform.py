"""Platform decisions: when a kernel interprets, where the compile cache
lives, and which processes may start an isolated (device-owning) child."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.core import compile_cache, experiment
from repro.core.config import Definition
from repro.core.experiment import (ExperimentSettings, check_can_isolate,
                                   run_definition)
from repro.kernels import interpret_mode

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("backend, interpret", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_interpret_mode_only_on_cpu_backend(backend, interpret):
    assert interpret_mode(backend) is interpret


def test_interpret_mode_follows_the_current_backend():
    assert interpret_mode() is (jax.default_backend() == "cpu")


@pytest.fixture()
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == tmp_path
    # JAX reads the variable itself: no code sets another directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == REPO / ".jax_cache"
    assert compile_cache.enable() == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_isolation_refused_from_accelerator_parent(backend):
    with pytest.raises(RuntimeError, match=f"holds the '{backend}' backend"):
        check_can_isolate(backend)


@pytest.mark.parametrize("backend", [None, "cpu"])
def test_isolation_allowed_without_accelerator(backend):
    check_can_isolate(backend)


def test_isolated_run_refused_before_spawning(monkeypatch):
    """An isolated run from a parent that holds the chip raises at once,
    naming why, instead of starting a child that would hang on it."""
    monkeypatch.setattr(experiment, "held_backend", lambda: "tpu")
    d = Definition(algorithm="bruteforce", constructor="BruteForce",
                   module=None, arguments=("euclidean",),
                   query_argument_groups=((),))
    with pytest.raises(RuntimeError, match="child process cannot get"):
        run_definition(d, "blobs-euclidean-500",
                       ExperimentSettings(count=5, isolated=True,
                                          timeout=60))


CFG = """
float:
  euclidean:
    bruteforce:
      constructor: BruteForce
      base-args: ["@metric"]
"""


def test_isolated_runner_parent_initializes_no_backend(tmp_path):
    """``--isolated``: the children build the dataset and run the index;
    the parent process never initializes a JAX backend."""
    code = (
        "from repro.core.runner import run_benchmark\n"
        "from jax._src import xla_bridge\n"
        f"recs = run_benchmark('blobs-euclidean-500', {CFG!r}, count=5,\n"
        "                     isolated=True, timeout=240, verbose=False)\n"
        "assert len(recs) == 1, recs\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('parent-clean')\n")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu", REPRO_DATA_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "parent-clean" in out.stdout
    assert (tmp_path / "blobs-euclidean-500.npz").exists()
