"""Bring-up contracts (ISSUE 22), all checked on the CPU in subprocesses so
that nothing here touches this process's JAX: importing tpusim starts no
backend, the compile cache is placed from outside by one rule, and the
paths that must have a chip fail without one instead of skipping or
falling back."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_update=None, env_remove=(), timeout=300):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: not needed
    for name in env_remove:
        env.pop(name, None)
    env.update(env_update or {})
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True,
    )


def test_import_starts_no_backend():
    """A `serve --jobs --workers N` coordinator must not hold the chip its
    worker children need, so importing the package may not initialise a
    backend: with JAX_PLATFORMS naming a backend that does not exist, any
    backend start raises."""
    code = (
        "import tpusim.svc, tpusim.apply, tpusim.sim.driver, tpusim.cli, "
        "tpusim.sim.pallas_engine, tpusim.parallel.shard_engine\n"
        "print('imported')\n"
    )
    r = _run(["-c", code], {"JAX_PLATFORMS": "no_such_backend"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "imported" in r.stdout
    # and the probe itself is sound: touching a device under that setting
    # does fail
    r = _run(["-c", "import jax; jax.devices()"],
             {"JAX_PLATFORMS": "no_such_backend"})
    assert r.returncode != 0


_CACHE_PROBE = (
    "from tpusim.compile_cache import enable_compile_cache\n"
    "d = enable_compile_cache()\n"
    "import jax, jax.numpy as jnp\n"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
    "assert jax.config.jax_compilation_cache_dir == d, "
    "(jax.config.jax_compilation_cache_dir, d)\n"
    "print('CACHE_DIR=' + d)\n"
)


def test_compile_cache_env_wins_and_second_run_writes_nothing(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets no
    directory of its own, and a second identical run finds every
    executable the first one compiled (the rule drops JAX's compile-time
    floor, so even this toy jit is kept)."""
    cache = str(tmp_path / "cc")
    env = {"JAX_COMPILATION_CACHE_DIR": cache, "JAX_PLATFORMS": "cpu"}
    r = _run(["-c", _CACHE_PROBE], env,
             env_remove=("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",))
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"CACHE_DIR={cache}" in r.stdout
    first = sorted(os.listdir(cache))
    assert first, "the first run cached nothing"
    r = _run(["-c", _CACHE_PROBE], env,
             env_remove=("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",))
    assert r.returncode == 0, r.stderr[-2000:]
    assert sorted(os.listdir(cache)) == first


def test_compile_cache_defaults_to_the_checkout():
    """Without the variable the cache is <checkout>/.jax_cache: a fixed
    path, never one made from a temporary name, a pid or the time. The
    floor, too, is the environment's when it sets one — set high here so
    that this probe leaves no entry in the checkout."""
    r = _run(["-c", _CACHE_PROBE],
             {"JAX_PLATFORMS": "cpu",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "3600"},
             env_remove=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"CACHE_DIR={os.path.join(REPO, '.jax_cache')}" in r.stdout


def test_chip_smoke_fails_at_the_device_stage_on_cpu():
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "JAX found no TPU" in r.stderr
    assert '"ok"' not in r.stdout and "[smoke:headline]" not in r.stdout


def test_tpu_lane_fails_without_a_chip():
    """`TPUSIM_TPU_TESTS=1 pytest -m tpu` with no chip is a failure, not a
    skip: a lane that skips reports success for a chip nobody saw."""
    r = _run(
        ["-m", "pytest", "tests/test_tpu.py::test_backend_is_accelerator",
         "-q", "-m", "tpu", "-p", "no:cacheprovider"],
        {"TPUSIM_TPU_TESTS": "1", "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0, r.stdout[-2000:]
    assert "skipped" not in r.stdout and "1 error" in r.stdout


def test_device_stamp_refuses_an_unrequested_cpu(monkeypatch):
    from tpusim.obs.bench import device_stamp

    stamp = device_stamp()  # conftest asked for the CPU
    assert stamp["platform"] == "cpu" and stamp["device_count"] >= 1
    assert set(stamp) == {"platform", "device_kind", "device_count"}
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="not on a TPU"):
        device_stamp()
