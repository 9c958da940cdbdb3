"""Test harness: an 8-device virtual CPU mesh, so unit tests run anywhere
without touching TPU hardware. JAX_PLATFORMS=cpu is set here, before jax
is imported, which is all the installed JAX needs.

TPU lane: `TPUSIM_TPU_TESTS=1 pytest -m tpu` leaves the platform alone and
runs only the `tpu`-marked on-device tests (tests/test_tpu.py) — golden
frag values and engine equivalence asserted on real TPU numerics. With no
chip that lane FAILS (tests/test_tpu.py); it never skips. Without the env
var, tpu-marked tests are skipped and everything else runs on the virtual
CPU mesh.
"""

import os

import pytest

TPU_LANE = os.environ.get("TPUSIM_TPU_TESTS") == "1"

if not TPU_LANE:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Drop every compiled executable when a test module ends. Each live
    XLA:CPU executable holds several memory mappings and nothing in a
    pytest process ever frees them: the seed's tier-1 run reached
    vm.max_map_count (65,530 here) after ~350 tests, the next mmap failed
    and the interpreter died (SIGSEGV or SIGABRT inside whatever native
    code allocated next — it happened to be the compile cache's
    executable.serialize())."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: on-accelerator tests (TPUSIM_TPU_TESTS=1 pytest -m tpu)"
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 lane (pytest -m 'not slow'); run "
        "explicitly via `make resume-smoke` or plain pytest",
    )


def pytest_collection_modifyitems(config, items):
    if TPU_LANE:
        return
    skip = pytest.mark.skip(reason="TPU lane disabled (set TPUSIM_TPU_TESTS=1)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
