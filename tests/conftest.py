"""Test harness: force CPU JAX with an 8-device virtual mesh.

Multi-chip sharding (`shard_map`/`psum`) is tested without real TPUs via
``--xla_force_host_platform_device_count`` (SURVEY.md §4); Pallas kernels
run in interpret mode off the chip. ``JAX_PLATFORMS=cpu`` goes into the
environment (subprocesses inherit it) and into the live config (in case
jax was imported first). Backend clients are created lazily on first
use, so doing this in conftest (before any test touches jax) is safe.
The chip itself is driven by ``chip_smoke.py``, never by pytest.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# Persistent XLA compilation cache, shared by the pytest process AND
# every subprocess a test spawns (supervisor children, gang workers,
# CLI chaos runs all re-jit the same small programs). Set via env vars
# rather than jax.config.update so children inherit it; setdefault so
# an operator's own cache dir wins. The zero thresholds matter on CPU:
# this suite's programs are tiny and would otherwise all fall under the
# default min-compile-time cutoff.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "tpu-cooc-xla-cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Fast lane by default (VERDICT r4 Next #8): the soak / sweep /
    multihost / pallas-rect surfaces are minutes each, pushing the
    default suite past CI-feedback territory. They are deselected
    unless the round gate opts back in (``TPU_COOC_FULL_SUITE=1``) or
    the operator's own selection must win: an explicit ``-m``/``-k``
    expression, or a selection consisting ENTIRELY of slow tests
    (``pytest tests/test_multihost.py`` means run exactly those — while
    the driver's ``pytest tests/`` still gets the fast lane because the
    collection is mixed)."""
    if os.environ.get("TPU_COOC_FULL_SUITE", "").lower() in (
            "1", "true", "yes"):
        return
    if config.getoption("-m") or config.getoption("-k"):
        return
    kept = [i for i in items if "slow" not in i.keywords]
    if not kept:
        return  # everything named is slow: the operator asked for it
    deselected = [i for i in items if "slow" in i.keywords]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = kept
