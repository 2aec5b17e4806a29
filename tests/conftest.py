"""Force an 8-device virtual CPU platform for all tests.

Runs before any test module imports jax: ``JAX_PLATFORMS`` and
``XLA_FLAGS`` alone pin the hermetic suite to eight virtual CPU devices
(backends initialize lazily — on first jax.devices() — which happens
after this).

Accelerator tier (the reference's tests/gpu_tests pattern):
``TPUSNAPSHOT_TPU_TESTS=1 pytest tests/tpu_tests`` keeps the ambient
platform (the real TPU) instead, and there a CPU backend is a failure,
not a skip. The hatch requires BOTH the env var ``== "1"`` and an
invocation whose test paths all lie inside tpu_tests: the hermetic suite
depends on the forced 8-device CPU mesh, so a mixed or broad invocation
(``TPUSNAPSHOT_TPU_TESTS=1 pytest tests/``) keeps the forcing and the
tpu tier self-skips on cpu.
"""

import os
import sys


_TIER_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpu_tests")


def _inside_tier(path: str) -> bool:
    """Whether ``path`` is the tier directory or inside it (anchored to
    the resolved dir — a checkout path merely *containing* "tpu_tests"
    must not satisfy the gate)."""
    p = os.path.abspath(path)
    return p == _TIER_DIR or p.startswith(_TIER_DIR + os.sep)


def _tpu_tier_invocation() -> bool:
    if os.environ.get("TPUSNAPSHOT_TPU_TESTS") != "1":
        return False
    # Positional args that resolve to existing paths (strip ::nodeid).
    paths = [
        a.split("::")[0]
        for a in sys.argv[1:]
        if not a.startswith("-") and os.path.exists(a.split("::")[0])
    ]
    if paths:
        return all(_inside_tier(p) for p in paths)
    # Bare `pytest` run: honor the env var only from inside the tier dir.
    return _inside_tier(os.getcwd())


TPU_TIER = _tpu_tier_invocation()

if not TPU_TIER:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def pytest_sessionstart(session):
    """The accelerator tier on a machine that found no chip is a failed
    run, not an all-skipped green one."""
    if not TPU_TIER:
        return
    import jax
    import pytest

    if jax.default_backend() != "tpu":
        raise pytest.UsageError(
            f"TPUSNAPSHOT_TPU_TESTS=1 asks for the real-accelerator tier "
            f"but JAX's backend is {jax.default_backend()!r}, not 'tpu'."
        )
