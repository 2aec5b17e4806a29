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

Every test also runs under a time limit of its own (``pytest-timeout``
is not installed where the suite runs, so this is the standard library):
``TEST_LIMIT_S`` a phase (setup, call, teardown), or what the test asks
for with ``@pytest.mark.time_limit(seconds)``. On expiry every thread's
stack goes to stderr and into the failure, and the test fails in the
main thread; the tests after it run on. A main thread that has not come
back ``HARD_EXIT_GRACE_S`` later is stuck below Python, and the process
exits with a last dump (xdist reports the test and replaces the worker).
The accelerator tier has no limit: chip compiles are long and that tier
runs under ``chiprun --timeout``.
"""

import contextlib
import faulthandler
import os
import signal
import sys
import tempfile

import pytest


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


# ------------------------------------------------------- per-test time limit

# Eight times the slowest test under the driver's ``-n 6`` (21.1 s,
# ``test_dlrm_elastic_resume[sync]``; CHANGES.md, PR 25).
TEST_LIMIT_S = 180.0
HARD_EXIT_GRACE_S = 30.0

_real_stderr_fd = None  # fd 2 as it was before pytest's capture took it


def pytest_configure(config):
    global _real_stderr_fd
    if not TPU_TIER and _real_stderr_fd is None:
        # Capture is suspended while plugins are configured, so fd 2 is
        # still the terminal (or the xdist controller's pipe) here.
        _real_stderr_fd = os.dup(2)


def _inflight_file(config):
    """Where an xdist worker says which test it is in. ``--dist
    loadfile`` hands a file whose worker died to the next worker whole,
    the test that killed it included (xdist 3.8.0, ``LoadScopeScheduling.
    remove_node``): the record a dead worker leaves is how the next one
    knows not to run that test again. The workers' temporary directories
    are siblings under the run's own, which pytest prunes."""
    if not hasattr(config, "workerinput"):
        return None
    shared = config._tmp_path_factory.getbasetemp().parent / "inflight"
    return shared / config.workerinput["workerid"]


def _ended_a_worker(item) -> bool:
    mine = _inflight_file(item.config)
    if mine is None or not mine.parent.is_dir():
        return False
    for other in mine.parent.iterdir():
        try:
            if other != mine and other.read_text() == item.nodeid:
                return True
        except FileNotFoundError:  # a live worker, between two tests
            pass
    return False


def _dump_all_stacks() -> str:
    with tempfile.TemporaryFile(mode="w+") as f:
        faulthandler.dump_traceback(file=f, all_threads=True)
        f.seek(0)
        return f.read()


@contextlib.contextmanager
def _time_limit(item, phase):
    """Bound one phase of one test. The expiry is recorded as well as
    raised: the collector, and ``except`` nets, swallow what is raised
    inside them, and a test whose limit fired has failed whatever came
    back up."""
    if TPU_TIER or _real_stderr_fd is None:
        yield
        return
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_LIMIT_S
    expired = []

    def on_alarm(signum, frame):
        message = (
            f"{item.nodeid} ({phase}) exceeded its time limit of "
            f"{limit:g} s; every thread's stack:\n{_dump_all_stacks()}"
        )
        os.write(_real_stderr_fd, f"\n{message}\n".encode())
        expired.append(message)
        pytest.fail(message, pytrace=False)

    inflight = _inflight_file(item.config)
    if inflight is not None:
        if phase == "setup" and _ended_a_worker(item):
            pytest.fail(
                f"{item.nodeid} ended a worker {HARD_EXIT_GRACE_S:g} s "
                "past its time limit on an earlier attempt (the dump is "
                "on stderr); not run again",
                pytrace=False,
            )
        inflight.parent.mkdir(exist_ok=True)
        inflight.write_text(item.nodeid)
    previous = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(
        limit + HARD_EXIT_GRACE_S, exit=True, file=_real_stderr_fd
    )
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)
        if inflight is not None:
            inflight.unlink()
        if expired:
            pytest.fail(expired[0], pytrace=False)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item, "teardown"):
        return (yield)
