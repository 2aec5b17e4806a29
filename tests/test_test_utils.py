"""Watch the watchmen: the equality helpers are themselves tested
(reference analog: tests/test_test_utils.py:27-108)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu.utils.test_utils import (
    assert_state_dict_eq,
    check_state_dict_eq,
)


def test_equal_dicts():
    a = {"x": np.arange(4), "y": {"z": jnp.ones(3)}, "s": "str", "n": 5}
    b = {"x": np.arange(4), "y": {"z": jnp.ones(3)}, "s": "str", "n": 5}
    assert check_state_dict_eq(a, b)
    assert_state_dict_eq(a, b)


def test_value_mismatch():
    assert not check_state_dict_eq({"x": np.arange(4)}, {"x": np.arange(1, 5)})


def test_shape_mismatch():
    assert not check_state_dict_eq({"x": np.zeros(3)}, {"x": np.zeros(4)})


def test_dtype_mismatch_exact():
    assert not check_state_dict_eq(
        {"x": np.zeros(3, np.float32)}, {"x": np.zeros(3, np.float64)}
    )


def test_key_mismatch():
    assert not check_state_dict_eq({"x": 1}, {"y": 1})
    assert not check_state_dict_eq({"x": 1}, {"x": 1, "y": 2})


def test_list_and_tuple():
    assert check_state_dict_eq([1, (2, np.ones(2))], [1, (2, np.ones(2))])
    assert not check_state_dict_eq([1, 2], [1, 2, 3])


def test_nan_not_equal_exact():
    assert not check_state_dict_eq(
        {"x": np.array([np.nan])}, {"x": np.array([0.0])}
    )


def test_allclose_mode():
    a = {"x": np.array([1.0])}
    b = {"x": np.array([1.0 + 1e-9])}
    assert not check_state_dict_eq(a, b, exact=True)
    assert check_state_dict_eq(a, b, exact=False)


def test_prng_key_equality():
    a = {"k": jax.random.key(1)}
    b = {"k": jax.random.key(1)}
    c = {"k": jax.random.key(2)}
    assert check_state_dict_eq(a, b)
    assert not check_state_dict_eq(a, c)


def test_mixed_array_and_scalar_not_equal():
    assert not check_state_dict_eq({"x": np.array([1])}, {"x": 1})


def test_statefuls():
    from torchsnapshot_tpu import FnStateful, PytreeStateful

    tree = {"a": np.arange(3), "b": [1, 2]}
    ps = PytreeStateful(tree)
    assert ps.state_dict() is tree
    ps.load_state_dict({"a": np.zeros(3), "b": [0]})
    assert ps.tree["b"] == [0]

    import optax

    opt = optax.adam(1e-3)
    state = opt.init({"w": jnp.ones(3)})
    converted = PytreeStateful(state, convert=True)
    sd = converted.state_dict()
    assert isinstance(sd, dict)
    converted.load_state_dict(sd)
    assert isinstance(converted.tree, tuple)  # NamedTuple structure preserved

    box = {"v": 1}
    fs = FnStateful(lambda: {"v": box["v"]}, lambda sd: box.update(v=sd["v"]))
    assert fs.state_dict() == {"v": 1}
    fs.load_state_dict({"v": 42})
    assert box["v"] == 42


# ------------------------------------------- the per-test limit of conftest.py

_CHILD_TESTS = """
import threading

import pytest


@pytest.mark.time_limit(2)
def test_blocks():
    lock = threading.Lock()
    lock.acquire()
    lock.acquire()  # BLOCKS HERE


@pytest.mark.time_limit(2)
def test_swallows():
    lock = threading.Lock()
    lock.acquire()
    try:
        lock.acquire()
    except BaseException:
        pass  # what the collector does with what a finaliser raises


def test_after():
    pass
"""


@pytest.fixture(scope="module")
def limited_child(tmp_path_factory):
    """A child pytest under this suite's conftest.py, on a file whose
    first two tests block for ever on a lock they hold."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    child_dir = tmp_path_factory.mktemp("limited_child")
    (child_dir / "test_child.py").write_text(_CHILD_TESTS)
    return subprocess.run(
        [sys.executable, "-m", "pytest", "test_child.py", "-q", "-rA",
         "-p", "conftest", "-p", "no:cacheprovider", "-p", "no:xdist"],
        cwd=child_dir,
        env=dict(os.environ, PYTHONPATH=tests_dir),
        capture_output=True,
        text=True,
        timeout=60,
    )


def test_limit_fails_the_blocked_test_and_the_file_runs_on(limited_child):
    out = limited_child.stdout
    assert limited_child.returncode == 1, out + limited_child.stderr
    assert "FAILED test_child.py::test_blocks" in out
    assert "PASSED test_child.py::test_after" in out
    assert "2 failed, 1 passed" in out


def test_limit_dumps_every_stack_naming_the_blocking_line(limited_child):
    blocking_line = 1 + _CHILD_TESTS.splitlines().index(
        "    lock.acquire()  # BLOCKS HERE"
    )
    assert (
        "test_child.py::test_blocks (call) exceeded its time limit of 2 s"
        in limited_child.stderr
    )
    assert (
        f'test_child.py", line {blocking_line} in test_blocks'
        in limited_child.stderr
    )


def test_limit_fails_a_test_that_swallowed_the_expiry(limited_child):
    assert "FAILED test_child.py::test_swallows" in limited_child.stdout
