"""chip_smoke.py's contract as far as a CPU can show it: the default
invocation refuses a CPU backend and prints no result; the explicit
rehearsal flag runs the whole flow at toy widths and says it is not a
chip run; the compile cache lands where the environment places it.
"""

import json
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO_ROOT, "chip_smoke.py")


def _run_smoke(tmp_path, *args):
    env = dict(os.environ)
    # conftest already pins JAX_PLATFORMS=cpu and 8 virtual devices; the
    # cache goes to a fresh directory so the checkout stays clean.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    return subprocess.run(
        [sys.executable, _SMOKE, *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(tmp_path),
    )


def test_default_invocation_refuses_a_cpu_backend(tmp_path):
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == "", "no result may be printed"


def test_cpu_rehearsal_runs_the_whole_flow(tmp_path):
    proc = _run_smoke(tmp_path, "--cpu-rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert "NOT A CHIP RUN" in lines[0]
    # Last line: the driver's object, exactly these keys and types.
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    device = verdict["device"]
    assert set(device) == {"platform", "kind", "count"}
    assert device["platform"] == "cpu" and isinstance(device["kind"], str)
    # Eight virtual devices: the sharded leg ran too.
    assert type(device["count"]) is int and device["count"] == 8
    # The line before it: what was observed, claiming nothing.
    doc = json.loads(lines[-2])
    assert doc["chip_run"] is False
    assert list(doc)[-1] == "claim" and doc["claim"] is None
    assert doc["capture_route"] in ("device clones", "host-staging fallback")
    assert set(doc["sharded_restore_s"]) == {"2-way", "2x2"}
    out = proc.stdout
    assert "resumed losses equal the uninterrupted run's exactly" in out
    assert "bit-identical to the host copy taken at save time" in out
    assert "from JAX_COMPILATION_CACHE_DIR" in out
    assert os.listdir(tmp_path / "jax-cache"), "the placed cache was not used"


def test_compile_cache_placement(tmp_path, monkeypatch):
    import jax

    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    before = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
        )
    }
    try:
        # Placed from outside: nothing is set in code.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
        assert configure_compile_cache(str(tmp_path)) == str(tmp_path / "placed")
        assert (
            jax.config.jax_compilation_cache_dir
            == before["jax_compilation_cache_dir"]
        )
        # Not placed: the fixed in-checkout path, the same on every call.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        expected = os.path.join(str(tmp_path), ".jax_cache")
        assert configure_compile_cache(str(tmp_path)) == expected
        assert configure_compile_cache(str(tmp_path)) == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
