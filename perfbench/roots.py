"""A run owns its snapshot root.

Every run makes its root with ``tempfile.mkdtemp`` under one parent
directory (under the system temp dir, which the driver gives each side
of a comparison its own), removes it on every way out, and at start
removes any root a former run left under that parent: no step number,
marker or file of another run can ever be resolved, and a killed run's
gigabytes do not pile up on the machine. A root's name carries its
owner's process id, and the sweep leaves the root of a process that is
still alive, so two runs side by side on one host do not undo each other.
"""

import contextlib
import os
import shutil
import tempfile

_PARENT_NAME = "perfbench-roots"


def default_parent() -> str:
    return os.path.join(tempfile.gettempdir(), _PARENT_NAME)


def _owner_alive(name: str) -> bool:
    """Whether ``run-<pid>-...`` names a process that still runs."""
    parts = name.split("-")
    if len(parts) < 3 or parts[0] != "run" or not parts[1].isdigit():
        return False
    try:
        os.kill(int(parts[1]), 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def sweep_former_roots(parent: str) -> list:
    """Removes whatever former runs left under ``parent``; returns the
    names removed."""
    if not os.path.isdir(parent):
        return []
    removed = []
    for name in sorted(os.listdir(parent)):
        if _owner_alive(name):
            continue
        path = os.path.join(parent, name)
        if os.path.isdir(path) and not os.path.islink(path):
            shutil.rmtree(path, ignore_errors=True)
        else:
            with contextlib.suppress(FileNotFoundError):
                os.remove(path)
        removed.append(name)
    return removed


@contextlib.contextmanager
def run_root(parent: str = None):
    """Yields a fresh root under ``parent``; it is gone when the block
    ends, however it ends."""
    parent = parent or default_parent()
    os.makedirs(parent, exist_ok=True)
    sweep_former_roots(parent)
    root = tempfile.mkdtemp(prefix=f"run-{os.getpid()}-", dir=parent)
    try:
        yield root
    finally:
        shutil.rmtree(root, ignore_errors=True)
