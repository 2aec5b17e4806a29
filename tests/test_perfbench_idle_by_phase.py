"""Tier-1 guards the benchmark's own tests: this collects
``perfbench/tests/test_idle_by_phase.py`` as it stands (one thin file a
module, so that ``--dist loadfile`` spreads them over the workers)."""

from perfbench.tests.test_idle_by_phase import *  # noqa: F401,F403
