"""Tier-1 guards the benchmark's own tests: this collects
``perfbench/tests/test_job_sdar.py`` as it stands (one thin file a
module, so that ``--dist loadfile`` spreads them over the workers)."""

from perfbench.tests.test_job_sdar import *  # noqa: F401,F403
