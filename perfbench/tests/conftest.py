"""The benchmark's own tests: ``python -m pytest perfbench/tests -q``.

They run on the CPU backend with four virtual devices (the sharded toy
cell needs them), at toy sizes, and never look for a chip.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)
