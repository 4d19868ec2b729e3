"""These tests are run by hand, ``python -m pytest benchmark/tests``, on
the CPU: four virtual devices, kernels interpreted. They are not part of
the repo's tier-1 run."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ["OKTOPK_PALLAS_INTERPRET"] = "1"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
