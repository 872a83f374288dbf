"""Test harness: force JAX onto a virtual 8-device CPU mesh.

Stands in for real multi-chip TPU hardware the same way the reference's
(unused) akka-multi-node-testkit would have stood in for a cluster
(SURVEY.md §4). Must run before jax is imported anywhere.
"""

import os
import sys

# the root scripts tests import (scenarios, chip_smoke) live beside
# the package, not in it
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
