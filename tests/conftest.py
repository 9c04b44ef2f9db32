"""Test environment: force an 8-device CPU platform for the whole suite.

This is the TPU-native analogue of the reference's dockerized Flyte demo sandbox
(``tests/integration/test_flyte_remote.py:36-60``): an
``xla_force_host_platform_device_count=8`` CPU mesh stands in for a v5e-8 so
distributed semantics (sharding, collectives, multi-chip compilation) are tested
without TPU hardware (SURVEY.md §4).
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# compile-time dominates the suite's wall-clock on CPU (a single-core box pays
# every XLA optimization pass serially); level 0 cuts compile ~2x with the whole
# suite still green — tests assert semantics, never CPU performance. Benches and
# production paths never read this (it is pytest-conftest scoped).
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

# persistent compilation cache: the suite's wall-clock is dominated by XLA compiles
# of shape-stable programs (parallel/gpt/continuous suites); cache them across runs
# and across test processes. Entries key on program + flags, so the 8-device mesh
# programs and single-device programs coexist.
from unionml_tpu.utils import configure_compile_cache  # noqa: E402 - after the env pins above

configure_compile_cache()
