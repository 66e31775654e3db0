"""Shared test harness configuration.

Tests run on the CPU (`JAX_PLATFORMS=cpu`), with the Pallas kernels in
interpret mode. The only fixture drops JAX's compilation caches at module
boundaries: each test module leaves its jitted executables cached, and
bounding the number of live executables keeps the CPU client's memory
flat across a suite of several hundred compiles. The cost is a handful
of recompiles per module. (It was added when an older jaxlib's CPU
compiler crashed late in the suite; no run has yet shown the installed
JAX without it.)
"""
import os
import sys

import jax
import pytest

# chip_smoke.py lives at the repository root; its phases are rehearsed here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    yield
    jax.clear_caches()
