"""Test harness config.

Force JAX onto the XLA-CPU backend with 8 virtual devices so model/sharding
tests run without TPU hardware (SURVEY.md §4 "Device tests"): the
environment first, then ``jax.config`` after the import, which wins as long
as no backend has been initialized.  Tests never touch a chip; what runs on
one is ``chip_smoke.py``.  Nothing here describes a TPU topology or loads
libtpu — tests/test_chip_compile.py does that inside a fixture, so that
every pytest-xdist worker collects the same tests.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# Tests (and the processes they start) compile on the CPU backend and keep
# out of the persistent compile cache: its CPU entries are AOT results that
# XLA warns about on load, and nothing here needs them.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()
