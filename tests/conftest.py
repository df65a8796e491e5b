"""Test harness: the tests run on the CPU, with 8 virtual devices so the
multi-chip sharding tests run anywhere (SURVEY.md §4).

``jax_platforms`` is set in config as well as by the driver's
``JAX_PLATFORMS=cpu``, so a bare ``pytest`` never reaches for a chip.
XLA_FLAGS works because the CPU client is created lazily (the first
``jax.devices()`` happens inside the tests). The persistent compilation
cache stays off: ``run_sim.main`` points it at ``<repo>/.jax_cache``, and
CPU test compiles have no business there.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
