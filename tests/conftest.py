"""Test configuration: the suite runs on the CPU backend with 8 virtual
devices (the mesh/sharding tests need >1 device; a bare ``pytest`` on a TPU
host must not grab the chip).  Set before any backend initializes.  Chip
runs happen through ``chip_smoke.py`` and ``benchmarks/run.py``, never under pytest;
the TPU *compiler* is exercised without a chip by tests/test_tpu_compile.py.

The persistent compile cache stays at the package default
(``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``).
"""

import os

import jax

jax.config.update("jax_platforms", "cpu")
# int64 is required by the score kernels' exact-integer arithmetic (it is
# emulated on TPU; float64 is never used so TPU compatibility is preserved).
jax.config.update("jax_enable_x64", True)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
