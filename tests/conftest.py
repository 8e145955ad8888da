"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "Spark-without-a-cluster" strategy
(SparkTestUtils.sparkTest, local[*]) — distributed code paths are exercised
against 8 fake CPU devices via XLA_FLAGS, no TPU needed for correctness
(SURVEY.md §4 implication). The backend-forcing dance lives in
photon_tpu.utils.virtual_devices, shared with the driver's dryrun entry
point.
"""

import os

# Tests are hermetic: drivers run in-process call configure_compile_cache(),
# and a persistent cache under the checkout would make one run's compiles
# depend on the last one's. Read by JAX at import, inherited by subprocesses.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from photon_tpu.utils.virtual_devices import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Release compiled executables after each test module. A full-suite
    process accumulates hundreds of XLA:CPU programs; past ~260 tests the
    next compilation segfaulted inside backend_compile (observed twice at
    test_variance::test_random_effect_full_variances_vmapped, which passes
    in a fresh process). Bounding the live-executable set keeps the suite
    one process and deterministic."""
    yield
    jax.clear_caches()
