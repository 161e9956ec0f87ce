"""Test configuration: force an 8-device virtual CPU platform.

The sandbox the tests run in has no accelerator; sharding/collective tests
run on a virtual 8-device CPU mesh exactly as the driver's dryrun does. The
TPU execution path itself is exercised by chip_smoke.py on the real chip;
``MXTPU_TEST_PLATFORM=tpu`` runs a test file there instead (the kernel
suites, one pytest process per chip).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The package places JAX's persistent compile cache at <repo>/.jax_cache
# on import (compile/cache.py). Tests — and the workers they spawn, which
# inherit this — leave it unused: a run must not depend on what an
# earlier run compiled, and XLA:CPU logs two screens of target-feature
# warnings per entry it loads.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax

jax.config.update("jax_platforms", os.environ.get("MXTPU_TEST_PLATFORM", "cpu"))

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_everything():
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield
