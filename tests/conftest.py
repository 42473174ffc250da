"""Test configuration.

Tests run on XLA-CPU with 8 virtual devices (the "no real cluster" fake
backend — SURVEY.md §4 TPU plan), so sharding/collective tests exercise the
same mesh code paths the driver validates with dryrun_multichip.
Must set env vars BEFORE jax initializes.
"""

import os

# The virtual 8-device CPU backend, whatever the environment says:
# XLA_FLAGS is read at (lazy) CPU client creation, which has not
# happened yet at conftest time, and the platform is pinned in jax's
# config below so a host with a chip still runs the tests on the CPU.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_ENABLE_X64"] = "0"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import numpy as np
import pytest

# Op-correctness tests check math, not MXU throughput: run matmuls at
# highest precision (bench/production paths use the bf16 default).
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache: CPU-XLA conv compiles are slow (~20s for
# LeNet); cache them across pytest runs.
from paddle_tpu.sysconfig import enable_compile_cache  # noqa: E402

enable_compile_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _seed_framework():
    import paddle_tpu
    paddle_tpu.seed(1234)
    yield
