"""Test config: force a virtual 8-device CPU mesh BEFORE jax initializes
(SURVEY §4: CPU-mesh fixture pattern)."""
import os

# hard override: unit tests run on the virtual CPU mesh whatever the
# environment says; the chip is reached with `python chip_smoke.py`
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# a platform configured before this file was imported must not win either
jax.config.update("jax_platforms", "cpu")

# numeric golden tests need true-f32 matmuls (the TPU-native default is
# bf16-pass matmul, below finite-difference resolution)
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield
