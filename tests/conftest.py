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

# ONE persistent compile cache for the tests, in the temporary directory
# beside `paddle_tpu_extensions`: tier-1 is compiles (a tiny step is built
# again by each test that drives it, by each of the six workers and by
# each process a test starts), and the same program is compiled once a
# machine, not once a `jax.jit`. The process that starts the run names the
# directory and its workers and their children inherit it; a directory the
# environment already names is JAX's and nothing is set here. Every
# program is kept, the one-operation ones too (13,300 entries and 160 MB
# a cold run at PR 50); the directory is emptied where it has passed
# `_TEST_CACHE_MAX_BYTES` (JAX's own bound lists the directory at every
# write). A key is the program's text, its compile options and the
# installed jaxlib, so an entry from another tree is never a wrong one.
_TEST_CACHE_MAX_BYTES = 1 << 30
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import shutil
    import tempfile
    _cache = os.path.join(tempfile.gettempdir(), "paddle_tpu_tests_jax_cache")
    try:
        with os.scandir(_cache) as entries:
            if sum(e.stat().st_size
                   for e in entries) > _TEST_CACHE_MAX_BYTES:
                shutil.rmtree(_cache, ignore_errors=True)
    except OSError:
        pass
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

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
