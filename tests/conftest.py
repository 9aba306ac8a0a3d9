"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding
(shard_map over a Mesh) is exercised without TPU hardware. Must run
before the first `import jax` anywhere in the test session: the XLA
flag and JAX_PLATFORMS are read when the backend starts.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# The compilation cache is the library's one cache
# (jepsen_tpu.tpu.spmd.enable_compile_cache): JAX_COMPILATION_CACHE_DIR
# when set, else <repo>/.jax_cache. Only the thresholds are lowered here
# so the many small CPU test compiles are cached too.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

# Tests run on the CPU; JEPSEN_TPU_TEST_REAL_DEVICE=1 runs them on the
# machine's accelerator instead (tests needing more devices than it has
# then skip via the `mesh`/`devices8` fixtures).
if os.environ.get("JEPSEN_TPU_TEST_REAL_DEVICE") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running e2e, excluded from tier-1 (-m 'not slow')")
    # the wgl kernels donate their packed segment tensors; backends
    # that can't alias them (CPU, which tier-1 forces) warn per
    # compile — pytest resets warning filters, so wgl.py's module
    # filter needs re-asserting here
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable")
