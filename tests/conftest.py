"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding (TP/DP/SP) is tested on a virtual CPU mesh, the
multi-chip-simulatable test layer the reference lacks (SURVEY.md §4):
`--xla_force_host_platform_device_count=8` gives 8 XLA CPU devices so
pjit/shard_map collectives execute for real, single-host.
"""

import glob
import os
import shutil
import tempfile
import time

# Unit tests run on the virtual CPU mesh, unconditionally.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Per-compile memory_analysis capture (observability/compile_watch) goes
# through jax's AOT path, whose executable cache is separate from the
# traced-call cache: every first call per signature would pay a SECOND
# full XLA compile. Across the whole suite (hundreds of executables in
# one process) that doubles compile wall time and has crashed XLA's CPU
# compiler under the accumulated load — so CI runs with capture off,
# keeping the compile count identical to an uninstrumented run. The
# capture path itself is exercised by tests that explicitly opt in
# (tests/test_memory_ledger.py sets BIGDL_TPU_COMPILE_MEMORY=1). The
# program's own default is off too since PR 55; said here all the same.
os.environ.setdefault("BIGDL_TPU_COMPILE_MEMORY", "0")

# The AOT suite builds offline TPU topologies via libtpu, which by default
# queries the GCE metadata server for worker identity. Off-GCE (or when the
# metadata service answers 403) that is 30 retries per variable — minutes
# of wall stall per pytest process before the query gives up and AOT
# lowering proceeds identically. Nothing in the CPU suite runs on a real
# TPU worker, so skip the query outright.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

# One compile cache a run. An `LLMEngine` makes its jitted closures anew
# and most tests build one, so without a cache every engine, every xdist
# worker and every child process a test starts (router replicas,
# chip_smoke's phases, `benchmark/run.py --tiny`) compiles the same tiny
# programs again: compile time was most of the suite's. The process that
# starts the run (the xdist controller, or the only process) makes an
# EMPTY directory and exports it before jax is imported; workers and
# children inherit the environment, so a program is compiled once a run,
# by whoever needs it first. A run starts cold and removes its directory
# at session finish: no run sees another's state. A test that needs a
# cold compile, or whose executables the cache cannot read back, turns
# the cache off for its module with `no_compile_cache` below.
_RUN_CACHE_PREFIX = "bigdl_tpu_test_jax_cache_"
_run_cache_dir = None   # set only in the process that made it


def _start_run_cache():
    global _run_cache_dir
    if "PYTEST_XDIST_WORKER" in os.environ:
        return          # the controller made it; the variables are set
    # a run killed by a time limit cannot clean up after itself
    for old in glob.glob(os.path.join(tempfile.gettempdir(),
                                      _RUN_CACHE_PREFIX + "*")):
        try:
            stale = time.time() - os.path.getmtime(old) > 86400
        except OSError:
            continue    # another run removed it first
        if stale:
            shutil.rmtree(old, ignore_errors=True)
    _run_cache_dir = tempfile.mkdtemp(prefix=_RUN_CACHE_PREFIX)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache_dir
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


_start_run_cache()


def pytest_sessionfinish(session):
    if _run_cache_dir is not None:
        shutil.rmtree(_run_cache_dir, ignore_errors=True)


import jax  # noqa: E402

# If jax was already imported by a pytest plugin before this conftest ran,
# the env var is too late — force the CPU via config too.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _run_cache_write_threshold():
    """An entry point called in-process (the CLIs, the examples) raises
    the cache's write threshold for its process to a deployment's 1 s
    (`config.enable_compilation_cache`); left so, that worker would
    share only its slow compiles for the rest of the run."""
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@pytest.fixture(scope="module")
def no_compile_cache():
    """The run's compile cache off for one module: for tests that need
    a cold compile, and for `tests/test_aot_tpu.py`, whose executables
    the cache can write and not read back. JAX decides once a process
    whether the cache is used, hence the resets."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture()
def forced_pallas(monkeypatch):
    """`matmul_backend="pallas"` with the dequant-matmul kernels in
    interpret mode: a model's forward runs them on the CPU. Every
    linear's N must be a multiple of 128 (a forced plan raises where
    the kernel has no tiling)."""
    import bigdl_tpu.ops.pallas.dequant_matmul as dq
    from bigdl_tpu.config import set_flags

    real = dq.q_matmul_kernel
    monkeypatch.setattr(
        dq, "q_matmul_kernel",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    set_flags(matmul_backend="pallas")
    yield
    set_flags(matmul_backend="auto")
