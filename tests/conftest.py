"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding (TP/DP/SP) is tested on a virtual CPU mesh, the
multi-chip-simulatable test layer the reference lacks (SURVEY.md §4):
`--xla_force_host_platform_device_count=8` gives 8 XLA CPU devices so
pjit/shard_map collectives execute for real, single-host.
"""

import os

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
# (tests/test_memory_ledger.py sets BIGDL_TPU_COMPILE_MEMORY=1).
os.environ.setdefault("BIGDL_TPU_COMPILE_MEMORY", "0")

# The AOT suite builds offline TPU topologies via libtpu, which by default
# queries the GCE metadata server for worker identity. Off-GCE (or when the
# metadata service answers 403) that is 30 retries per variable — minutes
# of wall stall per pytest process before the query gives up and AOT
# lowering proceeds identically. Nothing in the CPU suite runs on a real
# TPU worker, so skip the query outright.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

# Entry points the tests call in-process (the CLIs, examples) turn on
# the persistent compilation cache; tests compile everything fresh.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

import jax  # noqa: E402

# If jax was already imported by a pytest plugin before this conftest ran,
# the env var is too late — force the CPU via config too.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


import pytest  # noqa: E402


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
