"""Compile-only kernel probes.

Per-geometry dispatch probes (ops/attention._kernel_compiles,
ops/pallas/dequant_matmul.gemv_kernel_compiles and
matmul_kernel_compiles, ops/matmul.vmapped_pallas_ok,
ops/pallas/moe_dispatch.ragged_kernel_compiles) compile the kernel a
dispatch site is about to use, at that site's geometry, from INSIDE a
model's outer jit trace.

They run only where the kernel is the designed choice: the live backend
is a TPU and dispatch is "auto". There a kernel Mosaic refuses is a
defect, so a failed probe RAISES `KernelProbeError` carrying the
compiler's message (and counts one `outcome="fallback"` first, so the
scrape shows it). An earlier contract logged a warning and pinned the
geometry to XLA; the first full chip bench then ran 0 of 4 kernel
families and reported success. Shapes for which XLA is the designed
choice (rows past the measured crossover, GSPMD-sharded operands) never
reach a probe — dispatch counts them with `record_dispatch_rule` under
their own label.

The probe is AOT lower+compile from abstract `ShapeDtypeStruct`s:
nothing executes, no device buffers are allocated next to a resident
multi-GB model, and the fresh `jax.jit(...).lower()` trace is
independent of any ambient trace, so no tracer leaks in or out. (A tiny
concrete call under `jax.ensure_compile_time_eval()` does not work: on
a live TPU grid primitives have no eager eval rule.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class KernelProbeError(RuntimeError):
    """The TPU compiler refused a Pallas kernel that auto dispatch
    selected for this geometry."""


def _probe_counter():
    from bigdl_tpu.observability.metrics import default_registry

    return default_registry().counter(
        "bigdl_tpu_kernel_probe_total",
        "Kernel dispatch outcomes per kernel: compile probe passed "
        "(compiled), probe refused by the compiler (fallback; raises), "
        "XLA chosen by a dispatch rule (xla_by_rule); a stacked linear "
        "of a layer scan whose kernel reads the layer where it lies "
        "(stack_in_place) or that took an XLA plan and sliced the layer "
        "out (stack_by_value).",
        labelnames=("kernel", "outcome"))


def record_probe_result(kernel: str, ok: bool) -> None:
    """Count a probe outcome in the observability registry
    (bigdl_tpu_kernel_probe_total{kernel, outcome="compiled"|"fallback"}),
    once per new geometry."""
    _probe_counter().labels(kernel, "compiled" if ok else "fallback").inc()


def record_dispatch_rule(kernel: str) -> None:
    """Count a dispatch that took XLA BY DESIGN (rows above
    `matmul.PALLAS_MAX_ROWS`, operands sharded under GSPMD) — a rule, not a
    probe outcome, so `outcome="fallback"` keeps meaning "the compiler
    refused a kernel". Trace-time counts, like the probes."""
    _probe_counter().labels(kernel, "xla_by_rule").inc()


def record_stacked(kernel: str, in_place: bool) -> None:
    """Count one linear of a layer scan over stacked weights
    (`ops/matmul.StackedQ`): `stack_in_place` where a kernel plan
    addresses the layer inside the stack, `stack_by_value` where an XLA
    plan took the layer out first. Trace-time counts, like the probes:
    once per linear per traced program."""
    _probe_counter().labels(
        kernel, "stack_in_place" if in_place else "stack_by_value").inc()


def probe_compile(fn, *arg_structs) -> None:
    """AOT-compile `fn` for the ambient backend from abstract shapes.
    Raises whatever the lowering/compilation raises. Safe while tracing
    an outer jit: only ShapeDtypeStructs cross the boundary.

    The structs are rebuilt from (shape, dtype) alone: one made inside
    a shard_map body (`quant_struct`'s eval_shape) carries a sharding
    over that trace's ABSTRACT mesh, and lowering a fresh jit from it
    fails ("only AbstractMesh exists in a jitted computation") — met on
    the four-chip smoke, where every probe of the explicit-TP path used
    to die this way and pin the shards to XLA without a word.
    """
    arg_structs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), arg_structs)
    jax.jit(fn).lower(*arg_structs).compile()


def probe_kernel(kernel: str, cache: set, key, fn, *arg_structs) -> bool:
    """Compile `fn` once per `key`; True when the compiler accepts it
    (remembered in `cache`), `KernelProbeError` when it does not."""
    if key in cache:
        return True
    try:
        probe_compile(fn, *arg_structs)
    except Exception as e:  # noqa: BLE001 — Mosaic/XLA raise many types
        record_probe_result(kernel, False)
        raise KernelProbeError(
            f"pallas {kernel} kernel refused by the TPU compiler at "
            f"geometry {key}: {type(e).__name__}: {e}") from e
    record_probe_result(kernel, True)
    cache.add(key)
    return True


def stacked_struct(tree, n: int):
    """ShapeDtypeStruct pytree of `tree` with a leading axis of `n`
    prepended to every leaf (QTensor-safe) — abstract analog of
    `jax.tree.map(lambda a: jnp.stack([a] * n), tree)`."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)


def quant_struct(k: int, n: int, qtype: str, mxu: bool = False):
    """Abstract QTensor [k, n] for `qtype` — the shapes/dtypes quantize()
    would produce, computed without materializing anything (eval_shape
    stays fully abstract for the jnp-only sym/asym/codebook encoders the
    Pallas kernels support). `mxu` applies the int4-dtype MXU layout
    (quant.to_mxu_layout) to the abstract result."""
    from bigdl_tpu.ops.quant import quantize, to_mxu_layout

    def build():
        qt = quantize(jnp.zeros((k, n), jnp.float32), qtype)
        return to_mxu_layout(qt) if mxu else qt

    return jax.eval_shape(build)
