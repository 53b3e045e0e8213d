"""Central runtime flags: one typed object + env overrides.

The reference's de-facto flag system is ~12 scattered environment
variables (SURVEY.md §5: BIGDL_OPT_IPEX, IPEX_LLM_QUANTIZE_KV_CACHE,
IPEX_LLM_LOW_MEM, BIGDL_LLM_XMX_DISABLED, KV_CACHE_ALLOC_BLOCK_LENGTH...).
Here every knob lives on one dataclass, read once from the environment and
overridable in code — `flags()` is the single source of truth the rest of
the framework consults.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "no", "off")


@dataclasses.dataclass
class RuntimeFlags:
    # kernel dispatch: "auto" (Pallas on TPU when supported), "xla", "pallas"
    matmul_backend: str = "auto"
    # decode-attention dispatch, same values (ops/pallas/decode_attention)
    attention_backend: str = "auto"
    # MoE prefill dispatch: "auto" (sorted ragged kernel on TPU, dense
    # combine elsewhere), "ragged" (force, incl. interpret), "dense"
    moe_dispatch: str = "auto"
    # load-time weight prepacking (ops/quant.prepack_tree): "auto"
    # (retile QTensor planes into the kernel layout when the target is
    # TPU: sym_int4 to int4-dtype codes, which Mosaic loads natively
    # instead of running the VPU nibble-unpack chain), "on" (force the
    # retile anywhere), "off" (keep the canonical split-block planes).
    # Applied ONCE at checkpoint load; save_low_bit always writes
    # canonical planes.
    prepack: str = "auto"
    # resident single-dispatch decode step: fuse forward + sampling +
    # EOS bookkeeping into ONE tracked_jit per token so the serving
    # engine/generator issue a single host dispatch per step. "auto"
    # (on whenever the step has no host-side per-row work: no penalty
    # sampling, no fault hooks), "on" (same gate, assert-style intent),
    # "off" (legacy multi-dispatch step)
    decode_resident: str = "auto"
    # perf-regression sentinel (observability/sentinel.py): "auto"/"on"
    # watch the decode EWMAs against the rolling baseline, "off" skip
    # sentinel construction entirely (zero per-step overhead)
    sentinel: str = "auto"
    # quality observability (observability/quality.py): "auto"/"on"
    # record load-time quantization-error attribution and feed the
    # decode-path quality telemetry + QualitySentinel; "off" skips the
    # dequant round-trip at load and all per-step quality work
    quality: str = "auto"
    # host-side C++ kernels (bigdl_tpu.native); disable to force pure JAX
    disable_native: bool = False
    native_cache_dir: Optional[str] = None
    # default KV-cache storage dtype when the caller doesn't specify:
    # "bf16" | "fp8_e5m2" | "int8" | "int4" (block-scaled codes)
    kv_cache_dtype: str = "bf16"
    # DEPRECATED boolean alias for kv_cache_dtype="fp8_e5m2" (reference
    # IPEX_LLM_QUANTIZE_KV_CACHE); consulted only when kv_cache_dtype
    # is left at its default
    quantize_kv_cache: bool = False
    # default max sequence length for loaded models
    default_max_seq: int = 2048
    # paged KV cache: positions per arena page. 0 = off (per-slot slab);
    # otherwise a power of two that divides max_seq. 128 matches the TPU
    # lane tile (a run of whole pages is the paged Pallas kernel's S-block);
    # smaller values are legal on the XLA fallback path (tests use 16).
    kv_page_size: int = 0
    # paged KV cache: total physical pages in the arena. 0 = auto-size
    # to max_batch * (max_seq / page_size) + 1 (the +1 is the pinned
    # null page) — i.e. the same worst case the slab held. Undersize it
    # deliberately to oversubscribe: admission then rides on prefix
    # sharing actually deduplicating pages.
    kv_pages: int = 0
    # radix-tree prefix sharing across requests (paged mode only):
    # "auto"/"on" share full-page prompt chunks copy-on-write, "off"
    # keeps every sequence's pages private
    prefix_sharing: str = "auto"
    # AOT cross-compilation target: set to "tpu" while LOWERING a program
    # for a TPU topology from a CPU host (tests/test_aot_tpu.py) so kernel
    # dispatch routes to Pallas even though jax.default_backend() is cpu.
    # Compile probes are skipped (they cannot execute on an abstract
    # topology) — Mosaic rejections surface at .compile(), which is the
    # point of the AOT suite.
    aot_target: Optional[str] = None

    @classmethod
    def from_env(cls) -> "RuntimeFlags":
        return cls(
            matmul_backend=os.environ.get("BIGDL_TPU_MATMUL_BACKEND", "auto"),
            attention_backend=os.environ.get(
                "BIGDL_TPU_ATTENTION_BACKEND", "auto"),
            moe_dispatch=os.environ.get("BIGDL_TPU_MOE_DISPATCH", "auto"),
            prepack=_tristate_env("BIGDL_TPU_PREPACK",
                                  lambda s: resolve_prepack(s)),
            decode_resident=_tristate_env(
                "BIGDL_TPU_DECODE_RESIDENT",
                lambda s: resolve_decode_resident(s)),
            sentinel=_tristate_env("BIGDL_TPU_SENTINEL",
                                   lambda s: resolve_sentinel(s)),
            quality=_tristate_env("BIGDL_TPU_QUALITY",
                                  lambda s: resolve_quality(s)),
            disable_native=_env_bool("BIGDL_TPU_DISABLE_NATIVE"),
            native_cache_dir=os.environ.get("BIGDL_TPU_NATIVE_CACHE"),
            kv_cache_dtype=os.environ.get(
                "BIGDL_TPU_KV_CACHE_DTYPE", "bf16").strip().lower() or "bf16",
            quantize_kv_cache=_env_bool("BIGDL_TPU_QUANTIZE_KV_CACHE"),
            default_max_seq=int(os.environ.get("BIGDL_TPU_MAX_SEQ", "2048")),
            kv_page_size=_checked_env(
                "BIGDL_TPU_KV_PAGE_SIZE", resolve_kv_page_size, 0),
            kv_pages=_checked_env("BIGDL_TPU_KV_PAGES", resolve_kv_pages, 0),
            prefix_sharing=_tristate_env(
                "BIGDL_TPU_PREFIX_SHARING",
                lambda s: resolve_prefix_sharing(s)),
            aot_target=(os.environ.get("BIGDL_TPU_AOT_TARGET") or "").strip()
            .lower() or None,
        )


_TRISTATE = ("auto", "on", "off")


def _tristate_env(name: str, resolver) -> str:
    """Resolve a tristate env knob, falling back to "auto" on a bad
    value: a typo must not crash the process at flag load —
    utils/env_check.py runs the same resolver and reports it."""
    try:
        return resolver(os.environ.get(name, "auto"))
    except ValueError:
        return "auto"


def _checked_env(name: str, resolver, default):
    """Resolve a validated (non-tristate) env knob, falling back to
    ``default`` on a bad value — same contract as ``_tristate_env``:
    utils/env_check.py re-runs the resolver and reports the typo."""
    try:
        return resolver(os.environ.get(name, default))
    except ValueError:
        return default


def resolve_kv_page_size(spec) -> int:
    """Normalize a BIGDL_TPU_KV_PAGE_SIZE spec: 0 disables paging,
    otherwise a power-of-two count of token positions per page."""
    try:
        n = int(str(spec).strip() or 0)
    except (TypeError, ValueError):
        raise ValueError(
            f"kv_page_size must be an integer, got {spec!r}")
    if n < 0 or (n and n & (n - 1)):
        raise ValueError(
            f"kv_page_size must be 0 (off) or a power of two, "
            f"got {spec!r}")
    return n


def resolve_kv_pages(spec) -> int:
    """Normalize a BIGDL_TPU_KV_PAGES spec: 0 auto-sizes the arena,
    otherwise a total page count >= 2 (page 0 is the pinned null page)."""
    try:
        n = int(str(spec).strip() or 0)
    except (TypeError, ValueError):
        raise ValueError(f"kv_pages must be an integer, got {spec!r}")
    if n < 0 or n == 1:
        raise ValueError(
            f"kv_pages must be 0 (auto) or >= 2 (page 0 is reserved), "
            f"got {spec!r}")
    return n


def resolve_prefix_sharing(spec) -> str:
    """Normalize a BIGDL_TPU_PREFIX_SHARING spec to "auto"|"on"|"off"."""
    s = str(spec).strip().lower() if spec is not None else "auto"
    s = {"1": "on", "true": "on", "0": "off", "false": "off",
         "": "auto"}.get(s, s)
    if s not in _TRISTATE:
        raise ValueError(
            f"unknown prefix_sharing mode {spec!r}; "
            f"choose from {_TRISTATE}")
    return s


def resolve_prepack(spec) -> str:
    """Normalize a BIGDL_TPU_PREPACK spec to "auto" | "on" | "off"."""
    s = str(spec).strip().lower() if spec is not None else "auto"
    s = {"1": "on", "true": "on", "0": "off", "false": "off",
         "": "auto"}.get(s, s)
    if s not in _TRISTATE:
        raise ValueError(
            f"unknown prepack mode {spec!r}; choose from {_TRISTATE}")
    return s


def resolve_decode_resident(spec) -> str:
    """Normalize a BIGDL_TPU_DECODE_RESIDENT spec to "auto"|"on"|"off"."""
    s = str(spec).strip().lower() if spec is not None else "auto"
    s = {"1": "on", "true": "on", "0": "off", "false": "off",
         "": "auto"}.get(s, s)
    if s not in _TRISTATE:
        raise ValueError(
            f"unknown decode_resident mode {spec!r}; "
            f"choose from {_TRISTATE}")
    return s


def resolve_sentinel(spec) -> str:
    """Normalize a BIGDL_TPU_SENTINEL spec to "auto" | "on" | "off"."""
    s = str(spec).strip().lower() if spec is not None else "auto"
    s = {"1": "on", "true": "on", "0": "off", "false": "off",
         "": "auto"}.get(s, s)
    if s not in _TRISTATE:
        raise ValueError(
            f"unknown sentinel mode {spec!r}; choose from {_TRISTATE}")
    return s


def sentinel_enabled() -> bool:
    """Effective perf-sentinel switch: "off" disables, "on"/"auto"
    enable (the sentinel's own warmup/baseline logic handles the rest)."""
    return flags().sentinel != "off"


def resolve_quality(spec) -> str:
    """Normalize a BIGDL_TPU_QUALITY spec to "auto" | "on" | "off"."""
    s = str(spec).strip().lower() if spec is not None else "auto"
    s = {"1": "on", "true": "on", "0": "off", "false": "off",
         "": "auto"}.get(s, s)
    if s not in _TRISTATE:
        raise ValueError(
            f"unknown quality mode {spec!r}; choose from {_TRISTATE}")
    return s


def quality_enabled() -> bool:
    """Effective quality-observability switch: "off" disables both the
    load-time attribution and the decode-path telemetry/sentinel;
    "on"/"auto" enable."""
    return flags().quality != "off"


def decode_resident_enabled() -> bool:
    """Effective resident-decode switch: "off" disables, "on"/"auto"
    enable (the per-step gate — penalties, fault hooks, logprob rows —
    lives at the call sites, which fall back to the legacy multi-
    dispatch step for work that must run on host)."""
    return flags().decode_resident != "off"


_flags: Optional[RuntimeFlags] = None


def flags() -> RuntimeFlags:
    global _flags
    if _flags is None:
        _flags = RuntimeFlags.from_env()
    return _flags


def default_kv_cache_dtype() -> str:
    """Effective default KV-cache storage dtype from flags.

    `kv_cache_dtype` wins when set to anything but the default; otherwise
    the deprecated `quantize_kv_cache` boolean maps True -> "fp8_e5m2"
    (with its one-time deprecation warning)."""
    from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

    f = flags()
    if f.kv_cache_dtype and f.kv_cache_dtype != "bf16":
        return resolve_kv_cache_dtype(f.kv_cache_dtype)
    return resolve_kv_cache_dtype(f.quantize_kv_cache)


def target_is_tpu() -> bool:
    """True when code will EXECUTE on TPU: the live backend is TPU, or we
    are AOT-lowering for a TPU topology (flags().aot_target == 'tpu').
    Kernel dispatch consults this instead of jax.default_backend(). A
    backend that fails to initialise raises here — answering False would
    send a TPU deployment down the XLA paths without a word."""
    t = flags().aot_target
    if t is not None and t != "tpu":
        raise ValueError(f"unknown aot_target {t!r}; only 'tpu' is supported")
    if t == "tpu":
        return True
    import jax

    return jax.default_backend() == "tpu"


def under_spmd(*arrays) -> bool:
    """True when any array is (being traced as) sharded over a
    multi-device mesh. Pallas kernels cannot be auto-partitioned by
    GSPMD — dispatching one inside a sharded program is a hard compile
    error ("Mosaic kernels cannot be automatically partitioned") — so
    kernel dispatch consults this and falls back to XLA ops, which
    partition cleanly. Explicitly shard_mapped kernel calls (parallel/
    sp.py, cp.py) see LOCAL per-device shapes and are unaffected."""
    for a in arrays:
        sh = getattr(getattr(a, "aval", None), "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is None or getattr(mesh, "size", 0) <= 1:
            continue
        # Manual axes = inside a shard_map body (per-device local view;
        # kernels are legal there) — only Auto/Explicit axes mean GSPMD
        # will partition this op
        from jax.sharding import AxisType

        auto = 1
        for size, t in zip(mesh.axis_sizes, mesh.axis_types):
            if t != AxisType.Manual:
                auto *= size
        if auto > 1:
            return True
    return False


def set_flags(**kwargs) -> RuntimeFlags:
    """Override flags in code (tests, notebooks). Returns the new flags."""
    global _flags
    f = dataclasses.replace(flags(), **kwargs)
    _flags = f
    return f


COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache. Every entry point a
    user starts (api_server, the CLIs, examples, the bench scripts)
    calls this once before its first jit, so a restart finds the
    previous start's executables instead of cold-compiling them.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    no directory is set in code; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move between starts)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
