"""Scaled-dot-product attention over the static KV cache.

TPU-native equivalent of the reference's attention dispatch surface: the
prefill flash/native_sdp paths and the decode `sdp_fp8`/ESIMD `sdp_forward`
kernels (reference transformers/models/llama.py:1320-1349, models/utils.py:
315-355 gates, and the SYCL ops inventoried in SURVEY.md §2.3-C/D).

One function serves prefill and decode: queries carry explicit positions, so
causal masking and cache-tail masking collapse into a single comparison —
no separate mask tensors, no dynamic shapes, garbage in the unwritten cache
tail is masked because key_pos > query_pos there. GQA is computed by
reshaping queries to [.., kv_heads, group, ..] (no KV head replication, which
would multiply HBM traffic by the group size).

FP8 KV: pass e5m2 k/v straight in — the upcast happens inside and XLA fuses
it into the QK/AV matmul operand reads (the reference needs dedicated
`query_key_fp8_matmul` kernels for this; XLA gets it from fusion).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


_probe_cache: set = set()


def _note_dequant_path(kv_dtype_name: str, path: str) -> None:
    """Count which dequant path a quantized-KV attention dispatch took
    ("fused" Pallas kernel vs "xla" ops). Trace-time counts: each
    (shape, dtype) combination increments once per trace, not once per
    executed step — enough to tell WHICH path a deployment is on."""
    from bigdl_tpu.observability import default_registry

    default_registry().counter(
        "bigdl_tpu_kv_dequant_path_total",
        "KV-cache dequantization dispatches by storage dtype and "
        "path (fused kernel vs XLA ops); trace-time counts",
        labelnames=("dtype", "path")).labels(kv_dtype_name, path).inc()


def _kernel_compiles(kind: str, h: int, hkv: int, hd: int, sq: int,
                     skv: int, kv_dtype_name: str) -> bool:
    """Compile probe, cached PER GEOMETRY, for the Pallas attention
    kernel auto dispatch is about to use on a live TPU (contract in
    ops/probing.py: True, or `KernelProbeError` with the compiler's
    message — never a quiet XLA run). Mosaic failures can be
    shape-dependent, so every geometry is probed once; callers
    normalize `sq` to the kernel's block class (prefill lengths vary
    per request; every class needs only one probe compile). Forced
    "pallas" mode bypasses the probe and raises at the call itself."""
    from bigdl_tpu.config import flags as _flags

    if _flags().aot_target == "tpu":
        # AOT lowering for a topology: Mosaic rejections surface at the
        # caller's own .compile()
        return True
    if kind == "decode":
        from bigdl_tpu.ops.pallas.decode_attention import (
            decode_attention_pallas as kernel)
    elif kind == "paged_decode":
        from bigdl_tpu.ops.pallas.paged_decode_attention import (
            paged_decode_attention_pallas as kernel)
    else:
        from bigdl_tpu.ops.pallas.prefill_attention import (
            prefill_attention_pallas as kernel)
    from bigdl_tpu.ops.probing import probe_kernel

    key = (kind, h, hkv, hd, sq, skv, kv_dtype_name)
    kdt = jnp.dtype(kv_dtype_name)
    scaled = kv_dtype_name in ("int8", "int4")
    f32, i32 = jnp.float32, jnp.int32
    if kind == "paged_decode":
        # paged probe overloads the key slots: sq carries page_size,
        # skv carries the block-table width (logical pages)
        ps, np_ = sq, skv
        from bigdl_tpu.ops.kvcache import kv_dtype_name as canonical
        from bigdl_tpu.ops.paged import init_paged_cache

        arena = jax.eval_shape(lambda: init_paged_cache(
            1, np_ + 1, ps, hkv, hd, 1, kv_cache_dtype=canonical(kdt)))
        structs = [jax.ShapeDtypeStruct((1, 1, h, hd), jnp.bfloat16),
                   arena.k, arena.v,
                   jax.ShapeDtypeStruct((1, np_), i32),
                   jax.ShapeDtypeStruct((1,), i32)]
        sc = arena.k_scale

        def fn(q_, k_, v_, b_, p_, ks=None, vs=None):
            return kernel(q_, k_, v_, b_, p_, hd ** -0.5, hkv,
                          k_scale=ks, v_scale=vs)
    else:
        # the decode kernel takes the [L, B, S, Hkv, hd] stack; prefill
        # one layer
        lead = (1, 1) if kind == "decode" else (1,)
        kv = jax.ShapeDtypeStruct(lead + (skv, hkv, hd), kdt)
        structs = [jax.ShapeDtypeStruct((1, sq, h, hd), jnp.bfloat16),
                   kv, kv, jax.ShapeDtypeStruct((), i32)]
        sc = jax.ShapeDtypeStruct(lead + (skv, hkv), f32)

        def fn(q_, k_, v_, p_, ks=None, vs=None):
            return kernel(q_, k_, v_, p_, hd ** -0.5,
                          k_scale=ks, v_scale=vs)
    if scaled:
        # block-scaled codes probe with their f32 scale planes — the
        # scaled kernel bodies are distinct Mosaic programs
        structs += [sc, sc]
    return probe_kernel(f"{kind}_attention", _probe_cache, key, fn,
                        *structs)


def _live_window(sliding_window, skv: int):
    """A static window at least as long as the whole cache masks
    nothing (key j is cut only when j <= q_pos - window < 0): drop it,
    so e.g. Mistral's published 4096 window served at max_seq 2048
    keeps the Pallas kernels of this dispatch, which implement no
    window (a live window takes the XLA ops here; the one window
    kernel, `ops/swa.window_decode`'s, reads a K/V ring instead)."""
    if isinstance(sliding_window, int) and sliding_window >= skv:
        return None
    return sliding_window


def sdp_attention(
    q: jax.Array,          # [B, Sq, H, D] (post-RoPE)
    k: jax.Array,          # [B, Skv, Hkv, D] (cache slice; any storage dtype)
    v: jax.Array,          # [B, Skv, Hkv, D]
    q_pos: jax.Array,      # scalar int32: absolute position of q[..., 0, ...]
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,   # [H] f32 (bloom families)
    backend: Optional[str] = None,   # overrides flags().attention_backend
    k_scale: Optional[jax.Array] = None,   # [B, Skv, Hkv] f32: int8/int4
    v_scale: Optional[jax.Array] = None,   # codes' per-(token, head) scales
    layer: Optional[jax.Array] = None,     # k/v (and scales) are the whole
                                           # [L, ...] stack; attend this layer
) -> jax.Array:
    """Causal SDP against a (possibly partially-filled) KV cache.

    Query i attends keys j where j <= q_pos + i (and within the sliding
    window if set). Returns [B, Sq, H, D] in q.dtype. Softmax in f32.
    A per-slot `q_pos` below 0 marks a slot that holds nothing (serving's
    empty slots): its row is finite and means nothing — zeros from the
    decode kernel, which multiplies no key for it; the XLA ops read key 0.

    Decode (Sq=1) on TPU dispatches to the fused Pallas kernel
    (ops/pallas/decode_attention — the reference's `sdp_fp8`/ESIMD
    `sdp_forward` equivalent) unless BIGDL_TPU_ATTENTION_BACKEND=xla.

    Block-scaled KV (kv_cache_dtype int8/int4): pass the raw code planes
    as k/v plus their scale planes — the kernels dequantize in-register;
    the XLA fallback upcasts codes * scales before the einsums.

    With `layer`, k/v (and the scale planes) are the cache's whole
    `[L, B, Skv, ...]` stack: the decode kernel addresses the layer in
    place (no slab-sized slice inside a layer scan); every other path
    slices it here.
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[-3], k.shape[-2]
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    sliding_window = _live_window(sliding_window, skv)
    quant_name = (str(k.dtype)
                  if k.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32)
                  else None)

    from bigdl_tpu.config import flags, target_is_tpu, under_spmd

    be = backend or flags().attention_backend
    if be == "auto" and under_spmd(q, k, v):
        # GSPMD cannot auto-partition Mosaic kernels (hard compile
        # error); sharded programs take the XLA ops, which partition
        # cleanly — explicitly shard_mapped paths (parallel/sp, cp)
        # still reach the kernels with local shapes
        be = "xla"
    if be in ("auto", "pallas"):
        from bigdl_tpu.ops.pallas.decode_attention import (
            decode_attention_pallas, decode_attention_supported)

        supported = decode_attention_supported(
            q, k, v, q_pos, scale, logits_soft_cap, sliding_window,
            alibi_slopes, k_scale)
        on_tpu = target_is_tpu()
        if supported and (be == "pallas" or on_tpu and _kernel_compiles(
                "decode", h, hkv, d, 1, skv, str(k.dtype))):
            if quant_name:
                _note_dequant_path(quant_name, "fused")
            if layer is None:        # one layer held: a stack of one
                k, v = k[None], v[None]
                if k_scale is not None:
                    k_scale, v_scale = k_scale[None], v_scale[None]
            return decode_attention_pallas(
                q, k, v, q_pos, float(scale), interpret=not on_tpu,
                k_scale=k_scale, v_scale=v_scale,
                layer=0 if layer is None else layer)
    if layer is not None:       # every other path works on the one layer
        k, v, k_scale, v_scale = (
            x if x is None else jax.lax.dynamic_index_in_dim(
                x, layer, 0, keepdims=False)
            for x in (k, v, k_scale, v_scale))
    if be in ("auto", "pallas"):
        from bigdl_tpu.ops.pallas.prefill_attention import (
            prefill_attention_pallas, prefill_attention_supported)

        # blockwise prefill (flash): scores never touch HBM — the win
        # grows with S * S_max (the pre-allocated cache is read once);
        # scalar positions only (serving prefills per slot at Sq=1)
        pre_ok = (getattr(q_pos, "ndim", 0) == 0
                  and prefill_attention_supported(
                      q, k, v, q_pos, scale, logits_soft_cap,
                      sliding_window, alibi_slopes, k_scale))
        if pre_ok and be == "pallas":
            if quant_name:
                _note_dequant_path(quant_name, "fused")
            return prefill_attention_pallas(q, k, v, q_pos, float(scale),
                                            interpret=not on_tpu,
                                            k_scale=k_scale, v_scale=v_scale)
        # probe once per BLOCK CLASS of sq (256-aligned vs 128-aligned),
        # not per exact prompt length
        probe_sq = 256 if sq % 256 == 0 else 128
        if pre_ok and on_tpu and _kernel_compiles(
                "prefill", h, hkv, d, probe_sq, skv, str(k.dtype)):
            if quant_name:
                _note_dequant_path(quant_name, "fused")
            return prefill_attention_pallas(q, k, v, q_pos, float(scale),
                                            k_scale=k_scale, v_scale=v_scale)

    if (backend or flags().attention_backend) == "auto" and target_is_tpu():
        # XLA by design on a TPU (sharded under GSPMD, or a geometry /
        # feature the kernels do not cover): a rule, not a probe outcome
        from bigdl_tpu.ops.probing import record_dispatch_rule

        record_dispatch_rule("attention")
    if quant_name:
        _note_dequant_path(quant_name, "xla")
    qf = q.reshape(b, sq, hkv, g, d).astype(jnp.bfloat16)
    if k_scale is not None:
        # dequant in f32 (a bf16 scale multiply would round the scales)
        kf = (k.astype(jnp.float32)
              * k_scale[..., None].astype(jnp.float32)).astype(jnp.bfloat16)
        vf = (v.astype(jnp.float32)
              * v_scale[..., None].astype(jnp.float32)).astype(jnp.bfloat16)
    else:
        kf = k.astype(jnp.bfloat16)
        vf = v.astype(jnp.bfloat16)

    # [B, Hkv, G, Sq, Skv]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    if alibi_slopes is not None:
        # bias slopes[h] * k_pos; per-query-row constants cancel in softmax,
        # so keying on absolute key position is the standard causal form
        sl = alibi_slopes.reshape(hkv, g).astype(jnp.float32)
        kpos = jnp.arange(skv, dtype=jnp.float32)
        scores = scores + sl[None, :, :, None, None] * kpos[None, None, None, None, :]
    if logits_soft_cap is not None:
        scores = jnp.tanh(scores / logits_soft_cap) * logits_soft_cap

    k_ids = jnp.arange(skv, dtype=jnp.int32)                 # [Skv]
    if getattr(q_pos, "ndim", 0) == 1:
        # per-slot positions (continuous batching): [B, Sq, Skv] mask;
        # an empty slot (below 0) keeps key 0, so its softmax is finite
        q_ids = (jnp.maximum(q_pos, 0)[:, None]
                 + jnp.arange(sq, dtype=jnp.int32)[None, :])
        mask = k_ids[None, None, :] <= q_ids[:, :, None]
        if sliding_window is not None:
            mask &= k_ids[None, None, :] > q_ids[:, :, None] - sliding_window
        # [B, Skv->k, Sq->q] -> broadcast over (Hkv, G): [B,1,1,Sq,Skv]
        scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    else:
        q_ids = q_pos + jnp.arange(sq, dtype=jnp.int32)      # [Sq]
        mask = k_ids[None, :] <= q_ids[:, None]              # [Sq, Skv]
        if sliding_window is not None:
            mask &= k_ids[None, :] > q_ids[:, None] - sliding_window
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(jnp.bfloat16), vf,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def sdp_attention_paged(
    q: jax.Array,             # [B, Sq, H, D] (post-RoPE)
    arena_k: jax.Array,       # [L*Hkv, P, ps, D] the cache's whole stack
    arena_v: jax.Array,       # (4-bit codes: [L*Hkv, P, ps/8, 8, D])
    block_tables: jax.Array,  # [B, NP] int32 (0 = null page)
    q_pos: jax.Array,         # [B] int32 per-slot positions
    kv_heads: int,            # Hkv: a layer is that many planes of the stack
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    k_scale: Optional[jax.Array] = None,   # [L, P, Hkv, ps] f32 scales
    v_scale: Optional[jax.Array] = None,
    layer=0,                  # int32 scalar: attend this layer of the stack
) -> jax.Array:
    """Causal SDP reading K/V through a block table (paged cache, the
    layout of `ops/paged.py`).

    Decode (Sq=1) on TPU dispatches to the paged Pallas kernel, which
    copies the pages the prefetched layer index and block table name
    out of the arena where it lies, the live ones only — neither the
    layer nor the gather materializes.
    Everywhere else the fallback runs: ONE XLA gather over
    ``stack[layer, block_tables]`` reassembles the dense
    ``[B, NP * ps, Hkv, D]`` view (shape-identical to the slab
    read, ``NP * ps == max_seq``) and the regular `sdp_attention`
    dispatch finishes the job — so paged decode is byte-identical to
    slab decode wherever both take the XLA path, and the slab decode
    kernel still serves gathered views on TPU when the paged kernel
    cannot lower."""
    from bigdl_tpu.ops.paged import (_gather_codes, _gather_scales,
                                     code_page_size)

    b, sq, h, d = q.shape
    hkv, ps = kv_heads, code_page_size(arena_k)
    if scale is None:
        scale = d ** -0.5
    sliding_window = _live_window(sliding_window,
                                  block_tables.shape[1] * ps)
    quant_name = (str(arena_k.dtype)
                  if arena_k.dtype not in (jnp.bfloat16, jnp.float16,
                                           jnp.float32)
                  else None)

    from bigdl_tpu.config import flags, target_is_tpu, under_spmd

    be = backend or flags().attention_backend
    if be == "auto" and under_spmd(q, arena_k, arena_v):
        be = "xla"
    if be in ("auto", "pallas"):
        from bigdl_tpu.ops.pallas.paged_decode_attention import (
            paged_decode_attention_pallas, paged_decode_attention_supported)

        supported = paged_decode_attention_supported(
            q, arena_k, hkv, logits_soft_cap, sliding_window, alibi_slopes,
            k_scale)
        on_tpu = target_is_tpu()
        if supported and be == "pallas":
            if quant_name:
                _note_dequant_path(quant_name, "fused")
            return paged_decode_attention_pallas(
                q, arena_k, arena_v, block_tables, q_pos, float(scale), hkv,
                interpret=not on_tpu, k_scale=k_scale, v_scale=v_scale,
                layer=layer)
        if supported and on_tpu and _kernel_compiles(
                "paged_decode", h, hkv, d, ps, block_tables.shape[1],
                str(arena_k.dtype)):
            if quant_name:
                _note_dequant_path(quant_name, "fused")
            return paged_decode_attention_pallas(
                q, arena_k, arena_v, block_tables, q_pos, float(scale), hkv,
                k_scale=k_scale, v_scale=v_scale, layer=layer)

    kd = _gather_codes(arena_k, layer, block_tables, hkv)
    vd = _gather_codes(arena_v, layer, block_tables, hkv)
    ksd = vsd = None
    if k_scale is not None:
        ksd = _gather_scales(k_scale, layer, block_tables)
        vsd = _gather_scales(v_scale, layer, block_tables)
    return sdp_attention(q, kd, vd, q_pos, scale=scale,
                         logits_soft_cap=logits_soft_cap,
                         sliding_window=sliding_window,
                         alibi_slopes=alibi_slopes, backend=backend,
                         k_scale=ksd, v_scale=vsd)
