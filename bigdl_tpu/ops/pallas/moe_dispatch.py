"""Ragged MoE dispatch: sorted token groups x per-expert weights.

The reference's Mixtral prefill runs every token through every selected
expert via a host-side Python loop (reference transformers/models/
mixtral.py:79-138); the in-repo dense fallback (models/llama.py
`_moe_mlp`) instead runs EVERY expert over EVERY token — E/k times the
needed FLOPs (4x for Mixtral 8x top-2), acceptable only because it keeps
shapes static. This module removes that waste while staying jit-static:

1. Token-choice pairs are argsorted by expert and scattered into a
   block-padded buffer: each expert's group is padded up to the token
   tile T, so every tile belongs to exactly ONE expert. The buffer size
   N*k + E*T is a static worst case; padding rows are zeros.
2. `ragged_expert_matmul` — a Pallas kernel whose weight BlockSpec
   selects the expert via a scalar-prefetched per-tile expert id
   (pltpu.PrefetchScalarGridSpec): tile i streams expert e_ids[i]'s
   packed weight block. Same dequant tile math as
   ops/pallas/dequant_matmul; dense bf16 expert stacks use a dense
   branch of the same kernel.
3. Outputs gather back through the same permutation with the routing
   weights applied in a scatter-add combine.

Exact (no capacity drops, unlike the classic fixed-capacity dispatch):
every token-choice is computed; only tile padding is wasted.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.codebooks import CODEBOOKS
from bigdl_tpu.ops.quant import QTensor, get_qtype
from bigdl_tpu.ops.pallas.dequant_matmul import (_accumulate, _dequant_tile,
                                                 _pick_tile, _unpack_tile)

TOKEN_TILE = 128


def _ragged_tiles(qtype, kp: int, n: int):
    """Tile classes the kernel would pick; None when untileable."""
    b = 1
    if qtype is not None:
        qt = get_qtype(qtype)
        b = qt.block_size
        kp = -(-kp // b) * b
    bkc = [2048, 1024, 512, 256, 128, 64, 32]
    bk = _pick_tile(kp, [c for c in bkc if c % b == 0])
    bn = _pick_tile(n, [512, 256, 128])
    if not bk or not bn:
        return None
    while bk * bn * 3 > 4 * 1024 * 1024 and bk > max(b, 32):
        bk //= 2
    if kp % bk or (qtype is not None and bk % b):
        return None
    return bk, bn




def _ragged_kernel_q(e_ref, x_ref, data_ref, scale_ref, *rest, block,
                     kind, codebook, bk, bn, nk, bits):
    if kind == "asym":
        zero_ref, out_ref, acc_ref = rest
    else:
        (out_ref, acc_ref), zero_ref = rest, None
    if bits == 4:
        codes = _unpack_tile(data_ref[0], block, bk, bn)
        zero = zero_ref[0] if zero_ref is not None else None
        w = _dequant_tile(codes, scale_ref[0], zero, kind, codebook, bk, bn)
    else:
        s = scale_ref[0].astype(jnp.float32)[:, None, :]
        vals = data_ref[0].astype(jnp.float32).reshape(
            bk // block, block, bn) * s
        w = vals.reshape(bk, bn).astype(jnp.bfloat16)
    _accumulate(x_ref[:], w, out_ref, acc_ref, nk)


def _ragged_kernel_dense(e_ref, x_ref, w_ref, out_ref, acc_ref, *, nk):
    _accumulate(x_ref[:], w_ref[0].astype(jnp.bfloat16), out_ref, acc_ref,
                nk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_expert_matmul(x: jax.Array,          # [Np, K] (tile-padded)
                         w,                     # QTensor/dense, leading E
                         tile_expert: jax.Array,  # [Np // T] int32
                         *, interpret: bool = False) -> jax.Array:
    """x tile i @ W[tile_expert[i]] -> [Np, N]. Np % TOKEN_TILE == 0."""
    np_, klog = x.shape
    t = TOKEN_TILE
    if np_ % t:
        raise NotImplementedError(f"Np={np_} not a multiple of {t}")
    x2 = x.astype(jnp.bfloat16)

    quantized = isinstance(w, QTensor)
    if quantized:
        qt = get_qtype(w.qtype)
        if qt.kind not in ("sym", "asym", "codebook") \
                or qt.storage_bits not in (4, 8) \
                or (qt.storage_bits == 8 and qt.kind != "sym"):
            raise NotImplementedError(
                f"ragged kernel does not support {w.qtype}")
        kp = w.scale.shape[1] * qt.block_size
        n = w.data.shape[-1]
        b = qt.block_size
    else:
        kp, n = w.shape[1], w.shape[2]
        b = 1
    if kp != klog:
        x2 = jnp.pad(x2, ((0, 0), (0, kp - klog)))

    tiles = _ragged_tiles(w.qtype if quantized else None, kp, n)
    if tiles is None:
        raise NotImplementedError(f"shapes not tileable: K={kp} N={n}")
    bk, bn = tiles
    nk = kp // bk
    grid = (np_ // t, n // bn, nk)

    x_spec = pl.BlockSpec((t, bk), lambda i, j, k, e: (i, k))
    out_spec = pl.BlockSpec((t, bn), lambda i, j, k, e: (i, j))
    out_shape = jax.ShapeDtypeStruct((np_, n), x.dtype)
    scratch = [pltpu.VMEM((t, bn), jnp.float32)]

    if quantized:
        rows = bk // 2 if qt.storage_bits == 4 else bk
        data_spec = pl.BlockSpec((1, rows, bn),
                                 lambda i, j, k, e: (e[i], k, j))
        scale_spec = pl.BlockSpec((1, bk // b, bn),
                                  lambda i, j, k, e: (e[i], k, j))
        codebook = None
        if qt.kind == "codebook":
            codebook = [float(v) for v in CODEBOOKS[qt.codebook]]
        kernel = functools.partial(
            _ragged_kernel_q, block=b, kind=qt.kind, codebook=codebook,
            bk=bk, bn=bn, nk=nk, bits=qt.storage_bits)
        operands = [w.data, w.scale]
        in_specs = [x_spec, data_spec, scale_spec]
        if qt.kind == "asym":
            operands.append(w.zero)
            in_specs.append(scale_spec)
    else:
        data_spec = pl.BlockSpec((1, bk, bn),
                                 lambda i, j, k, e: (e[i], k, j))
        kernel = functools.partial(_ragged_kernel_dense, nk=nk)
        operands = [w]
        in_specs = [x_spec, data_spec]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, name="moe_ragged_matmul", grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(tile_expert, x2, *operands)


_probe_cache: set = set()


def ragged_kernel_compiles(qtype: Optional[str], k: int, n: int) -> bool:
    """Per-geometry compile probe (contract in ops/probing.py: True, or
    `KernelProbeError`): verifies tileability of the REAL (K, N) first
    — False there is a RULE, the dense combine serves the shape — then
    compiles the kernel with the real tile classes on a small stand-in
    (K = 2 tiles, N = 1 tile, E = 2)."""
    tiles = _ragged_tiles(qtype, k, n)
    if tiles is None:
        return False
    from bigdl_tpu.config import flags as _flags

    if _flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    bk, bn = tiles
    from bigdl_tpu.ops.probing import (probe_kernel, quant_struct,
                                       stacked_struct)

    kd = min(2 * bk, k if qtype is None else -(-k // bk) * bk)
    kd = kd - kd % bk or bk
    if qtype is None:
        w = jax.ShapeDtypeStruct((2, kd, bn), jnp.bfloat16)
    else:
        w = stacked_struct(quant_struct(kd, bn, qtype), 2)
    return probe_kernel(
        "moe_ragged", _probe_cache, (qtype, bk, bn), ragged_expert_matmul,
        jax.ShapeDtypeStruct((TOKEN_TILE, kd), jnp.bfloat16), w,
        jax.ShapeDtypeStruct((1,), jnp.int32))


def moe_mlp_ragged(
    xf: jax.Array,            # [N, D]
    topi: jax.Array,          # [N, k] int32 expert choices
    topw: jax.Array,          # [N, k] f32 routing weights
    gate_w,                   # [E, D, F] stack (QTensor or dense) or None
    up_w,
    down_w,                   # [E, F, D]
    act,
    num_experts: int,
    *, interpret: bool = False,
) -> jax.Array:
    """Exact sorted-dispatch MoE MLP -> [N, D] (see module docstring)."""
    n, k = topi.shape
    t = TOKEN_TILE
    nk_tot = n * k
    # static worst case: every expert's group padded up to the tile
    np_ = -(-(nk_tot + num_experts * (t - 1)) // t) * t

    flat_e = topi.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_w = topw.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=num_experts)
    padded = -(-counts // t) * t                       # per-expert region
    starts = jnp.cumsum(padded) - padded               # region starts
    group_start = jnp.cumsum(counts) - counts          # in sorted order
    ranks = jnp.arange(nk_tot) - group_start[sorted_e]
    dest = starts[sorted_e] + ranks                    # [N*k] -> buffer row

    xbuf = jnp.zeros((np_, xf.shape[1]), xf.dtype)
    xbuf = xbuf.at[dest].set(xf[flat_tok[order]])

    # expert of each tile: which padded region contains its first row
    tile_first = jnp.arange(np_ // t, dtype=jnp.int32) * t
    region_end = jnp.cumsum(padded)
    tile_expert = jnp.searchsorted(region_end, tile_first,
                                   side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, num_experts - 1)

    if gate_w is not None:
        h = act(ragged_expert_matmul(xbuf, gate_w, tile_expert,
                                     interpret=interpret)) \
            * ragged_expert_matmul(xbuf, up_w, tile_expert,
                                   interpret=interpret)
    else:
        h = act(ragged_expert_matmul(xbuf, up_w, tile_expert,
                                     interpret=interpret))
    y = ragged_expert_matmul(h.astype(xf.dtype), down_w, tile_expert,
                             interpret=interpret)      # [Np, D]

    contrib = y[dest] * flat_w[order][:, None].astype(y.dtype)
    out = jnp.zeros_like(xf).at[flat_tok[order]].add(contrib)
    return out
