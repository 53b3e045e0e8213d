"""Ragged MoE dispatch: sorted token groups x per-expert weights.

The reference's Mixtral prefill runs every token through every selected
expert via a host-side Python loop (reference transformers/models/
mixtral.py:79-138); the in-repo dense fallback (models/llama.py
`_moe_mlp`) instead runs EVERY expert over EVERY token — E/k times the
needed FLOPs (4x for Mixtral 8x top-2), acceptable only because it keeps
shapes static. This module removes that waste while staying jit-static:

1. Token-choice pairs are argsorted by expert and laid into a
   block-padded buffer: each expert's group is padded up to the token
   tile T, so every tile belongs to exactly ONE expert. The buffer size
   N*k + E*T is a static worst case; padding rows are zeros. Every
   buffer row can say which sorted pair sits in it (`ragged_plan`), so
   the rows arrive by ONE row gather from the tokens
   (`ragged_rows_in`): no scatter whose updates are hidden-size rows.
2. `ragged_expert_matmul` — a Pallas kernel whose weight BlockSpec
   selects the expert via a scalar-prefetched per-tile expert id
   (pltpu.PrefetchScalarGridSpec): tile i streams expert e_ids[i]'s
   packed weight block. Same dequant tile math as
   ops/pallas/dequant_matmul; dense bf16 expert stacks use a dense
   branch of the same kernel.
3. Outputs gather back through the inverse permutation: token n sums
   its k choices' buffer rows times their routing weights in float32
   (`ragged_rows_out`), in the order of the choices; no scatter-add.

`ragged_plan` / `ragged_rows_in` / `ragged_rows_out` are the dispatch of
`moe_mlp_ragged` here AND of `ops/moe_routed._prefill` (the routed layer
that knows its share: choices of experts held elsewhere take no row).

Exact (no capacity drops, unlike the classic fixed-capacity dispatch):
every token-choice is computed; only tile padding is wasted.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.codebooks import CODEBOOKS
from bigdl_tpu.ops.quant import QTensor, get_qtype
from bigdl_tpu.ops.pallas.dequant_matmul import (_accumulate, _dequant_tile,
                                                 _pick_tile, _unpack_tile)

TOKEN_TILE = 128


class RaggedPlan(NamedTuple):
    """Where the rows of a sorted ragged dispatch lie (`ragged_plan`)."""
    row_token: jax.Array     # [Np] int32: the token whose row sits there,
    #                          N for padding and dead tiles
    choice_row: jax.Array    # [N, k] int32: the buffer row of each choice
    tile_expert: jax.Array   # [Np // T] int32: the expert of each tile
    n_active: jax.Array      # int32: leading tiles that hold any row


def ragged_plan(flat_e: jax.Array, k: int, held: int, t: int) -> RaggedPlan:
    """The layout of `flat_e` `[N*k]` (the expert of each token-choice
    pair in token order, `held` for a choice that takes no row) sorted
    by expert into a buffer where expert e's group starts on a tile of
    `t` rows. The buffer has the static worst case of rows,
    `ceil((N*k + held*(t-1)) / t) * t`: every pair gets its row whatever
    the routing does. Index arithmetic on `[N*k]`, `[held]` and `[Np]`
    int32 vectors only; the one scatter moves `N*k` int32 scalars."""
    nk_tot = flat_e.shape[0]
    np_ = -(-(nk_tot + held * (t - 1)) // t) * t
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    counts = jnp.bincount(flat_e, length=held + 1)[:held].astype(jnp.int32)
    padded = -(-counts // t) * t                       # per-expert region
    region_end = jnp.cumsum(padded)
    starts = region_end - padded                       # region starts
    group_start = jnp.cumsum(counts) - counts          # in sorted order
    # expert of each tile: which padded region contains its first row
    tile_first = jnp.arange(np_ // t, dtype=jnp.int32) * t
    tile_expert = jnp.minimum(
        jnp.searchsorted(region_end, tile_first, side="right"),
        held - 1).astype(jnp.int32)
    n_active = (region_end[-1] // t).astype(jnp.int32)
    # in: buffer row p is the j-th pair of its tile's expert, if it has one
    e_row = jnp.repeat(tile_expert, t)
    j = jnp.arange(np_, dtype=jnp.int32) - starts[e_row]
    src = jnp.minimum(group_start[e_row] + j, nk_tot - 1)
    row_token = jnp.where(j < counts[e_row], order[src] // k, nk_tot // k)
    # out: the row of the pair at sorted position i, back in token order
    sorted_e = jnp.minimum(flat_e[order], held - 1)
    dest = (starts[sorted_e] + jnp.arange(nk_tot, dtype=jnp.int32)
            - group_start[sorted_e])
    choice_row = jnp.zeros((nk_tot,), jnp.int32).at[order].set(
        jnp.minimum(dest, np_ - 1), unique_indices=True)
    return RaggedPlan(row_token, choice_row.reshape(-1, k), tile_expert,
                      n_active)


def ragged_rows_in(xf: jax.Array, plan: RaggedPlan) -> jax.Array:
    """The buffer `[Np, D]`: one row gather from the tokens `[N, D]`
    with a row of zeros after them, which is what an empty row names
    (a mask over the gathered buffer is a pass of its own over `Np`
    rows: 0.38 ms a layer at `[12288, 5120]` on a v5e, twice the
    gather)."""
    return jnp.pad(xf, ((0, 1), (0, 0)))[plan.row_token]


def ragged_rows_out(y: jax.Array, plan: RaggedPlan, w: jax.Array,
                    mine=None) -> jax.Array:
    """`out[n] = sum_k w[n, k] * y[choice_row[n, k]]` in float32 over the
    choices in their order; choices outside `mine` `[N, k]` (no row of
    theirs in `y` `[Np, D]`) add nothing, whatever the row read holds."""
    rows = y[plan.choice_row].astype(jnp.float32)               # [N, k, D]
    if mine is not None:
        rows = jnp.where(mine[..., None], rows, 0.0)
    return jnp.sum(rows * w.astype(jnp.float32)[..., None], axis=1)


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
    plan = ragged_plan(topi.reshape(-1).astype(jnp.int32), topi.shape[1],
                       num_experts, TOKEN_TILE)
    xbuf = ragged_rows_in(xf, plan)
    tile_expert = plan.tile_expert

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

    return ragged_rows_out(y, plan, topw).astype(xf.dtype)
