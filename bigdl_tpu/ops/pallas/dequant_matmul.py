"""Pallas TPU kernel: fused dequantize + matmul over packed low-bit weights.

TPU-native replacement for the reference's SYCL `linear_q4_0.forward_new`
(reference transformers/low_bit_linear.py:608-631) and the CPU
`ggml_compute_forward_mul_mat_q_fp32` (low_bit_linear.py:418-453).

Why a kernel at all: decode (M≈1) is HBM-bandwidth-bound. The XLA fallback
materializes the dequantized bf16 weight (2*K*N bytes of HBM traffic); this
kernel streams the *packed* data (K*N/2 bytes for int4 + scales) into VMEM
and unpacks on the VPU right before feeding the MXU — a ~4x cut in bytes
moved, which is a ~4x cut in decode latency at the roofline.

Layout contract (see ops/quant.py):
  data  uint8 [Kp/2, N]  — split-block nibbles: within a block of B rows,
                           byte j holds value j (lo) and value j+B/2 (hi)
  scale f16   [Kp/B, N]
  zero  f16   [Kp/B, N]  — asym only
  int8: data int8 [Kp, N]

A scanned model hands over the `[L, ...]` STACKS of its layers' planes
and the layer index, which the grid prefetches: the weight's index maps
address the layer where it lies, no per-layer slice is written (a slice
read and wrote the bytes the GEMV then reads once: PERF.md 6, PR 46).
A single `[K, N]` weight takes the same bodies through the plain grid
(`_weight_call`).

Grid: (M/bm, N/bn, K/bk), K innermost, f32 accumulation in VMEM scratch.
At a prefill chunk's 256 rows (one row tile) each weight tile is read
from HBM once, as packed codes, and dequantized once: XLA's plan for the
same linear writes the layer to HBM as float32 and again as bf16 first
(per-shape chip table: PERF.md section 6, PR 29).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.quant import QTensor, get_qtype
from bigdl_tpu.ops.codebooks import CODEBOOKS


# generic grid is (M/bm, N/bn, K/bk): M and N tiles are independent,
# only the K sweep carries the accumulator. The scoped-VMEM limit is
# raised over Mosaic's 16 MiB default: a (2048, 512) weight tile lives
# there as int4, float32 and bf16 at once (about 14 MB with the x tile
# and the accumulator; a v5e has 128 MiB)
_GENERIC_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)

# `_matmul_tiles` budget of the generic path: the largest streaming
# tile it admits at bm = 256 is (2048, 512). On the chip (my chip run,
# PR 29; [4096, 28672] at 256 rows) the kernel took 0.393 ms at the
# 4 MB budget's (1024, 512) and 0.370 ms at (2048, 512); the MXU's
# least is 0.305
_GENERIC_BUDGET = 8 * 1024 * 1024
_GENERIC_BK = (2048, 1024, 512, 256, 128, 64, 32)


def _pick_tile(dim: int, candidates) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return 0


def _unpack_tile(data, block: int, bk: int, bn: int):
    """uint8 [bk//2, bn] split-block packed -> int32 codes [bk//B, B, bn].

    Mosaic has no 8-bit shift lowering; widen to i32 before the bit ops.
    """
    b2 = block // 2
    v = data.reshape(bk // block, b2, bn).astype(jnp.int32)
    lo = v & 0x0F
    hi = (v >> 4) & 0x0F
    return jnp.concatenate([lo, hi], axis=1)  # [bk//block, block, bn]


def _dequant_tile(codes_blk, scale, zero, kind: str, codebook, bk: int, bn: int):
    """codes [bk//B, B, bn] uint8 + scale/zero [bk//B, bn] -> bf16 [bk, bn]."""
    s = scale.astype(jnp.float32)[:, None, :]
    # Mosaic can't cast unsigned->float directly; hop through int32.
    codes_f = codes_blk.astype(jnp.int32).astype(jnp.float32)
    if kind == "sym":
        vals = (codes_f - 8.0) * s
    elif kind == "asym":
        z = zero.astype(jnp.float32)[:, None, :]
        vals = codes_f * s + z
    elif kind == "codebook":
        # LUT via a sequential compare/select chain (avoids gather, which
        # Mosaic lowers poorly). A binary select TREE is fewer selects but
        # keeps ~15 full-tile f32 temps live at once — 48MB of scoped VMEM
        # at generic tiles, a real Mosaic OOM (caught by tests/test_aot_
        # tpu.py); the chain keeps the live set at 2 buffers. Tables
        # smaller than 16 (nf3 has 8 entries) are zero-padded — those
        # codes never occur.
        c = codes_blk
        tbl = list(codebook) + [0.0] * (16 - len(codebook))
        vals = jnp.full(c.shape, tbl[0], jnp.float32)
        for i in range(1, 16):
            vals = jnp.where(c == i, tbl[i], vals)
        vals = vals * s
    else:
        raise NotImplementedError(kind)
    return vals.reshape(bk, bn).astype(jnp.bfloat16)


def _accumulate(x_tile, w, out_ref, acc_ref, nk, k_axis: int = 2):
    """Shared K-loop zero/accumulate/writeback. `k_axis` is the grid
    dimension that sweeps K (innermost); x_tile/w are VALUES."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_tile, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _gemm_step(x_ref, out_ref, acc_ref, nk, dequant):
    """One K step of the generic grid: `dequant()` gives the weight tile
    as bf16 [bk, bn]. The accumulator is zeroed BEFORE the tile is
    dequantized: a `pl.when` between the dequant and the dot ends the
    basic block, and Mosaic then runs the VPU's dequant and the MXU's
    passes one after the other instead of interleaved ([4096, 28672] at
    256 rows, (1024, 512) tiles: 0.600 ms that way round, 0.393 ms this;
    my chip run, PR 29)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], dequant(), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _kernel_4bit(x_ref, data_ref, scale_ref, *rest, block, kind, codebook,
                 bk, bn, nk):
    if kind == "asym":
        zero_ref, out_ref, acc_ref = rest
    else:
        (out_ref, acc_ref), zero_ref = rest, None

    def dequant():
        codes = _unpack_tile(data_ref[:], block, bk, bn)
        zero = zero_ref[:] if zero_ref is not None else None
        return _dequant_tile(codes, scale_ref[:], zero, kind, codebook,
                             bk, bn)

    _gemm_step(x_ref, out_ref, acc_ref, nk, dequant)


def _scaled_codes(data_ref, scale_ref, block, bk, bn):
    """Integer codes [bk, bn] x their block scales, in float32, rounded
    to bf16: the arithmetic of `_q_matmul_xla`'s dequantize."""
    s = scale_ref[:].astype(jnp.float32)[:, None, :]
    codes = data_ref[:].astype(jnp.int8).astype(jnp.float32)
    return (codes.reshape(bk // block, block, bn) * s) \
        .reshape(bk, bn).astype(jnp.bfloat16)


def _kernel_int(x_ref, data_ref, scale_ref, out_ref, acc_ref, *,
                block, bk, bn, nk):
    """Generic-tile body for integer-dtype codes: sym_int8, and sym_int4
    in the MXU (int4-dtype) layout — native int4 load, one convert,
    per-weight scale, no nibble unpack chain."""
    _gemm_step(x_ref, out_ref, acc_ref, nk,
               lambda: _scaled_codes(data_ref, scale_ref, block, bk, bn))


def _gemv_kernel(x_ref, data_ref, scale_ref, *rest, block, kind, codebook,
                 bk, bn, nk, bits):
    """Decode-GEMV body: grid (N/bn, K/bk), K innermost. x stays
    resident in VMEM across the K sweep; the packed data AND the
    per-step scale (zero) blocks stream via their BlockSpecs — an
    in-kernel dynamic slice of a resident scale buffer needs sublane-
    aligned offsets Mosaic cannot prove for K/block % 16 != 0 (caught
    by the AOT suite at down-proj-shaped K)."""
    if kind == "asym":
        zero_ref, out_ref, acc_ref = rest
    else:
        (out_ref, acc_ref), zero_ref = rest, None
    k = pl.program_id(1)
    rows = bk // block
    scale = scale_ref[:]
    zero = zero_ref[:] if zero_ref is not None else None
    if bits == 4:
        codes = _unpack_tile(data_ref[:], block, bk, bn)
        w = _dequant_tile(codes, scale, zero, kind, codebook, bk, bn)
    else:
        s = scale.astype(jnp.float32)[:, None, :]
        vals = data_ref[:].astype(jnp.float32).reshape(rows, block, bn) * s
        w = vals.reshape(bk, bn).astype(jnp.bfloat16)

    _accumulate(x_ref[:, pl.ds(k * bk, bk)], w, out_ref, acc_ref, nk,
                k_axis=1)


def _gemv_kernel_mxu(x3_ref, data_ref, scale_ref, out_ref, acc_ref, *,
                     block, bk, bn, nk):
    """Decode-GEMV body for the int4-dtype layout (scale-folded).

    The canonical split-block layout costs ~6 i32 VPU ops per weight to
    unpack (widen/mask/shift/concat). jnp.int4 arrays are bit-packed by
    XLA (same HBM bytes) and loaded natively by Mosaic, so per-weight
    work drops to ONE convert feeding a dot batched over scale blocks,
    and the scales factor out of the contraction:

        y[m, n] = sum_r scale[r, n] * sum_{k in block r} x[m, k] c[k, n]

    so they multiply the [rows, M, bn] partials in f32 (integer codes
    are exact in bf16; the scale is applied once).

    x arrives PRE-SPLIT as [K/block, M, block] (host-side reshape +
    transpose): splitting x's lane dimension inside the kernel is a
    Mosaic "unsupported shape cast" (caught by the AOT suite), and the
    batch (scale-block) axis must sit at the SAME position in both dot
    operands — the chip-side Mosaic rejects lhs-batch-at-1/rhs-batch-
    at-0 with "batch dims must be equal"."""
    k = pl.program_id(1)
    rows = bk // block

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cb = data_ref[:].astype(jnp.bfloat16).reshape(rows, block, bn)
    part = jax.lax.dot_general(
        x3_ref[:], cb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # [rows, M, bn]
    s = scale_ref[:].astype(jnp.float32)             # [rows, bn]
    acc_ref[:] += jnp.sum(part * s[:, None, :], axis=0)

    @pl.when(k == nk - 1)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _scale_rows_ok(bk: int, b: int, kp: int) -> bool:
    """The streamed scale block [bk//b, bn] must satisfy Mosaic's block
    tiling: second-to-last dim divisible by 8, or equal to the full
    array dim (kp//b). Violating K values (e.g. tensor-parallel local
    shards of 11008) fall back to the XLA matmul."""
    rows = bk // b
    return rows % 8 == 0 or bk == kp


def _matmul_tiles(qt, kp: int, n: int, bk_cands,
                  budget: int = 4 * 1024 * 1024, bm: int = 16):
    """Largest eligible (bk, bn) streaming tile under the VMEM budget.

    Eligibility couples bk to the quant block (bk % block == 0) and to
    Mosaic's scale-plane tiling (`_scale_rows_ok`); naively halving bk to
    fit VMEM can break it — e.g. the full-K tile for a tp=4 shard of
    ff=11008 (K=2752, an 86-row scale plane, legal only as ONE block)
    halves to 43 rows and falls off the kernel entirely.
    So search the whole (bk, bn) grid, shrinking bn before bk, and keep
    the largest legal product (ties favor the earlier = wider bn).

    The budget accounts the M-dependent terms too (x tile bm*bk bf16 +
    f32 accumulator bm*bn): at decode bm=16 they are noise, but at
    prefill-class bm=256 they rival the streamed weight tile — ignoring
    them let a forced all-M run pick tiles whose working set overflowed
    VMEM at 7B geometry."""
    b = qt.block_size
    best = None
    for bn in (512, 256, 128):
        if n % bn:
            continue
        for bk in bk_cands:
            if not bk or kp % bk or bk % b \
                    or not _scale_rows_ok(bk, b, kp):
                continue
            if bk * bn * 3 + bm * (2 * bk + 4 * bn) > budget:
                continue
            if best is None or bk * bn > best[0] * best[1]:
                best = (bk, bn)
    return best


# decode-GEMV M ceiling: the serving engine's decode batch. One padded
# sublane tile (mp=16) covers bs<=16; bs 17-32 pads to TWO sublane tiles
# (mp=32) — the x tile and accumulator double but stay VMEM-noise, and
# decode remains HBM-bound so the pad FLOPs are free.
GEMV_MAX_M = 32

# GEMM row tile ceiling: a prefill chunk's rows
# (EngineConfig.prefill_chunk) in one tile
GEMM_MAX_BM = 256


def _gemv_mp(m: int) -> int:
    return 16 if m <= 16 else 32


def _generic_bm(m: int):
    """GEMM row tile class: (bm, mp) with mp the padded M. Every row
    tile dequantizes each weight tile again, so M is covered by as FEW
    tiles of at most GEMM_MAX_BM rows as will do (sublane multiples of
    16): 200 rows are one tile of 208, not thirteen of 16."""
    tiles = -(-m // GEMM_MAX_BM)
    bm = -(-m // tiles)
    bm += -bm % 16
    return bm, tiles * bm


def gemv_tiles(qt, kp: int, n: int, m: int = 1):
    """(bk, bn) of the decode GEMV at `m` <= GEMV_MAX_M rows, or None
    when the shape has no legal tiling (ChatGLM2's K = 13696: no bk with
    an 8-row scale block divides it and the full K is over the budget)."""
    # kp itself is always legal (block dims == array dims), VMEM permitting
    return _matmul_tiles(qt, kp, n,
                         [4096, 2048, 1024, 512, 256, 128, 64, 32, kp],
                         bm=_gemv_mp(m))


def gemm_tiles(qt, kp: int, n: int, m: int):
    """(bk, bn) of the GEMM at `m` rows, or None: the joint (bk, bn)
    search keeps the working set (data tile + unpacked w tile + x tile +
    accumulator) in VMEM without sacrificing scale-plane legality."""
    bm = _generic_bm(m)[0]
    cands = [*_GENERIC_BK, kp]
    # a K that no smaller tile divides legally (ChatGLM2's 13696 = 2^7 x
    # 107: no bk with an 8-row scale block) takes ONE full-K tile under
    # twice the budget, (13696, 128): 0.224 ms against XLA's 0.689 at 256
    # rows (my chip run, PR 29)
    return (_matmul_tiles(qt, kp, n, cands, budget=_GENERIC_BUDGET, bm=bm)
            or _matmul_tiles(qt, kp, n, [kp], budget=2 * _GENERIC_BUDGET,
                             bm=bm))


_gemv_probe_cache: set = set()
_matmul_probe_cache: set = set()


def _probe_weight(kp: int, n: int, qtype: str, mxu: bool, stacked: bool):
    """The probes' stand-in weight: `[kp, n]`, or a stack of two."""
    from bigdl_tpu.ops.probing import quant_struct, stacked_struct

    w = quant_struct(kp, n, qtype, mxu=mxu)
    return stacked_struct(w, 2) if stacked else w


def gemv_kernel_compiles(qtype: str, kp: int, tiles, m: int = 1,
                         mxu: bool = False, stacked: bool = False) -> bool:
    """Compile probe of the decode GEMV at one geometry (contract in
    ops/probing.py: True, or `KernelProbeError`): compiles the REAL tile
    classes `tiles` = (bk, bn) on a stand-in sized (kp, bn). `mxu` says
    the codes are in the int4-dtype layout (the body follows from it);
    `m` only selects the padded row class (16 or 32); `stacked` says the
    call reads a layer of a stack (`_weight_call`'s second form)."""
    from bigdl_tpu.config import flags as _flags

    if _flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    qt = get_qtype(qtype)
    mp = _gemv_mp(m)
    bk, bn = tiles
    variant = "mxu" if mxu else "std"
    from bigdl_tpu.ops.probing import probe_kernel

    return probe_kernel(
        f"gemv_{variant}", _gemv_probe_cache,
        (qtype, kp, bn, bk, variant, mp, stacked),
        lambda xx, ww: _q_gemv_pallas(xx, ww, qt, mp, kp, bn, tiles, False,
                                      jnp.bfloat16, layer=0),
        jax.ShapeDtypeStruct((mp, kp), jnp.bfloat16),
        _probe_weight(kp, bn, qtype, mxu, stacked))


def matmul_kernel_compiles(qtype: str, m: int, kp: int, n: int, tiles,
                           mxu: bool = False, stacked: bool = False) -> bool:
    """Compile probe of the GEMM (same contract as
    `gemv_kernel_compiles`). Keyed by the padded bm class, not the raw
    M."""
    from bigdl_tpu.config import flags as _flags

    if _flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    qt = get_qtype(qtype)
    bm = _generic_bm(m)[0]
    from bigdl_tpu.ops.probing import probe_kernel

    return probe_kernel(
        "matmul_generic", _matmul_probe_cache,
        (qtype, bm, kp, n, bool(mxu), stacked),
        lambda xx, ww: _q_matmul_generic(xx, ww, qt, bm, kp, n, tiles,
                                         False, jnp.bfloat16, layer=0),
        jax.ShapeDtypeStruct((bm, kp), jnp.bfloat16),
        _probe_weight(kp, n, qtype, mxu, stacked))


def _weight_call(kernel, w: QTensor, layer, grid, in_specs, out_spec,
                 scratch, weight_specs):
    """`(call, body, prefetch)`: the `pallas_call` keyword arguments,
    the body and the operands that lead the call, for a single `[K, N]`
    weight or for the `[L, K, N]` stacks of a scanned model read at
    `layer`.

    `weight_specs` = [(block, index map)] of the weight's planes
    (`_planes`), which follow `in_specs` among the operands. One weight:
    the plain grid, nothing prefetched. A stack: the layer is prefetched
    as `int32[1]`, the planes' blocks gain a squeezed leading dimension
    that the index map fills from it, and the body is the same one,
    called without the prefetched ref. By the weight's rank: a stack of
    one through the stacked form read 0.3-1.6 % slower than the plain
    call, kernel alone (my chip run, PR 46)."""
    if w.data.ndim == 2:
        specs = [pl.BlockSpec(blk, idx) for blk, idx in weight_specs]
        return dict(grid_spec=pl.GridSpec(
            grid=grid, in_specs=in_specs + specs, out_specs=out_spec,
            scratch_shapes=scratch)), kernel, []

    def stacked(blk, idx):
        return pl.BlockSpec((None, *blk),
                            lambda *g: (g[-1][0], *idx(*g[:-1])))

    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=in_specs + [stacked(*ws) for ws in weight_specs],
        out_specs=out_spec, scratch_shapes=scratch)), \
        (lambda l_ref, *refs: kernel(*refs)), [lyr]


def _planes(w: QTensor):
    return [p for p in (w.data, w.scale, w.zero) if p is not None]


def _q_gemv_pallas(x2: jax.Array, w: QTensor, qt, m: int, kp: int, n: int,
                   tiles, interpret: bool, out_dtype=None, layer=None):
    """bs<=GEMV_MAX_M decode GEMV (the reference's `linear_fp16_esimd`
    decode GEMV role, low_bit_linear.py:744-745). M pads to one 16-row
    sublane tile (two for bs 17-32); x [mp, K] is VMEM-resident for the
    whole K sweep, the grid drops the M axis, and `tiles` = (bk, bn)
    maximize the streaming tile. FLOP overhead of the pad is irrelevant
    — decode is HBM-bound. The body follows from the codes' dtype:
    int4-dtype codes take `_gemv_kernel_mxu`, the canonical packing (and
    int8) `_gemv_kernel`. `w` is one weight, or the `[L, ...]` stacks of
    a scanned model read at `layer` (`_weight_call`)."""
    mp = _gemv_mp(m)
    if x2.shape[0] != mp:
        x2 = jax.lax.pad(x2, jnp.zeros((), x2.dtype),
                         ((0, mp - x2.shape[0], 0), (0, 0, 0)))
    b = qt.block_size
    bk, bn = tiles
    nk = kp // bk

    def tile(j, k):
        return k, j

    scale_spec = ((bk // b, bn), tile)
    if w.data.dtype == jnp.int4:
        kernel = functools.partial(
            _gemv_kernel_mxu, block=b, bk=bk, bn=bn, nk=nk)
        # x pre-split per scale block OUTSIDE the kernel, blocks leading
        # (see the body's docstring)
        x2 = x2.reshape(mp, kp // b, b).transpose(1, 0, 2)
        x_spec = pl.BlockSpec((bk // b, mp, b), lambda j, k, *_: (k, 0, 0))
        weight_specs = [((bk, bn), tile), scale_spec]
    else:
        codebook = None
        if qt.kind == "codebook":
            codebook = [float(v) for v in CODEBOOKS[qt.codebook]]
        bits = qt.storage_bits
        kernel = functools.partial(
            _gemv_kernel, block=b, kind=qt.kind, codebook=codebook,
            bk=bk, bn=bn, nk=nk, bits=bits)
        x_spec = pl.BlockSpec((mp, kp), lambda j, k, *_: (0, 0))  # resident
        weight_specs = [((bk // 2 if bits == 4 else bk, bn), tile),
                        scale_spec]
        if qt.kind == "asym":
            weight_specs.append(scale_spec)
    call, kernel, prefetch = _weight_call(
        kernel, w, layer, (n // bn, nk), [x_spec],
        pl.BlockSpec((mp, bn), lambda j, k, *_: (0, j)),
        [pltpu.VMEM((mp, bn), jnp.float32)], weight_specs)
    y = pl.pallas_call(
        kernel,
        name=f"qmatmul_gemv_{w.qtype}",
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype or x2.dtype),
        interpret=interpret,
        # N tiles are independent; only the K sweep carries the
        # accumulator — telling Mosaic lets it software-pipeline the
        # packed-data stream across j boundaries
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        **call,
    )(*prefetch, x2, *_planes(w))
    return y[:m]


def _q_matmul_generic(x2: jax.Array, w: QTensor, qt, m: int, kp: int,
                      n: int, tiles, interpret: bool,
                      out_dtype, layer=None) -> jax.Array:
    """The GEMM: x2 [m, kp] bf16 (already K-padded) against quantized W
    — grid (M/bm, N/bn, K/bk) at `tiles` = (bk, bn); `w` and `layer` as
    in `_q_gemv_pallas`."""
    # pad M up to a bf16-tileable multiple (min sublane 16)
    bm, mp = _generic_bm(m)
    if mp != m:
        x2 = jax.lax.pad(x2, jnp.zeros((), x2.dtype),
                         ((0, mp - m, 0), (0, 0, 0)))
    bk, bn = tiles
    nk = kp // bk
    b = qt.block_size

    def tile(i, j, k):
        return k, j

    scale_spec = ((bk // b, bn), tile)
    if w.data.dtype in (jnp.int4, jnp.int8):    # integer codes, unpacked
        weight_specs = [((bk, bn), tile), scale_spec]
        kernel = functools.partial(_kernel_int, block=b, bk=bk, bn=bn, nk=nk)
    else:                                       # split-block nibbles
        weight_specs = [((bk // 2, bn), tile), scale_spec]
        codebook = None
        if qt.kind == "codebook":
            codebook = [float(v) for v in CODEBOOKS[qt.codebook]]
        kernel = functools.partial(
            _kernel_4bit, block=b, kind=qt.kind, codebook=codebook,
            bk=bk, bn=bn, nk=nk)
        if qt.kind == "asym":
            weight_specs.append(scale_spec)
    call, kernel, prefetch = _weight_call(
        kernel, w, layer, (mp // bm, n // bn, nk),
        [pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k))],
        pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
        [pltpu.VMEM((bm, bn), jnp.float32)], weight_specs)
    y = pl.pallas_call(
        kernel,
        name=f"qmatmul_gemm_{w.qtype}",    # the kernel's trace name
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        interpret=interpret,
        compiler_params=_GENERIC_SEMANTICS,
        **call,
    )(*prefetch, x2, *_planes(w))

    if mp != m:
        y = y[:m]
    return y


def q_matmul_kernel(x: jax.Array, w: QTensor, gemv: bool, tiles, *,
                    layer=None, interpret: bool = False) -> jax.Array:
    """x [..., K] @ quantized W [K, N] -> [..., N] through the kernel
    that `ops/matmul.select_matmul` chose: the decode GEMV (`gemv`) or
    the GEMM, at its `tiles`. With `layer`, `w` holds the `[L, ...]`
    stacks of a scanned model's layers and the kernel reads that layer
    where it lies. Unjitted: model forwards call this inside their own
    jit (a nested jit's closed_call fails to lower inside shard_map's
    Manual-mesh trace — caught by the explicit-TP AOT test)."""
    qt = get_qtype(w.qtype)
    batch_shape = x.shape[:-1]
    klog, n = w.shape
    kp = w.scale.shape[-2] * qt.block_size
    m = 1
    for d in batch_shape:
        m *= d
    x2 = x.reshape(m, klog).astype(jnp.bfloat16)
    if kp != klog:
        x2 = jax.lax.pad(x2, jnp.zeros((), x2.dtype),
                         ((0, 0, 0), (0, kp - klog, 0)))
    call = _q_gemv_pallas if gemv else _q_matmul_generic
    y = call(x2, w, qt, m, kp, n, tiles, interpret, x.dtype, layer)
    return y.reshape(*batch_shape, n)
