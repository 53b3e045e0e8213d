"""Quantized matmul: the hot op of the whole framework.

TPU-native equivalent of the reference's dequant-matmul kernels
(`linear_q4_0.forward_new` SYCL op, reference transformers/low_bit_linear.py:
608-631, and the CPU `ggml_compute_forward_mul_mat_q_fp32` path at
low_bit_linear.py:418-453).

Two execution paths:
- **XLA fallback** (`_q_matmul_xla`): dequantize to bf16 then `jnp.dot`.
  Works on any backend (CPU tests, interpret mode). On a v5e XLA fuses
  the dequant into the dot's operand for weights up to about 25 M
  elements; a larger one (an MLP projection) it writes to HBM as float32
  and again as bf16 before the dot (PERF.md 6, PR 29).
- **Pallas kernel** (`bigdl_tpu.ops.pallas.dequant_matmul`): streams the
  *packed* int4/int8 blocks HBM->VMEM and dequantizes in-kernel, so decode
  (GEMV-like, memory-bound) reads ~K*N/2 bytes instead of 2*K*N and a
  prefill chunk's GEMM writes no dense copy of the weights.

Which of them a call takes, and at which tiles, is `select_matmul`'s
answer: one function of what the call can see.

The public entry is `q_matmul(x, w)` where `w` is a QTensor of logical shape
[K, N] (contraction-major; see ops/quant.py) and x is [..., K], or a
`StackedQ`: one layer of a scanned model's `[L, K, N]` stack, which a
kernel plan reads where it lies.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.quant import (QTensor, dequantize_impl as dequantize,
                                 get_qtype)

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StackedQ:
    """Layer `layer` of `stack`, a QTensor whose planes are the `[L,
    ...]` stacks of a scanned model's layers: what a layer scan hands to
    `linear()` in place of a per-layer slice. A kernel plan addresses
    the layer in its index maps; an XLA plan takes it with `take()`,
    which is the slice the scan made itself."""

    stack: QTensor
    layer: jax.Array          # int32 scalar

    def tree_flatten(self):
        return (self.stack, self.layer), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    def take(self) -> QTensor:
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, self.layer, 0,
                                                   keepdims=False),
            self.stack)

    def apply_linear(self, x, bias=None, *, backend=None):
        return q_linear(x, self, bias, backend=backend)


def hold_stacks(layers: dict):
    """`(held, scanned)` of a scanned model's stacked layers: the plain
    QTensor leaves `[L, K, N]` stay whole, for the scan to close over;
    every other leaf (norms, biases, dense or adapter-wrapped weights,
    expert stacks) is scanned by value."""
    held = {k: v for k, v in layers.items()
            if isinstance(v, QTensor) and v.data.ndim == 3}
    return held, {k: v for k, v in layers.items() if k not in held}


def layer_params(held: dict, lp: dict, layer) -> dict:
    """One layer's leaves inside the scan: the scanned `lp` plus each
    held stack presented at `layer` (`hold_stacks`)."""
    return {**lp, **{k: StackedQ(v, layer) for k, v in held.items()}}


# qtypes the Pallas dequant-matmul kernels cover (sym / asym / codebook
# codes of 4 bits, sym codes of 8)
_PALLAS_QTYPES = frozenset({"sym_int4", "asym_int4", "nf4", "fp4", "nf3", "sym_int8"})


def _backend() -> str:
    # flags() folds BIGDL_TPU_MATMUL_BACKEND in at init; set_flags() wins
    from bigdl_tpu.config import flags

    return flags().matmul_backend


# formats whose XLA dequant materializes several full-size f32
# intermediates (codebook gathers, sign planes, sub-scale expansions):
# left unchunked, ONE 7B-class weight costs gigabytes of temp — a
# 32-layer mixtral-8x7B in iq2_xxs compiled to 9 GB of temp and OOM'd a
# 16 GB v5e despite only 12.8 GB of packed weights
_HEAVY_DECODE_QTYPES = frozenset(
    ("q2_k", "iq2_xxs", "iq2_xs", "iq1_s", "iq1_m"))
# ... and the weight size from which they are chunked
_HEAVY_CHUNK_ELEMS = 1 << 24


def _chunk_count(n: int, target_cols: int = 1024) -> int:
    """Smallest chunk count >= n/target that divides n (<= 64); when N is
    so large that every such count exceeds 64 (huge vocab heads), the
    LARGEST divisor <= 64 — giving up entirely would leave exactly the
    worst weights on the unchunked OOM path. 0 only when n is prime."""
    lo = max(1, -(-n // target_cols))
    for c in range(lo, 65):
        if n % c == 0:
            return c
    for c in range(64, 1, -1):
        if n % c == 0:
            return c
    return 0


def _chunk_planes(w: QTensor, min_elems: int, target_cols: int):
    """Shared chunk prep for the forward and backward chunked paths:
    (chunk_count, stacked planes tuple, per-chunk shape), or None when
    chunking is not applicable/worthwhile."""
    from bigdl_tpu.ops.quant import split_qtensor_n

    k, n = w.shape
    if k * n < min_elems:          # small weights: temp is already small
        return None
    c = _chunk_count(n, target_cols)
    if c <= 1:
        return None
    chunks = split_qtensor_n(w, [n // c] * c)
    stacked = []
    for f in ("data", "scale", "zero", "aux"):
        planes = [getattr(ch, f) for ch in chunks]
        stacked.append(None if planes[0] is None else jnp.stack(planes))
    return c, tuple(stacked), chunks[0].shape


def _q_matmul_xla_chunked(x: jax.Array, w: QTensor,
                          min_elems: int = 0,
                          target_cols: int = 1024):
    """Dequantize+dot in N-chunks under lax.map so XLA reuses one
    chunk's decode buffers instead of materializing them all at once
    (when it is worthwhile is `_xla_plan`'s rule). Returns None when N
    does not split."""
    prep = _chunk_planes(w, min_elems, target_cols)
    if prep is None:
        return None
    _, stacked, cshape = prep
    n = w.shape[1]
    xb = x.astype(jnp.bfloat16)

    def one(planes):
        d, s, z, a = planes
        wq = QTensor(d, s, z, w.qtype, cshape, a)
        return jnp.dot(xb, dequantize(wq, dtype=jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    ys = jax.lax.map(one, stacked)                            # [C, M, n/C]
    # downcast BEFORE the transpose: the cast commutes with moveaxis and
    # halves the transpose buffer (the whole point here is bounding temp)
    y = jnp.moveaxis(ys.astype(x.dtype), 0, -2)
    return y.reshape(*x.shape[:-1], n)


def _rows(x: jax.Array) -> int:
    m = 1
    for dim in x.shape[:-1]:
        m *= dim
    return m


def _q_matmul_xla(x: jax.Array, w: QTensor) -> jax.Array:
    """The dense XLA plan: dequantize, then dot."""
    with jax.named_scope("dequant"):
        dense = dequantize(w, dtype=jnp.bfloat16)
    y = jnp.dot(
        x.astype(jnp.bfloat16), dense, preferred_element_type=jnp.float32
    )
    return y.astype(x.dtype)


# formats with exact (or single-LUT) codes whose dequant factors as
# code * blockscale (+ blockzero): these fuse into the contraction
_FUSED_XLA_QTYPES = frozenset({"sym_int4", "asym_int4", "nf4", "sym_int8"})


def _q_matmul_xla_fused(x: jax.Array, w: QTensor) -> jax.Array:
    """Decode-shaped XLA path with the dequant fused INTO the dot.

    The plain fallback computes dequantize(W) -> [K, N] bf16 -> dot: the
    scale multiply touches all K*N weights and the scale-expanded bf16
    weight is a full-size temp. Scales factor out of the contraction
    (same algebra as the Pallas `_gemv_kernel_mxu`):

        y[m, n] = sum_r s[r, n] * sum_{j in block r} x[m, r, j] c[r, j, n]
                  (+ sum_r z[r, n] * sum_j x[m, r, j]   for asym)

    so this runs ONE batched `lax.dot_general` over the raw codes (int4
    codes are exact in bf16; nf4 is one LUT take) and applies scales to
    the [K/B, M, N] block partials in f32 — per-weight work drops to the
    unpack+convert, and at decode M the partial stack is megabytes, not
    the 2*K*N of a dense dequant. Used on TPU for decode-shaped calls
    when the Pallas kernel is unavailable (no legal tiling, SPMD
    tracing), or forced via backend="xla_fused"."""
    from bigdl_tpu.ops.quant import _unpack4
    from bigdl_tpu.ops.codebooks import CODEBOOKS

    qt = get_qtype(w.qtype)
    if w.qtype not in _FUSED_XLA_QTYPES:
        raise NotImplementedError(
            f"fused XLA matmul does not support {w.qtype}")
    b = qt.block_size
    k, n = w.shape
    kp = w.scale.shape[0] * b
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, k).astype(jnp.bfloat16)
    if kp != k:
        x2 = jax.lax.pad(x2, jnp.zeros((), x2.dtype),
                         ((0, 0, 0), (0, kp - k, 0)))
    m = x2.shape[0]
    rows = kp // b
    x3 = x2.reshape(m, rows, b).transpose(1, 0, 2)            # [r, M, B]

    data = w.data
    if data.dtype == jnp.int4:                # MXU layout: codes direct
        cb = data.astype(jnp.bfloat16)
    elif qt.storage_bits == 8:
        cb = data.astype(jnp.bfloat16)
    else:
        codes = _unpack4(data, b)                             # [kp, N] u8
        if qt.kind == "codebook":
            lut = jnp.asarray(CODEBOOKS[qt.codebook], jnp.bfloat16)
            cb = jnp.take(lut, codes.astype(jnp.int32), axis=0)
        elif qt.kind == "sym":
            cb = codes.astype(jnp.bfloat16) - 8.0
        else:                                                 # asym
            cb = codes.astype(jnp.bfloat16)
    cb3 = cb.reshape(rows, b, n)                              # [r, B, N]

    part = jax.lax.dot_general(                               # [r, M, N]
        x3, cb3, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = w.scale.astype(jnp.float32)                           # [r, N]
    y = jnp.sum(part * s[:, None, :], axis=0)                 # [M, N]
    if qt.kind == "asym":
        xsum = jnp.sum(x3.astype(jnp.float32), axis=2).T      # [M, r]
        y = y + jnp.dot(xsum, w.zero.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(*batch_shape, n)


# The plans a quantized linear can take (`select_matmul` picks one):
GEMV_MXU = "gemv_mxu"        # decode GEMV, int4-dtype layout
GEMV_STD = "gemv_std"        # decode GEMV, canonical packing (and int8)
GEMM = "gemm"                # the tiled GEMM of a prefill chunk
XLA_FUSED = "xla_fused"      # decode rows, dequant fused into the dot
XLA_CHUNKED = "xla_chunked"  # dequantize + dot in N-chunks, bounded temp
XLA = "xla"                  # dequantize, then dot


class MatmulPlan(NamedTuple):
    kind: str
    tiles: Optional[Tuple[int, int]] = None   # (bk, bn) of a kernel plan


# Rows past this take the XLA dequantize-then-dot plan in "auto": the
# crossover measured on a v5e (tools/qmatmul_ab.py; PERF.md 6, PR 29:
# sym_int4 in the int4-dtype layout, device time per layer of
# Mistral-7B's four linears, kernel / XLA): 0.92 / 2.22 ms at 256 rows,
# 1.62 / 2.36 at 512, 3.10 / 3.50 at 1024, 5.88 / 5.86 at 2048, 23.3 /
# 21.3 at 8192. The kernel dequantizes a weight tile once per 256 rows,
# in VMEM; XLA once per call, through float32 and bf16 copies of the
# layer in HBM, then runs the MXU at its peak.
PALLAS_MAX_ROWS = 1024

# one 7B-class weight (4096 x 11008 and up); decode-shaped calls against
# anything this large get the bounded-temp chunked plan
_DECODE_CHUNK_ELEMS = 1 << 25
_DECODE_CHUNK_ROWS = 16


def _xla_plan(qtype: str, rows: int, kp: int, n: int) -> MatmulPlan:
    if qtype in _HEAVY_DECODE_QTYPES:
        min_elems = _HEAVY_CHUNK_ELEMS
    elif rows <= _DECODE_CHUNK_ROWS:
        # decode against a 7B-class weight: the dense plan materializes
        # the FULL bf16 dequant (2*K*N bytes of temp) per layer — across
        # a scanned 32-layer decode XLA kept several alive at once and a
        # forced-XLA run died in RESOURCE_EXHAUSTED. Chunking over N
        # bounds the live temp to one chunk; over-N splits leave every
        # dot column's K-reduction untouched, so the result is bitwise
        # identical to the dense plan
        min_elems = _DECODE_CHUNK_ELEMS
    else:
        return MatmulPlan(XLA)
    if kp * n >= min_elems and _chunk_count(n) > 1:
        return MatmulPlan(XLA_CHUNKED)
    return MatmulPlan(XLA)


def kernel_plan(qtype: str, rows: int, kp: int, n: int,
                int4_layout: bool) -> Optional[MatmulPlan]:
    """The Pallas plan at this geometry, or None: a qtype the kernels do
    not cover, or a shape with no legal tiling."""
    from bigdl_tpu.ops.pallas.dequant_matmul import (GEMV_MAX_M, gemm_tiles,
                                                     gemv_tiles)

    if qtype not in _PALLAS_QTYPES:
        return None
    qt = get_qtype(qtype)
    if rows <= GEMV_MAX_M:
        kind = GEMV_MXU if int4_layout else GEMV_STD
        tiles = gemv_tiles(qt, kp, n, rows)
    else:
        kind, tiles = GEMM, gemm_tiles(qt, kp, n, rows)
    return None if tiles is None else MatmulPlan(kind, tiles)


def select_matmul(qtype: str, rows: int, kp: int, n: int, *,
                  int4_layout: bool, spmd: bool, tpu: bool,
                  backend: str = "auto") -> MatmulPlan:
    """THE choice of plan for x [rows, K] @ W [K, N], from what a call
    can see: the qtype, the rows, K padded to the quant block (`kp`), N,
    whether the codes are in the int4-dtype layout a TPU load gives
    sym_int4 (`quant.to_mxu_layout`), whether the operands are sharded
    under GSPMD (`config.under_spmd`; Mosaic kernels cannot be
    partitioned) and whether the target is a TPU. Pure: no flag, no
    probe, no device.

    "auto" on a TPU: the Pallas kernel for the qtypes it covers up to
    PALLAS_MAX_ROWS rows where a tiling is legal — the GEMV to
    GEMV_MAX_M rows (its body follows from the layout), the GEMM above;
    else XLA with the dequant fused into the dot at decode rows, in
    N-chunks where the dense plan's temporaries would not fit, dense
    otherwise. Off a TPU: XLA, never fused. A forced `backend` takes
    its plan whatever the target and the rows: "xla", "xla_fused" (the
    qtypes it covers; XLA for the rest), "pallas" — which raises
    NotImplementedError where the kernel has no legal tiling or does
    not cover the qtype."""
    if backend not in ("auto", "xla", "xla_fused", "pallas"):
        raise ValueError(f"unknown matmul backend {backend!r}")
    from bigdl_tpu.ops.pallas.dequant_matmul import GEMV_MAX_M

    fusable = qtype in _FUSED_XLA_QTYPES
    if backend == "xla_fused" and fusable:
        return MatmulPlan(XLA_FUSED)
    if backend == "pallas" or (backend == "auto" and tpu and not spmd
                               and rows <= PALLAS_MAX_ROWS):
        plan = kernel_plan(qtype, rows, kp, n, int4_layout)
        if plan is not None:
            return plan
        if backend == "pallas":
            raise NotImplementedError(
                f"no Pallas plan for {qtype} [{kp}, {n}] at {rows} rows")
    if backend == "auto" and tpu and fusable and rows <= GEMV_MAX_M:
        # decode rows the kernel does not take (no legal tiling, GSPMD):
        # fuse the dequant into the dot rather than materializing the
        # full bf16 weight
        return MatmulPlan(XLA_FUSED)
    return _xla_plan(qtype, rows, kp, n)


def _q_matmul_dispatch(x: jax.Array, w, be: str,
                       interpret: bool = False) -> jax.Array:
    """Ask `select_matmul`, probe the kernel it chose (auto on a live
    TPU: a kernel the compiler refuses raises, ops/probing.py) and run
    the plan. A `StackedQ` goes to a kernel as the stack and the layer;
    for an XLA plan its layer is taken first."""
    from bigdl_tpu.config import target_is_tpu, under_spmd
    from bigdl_tpu.ops.pallas import dequant_matmul as dq
    from bigdl_tpu.ops.probing import record_dispatch_rule, record_stacked

    view, layer = None, None
    if isinstance(w, StackedQ):
        view, w, layer = w, w.stack, w.layer
    rows, (k, n) = _rows(x), w.shape
    block = get_qtype(w.qtype).block_size
    kp = -(-k // block) * block
    int4 = w.data.dtype == jnp.int4
    tpu = target_is_tpu()
    plan = select_matmul(
        w.qtype, rows, kp, n, int4_layout=int4, tpu=tpu, backend=be,
        spmd=under_spmd(x, *jax.tree_util.tree_leaves(w)))
    if plan.tiles is not None:
        if be == "auto":
            if plan.kind == GEMM:
                dq.matmul_kernel_compiles(w.qtype, rows, kp, n, plan.tiles,
                                          mxu=int4, stacked=view is not None)
            else:
                dq.gemv_kernel_compiles(w.qtype, kp, plan.tiles, m=rows,
                                        mxu=int4, stacked=view is not None)
        if view is not None:
            record_stacked("matmul", in_place=True)
        return dq.q_matmul_kernel(x, w, plan.kind != GEMM, plan.tiles,
                                  layer=layer, interpret=interpret)
    if view is not None:
        record_stacked("matmul", in_place=False)
        w = view.take()
    if be == "auto" and tpu:
        # XLA by design (rows past the crossover, GSPMD-sharded
        # operands, a qtype or tiling the kernels do not cover): a
        # dispatch rule, counted apart from probe outcomes
        record_dispatch_rule("matmul")
    if plan.kind == XLA_FUSED:
        return _q_matmul_xla_fused(x, w)
    if plan.kind == XLA_CHUNKED:
        return _q_matmul_xla_chunked(x, w)
    return _q_matmul_xla(x, w)


def q_matmul_pallas_impl(x: jax.Array, w: QTensor, *,
                         interpret: bool = False) -> jax.Array:
    """x [..., K] @ quantized W [K, N] through the Pallas kernel,
    forced (`backend="pallas"`), with `interpret` for a CPU. Unjitted:
    see `dequant_matmul.q_matmul_kernel`."""
    return _q_matmul_dispatch(x, w, "pallas", interpret)


# jitted entry for standalone callers (tests, probes, tools)
q_matmul_pallas = functools.partial(
    jax.jit, static_argnames=("interpret",))(q_matmul_pallas_impl)


_VMAPPED_PALLAS: set = set()


def vmapped_pallas_ok(qtype: str, k: int = 256, n: int = 256) -> bool:
    """Compile probe PER (qtype, K, N-tile) for a vmapped, dynamically-
    indexed q_matmul_pallas (contract in ops/probing.py: True, or
    `KernelProbeError`). Gates the MoE decode gather path's use of the
    fused kernel (models/llama.py `_moe_mlp`): pallas_call's batching
    rule, dynamic expert indexing, the qtype's dequant branch, and the
    REAL tile classes are what that path runs (Mosaic rejections are
    geometry-dependent). False only by RULE: not a TPU target, or a
    qtype the kernel does not cover. The stand-in keeps the full K (the
    GEMV x/scale residency depends on it) but only ONE N tile."""
    from bigdl_tpu.config import flags as _flags, target_is_tpu

    if not (target_is_tpu() and qtype in _PALLAS_QTYPES):
        return False
    from bigdl_tpu.ops.pallas.dequant_matmul import gemv_tiles

    if _flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    tiles = gemv_tiles(get_qtype(qtype), k, n)
    if tiles is not None:
        n = tiles[1]
    from bigdl_tpu.ops.probing import (probe_kernel, quant_struct,
                                       stacked_struct)

    def probe_fn(idx, x, ws):
        def per(i, row):
            wi = jax.tree.map(lambda a: a[i], ws)
            return q_matmul_pallas(row[None], wi)[0]

        return jax.vmap(per)(idx, x)

    return probe_kernel(
        "vmapped_gemm", _VMAPPED_PALLAS, (qtype, k, n), probe_fn,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2, k), jnp.bfloat16),
        stacked_struct(quant_struct(k, n, qtype), 2))


def _zero_cotangent(leaf):
    # int-packed leaves take float0 cotangents under AD
    import numpy as _np

    if jnp.issubdtype(leaf.dtype, jnp.inexact):
        return jnp.zeros_like(leaf)
    return _np.zeros(leaf.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _q_matmul_vjp(x: jax.Array, w: QTensor, be: str) -> jax.Array:
    return _q_matmul_dispatch(x, w, be)


def _q_matmul_fwd(x, w, be):
    return _q_matmul_dispatch(x, w, be), w


def _q_matmul_bwd(be, w, dy):
    # MatMulLowBit.backward equivalent (reference low_bit_linear.py:470-486):
    # dx = dy @ dequantize(W)^T; the quantized weight is never trainable, so
    # its cotangent is zero. This also makes the non-differentiable Pallas
    # forward transparently trainable-through.
    dw = jax.tree.map(_zero_cotangent, w)
    if isinstance(w, StackedQ):
        w = w.take()
    if w.qtype in _HEAVY_DECODE_QTYPES:
        dx = _q_matmul_bwd_chunked(dy, w)
        if dx is not None:
            return dx.astype(dy.dtype), dw
    wd = dequantize(w, dtype=jnp.bfloat16)
    dx = jnp.dot(dy.astype(jnp.bfloat16), wd.T,
                 preferred_element_type=jnp.float32)
    return dx.astype(dy.dtype), dw


def _q_matmul_bwd_chunked(dy: jax.Array, w: QTensor,
                          min_elems: int = _HEAVY_CHUNK_ELEMS,
                          target_cols: int = 1024):
    """dx = dy @ W^T accumulated over the same N-chunks as the forward,
    so heavy-decode formats keep their bounded-temp guarantee under AD
    (QLoRA over iq/k-quant bases). Returns None when not applicable."""
    prep = _chunk_planes(w, min_elems, target_cols)
    if prep is None:
        return None
    c, stacked, cshape = prep
    k, n = w.shape
    nc = n // c
    dyb = dy.astype(jnp.bfloat16).reshape(-1, n)

    def step(acc, xs):
        i, planes = xs
        d, s, z, a = planes
        wq = QTensor(d, s, z, w.qtype, cshape, a)
        dy_c = jax.lax.dynamic_slice_in_dim(dyb, i * nc, nc, axis=1)
        return acc + jnp.dot(dy_c,
                             dequantize(wq, dtype=jnp.bfloat16).T,
                             preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((dyb.shape[0], k), jnp.float32)
    dx, _ = jax.lax.scan(step, acc0, (jnp.arange(c), stacked))
    return dx.reshape(*dy.shape[:-1], k)


_q_matmul_vjp.defvjp(_q_matmul_fwd, _q_matmul_bwd)


def q_matmul(x: jax.Array, w, *, backend: Optional[str] = None) -> jax.Array:
    """Compute x @ W for a quantized W of logical shape [K, N] (a
    QTensor, or a `StackedQ`).

    x: [..., K] float array. Returns [..., N] in x.dtype. Differentiable
    w.r.t. x (dequant-matmul backward); the weight gets zero cotangent.
    """
    return _q_matmul_vjp(x, w, backend or _backend())


def linear(
    x: jax.Array,
    w,
    bias: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
) -> jax.Array:
    """Linear over either a QTensor or a dense [K, N] array.

    Model code calls this uniformly; float-qtype models (fp16/bf16 paths of
    the reference's BF16Linear/FP16Linear, low_bit_linear.py:671-827) carry
    dense leaves, quantized models carry QTensors. Adapter-wrapped weights
    (bigdl_tpu.qlora.LoraWeight — or any leaf exposing `apply_linear`)
    dispatch to themselves, which is how LoRA reaches every model family
    with no model-code changes.
    """
    if hasattr(w, "apply_linear"):
        return w.apply_linear(x, bias, backend=backend)
    if isinstance(w, QTensor):
        return q_linear(x, w, bias, backend=backend)
    y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def q_linear(
    x: jax.Array,
    w: QTensor,
    bias: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
) -> jax.Array:
    """LowBitLinear.forward equivalent: y = x @ W + b.

    (reference transformers/low_bit_linear.py:546-668; the tensor-parallel
    all-reduce the reference issues here — dist.inference_all_reduce at
    low_bit_linear.py:635-637 — is unnecessary in this design: sharded
    QTensors under pjit make XLA insert the collective.)
    """
    y = q_matmul(x, w, backend=backend)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y
