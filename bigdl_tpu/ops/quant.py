"""Quantization core: qtype registry, QTensor pytree, quantize/dequantize.

TPU-native re-design of the reference's ggml quantization layer
(reference: python/llm/src/ipex_llm/ggml/quantize.py:28-47 qtype registry;
native `ggml_quantize_tensor` / `ggml_dequantize` C API bound at
ggml/model/llama/llama_cpp.py:946-1127; `FP4Params` quantized parameter at
transformers/low_bit_linear.py:264-455).

Differences from the reference, by design:

- **Layout is contraction-major.** A quantized linear weight is stored as a
  ``[K, N]`` array (K = in_features = contraction dim, N = out_features), with
  quantization blocks running along K. HF checkpoints store ``[N, K]``; we
  transpose at quantize time. This makes the XLA fallback a plain
  ``x @ dequantize(w)`` and lets Pallas tile the packed data directly onto
  (sublane, lane) = (K-tiles, N-tiles) without transposes in the hot loop.
- **4-bit packing is "split-block"**: within each block of B values along K,
  packed byte j (j < B/2) holds value j in its low nibble and value j + B/2 in
  its high nibble (same as ggml q4_0's qs layout, ggml-common scheme). Unpack
  is then a concat of two nibble planes — no interleave — which vectorizes
  cleanly on the VPU.
- Scales are stored per (block, N) in bfloat16 (the reference's ggml blocks
  use fp16 scales, but Mosaic/TPU has no f16 compute; bf16 is native) and
  promoted to f32 in compute. GGUF/ggml checkpoint import converts f16
  scales to bf16 at load time.
- Everything is a registered JAX pytree, so QTensors live directly inside
  model parameter trees, shard with `jax.sharding`, and pass through jit.

Quantization here is vectorized JAX (it runs once, at load time). The hot
path — dequant-matmul — lives in ``bigdl_tpu/ops/matmul.py`` (XLA fallback)
and ``bigdl_tpu/ops/pallas/`` (TPU kernels).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.observability.compile_watch import tracked_jit
from bigdl_tpu.ops.codebooks import CODEBOOKS


# ---------------------------------------------------------------------------
# QType registry (mirrors ggml_tensor_qtype, reference ggml/quantize.py:28-47)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QType:
    name: str
    bits: int                 # logical bits per value
    block_size: int           # values per scale block (along K)
    kind: str                 # "sym" | "asym" | "codebook" | "fp8"
    storage_bits: int         # bits actually used in the packed layout
    codebook: Optional[str] = None  # key into CODEBOOKS for kind == "codebook"

    @property
    def is_4bit(self) -> bool:
        return self.storage_bits == 4


def _q(name, bits, block, kind, storage_bits=None, codebook=None):
    return QType(name, bits, block, kind, storage_bits or bits, codebook)


# Names follow the reference's user-facing strings (load_in_low_bit=...).
QTYPES = {
    "sym_int4": _q("sym_int4", 4, 32, "sym"),
    "asym_int4": _q("asym_int4", 4, 32, "asym"),
    "sym_int5": _q("sym_int5", 5, 32, "sym"),
    "asym_int5": _q("asym_int5", 5, 32, "asym"),
    "sym_int8": _q("sym_int8", 8, 32, "sym"),
    "nf4": _q("nf4", 4, 64, "codebook", codebook="nf4"),
    "nf3": _q("nf3", 3, 64, "codebook", storage_bits=4, codebook="nf3"),
    "fp4": _q("fp4", 4, 64, "codebook", codebook="fp4"),
    "fp8_e4m3": _q("fp8_e4m3", 8, 128, "fp8"),
    "fp8_e5m2": _q("fp8_e5m2", 8, 128, "fp8"),
    # 2-bit k-quant: 256-value superblocks of 16 sub-blocks, 4-bit
    # sub-scales/mins under fp16 super scales (ggml Q2_K; the format behind
    # the reference's "Mixtral on 16 GB" claim, README.md:16)
    "q2_k": _q("q2_k", 2, 256, "q2k"),
    # Ultra-low-bit group-codebook formats (TPU-native re-designs of the
    # reference's imatrix-weighted gguf_iq2_xxs / gguf_iq1_s, SURVEY.md
    # §2.3-B ggml_quantize_tensor_with_weights): groups of 8 values map to
    # one entry of a deterministic codebook (ops/codebooks.py
    # group_codebook) + per-32 4-bit sub-scales + per-256 bf16 scales.
    # iq2_xxs: 8-bit magnitude-pattern index + 8 sign bits = 2.19 bpw.
    # iq2_xs: 9-bit index + 7-bit parity-constrained signs in the SAME
    #   16 bits (double codebook at identical storage; ggml's XXS->XS).
    # iq1_s: 8-bit signed-ternary index = 1.19 bpw.
    # iq1_m: iq1_s + per-16 sub-scales + a per-group +-1/8 delta
    #   (1.44 bpw; the role of ggml's IQ1_M refinement).
    "iq2_xxs": _q("iq2_xxs", 2, 256, "iqx", codebook="iq2_xxs"),
    "iq2_xs": _q("iq2_xs", 2, 256, "iqx", codebook="iq2_xs"),
    "iq1_s": _q("iq1_s", 1, 256, "iqx", codebook="iq1_s"),
    "iq1_m": _q("iq1_m", 1, 256, "iqx", codebook="iq1_s"),
}
# Aliases used throughout the reference API surface.
QTYPES["int4"] = QTYPES["sym_int4"]
QTYPES["q4_0"] = QTYPES["sym_int4"]
QTYPES["q4_1"] = QTYPES["asym_int4"]
QTYPES["q5_0"] = QTYPES["sym_int5"]
QTYPES["q5_1"] = QTYPES["asym_int5"]
QTYPES["int8"] = QTYPES["sym_int8"]
QTYPES["q8_0"] = QTYPES["sym_int8"]
QTYPES["fp8"] = QTYPES["fp8_e5m2"]
# the reference's user-facing names for the iq formats (load_in_low_bit=...)
QTYPES["gguf_iq2_xxs"] = QTYPES["iq2_xxs"]
QTYPES["gguf_iq2_xs"] = QTYPES["iq2_xs"]
QTYPES["gguf_iq1_s"] = QTYPES["iq1_s"]
QTYPES["gguf_iq1_m"] = QTYPES["iq1_m"]

# float passthrough "qtypes" accepted by the convert API (no QTensor made).
FLOAT_QTYPES = ("fp16", "bf16", "fp32")

_FP8_MAX = {"fp8_e4m3": 448.0, "fp8_e5m2": 57344.0}
_FP8_DTYPE = {"fp8_e4m3": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}


def is_valid_qtype(name: str) -> bool:
    """True for concrete qtypes AND mixed_* policies."""
    return name in QTYPES or name in MIXED_QTYPES


def get_qtype(name: str) -> QType:
    try:
        return QTYPES[name]
    except KeyError:
        raise ValueError(
            f"unknown qtype {name!r}; known: {sorted(set(QTYPES))} + {FLOAT_QTYPES}"
        ) from None


# ---------------------------------------------------------------------------
# QTensor pytree
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """A block-quantized 2-D tensor of logical shape [K, N], blocks along K.

    Fields:
      data:  packed codes. 4-bit: uint8 [K//2, N] split-block nibble packing.
             8-bit sym: int8 [K, N]. fp8: float8_* [K, N].
      scale: bf16 [K // block, N] per-block scale (q2_k: superblock d).
      zero:  bf16 [K // block, N] per-block minimum (asym kinds), the
             superblock dmin (q2_k), or None.
      aux:   uint8 extra plane or None. int5 kinds: [K // 8, N] high-bit
             plane. q2_k: [K // 16, N] packed 4-bit sub-scale (low nibble)
             and sub-min (high nibble) per 16-value sub-block.
      qtype: qtype name (static).
      shape: logical (K, N) before padding (static). K may be padded up to a
             block multiple in `data`; `shape` records the true K.
    """

    data: jax.Array
    scale: jax.Array
    zero: Optional[jax.Array]
    qtype: str
    shape: Tuple[int, int]
    aux: Optional[jax.Array] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.data, self.scale, self.zero, self.aux), (self.qtype, self.shape)

    @classmethod
    def tree_unflatten(cls, aux_data, children):
        data, scale, zero, aux = children
        qtype, shape = aux_data
        return cls(data, scale, zero, qtype, shape, aux)

    # -- conveniences -------------------------------------------------------
    @property
    def qt(self) -> QType:
        return get_qtype(self.qtype)

    @property
    def k(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def nbytes(self) -> int:
        if self.data.dtype == jnp.int4:    # XLA packs int4 2-per-byte
            tot = -(-self.data.size // 2)
        else:
            tot = self.data.size * self.data.dtype.itemsize
        tot += self.scale.size * self.scale.dtype.itemsize
        if self.zero is not None:
            tot += self.zero.size * self.zero.dtype.itemsize
        if self.aux is not None:
            tot += self.aux.size * self.aux.dtype.itemsize
        return tot

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return dequantize(self, dtype=dtype)

    def __repr__(self):
        return (f"QTensor({self.qtype}, shape={self.shape}, "
                f"block={self.qt.block_size})")


# ---------------------------------------------------------------------------
# Packing helpers (split-block nibble layout)
# ---------------------------------------------------------------------------


def _safe_inv(x: jax.Array) -> jax.Array:
    """1/x with 0 -> 0 (no NaNs from empty/zero blocks)."""
    return jnp.where(x == 0, 0.0, 1.0 / jnp.where(x == 0, 1.0, x))


def _pack4(codes: jax.Array, block: int) -> jax.Array:
    """[K, N] uint8 codes (0..15) -> [K//2, N] split-block packed bytes."""
    k, n = codes.shape
    b2 = block // 2
    blk = codes.reshape(k // block, block, n)
    lo = blk[:, :b2, :]
    hi = blk[:, b2:, :]
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return packed.reshape(k // 2, n)


def _unpack4(packed: jax.Array, block: int) -> jax.Array:
    """[K//2, N] packed bytes -> [K, N] uint8 codes (0..15)."""
    k2, n = packed.shape
    b2 = block // 2
    blk = packed.reshape(k2 // b2, b2, n)
    lo = blk & jnp.uint8(0x0F)
    hi = blk >> 4
    return jnp.concatenate([lo, hi], axis=1).reshape(k2 * 2, n)


def _pack_bits1(bits: jax.Array) -> jax.Array:
    """[K, N] 0/1 uint8 -> [K//8, N] bit plane (bit j = row 8*i+j)."""
    k, n = bits.shape
    b = bits.reshape(k // 8, 8, n).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 8, 1)
    return jnp.sum(b << shifts, axis=1).astype(jnp.uint8)


def _unpack_bits1(plane: jax.Array) -> jax.Array:
    """[K//8, N] bit plane -> [K, N] 0/1 uint8."""
    k8, n = plane.shape
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 8, 1)
    bits = (plane[:, None, :] >> shifts) & jnp.uint8(1)
    return bits.reshape(k8 * 8, n)


def _pack2(codes: jax.Array, block: int) -> jax.Array:
    """[K, N] uint8 codes (0..3) -> [K//4, N]: 4 planes of block//4 rows."""
    k, n = codes.shape
    b4 = block // 4
    blk = codes.reshape(k // block, 4, b4, n)
    packed = (blk[:, 0] | (blk[:, 1] << 2) | (blk[:, 2] << 4)
              | (blk[:, 3] << 6)).astype(jnp.uint8)
    return packed.reshape(k // 4, n)


def _unpack2(packed: jax.Array, block: int) -> jax.Array:
    """[K//4, N] -> [K, N] uint8 codes (0..3)."""
    k4, n = packed.shape
    b4 = block // 4
    blk = packed.reshape(k4 // b4, b4, n)
    planes = jnp.stack([(blk >> (2 * i)) & jnp.uint8(3) for i in range(4)],
                       axis=1)
    return planes.reshape(k4 * 4, n)


def _pad_k(x: jax.Array, block: int) -> jax.Array:
    k = x.shape[0]
    rem = (-k) % block
    if rem:
        x = jnp.pad(x, ((0, rem), (0, 0)))
    return x


def _codebook_encode(code: np.ndarray, xn: jax.Array) -> jax.Array:
    """Nearest-codebook-entry encode via searchsorted on the sorted table."""
    order = np.argsort(code)
    sorted_code = code[order]
    bounds = (sorted_code[1:] + sorted_code[:-1]) / 2.0
    idx_sorted = jnp.searchsorted(jnp.asarray(bounds), xn)
    perm = jnp.asarray(order.astype(np.uint8))
    return perm[idx_sorted]


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------


def quantize(x: jax.Array, qtype: str,
             qw: Optional[jax.Array] = None) -> QTensor:
    """Quantize a [K, N] float array along K (blockwise) into a QTensor.

    For an HF linear weight w of shape [out, in], call
    ``quantize(w.T, qtype)`` (see `quantize_linear`).

    `qw` is an optional per-row importance vector [K] (the imatrix — the
    reference's `ggml_quantize_tensor_with_weights`, SURVEY.md §2.3-B):
    sym/asym/codebook formats run a weighted scale search, and the iq
    formats weight their codebook match. Other kinds ignore it.
    """
    if x.ndim != 2:
        raise ValueError(
            f"quantize expects a 2-D [K, N] array, got shape {x.shape}; "
            "reshape/flatten leading dims first"
        )
    if qw is not None and np.shape(qw) != (x.shape[0],):
        raise ValueError(
            f"imatrix length {np.shape(qw)} does not match the "
            f"contraction dim K={x.shape[0]} (importance is per INPUT "
            "feature)")
    qt = get_qtype(qtype)
    if qt.kind == "iqx":
        return _quantize_iqx(x, qt.name, qw)
    if qw is not None and qt.kind in ("sym", "asym", "codebook"):
        return _quantize_weighted(x, jnp.asarray(qw, jnp.float32), qt.name)
    if qw is not None and qt.kind == "q2k":
        return _quantize_q2k_weighted(x, jnp.asarray(qw, jnp.float32))
    return _quantize_core(x, qt.name)


@functools.partial(jax.jit, static_argnames=("qtype",))
def _quantize_core(x: jax.Array, qtype: str) -> QTensor:
    qt = get_qtype(qtype)
    k, n = x.shape
    b = qt.block_size
    x = _pad_k(x.astype(jnp.float32), b)
    kp = x.shape[0]
    nblk = kp // b
    xb = x.reshape(nblk, b, n)

    if qt.kind == "sym":
        # ggml-style signed-absmax scale: the max-|x| element maps exactly to
        # the most negative code (reference native q4_0/q5_0/q8_0 quantizers).
        amax_i = jnp.argmax(jnp.abs(xb), axis=1, keepdims=True)
        mx = jnp.take_along_axis(xb, amax_i, axis=1)  # [nblk, 1, n], signed
        half = float(1 << (qt.bits - 1))
        d = mx / -half
        inv = _safe_inv(d)
        q = jnp.clip(jnp.round(xb * inv) + half, 0, 2 * half - 1)
        q = q.reshape(kp, n).astype(jnp.uint8)
        scale = d.reshape(nblk, n).astype(jnp.bfloat16)
        if qt.bits == 4:
            return QTensor(_pack4(q, b), scale, None, qtype, (k, n))
        if qt.bits == 5:
            lo = _pack4(q & jnp.uint8(0x0F), b)
            hi = _pack_bits1(q >> 4)
            return QTensor(lo, scale, None, qtype, (k, n), aux=hi)
        if qt.bits == 8:
            q8 = (q.astype(jnp.int16) - 128).astype(jnp.int8)  # signed codes
            return QTensor(q8, scale, None, qtype, (k, n))
        raise ValueError(f"unsupported sym bits {qt.bits}")

    if qt.kind == "asym":
        mn = jnp.min(xb, axis=1, keepdims=True)
        mxv = jnp.max(xb, axis=1, keepdims=True)
        levels = float((1 << qt.bits) - 1)
        d = (mxv - mn) / levels
        inv = _safe_inv(d)
        q = jnp.clip(jnp.round((xb - mn) * inv), 0, levels)
        q = q.reshape(kp, n).astype(jnp.uint8)
        scale = d.reshape(nblk, n).astype(jnp.bfloat16)
        zero = mn.reshape(nblk, n).astype(jnp.bfloat16)
        if qt.bits == 4:
            return QTensor(_pack4(q, b), scale, zero, qtype, (k, n))
        if qt.bits == 5:
            lo = _pack4(q & jnp.uint8(0x0F), b)
            hi = _pack_bits1(q >> 4)
            return QTensor(lo, scale, zero, qtype, (k, n), aux=hi)
        raise ValueError(f"unsupported asym bits {qt.bits}")

    if qt.kind == "codebook":
        code = CODEBOOKS[qt.codebook]
        amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        d = amax
        inv = _safe_inv(d)
        q = _codebook_encode(code, xb * inv).reshape(kp, n).astype(jnp.uint8)
        scale = d.reshape(nblk, n).astype(jnp.bfloat16)
        return QTensor(_pack4(q, b), scale, None, qtype, (k, n))

    if qt.kind == "q2k":
        # per 16-value sub-block: asymmetric 2-bit with 4-bit quantized
        # sub scale/min under per-superblock fp16 scales (ggml Q2_K shape)
        sub = xb.reshape(nblk, b // 16, 16, n)
        mn = jnp.minimum(jnp.min(sub, axis=2), 0.0)        # [nblk, 16, n]
        mxv = jnp.max(sub, axis=2)
        ssc = jnp.maximum(mxv - mn, 0.0) / 3.0             # sub scale
        smin = -mn                                          # sub min (>=0)
        d = jnp.max(ssc, axis=1, keepdims=True) / 15.0     # [nblk, 1, n]
        dmin = jnp.max(smin, axis=1, keepdims=True) / 15.0
        dinv = _safe_inv(d)
        minv = _safe_inv(dmin)
        sc4 = jnp.clip(jnp.round(ssc * dinv), 0, 15).astype(jnp.uint8)
        m4 = jnp.clip(jnp.round(smin * minv), 0, 15).astype(jnp.uint8)
        eff_sc = d * sc4                                    # [nblk, 16, n]
        eff_m = dmin * m4
        inv_sc = _safe_inv(eff_sc)
        q = jnp.clip(jnp.round((sub + eff_m[:, :, None, :])
                               * inv_sc[:, :, None, :]), 0, 3)
        q = q.reshape(kp, n).astype(jnp.uint8)
        aux = (sc4 | (m4 << 4)).reshape(kp // 16, n)        # [K/16, N]
        return QTensor(
            _pack2(q, b),
            d[:, 0, :].astype(jnp.bfloat16),
            dmin[:, 0, :].astype(jnp.bfloat16),
            qtype, (k, n), aux=aux)

    if qt.kind == "fp8":
        fmax = _FP8_MAX[qt.name]
        fdt = _FP8_DTYPE[qt.name]
        amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        d = amax / fmax
        inv = _safe_inv(d)
        q = (xb * inv).astype(fdt).reshape(kp, n)
        scale = d.reshape(nblk, n).astype(jnp.bfloat16)
        return QTensor(q, scale, None, qtype, (k, n))

    raise ValueError(f"unsupported qtype kind {qt.kind}")


# ---------------------------------------------------------------------------
# Imatrix-weighted quantization (reference: ggml_quantize_tensor_with_weights
# bound at ggml/model/llama/llama_cpp.py:946-989; used by the reference for
# IQ2/IQ1/Q2_K with an importance matrix, transformers/utils.py:187-323)
# ---------------------------------------------------------------------------

_WEIGHTED_NCAND = 17        # scale candidates searched per block
_WEIGHTED_SPAN = 0.25       # +-25% around the absmax-derived scale


@functools.partial(jax.jit, static_argnames=("qtype",))
def _quantize_weighted(x: jax.Array, qw: jax.Array, qtype: str) -> QTensor:
    """Weighted-MSE scale search: per block, try scale candidates around
    the absmax scale and keep the one minimizing sum(qw * (x - deq)^2).
    The candidate loop is a `lax.scan` so memory stays one-candidate-deep.
    """
    qt = get_qtype(qtype)
    k, n = x.shape
    b = qt.block_size
    x = _pad_k(x.astype(jnp.float32), b)
    kp = x.shape[0]
    nblk = kp // b
    xb = x.reshape(nblk, b, n)
    wb = _pad_k(qw.reshape(-1, 1).astype(jnp.float32), b)
    wb = jnp.maximum(wb, 1e-12).reshape(nblk, b, 1)

    factors = jnp.linspace(1.0 - _WEIGHTED_SPAN, 1.0 + _WEIGHTED_SPAN,
                           _WEIGHTED_NCAND)

    if qt.kind == "sym":
        amax_i = jnp.argmax(jnp.abs(xb), axis=1, keepdims=True)
        mx = jnp.take_along_axis(xb, amax_i, axis=1)
        half = float(1 << (qt.bits - 1))
        base_d = mx / -half                                   # [nblk, 1, n]
        lo, hi = 0.0, 2 * half - 1

        def encode(d):
            q = jnp.clip(jnp.round(xb * _safe_inv(d)) + half, lo, hi)
            return q, (q - half) * d
    elif qt.kind == "asym":
        mn = jnp.min(xb, axis=1, keepdims=True)
        mxv = jnp.max(xb, axis=1, keepdims=True)
        levels = float((1 << qt.bits) - 1)
        base_d = (mxv - mn) / levels

        def encode(d):
            q = jnp.clip(jnp.round((xb - mn) * _safe_inv(d)), 0, levels)
            return q, q * d + mn
    else:                                       # codebook
        code = CODEBOOKS[qt.codebook]
        base_d = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
        code_j = jnp.asarray(code)

        def encode(d):
            q = _codebook_encode(code, xb * _safe_inv(d))
            return q, code_j[q] * d

    def try_factor(best, f):
        best_d, best_err = best
        d = base_d * f
        _, recon = encode(d)
        err = jnp.sum(wb * (xb - recon) ** 2, axis=1)          # [nblk, n]
        better = err < best_err
        return (jnp.where(better[:, None, :], d, best_d),
                jnp.where(better, err, best_err)), None

    init = (base_d, jnp.full((nblk, n), jnp.inf))
    (d_best, _), _ = lax.scan(try_factor, init, factors)

    q, _ = encode(d_best)
    q = q.reshape(kp, n).astype(jnp.uint8)
    scale = d_best.reshape(nblk, n).astype(jnp.bfloat16)

    if qt.kind == "asym":
        zero = mn.reshape(nblk, n).astype(jnp.bfloat16)
        if qt.bits == 4:
            return QTensor(_pack4(q, b), scale, zero, qtype, (k, n))
        lo4 = _pack4(q & jnp.uint8(0x0F), b)
        return QTensor(lo4, scale, zero, qtype, (k, n),
                       aux=_pack_bits1(q >> 4))
    if qt.kind == "codebook":
        return QTensor(_pack4(q, b), scale, None, qtype, (k, n))
    # sym
    if qt.bits == 4:
        return QTensor(_pack4(q, b), scale, None, qtype, (k, n))
    if qt.bits == 5:
        lo4 = _pack4(q & jnp.uint8(0x0F), b)
        return QTensor(lo4, scale, None, qtype, (k, n),
                       aux=_pack_bits1(q >> 4))
    q8 = (q.astype(jnp.int16) - 128).astype(jnp.int8)
    return QTensor(q8, scale, None, qtype, (k, n))


@jax.jit
def _quantize_q2k_weighted(x: jax.Array, qw: jax.Array) -> QTensor:
    """Imatrix-weighted q2_k: per sub-block, search scale candidates for
    the (ssc, smin) fit minimizing the weighted reconstruction error
    (the reference's Q2_K-with-imatrix path of
    ggml_quantize_tensor_with_weights)."""
    qt = get_qtype("q2_k")
    k, n = x.shape
    b = qt.block_size
    x = _pad_k(x.astype(jnp.float32), b)
    kp = x.shape[0]
    nblk = kp // b
    xb = x.reshape(nblk, b, n)
    wb = _pad_k(qw.reshape(-1, 1).astype(jnp.float32), b)
    wb = jnp.maximum(wb, 1e-12).reshape(nblk, b // 16, 16, 1)

    sub = xb.reshape(nblk, b // 16, 16, n)
    mn = jnp.minimum(jnp.min(sub, axis=2), 0.0)
    mxv = jnp.max(sub, axis=2)
    base_ssc = jnp.maximum(mxv - mn, 0.0) / 3.0          # [nblk, 16, n]
    smin = -mn

    factors = jnp.linspace(1.0 - _WEIGHTED_SPAN, 1.0 + _WEIGHTED_SPAN,
                           _WEIGHTED_NCAND)

    def recon_err(ssc):
        inv = _safe_inv(ssc)
        q = jnp.clip(jnp.round((sub + smin[:, :, None, :])
                               * inv[:, :, None, :]), 0, 3)
        rec = q * ssc[:, :, None, :] - smin[:, :, None, :]
        err = jnp.sum(wb * (sub - rec) ** 2, axis=2)      # [nblk, 16, n]
        return err

    def try_factor(best, f):
        best_ssc, best_err = best
        ssc = base_ssc * f
        err = recon_err(ssc)
        better = err < best_err
        return (jnp.where(better, ssc, best_ssc),
                jnp.where(better, err, best_err)), None

    init = (base_ssc, jnp.full(base_ssc.shape, jnp.inf))
    (ssc, _), _ = lax.scan(try_factor, init, factors)

    # same superblock packing as the unweighted core
    d = jnp.max(ssc, axis=1, keepdims=True) / 15.0
    dmin = jnp.max(smin, axis=1, keepdims=True) / 15.0
    dinv = _safe_inv(d)
    minv = _safe_inv(dmin)
    sc4 = jnp.clip(jnp.round(ssc * dinv), 0, 15).astype(jnp.uint8)
    m4 = jnp.clip(jnp.round(smin * minv), 0, 15).astype(jnp.uint8)
    eff_sc = d * sc4
    eff_m = dmin * m4
    inv_sc = _safe_inv(eff_sc)
    q = jnp.clip(jnp.round((sub + eff_m[:, :, None, :])
                           * inv_sc[:, :, None, :]), 0, 3)
    q = q.reshape(kp, n).astype(jnp.uint8)
    aux = (sc4 | (m4 << 4)).reshape(kp // 16, n)
    return QTensor(
        _pack2(q, b),
        d[:, 0, :].astype(jnp.bfloat16),
        dmin[:, 0, :].astype(jnp.bfloat16),
        "q2_k", (k, n), aux=aux)


# ---------------------------------------------------------------------------
# iq formats: group-of-8 codebook quantization (iq2_xxs / iq1_s)
# ---------------------------------------------------------------------------

_IQ_CHUNK = 1024          # encode N columns at a time (bounds the [G,256,Nc]
                          # score tensor to ~0.5 GB f32 for K=4096)


def _iq_scales(xc: jax.Array, gmax: float, sub: int = 32):
    """Per-`sub` sub-scale (4-bit) under per-256 bf16 superscale.

    Returns (d [K/256, Nc], s4 [K/sub, Nc] uint8, effk [K, Nc])."""
    kp, nc = xc.shape
    per = 256 // sub
    s = jnp.max(jnp.abs(xc.reshape(kp // sub, sub, nc)), axis=1) / gmax
    d = jnp.max(s.reshape(kp // 256, per, nc), axis=1) / 15.0
    drep = jnp.repeat(d, per, axis=0)
    s4 = jnp.clip(jnp.round(s * _safe_inv(drep)), 0, 15).astype(jnp.uint8)
    eff = drep * s4.astype(jnp.float32)
    return d, s4, jnp.repeat(eff, sub, axis=0)


# Native iq1_m per-group shift magnitude. DELIBERATELY 1/8 (not ggml's
# IQ1M_DELTA = 0.0625, which gguf.py uses to decode real ggml files):
# this native format pairs the delta with per-16 sub-scales, and 1/8
# measured lower RMSE here. The two formats are independent layouts.
_IQ_DELTA = 0.125


@functools.partial(jax.jit, static_argnames=("qtype", "iters"))
def _iqx_encode_chunk(xc: jax.Array, wv: jax.Array, qtype: str,
                      iters: int = 2):
    """Encode one [K, Nc] chunk. wv: [K, 1] importance (ones if no imatrix).

    Codebook match maximizes sum(w * y * c) - 0.5 * sum(w * c^2) per group
    (equivalent to weighted-MSE argmin), computed as one [G, J, Nc]
    einsum — MXU work, not a loop.

    Coordinate descent (`iters` extra rounds): the amax-derived initial
    scale is far from optimal for coarse codebooks — for ternary iq1_s it
    pins the group max to +-1, which rounds most of a Gaussian group to
    zero, and no imatrix weighting can rescue a bad scale (the r2 ppl
    numbers showed exactly that). Each round re-fits every sub-scale by
    weighted least squares against the CHOSEN patterns
    (eff* = sum(w x c) / sum(w c^2) — exact given the assignment, the
    same scale-search idea as ggml's iq quantizers), then re-assigns
    patterns under the new scale. Monotone in weighted MSE modulo the
    4-bit scale rounding.

    Format variants:
    - iq2_xxs: unsigned cb[256], free 8-bit signs.
    - iq2_xs: unsigned cb[512]; signs parity-constrained to 7 stored
      bits (the lowest-|w x c| sign flips when the parity is odd), code
      packed as uint16 = idx | sign7 << 9 in two uint8 rows.
    - iq1_s: signed ternary cb[256].
    - iq1_m: iq1_s + per-16 sub-scales + per-group delta in
      {-1/8, +1/8}: values decode as eff * (c + delta). The (pattern,
      delta) pair is chosen jointly — score(c, d) separates as
      [s1 - s2/2] + d*(Sy - Swc) with the d^2 term constant.

    Returns (data, d, aux, extra): `extra` is the packed per-group delta
    bits for iq1_m, else None."""
    from bigdl_tpu.ops.codebooks import group_codebook

    qt = get_qtype(qtype)
    cb = jnp.asarray(group_codebook(qt.codebook))             # [J, 8]
    name = qt.name
    signed_cb = name in ("iq1_s", "iq1_m")
    with_delta = name == "iq1_m"
    xs_signs = name == "iq2_xs"
    sub = 16 if with_delta else 32
    gmax = float(np.max(np.abs(group_codebook(qt.codebook))))
    kp, nc = xc.shape
    g = kp // 8
    per = 256 // sub

    d, s4, effk = _iq_scales(xc, gmax, sub=sub)
    # wv: [K, 1] (uniform across columns) or [K, Nc] (magnitude-
    # modulated imatrix weights — per-column by construction)
    w = wv.reshape(g, 8, -1)
    percol = w.shape[-1] != 1
    drep = jnp.repeat(d, per, axis=0)                         # [K/sub, Nc]
    if percol:
        s2 = jnp.einsum("gkn,jk->gjn", w, cb * cb)            # [g, J, Nc]
    else:
        s2 = jnp.einsum("gk,jk->gj", w[..., 0], cb * cb)[:, :, None]
    if with_delta:
        if percol:
            swc = jnp.einsum("gkn,jk->gjn", w, cb)
        else:
            swc = jnp.einsum("gk,jk->gj", w[..., 0], cb)[:, :, None]

    def assign(effk):
        y = xc * _safe_inv(effk)                              # [K, Nc]
        a = (y if signed_cb else jnp.abs(y)).reshape(g, 8, nc)
        s1 = jnp.einsum("gkn,jk->gjn", a * w, cb)
        base = s1 - 0.5 * s2                                  # [g, J, Nc]
        if not with_delta:
            return jnp.argmax(base, axis=1), None
        sy = jnp.sum((a * w), axis=1)                         # [g, Nc]
        dterm = _IQ_DELTA * (sy[:, None, :] - swc)
        plus, minus = base + dterm, base - dterm
        jp, jm = jnp.argmax(plus, axis=1), jnp.argmax(minus, axis=1)
        bp = jnp.take_along_axis(plus, jp[:, None, :], axis=1)[:, 0]
        bm = jnp.take_along_axis(minus, jm[:, None, :], axis=1)[:, 0]
        take_p = bp >= bm
        return jnp.where(take_p, jp, jm), take_p              # [g, Nc] x2

    def stored_neg(idx):
        """Sign bits as they will be STORED: for iq2_xs the 7-bit parity
        constraint flips the cheapest position of every odd-parity
        group, so the decode differs from the raw (x < 0) signs — the
        scale refit must see the corrected signs or it optimizes for a
        decode that never happens (r4 advice)."""
        neg = (xc < 0).astype(jnp.int32).reshape(g, 8, nc)
        if xs_signs:
            pattern = cb[idx].transpose(0, 2, 1)              # [g, 8, Nc]
            cost = jnp.abs(xc.reshape(g, 8, nc)) * pattern * w
            odd = (jnp.sum(neg, axis=1) & 1) == 1             # [g, Nc]
            flip_at = jnp.argmin(cost, axis=1)                # [g, Nc]
            onehot = (jnp.arange(8)[None, :, None]
                      == flip_at[:, None, :])
            neg = jnp.where(odd[:, None, :] & onehot, 1 - neg, neg)
        return neg

    def decoded_units(idx, dpos):
        """Chosen patterns at unit scale, signs + delta folded."""
        c = cb[idx].transpose(0, 2, 1).reshape(kp, nc)        # [K, Nc]
        if not signed_cb:
            # stored sign bit is (x < 0): x == 0 decodes as +c
            sgn = 1.0 - 2.0 * stored_neg(idx).astype(jnp.float32)
            c = c * sgn.reshape(kp, nc)
        if with_delta:
            delta = jnp.where(dpos, _IQ_DELTA, -_IQ_DELTA)    # [g, Nc]
            c = c + jnp.repeat(delta, 8, axis=0)
        return c

    idx, dpos = assign(effk)
    for _ in range(iters):
        c = decoded_units(idx, dpos)
        wk = wv                                               # [K, 1]
        num = jnp.sum((wk * xc * c).reshape(kp // sub, sub, nc), axis=1)
        den = jnp.sum((wk * c * c).reshape(kp // sub, sub, nc), axis=1)
        eff = num * _safe_inv(den)                            # [K/sub, Nc]
        s4 = jnp.clip(jnp.round(eff * _safe_inv(drep)),
                      0, 15).astype(jnp.uint8)
        effk = jnp.repeat(drep * s4.astype(jnp.float32), sub, axis=0)
        idx, dpos = assign(effk)

    # pack sub-scales: 2 nibbles per byte along K
    s4p = s4.reshape(kp // (2 * sub), 2, nc)
    aux = (s4p[:, 0] | (s4p[:, 1] << 4)).astype(jnp.uint8)

    extra = None
    if with_delta:
        bits = dpos.astype(jnp.int32).reshape(g // 8, 8, nc)
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        extra = jnp.sum(bits << shifts, axis=1).astype(jnp.uint8)

    if signed_cb:
        data = idx.astype(jnp.uint8)                          # [K/8, Nc]
    elif xs_signs:
        # representable sign vectors have EVEN popcount (bit 7 is the
        # parity of bits 0-6); when the desired signs are odd, flip the
        # cheapest position — the one with the least |w x c| at stake
        neg = stored_neg(idx)
        shifts = jnp.arange(7, dtype=jnp.int32).reshape(1, 7, 1)
        sign7 = jnp.sum(neg[:, :7] << shifts, axis=1)         # [g, Nc]
        code = idx.astype(jnp.int32) | (sign7 << 9)           # 16 bits
        data = jnp.stack([code & 0xFF, code >> 8],
                         axis=1).reshape(2 * g, nc).astype(jnp.uint8)
    else:
        neg = stored_neg(idx)
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        signs = jnp.sum(neg << shifts, axis=1).astype(jnp.uint8)
        data = jnp.stack([idx.astype(jnp.uint8), signs],
                         axis=1).reshape(2 * g, nc)
    return data, d.astype(jnp.bfloat16), aux, extra


def _quantize_iqx(x: jax.Array, qtype: str,
                  qw: Optional[jax.Array]) -> QTensor:
    """Host-chunked iq encode (runs once at load time; the [G,256,N]
    score tensor is why this is chunked over N rather than one jit)."""
    k, n = x.shape
    x = _pad_k(jnp.asarray(x, jnp.float32), 256)
    kp = x.shape[0]
    if qw is None:
        wv = jnp.ones((kp, 1), jnp.float32)
    else:
        wv = _pad_k(jnp.asarray(qw, jnp.float32).reshape(-1, 1), 256)
        wv = jnp.maximum(wv, 1e-12)

    datas, ds, auxs, extras = [], [], [], []
    for c0 in range(0, n, _IQ_CHUNK):
        xc = x[:, c0:c0 + _IQ_CHUNK]
        if qw is None:
            wc = wv
        else:
            # llama.cpp's iq quantizers don't use the raw imatrix as
            # the MSE weight — they modulate it by weight magnitude,
            # w = qw * sqrt(sigma2 + x^2), sigma2 = 2*mean(x^2) per
            # superblock (quantize_row_iq2_xxs_impl and friends). The
            # raw-qw objective over-protects high-importance but
            # small-magnitude coordinates and measurably HURT iq ppl
            # on the in-repo testbeds (the r4 imatrix anomaly).
            x2 = xc * xc
            sigma2 = 2.0 * jnp.mean(
                x2.reshape(kp // 256, 256, -1), axis=1, keepdims=True)
            wc = wv * jnp.sqrt(
                (sigma2 + x2.reshape(kp // 256, 256, -1))
            ).reshape(kp, -1)
        data, d, aux, extra = _iqx_encode_chunk(xc, wc, qtype)
        datas.append(data)
        ds.append(d)
        auxs.append(aux)
        if extra is not None:
            extras.append(extra)
    return QTensor(jnp.concatenate(datas, axis=1),
                   jnp.concatenate(ds, axis=1),
                   # iq1_m: packed per-group delta bits ride the (otherwise
                   # unused) zero plane
                   jnp.concatenate(extras, axis=1) if extras else None,
                   get_qtype(qtype).name, (k, n),
                   aux=jnp.concatenate(auxs, axis=1))


def _dequantize_iqx(qt_t: QTensor, dtype) -> jax.Array:
    from bigdl_tpu.ops.codebooks import group_codebook

    t = qt_t.qt
    k, n = qt_t.shape
    cb = jnp.asarray(group_codebook(t.codebook))               # [J, 8]
    name = t.name
    signed_cb = name in ("iq1_s", "iq1_m")
    sub = 16 if name == "iq1_m" else 32

    if signed_cb:
        idx = qt_t.data                                        # [Kp/8, N]
        g = idx.shape[0]
        vals = cb[idx]                                         # [g, N, 8]
        vals = vals.transpose(0, 2, 1)                         # [g, 8, N]
        if name == "iq1_m":
            shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
            bits = (qt_t.zero.astype(jnp.int32)[:, None, :] >> shifts) & 1
            delta = jnp.where(bits.astype(bool), _IQ_DELTA, -_IQ_DELTA)
            vals = vals + delta.reshape(g, 1, n)
    elif name == "iq2_xs":
        gi = qt_t.data.reshape(-1, 2, qt_t.data.shape[1])
        code = (gi[:, 0].astype(jnp.int32)
                | (gi[:, 1].astype(jnp.int32) << 8))           # [g, N]
        idx, sign7 = code & 0x1FF, code >> 9
        g = idx.shape[0]
        vals = cb[idx].transpose(0, 2, 1)                      # [g, 8, N]
        # bit 7 of the sign byte is the parity of bits 0-6 (the derived
        # ksigns rule, ops/iq_grids.ksigns)
        par = sign7 ^ (sign7 >> 4)
        par = par ^ (par >> 2)
        par = par ^ (par >> 1)
        full = sign7 | ((par & 1) << 7)
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        neg = (full[:, None, :] >> shifts) & 1
        vals = vals * (1.0 - 2.0 * neg.astype(jnp.float32))
    else:
        gi = qt_t.data.reshape(-1, 2, qt_t.data.shape[1])
        idx, signs = gi[:, 0], gi[:, 1]
        g = idx.shape[0]
        vals = cb[idx].transpose(0, 2, 1)                      # [g, 8, N]
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        neg = (signs.astype(jnp.int32)[:, None, :] >> shifts) & 1
        vals = vals * (1.0 - 2.0 * neg.astype(jnp.float32))
    kp = g * 8

    s4p = qt_t.aux
    lo = (s4p & jnp.uint8(0xF)).astype(jnp.float32)
    hi = (s4p >> 4).astype(jnp.float32)
    s4 = jnp.stack([lo, hi], axis=1).reshape(kp // sub, n)
    per = 256 // sub
    drep = jnp.repeat(qt_t.scale.astype(jnp.float32), per, axis=0)
    effk = jnp.repeat(drep * s4, sub, axis=0)                  # [Kp, N]

    out = vals.reshape(kp, n) * effk
    return out[:k].astype(dtype)


def _expand_scale(scale: jax.Array, block: int, kp: int) -> jax.Array:
    """[nblk, N] -> [K, N] by repeating each block row `block` times."""
    nblk, n = scale.shape
    return jnp.broadcast_to(
        scale.astype(jnp.float32)[:, None, :], (nblk, block, n)
    ).reshape(kp, n)


def dequantize_impl(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    """QTensor -> dense [K, N] array of `dtype` (XLA reference path).

    Unjitted body: model forwards reach this inside their own jit, and a
    nested jit's closed_call fails to lower inside shard_map's Manual-
    mesh AOT trace (see ops/pallas/dequant_matmul.q_matmul_kernel).
    The jitted public alias `dequantize` is defined below for eager
    callers (conversion, tests)."""
    t = qt.qt
    k, n = qt.shape
    b = t.block_size

    if t.kind == "iqx":
        return _dequantize_iqx(qt, dtype)

    if t.kind == "sym" and t.bits == 8:
        kp = qt.data.shape[0]
        vals = qt.data.astype(jnp.float32)  # signed codes in [-128, 127]
        out = vals * _expand_scale(qt.scale, b, kp)
        return out[:k].astype(dtype)

    if t.kind == "fp8":
        kp = qt.data.shape[0]
        vals = qt.data.astype(jnp.float32)
        out = vals * _expand_scale(qt.scale, b, kp)
        return out[:k].astype(dtype)

    if t.kind == "codebook":
        codes = _unpack4(qt.data, b)
        kp = codes.shape[0]
        code = jnp.asarray(CODEBOOKS[t.codebook])
        vals = code[codes]
        out = vals * _expand_scale(qt.scale, b, kp)
        return out[:k].astype(dtype)

    if t.kind == "sym" and t.bits == 4:
        if qt.data.dtype == jnp.int4:      # MXU layout: signed, unpacked
            kp = qt.data.shape[0]
            vals = qt.data.astype(jnp.float32)
        else:
            codes = _unpack4(qt.data, b)
            kp = codes.shape[0]
            vals = codes.astype(jnp.float32) - 8.0
        out = vals * _expand_scale(qt.scale, b, kp)
        return out[:k].astype(dtype)

    if t.kind == "sym" and t.bits == 5:
        lo = _unpack4(qt.data, b)
        hi = _unpack_bits1(qt.aux)
        kp = lo.shape[0]
        codes = lo | (hi[:kp] << 4)
        vals = codes.astype(jnp.float32) - 16.0
        out = vals * _expand_scale(qt.scale, b, kp)
        return out[:k].astype(dtype)

    if t.kind == "asym" and t.bits == 4:
        codes = _unpack4(qt.data, b)
        kp = codes.shape[0]
        d = _expand_scale(qt.scale, b, kp)
        m = _expand_scale(qt.zero, b, kp)
        out = codes.astype(jnp.float32) * d + m
        return out[:k].astype(dtype)

    if t.kind == "q2k":
        codes = _unpack2(qt.data, b).astype(jnp.float32)    # [Kp, N]
        kp = codes.shape[0]
        sc4 = (qt.aux & jnp.uint8(0xF)).astype(jnp.float32)  # [Kp/16, N]
        m4 = (qt.aux >> 4).astype(jnp.float32)
        rep16 = lambda a: jnp.repeat(a, 16, axis=0)
        d = _expand_scale(qt.scale, b, kp)
        dmin = _expand_scale(qt.zero, b, kp)
        out = d * rep16(sc4) * codes - dmin * rep16(m4)
        return out[:k].astype(dtype)

    if t.kind == "asym" and t.bits == 5:
        lo = _unpack4(qt.data, b)
        hi = _unpack_bits1(qt.aux)
        kp = lo.shape[0]
        codes = lo | (hi[:kp] << 4)
        d = _expand_scale(qt.scale, b, kp)
        m = _expand_scale(qt.zero, b, kp)
        out = codes.astype(jnp.float32) * d + m
        return out[:k].astype(dtype)

    raise ValueError(f"cannot dequantize {t.name}")


# ---------------------------------------------------------------------------
# Linear-weight conveniences (HF [out, in] orientation)
# ---------------------------------------------------------------------------


# Mixed-precision policies: per-TENSOR candidate pick by dequantization MSE
# (the reference's mixed_fp4/mixed_fp8, low_bit_linear.py:302-335: each
# layer independently gets whichever 4-/8-bit format reconstructs it best).
MIXED_QTYPES = {
    "mixed_fp4": ("fp4", "nf4", "sym_int4"),
    "mixed_fp8": ("fp8_e4m3", "fp8_e5m2", "sym_int8"),
}


def quantize_auto(x: jax.Array, qtype: str,
                  qw: Optional[jax.Array] = None) -> QTensor:
    """quantize(), plus the mixed_* policies (MSE-picked candidate; the
    MSE is imatrix-weighted when qw is given)."""
    if qtype not in MIXED_QTYPES:
        return quantize(x, qtype, qw=qw)
    xf = jnp.asarray(x, jnp.float32)
    wcol = (None if qw is None
            else jnp.asarray(qw, jnp.float32).reshape(-1, 1))
    best_qt, best_err = None, None
    for cand in MIXED_QTYPES[qtype]:
        qt = quantize(xf, cand, qw=qw)
        sq = (dequantize(qt, jnp.float32) - xf) ** 2
        if wcol is not None:
            sq = sq * wcol
        err = float(jnp.mean(sq))
        if best_err is None or err < best_err:
            best_qt, best_err = qt, err
    return best_qt


def quantize_linear(w_out_in: jax.Array, qtype: str,
                    qw: Optional[jax.Array] = None) -> QTensor:
    """Quantize an HF-layout linear weight [out, in] -> QTensor [in, out].

    `qw` is the imatrix row for this weight: importance per INPUT feature
    (length in_features = our contraction dim K)."""
    return quantize_auto(jnp.asarray(w_out_in).T, qtype, qw=qw)


def dequantize_linear(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    """QTensor [in, out] -> HF-layout dense weight [out, in]."""
    return dequantize(qt, dtype=dtype).T


def concat_qtensors_n(ws) -> QTensor:
    """Concatenate QTensors along N (the output dim).

    Because blocks run along K and every column quantizes independently,
    the result is BIT-IDENTICAL to quantizing the concatenated dense
    weight — the basis for merged-QKV / merged-gate-up projections (the
    reference does the same surgery on dense weights in `_optimize_pre`,
    transformers/convert.py:529-640). Works on layer-stacked planes
    (leading L dims) since every plane is N-last."""
    import dataclasses as dc

    w0 = ws[0]
    if len({w.qtype for w in ws}) != 1:
        raise ValueError("cannot concat mixed qtypes: "
                         f"{[w.qtype for w in ws]}")
    if len({w.shape[0] for w in ws}) != 1:
        raise ValueError("cannot concat differing K: "
                         f"{[w.shape for w in ws]}")
    rep = {}
    for f in ("data", "scale", "zero", "aux"):
        planes = [getattr(w, f) for w in ws]
        if any(p is None for p in planes):
            if any(p is not None for p in planes):
                raise ValueError(f"inconsistent {f} planes across operands")
            continue
        rep[f] = jnp.concatenate(planes, axis=-1)
    n_total = sum(w.shape[1] for w in ws)
    return dc.replace(w0, shape=(w0.shape[0], n_total), **rep)


def split_qtensor_n(w: QTensor, sizes) -> list:
    """Inverse of `concat_qtensors_n`: slice along N at the given sizes."""
    import dataclasses as dc

    if sum(sizes) != w.shape[1]:
        raise ValueError(f"split sizes {sizes} != N={w.shape[1]}")
    outs, off = [], 0
    for s in sizes:
        rep = {f: getattr(w, f)[..., off:off + s]
               for f in ("data", "scale", "zero", "aux")
               if getattr(w, f) is not None}
        outs.append(dc.replace(w, shape=(w.shape[0], s), **rep))
        off += s
    return outs


# public jitted alias (eager callers: conversion utilities, tests)
dequantize = functools.partial(
    jax.jit, static_argnames=("dtype",))(dequantize_impl)


# ---------------------------------------------------------------------------
# MXU (int4-dtype) weight layout
# ---------------------------------------------------------------------------


def to_mxu_layout(qt: QTensor) -> QTensor:
    """sym_int4 canonical (split-block packed uint8) -> int4-dtype data.

    The decode GEMV's bottleneck is the VPU nibble unpack (~6 i32 vector
    ops per weight over ~4 GB of weights every token). XLA stores
    jnp.int4 arrays bit-packed (same HBM bytes) and Mosaic loads them
    natively, so the in-kernel per-weight work drops to ONE
    int4->int8/bf16 convert. The transform is applied once at load time
    (transformers/model.py); the canonical layout remains the on-disk /
    GGUF interchange format (`from_mxu_layout` restores it bit-exactly —
    codes are just shifted by 8). sym_int8 is already MXU-ready; other
    qtypes pass through.

    One path for traced, host and device inputs: the chunked unpack
    below, jitted for concrete arrays. A relayout that fails RAISES — a
    load that kept the canonical packing instead would land the whole
    deployment on the slower split-block kernels without a word."""
    if qt.qtype not in ("sym_int4",) or qt.data.dtype == jnp.int4:
        return qt
    if qt.data.ndim >= 4:
        # [L, E, K//2, N] MoE expert stacks: the ragged MoE prefill
        # kernel (ops/pallas/moe_dispatch.py) and the vmapped decode
        # gather probe read the canonical packing — converting them
        # would feed int4-dtype data to kernels that bit-unpack uint8.
        return qt
    b2 = qt.qt.block_size // 2
    if isinstance(qt.data, jax.core.Tracer):
        return dataclasses.replace(qt, data=_mxu_unpack(qt.data, b2))
    return dataclasses.replace(
        qt, data=_mxu_unpack_jit(jnp.asarray(qt.data), b2=b2))


def _mxu_unpack(packed, b2: int):
    """Split-block packed uint8 [..., K/2, N] -> int4 codes [..., K, N].

    lax.map over the superblock axis bounds the transient to one
    [b2, n] row group: an unchunked expansion materializes ~4x the
    packed bytes (uint8 codes + int8) next to the resident model — a
    multi-GB load-time HBM spike for 7B stacked leaves."""
    *lead, k2, n = packed.shape

    def step(rows):
        codes = jnp.concatenate([rows & 0x0F, rows >> 4], axis=-2)
        return (codes.astype(jnp.int8) - jnp.int8(8)).astype(jnp.int4)

    out = jax.lax.map(step, packed.reshape(-1, b2, n))  # [S, 2*b2, n]
    return out.reshape(*lead, k2 * 2, n)


# The load-time relayout executable. Its output takes the device's
# default int4 layout — the one a host->device transfer produces and
# every compiled consumer expects (checked by AOT compile for v5e:
# row-major, T(64,128)(8,1)) — and follows the input's sharding.
_mxu_unpack_jit = tracked_jit("int4_mxu_relayout", _mxu_unpack,
                              static_argnames=("b2",))


def from_mxu_layout(qt: QTensor) -> QTensor:
    """Inverse of `to_mxu_layout` (for save_low_bit / GGUF export),
    chunked and jitted the same way: the eager op-by-op form holds
    several full-size int8/uint8 expansions of a stacked 7B leaf at
    once, next to the resident model."""
    if getattr(qt.data, "dtype", None) != jnp.int4:
        return qt
    b = qt.qt.block_size
    if isinstance(qt.data, jax.core.Tracer):
        return dataclasses.replace(qt, data=_mxu_pack(qt.data, b))
    return dataclasses.replace(qt, data=_mxu_pack_jit(qt.data, b=b))


def _mxu_pack(codes4, b: int):
    """int4 codes [..., K, N] -> split-block packed uint8 [..., K/2, N]."""
    *lead, k, n = codes4.shape

    def step(rows):
        c = (rows.astype(jnp.int8) + jnp.int8(8)).astype(jnp.uint8)
        return c[:b // 2] | (c[b // 2:] << 4)

    out = jax.lax.map(step, codes4.reshape(-1, b, n))   # [S, b/2, n]
    return out.reshape(*lead, k // 2, n)


_mxu_pack_jit = tracked_jit("int4_mxu_repack", _mxu_pack,
                            static_argnames=("b",))


def tree_to_mxu_layout(tree):
    """Apply `to_mxu_layout` to every sym_int4 QTensor in a pytree."""
    return jax.tree_util.tree_map(
        lambda x: to_mxu_layout(x) if isinstance(x, QTensor) else x,
        tree, is_leaf=lambda x: isinstance(x, QTensor))


def tree_from_mxu_layout(tree):
    return jax.tree_util.tree_map(
        lambda x: from_mxu_layout(x) if isinstance(x, QTensor) else x,
        tree, is_leaf=lambda x: isinstance(x, QTensor))


def prepack_tree(tree, mode: Optional[str] = None):
    """One-time load-time weight prepacking: retile every QTensor's
    code/scale planes into the layout the decode kernels want (today:
    the int4-dtype MXU layout for sym_int4 — native Mosaic int4 loads
    instead of the VPU nibble-unpack chain). Applied ONCE at checkpoint
    load (transformers/model.py); `save_low_bit` always repacks to the
    canonical split-block interchange format via `tree_from_mxu_layout`.

    `mode`: "auto" (prepack when the compute target is TPU), "on"
    (force the retile even off-TPU: the CPU fallbacks read both
    layouts, so "on" stays testable anywhere), "off"; defaults to
    flags().prepack (BIGDL_TPU_PREPACK).

    Returns (tree, report): report is a plain-JSON dict (mode, applied,
    qtensor/converted counts, packed bytes) that the memory ledger
    records, so a failed or skipped retile is visible instead of
    silently changing which GEMV body a deployment runs."""
    from bigdl_tpu.config import flags, resolve_prepack, target_is_tpu

    mode = resolve_prepack(mode) if mode is not None else flags().prepack
    report: dict = {"mode": mode, "applied": False,
                    "qtensors": 0, "converted": 0, "bytes_packed": 0}
    if mode == "off" or (mode != "on" and not target_is_tpu()):
        return tree, report

    is_q = lambda x: isinstance(x, QTensor)  # noqa: E731

    def conv(x):
        if not is_q(x):
            return x
        report["qtensors"] += 1
        y = to_mxu_layout(x)
        if y.data.dtype != x.data.dtype:
            report["converted"] += 1
        report["bytes_packed"] += int(y.nbytes)
        return y

    tree = jax.tree_util.tree_map(conv, tree, is_leaf=is_q)
    report["applied"] = report["converted"] > 0
    return tree, report
