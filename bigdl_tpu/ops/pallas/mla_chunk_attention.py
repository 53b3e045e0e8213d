"""Pallas TPU kernel: a prefill CHUNK's attention over a LATENT cache,
in the expanded form.

`T` query rows of one sequence at positions `p .. p + T - 1` attend the
latent plane `[C + R, S]` of their layer (the chunk's own rows already
written). K and V of a block of keys are expanded from the block's
compressed rows per head (`k_n = c_kv W_uk^T`, `v = c_kv W_uv`, rounded
to bf16 as the XLA sweep of `models/dots3_note.py` rounds them), the
scores `(q_n . k_n + q_r . k_r) * scale` are taken in float32, masked
by the causal bound `key <= p + row` and, where a selection `sel` `[T,
S]` is given, by its tile; the online softmax's state and every score
tile stay in VMEM. What XLA writes to HBM and reads back five times a
key block, `[heads, rows, 1024]` float32, never leaves the chip.

Grid `(sequence, head, key block)`: all `T` rows of one head are
resident (`q` `[T, nope + R]` bf16, `acc` `[T, v]` float32), so a key
block is expanded ONCE a head (no more operations than the XLA sweep),
and its rows go through the softmax `tq` at a time. `p` is a prefetched
scalar: a grid step past the last live block computes nothing and its
index maps name the last live block again, which the pipeline does not
fetch twice (`mla_attention.py`'s pattern); the latent STACK `[L, B, C
+ R, S]` and the layer index come as the decode kernels take them, so
no layer's plane is copied out.

VMEM at the cells' geometry (H 128, C 512, R 64, T 1024, 1024 keys a
block): the latent block 1.2 MB and the selection's int8 tile 1 MB, two
of each in flight; `q` 0.5 MB and the output block 0.5 MB, two each;
K and V 0.5 MB; `m`, `l`, `acc` 1.5 MB; the row blocks' score tiles 1-2
MB each: over the compiler's default of 16 MB, so `vmem_limit_bytes` is
raised to `_VMEM_LIMIT`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
NAME = "mla_chunk_attention"
_VMEM_LIMIT = 64 * 2 ** 20
_MAX_ROWS = 2048


def _key_block(s: int) -> int:
    """Keys a grid step holds: the largest of these that divides S."""
    for kb in (1024, 512, 256, 128):
        if s % kb == 0:
            return kb
    return 0


def _row_block(t: int) -> int:
    """Rows that go through the softmax together; 0 for a chunk the
    kernel does not take (every row of a head is resident and the row
    blocks are unrolled: compiled for v5e up to `_MAX_ROWS`)."""
    if t > _MAX_ROWS:
        return 0
    for tq in (256, 128):
        if t % tq == 0:
            return tq
    return t if t % 16 == 0 and t < 128 else 0


def mla_chunk_supported(q_nope, q_pe, latent, w_uk, w_uv) -> bool:
    """Geometry gate: a bf16 plane of `C + R` rows with `C`, the nope
    and the value widths multiples of 128 (slices at tile boundaries,
    the output a head's lane block), `S` a multiple of the key block,
    up to `_MAX_ROWS` rows in whole row blocks."""
    nope, r = q_nope.shape[-1], q_pe.shape[-1]
    c, vd = w_uv.shape[-2:]
    return (latent.dtype == jnp.bfloat16 and latent.shape[-2] == c + r
            and w_uk.shape[-2:] == (nope, c)
            and c % 128 == 0 and nope % 128 == 0 and vd % 128 == 0
            and r % 16 == 0 and _key_block(latent.shape[-1]) > 0
            and _row_block(q_nope.shape[1]) > 0)


def _kernel(*refs, scale, kb, nk, c, nope, tq, t, s, masked):
    layer_ref, pos_ref, q_ref, wk_ref, wv_ref = refs[:5]
    sel_ref = refs[5] if masked else None
    lat_ref, out_ref, k_ref, v_ref, m_ref, l_ref, acc_ref = refs[5 + masked:]
    del layer_ref                     # consumed by the index maps
    kj = pl.program_id(2)
    p = pos_ref[pl.program_id(0)]

    @pl.when(kj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kj * kb <= jnp.minimum(p + (t - 1), s - 1))
    def _():
        ckv = lat_ref[:c, :]                                  # [C, kb]
        nn = (((1,), (0,)), ((), ()))
        k_ref[...] = jax.lax.dot_general(
            wk_ref[...], ckv, nn,
            preferred_element_type=jnp.float32).astype(k_ref.dtype)
        v_ref[...] = jax.lax.dot_general(
            wv_ref[...], ckv, nn,
            preferred_element_type=jnp.float32).astype(v_ref.dtype)
        # key `col` of the block is live for row `row` of the chunk
        # while kj * kb + col <= p + row
        ahead = (jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 0)
                 - jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1))
        for r0 in range(0, t, tq):
            rows = slice(r0, r0 + tq)
            q = q_ref[rows, :]
            s_ = (jax.lax.dot_general(q[:, :nope], k_ref[...], nn,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(q[:, nope:], lat_ref[c:, :], nn,
                                        preferred_element_type=jnp.float32)
                  ) * scale
            live = ahead >= kj * kb - p - r0
            if masked:
                live &= sel_ref[rows, :].astype(jnp.int32) != 0
            s_ = jnp.where(live, s_, _NEG)
            m_prev = m_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a row with no live key yet sits at m = -1e30, where the
            # exponential of a dead key is 1: zeroed, as the XLA sweep does
            pr = jnp.where(live, jnp.exp(s_ - m_new), 0.0)
            l_ref[rows, :] = jnp.broadcast_to(
                l_ref[rows, :1] * corr + jnp.sum(pr, axis=-1, keepdims=True),
                (tq, l_ref.shape[1]))
            pv = jax.lax.dot_general(
                pr.astype(v_ref.dtype), v_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [tq, v]
            acc_ref[rows, :] = acc_ref[rows, :] * corr + pv
            m_ref[rows, :] = jnp.broadcast_to(m_new, (tq, m_ref.shape[1]))

    @pl.when(kj == nk - 1)
    def _():
        out_ref[...] = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_chunk_attention_pallas(
    q_nope: jax.Array,     # [B, T, H, nope]
    q_pe: jax.Array,       # [B, T, H, R] roped
    latent: jax.Array,     # [L, B, C + R, S] bf16 stack
    pos: jax.Array,        # scalar or [B] int32: the chunk's first position
    sel,                   # [B, T, S] bool / int, or None: causal only
    w_uk: jax.Array,       # [H, nope, C]
    w_uv: jax.Array,       # [H, C, v]
    scale: float,
    layer=0,               # int32 scalar: which layer of the stack
    interpret: bool = False,
) -> jax.Array:
    """The chunk's heads' outputs `[B, T, H, v]` float32."""
    b, t, h, nope = q_nope.shape
    r = q_pe.shape[-1]
    c, vd = w_uv.shape[-2:]
    s = latent.shape[-1]
    kb, tq = _key_block(s), _row_block(t)
    if not mla_chunk_supported(q_nope, q_pe, latent, w_uk, w_uv):
        raise NotImplementedError(
            f"{NAME} kernel: {t} rows of {h} x ({nope} + {r}) against latent "
            f"{latent.shape}, W_uv {w_uv.shape} is not a geometry it handles")
    nk = s // kb
    bf = jnp.bfloat16
    # a head's rows together: [B, H, T, nope + R]
    q = jnp.swapaxes(jnp.concatenate([q_nope, q_pe], axis=-1).astype(bf),
                     1, 2)
    wk = w_uk.astype(bf)
    wv = jnp.swapaxes(w_uv, 1, 2).astype(bf)                  # [H, v, C]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def blk(bi, kj, pos_ref):
        # past the sequence's last live block the same block is named
        # again: no new fetch
        return jnp.minimum(
            kj, jnp.minimum(pos_ref[bi] + (t - 1), s - 1) // kb)

    in_specs = [
        pl.BlockSpec((None, None, t, nope + r),
                     lambda bi, hi, kj, *_: (bi, hi, 0, 0)),
        pl.BlockSpec((None, nope, c), lambda bi, hi, kj, *_: (hi, 0, 0)),
        pl.BlockSpec((None, vd, c), lambda bi, hi, kj, *_: (hi, 0, 0)),
    ]
    operands = [q, wk, wv]
    if sel is not None:
        in_specs.append(pl.BlockSpec(
            (None, t, kb),
            lambda bi, hi, kj, lyr_ref, pos_ref: (bi, 0,
                                                  blk(bi, kj, pos_ref))))
        operands.append(sel.astype(jnp.int8))
    in_specs.append(pl.BlockSpec(
        (None, None, c + r, kb),
        lambda bi, hi, kj, lyr_ref, pos_ref: (lyr_ref[0], bi, 0,
                                              blk(bi, kj, pos_ref))))
    operands.append(latent)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, kb=kb, nk=nk, c=c, nope=nope,
                          tq=tq, t=t, s=s, masked=sel is not None),
        name=NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nk),
            in_specs=in_specs,
            # head `hi`'s lanes of the rows' [T, H * v]
            out_specs=pl.BlockSpec((None, t, vd),
                                   lambda bi, hi, kj, *_: (bi, 0, hi)),
            scratch_shapes=[
                pltpu.VMEM((nope, kb), bf),
                pltpu.VMEM((vd, kb), bf),
                pltpu.VMEM((t, 128), jnp.float32),
                pltpu.VMEM((t, 128), jnp.float32),
                pltpu.VMEM((t, vd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, h * vd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(lyr, posv, *operands)
    return out.reshape(b, t, h, vd)
