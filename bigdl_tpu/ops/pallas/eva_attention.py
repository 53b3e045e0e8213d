"""Pallas TPU kernels of EVA chunked linearized attention's decode step
(`ops/eva.py` has the equations and the cache's planes).

`eva_decode_attention`: one query row a slot against TWO planes in one
online softmax: the exact keys of the slot's current window (`win_k` /
`win_v` `[L, B, W, H, hd]`, live columns `0 .. pos % W`) and the chunk
summaries of every earlier window (`sum_k` / `sum_v` `[L, B, Ns, H,
hd]`, live columns `0 .. (pos // W) * (W / stride) - 1`). It is
`decode_attention.py`'s sweep (a block as `sb * H` rows of `hd`, all
heads against all rows in one MXU pass, a constant own-head mask) run
over the window plane's blocks and then the summary plane's; the grid
step `sj` names window block `min(sj, last live)` while `sj < nw` and
summary block `min(sj - nw, last live)` after, so a block past what is
live is neither fetched nor multiplied (PR 31's rule, at both planes). A
slot whose `pos` is negative holds nothing and writes zeros.

`eva_summarize`: the summary of the chunk that holds a slot's `pos`,
from the chunk's `stride` rows of the window plane (those up to `pos`
are live), written into column `pos // stride` of the summary stacks in
place. Run every decode step: the write at the chunk's last position is
the whole chunk's, and nothing reads the column before the window is
finished.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.decode_attention import (_NEG_INF,
                                                   _own_head_bias, _rows,
                                                   _s_block)


def _block(n: int, h: int) -> int:
    """Positions a block of a plane of `n` columns holds: the slab
    kernel's rule where the plane tiles by 128, else the plane whole (a
    test's plane)."""
    return _s_block(n, h) if n % 128 == 0 else n


def geometry_ok(q, win_k, sum_k, stride: int) -> bool:
    """Whether the kernels take this geometry on the chip: head size a
    lane multiple, both planes in blocks of 128 positions, a chunk that
    fills whole sublane tiles."""
    hd = q.shape[-1]
    return (hd % 128 == 0 and win_k.shape[2] % 128 == 0
            and sum_k.shape[2] % 128 == 0 and stride % 8 == 0
            and win_k.dtype == jnp.bfloat16)


def _sweep_step(q_ref, bias_ref, k_ref, v_ref, n_live, m_ref, l_ref,
                acc_ref, scale, hkv):
    """One block into the online softmax: its first `n_live` positions
    are live (the slab kernel's step)."""
    q = q_ref[...].astype(jnp.bfloat16)                   # [Hp, hd]
    k = _rows(k_ref, None)                                # [sb*H, hd]
    v = _rows(v_ref, None)
    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale + bias_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
    s_ = jnp.where(col < n_live * hkv, s_, _NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_ - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape)
    pv = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)


def _attn_kernel(layer_ref, pos_ref, q_ref, wbias_ref, sbias_ref, wk_ref,
                 wv_ref, sk_ref, sv_ref, out_ref, m_ref, l_ref, acc_ref, *,
                 scale, wb, nw, sb, nsb, window, per_window, hkv):
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    wpos = jnp.maximum(pos, 0) % window          # the query's own column
    n_sum = jnp.maximum(pos, 0) // window * per_window

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((pos >= 0) & (sj < nw) & (sj * wb <= wpos))
    def _():
        _sweep_step(q_ref, wbias_ref, wk_ref, wv_ref, wpos + 1 - sj * wb,
                    m_ref, l_ref, acc_ref, scale, hkv)

    @pl.when((pos >= 0) & (sj >= nw) & ((sj - nw) * sb < n_sum))
    def _():
        _sweep_step(q_ref, sbias_ref, sk_ref, sv_ref,
                    n_sum - (sj - nw) * sb, m_ref, l_ref, acc_ref, scale,
                    hkv)

    @pl.when(sj == nw + nsb - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "stride", "interpret"))
def eva_decode_attention_pallas(q, win_k, win_v, sum_k, sum_v, pos, *,
                                scale: float, stride: int, layer=0,
                                interpret: bool = False):
    """`q` `[B, 1, H, hd]` at `pos` `[B]` (below 0: nothing held) over
    layer `layer` of the window and summary stacks. Returns `[B, 1, H,
    hd]` in `q`'s type."""
    b, sq, h, hd = q.shape
    if sq != 1:
        raise NotImplementedError("decode kernel handles Sq == 1 only")
    window, hkv = win_k.shape[2], win_k.shape[3]
    if hkv != h:
        raise NotImplementedError("one key head a query head")
    n_cols = sum_k.shape[2]
    per_window = window // stride
    hp = -(-h // 16) * 16
    wb, sb = _block(window, hkv), _block(n_cols, hkv)
    nw, nsb = window // wb, n_cols // sb

    qr = q.reshape(b, h, hd)
    if hp != h:
        qr = jnp.pad(qr, ((0, 0), (0, hp - h), (0, 0)))
    wbias = jnp.asarray(_own_head_bias(hp, 1, hkv, wb * hkv))
    sbias = jnp.asarray(_own_head_bias(hp, 1, hkv, sb * hkv))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def win_index(bi, sj, lyr_ref, pos_ref):
        last = jnp.maximum(pos_ref[bi], 0) % window // wb
        return lyr_ref[0], bi, jnp.minimum(sj, last), 0, 0

    def sum_index(bi, sj, lyr_ref, pos_ref):
        live = jnp.maximum(pos_ref[bi], 0) // window * per_window
        last = jnp.clip((live - 1) // sb, 0, nsb - 1)
        return lyr_ref[0], bi, jnp.clip(sj - nw, 0, last), 0, 0

    q_spec = pl.BlockSpec((None, hp, hd), lambda bi, sj, *_: (bi, 0, 0))
    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, wb=wb, nw=nw, sb=sb,
                          nsb=nsb, window=window, per_window=per_window,
                          hkv=hkv),
        name="eva_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nw + nsb),
            in_specs=[
                q_spec,
                pl.BlockSpec((hp, wb * hkv), lambda bi, sj, *_: (0, 0)),
                pl.BlockSpec((hp, sb * hkv), lambda bi, sj, *_: (0, 0)),
                pl.BlockSpec((None, None, wb, hkv, hd), win_index),
                pl.BlockSpec((None, None, wb, hkv, hd), win_index),
                pl.BlockSpec((None, None, sb, hkv, hd), sum_index),
                pl.BlockSpec((None, None, sb, hkv, hd), sum_index),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lyr, posv, qr, wbias, sbias, win_k, win_v, sum_k, sum_v)
    return out[:, :h, :].reshape(b, 1, h, hd)


def _summarize_kernel(layer_ref, pos_ref, phi_ref, mu_ref, wk_ref, wv_ref,
                      sk_in, sv_in, sk_ref, sv_ref, *, scale, stride):
    """One slot: the `stride` rows of its chunk -> one summary row."""
    del layer_ref, sk_in, sv_in
    pos = jnp.maximum(pos_ref[pl.program_id(0)], 0)
    k = wk_ref[...].astype(jnp.float32)                   # [c, H, hd]
    v = wv_ref[...].astype(jnp.float32)
    logit = jnp.sum(k * phi_ref[...][None], axis=-1,
                    keepdims=True) * scale                # [c, H, 1]
    row = jax.lax.broadcasted_iota(jnp.int32, logit.shape, 0)
    live = row <= pos % stride
    logit = jnp.where(live, logit, _NEG_INF)
    p = jnp.where(live,
                  jnp.exp(logit - jnp.max(logit, axis=0, keepdims=True)),
                  0.0)
    alpha = p / jnp.sum(p, axis=0, keepdims=True)
    sk_ref[...] = (jnp.sum(alpha * k, axis=0, keepdims=True)
                   + mu_ref[...][None]).astype(sk_ref.dtype)
    sv_ref[...] = jnp.sum(alpha * v, axis=0,
                          keepdims=True).astype(sv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "stride", "interpret"))
def eva_summarize_pallas(win_k, win_v, sum_k, sum_v, pos, phi, mu, *,
                         scale: float, stride: int, layer=0,
                         interpret: bool = False):
    """Rewrite column `pos[b] // stride` of layer `layer` of the summary
    stacks from the chunk of the window stacks that holds `pos[b]` (rows
    up to `pos[b]` live). `phi`, `mu` `[H, hd]`. Returns the two summary
    stacks, updated in place. A slot below 0 writes its column 0."""
    b = win_k.shape[1]
    window, h, hd = win_k.shape[2:]
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    def chunk_index(bi, lyr_ref, pos_ref):
        return (lyr_ref[0], bi,
                jnp.maximum(pos_ref[bi], 0) % window // stride, 0, 0)

    def col_index(bi, lyr_ref, pos_ref):
        return lyr_ref[0], bi, jnp.maximum(pos_ref[bi], 0) // stride, 0, 0

    vec = pl.BlockSpec((h, hd), lambda bi, *_: (0, 0))
    chunk = pl.BlockSpec((None, None, stride, h, hd), chunk_index)
    col = pl.BlockSpec((None, None, 1, h, hd), col_index)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_summarize_kernel, scale=scale, stride=stride),
        name="eva_summarize",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[vec, vec, chunk, chunk, any_, any_],
            out_specs=[col, col],
        ),
        out_shape=[jax.ShapeDtypeStruct(sum_k.shape, sum_k.dtype),
                   jax.ShapeDtypeStruct(sum_v.shape, sum_v.dtype)],
        # operands: lyr, pos, phi, mu, win_k, win_v, sum_k, sum_v
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lyr, posv, phi.astype(jnp.float32), mu.astype(jnp.float32),
      win_k, win_v, sum_k, sum_v)
