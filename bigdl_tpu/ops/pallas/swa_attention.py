"""Pallas TPU kernels: single-token (decode) attention over K/V planes
that keep a position's heads side by side in the lanes, for a model
whose keys and values have different widths and whose layers are of two
kinds (`models/mimo_v2.py`): a sweep of a full-length plane, and a
window layer's RING with a learned sink in its softmax.

Why the heads lie in the lanes. A key of 192 values is a lane tile and
a half: a `[L, B, S, Hkv, 192]` stack is tiled over `(Hkv, 192)`, and a
kernel that reads it gets the 192 padded to 256, by a copy of the whole
stack in every call (AOT for v5e, `decode_attention` at 4 heads of 192 /
128 over 3 x 16 x 16384: 1.61 GB of temporaries, the K stack at 256
lanes). A row of `Hkv x 192` = 768 values (`Hkv' x 192` = 1,536 in a
window layer) is whole tiles, and so is V's `Hkv x 128`: the planes are
`[L, B, S, Hkv * hd]` and a block is `[sb, Hkv * hd]`, read as it lies.

How every head meets its own keys without a per-head view. The query
is laid out BLOCK-DIAGONALLY before the call (`block_diagonal`): row h
of `[Hp, Hkv * hd_k]` holds head h's query in the columns of its own KV
head `h // G` and zeros elsewhere, so ONE `[Hp, Hkv hd_k] x [Hkv hd_k,
sb]` MXU pass gives `[Hp, sb]` scores in which each head has met only
its own KV head: no mask over heads, and the softmax runs over `sb`
columns a head, not `sb * Hkv` (`decode_attention.py` pays that for
reading `(s, head)` rows). `p x V` gives `[Hp, Hkv * hd_v]`; `own_head`
keeps each head's own `hd_v` columns afterwards. The MXU does `Hkv`
times the needed work and is not the bound: the time follows the bytes.

`decode_attention_lanes` sweeps a slot's S-blocks up to the one that
holds its `pos` (the steps past it name that block again and fetch
nothing, `decode_attention._named_block`). `swa_decode_attention` sweeps
a slot's ring in the same blocks (`s_block`: a ring of 128 columns is
one block, one of 2048 x 512 lanes two): column `c` holds the position
`pos - ((pos - c) mod ring)`, live while it is no more than `window - 1`
behind `pos` and not below 0. While a ring is still filling (`pos <
ring - 1`) only the columns `0 .. pos` hold anything, so the blocks past
the one that holds `pos` are dead and are named again, not fetched, by
the full plane's own rule (`ring_blocks` counts them); once the ring is
full and as long as the window every column is live, and softmax does
not care about their order. The sink is one learned scalar a query
head: a column of the softmax with no value, which is what the online
softmax starts from (`m = b`, `l = 1`, `acc = 0`); with none it starts
from nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.decode_attention import _NEG_INF, _named_block

FULL_NAME = "decode_attention_lanes"
WINDOW_NAME = "swa_decode_attention"
# bytes of one K block: 1024 positions x 768 bf16 values
_BLOCK_BYTES = 1536 * 1024


def s_block(s: int, width: int) -> int:
    """Positions of one block: the largest power-of-two multiple of 128
    that divides `s` and keeps a `[sb, width]` bf16 block within
    `_BLOCK_BYTES`; an `s` that is no multiple of 128 (which the kernels
    refuse, `lanes_supported`) is one block."""
    if s % 128:
        return s
    sb = 128
    while s % (sb * 2) == 0 and sb * 2 * width * 2 <= _BLOCK_BYTES:
        sb *= 2
    return sb


def ring_blocks(positions, ring: int, width: int):
    """`(live, dead)` blocks of ONE window layer's K ring (as many of V)
    in a decode step whose queries sit at `positions` (plain ints, < 0
    an empty slot): the kernel's own rule, a block is fetched while it
    starts at or before `pos`."""
    sb = s_block(ring, width)
    ns = ring // sb
    held = [int(p) for p in positions if p >= 0]
    live = sum(min(p // sb, ns - 1) + 1 for p in held)
    return live, len(held) * ns - live


def block_diagonal(q: jax.Array, hkv: int) -> jax.Array:
    """`[B, H, d]` queries -> `[B, Hp, Hkv * d]`: head h's query in the
    columns of KV head `h // (H / Hkv)`, zeros elsewhere; `Hp` is `H`
    rounded up to the 16 sublanes of a bf16 tile."""
    b, h, d = q.shape
    own = (jnp.arange(h)[:, None] // (h // hkv)
           == jnp.arange(hkv)[None, :])                          # [H, Hkv]
    out = jnp.where(own[None, :, :, None], q[:, :, None, :],
                    jnp.zeros((), q.dtype)).reshape(b, h, hkv * d)
    return jnp.pad(out, ((0, 0), (0, -h % 16), (0, 0)))


def own_head(out: jax.Array, h: int, hkv: int) -> jax.Array:
    """`[B, Hp, Hkv * d]` -> `[B, H, d]`: of every head's row the
    columns of its own KV head."""
    b, d = out.shape[0], out.shape[-1] // hkv
    o = out[:, :h].reshape(b, hkv, h // hkv, hkv, d)
    eye = jnp.eye(hkv, dtype=bool)[None, :, None, :, None]
    return jnp.sum(jnp.where(eye, o, jnp.zeros((), o.dtype)),
                   axis=3).reshape(b, h, d)


def _kernel(layer_ref, pos_ref, q_ref, *rest, scale, sb, ns, window, sink):
    """One (slot, S-block) step of the online softmax. `window` > 0: the
    plane is a ring of `ns * sb` columns."""
    if sink:
        sink_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref = rest
    del layer_ref                     # consumed by the index maps
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(sj == 0)
    def _():
        if sink:
            # the sink is the softmax's first column and has no value
            m_ref[:] = sink_ref[...]
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # an empty slot (pos < 0) runs no block and writes zeros; a block
    # wholly past pos would add nothing (of a ring too: a position past
    # the ring's end has wrapped, and every block starts before it)
    @pl.when(sj * sb <= pos)
    def _():
        s_ = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [Hp, sb]
        col = sj * sb + jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
        if window:
            ring = ns * sb
            behind = pos % ring - col
            behind = jnp.where(behind < 0, behind + ring, behind)
            live = (behind < window) & (behind <= pos)
        else:
            live = col <= pos
        s_ = jnp.where(live, s_, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # a masked column sits 1e30 under the running max: exp -> 0
        p = jnp.exp(s_ - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(jnp.bfloat16), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(sj == ns - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)


def _call(name, q, k, v, q_pos, scale, hkv, layer, window, sink, interpret):
    b, h, _ = q.shape
    s, wk, wv = k.shape[2], k.shape[3], v.shape[3]
    sb = s_block(s, wk)
    ns = s // sb
    qd = block_diagonal(q.astype(jnp.bfloat16), hkv)
    hp = qd.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_index(bi, sj, lyr_ref, pos_ref):
        return lyr_ref[0], bi, _named_block(pos_ref, bi, sj, sb, ns), 0

    in_specs = [pl.BlockSpec((None, hp, wk), lambda bi, sj, *_: (bi, 0, 0))]
    operands = [lyr, pos, qd]
    if sink is not None:
        in_specs.append(pl.BlockSpec((hp, 128), lambda bi, sj, *_: (0, 0)))
        operands.append(jnp.broadcast_to(jnp.pad(
            sink.astype(jnp.float32), (0, hp - h))[:, None], (hp, 128)))
    in_specs += [pl.BlockSpec((None, None, sb, wk), kv_index),
                 pl.BlockSpec((None, None, sb, wv), kv_index)]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, sb=sb, ns=ns, window=window,
                          sink=sink is not None),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, hp, wv),
                                   lambda bi, sj, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, wv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, wv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, k, v)
    return own_head(out, h, hkv)


@functools.partial(jax.jit, static_argnames=("scale", "hkv", "interpret"))
def decode_attention_lanes_pallas(
    q: jax.Array,          # [B, H, hd_k]
    k: jax.Array,          # [L, B, S, Hkv * hd_k] bf16
    v: jax.Array,          # [L, B, S, Hkv * hd_v]
    q_pos: jax.Array,      # scalar int32 or [B]; < 0: an empty slot
    scale: float,
    hkv: int,
    layer=0,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention of one row a slot over the positions `0 ..
    q_pos` of layer `layer` of the stack: `[B, H, hd_v]` in q.dtype."""
    return _call(FULL_NAME, q, k, v, q_pos, scale, hkv, layer, 0, None,
                 interpret)


@functools.partial(jax.jit,
                   static_argnames=("scale", "hkv", "window", "interpret"))
def swa_decode_attention_pallas(
    q: jax.Array,          # [B, H, hd_k]
    k: jax.Array,          # [L, B, ring, Hkv * hd_k] bf16
    v: jax.Array,          # [L, B, ring, Hkv * hd_v]
    q_pos: jax.Array,      # scalar int32 or [B]; < 0: an empty slot
    scale: float,
    hkv: int,
    window: int,
    sink=None,             # [H] float32: the softmax's extra column
    layer=0,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention of one row a slot over the last `window`
    positions in layer `layer` of the ring stack, the sink in the
    softmax: `[B, H, hd_v]` in q.dtype."""
    return _call(WINDOW_NAME, q, k, v, q_pos, scale, hkv, layer, int(window),
                 sink, interpret)


def lanes_supported(q, k, v, hkv: int) -> bool:
    """Geometry both kernels take: bf16 planes whose rows are whole lane
    tiles, positions in whole 128s."""
    return (k.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16
            and k.ndim == 4 and q.shape[1] % hkv == 0
            and k.shape[3] == hkv * q.shape[2]
            and k.shape[3] % 128 == 0 and v.shape[3] % 128 == 0
            and k.shape[2] % 128 == 0)
