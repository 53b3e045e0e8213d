"""Pallas TPU kernel: fused single-token (decode) attention over the cache.

TPU-native replacement for the reference's decode-attention kernels —
`linear_q4_0.sdp_fp8` (FP8-KV decode SDP, reference transformers/models/
llama.py:435) and ESIMD `sdp_forward` (low_bit_linear.py:744-745 gates at
models/utils.py:315-355).

Decode attention is memory-bound: the whole KV cache is read to produce one
token. The XLA fallback computes scores/softmax/values as separate fusions
with an [B,H,1,S] intermediate round-trip; this kernel streams K and V
HBM->VMEM exactly one time, the scores/softmax/combine never leave VMEM,
and FP8 caches upcast in-register (the reference needs dedicated fp8 GEMM
kernels for the same effect).

Shapes: q [B, 1, H, hd]; cache k/v the whole STACK [L, B, S, Hkv, hd]
(bf16, float8_e5m2, or int8/int4 codes with [L, B, S, Hkv] f32 scale
planes) plus the layer index; pos int32 scalar or per-slot [B] (continuous
batching). The layer is addressed where it lies: `layer` is a prefetched
scalar and the BlockSpec index map picks block `(layer, b, s_block, 0, 0)`,
so no slice of the stack is ever materialized. A caller that holds one
layer passes `k[None]` and layer 0 (a bitcast).

The block arrives in the layout the cache has, `[sb, Hkv, hd]` — on the
chip the stack is tiled over its last two dims `(Hkv, hd)`, so a per-head
`[S, hd]` view does not exist without a relayout copy of the layer
(measured: `[B, S, Hkv*hd]` reshapes cost as much as the attention
itself). Instead the block is read as `sb * Hkv` rows of `hd` and ALL H
query heads meet all rows in one `[H, hd] x [hd, sb*Hkv]` MXU pass; a
constant additive mask keeps, for query head i, only the rows of its own
kv head i // G. The MXU does Hkv times the needed work and is still not
the bound: its time follows the rows streamed, i.e. the bytes read.

Only what is live is read. The sweep over a slot's S-blocks stops at the
block that holds its `pos`: the steps past it compute nothing and their
index map names the last live block again, which the pipeline does not
fetch twice (the latent kernel's pattern, `mla_attention.py`). A slot
whose `pos` is negative holds nothing: no block of it is multiplied and
its output is zeros; its steps name its first block, which is fetched
once (eliding that fetch too is ROADMAP S2b). `blocks_read` is that rule
as plain integers, for the engine's counter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
# rows (sb * Hkv) of one K or V block: 4096 x 128 bf16 is 1 MB a block,
# four in flight (K, V, double-buffered), scores [H, 4096] f32 in VMEM
_BLOCK_ROWS = 4096


def _s_block(s: int, hkv: int) -> int:
    """Largest 128-multiple power-of-two split of S whose block holds at
    most _BLOCK_ROWS (s, head) rows. 128 positions at least, so above 32
    kv heads a block is larger: 5120 rows at Hkv 40 (1.3 MB a block,
    scores [48, 5120] f32; compiles and runs on the v5e, PERF.md PR 26)."""
    sb = 128
    while sb * 2 * hkv <= _BLOCK_ROWS and s % (sb * 2) == 0:
        sb *= 2
    return sb


def blocks_read(positions, s: int, hkv: int) -> int:
    """S-blocks of one layer the kernel fetches for K (as many again
    for V) when the slots at `positions` (plain ints, the query's own
    position in each; below 0 for an empty slot) decode over an `S`-long
    cache of `hkv` heads: block `sb` of a slot is read while
    `sb * _s_block <= pos`, and the first block always."""
    sb = _s_block(s, hkv)
    return sum(min(max(int(p), 0) // sb, s // sb - 1) + 1
               for p in positions)


def slab_blocks(batch: int, s: int, hkv: int) -> int:
    """S-blocks of one layer of the whole slab: what `blocks_read`
    counts when every slot is full."""
    return batch * (s // _s_block(s, hkv))


def _stack_in_place(k) -> bool:
    """Whether the chip stores the [L, B, S, Hkv, hd] stack tiled over
    (Hkv, hd), which is what the kernel's blocks read. It does when hd
    fills the lanes and a position's heads fill at least one 32-bit
    sublane word (bf16 from 2 heads, 8-bit from 4, int4 from 8); below
    that it puts S inside the tile (AOT compiles for v5e, every dtype at
    Hkv 1-16 and hd 64 / 128 / 256: tests/test_aot_tpu.py)."""
    bits = 4 if k.dtype == jnp.int4 else 8 * k.dtype.itemsize
    return k.shape[-1] % 128 == 0 and k.shape[-2] * bits >= 32


def _own_head_bias(hp: int, g: int, hkv: int, rows: int) -> np.ndarray:
    """[Hp, rows] f32 additive mask, a constant of the geometry: query
    head i attends kv head i // G, so the rows of every other head
    (row c belongs to head c % Hkv) are pushed out of its softmax.
    Padded query rows keep some head's rows and are sliced off."""
    own = (np.arange(hp)[:, None] // g % hkv
           == np.arange(rows)[None, :] % hkv)
    return np.where(own, 0.0, _NEG_INF).astype(np.float32)


def _head_scales(sc_ref, hi, n, hkv):
    """Extract one kv head's scale column [n, 1] from a [1, n, Hkv] block
    (the per-head bodies of the paged-decode and prefill kernels).

    Scale planes ride full-Hkv in the lane axis (an [.., n, 1] per-head
    block would put 1 in the lanes); the column select is a one-hot
    mask + keepdims lane reduction. The [n, 1] result broadcasts over
    the K/V rows — a rank-1 [n] vector here trips Mosaic's layout
    inference ("unsupported implicit dim change"), so keep it 2D."""
    sel = jax.lax.broadcasted_iota(jnp.int32, (n, hkv), 1) == hi
    return jnp.sum(jnp.where(sel, sc_ref[0], 0.0), axis=1, keepdims=True)


def _dequant_rows(codes_ref, sc, dt=jnp.bfloat16):
    """[S, hd] codes x [S, 1] scales -> bf16 rows, matching the XLA
    fallback's `(codes * scale).astype(bf16)` bit for bit. The int->f32
    hop goes via bf16 (codes <= 127 are exact there; Mosaic has no
    direct low-bit-int -> f32 cast)."""
    return (codes_ref[0].astype(jnp.bfloat16).astype(jnp.float32)
            * sc).astype(dt)


def _rows(x_ref, sc_ref):
    """One [sb, Hkv, hd] block as [sb*Hkv, hd] bf16 rows, (s, head) order.

    With a scale block (int8/int4 codes) the rows are dequantized
    in-register as `_dequant_rows` does. The block is `[Hkv, sb]`,
    positions in the lanes, which is how the chip stores an
    [.., S, Hkv] f32 plane of few heads; the (token, head) scale must
    sit in the SUBLANES next to its row: a transpose, then a one-hot
    select and a keepdims lane reduction (a rank-2 [sb, Hkv] ->
    [sb, Hkv, 1] reshape trips Mosaic's layout inference)."""
    sb, hkv, hd = x_ref.shape
    x = x_ref[...]
    if sc_ref is None:
        x = x.astype(jnp.float32)
    else:
        eye = (jax.lax.broadcasted_iota(jnp.int32, (sb, hkv, hkv), 1)
               == jax.lax.broadcasted_iota(jnp.int32, (sb, hkv, hkv), 2))
        sc = jnp.sum(jnp.where(eye, sc_ref[...].T[:, None, :], 0.0),
                     axis=2, keepdims=True)               # [sb, Hkv, 1]
        x = x.astype(jnp.bfloat16).astype(jnp.float32) * sc
    # f32 [sb, Hkv, hd] -> [sb*Hkv, hd] keeps every (8, 128) tile whole
    return x.reshape(sb * hkv, hd).astype(jnp.bfloat16)


def _named_block(pos_ref, bi, sj, sb: int, ns: int):
    """S-block of slot `bi` that grid step (bi, sj) names. Past the
    slot's last live block it is that block again: no new fetch. An
    empty slot (`pos` < 0) names its first block throughout; a `pos`
    past the end (a caller that lets an idle slot's position run on)
    reads the slot whole."""
    return jnp.minimum(sj, jnp.clip(pos_ref[bi] // sb, 0, ns - 1))


def _kernel(layer_ref, pos_ref, q_ref, bias_ref, k_ref, v_ref, *rest,
            scale, sb, ns, hkv, scaled):
    """One (slot, S-block) step of the online-softmax sweep (the state
    machine of the prefill flash kernel, one query row per head)."""
    if scaled:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    del layer_ref                     # consumed by the index maps
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a block wholly past pos would add p = 0 under corr = 1: nothing.
    # An empty slot (pos < 0) runs no block and writes zeros
    @pl.when(sj * sb <= pos)
    def _():
        q = q_ref[...].astype(jnp.bfloat16)               # [Hp, hd]
        k = _rows(k_ref, ks_ref)                          # [sb*Hkv, hd]
        v = _rows(v_ref, vs_ref)

        s_ = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_ref[...]
        # column c is (position sj*sb + c // Hkv, head c % Hkv): live
        # while its position is <= pos
        col = jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
        s_ = jnp.where(col < (pos + 1 - sj * sb) * hkv, s_, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        # a row of another head sits >= 1e30 under the running max:
        # exp -> 0
        p = jnp.exp(s_ - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(sj == ns - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention_pallas(
    q: jax.Array,          # [B, 1, H, hd]
    k: jax.Array,          # [L, B, S, Hkv, hd] bf16|float8_e5m2|int8|int4
    v: jax.Array,
    q_pos: jax.Array,      # scalar int32 or [B] int32; < 0: an empty slot
    scale: float,
    interpret: bool = False,
    k_scale=None,          # [L, B, S, Hkv] f32 (int8/int4 codes), else None
    v_scale=None,
    layer=0,               # int32 scalar: which layer of the stack
) -> jax.Array:
    """Fused decode SDP over layer `layer` of the stack. Returns
    [B, 1, H, hd] in q.dtype."""
    b, sq, h, hd = q.shape
    s, hkv = k.shape[2], k.shape[3]
    if sq != 1:
        raise NotImplementedError("decode kernel handles Sq == 1 only")
    scaled = k_scale is not None
    if k.shape[0] > 1 and not _stack_in_place(k):
        # XLA would re-lay ALL layers out for this call, every call: hand
        # over the one layer, whose relayout is what is left
        k, v, k_scale, v_scale = (
            x if x is None else jax.lax.dynamic_slice_in_dim(x, layer, 1)
            for x in (k, v, k_scale, v_scale))
        layer = 0
    g = h // hkv
    hp = -(-h // 16) * 16             # query heads ride the sublanes
    sb = _s_block(s, hkv)
    ns = s // sb

    qr = q.reshape(b, h, hd)
    if hp != h:
        qr = jnp.pad(qr, ((0, 0), (0, hp - h), (0, 0)))
    bias = jnp.asarray(_own_head_bias(hp, g, hkv, sb * hkv))

    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def kv_index(bi, sj, lyr_ref, pos_ref):
        return lyr_ref[0], bi, _named_block(pos_ref, bi, sj, sb, ns), 0, 0

    q_spec = pl.BlockSpec((None, hp, hd), lambda bi, sj, *_: (bi, 0, 0))
    kv_spec = pl.BlockSpec((None, None, sb, hkv, hd), kv_index)
    in_specs = [
        q_spec,
        pl.BlockSpec((hp, sb * hkv), lambda bi, sj, *_: (0, 0)),
        kv_spec, kv_spec,
    ]
    operands = (lyr, pos, qr, bias, k, v)
    if scaled:
        def sc_index(bi, sj, lyr_ref, pos_ref):
            return lyr_ref[0], bi, 0, _named_block(pos_ref, bi, sj, sb, ns)

        # [L, B, Hkv, S]: a bitcast of the plane as the chip stores it
        # (see _rows)
        sc_spec = pl.BlockSpec((None, None, hkv, sb), sc_index)
        in_specs += [sc_spec, sc_spec]
        operands += tuple(jnp.swapaxes(x.astype(jnp.float32), -1, -2)
                          for x in (k_scale, v_scale))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, sb=sb, ns=ns, hkv=hkv,
                          scaled=scaled),
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=in_specs,
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
    )(*operands)

    return out[:, :h, :].reshape(b, 1, h, hd)


def attention_geometry_ok(q, k, logits_soft_cap, sliding_window,
                          alibi_slopes, k_scale=None) -> bool:
    """Shared feature/geometry gate for BOTH Pallas attention kernels
    (decode + blockwise prefill): plain softmax attention only (no
    window, no soft cap, no alibi, no sink), K and V of one `(kv_heads,
    head_dim)`, aligned shapes, KV dtypes the kernels upcast (or
    dequantize) in-register. A window over a K/V ring, a sink in the
    softmax and K wider than V are `ops/pallas/swa_attention.py`'s, on
    planes that keep a position's heads in the lanes (a head of 192
    values is no whole lane tile: this kernel's `[.., Hkv, hd]` blocks
    would be padded to 256 by a copy of the stack)."""
    if alibi_slopes is not None:
        return False
    if logits_soft_cap is not None or sliding_window is not None:
        return False
    h, hd = q.shape[2], q.shape[3]
    s, hkv = k.shape[-3], k.shape[-2]     # one layer or the [L, ...] stack
    if h % hkv != 0 or hd % 64 != 0 or s % 128 != 0:
        return False
    if k.dtype in (jnp.bfloat16, jnp.float8_e5m2):
        return k_scale is None
    if k.dtype in (jnp.int8, jnp.int4):
        # block-scaled codes need their scale planes for in-kernel dequant
        return k_scale is not None
    return False


def decode_attention_supported(q, k, v, q_pos, scale, logits_soft_cap,
                               sliding_window, alibi_slopes,
                               k_scale=None) -> bool:
    """Gate for the sdp_attention dispatch (bigdl_tpu.ops.attention)."""
    return q.shape[1] == 1 and attention_geometry_ok(
        q, k, logits_soft_cap, sliding_window, alibi_slopes, k_scale)
