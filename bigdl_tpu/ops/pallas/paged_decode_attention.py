"""Pallas TPU kernel: fused decode attention over a PAGED KV arena.

The slab decode kernel (`decode_attention.py`) streams each sequence's
K/V rows contiguously. Under the paged layout (`ops/paged.py`) a
sequence's rows live scattered across the arena wherever its block table
points — materializing a dense copy first would double the memory traffic
of an already bandwidth-bound op.

This kernel keeps the gather INSIDE the launch, on the arena as it lies:
the operands are the cache's whole stacks, codes ``[L*Hkv, P, ps, hd]``
(4-bit: ``[L*Hkv, P, ps/8, 8, hd]``) and (int8/int4) scales
``[L, P, Hkv, ps]``, the layout `ops/paged.py` keeps at rest (and why),
left in HBM (``pl.ANY``). The layer index, the block table and the
positions ride in as scalar-prefetch operands, and the kernel copies the
pages they name itself, planes ``layer * Hkv + head`` of page
``bt[b, j]``, into a double-buffered VMEM scratch: no layer, page or
scale plane is sliced, reshaped or copied on the way in.

**The grid follows what is live.** It is (slots, groups of KV heads),
and the table's columns are no axis of it. A grid step takes every KV
head of its slot where one page's heads fit a fixed budget of VMEM
(`run_block`, from the bytes of a page-head: both of ChatGLM2's heads,
all 8 of the tests'; above the budget, groups of heads) and walks the
slot's LIVE table columns in runs of `n` pages (8 at ChatGLM2's int8
geometry, as many as the budget holds), one online-softmax update over
``[Gp, n * ps]`` scores a head and run, the next run's pages in flight
while this one is multiplied. How many runs a slot has is read from the
prefetched table and positions, never from a shape: a column past the
slot's position is no step and no copy, a run that straddles the
position copies only its live pages, and an empty slot (its first column
is the null page, physical 0) starts no copy and writes zeros. Before
PR 42 a step was one (slot, head, column): 4,096 steps of 0.46 us at the
docqa cell's 32 x 2 x 64 whatever was live, 2.04 ms a call where the
live pages take 0.08 (PERF.md 6, PR 42). The online-softmax state
machine is the blocked slab kernel's; the position mask covers what a
run holds past the position (pages not copied: the buffers hold zeros
until a page lands there, so what the mask multiplies is finite).
`pages_read` is the copy rule as plain integers, for the engine's
counter.

Shapes: q ``[B, 1, H, hd]``; block_tables ``[B, NP]`` int32; pos ``[B]``
int32; layer an int32 scalar. A page's scales arrive as one ``[Hkv, ps]``
block, positions in the lanes, which is how the scores lie: the head's
ROW of it multiplies the scores (K) and the probabilities (V), so no
scale is turned into a column and the codes meet the MXU as they are
(`_paged_kernel`; on the chip the column, a transpose of one row a
page-head, cost 0.37 us of a grid step's 0.82: PERF.md 6, PR 40).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.paged import NULL_PAGE, code_page_size
from bigdl_tpu.ops.pallas.decode_attention import _NEG_INF

# bytes of K codes (as many again of V) that one run holds in VMEM: both
# heads of eight int8 pages of 128 x 128 at ChatGLM2's geometry. Four
# such buffers (K, V, each double-buffered) are 1 MB, and a head's
# `[n * ps, hd]` rows widened to float32 (the 4-bit merge) 512 KB at most
_RUN_BYTES = 256 * 1024


def run_block(hkv: int, np_: int, page_bytes: int) -> tuple[int, int]:
    """(KV heads a grid step takes, table columns a run takes), chosen
    from the bytes of one head of one page as `decode_attention._s_block`
    chooses its S-block: every head where one page's heads fit
    `_RUN_BYTES`, else as many (a divisor of `hkv`) as fit; then as many
    pages of them as fit."""
    fit = max(1, _RUN_BYTES // page_bytes)      # page-heads a run holds
    hh = max(h for h in range(1, min(hkv, fit) + 1) if hkv % h == 0)
    return hh, max(1, min(np_, fit // hh))


def _page_bytes(arena_k) -> int:
    """Bytes of one head of one page of a code stack."""
    bits = 4 if arena_k.dtype == jnp.int4 else 8 * arena_k.dtype.itemsize
    return math.prod(arena_k.shape[2:]) * bits // 8


def paged_attention_grid(b: int, np_: int, hkv: int, arena_k) -> tuple:
    """The kernel's grid over `b` slots behind tables of `np_` columns:
    (slots, groups of KV heads). The table's columns are no axis of it:
    a step walks its own slot's live runs."""
    return b, hkv // run_block(hkv, np_, _page_bytes(arena_k))[0]


def pages_read(positions, page_size: int, np_: int) -> int:
    """Pages of one layer the kernel copies for K (as many again for V)
    when the slots at `positions` (plain ints, the query's own position
    in each; below 0 for an empty slot) decode behind block tables of
    `np_` columns: a live slot's pages up to the one that holds its
    position, nothing of an empty slot."""
    return sum(min(int(p) // page_size + 1, np_)
               for p in positions if int(p) >= 0)


def _rows(x):
    """`n` pages of one head, `[n, ps, hd]`, as `[n * ps, hd]` bf16
    rows. int8/int4 codes come out as they are (<= 127: exact in bf16;
    Mosaic has no direct low-bit-int -> f32 cast). 4-bit pages are
    `[n, ps/8, 8, hd]`: their groups of 8 merge in f32, where every
    (8, 128) tile stays whole (as `decode_attention._rows` merges
    heads)."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.bfloat16)
    if x.ndim == 4:
        x = x.astype(jnp.float32)
    return x.reshape(-1, x.shape[-1]).astype(jnp.bfloat16)


def _paged_kernel(lyr_ref, pos_ref, bt_ref, q_ref, k_hbm, v_hbm, *rest,
                  scale, ps, np_, hkv, hh, n, gp, scaled):
    """One (slot, group of `hh` KV heads) step: the online-softmax sweep
    over the slot's live runs of `n` table columns, `[Gp, n * ps]`
    scores a head and run. The step copies a run's live pages itself
    (one DMA a page and plane: its `hh` heads, and its `[Hkv, ps]`
    scales) into one half of a double buffer while the other half is
    computed on; an empty slot (its first column is the null page) has
    no run, starts no copy and writes zeros.

    int8/int4: a page-head's scales are row `hi` of the page's
    `[Hkv, ps]` block, positions in the lanes — the layout of the
    scores. So the K scales multiply the SCORES and the V scales the
    probabilities, in f32, and the codes meet the MXU as they are:
    `q . (c_k s_k) = (q . c_k) s_k` and `p . (c_v s_v) = (p s_v) . c_v`,
    with no scale turned into a column and one rounding to bf16 (of
    `p s_v`) where dequantized rows have two (of `c s` and of `p`)."""
    if scaled:
        ks_hbm, vs_hbm, out_ref, *halves = rest[:-4]
        kbuf, vbuf, ksbuf, vsbuf = halves
    else:
        out_ref, *halves = rest[:-4]
        kbuf, vbuf = halves
    sem, m_ref, l_ref, acc_ref = rest[-4:]
    bi, hg = pl.program_id(0), pl.program_id(1)
    lyr, pos = lyr_ref[0], pos_ref[bi]
    live = jnp.where(bt_ref[bi, 0] == NULL_PAGE, 0,
                     jnp.clip(pos // ps + 1, 0, np_))   # pages
    runs = (live + n - 1) // n

    # what a masked position multiplies must be finite: the halves hold
    # zeros until a page lands there, and only pages ever do
    @pl.when((bi == 0) & (hg == 0))
    def _():
        for buf in halves:
            buf[...] = jnp.zeros_like(buf)

    planes = pl.ds(lyr * hkv + hg * hh, hh)

    def copy_run(r, half, act):
        """Start, or wait for (`act`), the copies of run r's live pages
        into `half`: one a page and plane."""
        for i in range(n):
            col = r * n + i
            page = bt_ref[bi, jnp.minimum(col, np_ - 1)]
            pairs = [(k_hbm.at[planes, page], kbuf),
                     (v_hbm.at[planes, page], vbuf)]
            if scaled:
                pairs += [(ks_hbm.at[lyr, page], ksbuf),
                          (vs_hbm.at[lyr, page], vsbuf)]

            @pl.when(col < live)
            def _():
                for src, buf in pairs:
                    getattr(pltpu.make_async_copy(
                        src, buf.at[half, i], sem.at[half]), act)()

    def scale_row(buf, half, h):
        """Head `h`'s scales of the run, `[1, n * ps]` as the scores lie."""
        hi = hg * hh + h
        return jnp.concatenate(
            [buf[half, i, pl.ds(hi, 1), :] for i in range(n)], axis=-1)

    @pl.when(runs > 0)
    def _():
        copy_run(0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def run(r, carry):
        half = jax.lax.rem(r, 2)

        @pl.when(r + 1 < runs)
        def _():
            copy_run(r + 1, 1 - half, "start")

        copy_run(r, half, "wait")
        # logical positions of the run's rows; a page of it past the
        # slot's position was not copied, and what lies there is masked
        ids = r * (n * ps) + jax.lax.broadcasted_iota(
            jnp.int32, (gp, n * ps), 1)
        for h in range(hh):
            q = q_ref[h].astype(jnp.bfloat16)             # [Gp, hd]
            s_ = jax.lax.dot_general(
                q, _rows(kbuf[half, :, h]), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Gp, n*ps]
            if scaled:
                s_ = s_ * scale_row(ksbuf, half, h)
            s_ = jnp.where(ids <= pos, s_, _NEG_INF)

            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_ - m_new)
            l_ref[h] = jnp.broadcast_to(
                l_ref[h, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
                l_ref.shape[1:])
            if scaled:
                p = p * scale_row(vsbuf, half, h)
            pv = jax.lax.dot_general(
                p.astype(jnp.bfloat16), _rows(vbuf[half, :, h]),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        return carry

    jax.lax.fori_loop(0, runs, run, 0)
    l = jnp.maximum(l_ref[:, :, :1], 1e-30)
    out_ref[...] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "kv_heads", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,             # [B, 1, H, hd]
    arena_k: jax.Array,       # [L*Hkv, P, ps, hd] the cache's whole stack
    arena_v: jax.Array,       # (4-bit codes: [L*Hkv, P, ps/8, 8, hd])
    block_tables: jax.Array,  # [B, NP] int32 (0 = null page)
    q_pos: jax.Array,         # [B] int32
    scale: float,
    kv_heads: int,
    interpret: bool = False,
    k_scale=None,             # [L, P, Hkv, ps] f32 for int8/int4 codes
    v_scale=None,
    layer=0,                  # int32 scalar: which layer of the stack
) -> jax.Array:
    """Fused paged decode SDP over layer `layer` of the arena. Returns
    [B, 1, H, hd] in q.dtype; the rows of an empty slot are zeros."""
    b, sq, h, hd = q.shape
    hkv, page = kv_heads, arena_k.shape[2:]
    ps = code_page_size(arena_k)
    np_ = block_tables.shape[1]
    if sq != 1:
        raise NotImplementedError("paged decode kernel handles Sq == 1")
    scaled = k_scale is not None
    g = h // hkv
    gp = max(16, -(-g // 8) * 8)   # pad query group to clean sublane run
    hh, n = run_block(hkv, np_, _page_bytes(arena_k))

    qr = q.reshape(b, hkv, g, hd)
    if gp != g:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = block_tables.astype(jnp.int32)

    # the whole point: the arena stays where it lies (`pl.ANY`) and the
    # kernel copies page bt[b, j] of that layer's planes from it, so
    # neither the gather nor the layer ever materializes in HBM
    q_spec = pl.BlockSpec((None, hh, gp, hd),
                          lambda bi, hg, *_: (bi, hg, 0, 0))
    arena_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, arena_spec, arena_spec]
    operands = (lyr, pos, bt, qr, arena_k, arena_v)
    halves = [pltpu.VMEM((2, n, hh) + page, arena_k.dtype)] * 2
    if scaled:
        in_specs += [arena_spec, arena_spec]
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
        halves += [pltpu.VMEM((2, n, hkv, ps), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, ps=ps, np_=np_,
                          hkv=hkv, hh=hh, n=n, gp=gp, scaled=scaled),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=paged_attention_grid(b, np_, hkv, arena_k),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=halves + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hh, gp, 128), jnp.float32),
                pltpu.VMEM((hh, gp, 128), jnp.float32),
                pltpu.VMEM((hh, gp, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
        interpret=interpret,
    )(*operands)

    return out[:, :, :g, :].reshape(b, 1, h, hd)


def paged_attention_geometry_ok(q, arena_k, kv_heads, logits_soft_cap,
                                sliding_window, alibi_slopes,
                                k_scale=None) -> bool:
    """Feature/geometry gate: plain softmax attention, MXU-aligned
    shapes, page_size a lane-tile multiple (a run of pages is the
    kernel's S-block, a page's positions whole lane tiles of it)."""
    if alibi_slopes is not None:
        return False
    if logits_soft_cap is not None or sliding_window is not None:
        return False
    h, hd = q.shape[2], q.shape[3]
    ps = code_page_size(arena_k)
    if h % kv_heads != 0 or hd % 64 != 0 or ps % 128 != 0:
        return False
    if arena_k.dtype in (jnp.bfloat16, jnp.float8_e5m2):
        return k_scale is None
    if arena_k.dtype in (jnp.int8, jnp.int4):
        return k_scale is not None
    return False


def paged_decode_attention_supported(q, arena_k, kv_heads, logits_soft_cap,
                                     sliding_window, alibi_slopes,
                                     k_scale=None) -> bool:
    """Gate for the sdp_attention_paged dispatch (bigdl_tpu.ops.attention)."""
    return q.shape[1] == 1 and paged_attention_geometry_ok(
        q, arena_k, kv_heads, logits_soft_cap, sliding_window, alibi_slopes,
        k_scale)
