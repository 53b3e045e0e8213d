"""Pallas TPU kernels of the routed expert layer that knows its share.

Two uses (`ops/moe_routed.py` builds both), one tile math:

- decode (1-64 tokens in flight): a tile is one HELD EXPERT and every
  tile multiplies the same few tokens. The tiles are ordered hit experts
  first; a tile past the last hit expert runs nothing and names the
  previous tile's blocks again, so an expert that no token chose costs
  no byte of HBM traffic, and a hit expert's int4 weights are read once
  a step. A routed layer is TWO calls:
  `moe_routed_decode_gate_up` streams the gate and the up matrix of an
  expert side by side, keeps two float32 accumulators and writes
  `act(g) * u * cw` (`cw` the tile's combine weights) once;
  `moe_routed_decode_down` multiplies that by the down matrix and SUMS
  over the experts in place, its `[T, bn]` output block resident over
  the expert axis. The step plan (`decode_tiles`) is by bytes: the
  fewest grid steps an expert whose working set is under
  `DECODE_VMEM_BUDGET`, so every expert matrix the cells run (0.8-8 MB
  packed) is ONE grid step and a larger one a few steps of megabytes.
  Inside a step the tile is dequantized `DECODE_CHUNK_ELEMS` weights at
  a time, so the unpack temporaries do not grow with the tile, in loop
  turns of `DECODE_CHUNK_UNROLL` chunks: a turn is a basic block, and
  only inside one does Mosaic run the VPU's unpack of a chunk under the
  MXU's passes over the one before (the chip table behind the three
  constants: PERF.md 6, PR 54; `tools/moe_routed_ab.py` sweeps them).
- prefill (`moe_routed_prefill`, `routed_expert_matmul`): the sorted
  ragged dispatch of `ops/pallas/moe_dispatch.py` (token-choice pairs
  sorted by expert, each expert's group padded to a token tile, so every
  tile belongs to one expert), over the held experts only: the buffer is
  sized for the worst case (every choice held here) and the tiles past
  the used part are skipped the same way. One call a matrix, at
  `routed_tiles`' power-of-two tiles.

`tile_expert[i]` is the expert (index into the held stack) whose
weights tile i multiplies, `n_active` how many leading tiles do work,
`layer` which layer of a `[L, E, ...]` stack (addressed where it lies,
PR 26's pattern: a per-layer slice of the stack handed to a kernel is a
copy of every held expert, every step); all are prefetched scalars that
the BlockSpec index maps read. Same
dequant tile math as `ops/pallas/dequant_matmul`; the stack keeps the
canonical split-block packing (`to_mxu_layout` leaves 4-D stacks alone).
Rows of skipped tiles are never written: `routed_expert_matmul`'s caller
masks them, and the decode pair never reads them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.dequant_matmul import (_dequant_tile, _pick_tile,
                                                 _unpack_tile)
from bigdl_tpu.ops.quant import QTensor, get_qtype

DECODE_NAME = "moe_routed_decode"
GATE_UP_NAME = DECODE_NAME + "_gate_up"
DOWN_NAME = DECODE_NAME + "_down"
PREFILL_NAME = "moe_routed_prefill"
PREFILL_TOKEN_TILE = 128

# the decode plan: what a grid step's working set may come to (counted by
# `_decode_step_bytes`), under the scoped-VMEM limit the calls ask of
# Mosaic (its default is 16 MiB; a v5e has 128), and the weights one pass
# of the dequant chain handles inside a step
DECODE_VMEM_LIMIT = 64 * 1024 * 1024
DECODE_VMEM_BUDGET = 48 * 1024 * 1024
DECODE_CHUNK_ELEMS = 1024 * 1024
DECODE_CHUNK_UNROLL = 8


def routed_tiles(qtype, k: int, n: int):
    """(bk, bn) the prefill kernel streams for a `[K, N]` expert; None
    where the shape does not tile (K a multiple of the quant block and
    of a K tile, N of 128)."""
    b = get_qtype(qtype).block_size if qtype is not None else 1
    if k % b:
        return None
    bk = _pick_tile(k, [c for c in (2048, 1024, 512, 256, 128) if c % b == 0])
    bn = _pick_tile(n, [512, 256, 128])
    if not bk or not bn:
        return None
    return bk, bn


def _chunk_lanes(bk: int, bn: int) -> int:
    """Lanes of a tile dequantized at a time: the widest multiple of 128
    that divides `bn` with `bk * lanes` <= DECODE_CHUNK_ELEMS."""
    fit = [c for c in range(128, bn + 1, 128)
           if bn % c == 0 and bk * c <= DECODE_CHUNK_ELEMS]
    return max(fit, default=128)


def _decode_step_bytes(qtype, k: int, t: int, bk: int, bn: int,
                       stacks: int) -> int:
    """VMEM a decode grid step holds at tile `(bk, bn)`: `stacks` weight
    tiles (2: gate and up) twice each (the pipeline's two buffers), the
    chunk's unpack temporaries (int32 codes, their float32 values, the
    scaled float32 and the bf16 tile: 14 bytes a weight), `x` `[T, K]`
    and the output block twice, and the float32 accumulators."""
    if qtype is None:
        weights, temps = 2 * bk * bn, 0
    else:
        b = get_qtype(qtype).block_size
        weights = bk * bn // 2 + (bk // b) * bn * 2
        temps = 14 * bk * _chunk_lanes(bk, bn)
    return (2 * stacks * weights + temps + 2 * t * k * 2 + 2 * t * bn * 2
            + stacks * t * bn * 4)


def decode_tiles(qtype, k: int, n: int, t: int, stacks: int = 1):
    """(bk, bn) a decode call streams for `[K, N]` experts at `t` rows,
    `stacks` matrices side by side: the fewest grid steps an expert
    whose working set (`_decode_step_bytes`) is under
    DECODE_VMEM_BUDGET, the wider `bn` on a tie (a full-N block is one
    contiguous piece of the stack). The full K and the full N are always
    legal blocks; a part of K is whole quant blocks, whole (32, 128)
    uint8 tiles of packed rows and whole (16, 128) tiles of scale rows.
    None where N is no multiple of 128 or nothing fits."""
    b = get_qtype(qtype).block_size if qtype is not None else 1
    if k % b or n % 128:
        return None
    unit = 16 * b if qtype is not None else 128
    best = None
    for nj in range(1, n // 128 + 1):
        if n % (nj * 128):
            continue
        bn = n // nj
        for nk in range(1, max(k // unit, 1) + 1):
            if k % nk or (nk > 1 and (k // nk) % unit):
                continue
            bk = k // nk
            if _decode_step_bytes(qtype, k, t, bk, bn,
                                  stacks) > DECODE_VMEM_BUDGET:
                continue
            if best is None or nj * nk < best[0]:
                best = (nj * nk, bk, bn)
            break                       # a smaller bk only adds steps
    return best and best[1:]


def _tile_weight(refs, c0, cn, *, block, bk, quantized):
    """bf16 `[bk, cn]`: lanes `c0 .. c0 + cn` of the weight tile in
    `refs` (data and scale, or a dense block). `_unpack_tile`'s nibbles
    (Mosaic shifts no 8-bit lanes: widen first) less its second mask: a
    widened uint8's high nibble has nothing above it."""
    if not quantized:
        return refs[0][:, pl.ds(c0, cn)].astype(jnp.bfloat16)
    v = refs[0][:, pl.ds(c0, cn)].reshape(
        bk // block, block // 2, cn).astype(jnp.int32)
    codes = jnp.concatenate([v & 0x0F, v >> 4], axis=1)
    return _dequant_tile(codes, refs[1][:, pl.ds(c0, cn)], None, "sym",
                         None, bk, cn)


def _sweep_tile(x, weights, accs, *, bn, cn, **tile):
    """`acc += x @ W` for each (weight refs, accumulator) pair, the tile
    dequantized `cn` lanes at a time, DECODE_CHUNK_UNROLL chunks a loop
    turn (one basic block: the VPU unpacks a chunk under the MXU's
    passes over the one before)."""
    chunks = bn // cn
    turn = max(u for u in range(1, DECODE_CHUNK_UNROLL + 1)
               if chunks % u == 0)

    def chunk(c, carry):
        for u in range(turn):
            c0 = pl.multiple_of((c * turn + u) * cn, cn)
            for refs, acc_ref in zip(weights, accs):
                acc_ref[:, pl.ds(c0, cn)] += jax.lax.dot_general(
                    x, _tile_weight(refs, c0, cn, **tile),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, chunks // turn, chunk, 0)


def _kernel(e_ref, act_ref, x_ref, *refs, block, bk, bn, nk, quantized):
    *weight, out_ref, acc_ref = refs
    i, k = pl.program_id(0), pl.program_id(2)
    del e_ref                         # consumed by the index maps

    @pl.when(i < act_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        if quantized:
            codes = _unpack_tile(weight[0][...], block, bk, bn)
            w = _dequant_tile(codes, weight[1][...], None, "sym", None,
                              bk, bn)
        else:
            w = weight[0][...].astype(jnp.bfloat16)
        # x stays resident over the K sweep (a [T, K] block per tile)
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:, pl.ds(k * bk, bk)], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            out_ref[...] = acc_ref[:].astype(out_ref.dtype)


def _gate_up_kernel(e_ref, act_ref, x_ref, *refs, act, bk, nk, per, **tile):
    """Grid (tile, N block, K block): both accumulators over the K sweep,
    then `act(gate) * up * cw` from their float32."""
    gate, up = refs[:per], refs[per:2 * per]
    cw_ref, out_ref, g_acc, u_acc = refs[2 * per:]
    i, k = pl.program_id(0), pl.program_id(2)
    del e_ref

    @pl.when(i < act_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            g_acc[:] = jnp.zeros_like(g_acc)
            u_acc[:] = jnp.zeros_like(u_acc)

        _sweep_tile(x_ref[:, pl.ds(k * bk, bk)], (gate, up), (g_acc, u_acc),
                    bk=bk, **tile)

        @pl.when(k == nk - 1)
        def _():
            out_ref[...] = (act(g_acc[:]) * u_acc[:]
                            * cw_ref[...]).astype(out_ref.dtype)


def _down_kernel(e_ref, act_ref, h_ref, *refs, bk, nk, tn, **tile):
    """Grid (N block, tile, K block): the output block stays put over the
    tiles, so the experts' products are summed in float32 where they are
    made and written once."""
    *weight, out_ref, acc_ref = refs
    i, k = pl.program_id(1), pl.program_id(2)
    del e_ref

    @pl.when((i == 0) & (k == 0))
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < act_ref[0])
    def _():
        _sweep_tile(h_ref[:, pl.ds(k * bk, bk)], (weight,), (acc_ref,),
                    bk=bk, **tile)

    @pl.when((i == tn - 1) & (k == nk - 1))
    def _():
        out_ref[...] = acc_ref[:].astype(out_ref.dtype)


def _layer_stack(w, layer, k: int):
    """(`[L, E, ...]` stack, layer, quant block, N) of a `[.., K, N]`
    weight stack that may lack the layer axis; refuses what the kernels
    do not read."""
    quantized = isinstance(w, QTensor)
    if (w.data if quantized else w).ndim == 3:      # one layer held
        w = jax.tree.map(lambda a: a[None], w)
        layer = 0
    if not quantized:
        return w, layer, 1, w.shape[-1]
    qt = get_qtype(w.qtype)
    if (qt.kind != "sym" or qt.storage_bits != 4
            or w.data.dtype != jnp.uint8):
        raise NotImplementedError(
            f"routed expert kernel reads canonical sym_int4 stacks, "
            f"not {w.qtype} / {w.data.dtype}")
    if w.scale.shape[-2] * qt.block_size != k:
        raise NotImplementedError("K is not a multiple of the block")
    return w, layer, qt.block_size, w.data.shape[-1]


def _prefetched(tile_expert, n_active, layer):
    """The prefetched scalars: the expert of every tile with the tail
    repeating the last active one, and [n_active, last active tile,
    layer]."""
    n_active = jnp.asarray(n_active, jnp.int32)
    last = jnp.maximum(n_active, 1) - 1
    te = jnp.asarray(tile_expert, jnp.int32)
    te = jnp.where(jnp.arange(te.shape[0]) <= last, te, te[last])
    return te, jnp.stack([n_active, last, jnp.asarray(layer, jnp.int32)])


def _on(i, act_ref, live, idle):
    """A tile past the active ones names the blocks of the last active
    step again: nothing is fetched or stored."""
    return jnp.where(i < act_ref[0], live, idle)


def _weight_operands(w, bk, bn, b, w_map):
    """Operands and BlockSpecs of one `[L, E, K, N]` stack at `(bk, bn)`."""
    if isinstance(w, QTensor):
        return [w.data, w.scale], [
            pl.BlockSpec((None, None, bk // 2, bn), w_map),
            pl.BlockSpec((None, None, bk // b, bn), w_map)]
    return [w], [pl.BlockSpec((None, None, bk, bn), w_map)]


@functools.partial(jax.jit,
                   static_argnames=("name", "shared_x", "interpret"))
def routed_expert_matmul(x: jax.Array,            # [Tn or 1, T, K]
                         w,                       # stack [E, ..] / [L, E, ..]
                         tile_expert: jax.Array,  # [Tn] int32
                         n_active: jax.Array,     # int32 scalar
                         layer=0,                 # int32 scalar
                         *, name: str, shared_x: bool = False,
                         interpret: bool = False) -> jax.Array:
    """Tile i of `x` (every tile the same block with `shared_x`) times
    `W[tile_expert[i]]` -> `[Tn, T, N]`, for the first `n_active` tiles;
    the rest is left unwritten. `w` is a sym_int4 `QTensor` stack
    `[E, K/2, N]` in the canonical packing or a dense `[E, K, N]`, or
    either with a leading layer axis, of which `layer` is read."""
    tn = tile_expert.shape[0]
    t, klog = x.shape[1], x.shape[2]
    quantized = isinstance(w, QTensor)
    w, layer, b, n = _layer_stack(w, layer, klog)
    tiles = routed_tiles(w.qtype if quantized else None, klog, n)
    if tiles is None or t % 16:
        raise NotImplementedError(
            f"routed expert kernel: K={klog} N={n} T={t} do not tile")
    bk, bn = tiles
    nk, nj = klog // bk, n // bn
    te, act = _prefetched(tile_expert, n_active, layer)

    def x_map(i, j, k, e_ref, act_ref):
        return (0 if shared_x else jnp.minimum(i, act_ref[1]), 0, 0)

    def w_map(i, j, k, e_ref, act_ref):
        return (act_ref[2], e_ref[i], _on(i, act_ref, k, nk - 1),
                _on(i, act_ref, j, nj - 1))

    def o_map(i, j, k, e_ref, act_ref):
        return (jnp.minimum(i, act_ref[1]), 0, _on(i, act_ref, j, nj - 1))

    operands, w_specs = _weight_operands(w, bk, bn, b, w_map)
    return pl.pallas_call(
        functools.partial(_kernel, block=b, bk=bk, bn=bn, nk=nk,
                          quantized=quantized),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tn, nj, nk),
            in_specs=[pl.BlockSpec((None, t, klog), x_map), *w_specs],
            out_specs=pl.BlockSpec((None, t, bn), o_map),
            scratch_shapes=[pltpu.VMEM((t, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tn, t, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(te, act, x.astype(jnp.bfloat16), *operands)


def _decode_plan(w, layer, k: int, t: int, stacks: int):
    """(`[L, E, ...]` stack, layer, quant block, N, bk, bn, chunk lanes) of
    a decode call; NotImplementedError where the shape does not tile."""
    quantized = isinstance(w, QTensor)
    w, layer, b, n = _layer_stack(w, layer, k)
    tiles = decode_tiles(w.qtype if quantized else None, k, n, t, stacks)
    if tiles is None or t % 16:
        raise NotImplementedError(
            f"routed decode kernel: K={k} N={n} T={t} do not tile")
    return w, layer, b, n, *tiles, _chunk_lanes(*tiles)


_DECODE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=DECODE_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def routed_gate_up(x: jax.Array,            # [1, T, K]
                   gate, up,                # stacks [E, ..] / [L, E, ..]
                   cw: jax.Array,           # [Tn, T] float32
                   tile_expert: jax.Array,  # [Tn] int32
                   n_active: jax.Array, layer=0, *, act,
                   interpret: bool = False) -> jax.Array:
    """`act(x @ G[e]) * (x @ U[e]) * cw[i][:, None]` with `e =
    tile_expert[i]` -> `[Tn, T, F]` for the first `n_active` tiles, the
    rest left unwritten: one sweep of `x` over both matrices of an
    expert, the product taken from the float32 accumulators."""
    tn, (t, k) = tile_expert.shape[0], x.shape[1:]
    quantized = isinstance(gate, QTensor)
    if quantized != isinstance(up, QTensor):
        raise NotImplementedError("gate and up stacks of two kinds")
    gate, layer, b, n, bk, bn, cn = _decode_plan(gate, layer, k, t, 2)
    up, _, _, n_up = _layer_stack(up, layer, k)
    if n_up != n:
        raise NotImplementedError("gate and up stacks of two widths")
    nk, nj = k // bk, n // bn
    te, pre = _prefetched(tile_expert, n_active, layer)

    def w_map(i, j, kk, e_ref, act_ref):
        return (act_ref[2], e_ref[i], _on(i, act_ref, kk, nk - 1),
                _on(i, act_ref, j, nj - 1))

    def o_map(i, j, kk, e_ref, act_ref):
        return (jnp.minimum(i, act_ref[1]), 0, _on(i, act_ref, j, nj - 1))

    def cw_map(i, j, kk, e_ref, act_ref):
        return (jnp.minimum(i, act_ref[1]), 0, 0)

    g_ops, g_specs = _weight_operands(gate, bk, bn, b, w_map)
    u_ops, u_specs = _weight_operands(up, bk, bn, b, w_map)
    return pl.pallas_call(
        functools.partial(_gate_up_kernel, act=act, bk=bk, nk=nk,
                          per=len(g_ops), block=b, bn=bn, cn=cn,
                          quantized=quantized),
        name=GATE_UP_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tn, nj, nk),
            in_specs=[pl.BlockSpec((None, t, k), lambda *_: (0, 0, 0)),
                      *g_specs, *u_specs,
                      pl.BlockSpec((None, t, 1), cw_map)],
            out_specs=pl.BlockSpec((None, t, bn), o_map),
            scratch_shapes=[pltpu.VMEM((t, bn), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((tn, t, n), x.dtype),
        compiler_params=_DECODE_PARAMS,
        interpret=interpret,
    )(te, pre, x.astype(jnp.bfloat16), *g_ops, *u_ops,
      cw.astype(jnp.float32)[..., None])


@functools.partial(jax.jit, static_argnames=("interpret",))
def routed_down_sum(h: jax.Array,            # [Tn, T, F]
                    down,                    # stack [E, ..] / [L, E, ..]
                    tile_expert: jax.Array,  # [Tn] int32
                    n_active: jax.Array, layer=0, *,
                    interpret: bool = False) -> jax.Array:
    """`sum_i h[i] @ D[tile_expert[i]]` over the first `n_active` tiles
    -> `[T, D]` (zeros with none), summed in float32 inside the call;
    tiles past `n_active` are not read."""
    tn, t, k = h.shape
    quantized = isinstance(down, QTensor)
    down, layer, b, n, bk, bn, cn = _decode_plan(down, layer, k, t, 1)
    nk, nj = k // bk, n // bn
    te, pre = _prefetched(tile_expert, n_active, layer)

    def h_map(j, i, kk, e_ref, act_ref):
        return (jnp.minimum(i, act_ref[1]), 0, 0)

    def w_map(j, i, kk, e_ref, act_ref):
        return (act_ref[2], e_ref[i], _on(i, act_ref, kk, nk - 1), j)

    operands, w_specs = _weight_operands(down, bk, bn, b, w_map)
    return pl.pallas_call(
        functools.partial(_down_kernel, bk=bk, nk=nk, tn=tn, block=b,
                          bn=bn, cn=cn, quantized=quantized),
        name=DOWN_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nj, tn, nk),
            in_specs=[pl.BlockSpec((None, t, k), h_map), *w_specs],
            out_specs=pl.BlockSpec((t, bn), lambda j, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((t, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, n), h.dtype),
        compiler_params=_DECODE_PARAMS,
        interpret=interpret,
    )(te, pre, h.astype(jnp.bfloat16), *operands)


_probe_cache: set = set()


def _probe_stack(qtype, k: int, n: int):
    """The probes' stand-in `[2, K, N]` expert stack."""
    from bigdl_tpu.ops.probing import quant_struct, stacked_struct

    if qtype is None:
        return jax.ShapeDtypeStruct((2, k, n), jnp.bfloat16)
    return stacked_struct(quant_struct(k, n, qtype), 2)


def routed_kernel_compiles(name: str, qtype, k: int, n: int, t: int) -> bool:
    """Compile probe of `routed_expert_matmul` per geometry (contract in
    ops/probing.py); False where the shape does not tile, which is a
    rule."""
    if routed_tiles(qtype, k, n) is None or t % 16:
        return False
    from bigdl_tpu.config import flags

    if flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    from bigdl_tpu.ops.probing import probe_kernel

    def fn(x, ws, te, na):
        return routed_expert_matmul(x, ws, te, na, 0, name=name)

    return probe_kernel(
        name, _probe_cache, (name, qtype, k, n, t), fn,
        jax.ShapeDtypeStruct((2, t, k), jnp.bfloat16),
        _probe_stack(qtype, k, n), jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))


def routed_decode_compiles(gate_q, down_q, d: int, ff: int, t: int) -> bool:
    """Compile probe of the decode pair (`routed_gate_up` on `[D, F]`
    stacks of qtype `gate_q`, `routed_down_sum` on `[F, D]` of `down_q`)
    per geometry; False where a shape does not tile, which is a rule."""
    if (decode_tiles(gate_q, d, ff, t, 2) is None
            or decode_tiles(down_q, ff, d, t) is None or t % 16):
        return False
    from bigdl_tpu.config import flags

    if flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    from bigdl_tpu.ops.probing import probe_kernel

    def fn(x, gate, up, down, cw, te, na):
        h = routed_gate_up(x, gate, up, cw, te, na, act=jax.nn.silu)
        return routed_down_sum(h, down, te, na)

    return probe_kernel(
        DECODE_NAME, _probe_cache, (DECODE_NAME, gate_q, down_q, d, ff, t),
        fn, jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16),
        _probe_stack(gate_q, d, ff), _probe_stack(gate_q, d, ff),
        _probe_stack(down_q, ff, d),
        jax.ShapeDtypeStruct((2, t), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
