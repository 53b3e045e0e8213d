"""Pallas TPU kernel of the routed expert layer that knows its share.

One kernel, two uses (`ops/moe_routed.py` builds both):

- decode (`moe_routed_decode`, 1-64 tokens in flight): a tile is one
  HELD EXPERT and every tile multiplies the same few tokens. The tiles
  are ordered hit experts first; a tile past the last hit expert runs
  nothing and names the previous tile's blocks again, so an expert that
  no token chose costs no byte of HBM traffic, and a hit expert's int4
  weights are read once a step.
- prefill (`moe_routed_prefill`): the sorted ragged dispatch of
  `ops/pallas/moe_dispatch.py` (token-choice pairs sorted by expert,
  each expert's group padded to a token tile, so every tile belongs to
  one expert), over the held experts only: the buffer is sized for the
  worst case (every choice held here) and the tiles past the used part
  are skipped the same way.

`tile_expert[i]` is the expert (index into the held stack) whose
weights tile i multiplies, `n_active` how many leading tiles do work,
`layer` which layer of a `[L, E, ...]` stack (addressed where it lies,
PR 26's pattern: a per-layer slice of the stack handed to a kernel is a
copy of every held expert, every step); all are prefetched scalars that
the BlockSpec index maps read. Same
dequant tile math as `ops/pallas/dequant_matmul`; the stack keeps the
canonical split-block packing (`to_mxu_layout` leaves 4-D stacks alone).
Rows of skipped tiles are never written: the caller masks them.
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
PREFILL_NAME = "moe_routed_prefill"
PREFILL_TOKEN_TILE = 128


def routed_tiles(qtype, k: int, n: int):
    """(bk, bn) the kernel streams for a `[K, N]` expert; None where the
    shape does not tile (K a multiple of the quant block and of a K
    tile, N of 128)."""
    b = get_qtype(qtype).block_size if qtype is not None else 1
    if k % b:
        return None
    bk = _pick_tile(k, [c for c in (2048, 1024, 512, 256, 128) if c % b == 0])
    bn = _pick_tile(n, [512, 256, 128])
    if not bk or not bn:
        return None
    return bk, bn


def _kernel(e_ref, act_ref, x_ref, data_ref, scale_ref, out_ref, acc_ref,
            *, block, bk, bn, nk, quantized):
    i, k = pl.program_id(0), pl.program_id(2)
    del e_ref                         # consumed by the index maps

    @pl.when(i < act_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        if quantized:
            codes = _unpack_tile(data_ref[...], block, bk, bn)
            w = _dequant_tile(codes, scale_ref[...], None, "sym", None,
                              bk, bn)
        else:
            w = data_ref[...].astype(jnp.bfloat16)
        # x stays resident over the K sweep (a [T, K] block per tile)
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:, pl.ds(k * bk, bk)], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            out_ref[...] = acc_ref[:].astype(out_ref.dtype)


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
    if (w.data if quantized else w).ndim == 3:      # one layer held
        w = jax.tree.map(lambda a: a[None], w)
        layer = 0
    if quantized:
        qt = get_qtype(w.qtype)
        if (qt.kind != "sym" or qt.storage_bits != 4
                or w.data.dtype != jnp.uint8):
            raise NotImplementedError(
                f"routed expert kernel reads canonical sym_int4 stacks, "
                f"not {w.qtype} / {w.data.dtype}")
        b = qt.block_size
        n = w.data.shape[-1]
        if w.scale.shape[-2] * b != klog:
            raise NotImplementedError("K is not a multiple of the block")
    else:
        b, n = 1, w.shape[-1]
    tiles = routed_tiles(w.qtype if quantized else None, klog, n)
    if tiles is None or t % 16:
        raise NotImplementedError(
            f"routed expert kernel: K={klog} N={n} T={t} do not tile")
    bk, bn = tiles
    nk, nj = klog // bk, n // bn
    last = jnp.maximum(jnp.asarray(n_active, jnp.int32), 1) - 1
    te = jnp.asarray(tile_expert, jnp.int32)
    # prefetched: the expert of every tile with the tail repeating the
    # last active one, and [n_active, last active tile]
    te = jnp.where(jnp.arange(tn) <= last, te, te[last])
    act = jnp.stack([jnp.asarray(n_active, jnp.int32), last,
                     jnp.asarray(layer, jnp.int32)])

    # a tile past the active ones names the blocks of the last active
    # step again (its last N and K block): nothing is fetched or stored
    def on(i, act_ref, live, idle):
        return jnp.where(i < act_ref[0], live, idle)

    def x_map(i, j, k, e_ref, act_ref):
        return (0 if shared_x else jnp.minimum(i, act_ref[1]), 0, 0)

    def w_map(i, j, k, e_ref, act_ref):
        return (act_ref[2], e_ref[i], on(i, act_ref, k, nk - 1),
                on(i, act_ref, j, nj - 1))

    def o_map(i, j, k, e_ref, act_ref):
        return (jnp.minimum(i, act_ref[1]), 0, on(i, act_ref, j, nj - 1))

    x_spec = pl.BlockSpec((None, t, klog), x_map)
    if quantized:
        operands = [w.data, w.scale]
        in_specs = [x_spec,
                    pl.BlockSpec((None, None, bk // 2, bn), w_map),
                    pl.BlockSpec((None, None, bk // b, bn), w_map)]
        body = functools.partial(_kernel, block=b, bk=bk, bn=bn, nk=nk,
                                 quantized=True)
    else:
        operands = [w]
        in_specs = [x_spec, pl.BlockSpec((None, None, bk, bn), w_map)]

        def body(e_ref, act_ref, x_ref, w_ref, out_ref, acc_ref):
            _kernel(e_ref, act_ref, x_ref, w_ref, None, out_ref, acc_ref,
                    block=1, bk=bk, bn=bn, nk=nk, quantized=False)

    return pl.pallas_call(
        body, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tn, nj, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, t, bn), o_map),
            scratch_shapes=[pltpu.VMEM((t, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tn, t, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(te, act, x.astype(jnp.bfloat16), *operands)


_probe_cache: set = set()


def routed_kernel_compiles(name: str, qtype, k: int, n: int, t: int,
                           shared_x: bool) -> bool:
    """Compile probe per geometry (contract in ops/probing.py); False
    where the shape does not tile, which is a rule."""
    if routed_tiles(qtype, k, n) is None or t % 16:
        return False
    from bigdl_tpu.config import flags

    if flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    from bigdl_tpu.ops.probing import (probe_kernel, quant_struct,
                                       stacked_struct)

    if qtype is None:
        w = jax.ShapeDtypeStruct((2, k, n), jnp.bfloat16)
    else:
        w = stacked_struct(quant_struct(k, n, qtype), 2)

    def fn(x, ws, te, na):
        return routed_expert_matmul(x, ws, te, na, 0, name=name,
                                    shared_x=shared_x)

    return probe_kernel(
        name, _probe_cache, (name, qtype, k, n, t, shared_x), fn,
        jax.ShapeDtypeStruct((1 if shared_x else 2, t, k), jnp.bfloat16),
        w, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
