"""Paged KV cache: one page arena per layer + per-sequence block tables.

The slab cache (`ops/kvcache.py`) reserves `[L, max_batch, max_seq, H, D]`
up front — every slot pays worst-case `max_seq` whether it holds a 30-token
chat turn or a book. This module replaces the per-slot axis with a pooled
one: a single page arena per K/V plane and an int32 **block table** per
sequence mapping logical page -> physical page (the vLLM PagedAttention
layout, re-done for XLA's static shapes). Memory now scales with *live
tokens*, so concurrency is bounded by real KV footprint instead of
``max_batch * max_seq`` worst case, and refcounted pages can be shared
copy-on-write across requests that start with the same prompt prefix (the
radix tree in ``serving/pagepool.py``).

**The layout at rest is the layout the block-table kernel reads**
(`ops/pallas/paged_decode_attention.py`), one for every storage dtype and
head count, and this module is the only one that knows it:

- codes ``[L * H_kv, P, page_size, D]``: a plane a (layer, head), pages
  of ``[page_size, D]``, positions in the sublanes and ``D`` in the
  lanes. A head of a page is ONE contiguous window of the array as it
  lies, which is what the kernel copies (one DMA a live page: its
  heads' windows), whole tiles for every dtype; a layer is ``H_kv``
  consecutive planes (`code_plane`), a split of the major axis, which
  is free. Two things were learned by compiling for
  the v5e (tests/test_aot_tpu.py; PERF.md 6, PR 40). With one head's
  ``D`` in the lanes a gather of whole pages (seeding, copy-on-write,
  export) moves those pages; with ``H_kv * D`` in the lanes XLA splits
  the PLANE into 128-lane halves first (1.2 GB of temporaries at
  ChatGLM2's 2 heads, 4.7 GB at 8). And 4-bit codes keep a page's
  positions in groups of `PAGE_ROWS` = 8, ``[.., page_size / 8, 8, D]``:
  the tile of 8 rows x 128 lanes is the one XLA's scatter can write a
  row into, where ``[.., page_size, D]`` gives them 64-row tiles and the
  append two relayouts of the whole arena per layer (8- and 16-bit
  storage has the 8-row tile either way).
- int8/int4 scales ``[L, P, H_kv, page_size]`` float32: positions in the
  lanes (a minor dimension of ``H_kv`` would be padded to 128 lanes).

``H_kv`` does not follow from a code plane's shape: `PagedKVCache`
carries it as static data (`kv_heads`; `head_dim`, `page_size`,
`num_layers` are properties), so no caller reads a plane's shape. Every
reader and writer addresses the whole stack
(`stack.at[code_plane(..), phys, off]`, one gather over (layer, tables)):
no layer of it is sliced out inside a layer scan. What leaves this module
is LOGICAL: `gather_pages_dense` and the `paged_read_layer*` functions
return ``[.., n, H_kv, D]`` / ``[.., n, H_kv]`` as the slab cache holds
them.

Static-shape rules (everything the slab layout promised still holds):

- The arena never reallocates; appends are advanced-index scatters
  ``arena.at[layer, phys, off].set(...)`` where ``phys``/``off`` come from
  the block table — one shape for the jit-compiled step's whole lifetime.
- Block tables are dense ``[B, NP]`` with ``NP = max_seq // page_size``;
  unallocated logical pages map to **page 0**, the reserved null/trash
  page. Out-of-range or padded writes land there and out-of-range reads
  gather it — both only ever touch positions attention masks out
  (``k_ids > pos``), so the garbage is never observable.
- Validity is still a per-slot ``pos``; the dense gather
  ``arena[layer, block_tables]`` reshapes to exactly the
  ``[B, max_seq, H, D]`` view the slab path reads, which is what makes
  paged decode byte-identical to slab decode (tests assert it for
  bf16/fp8/int8/int4).

int8/int4 storage carries the same per-(token, head) scales as the slab
cache — quantization happens in `paged_update_layer` with the exact
`quantize_kv` call `update_layer` uses, so codes and scales match the slab
bit for bit and pages stay in the tile-wise low-bit layout the fused
kernels stream (BitDecoding's packing argument, PAPERS.md).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.kvcache import (
    KV_CACHE_DTYPES,
    SCALED_KV_DTYPES,
    _logical_nbytes,
    kv_cache_nbytes,
    kv_dtype_name,
    quantize_kv,
    resolve_kv_cache_dtype,
)

#: physical page 0 is never handed out: it is the write sink for padded /
#: out-of-range positions and the gather source for unallocated logical
#: pages. Its contents are garbage by design — attention masks every
#: position that could read it.
NULL_PAGE = 0

#: positions of a page of 4-bit codes that share a tile (module
#: docstring); a page shorter than that is one group
PAGE_ROWS = 8


def code_plane(layer, kv_heads: int) -> jax.Array:
    """Rows of a code stack's major axis that hold layer `layer`: its
    heads, one plane each."""
    return layer * kv_heads + jnp.arange(kv_heads, dtype=jnp.int32)


def code_page_size(stack: jax.Array) -> int:
    """Positions a page of a code stack holds."""
    return math.prod(stack.shape[2:-1])


def _at_pos(stack: jax.Array, off: jax.Array) -> Tuple[jax.Array, ...]:
    """Index of position `off` of a page on a code stack: one axis, or
    (group, row) where 4-bit codes keep groups of rows."""
    if stack.ndim == 4:
        return (off,)
    return off // stack.shape[3], off % stack.shape[3]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Page-arena KV storage. Block tables are NOT part of the pytree —
    they are host-owned scheduling state (numpy, mutated per admission/
    finish) and ride into the jit as a separate ``[B, NP]`` operand, so
    donating the cache never aliases the table."""

    k: jax.Array    # [L * H_kv, P, page_size, D] storage dtype (4-bit:
    v: jax.Array    # [L * H_kv, P, page_size / 8, 8, D])
    pos: jax.Array  # [B] int32: per-slot number of valid positions
    # per-(token, head) f32 dequant scales for int8/int4 storage;
    # None for the scale-free dtypes (bf16 / fp8_e5m2)
    k_scale: Optional[jax.Array] = None   # [L, P, H_kv, page_size] f32
    v_scale: Optional[jax.Array] = None
    # static: a layer is this many consecutive planes of k and of v
    kv_heads: int = 1

    def tree_flatten(self):
        return ((self.k, self.v, self.pos, self.k_scale, self.v_scale),
                self.kv_heads)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, kv_heads=aux)

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return code_page_size(self.k)

    @property
    def head_dim(self) -> int:
        return self.k.shape[-1]

    @property
    def num_layers(self) -> int:
        return self.k.shape[0] // self.kv_heads

    @property
    def batch(self) -> int:
        return self.pos.shape[0]

    @property
    def kv_dtype(self) -> str:
        """Canonical kv_cache_dtype name of the storage."""
        return kv_dtype_name(self.k.dtype)


def init_paged_cache(
    num_layers: int,
    num_pages: int,
    page_size: int,
    kv_heads: int,
    head_dim: int,
    batch: int,
    dtype=jnp.bfloat16,
    kv_cache_dtype: Optional[str] = None,
) -> PagedKVCache:
    """Allocate an empty page arena (page 0 included — the null page is
    a real physical page so every block-table entry stays a valid
    index)."""
    name = resolve_kv_cache_dtype(kv_cache_dtype)
    dt = dtype if name == "bf16" else KV_CACHE_DTYPES[name]
    page = (page_size,)
    if jnp.dtype(dt) == jnp.dtype(jnp.int4):
        rows = math.gcd(page_size, PAGE_ROWS)
        page = (page_size // rows, rows)
    shape = (num_layers * kv_heads, num_pages) + page + (head_dim,)
    scaled = name in SCALED_KV_DTYPES
    sshape = (num_layers, num_pages, kv_heads, page_size)
    return PagedKVCache(
        k=jnp.zeros(shape, dt),
        v=jnp.zeros(shape, dt),
        pos=jnp.zeros((batch,), jnp.int32),
        k_scale=jnp.zeros(sshape, jnp.float32) if scaled else None,
        v_scale=jnp.zeros(sshape, jnp.float32) if scaled else None,
        kv_heads=kv_heads,
    )


def _page_offsets(pos: jax.Array, s_new: int, page_size: int,
                  block_tables: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """(phys, off) write coordinates for ``s_new`` tokens appended at
    per-slot ``pos``. Positions whose logical page is past the table
    width redirect to the null page (their offsets stay in range, so the
    scatter is always well-formed)."""
    npp = block_tables.shape[1]
    abs_pos = pos.reshape(-1, 1) + jnp.arange(s_new, dtype=jnp.int32)
    lp = abs_pos // page_size                                 # [B, Sn]
    off = abs_pos % page_size
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(lp, 0, npp - 1), axis=1)
    phys = jnp.where(lp < npp, phys, NULL_PAGE)
    return phys, off


def paged_update_layer(
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array | int,
    k_new: jax.Array,   # [B, S_new, H_kv, D]
    v_new: jax.Array,
    pos: jax.Array,     # [B] int32 per-slot append offsets
    block_tables: jax.Array,   # [B, NP] int32
    cache_ks: Optional[jax.Array] = None,
    cache_vs: Optional[jax.Array] = None,
):
    """Append k_new/v_new through the block table (the paged analog of
    `update_layer` with per-slot pos): one scatter per plane on the
    stack itself, so with a donated cache only the new rows move.
    Quantization is the same `quantize_kv` call the slab path makes, so
    stored codes/scales are bit-identical to a slab cache written at the
    same positions. Returns (ck, cv) or, with scale planes,
    (ck, cv, cks, cvs)."""
    scaled = cache_ks is not None
    if scaled:
        k_new, ks_new = quantize_kv(k_new, cache_k.dtype)
        v_new, vs_new = quantize_kv(v_new, cache_v.dtype)
    else:
        k_new = k_new.astype(cache_k.dtype)
        v_new = v_new.astype(cache_v.dtype)
    hkv = k_new.shape[2]
    phys, off = _page_offsets(pos, k_new.shape[1], code_page_size(cache_k),
                              block_tables)
    # one row of D a (token, head): the scattered window is the minor
    # dimension alone, which XLA writes in place (a window over the
    # heads made it re-lay the arena out)
    at = (code_plane(layer, hkv), phys[..., None]) + tuple(
        o[..., None] for o in _at_pos(cache_k, off))

    def put(stack, new):
        return stack.at[at].set(new)

    ck, cv = put(cache_k, k_new), put(cache_v, v_new)
    if not scaled:
        return ck, cv
    # index arrays on both sides of the head axis put [B, S_new] first
    # and the heads after it: the shape the new scales have
    return (ck, cv, cache_ks.at[layer, phys, :, off].set(ks_new),
            cache_vs.at[layer, phys, :, off].set(vs_new))


def _gather_codes(stack: jax.Array, layer, block_tables: jax.Array,
                  kv_heads: int) -> jax.Array:
    """Layer `layer` of a code stack, dense and logical
    ``[B, NP * ps, H_kv, D]``, via ONE XLA gather over (layer, table) —
    the fallback read. With ``NP * ps == max_seq`` the result is
    shape-identical to the slab layout's per-layer read."""
    by_layer = stack.reshape((-1, kv_heads) + stack.shape[1:])
    g = by_layer[layer, :, block_tables]   # [B, NP, H_kv, ps.., D]
    b, np_ = block_tables.shape
    return jnp.swapaxes(g.reshape(b, np_, kv_heads, -1, g.shape[-1]),
                        2, 3).reshape(b, -1, kv_heads, g.shape[-1])


def _gather_scales(stack: jax.Array, layer,
                   block_tables: jax.Array) -> jax.Array:
    """Layer `layer` of a scale stack as the slab's ``[B, NP * ps, H_kv]``."""
    g = jnp.swapaxes(stack[layer, block_tables], -1, -2)  # [B, NP, ps, H]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], g.shape[3])


def paged_read_layer_quantized(
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_ks: jax.Array,
    cache_vs: jax.Array,
    layer: jax.Array | int,
    block_tables: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One layer's raw codes + scales gathered dense (no dequant) — the
    feed for `sdp_attention(.., k_scale=, v_scale=)` so the upcast stays
    inside the fused kernels."""
    hkv = cache_ks.shape[2]
    return (_gather_codes(cache_k, layer, block_tables, hkv),
            _gather_codes(cache_v, layer, block_tables, hkv),
            _gather_scales(cache_ks, layer, block_tables),
            _gather_scales(cache_vs, layer, block_tables))


def paged_read_layer(
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array | int,
    block_tables: jax.Array,
    kv_heads: int,
    compute_dtype=jnp.bfloat16,
    cache_ks: Optional[jax.Array] = None,
    cache_vs: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Dense full-length K/V ``[B, NP * ps, H_kv, D]`` for one layer,
    gathered through the block table and upcast (dequantized when scale
    planes are given)."""
    from bigdl_tpu.ops.kvcache import dequantize_kv

    if cache_ks is not None:
        k, v, ks, vs = paged_read_layer_quantized(
            cache_k, cache_v, cache_ks, cache_vs, layer, block_tables)
        return (dequantize_kv(k, ks, compute_dtype),
                dequantize_kv(v, vs, compute_dtype))
    return (_gather_codes(cache_k, layer, block_tables,
                          kv_heads).astype(compute_dtype),
            _gather_codes(cache_v, layer, block_tables,
                          kv_heads).astype(compute_dtype))


def cow_copy_pages(cache: PagedKVCache,
                   srcs: jax.Array,    # [N] int32 physical source pages
                   dsts: jax.Array,    # [N] int32 destination pages
                   ) -> PagedKVCache:
    """Copy whole pages src -> dst on every plane, across every layer
    (the copy half of copy-on-write). Pair lists are fixed-length per
    compile — the engine pads with (0, 0) null-page self-copies, which
    are harmless no-ops on never-read data. Sources are gathered BEFORE
    the scatter, so a pair list that read and wrote the same page would
    still see pre-copy bytes. Pages are axis 1 of every plane."""
    def copy(plane):
        return plane.at[:, dsts].set(jnp.take(plane, srcs, axis=1))

    upd = dict(k=copy(cache.k), v=copy(cache.v))
    if cache.k_scale is not None:
        upd.update(k_scale=copy(cache.k_scale), v_scale=copy(cache.v_scale))
    return dataclasses.replace(cache, **upd)


def gather_pages_dense(cache: PagedKVCache,
                       pages: jax.Array):   # [n] int32 (0-padded tail)
    """Materialize ``n`` pages as dense LOGICAL ``[L, 1, n * ps, H, D]``
    planes (scales ``[L, 1, n * ps, H]``) — the slab layout a private
    prefill cache expects, used to seed an admission's cache1 from
    radix-shared pages and to export a sequence. Padding pages
    contribute garbage past the seeded length, which the prefill either
    overwrites or masks (positions > pos are never attended). Returns
    (k, v) or, with scale planes, (k, v, ks, vs)."""
    def codes(plane):
        g = jnp.take(plane, pages, axis=1)        # [L * H, n, ps.., D]
        g = g.reshape(-1, cache.kv_heads, pages.shape[0] * cache.page_size,
                      cache.head_dim)
        return jnp.swapaxes(g, 1, 2)[:, None]     # [L, 1, n * ps, H, D]

    def scales(plane):
        g = jnp.swapaxes(jnp.take(plane, pages, axis=1), -1, -2)
        nl, n, ps, h = g.shape                    # [L, n, ps, H]
        return g.reshape(nl, 1, n * ps, h)

    k, v = codes(cache.k), codes(cache.v)
    if cache.k_scale is None:
        return k, v
    return k, v, scales(cache.k_scale), scales(cache.v_scale)


def splice_pages(cache: PagedKVCache, planes,
                 pages: jax.Array) -> PagedKVCache:   # [n] int32
    """The inverse of `gather_pages_dense`: write dense LOGICAL planes,
    (k, v) ``[L, 1, s, H, D]`` or (k, v, ks, vs) with scales
    ``[L, 1, s, H]``, into the arena as WHOLE pages, every layer at
    once: positions ``[j * ps, (j + 1) * ps)`` land in page ``pages[j]``
    (the null page for a page nobody reads; ``s`` is padded up to
    ``n * ps``). An admission's private cache, or planes off the wire,
    into the layout at rest; `pos` is the caller's. One scatter of
    whole pages per plane, as `cow_copy_pages` makes: only those pages
    move. (A scatter of single positions with the layers in its window
    made XLA re-lay the arena out around it: 0.55 of the arena's bytes
    in temporaries, AOT for the v5e.)"""
    n, ps = pages.shape[0], cache.page_size

    def paged(plane):              # [L, 1, s, H, ..] -> [L, H, n, ps, ..]
        rows = plane[:, 0, :n * ps]
        rows = jnp.pad(rows, ((0, 0), (0, n * ps - rows.shape[1]))
                       + ((0, 0),) * (rows.ndim - 2))
        return jnp.moveaxis(
            rows.reshape((rows.shape[0], n, ps) + rows.shape[2:]), 3, 1)

    def codes(stack, plane):       # a plane a (layer, head)
        return stack.at[:, pages].set(
            paged(plane).reshape((-1, n) + stack.shape[2:])
            .astype(stack.dtype))

    def scales(stack, plane):      # [L, n, H, ps]: positions in the lanes
        return stack.at[:, pages].set(jnp.swapaxes(paged(plane), 1, 2))

    upd = dict(k=codes(cache.k, planes[0]), v=codes(cache.v, planes[1]))
    if cache.k_scale is not None:
        upd.update(k_scale=scales(cache.k_scale, planes[2]),
                   v_scale=scales(cache.v_scale, planes[3]))
    return dataclasses.replace(cache, **upd)


def paged_cache_nbytes(num_layers: int, num_pages: int, page_size: int,
                       kv_heads: int, head_dim: int,
                       kv_cache_dtype: Optional[str] = None
                       ) -> Dict[str, int]:
    """Storage footprint of a would-be arena without allocating it.
    By substitution (batch -> num_pages, max_seq -> page_size) this is
    exactly `kv_cache_nbytes`'s math, so an arena of
    ``old_batch * (max_seq // page_size)`` pages costs byte-for-byte what
    the old slab did — the equivalence the ledger-budget acceptance test
    leans on."""
    return kv_cache_nbytes(num_layers, num_pages, page_size, kv_heads,
                           head_dim, kv_cache_dtype)


def paged_cache_bytes(cache: PagedKVCache) -> Dict[str, int]:
    """Storage footprint of a live arena: codes, scales, total."""
    codes = _logical_nbytes(cache.k) + _logical_nbytes(cache.v)
    scales = 0
    if cache.k_scale is not None:
        scales = (_logical_nbytes(cache.k_scale)
                  + _logical_nbytes(cache.v_scale))
    return {"codes": codes, "scales": scales, "total": codes + scales}


def publish_paged_cache_bytes(cache: PagedKVCache,
                              registry=None) -> Dict[str, int]:
    """Set the `bigdl_tpu_kv_cache_bytes` gauge from the arena footprint
    (same metric family as the slab cache — dashboards keep working).
    Best-effort: metric export never gates allocation."""
    sizes = paged_cache_bytes(cache)
    try:
        if registry is None:
            from bigdl_tpu.observability import default_registry
            registry = default_registry()
        g = registry.gauge(
            "bigdl_tpu_kv_cache_bytes",
            "KV cache storage bytes by dtype and component "
            "(codes | scales | total); int4 counted at two codes per byte",
            labelnames=("dtype", "component"))
        for comp, val in sizes.items():
            g.labels(cache.kv_dtype, comp).set(float(val))
    except Exception:
        pass
    return sizes
