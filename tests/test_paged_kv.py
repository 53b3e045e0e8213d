"""Paged KV cache: pool/radix invariants, CoW safety, byte-identity.

The load-bearing claims of the paged path, each pinned here:

- refcounts never go negative (double free raises), pages free exactly
  at zero, allocation is all-or-nothing;
- the radix tree matches longest prefixes (full pages only), evicts
  only leaves the tree alone references, and drop stops at shared nodes;
- copy-on-write never mutates the shared page — a concurrent reader's
  bytes are untouched;
- paged decode is byte-identical to the per-slot slab under greedy AND
  seeded sampling, across bf16/int8/int4 KV storage;
- with prefix sharing on, a 64-way shared-prompt burst runs inside the
  arena budget that previously backed 8 slots (ISSUE 17 acceptance).
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from bigdl_tpu.ops.paged import NULL_PAGE
from bigdl_tpu.serving.pagepool import PagePool, RadixCache


# ---------------------------------------------------------------------------
# PagePool invariants


def test_pool_alloc_all_or_nothing():
    pool = PagePool(num_pages=5, page_size=16)   # 4 allocatable
    got = pool.alloc(3)
    assert got is not None and len(got) == 3
    assert NULL_PAGE not in got
    assert pool.num_free == 1
    assert pool.alloc(2) is None          # refused outright...
    assert pool.num_free == 1             # ...nothing partially granted
    assert pool.exhausted_total == 1
    assert pool.alloc(0) == []


def test_pool_refcount_never_negative():
    pool = PagePool(num_pages=4, page_size=16)
    (p,) = pool.alloc(1)
    assert pool.refcount(p) == 1
    assert pool.incref(p) == 2
    assert pool.decref(p) == 1
    assert pool.decref(p) == 0            # freed exactly at zero
    assert p in pool._free
    with pytest.raises(RuntimeError, match="double free"):
        pool.decref(p)
    with pytest.raises(RuntimeError, match="use-after-free"):
        pool.incref(p)


def test_pool_null_page_pinned():
    pool = PagePool(num_pages=3, page_size=16)
    assert pool.refcount(NULL_PAGE) == 1
    pool.decref(NULL_PAGE)                # no-ops, never frees
    pool.incref(NULL_PAGE)
    assert pool.refcount(NULL_PAGE) == 1
    for _ in range(2):
        got = pool.alloc(1)
        assert got is not None and got[0] != NULL_PAGE
    assert pool.alloc(1) is None          # null page never handed out


def test_pool_shared_accounting():
    pool = PagePool(num_pages=6, page_size=16)
    a, b = pool.alloc(2)
    pool.incref(a)
    assert pool.num_shared == 1
    assert pool.num_used == 2
    pool.decref(a)
    assert pool.num_shared == 0
    pool.decref(a)
    pool.decref(b)
    assert pool.num_used == 0


# ---------------------------------------------------------------------------
# RadixCache


def test_radix_longest_prefix_match():
    pool = PagePool(num_pages=12, page_size=4)
    radix = RadixCache(pool)
    prompt = list(range(100, 111))                  # 11 tokens: 2 full + tail
    pages = pool.alloc(3)
    assert radix.insert(prompt, pages) == 3
    # exact full-prefix reuse
    matched, got = radix.match(prompt[:8] + [1, 2])
    assert (matched, got) == (8, pages[:2])
    # longest-prefix: diverges inside the second page -> one page only
    matched, got = radix.match(prompt[:4] + [9, 9, 9, 9, 1])
    assert (matched, got) == (4, pages[:1])
    # the partial tail node is never returned by match
    matched, got = radix.match(prompt)
    assert matched == 8
    # a second prompt sharing page one splits, no duplicate nodes
    other = prompt[:4] + [50, 51, 52, 53]
    pages2 = pool.alloc(2)
    created = radix.insert(other, [pages[0], pages2[0]])
    assert created == 1                             # first page node reused
    assert radix.match(other)[1] == [pages[0], pages2[0]]


def test_radix_match_too_short_for_a_page():
    pool = PagePool(num_pages=4, page_size=8)
    radix = RadixCache(pool)
    radix.insert([1, 2, 3], pool.alloc(1))
    assert radix.match([1, 2, 3]) == (0, [])


def test_radix_evicts_only_unreferenced_leaves():
    pool = PagePool(num_pages=8, page_size=4)
    radix = RadixCache(pool)
    prompt = list(range(8))
    p = pool.alloc(2)
    radix.insert(prompt, p)                         # tree adds 1 ref each
    for pg in p:
        pool.decref(pg)                 # the admitting slot released its row
    pool.incref(p[1])                               # a live slot maps page 2
    assert radix.evict(10) == 0                     # leaf is slot-mapped: kept
    assert radix.num_nodes == 2
    pool.decref(p[1])
    # leaf now tree-only; removing it exposes the parent, which follows
    assert radix.evict(10) == 2
    assert radix.num_nodes == 0
    assert pool.num_used == 0


def test_radix_evict_is_lru():
    pool = PagePool(num_pages=8, page_size=4)
    radix = RadixCache(pool)
    pa, pb = pool.alloc(1), pool.alloc(1)
    radix.insert([1, 2, 3, 4], pa)
    radix.insert([5, 6, 7, 8], pb)
    pool.decref(pa[0])
    pool.decref(pb[0])                   # rows released; tree-only refs
    radix.match([1, 2, 3, 4])            # refresh the first path
    assert radix.evict(1) == 1
    assert radix.match([1, 2, 3, 4])[0] == 4        # survivor
    assert radix.match([5, 6, 7, 8])[0] == 0        # evicted


def test_radix_drop_stops_at_shared_nodes():
    pool = PagePool(num_pages=8, page_size=4)
    radix = RadixCache(pool)
    a = [1, 2, 3, 4, 10, 11, 12, 13]
    b = [1, 2, 3, 4, 20, 21, 22, 23]
    pa = pool.alloc(2)
    radix.insert(a, pa)
    pb = pool.alloc(1)
    radix.insert(b, [pa[0], pb[0]])
    # dropping `a` removes its private leaf, keeps the shared first page
    assert radix.drop(a) == 1
    assert radix.match(b) == (8, [pa[0], pb[0]])
    assert radix.match(a) == (4, [pa[0]])
    assert radix.drop(b) == 2                       # now the path is private
    assert radix.num_nodes == 0


def test_radix_clear_releases_every_ref():
    pool = PagePool(num_pages=8, page_size=4)
    radix = RadixCache(pool)
    pages = pool.alloc(3)
    radix.insert(list(range(10)), pages)
    for pg in pages:
        pool.decref(pg)                  # rows released; tree-only refs
    assert radix.clear() == 3
    assert pool.num_used == 0
    assert radix.num_nodes == 0


# ---------------------------------------------------------------------------
# copy-on-write at the arena level


def test_cow_copy_preserves_source_page():
    import dataclasses

    import jax.numpy as jnp

    from bigdl_tpu.ops.paged import cow_copy_pages, init_paged_cache

    cache = init_paged_cache(2, 4, 8, 2, 4, batch=1)
    cache = dataclasses.replace(cache, k=cache.k.at[:, 1].set(1.0),
                                v=cache.v.at[:, 1].set(2.0))
    before_k = np.asarray(cache.k).copy()
    new = cow_copy_pages(cache, jnp.asarray([1], jnp.int32),
                         jnp.asarray([2], jnp.int32))
    hk, hv = np.asarray(new.k), np.asarray(new.v)
    # the shared source page is bit-untouched; the copy is exact
    assert (hk[:, 1] == before_k[:, 1]).all()
    assert (hk[:, 2] == before_k[:, 1]).all()
    assert (hv[:, 2] == 2.0).all()
    # null->null self-copy (the padding lanes of a batched CoW step)
    # is the identity
    same = cow_copy_pages(new, jnp.asarray([0], jnp.int32),
                          jnp.asarray([0], jnp.int32))
    assert (np.asarray(same.k) == hk).all()


# ---------------------------------------------------------------------------
# the layout at rest, through ops/paged.py's accessors only (PR 40): every
# storage dtype at ChatGLM2's 2 kv groups and at Mistral's 8 heads

_LAYOUTS = [(kv, hkv) for kv in ("bf16", "fp8_e5m2", "int8", "int4")
            for hkv in (2, 8)]


def _filled_arena(kv, hkv, seed, ps=16, hd=8, b=2, np_=4, n=37, layers=2):
    """A slab cache and a paged one given the same `n` tokens a slot in
    two appends, the paged one through block tables in permuted order."""
    import dataclasses

    import jax.numpy as jnp

    from bigdl_tpu.ops.kvcache import init_cache, update_layer
    from bigdl_tpu.ops.paged import init_paged_cache, paged_update_layer

    rng = np.random.default_rng(seed)
    slab = init_cache(layers, b, np_ * ps, hkv, hd, kv_cache_dtype=kv)
    # the null page, a page for every table entry, and one left free
    cache = init_paged_cache(layers, b * np_ + 2, ps, hkv, hd, b,
                             kv_cache_dtype=kv)
    assert (cache.page_size, cache.kv_heads, cache.head_dim) == (ps, hkv, hd)
    tables = jnp.asarray(
        1 + rng.permutation(b * np_).reshape(b, np_), jnp.int32)
    sp = [slab.k, slab.v, slab.k_scale, slab.v_scale]
    pp = [cache.k, cache.v, cache.k_scale, cache.v_scale]
    scaled = cache.k_scale is not None
    for layer in range(layers):
        for start, stop in ((0, n - 5), (n - 5, n)):   # a prefill, a tail
            k, v = (jnp.asarray(rng.standard_normal(
                (b, stop - start, hkv, hd)) * (layer + 1), jnp.bfloat16)
                for _ in range(2))
            pos = jnp.full((b,), start, jnp.int32)
            out = update_layer(sp[0], sp[1], layer, k, v, pos, sp[2], sp[3])
            sp[:len(out)] = out
            out = paged_update_layer(pp[0], pp[1], layer, k, v, pos, tables,
                                     pp[2], pp[3])
            pp[:len(out)] = out
            assert len(out) == (4 if scaled else 2)
    cache = dataclasses.replace(
        cache, **dict(zip(("k", "v", "k_scale", "v_scale"), pp)))
    return sp, cache, tables, n


def _same(a, b):
    return (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


@pytest.mark.parametrize("kv,hkv", _LAYOUTS)
def test_paged_append_reads_back_the_slab_bit_for_bit(kv, hkv):
    from bigdl_tpu.ops.kvcache import read_layer
    from bigdl_tpu.ops.paged import (paged_read_layer,
                                     paged_read_layer_quantized)

    sp, cache, tables, n = _filled_arena(kv, hkv, seed=1)
    for layer in range(cache.num_layers):
        want = read_layer(sp[0], sp[1], layer, cache_ks=sp[2],
                          cache_vs=sp[3])
        got = paged_read_layer(cache.k, cache.v, layer, tables,
                               cache.kv_heads, cache_ks=cache.k_scale,
                               cache_vs=cache.v_scale)
        for w, g in zip(want, got):
            assert g.shape == w.shape and _same(g[:, :n], w[:, :n])
        if cache.k_scale is not None:        # codes AND scales, undequantized
            raw = paged_read_layer_quantized(
                cache.k, cache.v, cache.k_scale, cache.v_scale, layer, tables)
            for w, g in zip(sp, raw):
                assert g.shape == w.shape[1:] and g.dtype == w.dtype
                assert _same(g[:, :n], w[layer, :, :n])


@pytest.mark.parametrize("kv,hkv", _LAYOUTS)
def test_pages_gather_splice_and_cow_agree_on_one_arena(kv, hkv):
    """`gather_pages_dense` hands out the slab's logical planes,
    `splice_pages` (the engine's insert and migration import) is its
    inverse, and `cow_copy_pages` copies a page on every plane."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.paged import (cow_copy_pages, gather_pages_dense,
                                     init_paged_cache, splice_pages)

    sp, cache, tables, n = _filled_arena(kv, hkv, seed=2)
    ps = cache.page_size
    dense = gather_pages_dense(cache, tables[1])
    assert len(dense) == (4 if cache.k_scale is not None else 2)
    for w, g in zip(sp, dense):              # slot 1 of the slab, as stored
        assert g.shape == (w.shape[0], 1) + w.shape[2:] and g.dtype == w.dtype
        assert _same(g[:, 0, :n], w[:, 1, :n])

    # splice the dense planes into an EMPTY arena at other pages, in
    # another order; the page past the n tokens goes to the null page
    fresh = init_paged_cache(cache.num_layers, cache.num_pages, ps, hkv,
                             cache.head_dim, 2, kv_cache_dtype=kv)
    row = np.asarray(tables[0])[::-1].copy()
    row[-(-n // ps):] = NULL_PAGE
    spliced = splice_pages(fresh, dense, jnp.asarray(row, jnp.int32))
    again = gather_pages_dense(spliced, jnp.asarray(row, jnp.int32))
    for w, g in zip(dense, again):
        assert _same(g[:, :, :n], w[:, :, :n])
    untouched = sorted(set(range(1, cache.num_pages)) - set(row.tolist()))
    for g in gather_pages_dense(spliced, jnp.asarray(untouched, jnp.int32)):
        assert not np.asarray(g, np.float32).any()
    # a private cache shorter than its pages (a chunk that does not
    # divide a page) is padded up to them
    short = splice_pages(fresh, [p[:, :, :n] for p in dense],
                         jnp.asarray(row, jnp.int32))
    for w, g in zip(again, gather_pages_dense(
            short, jnp.asarray(row, jnp.int32))):
        assert _same(g[:, :, :n], w[:, :, :n])

    # copy-on-write of slot 1's second page onto the free one: the copy
    # reads back as the source, the source is untouched
    src, dst = int(tables[1, 1]), cache.num_pages - 1
    copied = cow_copy_pages(cache, jnp.asarray([src], jnp.int32),
                            jnp.asarray([dst], jnp.int32))
    both = gather_pages_dense(copied, jnp.asarray([src, dst], jnp.int32))
    for w, g in zip(dense, both):
        assert _same(g[:, :, :ps], w[:, :, ps:2 * ps])
        assert _same(g[:, :, ps:], w[:, :, ps:2 * ps])


@pytest.mark.parametrize("kv,hkv", _LAYOUTS)
def test_block_table_kernel_agrees_with_the_xla_gather(kv, hkv):
    """The Pallas kernel (interpret mode) on the arena's stacks and a
    layer index against the XLA fallback's one gather of
    `stack[layer, tables]`, on the same arena: pages of 128 positions
    in permuted order, a slot deep in its third page and one in its
    second, the null page behind the rest of both tables."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.attention import sdp_attention_paged

    sp, cache, tables, n = _filled_arena(kv, hkv, seed=3, ps=128, hd=128,
                                         np_=3, n=300)
    tables = tables.at[1, 2].set(NULL_PAGE)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 1, 16, 128)), jnp.bfloat16)
    pos = jnp.asarray([n - 1, 140], jnp.int32)
    for layer in range(cache.num_layers):
        got, want = (np.asarray(sdp_attention_paged(
            q, cache.k, cache.v, tables, pos, hkv, backend=be,
            k_scale=cache.k_scale, v_scale=cache.v_scale,
            layer=jnp.asarray(layer, jnp.int32)), np.float32)
            for be in ("pallas", "xla"))
        assert got.shape == want.shape == (2, 1, 16, 128)
        # bf16 probabilities and rows on both sides: 2^-8 of the values
        assert np.linalg.norm(got - want) <= 0.01 * np.linalg.norm(want)


@functools.lru_cache(maxsize=1)
def _random_arena(kv, hkv, pages, layers=2, ps=128, hd=128):
    """An arena of the layout at rest with seeded codes and scales on
    every page but the null one, which holds the largest codes: what a
    masked or skipped position would show if it were read. The last one
    made is kept: the cases of a layout follow each other."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.paged import init_paged_cache

    rng = np.random.default_rng(5)
    cache = init_paged_cache(layers, pages, ps, hkv, hd, 1,
                             kv_cache_dtype=kv)

    def codes(x):
        if jnp.issubdtype(x.dtype, jnp.integer):
            lim = 7 if x.dtype == jnp.int4 else 127
            a = rng.integers(-lim, lim + 1, x.shape).astype(np.int8)
            a[:, NULL_PAGE] = lim
        else:
            a = rng.standard_normal(x.shape).astype(np.float32)
            a[:, NULL_PAGE] = 50.0
        return jnp.asarray(a).astype(x.dtype)

    def scales(x):
        if x is None:
            return None
        a = rng.uniform(0.005, 0.02, x.shape).astype(np.float32)
        a[:, NULL_PAGE] = 1.0
        return jnp.asarray(a)

    return (codes(cache.k), codes(cache.v), scales(cache.k_scale),
            scales(cache.v_scale))


def _live_tables(rng, positions, np_, ps, pages, empty=(), shared=None):
    """Block tables in permuted order for slots at `positions`: a page a
    live column, the null page behind it; `empty` slots keep a null
    table; `shared` = (a, b) gives slot b slot a's first page."""
    free = list(1 + rng.permutation(pages - 1))
    bt = np.full((len(positions), np_), NULL_PAGE, np.int32)
    for i, p in enumerate(positions):
        if i not in empty:
            for j in range(p // ps + 1):
                bt[i, j] = free.pop()
    if shared is not None:
        bt[shared[1], 0] = bt[shared[0], 0]
    return bt


def _kernel_against_gather(kv, hkv, positions, empty=(), shared=None,
                           np_=11, pages=32, h=32):
    """The kernel (interpret mode) and the XLA gather over layer 1 of
    one seeded arena, slots at `positions(pages a run, page size, table
    columns)`: the empty slots' rows are zeros whatever the null page
    holds, the live ones agree to 1 % of the norm."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.attention import sdp_attention_paged
    from bigdl_tpu.ops.pallas.paged_decode_attention import (_page_bytes,
                                                             run_block)

    ps, hd = 128, 128
    k, v, ks, vs = _random_arena(kv, hkv, pages)
    hh, n = run_block(hkv, np_, _page_bytes(k))
    assert hh == hkv
    pos = positions(n, ps, np_)
    rng = np.random.default_rng(len(pos) + sum(pos))
    bt = _live_tables(rng, pos, np_, ps, pages, empty, shared)
    live = [i for i in range(len(pos)) if i not in empty]
    q = jnp.asarray(rng.standard_normal((len(pos), 1, h, hd)), jnp.bfloat16)
    got, want = (np.asarray(sdp_attention_paged(
        q, k, v, jnp.asarray(bt), jnp.asarray(pos, jnp.int32), hkv,
        backend=be, k_scale=ks, v_scale=vs,
        layer=jnp.asarray(1, jnp.int32)), np.float32)
        for be in ("pallas", "xla"))
    assert got.shape == want.shape == (len(pos), 1, h, hd)
    assert np.isfinite(got).all()
    assert not got[list(empty)].any()
    if live:
        assert np.linalg.norm(got[live] - want[live]) <= 0.01 * (
            np.linalg.norm(want[live]))
    return n


# four slots behind 11 table columns (no multiple of 2, 4 or 8 pages a
# run): (name, positions, empty slots, (a, b): b shares a's first page)
_KERNEL_CASES = [
    # a page's last and first row, and a multi-page run's
    ("page_edges", lambda n, ps, np_: [
        3 * ps - 1, 3 * ps, n * ps - 1, min(n, np_ - 1) * ps], (), None),
    # an empty slot (null table, position 0) between live ones
    ("empty_between", lambda n, ps, np_: [200, 0, 5 * ps + 7, 0],
     (1, 3), None),
    ("every_slot_empty", lambda n, ps, np_: [0, 0, 0, 0],
     (0, 1, 2, 3), None),
    # every column of a table the pages-a-run does not divide
    ("full_table", lambda n, ps, np_: [np_ * ps - 1, 17,
                                       (np_ - 1) * ps, ps], (), None),
    ("shared_page", lambda n, ps, np_: [2 * ps + 5, 6 * ps + 100,
                                        ps - 1, 0], (3,), (0, 1)),
]


@pytest.mark.parametrize("case", _KERNEL_CASES, ids=lambda c: c[0])
@pytest.mark.parametrize("kv,hkv,pages_a_run", [
    ("int8", 2, 8),          # the docqa cell's: G = 16, both heads a step
    ("bf16", 2, 4),
    ("fp8_e5m2", 8, 2),      # G = 4, all 8 heads a step
    ("int4", 8, 4),
])
def test_block_table_kernel_follows_what_is_live(kv, hkv, pages_a_run, case):
    """The kernel's copies, semaphores and its loop over a slot's live
    runs, run as written by interpret mode, against the XLA gather:
    positions at the edges of a page and of a run, empty slots, a full
    table, a page two slots share; the layouts of
    `test_block_table_kernel_agrees_with_the_xla_gather` that take 8, 4
    and 2 pages a run (1 and the whole table are that test's)."""
    _, positions, empty, shared = case
    assert _kernel_against_gather(kv, hkv, positions, empty,
                                  shared) == pages_a_run


@pytest.mark.parametrize("kv,hkv", [("int8", 2), ("bf16", 8)])
def test_block_table_kernel_serves_one_slot(kv, hkv):
    """The benchmark's after-window check (a): one slot, pages in
    seeded order."""
    _kernel_against_gather(kv, hkv, lambda n, ps, np_: [4 * ps + 39])


def test_block_table_kernel_takes_head_groups_past_the_budget():
    """32 KV heads of int8 pages outgrow a run's budget: a grid step
    takes 16 of them (one page a run), the scales' rows by the group's
    offset, and the result is still the XLA gather's."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.attention import sdp_attention_paged
    from bigdl_tpu.ops.pallas.paged_decode_attention import (_page_bytes,
                                                             run_block)

    hkv, ps, hd, np_, pages = 32, 128, 128, 3, 8
    rng = np.random.default_rng(6)
    k, v, ks, vs = _random_arena("int8", hkv, pages, layers=1)
    assert run_block(hkv, np_, _page_bytes(k)) == (16, 1)
    pos = [2 * ps + 3, 0, ps - 1]
    bt = _live_tables(rng, pos, np_, ps, pages, empty=(1,))
    q = jnp.asarray(rng.standard_normal((3, 1, 64, hd)), jnp.bfloat16)
    got, want = (np.asarray(sdp_attention_paged(
        q, k, v, jnp.asarray(bt), jnp.asarray(pos, jnp.int32), hkv,
        backend=be, k_scale=ks, v_scale=vs), np.float32)
        for be in ("pallas", "xla"))
    assert not got[1].any()
    live = [0, 2]
    assert np.linalg.norm(got[live] - want[live]) <= 0.01 * (
        np.linalg.norm(want[live]))


def test_pages_read_is_the_kernels_rule():
    """`pages_read`: a live slot's pages up to the one that holds its
    position, nothing of an empty slot (position below 0), never more
    than the table has."""
    from bigdl_tpu.ops.pallas.paged_decode_attention import pages_read

    ps, np_ = 128, 64
    assert pages_read([], ps, np_) == 0
    assert pages_read([-1, -1, -1], ps, np_) == 0
    assert pages_read([0], ps, np_) == 1
    # a position on a page's boundary: its last row, its first
    assert pages_read([ps - 1], ps, np_) == 1
    assert pages_read([ps], ps, np_) == 2
    assert pages_read([-1, 3 * ps - 1, -1, 3 * ps], ps, np_) == 3 + 4
    # a full table, and a position past it
    assert pages_read([np_ * ps - 1] * 4, ps, np_) == 4 * np_
    assert pages_read([np_ * ps + 5], ps, np_) == np_


@pytest.mark.parametrize("kv,hkv,b,np_,want", [
    ("int8", 2, 32, 64, (32, 1)),      # the docqa cell: 32 steps, 8 pages
    ("bf16", 8, 8, 16, (8, 1)),
    ("int4", 8, 8, 16, (8, 1)),
    ("bf16", 32, 4, 16, (4, 4)),       # a page's heads outgrow a run
])
def test_block_table_kernel_grid_is_slots_by_head_groups(kv, hkv, b, np_,
                                                         want):
    """The grid is (slots, groups of KV heads) whatever the table's
    width: a table column is no grid step, so none is spent on a column
    past a slot's position."""
    import jax

    from bigdl_tpu.ops.paged import init_paged_cache
    from bigdl_tpu.ops.pallas.paged_decode_attention import (
        _RUN_BYTES, _page_bytes, paged_attention_grid, run_block)

    k = jax.eval_shape(lambda: init_paged_cache(
        1, 4, 128, hkv, 128, b, kv_cache_dtype=kv)).k
    assert paged_attention_grid(b, np_, hkv, k) == want
    assert paged_attention_grid(b, 4 * np_, hkv, k) == want
    hh, n = run_block(hkv, np_, _page_bytes(k))
    assert hkv % hh == 0 and 1 <= n <= np_
    assert hh * n * _page_bytes(k) <= _RUN_BYTES


# ---------------------------------------------------------------------------
# engine-level byte-identity (paged vs slab)


def _drive(eng, prompts, params_of, max_steps=800):
    from collections import defaultdict

    outs = defaultdict(list)
    done = set()
    for i, (p, sp) in enumerate(zip(prompts, params_of)):
        eng.add_request(f"r{i}", p, sp)
    for _ in range(max_steps):
        eng.step()
        for i in range(len(prompts)):
            rid = f"r{i}"
            if rid in done:
                continue
            for o in eng.get_outputs(rid):
                outs[rid] += o.new_token_ids
                if o.finished:
                    done.add(rid)
        if len(done) == len(prompts):
            break
    assert len(done) == len(prompts), f"unfinished: {done}"
    return dict(outs)


def _mk_engine(kv_dtype=None, **kw):
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.utils.testing import tiny_random_model

    cfg = dict(max_batch=4, max_seq=64, prefill_bucket=8,
               prefill_chunk=8, prefix_cache_entries=0)
    if kv_dtype:
        cfg["kv_cache_dtype"] = kv_dtype
    registry = kw.pop("registry", None)
    cfg.update(kw)
    return LLMEngine(tiny_random_model(seed=0), EngineConfig(**cfg),
                     registry=registry)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_paged_matches_slab_greedy_and_sampled(kv_dtype):
    from bigdl_tpu.serving import SamplingParams

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 250, 13).tolist() for _ in range(4)]
    # half greedy, half seeded-sampled in ONE wave: identical logits
    # must give identical argmax AND identical gumbel draws
    params_of = [
        SamplingParams(max_tokens=8) if i % 2 == 0 else
        SamplingParams(max_tokens=8, temperature=0.8, top_k=8, seed=i)
        for i in range(4)]
    slab = _drive(_mk_engine(kv_dtype), prompts, params_of)
    paged = _drive(_mk_engine(kv_dtype, kv_page_size=16,
                              prefix_sharing="off"),
                   prompts, params_of)
    assert slab == paged


def test_prefix_sharing_stays_byte_identical_and_hits():
    from bigdl_tpu.serving import SamplingParams

    pre = list(range(1, 33))                   # 2 full pages at ps=16
    prompts = [pre + [100 + i, 200 + i] for i in range(4)]
    params_of = [SamplingParams(max_tokens=8)] * 4
    baseline = _drive(_mk_engine(), prompts, params_of)
    eng = _mk_engine(kv_page_size=16, prefix_sharing="on")
    shared = _drive(eng, prompts, params_of)
    assert shared == baseline
    snap = eng._paged_snapshot()
    # requests 2..4 each reuse the 32-token prefix from the radix
    assert snap["radix"]["hits"] == 3
    assert snap["radix"]["hit_tokens"] == 3 * 32
    assert snap["pool_exhausted_total"] == 0


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "slab"])
def test_metrics_carry_the_pages_counter_only_for_a_paged_cache(paged):
    """Every decode step of a paged engine adds to
    `bigdl_tpu_paged_attn_pages_total` what the block-table kernel's own
    rule gives for the live slots' positions (`pages_read`, times
    layers) beside every column of every table; `/metrics` of a slab
    engine has no such series (and a paged one none of the slab's)."""
    from bigdl_tpu.observability.metrics import MetricsRegistry
    from bigdl_tpu.ops.pallas.paged_decode_attention import pages_read
    from bigdl_tpu.serving import SamplingParams

    ps = 16
    # a registry of its own: the default one is every engine's
    eng = _mk_engine(registry=MetricsRegistry(),
                     **(dict(kv_page_size=ps) if paged else {}))
    eng.add_request("short", [7, 3, 99, 5], SamplingParams(max_tokens=3))
    eng.add_request("long", list(range(1, 30)), SamplingParams(max_tokens=9))
    layers, b, np_ = eng.cache.num_layers, 4, 64 // ps
    steps = 0
    while eng.has_unfinished() and steps < 100:
        live = [len(sl.req.prompt_token_ids) + len(sl.generated) - 1
                for sl in eng.slots if sl.active]
        settled = not eng.waiting and eng._admitting is None
        before = ({k: eng._m_paged_pages.labels(k).value
                   for k in ("read", "table")} if paged else None)
        eng.step()
        steps += 1
        if paged and settled and live:
            assert (eng._m_paged_pages.labels("read").value - before["read"]
                    == layers * pages_read(live, ps, np_))
            assert (eng._m_paged_pages.labels("table").value
                    - before["table"] == layers * b * np_)
    text = eng.registry.render()
    for kind in ("read", "table"):
        series = 'bigdl_tpu_paged_attn_pages_total{kind="%s"}' % kind
        assert (series in text) == paged
    assert ('bigdl_tpu_decode_attn_blocks_total{kind="read"}'
            in text) != paged
    if paged:
        read, table = (eng._m_paged_pages.labels(k).value
                       for k in ("read", "table"))
        assert 0 < read < table


def test_finish_releases_pages_and_reset_clears_radix():
    from bigdl_tpu.serving import SamplingParams

    eng = _mk_engine(kv_page_size=16, prefix_sharing="on")
    _drive(eng, [list(range(40, 60))], [SamplingParams(max_tokens=4)])
    # the slot released its row; only radix nodes still hold pages
    assert eng.pool.num_used == eng.radix.num_nodes > 0
    eng.reset_prefix_cache()
    assert eng.radix.num_nodes == 0
    assert eng.pool.num_used == 0
    assert eng.pool.num_free == eng.pool.num_pages - 1


def test_64_concurrent_in_8_slot_budget():
    """ISSUE 17 acceptance: >= 64 sequences resident at once, inside
    the arena bytes that previously backed an 8-slot slab. 64 requests
    share a 944-token prefix (59 full pages); each admission reserves
    only the worst-case NEW pages (max_seq-clamped), so the whole burst
    fits a 513-page arena == 8 slots x 1024 positions (+ null page)."""
    import dataclasses

    from bigdl_tpu.ops.kvcache import kv_cache_nbytes
    from bigdl_tpu.ops.paged import paged_cache_bytes
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.utils.testing import TINY_LLAMA, tiny_random_model

    cfg = dataclasses.replace(TINY_LLAMA, max_position_embeddings=1024)
    eng = LLMEngine(
        tiny_random_model(seed=0, cfg=cfg),
        EngineConfig(max_batch=64, max_seq=1024, prefill_bucket=16,
                     prefill_chunk=16, prefix_cache_entries=0,
                     kv_page_size=16, kv_pages=513, prefix_sharing="on",
                     max_queue_depth=96))
    slab8 = kv_cache_nbytes(cfg.num_hidden_layers, 8, 1024,
                            cfg.num_key_value_heads, cfg.hd,
                            eng.kv_cache_dtype or "bf16")["total"]
    arena = paged_cache_bytes(eng.cache)["total"]
    # ledger parity: the arena costs what 8 slab slots cost (+1 page)
    assert arena <= slab8 + eng._kv_bytes_per_page

    rng = np.random.default_rng(0)
    pre = rng.integers(1, 250, 944).tolist()
    n = 64
    for i in range(n):
        # unique last token; generation is max_seq-clamped at 79 tokens,
        # which outlives the ~64-step admission ramp -> true overlap
        eng.add_request(f"c{i}", pre + [i + 1],
                        SamplingParams(max_tokens=200))
    peak = 0
    finished = set()
    for _ in range(3000):
        eng.step()
        peak = max(peak, sum(s.active for s in eng.slots))
        for i in range(n):
            rid = f"c{i}"
            if rid not in finished:
                finished.update(rid for o in eng.get_outputs(rid)
                                if o.finished)
        if len(finished) == n:
            break
    snap = eng._paged_snapshot()
    assert len(finished) == n, (len(finished), snap)
    assert peak >= 64, (peak, snap)
    assert snap["pool_exhausted_total"] == 0, snap
    # the prefix really was served from shared pages, not re-prefilled
    assert snap["radix"]["hit_tokens"] >= (n - 1) * 928, snap


# ---------------------------------------------------------------------------
# satellite: handoff retention decoupled from prefix_cache_entries


def _stage_fake_handoff(eng, prompt):
    import jax.numpy as jnp

    cfg = eng.cfg
    plen = len(prompt)
    shape = (cfg.num_hidden_layers, 1, plen,
             cfg.num_key_value_heads, cfg.hd)
    planes = (jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))
    eng.stage_handoff(prompt, planes)


def test_handoff_cap_zero_drops_snapshots():
    eng = _mk_engine(handoff_cache_entries=0)
    _stage_fake_handoff(eng, [1, 2, 3, 4])
    eng._drain_handoffs()
    assert not eng._handoff_in
    assert not eng._prefix_cache


def test_handoff_cap_bounds_entries_with_local_cache_off():
    # prefix_cache_entries=0 means local caching OFF; handoff retention
    # is bounded by ITS knob, not silently re-enabled at 2*max_batch
    eng = _mk_engine(prefix_cache_entries=0, handoff_cache_entries=2)
    for k in range(4):
        _stage_fake_handoff(eng, [10 + k, 11 + k, 12 + k])
    eng._drain_handoffs()
    assert len(eng._prefix_cache) == 2
    # default (-1) falls back to 2*max_batch
    eng2 = _mk_engine()
    for k in range(12):
        _stage_fake_handoff(eng2, [30 + k, 31 + k, 32 + k])
    eng2._drain_handoffs()
    assert len(eng2._prefix_cache) == 2 * 4


def test_paged_engine_clears_handoff_inbox():
    eng = _mk_engine(kv_page_size=16)
    _stage_fake_handoff(eng, [1, 2, 3, 4])
    eng._drain_handoffs()
    assert not eng._handoff_in
    assert not eng._prefix_cache


# ---------------------------------------------------------------------------
# config resolvers


def test_paged_knob_resolvers():
    from bigdl_tpu.config import (resolve_kv_page_size, resolve_kv_pages,
                                  resolve_prefix_sharing)

    assert resolve_kv_page_size(0) == 0
    assert resolve_kv_page_size("128") == 128
    for bad in ("48", -16, "x"):
        with pytest.raises(ValueError):
            resolve_kv_page_size(bad)
    assert resolve_kv_pages("0") == 0
    assert resolve_kv_pages(129) == 129
    for bad in ("1", -2, "y"):
        with pytest.raises(ValueError):
            resolve_kv_pages(bad)
    assert resolve_prefix_sharing("1") == "on"
    assert resolve_prefix_sharing(None) == "auto"
    with pytest.raises(ValueError):
        resolve_prefix_sharing("never")


def test_engine_rejects_bad_paged_geometry():
    with pytest.raises(ValueError):
        _mk_engine(kv_page_size=48)          # not a power of two
    with pytest.raises(ValueError):
        _mk_engine(kv_page_size=32, max_seq=72)   # max_seq % ps != 0
