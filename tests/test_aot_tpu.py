"""AOT compilation of every Pallas kernel + full model programs for v5e.

Interpret mode never takes a kernel through a real Mosaic lowering. This suite compiles them for an
OFFLINE v5e topology (jax.experimental.topologies + local libtpu; no chip
needed), so "should work on TPU" becomes "compiles for TPU" in CI.

`flags().aot_target = 'tpu'` routes kernel dispatch to Pallas during
lowering even though the host backend is CPU (probes cannot execute on an
abstract topology; Mosaic rejections surface at .compile(), which is what
this suite is for). The whole-model tests additionally assert the compiled
HLO actually CONTAINS Mosaic custom-calls — guarding against the silent
100%-XLA-fallback failure mode.

Compiled-memory figures are recorded in PARITY.md.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.config import set_flags

# The run's compile cache is off here: an executable for the offline
# topology is written to it and cannot be read back on a CPU host
# ("DeserializeLoadedExecutable not implemented"), so the cache would
# cost every case a write and a warning and save it nothing.
pytestmark = [pytest.mark.aot, pytest.mark.usefixtures("no_compile_cache")]


@pytest.fixture(scope="module")
def v5e():
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"offline v5e topology unavailable: {e}")
    return topo


@pytest.fixture()
def aot_flags():
    set_flags(aot_target="tpu")
    yield
    set_flags(aot_target=None)


def _sds(tree, dev):
    s = SingleDeviceSharding(dev)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree)


def _lower_resident(eng, dev, all_greedy=True, with_quality=False):
    """`engine_decode_resident` lowered at the engine's own geometry on
    its packed arguments: `ints` `[4, B]`, `floats` `[2, B]`."""
    b = eng.cfg_engine.max_batch
    return eng._decode_resident.lower(
        _sds(eng.params, dev),
        _sds(jax.ShapeDtypeStruct((4, b), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((2, b), jnp.float32), dev),
        _sds(jax.eval_shape(lambda: eng.cache), dev),
        all_greedy=all_greedy, with_quality=with_quality)


def _two_in_flight_bytes(ma) -> int:
    """What two resident steps in flight hold: the arguments once (the
    cache is donated from one to the next, the weights are shared), the
    temporaries and the small results of each."""
    return (ma.argument_size_in_bytes + 2 * ma.temp_size_in_bytes
            + 2 * (ma.output_size_in_bytes - ma.alias_size_in_bytes))


def _compile(fn, *abstract_args):
    return jax.jit(fn).lower(*abstract_args).compile()


def _has_mosaic_call(compiled) -> bool:
    txt = compiled.as_text()
    return "tpu_custom_call" in txt or "custom-call" in txt and "Mosaic" in txt


# ------------------------------------------------------------ kernels

GEMM_QTYPES = ["sym_int4", "asym_int4", "nf4", "fp4", "nf3", "sym_int8"]


@pytest.mark.parametrize("qtype", GEMM_QTYPES)
def test_dequant_matmul_generic_compiles(v5e, aot_flags, qtype):
    from bigdl_tpu.ops.matmul import q_matmul_pallas
    from bigdl_tpu.ops.quant import quantize

    dev = v5e.devices[0]
    wq = jax.eval_shape(
        lambda: quantize(jnp.zeros((4096, 4096), jnp.float32), qtype))
    x = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16)
    comp = _compile(lambda xx, ww: q_matmul_pallas(xx, ww),
                    _sds(x, dev), _sds(wq, dev))
    assert _has_mosaic_call(comp), "kernel lowered to XLA, not Mosaic"


@pytest.mark.parametrize("qtype,n", [("sym_int4", 4096), ("sym_int4", 11008),
                                     ("sym_int8", 4096), ("nf4", 4096),
                                     ("fp4", 4096), ("asym_int4", 4096)])
def test_dequant_gemv_compiles(v5e, aot_flags, qtype, n):
    """The decode-GEMV variant (M<=16, x/scales VMEM-resident) at
    llama-7B decode geometries — called directly, bypassing the probe."""
    from bigdl_tpu.ops.pallas.dequant_matmul import (_q_gemv_pallas,
                                                     gemv_tiles)
    from bigdl_tpu.ops.quant import get_qtype, quantize

    dev = v5e.devices[0]
    k = 4096
    qt = get_qtype(qtype)
    wq = jax.eval_shape(
        lambda: quantize(jnp.zeros((k, n), jnp.float32), qtype))
    x = jax.ShapeDtypeStruct((1, k), jnp.bfloat16)
    comp = _compile(
        lambda xx, ww: _q_gemv_pallas(xx, ww, qt, 1, k, n,
                                      gemv_tiles(qt, k, n), False, xx.dtype),
        _sds(x, dev), _sds(wq, dev))
    assert _has_mosaic_call(comp)


@pytest.mark.parametrize("k,n", [
    (4096, 12288),   # merged QKV (7B, fused q+k+v)
    (4096, 22016),   # merged gate-up
    (11008, 4096),   # down-proj
    (4096, 4096),    # o-proj
])
def test_dequant_gemv_mxu_compiles(v5e, aot_flags, k, n):
    """The int4-dtype-layout GEMV (native Mosaic int4 load — no VPU
    nibble unpack) at all four 7B merged decode shapes."""
    from bigdl_tpu.ops.pallas.dequant_matmul import (_q_gemv_pallas,
                                                     gemv_tiles)
    from bigdl_tpu.ops.probing import quant_struct
    from bigdl_tpu.ops.quant import get_qtype

    dev = v5e.devices[0]
    qt = get_qtype("sym_int4")
    wq = quant_struct(k, n, "sym_int4", mxu=True)
    assert wq.data.dtype == jnp.int4
    x = jax.ShapeDtypeStruct((1, k), jnp.bfloat16)
    comp = _compile(
        lambda xx, ww: _q_gemv_pallas(xx, ww, qt, 1, k, n,
                                      gemv_tiles(qt, k, n), False, xx.dtype),
        _sds(x, dev), _sds(wq, dev))
    assert _has_mosaic_call(comp)


def test_dequant_generic_i4_compiles(v5e, aot_flags):
    """Generic-tile body for the int4-dtype layout (prefill-class M
    under forced-pallas dispatch)."""
    from bigdl_tpu.ops.matmul import q_matmul_pallas
    from bigdl_tpu.ops.probing import quant_struct

    dev = v5e.devices[0]
    wq = quant_struct(4096, 4096, "sym_int4", mxu=True)
    x = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16)
    comp = _compile(lambda xx, ww: q_matmul_pallas(xx, ww),
                    _sds(x, dev), _sds(wq, dev))
    assert _has_mosaic_call(comp)


# [K, N] of every quantized linear a prefill chunk (M = 256) meets in
# the benchmark's three configurations
PREFILL_CHUNK_LINEARS = [
    (4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096),   # Mistral-7B
    (4096, 4608), (4096, 27392), (13696, 4096),                 # ChatGLM2-6B
    (5120, 1536), (1536, 24576), (5120, 640), (16384, 5120),    # DeepSeek-V2
    (5120, 3072), (3072, 5120), (5120, 12288), (12288, 5120),
]


@pytest.mark.parametrize("k,n", PREFILL_CHUNK_LINEARS + [(32224, 4096)])
def test_dequant_gemm_compiles_at_a_prefill_chunk(v5e, aot_flags, k, n):
    """The int4-dtype GEMM (`qmatmul_gemm_sym_int4`) at M = 256, as auto
    dispatch sends a chunk's linears to it. ChatGLM2's K = 13696 = 2^7 x
    107 (no bk with an 8-row scale block divides it) takes one full-K
    tile. A K with no legal tiling at all (32224 = 2^5 x 1007: the
    full-K tile is over the VMEM budget) is XLA's by RULE: the tile
    search says so before anything compiles."""
    from bigdl_tpu.ops.matmul import kernel_plan, q_matmul
    from bigdl_tpu.ops.probing import quant_struct

    dev = v5e.devices[0]
    wq = quant_struct(k, n, "sym_int4", mxu=True)
    x = jax.ShapeDtypeStruct((1, 256, k), jnp.bfloat16)
    comp = _compile(lambda xx, ww: q_matmul(xx, ww),
                    _sds(x, dev), _sds(wq, dev))
    if k == 32224:
        assert kernel_plan("sym_int4", 256, k, n, True) is None
        assert not _has_mosaic_call(comp)
    else:
        assert "qmatmul_gemm_sym_int4" in comp.as_text()


@pytest.mark.parametrize("k,n", [
    (4096, 1024),    # q/k/v column shard (also o-proj local K)
    (1024, 4096),    # o-proj row shard
    (4096, 2816),    # gate/up column shard (ff 11008 lane-padded 11264)
    (2816, 4096),    # down-proj row shard
])
def test_dequant_gemv_compiles_tp4_shards(v5e, aot_flags, k, n):
    """ALL FOUR llama2-7B matmul shapes at tp=4 must
    dispatch to the decode-GEMV kernel (with pad_ff_for_tp's ff
    lane-padding, 11008 -> 11264). Before the joint (bk, bn) tile
    search, the down-proj shard (K=2752) fell off the kernel entirely."""
    from bigdl_tpu.ops.pallas.dequant_matmul import (_q_gemv_pallas,
                                                     gemv_tiles)
    from bigdl_tpu.ops.quant import get_qtype, quantize

    dev = v5e.devices[0]
    qt = get_qtype("sym_int4")
    tiles = gemv_tiles(qt, k, n)
    assert tiles is not None, "shape not kernel-eligible"
    wq = jax.eval_shape(
        lambda: quantize(jnp.zeros((k, n), jnp.float32), "sym_int4"))
    x = jax.ShapeDtypeStruct((1, k), jnp.bfloat16)
    comp = _compile(
        lambda xx, ww: _q_gemv_pallas(xx, ww, qt, 1, k, n, tiles, False,
                                      xx.dtype),
        _sds(x, dev), _sds(wq, dev))
    assert _has_mosaic_call(comp)


@pytest.mark.parametrize("b,s,h,hkv,hd,kvdt", [
    (1, 1024, 32, 32, 128, "bfloat16"),     # llama2-7B MHA
    (1, 2048, 32, 8, 128, "bfloat16"),      # GQA (mistral/llama3)
    (1, 2048, 32, 8, 128, "float8_e5m2"),   # fp8 KV cache
    (8, 1024, 32, 8, 128, "bfloat16"),      # batched serving decode
    (1, 4096, 40, 40, 128, "bfloat16"),     # 13B-class long cache
    (1, 16384, 32, 8, 128, "bfloat16"),     # 16k: 32 S blocks
    (1, 32768, 32, 8, 128, "float8_e5m2"),  # 32k fp8 KV
])
def test_decode_attention_compiles(v5e, aot_flags, b, s, h, hkv, hd, kvdt):
    from bigdl_tpu.ops.pallas.decode_attention import decode_attention_pallas

    dev = v5e.devices[0]
    kdt = jnp.dtype(kvdt)
    q = jax.ShapeDtypeStruct((b, 1, h, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, b, s, hkv, hd), kdt)    # a stack of one
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    comp = _compile(
        lambda qq, kk, vv, pp: decode_attention_pallas(
            qq, kk, vv, pp, hd ** -0.5),
        _sds(q, dev), _sds(kv, dev), _sds(kv, dev), _sds(pos, dev))
    assert _has_mosaic_call(comp)


def _scale_planes(shape, dev):
    sc = jax.ShapeDtypeStruct(shape, jnp.float32)
    return _sds(sc, dev), _sds(sc, dev)


@pytest.mark.parametrize("b,s,kvdt", [
    (1, 2048, "int8"), (8, 2048, "int8"),
    (1, 2048, "int4"), (8, 2048, "int4"),
    (1, 16384, "int8"), (1, 16384, "int4"),
])
def test_decode_attention_scaled_kv_compiles(v5e, aot_flags, b, s, kvdt):
    """Block-scaled int8/int4 KV at Mistral-7B GQA 32/8: codes plus
    f32 (token, head) scale planes, dequantized in the kernel — a
    distinct Mosaic program from the bf16/fp8 one above."""
    from bigdl_tpu.ops.pallas.decode_attention import decode_attention_pallas

    dev = v5e.devices[0]
    h, hkv, hd = 32, 8, 128
    q = jax.ShapeDtypeStruct((b, 1, h, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, b, s, hkv, hd), jnp.dtype(kvdt))
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    ks, vs = _scale_planes((1, b, s, hkv), dev)
    comp = _compile(
        lambda qq, kk, vv, pp, ks_, vs_: decode_attention_pallas(
            qq, kk, vv, pp, hd ** -0.5, k_scale=ks_, v_scale=vs_),
        _sds(q, dev), _sds(kv, dev), _sds(kv, dev), _sds(pos, dev), ks, vs)
    assert _has_mosaic_call(comp)


def _moves_of(txt, shapes, appends_in_place=False):
    """Instructions of a compiled program that MATERIALIZE one of
    `shapes` (a copy, a fusion, a reshape, a slice, an update: anything
    but a view or the plumbing of tuples and loops).

    With `appends_in_place`, for a program that WRITES the arrays it is
    searched for: a scatter, or the fusion around one whose result is
    its first operand's buffer (`aliasing_operands` names operand 0 and
    the result), is the append itself, only the new rows move; and a
    result the compiler keeps in VMEM (`S(1)` in its layout) is its own
    staging of a small plane, not a copy in HBM."""
    import re

    views = ("parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "conditional", "call", "copy-start", "copy-done", "constant")
    found = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?\w+\[([\d,]*)\](\S*) "
            r"([\w\-]+)\((.*)$", txt, re.M):
        name, dims, layout, op, rest = m.groups()
        if op in views or tuple(
                int(d) for d in dims.split(",") if d) not in shapes:
            continue
        if appends_in_place and (
                "S(1)" in layout or op == "scatter" or op == "fusion"
                and re.search(r'"aliasing_operands":\{"lists":\[\{"indices":'
                              r'\["0","\d+"\]\}\]\}', rest)
                and "/scatter" in rest):
            continue
        found.append(f"{name} = [{dims}] {op}")
    return found


@pytest.mark.parametrize("layers,b,s,hkv,hd,kvdt,in_place", [
    (32, 32, 2048, 8, 128, "bfloat16", True),   # the Mistral cells' slab
    (32, 32, 2048, 8, 128, "int8", True),       # the same, scale planes
    (32, 32, 2048, 8, 128, "int4", True),
    (32, 32, 2048, 8, 128, "float8_e5m2", True),
    (4, 4, 8192, 8, 128, "bfloat16", True),     # long cache
    (4, 4, 8192, 8, 128, "int8", True),
    (4, 4, 2048, 2, 128, "bfloat16", True),     # ChatGLM2's 2 kv groups
    (4, 4, 2048, 4, 128, "int8", True),
    # a position's heads under one 32-bit word, or hd under the lanes:
    # the chip tiles such a stack with S inside, the wrapper slices
    (4, 4, 2048, 2, 128, "int8", False),
    (4, 4, 2048, 8, 64, "bfloat16", False),
])
def test_decode_attention_over_stack_in_place(v5e, aot_flags, layers, b, s,
                                              hkv, hd, kvdt, in_place):
    """The kernel takes the cache's whole [L, B, S, Hkv, hd] stack and a
    traced layer index. Where the chip tiles the stack over (Hkv, hd)
    (`_stack_in_place`) neither the stack, nor a layer of it, nor a
    scale plane is copied, sliced or reshaped on the way in; elsewhere
    one layer is, and never the stack."""
    from bigdl_tpu.ops.pallas.decode_attention import (
        _stack_in_place, decode_attention_pallas)

    dev = v5e.devices[0]
    h = 32
    q = _sds(jax.ShapeDtypeStruct((b, 1, h, hd), jnp.bfloat16), dev)
    kv = _sds(jax.ShapeDtypeStruct((layers, b, s, hkv, hd),
                                   jnp.dtype(kvdt)), dev)
    assert _stack_in_place(kv) == in_place
    pos = _sds(jax.ShapeDtypeStruct((b,), jnp.int32), dev)
    lyr = _sds(jax.ShapeDtypeStruct((), jnp.int32), dev)
    scales = ()
    if kvdt in ("int8", "int4"):
        scales = _scale_planes((layers, b, s, hkv), dev)
    comp = _compile(
        lambda qq, kk, vv, pp, ll, *sc: decode_attention_pallas(
            qq, kk, vv, pp, hd ** -0.5, layer=ll,
            **(dict(k_scale=sc[0], v_scale=sc[1]) if sc else {})),
        q, kv, kv, pos, lyr, *scales)
    assert _has_mosaic_call(comp)
    stack = {(layers, b, s, hkv, hd), (layers, b, s, hkv),
             (layers, b, hkv, s)}
    layer = {(b, s, hkv, hd), (1, b, s, hkv, hd), (b, s, hkv * hd),
             (b, s, hkv), (b, hkv, s), (1, b, s, hkv), (1, b, hkv, s)}
    txt = comp.as_text()
    assert not _moves_of(txt, stack)
    assert bool(_moves_of(txt, layer)) != in_place, _moves_of(txt, layer)
    if in_place:
        assert comp.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("b,kvdt", [
    (1, "bfloat16"), (8, "bfloat16"), (8, "float8_e5m2"),
    (1, "int8"), (8, "int8"), (8, "int4"),
])
def test_paged_decode_attention_compiles(v5e, aot_flags, b, kvdt):
    """The block-table kernel (ops/pallas/paged_decode_attention) at
    Mistral-7B GQA 32/8, hd 128, 128-position pages over a max_seq-2048
    arena, on the cache's whole stack as `ops/paged.py` keeps it: the
    kernel copies the pages the prefetched layer index and block table
    name out of the arena where it lies, and nothing of the arena is
    moved on the way in."""
    _compile_paged_kernel(v5e, b, kvdt, layers=4, hkv=8, np_=16,
                          pages=8 * 16 + 1)


def test_paged_decode_attention_compiles_at_the_docqa_cell(v5e, aot_flags):
    """The same at the docqa cell's own geometry (ChatGLM2's 2 KV
    groups, int8 pages, all 28 layers' stack of 1280 pages, 32 slots
    behind 64 table columns): 32 grid steps where a step a (slot, head,
    column) made 4,096 (PERF.md 6, PR 42), and still nothing over the
    arena."""
    import math

    from bigdl_tpu.ops.pallas.paged_decode_attention import (
        paged_attention_grid)

    cache = _compile_paged_kernel(v5e, 32, "int8", layers=28, hkv=2,
                                  np_=64, pages=1280)
    grid = paged_attention_grid(32, 64, 2, cache.k)
    assert grid == (32, 1) and math.prod(grid) <= 512


def test_paged_decode_attention_compiles_in_head_groups(v5e, aot_flags):
    """32 KV heads of int8 pages: a grid step takes 16 of them, and the
    scales' rows at the group's offset."""
    from bigdl_tpu.ops.pallas.paged_decode_attention import (
        paged_attention_grid)

    cache = _compile_paged_kernel(v5e, 4, "int8", layers=2, hkv=32,
                                  np_=16, pages=65)
    assert paged_attention_grid(4, 16, 32, cache.k) == (4, 2)


def _compile_paged_kernel(v5e, b, kvdt, layers, hkv, np_, pages):
    from bigdl_tpu.ops.pallas.paged_decode_attention import (
        paged_decode_attention_pallas)
    from bigdl_tpu.ops.paged import init_paged_cache

    dev = v5e.devices[0]
    h, hd, ps = 32, 128, 128
    q = jax.ShapeDtypeStruct((b, 1, h, hd), jnp.bfloat16)
    cache = _sds(jax.eval_shape(lambda: init_paged_cache(
        layers, pages, ps, hkv, hd, b, kv_cache_dtype={
            "bfloat16": "bf16", "float8_e5m2": "fp8_e5m2"}.get(kvdt, kvdt))),
        dev)
    assert cache.k.dtype == jnp.dtype(kvdt)
    bt = jax.ShapeDtypeStruct((b, np_), jnp.int32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    comp = _compile(
        lambda qq, c, bb, ll: paged_decode_attention_pallas(
            qq, c.k, c.v, bb, c.pos, hd ** -0.5, hkv, k_scale=c.k_scale,
            v_scale=c.v_scale, layer=ll),
        _sds(q, dev), cache, _sds(bt, dev), _sds(i32, dev))
    assert _has_mosaic_call(comp)
    planes = {p.shape for p in (cache.k, cache.k_scale) if p is not None}
    assert not _moves_of(comp.as_text(), planes | {
        (hkv,) + cache.k.shape[1:], cache.k.shape[1:]} | {
            p[1:] for p in planes})
    assert comp.memory_analysis().temp_size_in_bytes < 4 << 20
    return cache


def _arena_shapes(layers, pages, ps, hkv, hd):
    """Every shape the arena, a layer of it or a scale plane has or had:
    the layout at rest (`ops/paged.py`), views of it the kernel could
    be handed, and the one of before PR 40 (`[L, P, ps, Hkv, hd]`,
    scales `[L, P, ps, Hkv]`)."""
    page = [(ps // 8, 8, hd), (ps, hd), (ps, hkv * hd), (ps, hkv, hd),
            (hkv, ps), (ps, hkv)]
    return {lead + p for p in page
            for lead in ((layers * hkv, pages), (layers, pages),
                         (hkv, pages), (1, pages), (pages,))}


@pytest.mark.parametrize("hkv,kv", [
    (2, "int8"),                    # the docqa cell: ChatGLM2's 2 kv groups
    (8, "bf16"), (8, "int8"), (8, "int4"), (8, "fp8_e5m2"),
])
def test_paged_decode_step_moves_no_arena(v5e, aot_flags, hkv, kv):
    """One whole paged decode step, `forward_paged` at Sq 1, at the
    docqa cell's geometry (1280 pages of 128 positions, 32 slots, 64
    table columns; 4 layers are enough): the append scatters into the
    stack and the block-table kernel reads the stack, so NO instruction
    materializes the arena, a layer of it or a scale plane, in the
    layout at rest or any it had. On the chip those were eight of the
    ten longest operations of the cell, 0.651 of 2.89 s busy, and 7.2 GB
    of temporaries at 28 layers (PERF.md 6, PR 40)."""
    import dataclasses

    from bigdl_tpu.models import llama as M
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    dev = v5e.devices[0]
    layers, pages, ps, b, np_ = 4, 1280, 128, 32, 64
    cfg = dataclasses.replace(LLAMA2_7B, num_hidden_layers=layers,
                              num_key_value_heads=hkv)
    params = _sds(jax.eval_shape(lambda: prepack_tree(M.merge_projections(
        random_llama_params(cfg, "sym_int4"), cfg))[0]), dev)
    cache = _sds(jax.eval_shape(
        lambda: M.new_paged_cache(cfg, pages, ps, b, kv)), dev)
    ids = _sds(jax.ShapeDtypeStruct((b, 1), jnp.int32), dev)
    bt = _sds(jax.ShapeDtypeStruct((b, np_), jnp.int32), dev)
    comp = jax.jit(
        lambda p, i, c, t: M.forward_paged(p, cfg, i, c, t, last_only=True),
        donate_argnums=(2,)).lower(params, ids, cache, bt).compile()
    txt = comp.as_text()
    assert "paged_decode_attention" in txt and _has_mosaic_call(comp)
    moved = _moves_of(txt, _arena_shapes(layers, pages, ps, hkv, cfg.hd),
                      appends_in_place=True)
    assert not moved, f"the arena is materialized: {moved}"
    assert comp.memory_analysis().temp_size_in_bytes < 128 << 20


# Mistral-7B sym_int4 decode matmuls, merged layout: qkv, o, gate_up,
# down, lm_head
MISTRAL_7B_GEMV = [(4096, 6144), (4096, 4096), (4096, 28672),
                   (14336, 4096), (4096, 32000)]


@pytest.mark.parametrize("mxu", [False, True], ids=["canonical", "mxu"])
@pytest.mark.parametrize("k,n", MISTRAL_7B_GEMV)
def test_xla_fused_gemv_compiles(v5e, k, n, mxu):
    """The decode-shaped XLA path with the dequant fused into the dot
    (ops/matmul._q_matmul_xla_fused) — what serves a decode matmul the
    Pallas kernel is not chosen for — at both weight layouts."""
    from bigdl_tpu.ops.matmul import q_matmul
    from bigdl_tpu.ops.probing import quant_struct

    dev = v5e.devices[0]
    x = jax.ShapeDtypeStruct((8, k), jnp.bfloat16)
    comp = _compile(lambda xx, ww: q_matmul(xx, ww, backend="xla_fused"),
                    _sds(x, dev), _sds(quant_struct(k, n, "sym_int4",
                                                    mxu=mxu), dev))
    ma = comp.memory_analysis()
    # fused: no full bf16 dequant of the weight (2*K*N bytes) in temp
    assert ma.temp_size_in_bytes < k * n


@pytest.mark.parametrize("k,n", MISTRAL_7B_GEMV)
def test_q_matmul_auto_dispatch_is_mosaic_at_mistral_shapes(v5e, aot_flags,
                                                            k, n):
    """`auto` dispatch at decode M on the shipped (prepacked) layout
    lowers to the Pallas GEMV at every Mistral-7B shape."""
    from bigdl_tpu.ops.matmul import q_matmul
    from bigdl_tpu.ops.probing import quant_struct

    dev = v5e.devices[0]
    x = jax.ShapeDtypeStruct((8, k), jnp.bfloat16)
    comp = _compile(q_matmul, _sds(x, dev),
                    _sds(quant_struct(k, n, "sym_int4", mxu=True), dev))
    assert _has_mosaic_call(comp)


@pytest.mark.parametrize("b,sq,s,h,hkv,hd,kvdt", [
    (1, 512, 1024, 32, 32, 128, "bfloat16"),
    (1, 1024, 2048, 32, 8, 128, "bfloat16"),
    (1, 1024, 2048, 32, 8, 128, "float8_e5m2"),
])
def test_prefill_attention_compiles(v5e, aot_flags, b, sq, s, h, hkv, hd,
                                    kvdt):
    from bigdl_tpu.ops.pallas.prefill_attention import (
        prefill_attention_pallas)

    dev = v5e.devices[0]
    kdt = jnp.dtype(kvdt)
    q = jax.ShapeDtypeStruct((b, sq, h, hd), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, hkv, hd), kdt)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    comp = _compile(
        lambda qq, kk, vv, pp: prefill_attention_pallas(
            qq, kk, vv, pp, hd ** -0.5),
        _sds(q, dev), _sds(kv, dev), _sds(kv, dev), _sds(pos, dev))
    assert _has_mosaic_call(comp)


def test_prefill_attention_vjp_compiles(v5e, aot_flags):
    """Training path: grad through the Pallas forward (custom VJP runs the
    XLA reference backward — both must lower in one program)."""
    from bigdl_tpu.ops.pallas.prefill_attention import (
        prefill_attention_pallas)

    dev = v5e.devices[0]
    q = jax.ShapeDtypeStruct((1, 512, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 512, 32, 128), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def loss(qq, kk, vv, pp):
        return jnp.sum(prefill_attention_pallas(
            qq, kk, vv, pp, 128 ** -0.5).astype(jnp.float32))

    comp = _compile(jax.grad(loss), _sds(q, dev), _sds(kv, dev),
                    _sds(kv, dev), _sds(pos, dev))
    assert comp is not None


@pytest.mark.parametrize("qtype", [None, "sym_int4"])
def test_moe_ragged_compiles(v5e, aot_flags, qtype):
    from bigdl_tpu.ops.pallas.moe_dispatch import (TOKEN_TILE,
                                                   ragged_expert_matmul)
    from bigdl_tpu.ops.quant import quantize

    dev = v5e.devices[0]
    e, k, n, toks = 8, 1024, 2816, 256
    if qtype is None:
        w = jax.ShapeDtypeStruct((e, k, n), jnp.bfloat16)
    else:
        one = jax.eval_shape(
            lambda: quantize(jnp.zeros((k, n), jnp.float32), qtype))
        w = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((e,) + a.shape, a.dtype), one)
    x = jax.ShapeDtypeStruct((toks, k), jnp.bfloat16)
    te = jax.ShapeDtypeStruct((toks // TOKEN_TILE,), jnp.int32)
    comp = _compile(lambda xx, ww, tt: ragged_expert_matmul(xx, ww, tt),
                    _sds(x, dev), _sds(w, dev), _sds(te, dev))
    assert _has_mosaic_call(comp)


# ------------------------------------------------------- whole model

def _llama7b_abstract(dev, qtype="sym_int4", batch=1, max_seq=2048,
                      quantized_cache=False):
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    cfg = LLAMA2_7B
    params = _sds(jax.eval_shape(
        lambda: random_llama_params(cfg, qtype)), dev)
    cache = _sds(jax.eval_shape(
        lambda: M.new_cache(cfg, batch, max_seq,
                            quantized=quantized_cache)), dev)
    return cfg, params, cache


RECORDED = {}


def test_llama7b_decode_compiles(v5e, aot_flags):
    from bigdl_tpu.models import llama as M

    dev = v5e.devices[0]
    cfg, params, cache = _llama7b_abstract(dev)
    ids = _sds(jax.ShapeDtypeStruct((1, 1), jnp.int32), dev)
    comp = _compile(lambda p, i, c: M.forward(p, cfg, i, c),
                    params, ids, cache)
    assert _has_mosaic_call(comp), (
        "7B decode compiled WITHOUT any Mosaic kernel — silent XLA fallback")
    ma = comp.memory_analysis()
    RECORDED["decode"] = ma
    # whole-model INT4: weights ~3.5GB + bf16 KV cache; must fit v5e 16G
    assert ma.argument_size_in_bytes < 8e9


@pytest.mark.parametrize("mxu", [False, True], ids=["canonical", "mxu"])
@pytest.mark.parametrize("sq", [1, 1024])
def test_llama7b_merged_projections_compile(v5e, aot_flags, sq, mxu):
    """Merged-QKV + merged-gate-up layout, canonical AND int4-dtype MXU
    weight re-layout (the full from_pretrained default): decode must
    still dispatch Mosaic kernels at the fused shapes (N=12288 qkv,
    N=22016 gate_up), prefill must compile clean. The mxu case is the
    whole-model superset of test_dequant_gemv_mxu_compiles — int4
    arrays through the lax.scan layer stack and the M-routed dispatch —
    i.e. the exact program the 08:03 live window timed out on."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    dev = v5e.devices[0]
    cfg = LLAMA2_7B
    prepack = "on" if mxu else "off"               # pin: no ambient env
    params = _sds(jax.eval_shape(
        lambda: prepack_tree(M.merge_projections(
            random_llama_params(cfg, "sym_int4"), cfg), prepack)[0]), dev)
    flat = jax.tree_util.tree_leaves(params)
    has_int4 = any(a.dtype == jnp.int4 for a in flat)
    assert has_int4 == mxu, f"prepack={prepack} but int4 planes={has_int4}"
    cache = _sds(jax.eval_shape(lambda: M.new_cache(cfg, 1, 2048)), dev)
    ids = _sds(jax.ShapeDtypeStruct((1, sq), jnp.int32), dev)
    comp = _compile(
        lambda p, i, c: M.forward(p, cfg, i, c, last_only=(sq > 1)),
        params, ids, cache)
    assert _has_mosaic_call(comp)
    if mxu:
        ma = comp.memory_analysis()
        RECORDED[f"mxu_layout_sq{sq}"] = ma
        assert ma.argument_size_in_bytes < 8e9


def test_llama7b_prefill_compiles(v5e, aot_flags):
    from bigdl_tpu.models import llama as M

    dev = v5e.devices[0]
    cfg, params, cache = _llama7b_abstract(dev)
    ids = _sds(jax.ShapeDtypeStruct((1, 512), jnp.int32), dev)
    comp = _compile(
        lambda p, i, c: M.forward(p, cfg, i, c, last_only=True),
        params, ids, cache)
    assert _has_mosaic_call(comp)
    RECORDED["prefill"] = comp.memory_analysis()


def test_llama7b_decode_fp8_cache_compiles(v5e, aot_flags):
    from bigdl_tpu.models import llama as M

    dev = v5e.devices[0]
    cfg, params, cache = _llama7b_abstract(dev, quantized_cache=True)
    ids = _sds(jax.ShapeDtypeStruct((1, 1), jnp.int32), dev)
    comp = _compile(lambda p, i, c: M.forward(p, cfg, i, c),
                    params, ids, cache)
    assert _has_mosaic_call(comp)


def _mistral7b_engine(b, s):
    """`LLMEngine` over a registry-built Mistral-7B at published width,
    merged + prepacked like a from_pretrained load. Shapes only: the
    engine never sees an array."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.registry import get_family
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.smoke import MISTRAL_7B_HF
    from bigdl_tpu.utils.testing import random_llama_params

    family = get_family("MistralForCausalLM", MISTRAL_7B_HF)
    cfg = family.config_from_hf(MISTRAL_7B_HF)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(M.merge_projections(
            random_llama_params(cfg, "sym_int4"), cfg))[0])
        config, hf_config, qtype = cfg, MISTRAL_7B_HF, "sym_int4"

    Model.family = family
    return LLMEngine(Model, EngineConfig(max_batch=b, max_seq=s,
                                         sentinel=False, quality=False))


@pytest.mark.parametrize("b", [8, 32])
def test_engine_decode_resident_step_compiles(v5e, aot_flags, b):
    """One whole serving decode step AS THE ENGINE BUILDS IT
    (engine_decode_resident: layer scan + health + sampling in one
    executable) for a registry-built Mistral-7B at published width,
    merged + prepacked like a from_pretrained load, over a max_seq-2048
    slab of 8 slots and of the benchmark cells' 32. Shapes only: the
    engine never sees an array.

    Structural guard: inside the layer scan the KV stack is addressed
    where it lies. No instruction materializes a layer's slab (a slice
    taken out of the scan carry, a reshape of it for the kernel, a copy)
    and no stack-sized result is fed by a slab-sized update (a layer
    written back whole). `memory_analysis()` does not see such copies
    (their buffers are reused), so the guard reads the compiled text; on
    the chip they were eight ops per layer per step, 41-46 % of the
    serving cells' device time (PERF.md, PR 26)."""
    import re

    dev = v5e.devices[0]
    s = 2048
    eng = _mistral7b_engine(b, s)
    comp = _lower_resident(eng, dev).compile()
    assert _has_mosaic_call(comp), (
        "engine decode step compiled WITHOUT any Mosaic kernel")
    txt = comp.as_text()
    # GEMV x5 (qkv, o, gate_up, down, lm_head) + decode attention
    assert txt.count("tpu_custom_call") >= 6
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    kv_gb = 2 * 32 * b * s * 8 * 128 * 2 / 1e9    # ~2.1 GB at 8 slots
    assert 4.0 + kv_gb < live / 1e9 < 5.9 + kv_gb   # + ~4.3 GB weights

    layers, hkv, hd = 32, 8, 128
    slab = b * s * hkv * hd
    moved = _moves_of(txt, {(b, s, hkv, hd), (1, b, s, hkv, hd),
                            (b, s, hkv * hd)})
    assert not moved, f"a layer's slab is materialized: {moved}"
    # a stack-sized result may only take in the new rows: none of its
    # operands (beside the stack itself) is as large as a layer
    sizes = {m.group(1): int(np.prod([int(d) for d in m.group(2).split(",")
                                      if d] or [1]))
             for m in re.finditer(
                 r"(%[\w.\-]+)(?: =|:) \(?\w+\[([\d,]*)\]", txt)}
    stack = f"[{layers},{b},{s},{hkv},{hd}]"
    for m in re.finditer(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+" + re.escape(stack)
            + r"\S* (fusion|scatter|dynamic-update-slice|copy)\((.*?)\)",
            txt, re.M):
        name, op, args = m.groups()
        fed = [a for a in re.findall(r"%[\w.\-]+", args)
               if slab <= sizes.get(a, 0) < layers * slab]
        assert op != "copy" and not fed, (
            f"{name} ({op}) writes a layer-sized operand {fed} into the "
            f"stack")


def _sorts_by_place(txt):
    """`(outside, inside)`: the `sort(` instructions of a compiled
    program's text that run whenever the program does, and those that
    lie in a branch of a `conditional` (or in a computation only a
    branch calls)."""
    import re

    comps, name, entry = {}, None, None
    for line in txt.splitlines():
        m = re.match(r"(ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif name is not None:
            comps[name].append(line)
    called = (r"\b(%s)=(\{[^}]*\}|%%[\w.\-]+)" % "|".join((
        "to_apply", "calls", "body", "condition", "branch_computations",
        "true_computation", "false_computation")))
    branches = ("branch_computations", "true_computation",
                "false_computation")
    always, todo = set(), [entry]
    while todo:         # what ENTRY reaches through no conditional's branch
        c = todo.pop()
        if c in always:
            continue
        always.add(c)
        for line in comps[c]:
            for attr, names in re.findall(called, line):
                if attr not in branches:
                    todo += re.findall(r"%[\w.\-]+", names)

    def count(cs):
        return sum(len(re.findall(r"\bsort\(", ln))
                   for c in cs for ln in comps[c])
    return count(always), count(set(comps) - always)


def test_engine_decode_resident_sampled_step_sorts_no_vocabulary(v5e,
                                                                  aot_flags):
    """The resident step with its sampler and its quality columns
    (`all_greedy=False, with_quality=True`) for the same Mistral-7B:
    the one `sort` of the program is the nucleus branch's, inside the
    sampler's `conditional`, and nothing that runs every step sorts a
    `[B, 32000]` row. In this program `jnp.sort` and `lax.top_k(lg, 2)`
    were both compiled to a full stable key-value sort (0.09 s of a 3 s
    trace each, three a step, before PR 51): this is the guard against
    one creeping back."""
    dev = v5e.devices[0]
    eng = _mistral7b_engine(8, 2048)
    txt = _lower_resident(eng, dev, all_greedy=False,
                          with_quality=True).compile().as_text()
    assert "tpu_custom_call" in txt
    assert txt.count(" conditional(") == 1
    outside, inside = _sorts_by_place(txt)
    assert (outside, inside) == (0, 1), (
        f"{outside} sort(s) run every step, {inside} under a conditional")


def test_engine_prefill_chunk_dequantizes_in_vmem(v5e, aot_flags):
    """One whole prefill chunk AS THE ENGINE BUILDS IT (`engine_prefill`,
    256 rows into a private 1024-position cache) for the same
    Mistral-7B: every int4 linear of the layer scan is the Pallas GEMM
    that dequantizes a weight tile in VMEM, and no float32 (or bf16)
    copy of a layer's weights is written to HBM. On the chip those
    copies were 78 of a chunk's 112 ms (PERF.md, PR 29)."""
    import re

    from bigdl_tpu.ops.kvcache import init_cache_spec

    dev = v5e.devices[0]
    eng = _mistral7b_engine(8, 2048)
    chunk = eng._chunk
    assert chunk == 256
    cache1 = jax.eval_shape(lambda: init_cache_spec(
        eng._cache_spec, 1, 1024, kv_cache_dtype=eng.kv_cache_dtype))
    tokens = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    txt = eng._prefill.lower(_sds(eng.params, dev), _sds(tokens, dev),
                             _sds(cache1, dev)).compile().as_text()
    # qkv, o, gate_up, down in the layer scan
    assert txt.count("qmatmul_gemm_sym_int4") >= 4
    dense = re.findall(
        r"(?:f32|bf16)\[(?:1,)?(?:4096,6144|4096,4096|4096,28672|14336,4096"
        r"|\d+,32,(?:6144|4096|28672))\]", txt)
    assert not dense, f"a layer's weights are dequantized in HBM: {dense[:3]}"


def test_vmapped_gemv_compiles(v5e, aot_flags):
    """MoE decode gathers per-token expert weights and runs the matmul
    under vmap with dynamic indexing — pallas_call's batching rule must
    lower for v5e too (the vmapped_pallas_ok probe's real path)."""
    from bigdl_tpu.ops.matmul import q_matmul_pallas
    from bigdl_tpu.ops.quant import quantize

    dev = v5e.devices[0]
    e, k, n = 4, 1024, 2816
    one = jax.eval_shape(
        lambda: quantize(jnp.zeros((k, n), jnp.float32), "sym_int4"))
    stack = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((e,) + a.shape, a.dtype), one)
    x = jax.ShapeDtypeStruct((8, k), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32)

    def per(i, row, ws):
        wi = jax.tree.map(lambda a: a[i], ws)
        return q_matmul_pallas(row[None], wi)[0]

    comp = _compile(
        lambda ii, xx, ws: jax.vmap(per, in_axes=(0, 0, None))(ii, xx, ws),
        _sds(idx, dev), _sds(x, dev), _sds(stack, dev))
    assert _has_mosaic_call(comp)


def test_sharded_int4_inference_compiles_v5e_mesh(v5e, aot_flags):
    """Multi-chip REALITY check (the CPU-mesh dryrun can't see Mosaic):
    a tp-sharded INT4 forward must compile for a real v5e 2x2 topology.
    GSPMD cannot auto-partition Pallas kernels, so under a multi-device
    mesh the dispatch falls back to XLA ops (config.under_spmd) — this
    test is the regression gate for that guard (it hard-crashed the
    compile before), and asserts the partitioned program carries the
    row-parallel all-reduce."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.parallel.sharding import llama_param_specs
    from bigdl_tpu.utils.testing import random_llama_params

    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("dp", "tp"))
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        num_key_value_heads=32)
    pshape = jax.eval_shape(lambda: random_llama_params(cfg, "sym_int4"))
    specs = llama_param_specs(pshape, mesh)
    p_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        pshape, specs)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, PartitionSpec())),
        jax.eval_shape(lambda: M.new_cache(cfg, 1, 1024)))
    ids = jax.ShapeDtypeStruct((1, 1), jnp.int32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    with mesh:
        comp = jax.jit(lambda p, i, c: M.forward(p, cfg, i, c)).lower(
            p_s, ids, cache).compile()
    txt = comp.as_text()
    assert "all-reduce" in txt, "no row-parallel reduction emitted"


def _train_step_compile(v5e, cfg, mesh_shape, step_factory,
                        params_builder, batch_shape):
    """Shared sharded-train-step AOT harness: build the (dp, tp) mesh,
    ShapeDtypeStructs for params (sharded by llama_param_specs),
    replicated optimizer state, dp-sharded batch; compile the step for
    the real topology. Returns the compiled executable."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from bigdl_tpu.parallel.sharding import llama_param_specs

    mesh = Mesh(np.array(v5e.devices).reshape(*mesh_shape), ("dp", "tp"))
    built = jax.eval_shape(params_builder)

    def sds_tree(tree):
        specs = llama_param_specs(tree, mesh)
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    opt = optax.adamw(1e-4)
    step = step_factory(opt)
    trainable = built[0] if isinstance(built, tuple) else built
    os_s = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, PartitionSpec())),
        jax.eval_shape(lambda: opt.init(jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), trainable))))
    batch = {
        k: jax.ShapeDtypeStruct(batch_shape, jnp.int32,
                                sharding=NamedSharding(
                                    mesh, PartitionSpec("dp")))
        for k in ("input_ids", "attention_mask")}
    with mesh:
        if isinstance(built, tuple):    # (trainable, frozen) QLoRA split
            t_s, f_s = sds_tree(built[0]), sds_tree(built[1])
            return step.lower(t_s, os_s, f_s, batch).compile()
        return step.lower(sds_tree(built), os_s, batch).compile()


def test_sharded_train_step_compiles_v5e_mesh(v5e, aot_flags):
    """dp x tp training step (grad all-reduce over dp, tensor-parallel
    activations over tp) compiles for the v5e 2x2 topology."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.training import make_train_step
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=2, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=1024)
    comp = _train_step_compile(
        v5e, cfg, (2, 2),
        lambda opt: make_train_step(M.forward_train, cfg, opt),
        lambda: random_llama_params(cfg, None),
        (4, 256))
    assert "all-reduce" in comp.as_text()


def _tp_compile(v5e, cfg, make_params, max_seq=2048):
    """Shared explicit-TP abstract-compile harness: build the tp=4 mesh,
    sharded param/cache/ids ShapeDtypeStructs from `make_params(cfg,
    n)` (evaluated under eval_shape), compile TP._tp_fn for the real
    topology. Returns (compiled, hlo_text)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from bigdl_tpu.models import llama as M
    from bigdl_tpu.ops.kvcache import KVCache
    from bigdl_tpu.parallel import tp as TP

    mesh = Mesh(np.array(v5e.devices), ("tp",))
    n = mesh.shape["tp"]
    pshape = jax.eval_shape(lambda: make_params(cfg, n))
    specs = TP.tp_param_specs(pshape, mesh)
    p_s = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
        pshape, specs)
    cshape = jax.eval_shape(lambda: M.new_cache(cfg, 1, max_seq))
    csh = NamedSharding(mesh, TP.tp_cache_specs())
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    cache_s = KVCache(
        jax.ShapeDtypeStruct(cshape.k.shape, cshape.k.dtype, sharding=csh),
        jax.ShapeDtypeStruct(cshape.v.shape, cshape.v.dtype, sharding=csh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    ids = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=rep)
    fn = TP._tp_fn(cfg, mesh, "tp")
    with mesh:
        comp = fn.lower(p_s, ids, cache_s).compile()
    return comp, comp.as_text()


def test_explicit_tp_kernels_compile_v5e_mesh(v5e, aot_flags):
    """The explicit-shard_map TP path (parallel/tp.py) is the
    kernel-capable multi-chip route: the partitioned program must
    contain Mosaic custom-calls (kernels on LOCAL shards) AND the
    row-parallel all-reduce."""
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.parallel import tp as TP
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=2, num_attention_heads=32,
        num_key_value_heads=32)
    # pad_ff_for_tp: gate/up/down shards lane-align (11008 -> 11264),
    # lm_head vocab shards too (32000 -> 32256) — the same transform
    # shard_params_tp applies on real arrays
    comp, txt = _tp_compile(v5e, cfg, lambda c, n: TP.pad_ff_for_tp(
        random_llama_params(c, "sym_int4"), n))
    assert _has_mosaic_call(comp), (
        "explicit TP compiled without Mosaic kernels — the whole point "
        "of the shard_map path")
    assert "all-reduce" in txt


def test_explicit_tp_moe_compiles_v5e_mesh(v5e, aot_flags):
    """Mixtral-geometry MoE under explicit TP must
    compile for the real v5e topology with Mosaic kernels AND the
    all-reduce — expert ff sharded across tp, psum on the partial
    expert outputs (8x7B geometry at 2 layers to bound compile time)."""
    from bigdl_tpu.models.mixtral import MixtralConfig
    from bigdl_tpu.utils.testing import random_mixtral_params

    cfg = MixtralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=2, num_attention_heads=32,
        num_key_value_heads=8, num_local_experts=8,
        num_experts_per_tok=2)
    comp, txt = _tp_compile(
        v5e, cfg, lambda c, n: random_mixtral_params(c, "sym_int4"))
    assert _has_mosaic_call(comp), (
        "explicit-TP MoE compiled without Mosaic kernels")
    assert "all-reduce" in txt


def test_explicit_tp_parallel_residual_compiles_v5e_mesh(v5e, aot_flags):
    """A falcon-style (parallel-residual, shared input
    norm, non-gated gelu MLP) family must compile for the real v5e
    topology under explicit TP with Mosaic kernels AND the all-reduce —
    these families previously could never use Pallas kernels
    multi-chip."""
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.parallel import tp as TP
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=16384,
        num_hidden_layers=2, num_attention_heads=32,
        num_key_value_heads=8, parallel_residual=True,
        shared_input_norm=True, mlp_gated=False, hidden_act="gelu")
    comp, txt = _tp_compile(v5e, cfg, lambda c, n: TP.pad_ff_for_tp(
        random_llama_params(c, "sym_int4"), n))
    assert _has_mosaic_call(comp)
    assert "all-reduce" in txt


def test_mixtral_prefill_compiles(v5e, aot_flags):
    """MoE model: ragged dispatch + router on the prefill path at a
    mixtral-like (downscaled-experts) geometry."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.utils.testing import random_mixtral_params

    dev = v5e.devices[0]

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=4, num_attention_heads=32, num_key_value_heads=8,
        num_local_experts=8, num_experts_per_tok=2)
    params = _sds(jax.eval_shape(
        lambda: random_mixtral_params(cfg, "sym_int4")), dev)
    cache = _sds(jax.eval_shape(lambda: M.new_cache(cfg, 1, 1024)), dev)
    ids = _sds(jax.ShapeDtypeStruct((1, 256), jnp.int32), dev)
    comp = _compile(
        lambda p, i, c: M.forward(p, cfg, i, c, last_only=True),
        params, ids, cache)
    assert _has_mosaic_call(comp)


def test_mixtral_8x7b_int2_fits_one_chip(v5e, aot_flags):
    """The reference's INT2 feasibility headline — 'run Mixtral-8x7B on
    Intel GPU with 16GB VRAM via iq2' (reference README.md:16) — on one
    16GB v5e: FULL 8x7B geometry (32 layers, 8 experts, ff 14336) in
    iq2_xxs (2.19 bpw group codebooks) must compile for decode with
    compiled argument + temp memory under 16GB."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.utils.testing import random_mixtral_params

    dev = v5e.devices[0]
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=8, num_local_experts=8,
        num_experts_per_tok=2)
    params = _sds(jax.eval_shape(
        lambda: random_mixtral_params(cfg, "iq2_xxs")), dev)
    import math

    arg_bytes = sum(
        a.dtype.itemsize * math.prod(a.shape)
        for a in jax.tree_util.tree_leaves(params))
    # 46.7B params at 2.19 bpw ~ 12.8GB packed
    assert 11e9 < arg_bytes < 14.5e9, arg_bytes / 1e9
    cache = _sds(jax.eval_shape(lambda: M.new_cache(cfg, 1, 1024)), dev)
    ids = _sds(jax.ShapeDtypeStruct((1, 1), jnp.int32), dev)
    comp = _compile(lambda p, i, c: M.forward(p, cfg, i, c),
                    params, ids, cache)
    ma = comp.memory_analysis()
    RECORDED["mixtral_8x7b_iq2"] = ma
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < 16e9, f"{total / 1e9:.2f} GB exceeds one v5e"


def test_cp_32k_ring_prefill_compiles_v5e_mesh(v5e, aot_flags):
    """Long-context + distributed, on real topology: a 32k-token llama2-7B
    prompt ring-prefills over an sp=4 v5e mesh (parallel/cp.py — the KV
    for the prompt never materializes on one chip). Asserts the ICI
    collectives (ppermute ring shifts) are in the compiled HLO and the
    per-chip memory fits."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.models import llama as M
    from bigdl_tpu.parallel import cp as CP
    from bigdl_tpu.utils.testing import LLAMA2_7B, random_llama_params

    mesh = Mesh(np.array(v5e.devices), ("sp",))
    n = mesh.shape["sp"]
    cfg = LLAMA2_7B
    s = 32768
    fn = CP._prefill_fn(cfg, mesh, "sp", s, s, jnp.bfloat16)

    pshape = jax.eval_shape(lambda: random_llama_params(cfg, "sym_int4"))
    rep = NamedSharding(mesh, P())
    p_s = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        pshape)
    tok = jax.ShapeDtypeStruct(
        (1, s), jnp.int32, sharding=NamedSharding(mesh, P(None, "sp")))
    with mesh:
        comp = fn.lower(p_s, tok).compile()
    txt = comp.as_text()
    assert "collective-permute" in txt or "ppermute" in txt, \
        "ring attention compiled without ICI permutes"
    ma = comp.memory_analysis()
    RECORDED["cp_32k_sp4"] = ma
    per_chip = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes)
    # replicated int4 weights (~4GB) + 1/4 of the 32k KV + ring buffers
    assert per_chip < 16e9, f"{per_chip / 1e9:.2f} GB exceeds one v5e"


def test_llama70b_int4_tp4_fits_v5e_mesh(v5e, aot_flags):
    """The reference's 70B multi-device claim (Deepspeed-AutoTP runs
    llama2-70B INT4 across 4 devices, example/GPU/Deepspeed-AutoTP):
    FULL llama2-70B geometry (80 layers, GQA 64/8, ff 28672) in
    sym_int4 under explicit tp=4 must compile for the v5e 2x2 topology
    with per-chip memory inside 16GB (~35GB packed weights / 4 + its KV
    shard), Mosaic kernels on the shards, and the all-reduce."""
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.parallel import tp as TP
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64,
        num_key_value_heads=8, max_position_embeddings=4096)
    comp, txt = _tp_compile(v5e, cfg, lambda c, n: TP.pad_ff_for_tp(
        random_llama_params(c, "sym_int4"), n))
    assert _has_mosaic_call(comp)
    assert "all-reduce" in txt
    ma = comp.memory_analysis()
    RECORDED["llama70b_tp4"] = ma
    per_chip = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes)
    assert per_chip < 16e9, f"{per_chip / 1e9:.2f} GB exceeds one v5e"


def test_llama70b_qlora_step_tp4_fits_v5e_mesh(v5e, aot_flags):
    """The reference's 70B finetuning claim (QLoRA Alpaca 70B in 3.14h
    on 8 GPUs, README.md:20): a FULL llama2-70B QLoRA train step —
    frozen sym_int4 base sharded tp=4 (the 35GB base cannot split any
    coarser on 16GB chips, so dp=1 here; the dp grad all-reduce is
    covered by test_sharded_train_step_compiles_v5e_mesh at dp=2 and
    the CPU-mesh QLoRA tests), trainable LoRA adapters, the recipe's
    micro-batch 8 x 256 — must compile for the v5e 2x2 topology with
    per-chip memory inside 16GB and the tp all-reduces present."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.qlora import LoraConfig, attach_lora, \
        lora_trainable_mask
    from bigdl_tpu.training import make_lora_train_step, partition
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64,
        num_key_value_heads=8, max_position_embeddings=4096)

    def build():
        params = random_llama_params(cfg, qtype="sym_int4")
        params = attach_lora(params, LoraConfig(r=16,
                                                training_mode="qlora"))
        return partition(params, lora_trainable_mask(params))

    comp = _train_step_compile(
        v5e, cfg, (1, 4),
        lambda opt: make_lora_train_step(M.forward_train, cfg, opt),
        build, (8, 256))
    assert "all-reduce" in comp.as_text()
    ma = comp.memory_analysis()
    RECORDED["llama70b_qlora_tp4"] = ma
    per_chip = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes)
    assert per_chip < 16e9, f"{per_chip / 1e9:.2f} GB exceeds one v5e"


# ------------------------------------------------ DeepSeek-V2 (PR 28)

def test_mla_decode_attention_compiles_on_the_stack_in_place(v5e, aot_flags):
    """128 query heads over the 576-wide latent rows of 32 slots x 4096
    positions, the [20, ...] stack as operand and the layer a
    prefetched scalar: a Mosaic call, and no copy of a layer's slab
    (temp bytes stay far under one layer's 151 MB)."""
    from bigdl_tpu.ops.pallas.mla_attention import (
        mla_decode_attention_pallas)

    dev = v5e.devices[0]
    b, h, c, r, s, layers = 32, 128, 512, 64, 4096, 20
    bf = jnp.bfloat16
    comp = _compile(
        lambda qc, qpe, lat, pos, lyr: mla_decode_attention_pallas(
            qc, qpe, lat, pos, 192 ** -0.5, layer=lyr),
        _sds(jax.ShapeDtypeStruct((b, h, c), bf), dev),
        _sds(jax.ShapeDtypeStruct((b, h, r), bf), dev),
        _sds(jax.ShapeDtypeStruct((layers, b, c + r, s), bf), dev),
        _sds(jax.ShapeDtypeStruct((b,), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev))
    assert _has_mosaic_call(comp)
    mem = comp.memory_analysis()
    # the chip stores [.., 576, 4096] bf16 without padding
    assert mem.argument_size_in_bytes < layers * b * (c + r) * s * 2 * 1.01
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


def test_latent_append_compiles_in_place(v5e, aot_flags):
    from bigdl_tpu.ops.pallas.mla_attention import latent_append_pallas

    dev = v5e.devices[0]
    stack = jax.ShapeDtypeStruct((20, 32, 576, 4096), jnp.bfloat16)
    comp = jax.jit(
        lambda st, new, pos, lyr: latent_append_pallas(st, lyr, new, pos),
        donate_argnums=(0,)).lower(
        _sds(stack, dev),
        _sds(jax.ShapeDtypeStruct((32, 576), jnp.bfloat16), dev),
        _sds(jax.ShapeDtypeStruct((32,), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev)).compile()
    assert _has_mosaic_call(comp)
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes >= 20 * 32 * 576 * 4096 * 2
    assert mem.temp_size_in_bytes < 16 * 2 ** 20


@pytest.mark.parametrize("name,t,shared,k,n", [
    ("moe_routed_decode", 32, True, 5120, 1536),
    ("moe_routed_decode", 32, False, 1536, 5120),
    # the benchmark's layer check decodes 8 one-token slots (one tile)
    ("moe_routed_decode", 16, True, 5120, 1536),
    ("moe_routed_decode", 16, False, 1536, 5120),
    ("moe_routed_prefill", 128, False, 5120, 1536),
    ("moe_routed_prefill", 128, False, 1536, 5120),
    # a block family's pass: 16 slots x 4 rows = DECODE_MAX_TOKENS, at
    # SDAR-30B-A3B's expert widths (PR 53)
    ("moe_routed_decode", 64, True, 2048, 768),
    ("moe_routed_decode", 64, False, 768, 2048),
])
def test_routed_expert_kernel_compiles_on_the_layer_stack(
        v5e, aot_flags, name, t, shared, k, n):
    """The routed kernel at the published expert widths over a [19, 20,
    ...] int4 stack addressed by layer: no copy of a layer's experts."""
    from bigdl_tpu.ops.pallas.moe_routed import routed_expert_matmul
    from bigdl_tpu.ops.probing import quant_struct, stacked_struct

    dev = v5e.devices[0]
    tiles = 20 if name.endswith("decode") else 32
    w = stacked_struct(stacked_struct(quant_struct(k, n, "sym_int4"), 20), 19)
    x = jax.ShapeDtypeStruct((1 if shared else tiles, t, k), jnp.bfloat16)
    comp = _compile(
        lambda xx, ww, te, na, lyr: routed_expert_matmul(
            xx, ww, te, na, lyr, name=name, shared_x=shared),
        _sds(x, dev), _sds(w, dev),
        _sds(jax.ShapeDtypeStruct((tiles,), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev))
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


@pytest.mark.parametrize("held,t,d,f", [
    (32, 64, 2048, 768),        # sdar-30b-ep4-fixedlen-closed (a block pass)
    (32, 16, 2048, 1024),       # trinity-mini-ep4-thinking-closed
    (32, 16, 4096, 2048),       # mimo-v25-ep8-mixedlen-closed
    (20, 32, 5120, 1536),       # deepseekv2-ep8-reason-closed
    (32, 16, 5120, 1536),       # dots3-ep8-longdoc-closed
    (32, 16, 7168, 2048),       # deepseekv32-ep8-reason-closed (verify rows)
    (20, 64, 5120, 1536),       # the decode path's most rows
    (32, 64, 7168, 2048),
], ids=["sdar", "trinity", "mimo", "deepseek_v2", "dots3", "deepseek_v32",
        "deepseek_v2_64_rows", "deepseek_v32_64_rows"])
def test_routed_decode_pair_compiles_at_the_cells_geometries(
        v5e, aot_flags, held, t, d, f):
    """The decode pair (`moe_routed_decode_gate_up`, `..._down`) at the
    six routed cells' decode geometry and `decode_tiles`' plan, over
    `[3, held, ...]` int4 stacks addressed by layer: a working set over
    the scoped-VMEM limit the calls ask for fails HERE, not in a cell;
    the layer's result is `[T, D]`, and XLA holds nothing `[held, T, *]`
    but the one product between the calls."""
    from bigdl_tpu.ops.pallas import moe_routed as kernels
    from bigdl_tpu.ops.probing import quant_struct, stacked_struct

    dev = v5e.devices[0]
    st = lambda k, n: stacked_struct(stacked_struct(            # noqa: E731
        quant_struct(k, n, "sym_int4"), held), 3)

    def layer(x, gate, up, down, cw, te, na, lyr):
        h = kernels.routed_gate_up(x, gate, up, cw, te, na, lyr,
                                   act=jax.nn.silu)
        return kernels.routed_down_sum(h, down, te, na, lyr)

    comp = _compile(
        layer, _sds(jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16), dev),
        _sds(st(d, f), dev), _sds(st(d, f), dev), _sds(st(f, d), dev),
        _sds(jax.ShapeDtypeStruct((held, t), jnp.float32), dev),
        _sds(jax.ShapeDtypeStruct((held,), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((), jnp.int32), dev))
    txt = comp.as_text()
    assert kernels.GATE_UP_NAME in txt and kernels.DOWN_NAME in txt
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes <= held * t * f * 2 + 4096
    assert comp.memory_analysis().output_size_in_bytes < t * d * 2 + 4096


def _row_scatters(txt, d):
    """Scatters of a compiled program (alone or as the root of a fusion:
    either way the instruction is in the text) whose result, hence whose
    updates, are rows of `d` elements."""
    import re

    return [f"{name} = {dt}[{dims}]" for name, dt, dims in re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* scatter\(",
        txt, re.M) if str(d) in dims.split(",")]


@pytest.mark.parametrize("n,k,held,total,d,f", [
    (1024, 8, 32, 256, 5120, 1536),       # dots3-ep8-longdoc-closed
    (256, 6, 20, 160, 5120, 1536),        # deepseekv2-ep8-reason-closed
    (1024, 8, 32, 256, 7168, 2048),       # deepseekv32-ep8-reason-closed
], ids=["dots3", "deepseek_v2", "deepseek_v32"])
def test_routed_prefill_dispatch_scatters_no_rows(v5e, aot_flags, n, k, held,
                                                  total, d, f):
    """`routed_experts` for a prefill chunk of the three routed cells
    (`sym_int4` stacks with a layer axis, this chip's share of the
    experts): token rows reach the expert buffer and expert outputs the
    tokens by GATHER, so the compiled program holds no scatter whose
    updates or result are `[*, D]` rows; what it may scatter are the
    `N*k` int32 scalars of the inverse permutation and the bincount. On
    the parent of PR 44 all three cases fail: `bf16[12288, D]` /
    `bf16[4096, D]` `scatter` (the rows in) and `f32[N, D]` `scatter`
    (the scatter-add back), 73 + 34 ms of the dots3 cell's 315 ms chunk
    on the chip (PERF.md 6, PR 44)."""
    from bigdl_tpu.ops import moe_routed
    from bigdl_tpu.ops.probing import quant_struct, stacked_struct

    dev = v5e.devices[0]
    st = lambda kk, nn: stacked_struct(stacked_struct(          # noqa: E731
        quant_struct(kk, nn, "sym_int4"), held), 2)
    stacks = {"experts_gate": st(d, f), "experts_up": st(d, f),
              "experts_down": st(f, d)}
    share = moe_routed.Share(total, held, held)
    comp = _compile(
        lambda x, lg, sk, lyr: moe_routed.routed_experts(
            x, lg, sk, share, top_k=k, act=jax.nn.silu, layer=lyr),
        _sds(jax.ShapeDtypeStruct((n, d), jnp.bfloat16), dev),
        _sds(jax.ShapeDtypeStruct((n, total), jnp.float32), dev),
        _sds(stacks, dev), _sds(jax.ShapeDtypeStruct((), jnp.int32), dev))
    txt = comp.as_text()
    assert "moe_routed_prefill" in txt and _has_mosaic_call(comp)
    assert " gather(" in txt
    rows = _row_scatters(txt, d)
    assert not rows, f"hidden-size rows are scattered: {rows}"


# -- dots3-note (PR 33): sparse attention over a latent cache, a ring ------

DOTS3 = dict(b=8, s=16384, full=5, win=9, ring=640)


@pytest.mark.parametrize("kernel", ["dsa_index_score", "dsa_select",
                                    "sparse_mla_decode", "window_mla_decode",
                                    "ring_append", "index_append"])
def test_dots3_note_decode_kernels_compile_at_published_widths(
        v5e, aot_flags, kernel):
    """Each new kernel of the sparse-attention family for one v5e at the
    published widths over the cell's slab (8 slots x 16384 positions; 64
    index heads x 128; 128 heads over 576-wide rows with a selection
    mask; 64 heads over the 1088-wide ring of 640 with window 513; the
    append kernel on the ring and on the index plane), the stack as
    operand and the layer a prefetched scalar: a Mosaic call and no copy
    of a layer's plane."""
    from bigdl_tpu.ops.pallas import dsa_attention as K
    from bigdl_tpu.ops.pallas.mla_attention import latent_append_pallas

    dev = v5e.devices[0]
    b, s, ring = DOTS3["b"], DOTS3["s"], DOTS3["ring"]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sd(shape, dt=bf):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    pos, lyr = sd((b,), i32), sd((), i32)
    if kernel == "dsa_index_score":
        comp = _compile(
            lambda q, w, ix, p, ly: K.dsa_index_score_pallas(q, w, ix, p,
                                                             layer=ly),
            sd((b, 64, 128)), sd((b, 64), jnp.float32),
            sd((DOTS3["full"], b, 128, s)), pos, lyr)
    elif kernel == "dsa_select":
        comp = _compile(lambda sc: K.dsa_select_pallas(sc, 2048),
                        sd((b, s), jnp.float32))
    elif kernel == "sparse_mla_decode":
        comp = _compile(
            lambda qc, qp, lat, p, m, ly: K.sparse_mla_decode_pallas(
                qc, qp, lat, p, m, 192 ** -0.5, layer=ly),
            sd((b, 128, 512)), sd((b, 128, 64)),
            sd((DOTS3["full"], b, 576, s)), pos, sd((b, s), jnp.bool_), lyr)
    elif kernel == "window_mla_decode":
        comp = _compile(
            lambda qc, qp, lat, p, ly: K.window_mla_decode_pallas(
                qc, qp, lat, p, 256 ** -0.5, 513, layer=ly),
            sd((b, 64, 1024)), sd((b, 64, 64)),
            sd((DOTS3["win"], b, 1088, ring)), pos, lyr)
    else:
        shape = ((DOTS3["win"], b, 1088, ring) if kernel == "ring_append"
                 else (DOTS3["full"], b, 128, s))
        comp = jax.jit(
            lambda st, new, p, ly: latent_append_pallas(st, ly, new, p),
            donate_argnums=(0,)).lower(
            sd(shape), sd((b, shape[2])), pos, lyr).compile()
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def test_dots3_note_engine_programs_compile_and_fit(v5e, aot_flags):
    """The engine's resident decode step for the cell's configuration (14
    layers at published widths, 8 slots x 16384, shapes only): every new
    kernel is in it, no instruction materializes a layer of a plane, and
    arguments plus temporaries stay under 11 GB of the chip's 16."""
    import json
    import re
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_dots3_note as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import dots3_note
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads(
        (bench / "configs" / "dots3-note-ep8-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            dots3_note.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    dev = v5e.devices[0]
    b = doc["engine"]["max_batch"]
    eng = LLMEngine(Model, EngineConfig(
        max_batch=b, max_seq=doc["engine"]["max_seq"],
        prefill_chunk=doc["engine"]["prefill_chunk"], sentinel=False,
        quality=False))
    comp = _lower_resident(eng, dev).compile()
    txt = comp.as_text()
    for name in ("dsa_index_score", "dsa_select", "sparse_mla_decode",
                 "window_mla_decode", "mla_latent_append",
                 "moe_routed_decode"):
        assert name in txt, name
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 8.0e9 < live < 11e9, live / 1e9    # 8.30 GB weights + slab
    moved = re.findall(
        r"= \w+\[(?:\d+,)?8,(?:576|128|1088),(?:16384|640)\]\S* "
        r"(?:copy|fusion|dynamic-slice)\(", txt)
    assert not moved, f"a layer of a cache plane is materialized: {moved}"


@pytest.mark.parametrize("s", [8192, 16384])
def test_mla_chunk_attention_compiles_at_the_cells_geometry(v5e, aot_flags,
                                                            s):
    """The latent chunk kernel for one v5e as the dots3 and DeepSeek-V3.2
    cells call it: 1024 rows of 128 heads x (128 + 64) with a selection
    over a private cache of 8192 / 16384 positions, the five-layer stack
    as operand and the layer a prefetched scalar: a Mosaic call, and no
    temporary the size of a layer's plane or of a head's scores."""
    from bigdl_tpu.ops.pallas import mla_chunk_attention as K

    dev = v5e.devices[0]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sd(shape, dt=bf):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    comp = _compile(
        lambda qn, qp, lat, p, m, wk, wv, ly: K.mla_chunk_attention_pallas(
            qn, qp, lat, p, m, wk, wv, 192 ** -0.5, layer=ly),
        sd((1, 1024, 128, 128)), sd((1, 1024, 128, 64)),
        sd((DOTS3["full"], 1, 576, s)), sd((1,), i32),
        sd((1, 1024, s), jnp.bool_), sd((128, 128, 512)),
        sd((128, 512, 128)), sd((), i32))
    txt = comp.as_text()
    assert "tpu_custom_call" in txt and K.NAME in txt
    # q in head order 50 MB, W_uv transposed 17 MB, the int8 selection
    assert comp.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


def test_dots3_note_full_layer_chunk_keeps_its_scores_in_vmem(v5e,
                                                              aot_flags):
    """One full layer's attention of a 1024-row chunk AS `forward` RUNS
    IT (`attention_block`, published widths, int4 linears) into a
    private cache of 8192 positions: the sweep is the chunk kernel, and
    no `[heads, rows, 1024]` float32 score block of the XLA sweep (512
    MB a key block: PERF.md 6, PR 47) is left in the program."""
    import json
    import re
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_dots3_note as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import dots3_note
    from bigdl_tpu.ops.kvcache import init_cache_spec
    from bigdl_tpu.ops.quant import prepack_tree

    doc = json.loads(
        (bench / "configs" / "dots3-note-ep8-int4.json").read_text())
    _, cfg, _ = _family_config(doc)
    li = cfg.layer_types.index(dots3_note.FULL)
    cfg1 = dataclasses.replace(
        cfg, num_hidden_layers=1, layer_types=(dots3_note.FULL,),
        first_k_dense_replace=1)
    lp = jax.eval_shape(lambda: prepack_tree(dots3_note.prepare_params(
        weights.build_params(cfg1, "sym_int4", 1), cfg1), "on")[0])[
        "layers"][0]
    assert cfg.layer_types[li] == dots3_note.FULL and "index_q_proj" in lp
    dev = v5e.devices[0]
    cache = jax.eval_shape(lambda: init_cache_spec(
        dots3_note.cache_spec(cfg1).unrolled(), 1, 8192))
    y = jax.ShapeDtypeStruct((1, 1024, cfg.hidden_size), jnp.bfloat16)
    comp = _compile(
        lambda yy, pp, cc: dots3_note.attention_block(
            yy, pp, cfg1, cc, dots3_note.FULL),
        _sds(y, dev), _sds(lp, dev), _sds(cache, dev))
    txt = comp.as_text()
    # the operation's own name, which the trace group reads
    assert re.search(r"^\s*%?mla_chunk_attention\S* = f32\[1,1024,16384\]\S* "
                     r"custom-call\(", txt, re.M)
    wide = re.findall(r"f32\[(?:1,)?128,1024,1024\]", txt)
    assert not wide, f"[heads, rows, keys] in float32: {wide[:3]}"


@pytest.mark.parametrize("kernel", ["eva_decode_attention", "eva_summarize"])
def test_evabyte_decode_kernels_compile_at_published_widths(v5e, aot_flags,
                                                            kernel):
    """The two kernels of chunked linearized attention on the stacks
    where they lie (32 heads of 128, a window of 2048, 512 summary
    columns, 6 slots): Mosaic takes them, and the summary stacks are
    rewritten in place."""
    from bigdl_tpu.ops.pallas import eva_attention as K

    dev = v5e.devices[0]
    layers, b, w, ns, h, hd = 2, 6, 2048, 512, 32, 128

    def sd(shape, dt=jnp.bfloat16):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    win, summ = sd((layers, b, w, h, hd)), sd((layers, b, ns, h, hd))
    pos = sd((b,), jnp.int32)
    if kernel == "eva_decode_attention":
        comp = _compile(
            lambda q, wk, wv, sk, sv, p: K.eva_decode_attention_pallas(
                q, wk, wv, sk, sv, p, scale=hd ** -0.5, stride=16, layer=1),
            sd((b, 1, h, hd)), win, win, summ, summ, pos)
    else:
        vec = sd((h, hd), jnp.float32)
        comp = jax.jit(
            lambda wk, wv, sk, sv, p, phi, mu: K.eva_summarize_pallas(
                wk, wv, sk, sv, p, phi, mu, scale=hd ** -0.5, stride=16,
                layer=1), donate_argnums=(2, 3)).lower(
            win, win, summ, summ, pos, vec, vec).compile()
        assert comp.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert _has_mosaic_call(comp) and kernel in comp.as_text()


def test_evabyte_engine_decode_step_compiles_and_fits(v5e, aot_flags):
    """The engine's resident decode step for the cell's configuration (32
    layers at published widths, 6 slots x 8192, shapes only): both
    kernels are in it, no instruction copies a plane, and arguments plus
    temporaries are the weights and the slab (3.65 + 8.05 GB), under
    the chip's 16 with the private prefill cache (1.34 GB) beside."""
    import json
    import re
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_evabyte as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import evabyte
    from bigdl_tpu.ops.kvcache import cache_nbytes
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads((bench / "configs" / "evabyte-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            evabyte.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    dev = v5e.devices[0]
    b = doc["engine"]["max_batch"]
    eng = LLMEngine(Model, EngineConfig(
        max_batch=b, max_seq=doc["engine"]["max_seq"],
        prefill_chunk=doc["engine"]["prefill_chunk"], sentinel=False,
        quality=False))
    assert cache_nbytes(eng._cache_spec, b, 8192)["total"] == 8_053_063_680
    assert eng._admission_cost(6144) == 32 * (2048 + 512) * 16384
    comp = _lower_resident(eng, dev).compile()
    txt = comp.as_text()
    for name in ("eva_decode_attention", "eva_summarize"):
        assert name in txt, name
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 11.5e9 < live < 12.2e9, live / 1e9
    assert ma.temp_size_in_bytes < 64 * 2 ** 20
    # the step goes out one ahead: two of it in flight hold the
    # arguments once and the temporaries twice, inside the chip's 16 GB
    print("evabyte decode step: args GB", ma.argument_size_in_bytes / 1e9,
          "temp GB", ma.temp_size_in_bytes / 1e9)
    assert _two_in_flight_bytes(ma) < 12.3e9, _two_in_flight_bytes(ma) / 1e9
    moved = re.findall(
        r"= \w+\[(?:\d+,)?6,(?:2048|512),32,128\]\S* "
        r"(?:copy|dynamic-slice)\(", txt)
    assert not moved, f"a plane of the cache is materialized: {moved}"


# -- DeepSeek-V3.2 (PR 43): MTP drafting, a two-row verify step -----------

V32 = dict(b=8, s=8192, bodies=8)


@pytest.mark.parametrize("kernel", ["dsa_index_score", "sparse_mla_decode"])
def test_two_row_verify_kernels_compile_at_published_widths(v5e, aot_flags,
                                                            kernel):
    """The two kernels that take the R = 2 rows of a verify step folded
    beside the heads, for one v5e at the published widths over the cell's
    slab (8 slots x 8192, 8 bodies): a Mosaic call, no copy of a plane."""
    from bigdl_tpu.ops.pallas import dsa_attention as K

    dev = v5e.devices[0]
    b, s = V32["b"], V32["s"]

    def sd(shape, dt=jnp.bfloat16):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    pos, lyr = sd((b,), jnp.int32), sd((), jnp.int32)
    if kernel == "dsa_index_score":
        comp = _compile(
            lambda q, w, ix, p, ly: K.dsa_index_score_pallas(q, w, ix, p,
                                                             layer=ly),
            sd((b, 2, 64, 128)), sd((b, 2, 64), jnp.float32),
            sd((V32["bodies"], b, 128, s)), pos, lyr)
    else:
        comp = _compile(
            lambda qc, qp, lat, p, m, ly: K.sparse_mla_decode_pallas(
                qc, qp, lat, p, m, 192 ** -0.5, layer=ly),
            sd((b, 2, 128, 512)), sd((b, 2, 128, 64)),
            sd((V32["bodies"], b, 576, s)), pos, sd((b, 2, s), jnp.bool_),
            lyr)
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def _v32_engine():
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_deepseek_v32 as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import deepseek_v32
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads(
        (bench / "configs" / "deepseek-v32-ep8-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            deepseek_v32.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    e = doc["engine"]
    return LLMEngine(Model, EngineConfig(
        max_batch=e["max_batch"], max_seq=e["max_seq"],
        prefill_chunk=e["prefill_chunk"],
        speculative_tokens=e["speculative_tokens"], sentinel=False,
        quality=False)), cfg


def test_deepseek_v32_verify_step_compiles_and_fits(v5e, aot_flags):
    """`engine_decode_resident_mtp` for the cell's configuration (7
    layers and the MTP module at published widths, 8 slots x 8192, shapes
    only): the two-row kernels, the append and the routed kernel are in
    it, no instruction materializes a layer of a plane, and arguments
    plus temporaries stay under 11 GB of the chip's 16 (8.4 GB of weights
    as AOT counts the served tree, 0.74 GB of slab)."""
    import re

    eng, cfg = _v32_engine()
    dev = v5e.devices[0]
    b = eng.cfg_engine.max_batch
    i32 = _sds(jax.ShapeDtypeStruct((b,), jnp.int32), dev)
    ints = _sds(jax.ShapeDtypeStruct((4, b), jnp.int32), dev)
    floats = _sds(jax.ShapeDtypeStruct((2, b), jnp.float32), dev)
    q = _sds(jax.ShapeDtypeStruct((b, cfg.vocab_size), jnp.float32), dev)
    comp = eng._decode_resident_mtp.lower(
        _sds(eng.params, dev), ints, floats, i32, q,
        _sds(jax.eval_shape(lambda: eng.cache), dev)).compile()
    txt = comp.as_text()
    for name in ("dsa_index_score", "dsa_select", "sparse_mla_decode",
                 "mla_latent_append", "moe_routed_decode"):
        assert name in txt, name
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"engine_decode_resident_mtp: {live / 1e9:.2f} GB live, "
          f"{ma.argument_size_in_bytes / 1e9:.2f} GB of arguments")
    assert 8.0e9 < live < 11e9, live / 1e9
    moved = re.findall(
        r"= \w+\[(?:\d+,)?8,(?:576|128),8192\]\S* "
        r"(?:copy|fusion|dynamic-slice)\(", txt)
    assert not moved, f"a layer of a cache plane is materialized: {moved}"


# -- MiMo-V2 (PR 45): full K/V planes beside K/V rings, a sink ------------

MIMO = dict(b=16, s=16384, full=3, win=9, ring=128)


@pytest.mark.parametrize("kernel", ["decode_attention_lanes",
                                    "swa_decode_attention"])
def test_mimo_v2_decode_kernels_compile_at_published_widths(v5e, aot_flags,
                                                            kernel):
    """The two decode kernels for one v5e at the published widths over
    the cell's slab (16 slots; 64 query heads of 192 on 4 KV heads over
    rows of 768 / 512 values and 16384 positions; on 8 KV heads over
    rings of 128 rows of 1,536 / 1,024 with the sink), the stack as
    operand and the layer a prefetched scalar: a Mosaic call and no copy
    of a plane (a `[.., 4, 192]` stack is copied whole at 256 lanes,
    1.61 GB a call: PERF.md 6, PR 45)."""
    from bigdl_tpu.ops.pallas import swa_attention as K

    dev = v5e.devices[0]
    b, s, ring = MIMO["b"], MIMO["s"], MIMO["ring"]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sd(shape, dt=bf):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    pos, lyr, q = sd((b,), i32), sd((), i32), sd((b, 64, 192))
    if kernel == "decode_attention_lanes":
        comp = _compile(
            lambda q_, k, v, p, ly: K.decode_attention_lanes_pallas(
                q_, k, v, p, 192 ** -0.5, 4, layer=ly),
            q, sd((MIMO["full"], b, s, 768)), sd((MIMO["full"], b, s, 512)),
            pos, lyr)
    else:
        comp = _compile(
            lambda q_, k, v, p, ly, sk: K.swa_decode_attention_pallas(
                q_, k, v, p, 192 ** -0.5, 8, 128, sink=sk, layer=ly),
            q, sd((MIMO["win"], b, ring, 1536)),
            sd((MIMO["win"], b, ring, 1024)), pos, lyr,
            sd((64,), jnp.float32))
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def _mimo_v2_engine():
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_mimo_v2 as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import mimo_v2
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads(
        (bench / "configs" / "mimo-v25-ep8-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            mimo_v2.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    eng = doc["engine"]
    return LLMEngine(Model, EngineConfig(
        max_batch=eng["max_batch"], max_seq=eng["max_seq"],
        prefill_chunk=eng["prefill_chunk"],
        prefill_bucket=eng["prefill_bucket"], sentinel=False,
        quality=False))


def test_mimo_v2_engine_decode_step_compiles_and_fits(v5e, aot_flags):
    """The engine's resident decode step for the cell's configuration (12
    layers at published widths, 16 slots x 16384, shapes only): both
    decode kernels and the routed kernel are in it, no instruction
    materializes a layer of a plane, and arguments plus temporaries stay
    under 9.5 GB of the chip's 16."""
    import re

    dev = v5e.devices[0]
    eng = _mimo_v2_engine()
    b = eng.cfg_engine.max_batch
    comp = _lower_resident(eng, dev).compile()
    txt = comp.as_text()
    for name in ("decode_attention_lanes", "swa_decode_attention",
                 "moe_routed_decode", "qmatmul_gemv_sym_int4"):
        assert name in txt, name
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 7.5e9 < live < 9.5e9, live / 1e9    # 6 GB weights + 2.1 GB slab
    moved = re.findall(
        r"= \w+\[(?:1,)?16,(?:16384|128),(?:768|512|1536|1024)\]\S* "
        r"(?:copy|fusion|dynamic-slice)\(", txt)
    assert not moved, f"a layer of a cache plane is materialized: {moved}"
    assert not re.findall(
        r"= \w+\[(?:3|9),16,(?:16384|128),(?:768|512|1536|1024)\]\S* copy\(",
        txt)


@pytest.mark.parametrize("alloc", [4096, 16384])
def test_mimo_v2_prefill_chunk_keeps_no_rows_by_keys_scores(v5e, aot_flags,
                                                            alloc):
    """One 1024-row prefill chunk AS THE ENGINE BUILDS IT into a private
    cache of 4096 and of 16384 positions: no float32 `[heads, rows, S]`
    temporary (the full layers sweep 512-key blocks, the window layers a
    band of 256 + 127 keys), the int4 GEMM in every linear, and
    temporaries that do not grow with the cache's length but for the
    full layers' one layer of planes."""
    import re

    from bigdl_tpu.ops.kvcache import init_cache_spec

    dev = v5e.devices[0]
    eng = _mimo_v2_engine()
    chunk = eng._chunk
    assert chunk == 1024
    cache1 = jax.eval_shape(lambda: init_cache_spec(
        eng._cache_spec.unrolled(), 1, alloc,
        kv_cache_dtype=eng.kv_cache_dtype))
    tokens = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    comp = eng._prefill.lower(_sds(eng.params, dev), _sds(tokens, dev),
                              _sds(cache1, dev)).compile()
    txt = comp.as_text()
    assert "qmatmul_gemm_sym_int4" in txt and "moe_routed_prefill" in txt
    wide = re.findall(rf"f32\[(?:1,)?(?:64|4,16|8,8),1024,{alloc}\]", txt)
    assert not wide, f"[heads, rows, S] in float32: {wide[:3]}"
    ma = comp.memory_analysis()
    # scores of one key block [64, 1024, 512] f32 are 134 MB
    assert ma.temp_size_in_bytes < 1.0e9, ma.temp_size_in_bytes / 1e9
    print("mimo_v2 prefill chunk", alloc, "temp GB",
          ma.temp_size_in_bytes / 1e9, "args GB",
          ma.argument_size_in_bytes / 1e9)


# -- AFMoE / Trinity-Mini (PR 49): 2048-position rings swept in blocks, ----
# -- all 32 layers, seven periods under one scan ---------------------------

def test_afmoe_ring_kernel_sweeps_a_2048_ring_in_blocks(v5e, aot_flags):
    """`swa_decode_attention` for one v5e over the cell's rings (24
    layers x 16 slots x 2048 columns of 4 x 128 values, no sink): two
    blocks of 1024 a slot under the online softmax, the stack as operand
    and the layer a prefetched scalar; a Mosaic call and no copy of a
    plane."""
    from bigdl_tpu.ops.pallas import swa_attention as K

    dev = v5e.devices[0]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sd(shape, dt=bf):
        return _sds(jax.ShapeDtypeStruct(shape, dt), dev)

    assert K.s_block(2048, 512) == 1024
    comp = _compile(
        lambda q_, k, v, p, ly: K.swa_decode_attention_pallas(
            q_, k, v, p, 128 ** -0.5, 4, 2048, layer=ly),
        sd((16, 32, 128)), sd((24, 16, 2048, 512)), sd((24, 16, 2048, 512)),
        sd((16,), i32), sd((), i32))
    assert _has_mosaic_call(comp)
    assert comp.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def _afmoe_engine():
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_afmoe as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import afmoe
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads(
        (bench / "configs" / "trinity-mini-ep4-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            afmoe.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    eng = doc["engine"]
    return LLMEngine(Model, EngineConfig(
        max_batch=eng["max_batch"], max_seq=eng["max_seq"],
        prefill_chunk=eng["prefill_chunk"],
        prefill_bucket=eng["prefill_bucket"], sentinel=False,
        quality=False))


def test_afmoe_engine_decode_step_compiles_and_fits(v5e, aot_flags):
    """The engine's resident decode step for the cell's configuration
    (all 32 layers at published widths, 16 slots x 8192, shapes only):
    both decode kernels (the ring kernel, no XLA fallback), the routed
    kernel and the int4 GEMV are in it, the seven periods are ONE loop,
    no instruction materializes a layer of a plane or of a quantized
    stack, and arguments plus temporaries stay under 9.5 GB."""
    import re

    dev = v5e.devices[0]
    eng = _afmoe_engine()
    b = eng.cfg_engine.max_batch
    lowered = _lower_resident(eng, dev)
    comp = lowered.compile()
    txt = comp.as_text()
    for name in ("decode_attention_lanes", "swa_decode_attention",
                 "moe_routed_decode", "qmatmul_gemv_sym_int4"):
        assert name in txt, name
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print("afmoe decode step: args GB", ma.argument_size_in_bytes / 1e9,
          "temp GB", ma.temp_size_in_bytes / 1e9, "live GB", live / 1e9,
          "lowered chars", len(lowered.as_text()))
    assert 7.0e9 < live < 9.5e9, live / 1e9   # 4.3 GB weights + 3.8 GB slab
    # the step goes out one ahead: two of it in flight (arguments 8.07 GB
    # once, 0.004 GB of temporaries each) are far inside the chip's 16 GB
    assert ma.temp_size_in_bytes < 64 * 2 ** 20
    assert _two_in_flight_bytes(ma) < 9.5e9, _two_in_flight_bytes(ma) / 1e9
    moved = re.findall(
        r"= \w+\[(?:1,)?16,(?:8192|2048),512\]\S* "
        r"(?:copy|fusion|dynamic-slice)\(", txt)
    assert not moved, f"a layer of a cache plane is materialized: {moved}"
    assert not re.findall(r"= \w+\[(?:8|24),16,(?:8192|2048),512\]\S* copy\(",
                          txt)
    # no layer of a quantized stack is sliced out: the kernels read it
    # where it lies
    assert not re.findall(r"dynamic-slice\S*\(s4\[(?:32|30),", txt)


@pytest.mark.parametrize("alloc", [4096, 8192])
def test_afmoe_prefill_chunk_keeps_no_rows_by_keys_scores(v5e, aot_flags,
                                                          alloc):
    """One 1024-row prefill chunk AS THE ENGINE BUILDS IT into a private
    cache of 4096 and of 8192 positions: no float32 `[heads, rows, S]`
    temporary (the full layers sweep 512-key blocks, the window layers a
    band of 256 + 2047 keys a row block), the int4 GEMM in every linear."""
    import re

    from bigdl_tpu.ops.kvcache import init_cache_spec

    dev = v5e.devices[0]
    eng = _afmoe_engine()
    chunk = eng._chunk
    assert chunk == 1024
    cache1 = jax.eval_shape(lambda: init_cache_spec(
        eng._cache_spec.unrolled(), 1, alloc,
        kv_cache_dtype=eng.kv_cache_dtype))
    tokens = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    comp = eng._prefill.lower(_sds(eng.params, dev), _sds(tokens, dev),
                              _sds(cache1, dev)).compile()
    txt = comp.as_text()
    assert "qmatmul_gemm_sym_int4" in txt and "moe_routed_prefill" in txt
    wide = re.findall(rf"f32\[(?:1,)?(?:32|4,8),1024,{alloc}\]", txt)
    assert not wide, f"[heads, rows, S] in float32: {wide[:3]}"
    ma = comp.memory_analysis()
    # a row block's band scores [32, 256, 2303] f32 are 75 MB
    assert ma.temp_size_in_bytes < 1.0e9, ma.temp_size_in_bytes / 1e9
    print("afmoe prefill chunk", alloc, "temp GB",
          ma.temp_size_in_bytes / 1e9, "args GB",
          ma.argument_size_in_bytes / 1e9)


def _sdar_engine():
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "benchmark"
    sys.path[:0] = [str(bench)]
    from harness import weights_sdar_moe as weights
    from harness.weights import _family_config

    from bigdl_tpu.models import sdar_moe
    from bigdl_tpu.ops.quant import prepack_tree
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    doc = json.loads(
        (bench / "configs" / "sdar-30b-a3b-ep4-int4.json").read_text())
    family, cfg, hf = _family_config(doc)

    class Model:
        params = jax.eval_shape(lambda: prepack_tree(
            sdar_moe.prepare_params(
                weights.build_params(cfg, "sym_int4", 1), cfg), "on")[0])
        config, hf_config, qtype = cfg, hf, "sym_int4"

    Model.family = family
    eng = doc["engine"]
    return LLMEngine(Model, EngineConfig(
        max_batch=eng["max_batch"], max_seq=eng["max_seq"],
        prefill_chunk=eng["prefill_chunk"],
        prefill_bucket=eng["prefill_bucket"], sentinel=False,
        quality=False))


@pytest.mark.parametrize("all_greedy", [True, False])
def test_sdar_engine_block_pass_compiles_and_fits(v5e, aot_flags,
                                                  all_greedy):
    """The engine's resident BLOCK pass for the cell's configuration
    (all 48 layers at published widths, 16 slots x 3072, four rows a
    slot, shapes only): the four rows of a slot are 128 query heads of
    `decode_attention_lanes`, 64 rows take the routed DECODE kernel, the
    48 layers are ONE loop, no instruction materializes a layer of a
    plane or of a quantized stack, no row of the vocabulary is sorted,
    and arguments plus temporaries, two passes in flight, stay under
    11 GB."""
    import re

    dev = v5e.devices[0]
    eng = _sdar_engine()
    b, blk = eng.cfg_engine.max_batch, eng._block.length
    assert (b, blk, b * blk) == (16, 4, 64)
    lowered = eng._block_resident.lower(
        _sds(eng.params, dev),
        _sds(jax.ShapeDtypeStruct((b, 2 * blk + 5), jnp.int32), dev),
        _sds(jax.ShapeDtypeStruct((2, b), jnp.float32), dev),
        _sds(jax.eval_shape(lambda: eng.cache), dev),
        all_greedy=all_greedy)
    comp = lowered.compile()
    txt = comp.as_text()
    for name in ("decode_attention_lanes", "moe_routed_decode",
                 "qmatmul_"):
        assert name in txt, name
    assert "moe_routed_prefill" not in txt
    ma = comp.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print("sdar block pass: args GB", ma.argument_size_in_bytes / 1e9,
          "temp GB", ma.temp_size_in_bytes / 1e9, "live GB", live / 1e9,
          "two in flight GB", _two_in_flight_bytes(ma) / 1e9)
    assert 8.5e9 < live < 11.0e9, live / 1e9    # 4.8 GB weights + 4.8 slab
    assert ma.temp_size_in_bytes < 256 * 2 ** 20
    assert _two_in_flight_bytes(ma) < 11.0e9
    moved = re.findall(
        r"= \w+\[(?:1,)?16,3072,512\]\S* (?:copy|fusion|dynamic-slice)\(",
        txt)
    assert not moved, f"a layer of a cache plane is materialized: {moved}"
    assert not re.findall(r"= \w+\[48,16,3072,512\]\S* copy\(", txt)
    assert not re.findall(r"dynamic-slice\S*\(s4\[48,", txt)
    assert not re.findall(r"sort\S*\([^)]*\[64,37984\]", txt)


def test_sdar_prefill_chunk_is_block_causal_and_keeps_no_wide_scores(
        v5e, aot_flags):
    """One 1024-row prefill chunk AS THE ENGINE BUILDS IT into the
    cell's private cache of 2048 positions: no float32 `[heads, rows,
    S]` temporary (512-key blocks under the block-causal `live`), the
    int4 GEMM in every linear and the routed PREFILL kernel."""
    import re

    from bigdl_tpu.ops.kvcache import init_cache_spec

    dev = v5e.devices[0]
    eng = _sdar_engine()
    chunk, alloc = eng._chunk, eng.cfg_engine.prefill_bucket
    assert (chunk, alloc) == (1024, 2048)
    cache1 = jax.eval_shape(lambda: init_cache_spec(
        eng._cache_spec.unrolled(), 1, alloc,
        kv_cache_dtype=eng.kv_cache_dtype))
    tokens = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    comp = eng._prefill.lower(_sds(eng.params, dev), _sds(tokens, dev),
                              _sds(cache1, dev)).compile()
    txt = comp.as_text()
    assert "qmatmul_gemm_sym_int4" in txt and "moe_routed_prefill" in txt
    wide = re.findall(rf"f32\[(?:1,)?(?:32|4,8),1024,{alloc}\]", txt)
    assert not wide, f"[heads, rows, S] in float32: {wide[:3]}"
    ma = comp.memory_analysis()
    assert ma.temp_size_in_bytes < 1.0e9, ma.temp_size_in_bytes / 1e9
    print("sdar prefill chunk temp GB", ma.temp_size_in_bytes / 1e9,
          "args GB", ma.argument_size_in_bytes / 1e9)
