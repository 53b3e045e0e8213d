"""Fused decode-attention kernel vs the XLA reference (interpret mode on
CPU; the same kernel compiles for real on TPU via the auto dispatch)."""

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu.config import set_flags
from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.pallas.decode_attention import (
    decode_attention_pallas, decode_attention_supported)


def _mk(b, s, h, hkv, hd, seed=0, kv_dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32),
                    jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)).astype(np.float32),
                    kv_dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)).astype(np.float32),
                    kv_dtype)
    return q, k, v


@pytest.mark.parametrize("h,hkv,hd", [(8, 8, 64), (8, 2, 64), (4, 1, 128)])
def test_matches_xla(h, hkv, hd):
    q, k, v = _mk(2, 128, h, hkv, hd)
    pos = jnp.asarray(37, jnp.int32)
    try:
        set_flags(attention_backend="xla")
        ref = sdp_attention(q, k, v, pos)
    finally:
        set_flags(attention_backend="auto")
    got = decode_attention_pallas(q, k[None], v[None], pos, hd ** -0.5, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_per_slot_positions():
    q, k, v = _mk(3, 128, 4, 4, 64, seed=1)
    pos = jnp.asarray([5, 60, 127], jnp.int32)
    try:
        set_flags(attention_backend="xla")
        ref = sdp_attention(q, k, v, pos)
    finally:
        set_flags(attention_backend="auto")
    got = decode_attention_pallas(q, k[None], v[None], pos, 64 ** -0.5, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_fp8_kv():
    q, k, v = _mk(1, 128, 4, 2, 64, seed=2, kv_dtype=jnp.float8_e5m2)
    pos = jnp.asarray(100, jnp.int32)
    try:
        set_flags(attention_backend="xla")
        ref = sdp_attention(q, k, v, pos)
    finally:
        set_flags(attention_backend="auto")
    got = decode_attention_pallas(q, k[None], v[None], pos, 64 ** -0.5, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=6e-2, atol=6e-2)


def test_mask_strictness():
    """Keys beyond pos must have exactly zero influence."""
    q, k, v = _mk(1, 128, 2, 2, 64, seed=3)
    pos = jnp.asarray(10, jnp.int32)
    out1 = decode_attention_pallas(q, k[None], v[None], pos, 64 ** -0.5, interpret=True)
    # poison the tail — result must not move
    k2 = k.at[:, 11:].set(100.0)
    v2 = v.at[:, 11:].set(-100.0)
    out2 = decode_attention_pallas(q, k2[None], v2[None], pos, 64 ** -0.5,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out1, np.float32),
                               np.asarray(out2, np.float32), rtol=1e-5)


def test_supported_gate():
    q, k, v = _mk(1, 128, 4, 2, 64)
    pos = jnp.asarray(0, jnp.int32)
    assert decode_attention_supported(q, k, v, pos, 0.125, None, None, None)
    # prefill, softcap, bad S, alibi -> fallback
    q2 = jnp.zeros((1, 4, 4, 64), jnp.bfloat16)
    assert not decode_attention_supported(q2, k, v, pos, 0.125, None, None,
                                          None)
    assert not decode_attention_supported(q, k, v, pos, 0.125, 50.0, None,
                                          None)
    k3 = jnp.zeros((1, 100, 2, 64), jnp.bfloat16)
    assert not decode_attention_supported(q, k3, v, pos, 0.125, None, None,
                                          None)
    assert not decode_attention_supported(q, k, v, pos, 0.125, None, None,
                                          jnp.ones((4,)))


def test_blocked_long_cache_matches_xla(monkeypatch):
    """A cache of several S blocks (the online-softmax sweep carries
    its state across them) must match the XLA reference (block shrunk
    so interpret mode stays fast)."""
    from bigdl_tpu.ops.pallas import decode_attention as DA

    monkeypatch.setattr(DA, "_BLOCK_ROWS", 256)
    q, k, v = _mk(2, 1024, 4, 2, 64, seed=3)
    for pos_v in (999, 300, 0):
        pos = jnp.asarray(pos_v, jnp.int32)
        try:
            set_flags(attention_backend="xla")
            ref = sdp_attention(q, k, v, pos)
        finally:
            set_flags(attention_backend="auto")
        got = DA.decode_attention_pallas(q, k[None], v[None], pos, 64 ** -0.5,
                                         interpret=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"pos={pos_v}")


def test_blocked_per_slot_positions(monkeypatch):
    from bigdl_tpu.ops.pallas import decode_attention as DA

    monkeypatch.setattr(DA, "_BLOCK_ROWS", 256)
    q, k, v = _mk(3, 512, 4, 4, 64, seed=4)
    pos = jnp.asarray([5, 300, 511], jnp.int32)
    try:
        set_flags(attention_backend="xla")
        ref = sdp_attention(q, k, v, pos)
    finally:
        set_flags(attention_backend="auto")
    got = DA.decode_attention_pallas(q, k[None], v[None], pos, 64 ** -0.5,
                                     interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# -- the kernel over the cache's [L, B, S, Hkv, hd] stack -------------------

_STACK_DTYPES = {"bf16": jnp.bfloat16, "fp8_e5m2": jnp.float8_e5m2,
                 "int8": jnp.int8, "int4": jnp.int4}


def _mk_stack(name, layers, b, s, h, hkv, hd, seed):
    """q plus a `layers`-deep stack in storage dtype `name` (codes and
    scale planes for int8/int4, else scale planes None)."""
    from bigdl_tpu.ops.kvcache import quantize_kv

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((layers, b, s, hkv, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((layers, b, s, hkv, hd)),
                    jnp.float32)
    dt = _STACK_DTYPES[name]
    if name in ("int8", "int4"):
        (k, ks), (v, vs) = quantize_kv(k, dt), quantize_kv(v, dt)
        return q, k, v, ks, vs
    return q, k.astype(dt), v.astype(dt), None, None


def _xla_layer(q, k, v, ks, vs, layer, pos):
    one = [None if x is None else x[layer] for x in (k, v, ks, vs)]
    return sdp_attention(q, one[0], one[1], pos, backend="xla",
                         k_scale=one[2], v_scale=one[3])


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("name", sorted(_STACK_DTYPES))
def test_stack_layer_matches_xla(name, layer):
    """Layer first / middle / last of a stack, per-slot positions: the
    kernel addresses the layer in place and equals the XLA path on the
    slice; the same layer passed alone (`k[i][None]`, layer 0) gives the
    same bits."""
    q, k, v, ks, vs = _mk_stack(name, 3, 2, 256, 8, 2, 64, seed=20 + layer)
    pos = jnp.asarray([200, 31], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, 64 ** -0.5, interpret=True,
                                  k_scale=ks, v_scale=vs, layer=layer)
    ref = _xla_layer(q, k, v, ks, vs, layer, pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)
    alone = [None if x is None else x[layer][None] for x in (k, v, ks, vs)]
    same = decode_attention_pallas(q, alone[0], alone[1], pos, 64 ** -0.5,
                                   interpret=True, k_scale=alone[2],
                                   v_scale=alone[3])
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(same, np.float32))


@pytest.mark.parametrize("name", sorted(_STACK_DTYPES))
def test_stack_scalar_pos_traced_layer(name):
    """Scalar position and a TRACED layer index (what a layer scan
    hands over), through `sdp_attention(.., layer=)`."""
    import jax

    q, k, v, ks, vs = _mk_stack(name, 3, 2, 256, 4, 4, 64, seed=30)
    pos = jnp.asarray(97, jnp.int32)
    got = jax.jit(lambda li: sdp_attention(
        q, k, v, pos, backend="pallas", k_scale=ks, v_scale=vs,
        layer=li))(jnp.int32(1))
    ref = _xla_layer(q, k, v, ks, vs, 1, pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)
    # the XLA path given the stack slices the same layer
    xla = sdp_attention(q, k, v, pos, backend="xla", k_scale=ks,
                        v_scale=vs, layer=jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(xla, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("name", ["bf16", "int8"])
def test_stack_long_cache(name):
    """S over 4096 (17 blocks of 256 at Hkv 2), last layer of two."""
    q, k, v, ks, vs = _mk_stack(name, 2, 1, 4352, 4, 2, 64, seed=40)
    pos = jnp.asarray([4200], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, 64 ** -0.5, interpret=True,
                                  k_scale=ks, v_scale=vs, layer=1)
    ref = _xla_layer(q, k, v, ks, vs, 1, pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# -- only what is live is read: blocks past pos, empty slots ----------------


def _parent_decode(q, k, v, pos, scale, k_scale, v_scale, layer):
    """The kernel as it was before it followed `pos` (PR 30's body and
    index maps): every (slot, S-block) fetched and multiplied, masked
    afterwards. The reference the live rows must equal bit for bit."""
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from bigdl_tpu.ops.pallas import decode_attention as DA

    b, _, h, hd = q.shape
    s, hkv = k.shape[2], k.shape[3]
    scaled = k_scale is not None
    hp = -(-h // 16) * 16
    sb = DA._s_block(s, hkv)
    ns = s // sb

    def kernel(layer_ref, pos_ref, q_ref, bias_ref, k_ref, v_ref, *rest):
        if scaled:
            ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
        else:
            out_ref, m_ref, l_ref, acc_ref = rest
            ks_ref = vs_ref = None
        sj = pl.program_id(1)
        p_ = pos_ref[pl.program_id(0)]

        @pl.when(sj == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, DA._NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        qq = q_ref[...].astype(jnp.bfloat16)
        kk = DA._rows(k_ref, ks_ref)
        vv = DA._rows(v_ref, vs_ref)
        s_ = jax.lax.dot_general(
            qq, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, s_.shape, 1)
        s_ = jnp.where(col < (p_ + 1 - sj * sb) * hkv, s_, DA._NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_ - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

        @pl.when(sj == ns - 1)
        def _():
            l = jnp.maximum(l_ref[:, :1], 1e-30)
            out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)

    qr = jnp.pad(q.reshape(b, h, hd), ((0, 0), (0, hp - h), (0, 0)))
    bias = jnp.asarray(DA._own_head_bias(hp, h // hkv, hkv, sb * hkv))
    q_spec = pl.BlockSpec((None, hp, hd), lambda bi, sj, *_: (bi, 0, 0))
    kv_spec = pl.BlockSpec(
        (None, None, sb, hkv, hd),
        lambda bi, sj, lyr_ref, pos_ref: (lyr_ref[0], bi, sj, 0, 0))
    in_specs = [q_spec,
                pl.BlockSpec((hp, sb * hkv), lambda bi, sj, *_: (0, 0)),
                kv_spec, kv_spec]
    operands = (jnp.asarray(layer, jnp.int32).reshape(1),
                jnp.asarray(pos, jnp.int32), qr, bias, k, v)
    if scaled:
        sc_spec = pl.BlockSpec(
            (None, None, hkv, sb),
            lambda bi, sj, lyr_ref, pos_ref: (lyr_ref[0], bi, 0, sj))
        in_specs += [sc_spec, sc_spec]
        operands += tuple(jnp.swapaxes(x.astype(jnp.float32), -1, -2)
                          for x in (k_scale, v_scale))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, ns), in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((hp, 128), jnp.float32),
                            pltpu.VMEM((hp, 128), jnp.float32),
                            pltpu.VMEM((hp, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hp, hd), q.dtype),
        interpret=True,
    )(*operands)
    return out[:, :h, :].reshape(b, 1, h, hd)


_SB, _S = 128, 512        # four blocks a slot (with _BLOCK_ROWS shrunk)
# per-slot positions that straddle the block edges; -1 is an empty slot
# (first, in the middle, two in a row, last), 3000 an idle slot whose
# position ran on past S (a caller that does not pin it)
_LIVE_CASES = {
    "edges": [-1, 0, 1, -1, -1, _SB - 1, _SB, -1, _SB + 1, _S - 1, 3000,
              -1],
    "led-by-empties": [-1, -1, -1, 2 * _SB, -1, 3000, -1, -1, _S - 1, 0],
    "all-live": [0, _SB - 1, _SB, _SB + 1, 3 * _SB - 1, _S - 1],
    "all-empty": [-1, -1, -1],
}


def _plant_garbage(name, k, v, ks, vs, pos):
    """Large finite values everywhere the kernel must not look: past
    `pos` in a live slot, everywhere in an empty one, in every layer."""
    s = k.shape[2]
    dead = (np.arange(s)[None, :]
            > np.asarray(pos)[:, None])[None, :, :, None]      # [1,B,S,1]
    big = {"bf16": 3e4, "fp8_e5m2": 3e4, "int8": 127, "int4": 7}[name]

    def put(x, val):
        if x is None:
            return None
        m = dead if x.ndim == 4 else dead[..., None]
        return jnp.where(m, jnp.asarray(val, jnp.float32).astype(x.dtype), x)

    return put(k, big), put(v, -big), put(ks, 1e3), put(vs, 1e3)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(_LIVE_CASES))
@pytest.mark.parametrize("name", sorted(_STACK_DTYPES))
def test_reads_only_what_is_live(monkeypatch, name, case, layer):
    """The sweep stops at a slot's last live block and no block of an
    empty slot (pos < 0) is multiplied, and nothing a live slot returns
    changes: bit for bit the rows of the kernel that read everything,
    whatever lies past `pos` and in the empty slots; the XLA path within
    its tolerance; zeros for an empty slot. The index maps name exactly
    `blocks_read` different blocks, in range."""
    from bigdl_tpu.ops.pallas import decode_attention as DA

    hkv = 2
    monkeypatch.setattr(DA, "_BLOCK_ROWS", _SB * hkv)
    pos_l = _LIVE_CASES[case]
    b = len(pos_l)
    assert DA._s_block(_S, hkv) == _SB
    q, k, v, ks, vs = _mk_stack(name, 3, b, _S, 4, hkv, 128,
                                seed=50 + layer)
    pos = jnp.asarray(pos_l, jnp.int32)
    live = np.asarray(pos_l) >= 0
    scale = 128 ** -0.5

    gk, gv, gks, gvs = _plant_garbage(name, k, v, ks, vs, pos)
    got = np.asarray(DA.decode_attention_pallas(
        q, gk, gv, pos, scale, interpret=True, k_scale=gks, v_scale=gvs,
        layer=layer), np.float32)
    assert np.isfinite(got).all()
    assert not got[~live].any()          # an empty slot: zeros

    if live.any():
        # the parent has no empty slots: give it position 0 there
        ref = np.asarray(_parent_decode(
            q, k, v, jnp.maximum(pos, 0), scale, ks, vs, layer), np.float32)
        np.testing.assert_array_equal(got[live], ref[live])
        xla = np.asarray(_xla_layer(q, k, v, ks, vs, layer, pos),
                         np.float32)
        np.testing.assert_allclose(got[live], xla[live],
                                   rtol=2e-2, atol=2e-2)

    # the index maps, step by step over the grid: in range, a live
    # block its own, and a new block exactly where `blocks_read` counts
    # one (an empty slot's first block is fetched and not multiplied)
    ns = _S // _SB
    named = []
    for bi in range(b):
        for sj in range(ns):
            blk = int(DA._named_block(np.asarray(pos_l), bi, sj, _SB, ns))
            assert 0 <= blk < ns
            named.append((bi, blk))
            if live[bi] and sj * _SB <= pos_l[bi]:
                assert blk == sj
    fetched = 1 + sum(a != b_ for a, b_ in zip(named, named[1:]))
    assert fetched == DA.blocks_read(pos_l, _S, hkv)
    assert DA.slab_blocks(b, _S, hkv) == len(named)


def test_engine_slab_decode_kernel_matches_xla_tokens():
    """8 greedy steps of the engine's slab decode give the same tokens
    with the kernel (interpret mode, stack + layer index from the layer
    scan) as with the XLA attention path."""
    import dataclasses

    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    cfg = dataclasses.replace(TINY_LLAMA, hidden_size=128,
                              num_hidden_layers=3, num_attention_heads=2,
                              num_key_value_heads=1)      # head_dim 64

    class Model:
        # a seed whose greedy argmax meets no near-tie in these 24 tokens
        # (the two paths differ in the last bf16 bit of a score)
        params = random_llama_params(cfg, qtype="sym_int4", seed=0)
        config = cfg
        hf_config = {"eos_token_id": None}

        class family:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

    prompts = [list(range(1, 9)), list(range(20, 26)), [7, 7, 7]]
    toks = {}
    for be in ("xla", "pallas"):
        set_flags(attention_backend=be)
        try:
            eng = LLMEngine(Model, EngineConfig(max_batch=4, max_seq=128))
            toks[be] = eng.generate(prompts, SamplingParams(max_tokens=8))
        finally:
            set_flags(attention_backend="auto")
    assert toks["pallas"] == toks["xla"]
    assert all(len(t) == 8 for t in toks["xla"])
