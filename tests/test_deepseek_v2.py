"""DeepSeek-V2 on the serving path, CPU, tiny widths, seeded weights:
DeepSeek's YaRN, the latent decode kernel (interpret mode) against the
absorbed and the non-absorbed XLA forms, the latent append, the routed
kernels against the dense combine, the latent slab through
`engine_insert`, preemption and export, an engine run against
`model.generate()`, checkpoint conversion from HF names, the cost
models and the refusals."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.config import set_flags
from bigdl_tpu.models import deepseek_v2
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import kvcache
from bigdl_tpu.ops import moe_routed
from bigdl_tpu.ops.pallas import mla_attention as mla

HF = {
    "architectures": ["DeepseekV2ForCausalLM"], "model_type": "deepseek_v2",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 128,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "n_routed_experts": 4, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "n_group": 4, "topk_group": 2, "topk_method": "group_limited_greedy",
    "routed_scaling_factor": 4.0, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 2048, "ep_size": 4, "ep_rank": 1,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64, "type": "yarn"},
}


def _hf_tensors(hf, seed=0):
    """Random float tensors under HF's names and `[out, in]` layout, for
    every expert of the whole layer (the converter keeps its share)."""
    rng = np.random.default_rng(seed)
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    c, r = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    nope, vd, ql = hf["qk_nope_head_dim"], hf["v_head_dim"], hf["q_lora_rank"]
    f, ff = hf["moe_intermediate_size"], hf["intermediate_size"]
    total = hf["n_routed_experts"] * hf["ep_size"]

    def w(o, i):
        return (rng.standard_normal((o, i)) * 0.05).astype(np.float32)

    out = [("model.embed_tokens.weight", w(hf["vocab_size"], d)),
           ("model.norm.weight", np.ones(d, np.float32)),
           ("lm_head.weight", w(hf["vocab_size"], d))]
    for i in range(hf["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += [(p + "input_layernorm.weight", np.ones(d, np.float32)),
                (p + "post_attention_layernorm.weight",
                 np.ones(d, np.float32)),
                (p + "self_attn.q_a_proj.weight", w(ql, d)),
                (p + "self_attn.q_a_layernorm.weight",
                 np.ones(ql, np.float32)),
                (p + "self_attn.q_b_proj.weight", w(h * (nope + r), ql)),
                (p + "self_attn.kv_a_proj_with_mqa.weight", w(c + r, d)),
                (p + "self_attn.kv_a_layernorm.weight",
                 np.ones(c, np.float32)),
                (p + "self_attn.kv_b_proj.weight", w(h * (nope + vd), c)),
                (p + "self_attn.o_proj.weight", w(d, h * vd))]
        if i < hf["first_k_dense_replace"]:
            out += [(p + "mlp.gate_proj.weight", w(ff, d)),
                    (p + "mlp.up_proj.weight", w(ff, d)),
                    (p + "mlp.down_proj.weight", w(d, ff))]
            continue
        fs = f * hf["n_shared_experts"]
        out += [(p + "mlp.gate.weight", w(total, d)),
                (p + "mlp.shared_experts.gate_proj.weight", w(fs, d)),
                (p + "mlp.shared_experts.up_proj.weight", w(fs, d)),
                (p + "mlp.shared_experts.down_proj.weight", w(d, fs))]
        for e in range(total):
            q = f"{p}mlp.experts.{e}."
            out += [(q + "gate_proj.weight", w(f, d)),
                    (q + "up_proj.weight", w(f, d)),
                    (q + "down_proj.weight", w(d, f))]
    return out


@pytest.fixture(scope="module")
def model():
    from bigdl_tpu.transformers.model import TpuCausalLM

    family = get_family("DeepseekV2ForCausalLM", HF)
    cfg = family.config_from_hf(HF)
    params = family.convert_params(_hf_tensors(HF), cfg, "sym_int4")
    return TpuCausalLM(params, cfg, family, HF, qtype="sym_int4",
                       max_seq=128)


def test_registry_loads_the_family_and_converts_its_share(model):
    cfg = model.config
    assert cfg.share == moe_routed.Share(16, 4, 4)
    spec = model.family.cache_spec(cfg)
    assert (spec.kind, spec.latent_dim, spec.num_layers) == ("latent", 144, 3)
    assert not model.family.SUPPORTS_PAGED_KV
    moe = model.params["moe_layers"]
    assert moe["router"].shape == (2, 64, 16)          # all 16 outputs
    assert moe["experts_gate"].data.shape[:2] == (2, 4)   # 4 held
    assert moe["w_uk"].shape == (2, 4, 32, 128)
    assert moe["w_uv"].shape == (2, 4, 128, 32)
    assert "kv_b_proj" not in moe
    # kv_a_proj's 144 columns padded to a lane multiple by zero columns
    assert moe["kv_a_proj"].shape == (64, 256)
    # the held experts are experts 4..7 of the checkpoint
    from bigdl_tpu.ops.quant import quantize_linear

    tensors = dict(_hf_tensors(HF))
    want = quantize_linear(jnp.asarray(
        tensors["model.layers.1.mlp.experts.5.gate_proj.weight"]),
        "sym_int4")
    np.testing.assert_array_equal(np.asarray(moe["experts_gate"].data[0, 1]),
                                  np.asarray(want.data))


def test_deepseek_yarn_against_hand_computed_values():
    """theta 10000, 64 channels, factor 40 over 4096 positions, beta
    32 / 1: correction dims 10.47 and 22.51, so the ramp runs from pair
    10 to pair 23; cos and sin keep magnitude 1 (mscale equals
    mscale_all_dim) and the softmax scale gains m^2 with m = 0.1 x
    0.707 x ln 40 + 1. What `"yarn"` returns for other families (0.1
    ln 40 + 1 on cos and sin) stays."""
    from bigdl_tpu.ops.rope import deepseek_yarn_freqs, scaled_rope_freqs

    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
               "mscale": 0.707, "mscale_all_dim": 0.707,
               "original_max_position_embeddings": 4096, "type": "yarn"}
    inv, cos_sin, softmax = deepseek_yarn_freqs(64, 10000.0, scaling, 163840)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(np.asarray(inv)[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv)[23:], base[23:] / 40,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv)[17],
                               base[17] * (7 / 13 / 40 + 6 / 13), rtol=1e-5)
    assert cos_sin == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and abs(softmax - m * m) < 1e-9
    _, other = scaled_rope_freqs(64, 10000.0, scaling, 64, 163840)
    assert abs(other - (0.1 * math.log(40) + 1)) < 1e-9


@pytest.mark.parametrize("pos", [[0, 0, 0], [5, 130, 200], [255, 255, 127]],
                         ids=["pos0", "mid_block", "full"])
def test_mla_decode_kernel_against_absorbed_and_expanded_xla(pos):
    """Interpret mode, 3 slots, layer 1 of a stack of 2, blocks of 128:
    the kernel, its absorbed XLA twin, and the NON-absorbed form (K and
    V materialised per head from the same rows) agree."""
    b, h, c, r, s, nope, vd = 3, 4, 128, 16, 256, 32, 32
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    lat = jax.random.normal(k[0], (2, b, c + r, s), jnp.bfloat16)
    q_nope = jax.random.normal(k[1], (b, h, nope), jnp.bfloat16)
    q_pe = jax.random.normal(k[2], (b, h, r), jnp.bfloat16)
    w_uk = jax.random.normal(k[3], (h, nope, c), jnp.float32) * 0.1
    w_uv = jax.random.normal(k[4], (h, c, vd), jnp.float32) * 0.1
    posv = jnp.asarray(pos, jnp.int32)
    scale = (nope + r) ** -0.5
    q_c = jnp.einsum("bhd,hdc->bhc", q_nope.astype(jnp.float32),
                     w_uk).astype(jnp.bfloat16)
    got = mla.mla_decode_attention_pallas(q_c, q_pe, lat, posv, scale,
                                          layer=1, interpret=True)
    twin = mla.mla_decode_attention_xla(q_c, q_pe, lat[1], posv, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(twin, np.float32), atol=0.02)
    # not absorbed: k_nope and v per head, float32
    one = lat[1].astype(jnp.float32)
    ckv, kpe = one[:, :c], one[:, c:]
    k_nope = jnp.einsum("bcs,hdc->bshd", ckv, w_uk)
    v = jnp.einsum("bcs,hcd->bshd", ckv, w_uv)
    scores = (jnp.einsum("bhd,bshd->bhs", q_nope.astype(jnp.float32), k_nope)
              + jnp.einsum("bhr,brs->bhs", q_pe.astype(jnp.float32), kpe))
    live = jnp.arange(s)[None, None, :] <= posv[:, None, None]
    probs = jax.nn.softmax(jnp.where(live, scores * scale, -jnp.inf), -1)
    want = jnp.einsum("bhs,bshd->bhd", probs, v)
    out = jnp.einsum("bhc,hcd->bhd", got.astype(jnp.float32), w_uv)
    err = float(jnp.linalg.norm(out - want) / jnp.linalg.norm(want))
    assert err < 0.02


def test_latent_append_kernel_writes_one_column_a_slot():
    st = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 32, 256),
                           jnp.bfloat16)
    new = jax.random.normal(jax.random.PRNGKey(1), (3, 32), jnp.bfloat16)
    pos = jnp.asarray([0, 130, 256], jnp.int32)      # the last: past the end
    want = kvcache.update_latent(st, 1, new[:, None], pos)
    got = mla.latent_append_pallas(st + 0, 1, new, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    changed = np.argwhere(np.asarray(got != st).any(axis=2))
    assert sorted(map(tuple, changed)) == [(1, 0, 0), (1, 1, 130)]


@pytest.mark.parametrize("n", [5, 32, 48, 64, 200],
                         ids=["few", "batch", "three_row_tiles", "block_pass",
                              "chunk"])
def test_routed_kernels_agree_with_the_dense_combine(n):
    """Interpret mode: the decode kernel (n <= 64: tiles are held
    experts, idle ones skipped; 64 is a block family's pass of 16 slots
    x 4 rows) and the sorted prefill kernel (n > 64)
    against every held expert on every token in XLA ops; a share of 4
    of 16 experts starting at expert 8, on a stack of 2 layers."""
    from bigdl_tpu.ops.quant import quantize

    d, f, e, held, first = 256, 128, 16, 4, 8
    k = jax.random.split(jax.random.PRNGKey(1), 6)

    def stack(key, kd, nd):
        return jax.vmap(jax.vmap(lambda kk: quantize(
            jax.random.normal(kk, (kd, nd)) * 0.05, "sym_int4")))(
                jax.random.split(key, 2 * held).reshape(2, held, 2))

    stacks = {"experts_gate": stack(k[0], d, f),
              "experts_up": stack(k[1], d, f),
              "experts_down": stack(k[2], f, d)}
    x = jax.random.normal(k[4], (n, d), jnp.bfloat16)
    logits = jnp.dot(x.astype(jnp.float32), jax.random.normal(k[3], (d, e)))
    kw = dict(top_k=3, act=jax.nn.silu, n_group=4, topk_group=2,
              method="group_limited_greedy", scaling_factor=2.0, layer=1)
    share = moe_routed.Share(e, first, held)
    try:
        set_flags(moe_dispatch="dense")
        want, s1 = moe_routed.routed_experts(x, logits, stacks, share, **kw)
        set_flags(moe_dispatch="ragged")
        got, s2 = moe_routed.routed_experts(x, logits, stacks, share, **kw)
    finally:
        set_flags(moe_dispatch="auto")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert int(s1[0] + s1[1]) == 3 * n and int(s1[3]) == 1
    assert 0 < int(s1[2]) <= held


# (T, D, F) of the six routed cells' decode programs (PR 54)
DECODE_GEOMETRIES = {
    "sdar": (64, 2048, 768), "trinity": (16, 2048, 1024),
    "mimo": (16, 4096, 2048), "deepseek_v2": (32, 5120, 1536),
    "dots3": (16, 5120, 1536), "deepseek_v32": (16, 7168, 2048)}


@pytest.mark.parametrize("cell", list(DECODE_GEOMETRIES))
@pytest.mark.parametrize("call", ["gate_up", "down"])
def test_decode_tile_rule_at_the_cells_geometries(cell, call):
    """The decode plan is by bytes: a legal `(bk, bn)` under the stated
    budget at the cell's rows AND at the most rows the decode path takes
    (64), `bk` whole quant blocks, scale-row tiles and packed-row tiles;
    an expert matrix of about a megabyte (SDAR's two, Trinity's) is ONE
    grid step, every other step moves a megabyte or more of packed
    weights, and the prefill call's tiles are the ones it had."""
    from bigdl_tpu.ops.pallas import moe_routed as kernels
    from bigdl_tpu.ops.quant import get_qtype

    t, d, f = DECODE_GEOMETRIES[cell]
    k, n, stacks = (d, f, 2) if call == "gate_up" else (f, d, 1)
    b = get_qtype("sym_int4").block_size
    for rows in (t, 64):
        bk, bn = kernels.decode_tiles("sym_int4", k, n, rows, stacks)
        assert k % bk == 0 and n % bn == 0 and bn % 128 == 0
        assert bk % b == 0
        assert bk == k or ((bk // b) % 16 == 0 and (bk // 2) % 32 == 0)
        assert kernels._decode_step_bytes(
            "sym_int4", k, rows, bk, bn, stacks) <= kernels.DECODE_VMEM_BUDGET
        assert kernels.DECODE_VMEM_BUDGET < kernels.DECODE_VMEM_LIMIT
        cn = kernels._chunk_lanes(bk, bn)
        assert bn % cn == 0 and cn % 128 == 0
        if k * n <= 2048 * 1024:
            assert (bk, bn) == (k, n)
        else:
            assert stacks * bk * bn // 2 >= 2 ** 20
    assert kernels.routed_tiles("sym_int4", k, n) == (
        max(c for c in (2048, 1024, 512, 256, 128) if k % c == 0),
        max(c for c in (512, 256, 128) if n % c == 0))


def test_decode_tile_rule_refuses_what_does_not_tile():
    from bigdl_tpu.ops.pallas import moe_routed as kernels

    assert kernels.decode_tiles("sym_int4", 2048, 200, 16) is None
    assert kernels.decode_tiles("sym_int4", 2040, 256, 16) is None
    assert kernels.decode_tiles(None, 64, 128, 16, 2) == (64, 128)
    # a K that no part of divides legally is one block, or nothing
    assert kernels.decode_tiles("sym_int4", 96, 128, 16) == (96, 128)
    # the probes answer a shape without a tiling by rule, compiling nothing
    assert not kernels.routed_decode_compiles("sym_int4", "sym_int4",
                                              2048, 200, 16)
    assert not kernels.routed_decode_compiles(None, None, 256, 128, 8)
    assert not kernels.routed_kernel_compiles(kernels.PREFILL_NAME,
                                              "sym_int4", 2040, 256, 128)


def _tiny_stacks(key, held, d, f, quantized=True):
    from bigdl_tpu.ops.quant import quantize

    def stack(kk, kd, nd):
        w = jax.random.normal(kk, (2, held, kd, nd)) * 0.05
        if not quantized:
            return w.astype(jnp.bfloat16)
        return jax.vmap(jax.vmap(lambda a: quantize(a, "sym_int4")))(w)

    k = jax.random.split(key, 3)
    return stack(k[0], d, f), stack(k[1], d, f), stack(k[2], f, d)


@pytest.mark.parametrize("n_active", [3, 6, 0],
                         ids=["idle_tiles", "every_tile_hit", "all_idle"])
@pytest.mark.parametrize("quantized", [True, False],
                         ids=["sym_int4", "dense"])
def test_fused_gate_up_equals_the_two_call_form(n_active, quantized,
                                                monkeypatch):
    """`routed_gate_up` (interpret mode, layer 1 of a `[2, 6, ...]`
    stack, the plan forced to several K and N blocks and several chunks a
    block) against gate and up as two `routed_expert_matmul` calls with
    the product in XLA ops: equal within bf16 rounding on the active
    tiles; tiles past `n_active` are nobody's to read. The down call over
    the fused result equals the masked sum of the two-call form's."""
    from bigdl_tpu.ops.pallas import moe_routed as kernels

    held, t, d, f = 6, 32, 1024, 512
    gate, up, down = _tiny_stacks(jax.random.PRNGKey(5), held, d, f,
                                  quantized)
    kx, kc = jax.random.split(jax.random.PRNGKey(6))
    x1 = jax.random.normal(kx, (1, t, d), jnp.bfloat16)
    cw = jax.random.uniform(kc, (held, t), jnp.float32, 0.0, 2.0)
    cw = cw * (cw > 0.7)
    order = jnp.asarray([4, 1, 5, 0, 2, 3], jnp.int32)
    na = jnp.int32(n_active)
    monkeypatch.setattr(kernels, "DECODE_VMEM_BUDGET", 1300 * 1024)
    monkeypatch.setattr(kernels, "DECODE_CHUNK_ELEMS", 64 * 1024)
    qn = "sym_int4" if quantized else None
    gu = kernels.decode_tiles(qn, d, f, t, 2)
    dn = kernels.decode_tiles(qn, f, d, t)
    assert d // gu[0] > 1 and f // gu[1] > 1 and d // dn[1] > 1
    assert kernels._chunk_lanes(*dn) < dn[1]
    mm = lambda x, w, shared: kernels.routed_expert_matmul(     # noqa: E731
        x, w, order, na, 1, name=kernels.DECODE_NAME, shared_x=shared,
        interpret=True)
    live = (jnp.arange(held) < na)[:, None, None]
    want_h = (jax.nn.silu(mm(x1, gate, True).astype(jnp.float32))
              * mm(x1, up, True).astype(jnp.float32) * cw[..., None])
    want_h = jnp.where(live, want_h, 0.0).astype(jnp.bfloat16)
    want_y = jnp.sum(jnp.where(
        live, mm(want_h, down, False).astype(jnp.float32), 0.0), axis=0)
    # the jitted calls read the plan at trace time
    h = kernels.routed_gate_up.__wrapped__(
        x1, gate, up, cw, order, na, 1, act=jax.nn.silu, interpret=True)
    y = kernels.routed_down_sum.__wrapped__(h, down, order, na, 1,
                                            interpret=True)
    got_h = jnp.where(live, h, 0.0)
    np.testing.assert_allclose(np.asarray(got_h, np.float32),
                               np.asarray(want_h, np.float32),
                               atol=0.02, rtol=0.02)
    assert h.shape == (held, t, f) and y.shape == (t, d)
    if not n_active:
        assert not np.asarray(y, np.float32).any()
        return
    # one rounding of the float32 sum against a rounding an expert
    err = jnp.linalg.norm(y.astype(jnp.float32) - want_y)
    assert float(err / jnp.linalg.norm(want_y)) < 0.01


@pytest.mark.parametrize("hit", [[1, 3], [0, 1, 2, 3], []],
                         ids=["two_hit", "all_hit", "none_hit"])
def test_decode_pair_equals_the_dense_combine(hit):
    """`_decode` (interpret mode) on hand-made combine weights against
    `_dense`, within the layer tests' tolerance: experts nobody chose
    between hit ones, every expert hit, and no held expert hit (zeros),
    at 20 tokens (padded to 32 rows) on layer 1 of the stacks."""
    held, n, d, f = 4, 20, 256, 128
    gate, up, down = _tiny_stacks(jax.random.PRNGKey(7), held, d, f)
    x = jax.random.normal(jax.random.PRNGKey(8), (n, d), jnp.bfloat16)
    comb = np.zeros((n, held), np.float32)
    rng = np.random.default_rng(0)
    for e in hit:
        rows = rng.choice(n, 7, replace=False)
        comb[rows, e] = rng.uniform(0.2, 2.0, 7)
    comb = jnp.asarray(comb)
    want = moe_routed._dense(x, comb, gate, up, down, 1, jax.nn.silu)
    got = moe_routed._decode(x, comb, jnp.any(comb != 0.0, axis=0), gate, up,
                             down, 1, jax.nn.silu, True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    if not hit:
        assert not np.asarray(got, np.float32).any()


def _choices(case):
    """`[N, 2]` expert choices over 16 experts of which 8..11 are held
    here, built so that the dispatch's index arithmetic binds."""
    t = 128
    away = lambda n: np.stack([np.arange(n) % 8,                # noqa: E731
                               12 + np.arange(n) % 4], axis=1)
    if case == "an_expert_with_no_assignment":      # expert 10: no row
        topi = away(100)
        topi[:, 0] = np.array([8, 9, 11])[np.arange(100) % 3]
    elif case == "a_count_that_is_a_whole_tile":    # expert 9: t rows
        topi = away(t)
        topi[:, 0] = 9
        topi[::5, 1] = 8
    elif case == "every_assignment_held_elsewhere":
        topi = away(80)
    elif case == "every_assignment_held_here":      # counts 129,129,1,1
        topi = np.tile([8, 9], (t + 2, 1))
        topi[-1] = [10, 11]
    elif case == "a_token_twice_in_one_tile":       # (9, 9): two rows
        topi = away(70)
        topi[:, 0] = 8 + np.arange(70) % 4
        topi[::3] = 9
    return jnp.asarray(topi, jnp.int32)


@pytest.mark.parametrize("case,tiles_active", [
    ("an_expert_with_no_assignment", 3),
    ("a_count_that_is_a_whole_tile", 2),
    ("every_assignment_held_elsewhere", 0),
    ("every_assignment_held_here", 6),      # all the buffer has
    ("a_token_twice_in_one_tile", 4),
])
def test_prefill_dispatch_rows_by_gather_against_the_dense_combine(
        case, tiles_active):
    """`_prefill` on hand-made choices (interpret mode) against `_dense`:
    every buffer row names its sorted pair and every choice its buffer
    row by index arithmetic (`moe_dispatch.ragged_plan`), so the edges of
    that arithmetic are tried one by one: an empty expert between two
    full ones, a group that ends on a tile's edge, no held choice at all
    (no active tile: zeros), the static worst case filled to its last
    row (`held` groups of one row past a tile), one token twice in one
    expert's tile."""
    from bigdl_tpu.ops.pallas.moe_dispatch import ragged_plan
    from bigdl_tpu.ops.quant import quantize

    d, f, e, held, first = 256, 128, 16, 4, 8
    k = jax.random.split(jax.random.PRNGKey(2), 5)

    def stack(key, kd, nd):
        return jax.vmap(jax.vmap(lambda kk: quantize(
            jax.random.normal(kk, (kd, nd)) * 0.05, "sym_int4")))(
                jax.random.split(key, 2 * held).reshape(2, held, 2))

    gate, up, down = stack(k[0], d, f), stack(k[1], d, f), stack(k[2], f, d)
    topi = _choices(case)
    n = topi.shape[0]
    x = jax.random.normal(k[3], (n, d), jnp.bfloat16)
    topw = jax.random.uniform(k[4], (n, 2), jnp.float32, 0.2, 2.0)
    share = moe_routed.Share(e, first, held)
    comb, mine = moe_routed._combine(topi, topw, share)
    plan = ragged_plan(jnp.where(mine, topi - first, held).reshape(-1), 2,
                       held, 128)
    assert plan.tile_expert.shape == (6,)
    assert int(plan.n_active) == tiles_active
    assert int(jnp.sum(plan.row_token < n)) == int(jnp.sum(mine))
    want = moe_routed._dense(x, comb, gate, up, down, 1, jax.nn.silu)
    got = moe_routed._prefill(x, topi, topw, mine, share, gate, up, down, 1,
                              jax.nn.silu, True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)
    if not tiles_active:
        assert not np.asarray(got, np.float32).any()


def test_whole_layer_share_is_plain_top_k_softmax_scaling():
    """held == total: nothing is left out, every choice is held."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (7, 8))
    topi, topw = moe_routed.route(logits, 2, scaling_factor=1.0)
    comb, mine = moe_routed._combine(topi, topw, moe_routed.Share(8, 0, 8))
    assert bool(mine.all())
    np.testing.assert_allclose(np.asarray(comb.sum(-1)),
                               np.asarray(topw.sum(-1)), rtol=1e-6)


def _engine(model, **kw):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    return LLMEngine(model, EngineConfig(
        max_batch=4, max_seq=128, prefill_chunk=16, prefix_cache_entries=0,
        **kw))


def _run(eng, want):
    got = {rid: [] for rid in want}
    done = set()
    for _ in range(400):
        eng.step()
        for rid in want:
            for o in eng.get_outputs(rid):
                got[rid] += list(o.new_token_ids)
                if o.finished:
                    done.add(rid)
        if len(done) == len(want):
            return got
    raise AssertionError(f"unfinished: {set(want) - done}")


def test_engine_serves_the_tiny_model_with_generates_greedy_tokens(model):
    """LLMEngine on the resident decode step over the latent slab:
    chunked prefill into a private latent cache, `engine_insert`, decode
    at per-slot positions; greedy tokens equal `model.generate()`; the
    device tally reaches `/metrics` at scrape time."""
    from bigdl_tpu.serving.engine import SamplingParams

    eng = _engine(model)
    assert isinstance(eng.cache, kvcache.KVCache)
    assert eng.cache.latent.shape == (3, 4, 144, 128) and eng.cache.k is None
    rng = np.random.default_rng(3)
    prompts = {f"r{i}": [int(x) for x in rng.integers(1, 256, n)]
               for i, n in enumerate((5, 20, 37))}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_tokens=8, temperature=0.0))
    got = _run(eng, prompts)
    for rid, p in prompts.items():
        ref = np.asarray(model.generate(np.asarray([p]), max_new_tokens=8,
                                        do_sample=False))[0][len(p):]
        assert got[rid] == [int(t) for t in ref], rid
    text = eng.registry.render()
    series = {ln.split(" ")[0]: float(ln.split(" ")[1])
              for ln in text.splitlines()
              if ln.startswith("bigdl_tpu_moe_")}
    held = series['bigdl_tpu_moe_assignments_total{held="yes"}']
    other = series['bigdl_tpu_moe_assignments_total{held="no"}']
    steps = series["bigdl_tpu_moe_layer_steps_total"]
    assert held > 0 and other > held and steps > 0
    assert 0 < series["bigdl_tpu_moe_experts_hit_total"] <= 4 * steps
    assert 'bigdl_tpu_kv_cache_bytes{dtype="bf16",component="latent"} ' \
        f"{3 * 4 * 144 * 128 * 2}" in text
    # a second scrape with no step between reads no new counts
    again = eng.registry.render()
    assert f'bigdl_tpu_moe_layer_steps_total {int(steps)}' in again


def test_latent_slab_through_insert_preemption_and_export(model):
    """The slab's splice, a preempted slot's re-admission and a slot's
    export all go through the cache's own description of its planes."""
    from bigdl_tpu.serving.engine import SamplingParams

    eng = _engine(model)
    p = [int(x) for x in np.random.default_rng(4).integers(1, 256, 21)]
    eng.add_request("a", p, SamplingParams(max_tokens=12, temperature=0.0))
    for _ in range(4):
        eng.step()
    idx = next(i for i, s in enumerate(eng.slots) if s.active)
    kv_len = int(eng.cache.pos[idx])
    assert kv_len >= len(p)
    planes = eng.cache.seq_slices(kv_len, row=idx)
    assert [tuple(x.shape) for x in planes] == [(3, 1, 144, kv_len)]
    assert float(jnp.abs(planes[0].astype(jnp.float32)).sum()) > 0
    # past the private cache's 32 positions (a prompt of 21 in chunks of
    # 16) and the few decoded ones the slot's rows are still zero:
    # insert wrote the private cache's columns and nothing else
    tail = eng.cache.latent[:, idx, :, 40:]
    assert float(jnp.abs(tail.astype(jnp.float32)).sum()) == 0
    # a snapshot seeds a new private cache (the prefix cache's path)
    spec = eng._cache_spec
    one = kvcache.init_cache_spec(spec, 1, 32).seeded(
        [np.asarray(x) for x in eng.cache.seq_slices(32, row=idx)], 16)
    assert int(one.pos) == 16
    np.testing.assert_array_equal(
        np.asarray(one.latent[:, :, :, :16], np.float32),
        np.asarray(eng.cache.latent[:, idx:idx + 1, :, :16], np.float32))
    assert float(jnp.abs(one.latent[..., 16:].astype(jnp.float32)).sum()) == 0
    # preempt the slot: the request re-prefills and finishes with the
    # tokens an undisturbed run gives
    eng._preempt()
    assert not any(s.active for s in eng.slots)
    got = _run(eng, {"a": p})
    ref = np.asarray(model.generate(np.asarray([p]), max_new_tokens=12,
                                    do_sample=False))[0][len(p):]
    assert got["a"] == [int(t) for t in ref]


def test_a_latent_cache_refuses_other_dtypes_and_paging(model):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    with pytest.raises(NotImplementedError, match="bf16 only"):
        model.family.new_cache(model.config, 1, 32, "fp8_e5m2")
    with pytest.raises(NotImplementedError, match="latent"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      kv_cache_dtype="fp8_e5m2"))
    with pytest.raises(ValueError, match="SUPPORTS_PAGED_KV"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      kv_page_size=16))


def test_cost_models_and_ledger_count_the_latent_plane(model):
    from bigdl_tpu.observability import roofline

    cfg = model.config
    assert roofline.kv_bytes_per_token(cfg, 100, "bf16") == 3 * 100 * 144 * 2
    assert roofline.attn_flops_per_token(cfg, 10) \
        == 3 * 2 * 4 * (2 * 128 + 16) * 10
    assert roofline.model_flops_per_token(cfg) == cfg.matmul_flops_per_token()
    spec = kvcache.cache_spec_of(model.family, cfg)
    cache = kvcache.init_cache_spec(spec, 2, 64, per_slot_pos=True)
    assert kvcache.kv_cache_bytes(cache) == kvcache.cache_nbytes(spec, 2, 64) \
        == {"codes": 3 * 2 * 144 * 64 * 2, "scales": 0,
            "total": 3 * 2 * 144 * 64 * 2}
    # a family without a declaration keeps K and V planes, as before
    llama = get_family("MistralForCausalLM", None)
    lcfg = llama.config_from_hf({
        "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2})
    lspec = kvcache.cache_spec_of(llama, lcfg)
    assert (lspec.kind, lspec.kv_heads, lspec.head_dim) == ("kv", 2, 16)
    assert kvcache.cache_nbytes(lspec, 2, 64, "int8") \
        == kvcache.kv_cache_nbytes(2, 2, 64, 2, 16, "int8")
    both = kvcache.init_cache_spec(lspec, 2, 64, "int8", per_slot_pos=True)
    assert set(both.planes()) == {"k", "v", "k_scale", "v_scale"}
    assert both.max_seq == 64 and both.kv_dtype == "int8"


def test_both_scans_read_the_quantized_stacks_in_place(forced_pallas,
                                                       monkeypatch):
    """The dense and the expert layers' scans close over their `[L, K,
    N]` stacks and hand `linear` the stack and the layer: logits of a
    34-row prefill through the interpret-mode kernels equal the by-value
    scan's (every leaf sliced by `lax.scan`, the form before PR 46) bit
    for bit. Widths at which every linear has a Pallas tiling; two
    layers of each kind."""
    hf = dict(HF, hidden_size=128, moe_intermediate_size=128,
              q_lora_rank=128, qk_nope_head_dim=48, num_hidden_layers=4,
              first_k_dense_replace=2)
    family = get_family("DeepseekV2ForCausalLM", hf)
    cfg = family.config_from_hf(hf)
    params = family.convert_params(_hf_tensors(hf), cfg, "sym_int4")
    toks = jnp.asarray(np.arange(34, dtype=np.int32)[None] % 256)

    def run():
        f = jax.jit(lambda p, t, c: deepseek_v2.forward(
            p, cfg, t, c, compute_dtype=jnp.float32))
        return np.asarray(
            f(params, toks, deepseek_v2.new_cache(cfg, 1, 64))[0])

    held = [deepseek_v2.hold_stacks(params[k])[0]
            for k in ("dense_layers", "moe_layers")]
    assert "down_proj" in held[0] and "shared_down" in held[1]
    assert not set(held[1]) & set(deepseek_v2._EXPERT_KEYS)
    in_place = run()
    monkeypatch.setattr(deepseek_v2, "hold_stacks",
                        lambda layers: ({}, layers))
    by_value = run()
    assert np.isfinite(in_place).all() and np.abs(in_place).max() > 0
    np.testing.assert_array_equal(in_place, by_value)
