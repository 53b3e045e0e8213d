"""EvaByte on the serving path, CPU, tiny widths at which every mechanism
BINDS (window 32, chunk 4, 2 layers, 4 heads of 16; sequences of 100-200
so that three to six windows pass): prefill in chunks that do not divide
the window and decode through a whole window and more against ONE pass
of the plain reference, all eight heads' logits; the splice of a private
cache mid-window; a slot reused after a longer request; each kernel in
interpret mode against its XLA form; an engine run with two requests of
different lengths; the plane-listing cache spec and the refusals."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import evabyte
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import eva, kvcache
from bigdl_tpu.ops.pallas import eva_attention as kernels

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "evabyte-int4"
SEED = 2 ** 31 + 39


def _tiny_config():
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def tiny():
    from harness import weights_evabyte as weights
    from harness.weights import _family_config

    config = _tiny_config()
    canonical = weights.canonical_params(config, SEED, check=False)
    _, cfg, _ = _family_config(config)
    return {"config": config, "cfg": cfg, "canonical": canonical,
            "params": evabyte.prepare_params(canonical, cfg),
            "quant": {"qtype": "sym_int4", "block": 32},
            "fwd": jax.jit(lambda p, t, c: evabyte.forward(
                p, cfg, t, c, all_heads=True))}


@pytest.fixture(scope="module")
def model():
    from harness import weights_evabyte as weights

    return weights.build_model(_tiny_config(), SEED, merge=True)[0]


def _reference(tiny, ids):
    from harness import reference_evabyte as reference

    return np.asarray(reference.all_head_logits(
        tiny["canonical"], tiny["config"]["reference"], tiny["quant"], ids))


def _rel(got, want):
    from harness.common import relative_l2

    return relative_l2(np.asarray(got, np.float32), want)


def test_registry_loads_the_family_and_its_cache_lists_four_planes(tiny):
    cfg = tiny["cfg"]
    family = get_family("EvaByteForCausalLM", tiny["config"]["hf_config"])
    assert family.name == "evabyte" and not family.is_recurrent
    assert not family.rewindable
    spec = kvcache.cache_spec_of(family, cfg)
    assert spec.has_strided and spec.stride == 4 and not spec.has_ring
    assert spec.unrolled() == spec          # a private cache keeps it
    cache = family.new_cache(cfg, 3, 256)
    assert list(cache.planes()) == ["sum_k", "sum_v", "win_k", "win_v"]
    assert cache.sum_k.shape == (2, 3, 64, 4, 16)      # max_seq / chunk
    assert cache.win_k.shape == (2, 3, 32, 4, 16)      # one window
    assert cache.max_seq == 256 and cache.stride == 4
    assert cache.k is None and cache.latent is None
    # the ledger's count is the allocation's, byte for byte
    assert kvcache.cache_nbytes(spec, 3, 256)["total"] \
        == kvcache.kv_cache_bytes(cache)["total"] \
        == 2 * 2 * 2 * 3 * (64 + 32) * 4 * 16
    # a jit round trip keeps the static stride
    again = jax.jit(lambda c: c.replace(pos=c.pos + 1))(cache)
    assert again.stride == 4 and again.max_seq == 256
    # what export and migration move: the summaries of the length, the
    # window whole
    assert [tuple(p.shape) for p in cache.seq_slices(70, row=1)] == [
        (2, 1, 18, 4, 16)] * 2 + [(2, 1, 32, 4, 16)] * 2


def test_every_layer_of_a_program_is_a_call_of_one_traced_body(tiny):
    """The layer's index is traced, so a program of any depth lowers the
    layer body once (and once more for the last layer, whose outputs
    JAX prunes differently) and calls it once a layer: at the published
    depth a step's module text is 2 MB, not 69 (PERF.md 6, PR 39)."""
    import dataclasses
    import re

    cfg = dataclasses.replace(tiny["cfg"], num_hidden_layers=6)
    params = dict(tiny["params"], layers=tiny["params"]["layers"] * 3)
    txt = jax.jit(lambda p, t, c: evabyte.forward(p, cfg, t, c)).lower(
        params, jnp.zeros((1, 8), jnp.int32),
        evabyte.new_cache(cfg, 1, 128)).as_text()
    calls = re.findall(r"call @(_layer\w*)\(", txt)
    assert len(calls) == 6 and len(set(calls)) <= 2, calls


@pytest.mark.parametrize("kv", ["int8", "int4", "fp8_e5m2"])
def test_window_and_summary_planes_are_bf16_only(tiny, kv):
    with pytest.raises(NotImplementedError, match="bf16 only"):
        evabyte.new_cache(tiny["cfg"], 1, 128, kv)


@pytest.mark.parametrize("chunks", [
    [200],                                  # six windows in one call
    [12] * 5 + [1] * 70,                    # chunks that cross windows,
                                            # then two windows decoded
    [7, 13, 40, 3] + [1] * 40,
    [50] + [1] * 80])
def test_chunked_prefill_then_decode_against_one_reference_pass(tiny,
                                                                chunks):
    """Prefill in chunks that do not divide the window (12 and 7 / 13
    over a window of 32), then one row at a time through a WHOLE window
    and more, so that summaries a decode step wrote are attended: all
    eight heads' logits of every position against one pass of the plain
    reference."""
    ids = np.random.default_rng(5).integers(0, 320, sum(chunks))
    want = _reference(tiny, ids)
    assert want.shape == (sum(chunks), 8 * 320)
    cache = evabyte.new_cache(tiny["cfg"], 1, 256)
    got, a = [], 0
    for n in chunks:
        lg, cache = tiny["fwd"](tiny["params"],
                                jnp.asarray(ids[None, a:a + n]), cache)
        got.append(np.asarray(lg[0]))
        a += n
    got = np.concatenate(got)
    assert int(cache.pos) == sum(chunks)
    assert _rel(got, want) < 0.006
    # every head, and the decoded rows on their own
    for p in range(8):
        assert _rel(got[:, p * 320:(p + 1) * 320],
                    want[:, p * 320:(p + 1) * 320]) < 0.008, p
    assert _rel(got[-40:], want[-40:]) < 0.006
    # the engine's view: head 0 alone
    lg0, _ = evabyte.forward(tiny["params"], tiny["cfg"],
                             jnp.asarray(ids[None, :20]),
                             evabyte.new_cache(tiny["cfg"], 1, 256))
    assert lg0.shape == (1, 20, 320)
    np.testing.assert_allclose(np.asarray(lg0[0]), got[:20, :320],
                               atol=1e-6)


@pytest.mark.parametrize("plen", [37, 64, 75, 90])
def test_a_private_cache_is_spliced_into_the_slab_mid_window(tiny, plen):
    """As the engine: a prompt prefilled in 16-row chunks (the last one
    right-padded) into a private cache of the slab's geometry,
    `spliced` into slot 1 of a three-slot slab whose slot held a LONGER
    request (its stale window and summary columns are never read), then
    decoded at per-slot positions past the next window boundary, the
    other slots empty (-1)."""
    cfg, fwd, params = tiny["cfg"], tiny["fwd"], tiny["params"]
    rng = np.random.default_rng(plen)
    ids = rng.integers(0, 320, plen + 40)
    want = _reference(tiny, ids)
    spec = evabyte.cache_spec(cfg)
    slab = kvcache.init_cache_spec(spec, 3, 256, per_slot_pos=True)
    # the slot's earlier tenant: 150 positions of another request
    old = rng.integers(0, 320, 150)
    _, one = fwd(params, jnp.asarray(old[None]),
                 kvcache.init_cache_spec(spec, 1, 160))
    slab = slab.spliced(one, 1, 150)
    assert float(jnp.abs(slab.sum_k[:, 1, 30:37].astype(jnp.float32)
                         ).sum()) > 0
    chunk = 16
    alloc = -(-plen // chunk) * chunk
    one = kvcache.init_cache_spec(spec, 1, alloc)
    for a in range(0, plen, chunk):
        part = np.zeros((1, chunk), np.int32)
        part[0, :len(ids[a:min(a + chunk, plen)])] = ids[a:min(a + chunk,
                                                              plen)]
        lg, one = fwd(params, jnp.asarray(part), one)
    first = np.asarray(lg[0, plen - 1 - a])
    assert _rel(first, want[plen - 1]) < 0.006
    slab = slab.spliced(one, 1, plen)
    assert [int(p) for p in slab.pos] == [0, plen, 0]
    rows = []
    for t in range(plen, plen + 40):
        tok = jnp.asarray([[0], [int(ids[t])], [0]], jnp.int32)
        live = jnp.asarray([False, True, False])
        lg, out = fwd(params, tok,
                      slab.replace(pos=jnp.where(live, slab.pos, -1)))
        slab = out.replace(pos=jnp.where(live, out.pos, 0))
        rows.append(np.asarray(lg[1, 0]))
        assert float(jnp.abs(lg[0]).max()) < 1e3      # an empty slot
    assert _rel(np.stack(rows), want[plen:]) < 0.006


def _planes(rng, layers=2, b=3, w=128, ns=128, h=4, hd=128):
    def r(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    return (r(layers, b, w, h, hd), r(layers, b, w, h, hd),
            r(layers, b, ns, h, hd), r(layers, b, ns, h, hd))


@pytest.mark.parametrize("pos", [[0, 5, 127], [128, 300, 1000],
                                 [-1, 255, 256], [2047, 640, -1]])
def test_decode_attention_kernel_against_its_xla_form(pos):
    """One query row a slot over both planes in one softmax, at
    positions in the first window (no summary live), mid-window, at a
    window's first position (one exact key) and with empty slots."""
    rng = np.random.default_rng(1)
    wk, wv, sk, sv = _planes(rng)
    b, h, hd, w, c = 3, 4, 128, 128, 16
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)
    posv = jnp.asarray(pos, jnp.int32)
    got = kernels.eva_decode_attention_pallas(
        q, wk, wv, sk, sv, posv, scale=hd ** -0.5, stride=c, layer=1,
        interpret=True)
    for i, p in enumerate(pos):
        if p < 0:
            assert float(jnp.abs(got[i]).max()) == 0.0
            continue
        n_w, n_s = p % w + 1, p // w * (w // c)
        keys = jnp.concatenate([wk[1, i, :n_w], sk[1, i, :n_s]]).astype(
            jnp.float32)
        vals = jnp.concatenate([wv[1, i, :n_w], sv[1, i, :n_s]]).astype(
            jnp.float32)
        sc = jnp.einsum("hd,thd->ht", q[i, 0].astype(jnp.float32),
                        keys) * hd ** -0.5
        want = jnp.einsum("ht,thd->hd", jax.nn.softmax(sc, axis=-1), vals)
        assert _rel(got[i, 0], np.asarray(want)) < 0.01, (i, p)


@pytest.mark.parametrize("pos", [[0, 5, 15], [16, 130, 2047], [-1, 47, 48]])
def test_summarize_kernel_against_the_reference_block(pos):
    """The chunk that holds `pos`, its rows up to `pos` live, to column
    `pos // 16` of the summary stacks, in place; the other columns and
    layers untouched."""
    rng = np.random.default_rng(2)
    wk, wv, sk, sv = _planes(rng)
    h, hd, w, c = 4, 128, 128, 16
    phi = jnp.asarray(rng.uniform(-1, 1, (h, hd)), jnp.float32) * hd ** -.5
    mu = jnp.asarray(rng.uniform(-1, 1, (h, hd)), jnp.float32) * hd ** -.5
    posv = jnp.asarray(pos, jnp.int32)
    sk2, sv2 = kernels.eva_summarize_pallas(
        wk, wv, sk, sv, posv, phi, mu, scale=hd ** -0.5, stride=c, layer=1,
        interpret=True)
    touched = np.zeros(sk.shape[:3], bool)
    for i, p in enumerate(pos):
        p = max(p, 0)
        lo = p % w // c * c
        live = jnp.arange(c) <= p % c
        k_sum, v_sum = eva.summarize_rows(
            wk[1, i, lo:lo + c], wv[1, i, lo:lo + c], live, phi, mu,
            hd ** -0.5)
        assert _rel(sk2[1, i, p // c], np.asarray(k_sum)) < 0.005, (i, p)
        assert _rel(sv2[1, i, p // c], np.asarray(v_sum)) < 0.005, (i, p)
        touched[1, i, p // c] = True
    same = ~touched[..., None, None]
    assert bool(jnp.all(jnp.where(same, sk2 == sk, True)))
    assert bool(jnp.all(jnp.where(same, sv2 == sv, True)))


def test_rows_read_is_the_kernels_rule():
    assert eva.rows_read([0, 2047, 2048, 6167], 2048, 16) == {
        "window": 1 + 2048 + 1 + 24, "summary": 0 + 0 + 128 + 384,
        "context": 1 + 2048 + 2049 + 6168}


def _engine(model, **kw):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    kw.setdefault("prefix_cache_entries", 0)
    return LLMEngine(model, EngineConfig(
        max_batch=3, max_seq=256, prefill_chunk=16, **kw))


def _run(eng, want):
    got = {rid: [] for rid in want}
    done = set()
    for _ in range(600):
        eng.step()
        for rid in want:
            for o in eng.get_outputs(rid):
                got[rid] += list(o.new_token_ids)
                if o.finished:
                    done.add(rid)
        if len(done) == len(want):
            return got
    raise AssertionError(f"unfinished: {set(want) - done}")


def test_engine_serves_two_requests_of_different_lengths(model, tiny):
    """LLMEngine on the resident decode step over the four planes:
    chunked prefill into a private cache of the slab's geometry,
    `engine_insert` mid-window, decode at per-slot positions across
    window boundaries (prompts of 45 and 110, 40 bytes each: the second
    crosses 128); the greedy bytes are the reference's best at nearly
    every position, the counters reach `/metrics`, and a third request
    reuses a slot."""
    from bigdl_tpu.serving.engine import SamplingParams
    from harness import served
    from harness import reference_evabyte as reference

    eng = _engine(model)
    assert eng.cache.win_k.shape == (2, 3, 32, 4, 16)
    assert eng.cache.sum_k.shape == (2, 3, 64, 4, 16) and eng.cache.k is None
    # a private prefill cache keeps the slab's geometry, and is charged
    # as that: one window and the summaries of the bucket
    assert eng._admission_cost(45) == kvcache.cache_nbytes(
        eng._cache_spec, 1, 64)["total"] == 2 * 2 * 2 * (16 + 32) * 4 * 16
    rng = np.random.default_rng(3)
    prompts = {f"r{i}": [int(x) for x in rng.integers(0, 320, n)]
               for i, n in enumerate((45, 110))}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_tokens=40,
                                               temperature=0.0))
    got = _run(eng, prompts)
    late = {"r2": [int(x) for x in rng.integers(0, 320, 33)]}
    eng.add_request("r2", late["r2"], SamplingParams(max_tokens=40,
                                                     temperature=0.0))
    got.update(_run(eng, late))
    prompts.update(late)
    arch = tiny["config"]["reference"]
    for rid, p in prompts.items():
        assert len(got[rid]) == 40 and max(got[rid]) < 320
        gaps = served.request_gaps(reference, tiny["canonical"], arch,
                                   tiny["quant"], p, got[rid])
        assert gaps.max() < 0.5 and (gaps == 0).mean() > 0.8, (rid, gaps)
    text = eng.registry.render()
    series = {ln.split(" ")[0]: float(ln.split(" ")[1])
              for ln in text.splitlines()
              if ln.startswith(("bigdl_tpu_eva_", "bigdl_tpu_kv_cache_bytes"))}
    rows = {k: series[f'bigdl_tpu_eva_rows_total{{kind="{k}"}}']
            for k in ("window", "summary", "context")}
    # 3 x 39 decode steps; what each read, by `rows_read`
    want = eva.rows_read([len(p) + t for p in prompts.values()
                          for t in range(39)], 32, 4)
    assert rows == {k: float(v) for k, v in want.items()}
    assert 0 < rows["summary"] < rows["context"] - rows["window"]
    for comp, n in (("window_kv", 32), ("summary", 64)):
        assert series['bigdl_tpu_kv_cache_bytes{dtype="bf16",component="'
                      f'{comp}"}}'] == 2 * 2 * 3 * n * 4 * 16 * 2


def test_prefix_snapshots_and_drafting_are_refused_with_a_message(model,
                                                                  tiny):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    with pytest.raises(ValueError, match="strided plane.*multiples of the "
                                         "window"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=128,
                                      prefix_cache_entries=4))
    one = evabyte.new_cache(tiny["cfg"], 1, 128)
    host = [np.zeros(p.shape, np.float32) for p in one.planes().values()]
    with pytest.raises(NotImplementedError, match="strided plane"):
        one.seeded(host, 32)
    # the Generator prefills such a family at the exact prompt length
    assert model.generator.recurrent is True
    ids = np.random.default_rng(9).integers(0, 320, (1, 45))
    out = np.asarray(model.generate(ids, max_new_tokens=6, do_sample=False))
    assert out.shape == (1, 51)
    want = _reference(tiny, out[0])[44:50, :320].argmax(-1)
    assert (out[0, 45:] == want).mean() >= 0.8


def test_cost_models_count_what_a_decoded_token_reads(tiny):
    from harness import costs_evabyte as costs

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    dims = costs.Dims.from_config(doc)
    assert costs.row_bytes(dims) == 16384
    # position 6167: 24 exact rows and 384 summaries a layer
    assert costs.kv_bytes_per_token(dims, 6168) == 32 * (24 + 384) * 16384
    # the slab of one slot: (2048 + 512) rows a layer
    spec = evabyte.cache_spec(evabyte.EvaByteConfig())
    assert kvcache.cache_nbytes(spec, 1, 8192)["total"] \
        == 32 * (2048 + 512) * 16384
    w = costs.linear_weight_bytes(dims, "sym_int4", 32)
    assert abs(w - 0.5625 * (32 * 202.375e6 + 4096 * 2560)) / w < 1e-3
    recs = [{"prompt_tokens": 4095, "chunks": [[1.0, 1], [2.0, 3]]}]
    work = costs.serving_work(doc, dims, recs, "bf16", (0.0, 3.0))
    # the first token is the prefill's; three decode steps at positions
    # 4095 (the window's last), 4096 (its first) and 4097
    assert work["eva_live_bytes"] == 32 * 16384 * (
        (2048 + 128) + (1 + 256) + (2 + 256))
    assert work["eva_summarize_bytes"] == 3 * 32 * 17 * 16384
